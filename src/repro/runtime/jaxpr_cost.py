"""Trip-aware jaxpr cost analysis.

XLA-CPU's `compiled.cost_analysis()` counts `while` (lax.scan) bodies ONCE
— a 36-layer scanned model reports ~1/36 of its FLOPs. This walker
recurses through scan/cond/pjit/remat with the static trip counts jax
knows, giving exact matmul FLOPs (and an elementwise tally) for the
roofline compute term, plus an HBM-traffic estimate for the memory term.

Traffic model: dot_general counts operands + result once per execution
(weights re-read per microbatch — matching an HBM-resident weight-
stationary-per-step schedule); other ops count result bytes only
(elementwise chains fuse; their inputs are usually some other op's
freshly-written result, already counted). Gather/scatter count operand +
result. This is an estimate — it cannot see XLA's actual fusion — but it
is trip-correct, which dominates the error.

WEIGHT traffic (`weight_bytes`): the decode roofline term the DB-PIM
serving path attacks. Three rules, in precedence order per operand:
  * PROVENANCE (the exact rule): `analyze(fn, params, ...)` tags every
    leaf of the argument(s) named by `weight_argnums` (default: arg 0,
    the params pytree at every call site in this repo) and propagates
    the tag through structural ops (convert/reshape/transpose/slice/
    broadcast) and into scan/cond/pjit/remat bodies by positional invar
    mapping. A dot_general operand that still carries the tag is a
    stored-parameter read and charges its full bytes — REGARDLESS of
    rank or batch dims. This is what counts the MoE per-expert einsum
    (`ecd,edf->ecf` — the rank-3 `edf` weight lowers with a batch dim,
    and jnp.einsum may even place it as the LHS operand) and any other
    stacked rank-3+ parameter read, while leaving attention/SSM
    activation einsums (operands PRODUCED in-graph: conv outputs,
    updated KV caches, softmax probs) uncharged even though some share
    the (rank-3, one-batch-dim) shape signature.
  * dot_general shape fallback: the rhs operand when it is rank-2 with
    no batch dims — `x @ W` projections whose weight lost its tag to a
    non-structural op (e.g. the in-graph int8 dequant multiply).
    Charged through `convert_src`, so an int8 weight dequantized
    in-graph charges 1 B/element.
  * pallas_call: every operand that is NOT a plain rank-2 float
    activation — i.e. integer payloads/index tables (int8 w_blocks,
    int32 idx) plus rank-2 floats with a leading broadcast dim of 1
    (per-filter scales) and float operands of rank != 2 (block payloads).
    For the packed kernels this is exactly payload + idx + scales.

PER-PATH WATERFALL (`weight_bytes_by_path`): every byte charged into
`weight_bytes` is ALSO attributed to the parameter path it came from —
the provenance tags carry the pytree key path of the seeding leaf
("blocks/attn/wq", "seg00/blocks/ssm/w_in", "blocks/moe/w1", ...; the
stacked kernel tables, a step argument of their own, label as
"tables/<family>/<part>").
Bytes charged by the shape fallbacks, whose provenance is unknown, land
in explicit "(untagged ...)" rows. The rows are charged at exactly the
same sites with exactly the same integer byte values as the scalar, so
`sum(weight_bytes_by_path.values()) == weight_bytes` holds EXACTLY —
the equality the serving benchmark asserts per call kind.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import numpy as np
from jax.extend import core as jcore


def _dtype_bytes(aval) -> int:
    try:
        return np.dtype(aval.dtype).itemsize
    except Exception:
        return 4


def _nelems(aval) -> int:
    try:
        n = 1
        for d in aval.shape:
            n *= int(d)
        return n
    except Exception:
        return 0


def _bytes(aval) -> int:
    return _nelems(aval) * _dtype_bytes(aval)


def _dot_flops(eqn) -> int:
    """2 * prod(out) * prod(contract dims of lhs)."""
    (lc, _), _ = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval
    out = eqn.outvars[0].aval
    k = 1
    for d in lc:
        k *= int(lhs.shape[d])
    return 2 * _nelems(out) * k


_SUBJAXPR_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr",
                    "body_jaxpr")

#: ops that read a stored array without computing on it — a tagged
#: (parameter-provenance) input keeps its tag through these. Anything
#: else (adds, muls, scatters, ...) produces a NEW array and drops it.
_STRUCTURAL = ("convert_element_type", "reshape", "transpose", "squeeze",
               "expand_dims", "slice", "dynamic_slice", "rev",
               "broadcast_in_dim", "sharding_constraint", "copy")


def _is_var(v) -> bool:
    return isinstance(v, jcore.Var)


def _map_tags(outer_invars, inner_invars, tagged):
    """Positional outer->inner tag mapping for sub-jaxpr recursion (scan
    consts+carry+xs, pjit/remat bodies). Tags are {var: param path}. A
    count mismatch (e.g. while's cond consts) drops the tags —
    undercounting is the safe failure."""
    if len(outer_invars) != len(inner_invars):
        return {}
    return {iv: tagged[ov] for ov, iv in zip(outer_invars, inner_invars)
            if _is_var(ov) and ov in tagged}


def _is_pallas_weight(aval) -> bool:
    """Weight-operand heuristic for pallas_call (see module docstring):
    everything except a plain rank-2 float activation counts as stored
    weight/metadata — int8 payloads, int32 index tables, (1, N) scales,
    rank>2 block payloads. Known limit: an INTEGER activation (only the
    dbmu bit-true oracle, which no serving graph contains) would be
    misclassified as weight."""
    try:
        kind = np.dtype(aval.dtype).kind
        shape = tuple(aval.shape)
    except Exception:
        return False
    if kind in ("i", "u"):
        return True
    # floating covers bf16 payloads too: ml_dtypes' bfloat16 reports
    # numpy kind "V" (void), so a kind == "f" check alone would silently
    # drop the value-only stacked payload from the weight tally
    is_float = kind == "f" or jax.numpy.issubdtype(aval.dtype,
                                                   jax.numpy.floating)
    return is_float and (len(shape) != 2 or shape[0] == 1)


#: waterfall rows for bytes the shape fallbacks charge — provenance
#: unknown, but the bytes must still appear in a row so the rows sum to
#: weight_bytes exactly
UNTAGGED_DOT = "(untagged dot rhs)"
UNTAGGED_PALLAS = "(untagged pallas operand)"


def _walk(jaxpr, mult: int, acc: Dict[str, float],
          convert_src: Dict[Any, Any] = None, weight_vars=None, wf=None):
    # convert_src: var -> pre-convert var, so a dot whose operand is a
    # freshly dequantized int8 weight charges int8 bytes (the dequant
    # fuses into the matmul on TPU; HBM sees the int8 tensor).
    # weight_vars: {var: param path} with parameter provenance (see
    # module docstring); grown in place as structural ops pass tags along.
    # wf: the per-path waterfall accumulator ({path: bytes}); every
    # weight_bytes charge below mirrors into it at the same value.
    convert_src = {} if convert_src is None else convert_src
    weight_vars = {} if weight_vars is None else weight_vars

    def tag_of(v):
        if not _is_var(v):
            return None
        p = weight_vars.get(v)
        if p is None:
            p = weight_vars.get(convert_src.get(v, v))
        return p

    def tagged(v):
        return tag_of(v) is not None

    def charge(b, path):
        acc["weight_bytes"] += b
        if wf is not None:
            wf[path] = wf.get(path, 0.0) + b

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in _STRUCTURAL and eqn.invars and tagged(eqn.invars[0]):
            weight_vars[eqn.outvars[0]] = tag_of(eqn.invars[0])
        if prim == "convert_element_type" and len(eqn.invars) == 1:
            convert_src[eqn.outvars[0]] = eqn.invars[0]
            continue          # dtype converts fuse; no HBM traffic charged
        if prim == "dot_general":
            f = _dot_flops(eqn) * mult
            acc["dot_flops"] += f
            acc["flops"] += f
            op_bytes = 0
            for v in eqn.invars:
                src = convert_src.get(v, v) if _is_var(v) else v
                op_bytes += _bytes(src.aval)
            acc["bytes"] += (op_bytes
                             + _bytes(eqn.outvars[0].aval)) * mult
            # weight traffic, per operand (charged once each):
            #   1. parameter provenance — exact, any rank (MoE expert
            #      einsums place the rank-3 weight on either side);
            #   2. rank-2 no-batch rhs — the x @ W shape fallback for
            #      weights whose tag died (in-graph int8 dequant).
            charged = [False, False]
            for i, v in enumerate(eqn.invars):
                path = tag_of(v)
                if path is not None:
                    src = convert_src.get(v, v)
                    charge(_bytes(src.aval) * mult, path)
                    charged[i] = True
            _, (_, rb) = eqn.params["dimension_numbers"]
            rhs_v = eqn.invars[1]
            rhs = convert_src.get(rhs_v, rhs_v) if _is_var(rhs_v) else rhs_v
            if (not charged[1]
                    and len(getattr(rhs.aval, "shape", ())) == 2 and not rb):
                charge(_bytes(rhs.aval) * mult, UNTAGGED_DOT)
            continue
        if prim == "pallas_call":
            # Custom kernel (e.g. joint_sparse_matmul): its inner jaxpr
            # sees per-BLOCK avals, so plain recursion would undercount
            # by the grid size. Prefer the kernel's static CostEstimate
            # for FLOPs; without one, recurse into the kernel body with
            # the grid trip count as the multiplier (each grid step runs
            # the body once on one block). HBM charges operands + result:
            # packed INT8 payloads charge 1 B/weight and compacted tables
            # only their stored bytes — exactly the joint-sparsity
            # traffic saving the roofline should see.
            ce = eqn.params.get("cost_estimate")
            f = float(getattr(ce, "flops", 0) or 0)
            if f:
                acc["dot_flops"] += f * mult
                acc["flops"] += f * mult
                acc["pallas_flops"] += f * mult
            else:
                grid = getattr(eqn.params.get("grid_mapping"), "grid", ())
                steps = 1
                for g in grid:
                    steps *= int(g)
                inner = eqn.params["jaxpr"]
                sub = {k: 0.0 for k in acc}
                _walk(getattr(inner, "jaxpr", inner), mult * steps, sub)
                acc["dot_flops"] += sub["dot_flops"]
                acc["flops"] += sub["flops"]
                acc["pallas_flops"] += sub["dot_flops"]
            b = (sum(_bytes(v.aval) for v in eqn.invars)
                 + sum(_bytes(v.aval) for v in eqn.outvars)) * mult
            acc["bytes"] += b
            acc["pallas_bytes"] += b
            for v in eqn.invars:
                if _is_pallas_weight(v.aval):
                    charge(_bytes(v.aval) * mult,
                           tag_of(v) or UNTAGGED_PALLAS)
            continue
        if prim == "scan":
            length = int(eqn.params.get("length", 1))
            inner = eqn.params["jaxpr"]
            # scan invars are [consts, carry, xs] and map 1:1 onto the
            # body's invars — a tagged stacked weight carried as xs keeps
            # its tag on the per-iteration slice.
            _walk(inner.jaxpr, mult * length, acc,
                  weight_vars=_map_tags(eqn.invars, inner.jaxpr.invars,
                                        weight_vars), wf=wf)
            continue
        if prim == "while":
            # unbounded a priori; models don't use raw while. Count once.
            _walk(eqn.params["body_jaxpr"].jaxpr, mult, acc, wf=wf)
            continue
        if prim == "cond":
            branches = eqn.params.get("branches", ())
            best = None
            best_wf = None
            for br in branches:
                a = {k: 0.0 for k in acc}
                a_wf = None if wf is None else {}
                _walk(br.jaxpr, mult, a,
                      weight_vars=_map_tags(eqn.invars[1:], br.jaxpr.invars,
                                            weight_vars), wf=a_wf)
                if best is None or a["flops"] > best["flops"]:
                    best, best_wf = a, a_wf
            if best:
                for k in acc:
                    acc[k] += best[k]
                if wf is not None and best_wf:
                    for p, b in best_wf.items():
                        wf[p] = wf.get(p, 0.0) + b
            continue
        handled = False
        for pname in _SUBJAXPR_PARAMS:
            if pname in eqn.params:
                sub = eqn.params[pname]
                inner = getattr(sub, "jaxpr", sub)
                _walk(inner, mult, acc,
                      weight_vars=_map_tags(eqn.invars, inner.invars,
                                            weight_vars), wf=wf)
                handled = True
                break
        if handled:
            continue
        # leaf op: elementwise/reduce/gather/etc. FLOPs counted; bytes only
        # for data-movement primitives — elementwise/reduce chains between
        # matmuls fuse on TPU (their operands are freshly produced dot
        # results already charged at the dot).
        out_b = sum(_bytes(v.aval) for v in eqn.outvars)
        out_n = sum(_nelems(v.aval) for v in eqn.outvars)
        acc["flops"] += out_n * mult
        if prim in ("gather", "scatter", "scatter-add", "scatter_add",
                    "dynamic_slice", "dynamic_update_slice", "sort",
                    "cumsum", "cumlogsumexp"):
            acc["bytes"] += (out_b + sum(_bytes(v.aval)
                                         for v in eqn.invars)) * mult


def _path_str(key_path) -> str:
    """'blocks/attn/wq'-style label from a tree_util key path."""
    parts = []
    for k in key_path:
        if hasattr(k, "key"):             # DictKey
            parts.append(str(k.key))
        elif hasattr(k, "idx"):           # SequenceKey
            parts.append(str(k.idx))
        elif hasattr(k, "name"):          # GetAttrKey
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def analyze(fn, *args, weight_argnums: Tuple[int, ...] = (0,)
            ) -> Dict[str, float]:
    """Trip-aware cost of `fn(*args)` (args may be ShapeDtypeStructs).

    weight_argnums: which positional args hold stored parameters — their
    leaves seed the provenance tags behind the exact weight_bytes rule
    (module docstring). Every call site in this repo passes params first,
    so the default (0,) is right; the serving steps also name their
    packed-table argument (1), so table traffic is attributed to its
    table path in ``weight_bytes_by_path`` instead of the untagged-pallas
    fallback row. Pass () to fall back to the pure shape heuristics (e.g.
    when arg 0 is an activation).

    The result's ``weight_bytes_by_path`` maps parameter paths to the
    weight bytes charged against them; its values sum to
    ``weight_bytes`` exactly (all charges are integer byte counts,
    mirrored per-row at the charge site)."""
    closed = jax.make_jaxpr(fn)(*args)
    acc = {"flops": 0.0, "dot_flops": 0.0, "bytes": 0.0,
           "pallas_flops": 0.0, "pallas_bytes": 0.0, "weight_bytes": 0.0}
    tags = {}
    leaf_counts = [len(jax.tree_util.tree_leaves(a)) for a in args]
    if sum(leaf_counts) == len(closed.jaxpr.invars):
        offsets = np.concatenate([[0], np.cumsum(leaf_counts)])
        for i in weight_argnums:
            if 0 <= i < len(args):
                paths, _ = jax.tree_util.tree_flatten_with_path(args[i])
                invars = closed.jaxpr.invars[offsets[i]:offsets[i + 1]]
                for (kp, _), v in zip(paths, invars):
                    tags[v] = _path_str(kp)
    wf: Dict[str, float] = {}
    _walk(closed.jaxpr, 1, acc, weight_vars=tags, wf=wf)
    # argument + result residency: params/opt-state are read and written
    # once per step regardless of op-level traffic.
    arg_bytes = sum(_bytes(v.aval) for v in closed.jaxpr.invars)
    acc["arg_bytes"] = float(arg_bytes)
    acc["weight_bytes_by_path"] = wf
    return acc


def analyze_call_kinds(calls: Dict[str, tuple],
                       weight_argnums: Tuple[int, ...] = (0,)
                       ) -> Dict[str, Dict[str, float]]:
    """Per-engine-call-kind cost attribution.

    `calls` maps a call kind — the serving engine's executables, e.g.
    "decode" / "prefill_chunk_exact" / "prefill_parallel" (the builders in
    launch.steps annotate their step fns with a matching ``call_kind``) —
    to an ``(fn, args)`` tuple. Each kind is traced and walked separately,
    so weight_bytes (and every other tally) stays attributable to the
    call that pays it instead of collapsing into one blended number: the
    chunked-prefill traffic savings the benchmarks guard are per-KIND
    contracts (a parallel SSM chunk reads its projections once, an exact
    chunk C times, a decode step once per token). ``weight_argnums`` is
    forwarded to every analyze call (see analyze)."""
    return {kind: analyze(fn, *args, weight_argnums=weight_argnums)
            for kind, (fn, args) in calls.items()}
