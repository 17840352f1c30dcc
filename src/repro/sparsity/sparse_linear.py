"""DB-PIM sparsity as a first-class LM feature.

The paper evaluates CNNs; this module applies the identical hybrid-grained
pipeline (block-wise value pruning -> FTA bit-level quantization) to the
projection matrices of any of the 10 assigned architectures:

  * `sparsify_params` compresses every eligible projection (attention
    q/k/v/o, MLP gate/up/down, MoE experts, SSM in/out) — stacked layer
    tensors are handled per-layer; routers/norms/embeddings stay dense
    (same reasoning as the paper's dw-conv exclusion);
  * `dequant_tree` reconstructs FTA-compliant float weights (exact on the
    INT8 x scale grid) so the SAME model code runs the compressed model;
  * `pim_speedup_estimate` maps each projection to the DB-PIM cost model
    -> a beyond-paper result: DB-PIM speedup/energy for transformer
    inference.

Serving packs the same projections into stacked tables
(`build_stacked_tables`) that feed the one joint Pallas kernel
(kernels.joint_sparse_matmul) through the layer scans.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fta, pruning
from repro.core.pim_model import (DEFAULT_PIM, LayerGEMM, evaluate_model,
                                  evaluate_dense_baseline,
                                  sparsity_from_export)
from repro.models.config import KERNEL_MODES, ModelConfig

ELIGIBLE = re.compile(
    r"(attn|xattn)/(wq|wk|wv|wo)$|mlp/w_(gate|up|down)$|"
    r"moe/w_(gate|up|down)$|moe/dense_mlp/w_(gate|up|down)$|"
    r"ssm/(in_proj|out_proj)$")


@dataclass
class DBPIMCompressed:
    """Compressed weight artifact tree + per-tensor sparsity metadata."""
    tensors: Dict[str, dict] = field(default_factory=dict)
    report: Dict[str, dict] = field(default_factory=dict)


def _key(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _compress_2d(w2: np.ndarray, value_sparsity: float, alpha: int):
    K, N = w2.shape
    pad = (-N) % alpha
    if pad:
        w2 = np.pad(w2, ((0, 0), (0, pad)))
    mask = np.asarray(pruning.block_prune_mask(w2.astype(np.float32),
                                               value_sparsity, alpha))
    amax = np.abs(w2).max() + 1e-12
    scale = amax / 127.0
    q = np.clip(np.round(w2 / scale), -127, 127).astype(np.int32)
    q_fta, phi = fta.fta_quantize(q, mask)
    return q_fta, float(scale), mask, np.asarray(phi), pad


def sparsify_params(params, cfg: ModelConfig,
                    value_sparsity: Optional[float] = None,
                    alpha: int = 8) -> DBPIMCompressed:
    vs = cfg.dbpim_value_sparsity if value_sparsity is None else value_sparsity
    out = DBPIMCompressed()
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in flat:
        key = _key(path)
        if not ELIGIBLE.search(key) or leaf.ndim < 2:
            continue
        arr = np.asarray(leaf, dtype=np.float32)
        lead = arr.shape[:-2]
        arr2 = arr.reshape((-1,) + arr.shape[-2:])
        qs, masks, phis = [], [], []
        scale_list = []
        pad = 0
        for l in range(arr2.shape[0]):
            q, scale, mask, phi, pad = _compress_2d(arr2[l], vs, alpha)
            qs.append(q)
            masks.append(mask)
            phis.append(phi)
            scale_list.append(scale)
        q_all = np.stack(qs).reshape(lead + qs[0].shape)
        mask_all = np.stack(masks).reshape(lead + masks[0].shape)
        out.tensors[key] = {
            "q": q_all.astype(np.int8), "scale": np.asarray(scale_list,
                                                            np.float32),
            "mask": mask_all.astype(np.int8), "pad": pad,
            "orig_shape": arr.shape, "dtype": str(leaf.dtype),
        }
        sp = sparsity_from_export(qs[0] * masks[0], masks[0], phis[0])
        out.report[key] = {
            "value_sparsity": sp.value_sparsity,
            "bit_sparsity": fta.achieved_bit_sparsity(qs[0], masks[0]),
            "phi_hist": sp.phi_hist,
            "int8_bytes": int(q_all.size),
            "orig_bytes": int(arr.size * (2 if "bfloat16" in str(leaf.dtype)
                                          else 4)),
        }
    return out


def dequant_tree(params, comp: DBPIMCompressed):
    """Replace eligible leaves with their FTA-compliant reconstruction."""
    def visit(path, leaf):
        key = _key(path)
        t = comp.tensors.get(key)
        if t is None:
            return leaf
        lead = t["orig_shape"][:-2]
        q = t["q"].reshape((-1,) + t["q"].shape[-2:]).astype(np.float32)
        w = q * t["scale"].reshape(-1, 1, 1)
        if t["pad"]:
            w = w[:, :, :-t["pad"]]
        w = w.reshape(t["orig_shape"])
        return jnp.asarray(w, dtype=leaf.dtype)

    return jax.tree_util.tree_map_with_path(visit, params)


def pim_speedup_estimate(comp: DBPIMCompressed, cfg: ModelConfig,
                         tokens: int = 64):
    """Map the compressed projections onto the DB-PIM cost model:
    speedup/energy/U_act of running this LM's matmuls on the paper's chip
    vs its dense digital-PIM baseline."""
    layers, sps = [], {}
    for key, t in comp.tensors.items():
        q2 = t["q"].reshape((-1,) + t["q"].shape[-2:])
        mask2 = t["mask"].reshape((-1,) + t["mask"].shape[-2:])
        K, N = q2.shape[-2:]
        g = LayerGEMM(key, M=tokens, K=K, N=N, kind="fc")
        layers.append(g)
        phi = np.asarray(fta.compute_thresholds(
            q2[0].astype(np.int32), mask2[0].astype(np.int32)))
        sps[key] = sparsity_from_export(q2[0].astype(np.int32),
                                        mask2[0].astype(np.int32), phi)
    ours = evaluate_model(layers, sps, use_input_bit=False)
    dense = evaluate_dense_baseline(layers)
    return {
        "speedup": dense.cycles / ours.cycles,
        "energy_savings": 1 - ours.energy_pj / dense.energy_pj,
        "u_act": ours.u_act,
        "n_projections": len(layers),
    }


# ---------------------------------------------------------------------------
# Stacked serving tables: ALL L layers of every projection family packed
# with one shared MAXB, as scan-carryable arrays. This is what lets
# `lax.scan`-stacked forwards (transformer / SSM / decode) run the joint
# kernel end-to-end instead of per-layer: the scan slices the leading
# layer axis, the body rebuilds the per-layer JointPacked view and
# dispatches through the same dense_fn(w, x, name) hook the layers
# already accept.
# ---------------------------------------------------------------------------

@dataclass
class StackedKernelTables:
    """Scan-carryable joint-sparse weights for a whole layer stack.

    ``arrays`` is a pytree of stacked jnp arrays (leading axis = layer) —
    pass it as scan xs next to the stacked params. ``static`` holds the
    per-projection (k, n, k_pad) logical dims the per-layer JointPacked
    view needs (scan cannot carry python ints). Grouped (MoE expert)
    entries — keys ``moe/*`` — carry a second leading axis E after the
    layer axis; the per-expert dispatch is the ``expert`` attribute of
    the dense_fn hook (models.moe routes its batched expert einsums
    through it).
    """
    arrays: Dict[str, Dict[str, jnp.ndarray]]
    static: Dict[str, Tuple[int, int, int]]
    interpret: Optional[bool] = None

    def dense_fn(self, slices):
        """Build the dense_fn(w, x, name) hook from one layer's slices
        (the per-iteration xs the scan body receives). The returned hook
        carries the grouped per-expert variant as ``mm.expert`` —
        ``expert(w, x, name)`` computes the batched expert contraction
        ``x[..., e, :, :] @ w[e]`` for every expert through the joint
        kernel (one ``joint_dense`` call per packed expert slice) when
        ``name`` is packed, and falls back to the plain einsum
        otherwise."""
        from repro.kernels import ops

        def _packed(t, name, e=None):
            k, n, k_pad = self.static[name]
            a = (t if e is None
                 else {key: arr[e] for key, arr in t.items()})
            return ops.JointPacked(a["w_blocks"], a["idx"], a["scales"],
                                   a["nblocks"], k, n, k_pad)

        def mm(w, x, name):
            t = None if slices is None else slices.get(name)
            if t is None:
                return x @ w
            return ops.joint_dense(x, _packed(t, name),
                                   interpret=self.interpret).astype(x.dtype)

        def expert(w, x, name):
            """x (..., E, C, D) x w (E, D, F) -> (..., E, C, F)."""
            t = None if slices is None else slices.get(name)
            if t is None:
                return jnp.einsum("...eck,ekf->...ecf", x, w)
            E = t["w_blocks"].shape[0]
            outs = [ops.joint_dense(x[..., e, :, :], _packed(t, name, e),
                                    interpret=self.interpret).astype(x.dtype)
                    for e in range(E)]
            return jnp.stack(outs, axis=-3)

        mm.expert = expert
        return mm


@dataclass
class SegmentedKernelTables:
    """Per-segment stacked packs for a whole decoder (models.segments
    layout): ``segments`` maps segment name -> StackedKernelTables, each
    packed independently with its own shared MAXB. The forward/decode
    segment loops thread ``segments[seg.name]`` through that segment's
    scan.

    ``arrays`` / ``static`` present the flat single-dict view older
    consumers (benchmarks, launch.serve byte accounting) iterate:
    single-segment stacks pass through unprefixed (identical to the
    pre-segmentation layout); multi-segment stacks prefix keys with the
    segment name ("seg02/wq")."""
    segments: Dict[str, StackedKernelTables]

    @property
    def arrays(self) -> Dict[str, Dict[str, jnp.ndarray]]:
        if set(self.segments) == {"blocks"}:
            return self.segments["blocks"].arrays
        return {f"{s}/{name}": t
                for s, seg in self.segments.items()
                for name, t in seg.arrays.items()}

    @property
    def static(self) -> Dict[str, Tuple[int, int, int]]:
        if set(self.segments) == {"blocks"}:
            return self.segments["blocks"].static
        return {f"{s}/{name}": t
                for s, seg in self.segments.items()
                for name, t in seg.static.items()}

    # -- pytree: the packed arrays are leaves, the layout is static -------
    # Serving steps take the tables as a jit ARGUMENT (not a closure
    # constant, which would bake the int8 payload into the executable).
    # The one child is the flat ``arrays`` view under the key "tables",
    # so leaf paths read "tables/<family>/<part>" — the labels the weight
    # waterfall (runtime.jaxpr_cost) attributes packed traffic to.

    def _tree_flatten_with_keys(self):
        layout = tuple((seg_name, tuple(seg.arrays),
                        tuple(sorted(seg.static.items())), seg.interpret)
                       for seg_name, seg in self.segments.items())
        return [(jax.tree_util.GetAttrKey("tables"), self.arrays)], layout

    @classmethod
    def _tree_unflatten(cls, layout, children):
        (flat,) = children
        single = [s for s, *_ in layout] == ["blocks"]
        segments = {}
        for seg_name, names, static, interpret in layout:
            segments[seg_name] = StackedKernelTables(
                arrays={n: flat[n if single else f"{seg_name}/{n}"]
                        for n in names},
                static=dict(static), interpret=interpret)
        return cls(segments=segments)


jax.tree_util.register_pytree_with_keys(
    SegmentedKernelTables, SegmentedKernelTables._tree_flatten_with_keys,
    SegmentedKernelTables._tree_unflatten)


def _stacked_projections(params, cfg: ModelConfig):
    """segment name -> {hook name -> stacked weight} for every decoder
    segment (models.segments.decoder_layout / packable_projections —
    the shared single source of truth). Rank-3 (L, K, N) entries pack
    per-layer; rank-4 ``moe/*`` entries (L, E, K, N) pack grouped across
    the expert axis too. Routers stay dense (same reasoning as the
    paper's dw-conv exclusion: tiny, accuracy-critical). Returns None
    (dense serving) when the param tree does not carry the stacked
    segment subtrees."""
    from repro.models.segments import decoder_layout, packable_projections

    out = {}
    for seg in decoder_layout(cfg):
        blk = params.get(seg.name)
        if blk is None:
            return None
        projs = {}
        for name in packable_projections(seg, cfg):
            node = blk
            for part in _proj_subpath(seg, name).split("/"):
                node = node.get(part) if isinstance(node, dict) else None
                if node is None:
                    break
            if node is None:
                continue        # e.g. gelu MLP has no w_gate
            projs[name] = node
        out[seg.name] = projs
    return out


def _proj_subpath(seg, name: str) -> str:
    """Param subpath of a hook name within one segment's block tree."""
    from repro.models.segments import projection_param_path
    full = projection_param_path(seg, name)
    return full[len(seg.name) + 1:]


def build_stacked_tables(params, cfg: ModelConfig,
                         mode: Optional[str] = None,
                         value_sparsity: Optional[float] = None,
                         bk: Optional[int] = None, bn: Optional[int] = None,
                         interpret: Optional[bool] = None,
                         ) -> Optional[SegmentedKernelTables]:
    """Pack every eligible stacked projection of `params` for serving,
    per decoder segment (each segment gets its own shared-MAXB pack).

    mode "joint" packs at cfg.dbpim_value_sparsity (column-balanced tile
    pruning + INT8/FTA payload: (1 - vs) * 0.5 of dense bf16 weight
    traffic); "bit" packs the same layout at zero value sparsity (0.5x
    traffic); "value" packs the bf16-PAYLOAD variant of the same layout
    (compacted blocks hold the raw bf16 weights with unit scales:
    (1 - vs) of dense traffic, no bit-level compression) so value-only
    sparsity also serves end-to-end through the scan. "dense" returns
    None — plain matmuls.

    Every family packs (the segment layout closed the matrix: hybrid
    sublayer runs and the whisper decoder — including cross-attention —
    are segments like any other; the whisper ENCODER stays dense, it
    runs once per request and never rides decode-step weight traffic).
    bk/bn default to the kernel tile, clamped down to the padded
    projection dims so reduced smoke configs (d_model < 128) do not pack
    pure padding.
    """
    from repro.kernels import ops

    mode = mode or (cfg.dbpim_mode if cfg.dbpim else "dense")
    if mode not in KERNEL_MODES:
        raise ValueError(f"mode {mode!r} not in {KERNEL_MODES}")
    if mode == "dense":
        return None
    if mode == "bit":
        vs = 0.0
    else:
        vs = value_sparsity if value_sparsity is not None else \
            cfg.dbpim_value_sparsity
    payload = "bf16" if mode == "value" else "int8"
    by_segment = _stacked_projections(params, cfg)
    if by_segment is None:
        return None

    segments: Dict[str, StackedKernelTables] = {}
    for seg_name, projections in by_segment.items():
        arrays: Dict[str, Dict[str, jnp.ndarray]] = {}
        static: Dict[str, Tuple[int, int, int]] = {}
        for name, w in projections.items():
            w = np.asarray(w, np.float32)
            _round8 = lambda d: max(8, 8 * (-(-d // 8)))
            bk_eff = bk if bk is not None else min(ops.BK,
                                                   _round8(w.shape[-2]))
            bn_eff = bn if bn is not None else min(ops.BN,
                                                   _round8(w.shape[-1]))
            pack = (ops.pack_joint_sparse_grouped if w.ndim == 4
                    else ops.pack_joint_sparse_stacked)
            packed = pack(w, value_sparsity=vs or None, bk=bk_eff,
                          bn=bn_eff, payload=payload)
            arrays[name] = {"w_blocks": packed.w_blocks, "idx": packed.idx,
                           "scales": packed.scales,
                           "nblocks": packed.nblocks}
            static[name] = (packed.k, packed.n, packed.k_pad)
        segments[seg_name] = StackedKernelTables(arrays=arrays,
                                                 static=static,
                                                 interpret=interpret)
    return SegmentedKernelTables(segments=segments)


def _packed_param_paths(cfg: ModelConfig):
    """Exact '/'-joined param paths of every packable projection. Exact
    paths — not suffixes — so a whisper decoder pack strips the decoder's
    cross-attention copies but never the dense encoder's identically-
    suffixed ones, and hybrid per-segment copies strip one segment at a
    time."""
    from repro.models.segments import (decoder_layout,
                                       packable_projections,
                                       projection_param_path)
    paths = set()
    for seg in decoder_layout(cfg):
        for name in packable_projections(seg, cfg):
            paths.add(projection_param_path(seg, name))
    return paths


def strip_packed_projections(params, cfg: ModelConfig):
    """Replace every stacked-packed projection with a (L, 1, 1) zero
    placeholder: once the tables serve those matmuls, keeping the dense
    bf16 copies device-resident alongside them would make joint serving
    cost ~1.3x dense HBM instead of ~0.3x. The placeholder keeps the
    param tree structure (scan xs still slice a leading layer axis; the
    dense_fn hook never reads the weight it intercepts) and falls through
    every sharding rule to replicated. Strips exactly what
    build_stacked_tables packs — cross-attention and hybrid per-segment
    copies included; the whisper encoder (unpacked) keeps its weights."""
    if _stacked_projections(params, cfg) is None:
        return params
    paths = _packed_param_paths(cfg)

    def visit(path, leaf):
        if _key(path) in paths:
            return jnp.zeros((leaf.shape[0], 1, 1), leaf.dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(visit, params)


def reconstruct_stacked_params(params, tables: SegmentedKernelTables, cfg):
    """Dense FTA reference weights: replace each packed projection in
    `params` with its unpacked (pruned + dequantized) stack, so the SAME
    plain-matmul forward reproduces what the joint kernels compute — the
    fp32-tolerance reference the stacked serving path is tested against.
    """
    from repro.kernels import ops
    from repro.models.segments import decoder_layout, projection_param_path

    segs = {s.name: s for s in decoder_layout(cfg)}
    recon = {}
    for seg_name, seg_tables in tables.segments.items():
        for name in seg_tables.arrays:
            t = seg_tables.arrays[name]
            k, n, k_pad = seg_tables.static[name]
            if t["w_blocks"].ndim == 6:      # grouped (L, E, ...) experts
                packed = ops.JointPackedGrouped(t["w_blocks"], t["idx"],
                                                t["scales"], t["nblocks"],
                                                k, n, k_pad)
                dense = ops.unpack_joint_sparse_grouped(packed)
            else:
                packed = ops.JointPackedStacked(t["w_blocks"], t["idx"],
                                                t["scales"], t["nblocks"],
                                                k, n, k_pad)
                dense = ops.unpack_joint_sparse_stacked(packed)
            full_path = projection_param_path(segs[seg_name], name)
            recon[full_path] = jnp.asarray(dense)

    def visit(path, leaf):
        dense = recon.get(_key(path))
        if dense is not None:
            return dense.astype(leaf.dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(visit, params)
