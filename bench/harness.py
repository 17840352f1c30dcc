"""One run of one cell: set-up, an open-loop window, the comparison with
the reference, and the result line.

Set-up: weights from the seed (``bench.weights``), packed and stripped by
the program (``build_stacked_tables``, ``strip_packed_projections``, the
serving CLI's own set-up), an engine at the configuration's slots,
length and chunk, and a warm-up that runs every call kind the window
uses (slot reset, prefill chunk, decode step). ``setup_s`` runs from
process start to the start of the window.

Window: the harness drives ``ServeEngine.submit`` and ``ServeEngine.tick``
itself. Each request has a due time in seconds from the window's start;
every request that is due is submitted at the next turn of the loop
(with ``arrival`` = the engine's tick count), the engine ticks while any
request is in flight, and otherwise the loop sleeps until the next due
time. A token's time is the host clock after the tick that produced it
(the logits are on the host when ``tick`` returns). Time to first token
runs from the due time. After the window the requests due inside it
drain under a cap while later arrivals keep the load up; one that does
not complete is failed, and counts as a miss in the tails.
"""

from __future__ import annotations

import contextlib
import gc
import glob
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import traffic as traffic_mod
from bench import weights as weights_mod

ROOT = Path(__file__).resolve().parents[1]
#: seconds the requests due in the window may take to finish after it
DRAIN_CAP_S = 45.0
#: requests compared with the reference in every run
N_COMPARED = 4
#: rids of the warm-up requests, outside every plan's range
WARM_RID = 1 << 40


class BenchError(RuntimeError):
    """The run cannot produce a result."""


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# -- the benchmark's files ----------------------------------------------------

def load_cell(workload: str, root: Path = ROOT) -> dict:
    """The cell, its configuration and mix, and the metrics it reports,
    all found by name from ``BENCHMARK.json``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    model = json.loads((root / cfg_entry["file"]).read_text())
    mix = traffic_mod.load_mix(
        root / spec["paths"][0] / "traffic" / f"{cell['traffic']}.json")

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return {"cell": cell, "model": model, "mix": mix, "end_to_end": e2e,
            "per_layer": per_layer,
            "layer_dir": root / spec["paths"][0] / "layer_metrics"}


def load_reader(layer_dir: Path, name: str):
    path = layer_dir / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_layer_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- set-up ---------------------------------------------------------------------

_SIZE_KEYS = {
    "dense": {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
              "n_heads": "num_attention_heads",
              "n_kv_heads": "num_key_value_heads",
              "d_ff": "intermediate_size", "vocab_size": "vocab_size",
              "rope_pct": "partial_rotary_factor",
              "rope_theta": "rope_theta"},
    "ssm": {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
            "vocab_size": "vocab_size", "ssm_state": "state_size",
            "ssm_expand": "expand", "ssm_head_dim": "head_dim",
            "ssm_conv_width": "conv_kernel"},
}


def program_config(model: dict):
    """The program's configuration for the file, checked size by size."""
    from repro.configs import get_config
    cfg = get_config(model["arch"], reduced=model.get("preset") == "reduced",
                     dbpim_mode=model["dbpim_mode"])
    fam = model["family"]
    if (cfg.family == "ssm") != (fam == "ssm"):
        raise BenchError(f"{model['arch']} is family {cfg.family}, the "
                         f"file says {fam}")
    for ours, theirs in _SIZE_KEYS[fam].items():
        if getattr(cfg, ours) != model[theirs]:
            raise BenchError(f"{model['arch']}: {ours}="
                             f"{getattr(cfg, ours)} but the file's "
                             f"{theirs}={model[theirs]}")
    if cfg.tie_embeddings != model["tie_word_embeddings"]:
        raise BenchError(f"{model['arch']}: tied embeddings differ")
    if fam == "dense" and (cfg.norm_type != "layernorm"
                           or cfg.mlp_type != "swiglu"):
        raise BenchError(f"{model['arch']}: norm or MLP kind differs")
    return cfg


def _check_layout(params, cfg):
    """The weights must have the program's own parameter layout."""
    import jax
    from repro.models import init_params
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    got = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree_util.tree_structure(want) != \
            jax.tree_util.tree_structure(got) or \
            jax.tree_util.tree_leaves(want) != jax.tree_util.tree_leaves(got):
        raise BenchError("bench weights do not match the program's "
                         "parameter layout")


@dataclass
class Setup:
    engine: object
    make_weights: object
    phases: Dict[str, float]


def build(model: dict, seed: int) -> Setup:
    """Weights, packing, engine and warm-up."""
    import jax
    from repro.launch.compile_cache import use_compile_cache
    from repro.serving import ServeEngine
    from repro.serving.workload import Request
    from repro.sparsity.sparse_linear import (build_stacked_tables,
                                              strip_packed_projections)

    use_compile_cache()
    # cache every program, however quick to compile: the second run of a
    # cell must find all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    phases = {}
    cfg = program_config(model)
    vs, tile = model["value_sparsity"], tuple(model["tile"])

    t0 = time.perf_counter()
    make = weights_mod.make_fn(model, vs, tile)
    params = jax.block_until_ready(make(seed))
    _check_layout(params, cfg)
    phases["weights"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    tables = build_stacked_tables(params, cfg, value_sparsity=vs)
    params = strip_packed_projections(params, cfg)
    jax.block_until_ready((params, tables))
    phases["pack"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    engine = ServeEngine(cfg, params, n_slots=model["n_slots"],
                         max_len=model["max_len"],
                         prefill_chunk=model["prefill_chunk"],
                         prefill_mode="chunked", stacked_tables=tables)
    del params, tables
    if engine.prefill_mode != "chunked":
        raise BenchError(f"{model['arch']}: no chunked prefill")
    phases["engine"] = time.perf_counter() - t0

    # warm-up: a two-chunk prompt (reset, prefill chunks) then decode
    t0 = time.perf_counter()
    C = model["prefill_chunk"]
    engine.submit(Request(rid=WARM_RID, prompt=tuple(range(1, C + 2)),
                          gen_len=3, arrival=engine.tick_count))
    while len(engine.outputs.get(WARM_RID, ())) < 3:
        engine.tick()
    phases["warmup"] = time.perf_counter() - t0
    return Setup(engine, make, phases)


# -- the window -----------------------------------------------------------------

@dataclass
class Tick:
    t_end: float
    kinds: tuple
    pf_flops: float = 0.0      # model FLOPs of the prompt tokens served
    dc_flops: float = 0.0      # model FLOPs of the decoded tokens served


@dataclass
class Served:
    """What the harness saw of one window."""
    due: Dict[int, float] = field(default_factory=dict)
    submit: Dict[int, float] = field(default_factory=dict)
    tokens: Dict[int, List[float]] = field(default_factory=dict)
    planned: Dict[int, object] = field(default_factory=dict)
    in_window: List[int] = field(default_factory=list)
    ticks: Dict[int, Tick] = field(default_factory=dict)
    window_s: float = 0.0
    end_s: float = 0.0
    backlog_trace: List[tuple] = field(default_factory=list)


class _Spans:
    """Host spans in the profiler's trace when tracing, else nothing."""

    def __init__(self, on: bool):
        self.on = on
        if on:
            from jax.profiler import TraceAnnotation
            self._ann = TraceAnnotation

    def __call__(self, name: str):
        return self._ann(name) if self.on else contextlib.nullcontext()


def _flops_coef(model: dict):
    from bench.work import token_flops
    vs, tile = model["value_sparsity"], tuple(model["tile"])
    base = token_flops(model, vs, tile, 0)
    return base, token_flops(model, vs, tile, 1) - base


def serve_window(engine, plan, seconds: float, model: dict, *,
                 backlog: Optional[int] = None, trace_at=None,
                 drain_cap: float = DRAIN_CAP_S) -> Served:
    """Drive ``engine`` through one window of ``plan``. ``backlog``: keep
    that many submitted requests waiting for a slot instead of following
    due times. ``trace_at``: (start, stop, logdir) of a profiler trace,
    seconds into the window."""
    from repro.serving.workload import Request
    import jax

    rec = Served(window_s=seconds)
    spans = _Spans(trace_at is not None)
    m = engine.metrics
    C = model["prefill_chunk"]
    base, coef = _flops_coef(model)
    seen: Dict[int, int] = {}
    pf_done: Dict[int, int] = {}
    active: Dict[int, object] = {}
    waiting: set = set()
    queue = list(plan)
    qi = 0
    tracing = False
    gc.collect()
    t0 = time.perf_counter()

    def submit(p, t):
        due = t if p.due_s is None else p.due_s
        engine.submit(Request(rid=p.rid, prompt=p.prompt, gen_len=p.gen_len,
                              arrival=engine.tick_count))
        rec.due[p.rid], rec.submit[p.rid] = due, t
        rec.tokens[p.rid] = []
        rec.planned[p.rid] = p
        # Poisson: due inside the window; backlog: submitted inside it
        if p.in_window and (p.due_s is not None or t < seconds):
            rec.in_window.append(p.rid)
        active[p.rid] = p
        waiting.add(p.rid)
        seen[p.rid] = 0

    while True:
        now = time.perf_counter() - t0
        if trace_at is not None:
            if not tracing and trace_at[0] <= now < trace_at[1]:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_at[2], profiler_options=opts)
                tracing = True
            elif tracing and now >= trace_at[1]:
                jax.profiler.stop_trace()
                tracing = False
                trace_at = (math.inf, math.inf, trace_at[2])
        closed = now >= seconds
        with spans("bench.bookkeeping"):
            if backlog is None:
                while qi < len(queue) and queue[qi].due_s <= now:
                    submit(queue[qi], now)
                    qi += 1
            elif not closed:
                while len(waiting) < backlog and qi < len(queue):
                    submit(queue[qi], now)
                    qi += 1
            if closed:
                left = [r for r in rec.in_window if r in active]
                if not left or now >= seconds + drain_cap:
                    break
        if not active:
            nxt = queue[qi].due_s if qi < len(queue) else None
            if nxt is None:
                if closed:
                    break
                nxt = seconds
            with spans("bench.wait"):
                time.sleep(max(0.0, min(nxt, seconds + drain_cap) - now))
            continue
        tick = engine.tick_count
        n_pf, n_dc = m.prefill_calls, m.decode_calls
        with spans(f"bench.tick {tick}"):
            engine.tick()
        t = time.perf_counter() - t0
        with spans("bench.bookkeeping"):
            # the engine runs its prefill call before its decode call
            kinds = ("prefill",) * (m.prefill_calls - n_pf) + \
                    ("decode",) * (m.decode_calls - n_dc)
            tk = Tick(t_end=t, kinds=kinds)
            for rid in list(active):
                p = active[rid]
                req = m.requests[rid]
                if req.admitted_tick is not None:
                    waiting.discard(rid)
                    P = len(p.prompt)
                    old = pf_done.get(rid, 0)
                    new = min(P, C * (tick - req.admitted_tick + 1))
                    if new > old:
                        # positions old..new-1 attend pos + 1 keys each
                        ctx = (new * (new + 1) - old * (old + 1)) // 2
                        tk.pf_flops += (new - old) * base + coef * ctx
                        pf_done[rid] = new
                out = engine.outputs.get(rid)
                n = len(out) if out is not None else 0
                k = seen[rid]
                if n > k:
                    rec.tokens[rid].extend([t] * (n - k))
                    first = 1 if k == 0 else 0
                    P = len(p.prompt)
                    for i in range(k + first, n):
                        tk.dc_flops += base + coef * (P + i)
                    seen[rid] = n
                if n >= p.gen_len:
                    del active[rid]
            rec.ticks[tick] = tk
            if backlog is None:
                rec.backlog_trace.append((t, len(waiting)))
    if tracing:
        jax.profiler.stop_trace()
    rec.end_s = time.perf_counter() - t0
    return rec


TRACE_S = 3.0
#: the trace starts this long before the request it waits for is due
TRACE_LEAD_S = 0.05


def trace_window(plan, seconds: float) -> tuple:
    """(start, stop) of the profiler trace, seconds into the window: 3 s
    from just before the first request due after 0.4 of the window, so
    that the traced ticks hold its prefill chunks beside decode steps
    (a backlog mix, whose requests have no due time, starts at 0.4)."""
    a = 0.4 * seconds
    due = [p.due_s for p in plan
           if p.due_s is not None and a <= p.due_s < seconds]
    if due:
        a = max(0.0, min(due) - TRACE_LEAD_S)
    return a, a + min(TRACE_S, 0.4 * seconds)


# -- end-to-end metrics --------------------------------------------------------

def e2e_metrics(rec: Served, names) -> dict:
    """Each requested end-to-end metric from one window's record."""
    win = rec.in_window
    done = [r for r in win if len(rec.tokens[r]) >= rec.planned[r].gen_len]
    out = {}
    if {"ttft_p50_ms", "ttft_p95_ms"} & set(names):
        ttft = [(rec.tokens[r][0] if rec.tokens[r] else rec.end_s)
                - rec.due[r] for r in win]
        if ttft:
            out["ttft_p50_ms"] = (float(np.percentile(ttft, 50)) * 1e3, "ms")
            out["ttft_p95_ms"] = (float(np.percentile(ttft, 95)) * 1e3, "ms")
    if "itl_p95_ms" in names:
        gaps = [b - a for r in win for a, b in zip(rec.tokens[r],
                                                   rec.tokens[r][1:])]
        if gaps:
            out["itl_p95_ms"] = (float(np.percentile(gaps, 95)) * 1e3, "ms")
    if "output_tok_s" in names:
        n = sum(1 for ts in rec.tokens.values() for t in ts
                if t <= rec.window_s)
        out["output_tok_s"] = (n / rec.window_s, "tokens/s")
    late = [rec.submit[r] - rec.due[r] for r in win]
    stats = {"attempted": len(win), "failed": len(win) - len(done),
             "generator_late_p95_ms":
                 float(np.percentile(late, 95)) * 1e3 if late else 0.0}
    return {"metrics": {k: v for k, v in out.items() if k in names},
            "stats": stats}


# -- the comparison with the reference ------------------------------------------

def pick_compared(rec: Served, outputs: Dict[int, list], seed: int,
                  n: int = N_COMPARED) -> List[int]:
    """The longest finished request of the window and others drawn from
    the seed."""
    done = [r for r in rec.in_window
            if len(outputs.get(r, ())) >= rec.planned[r].gen_len]
    if not done:
        return []
    size = lambda r: len(rec.planned[r].prompt) + rec.planned[r].gen_len
    longest = max(done, key=lambda r: (size(r), -r))
    rest = [r for r in done if r != longest]
    rng = np.random.default_rng([int(seed), 7])
    pick = list(rng.choice(rest, size=min(n - 1, len(rest)), replace=False)) \
        if rest else []
    return [longest] + [int(r) for r in pick]


def compared_batch(rids, planned, outputs, max_len: int):
    """(tokens, targets, mask) of shape (N_COMPARED, max_len): each row
    is prompt ++ served[:-1]; the position before each served token
    holds that token as target."""
    R = N_COMPARED
    tokens = np.zeros((R, max_len), np.int32)
    targets = np.zeros((R, max_len), np.int32)
    mask = np.zeros((R, max_len), bool)
    for i, rid in enumerate(rids):
        prompt = list(planned[rid].prompt)
        served = list(outputs[rid])
        seq = prompt + served[:-1]
        tokens[i, :len(seq)] = seq
        P = len(prompt)
        targets[i, P - 1:P - 1 + len(served)] = served
        mask[i, P - 1:P - 1 + len(served)] = True
    return tokens, targets, mask


def _rel_l2(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.linalg.norm(got - ref, axis=-1)
                        / np.linalg.norm(ref, axis=-1)))


def judge(readings: dict, limits: dict, compared: bool) -> tuple:
    """(correct, checks): every number the configuration limits, beside
    its limit; correct when some request was compared and no number
    passes its limit. The program's readings and the control's go
    through this alike."""
    checks = {name: {"value": readings[name], "limit": limit}
              for name, limit in limits.items()}
    return (compared and all(c["value"] <= c["limit"]
                             for c in checks.values()), checks)


def _readings(gaps, mask, first, ref_first) -> dict:
    g = gaps[mask]
    return {"logit_gap": float(g.max()) if g.size else math.inf,
            "mean_logit_gap": float(g.mean()) if g.size else math.inf,
            "flip_share": float((g > 0).mean()) if g.size else math.inf,
            "first_logits_rel_l2": _rel_l2(first, ref_first)}


def compare(make_weights, seed: int, model: dict, tokens, targets, mask,
            first_pos, first_logits, control: bool = False) -> dict:
    """Readings of the served tokens against the reference over the
    compared positions: the widest and the mean gap by which a served
    token's logit lies below the reference's best, the share of
    positions where it lies below at all, and the relative L2 error of
    the served first-token logits. With ``control`` the same readings of
    the float8 control (its own first choice at each position)."""
    import jax.numpy as jnp
    from bench import reference

    params = make_weights(seed)
    tok, tgt = jnp.asarray(tokens), jnp.asarray(targets)
    pos = jnp.asarray(first_pos)
    rows = len(first_logits)
    g, ref_first = (np.asarray(a) for a in
                    reference.reference_pass(params, tok, tgt, pos, model))
    out = {"program": _readings(g, mask, first_logits, ref_first[:rows]),
           "positions": int(mask.sum())}
    if control:
        ctl, ctl_first = reference.control_pass(params, tok, pos, model)
        gc_, _ = reference.reference_pass(params, tok, ctl, pos, model)
        out["control"] = _readings(np.asarray(gc_), mask,
                                   np.asarray(ctl_first)[:rows],
                                   ref_first[:rows])
    del params
    return out


# -- one run ---------------------------------------------------------------------

def device_info(require_tpu: bool, chips: int) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _layer_metrics(cellspec, rec: Served, logdir: str, model: dict,
                   peak: dict) -> tuple:
    from bench import trace as trace_mod
    files = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    if not files:
        raise BenchError("the profiler wrote no trace")
    parsed = trace_mod.load(files[-1])
    tick_calls = {n: t.kinds for n, t in rec.ticks.items()}
    red = trace_mod.reduce(parsed, tick_calls)
    ctx = {"reduced": red, "ticks": rec.ticks, "model": model,
           "peak": peak}
    metrics = {}
    for metric in cellspec["per_layer"]:
        val = load_reader(cellspec["layer_dir"], metric["name"])(ctx)
        if val is not None:
            metrics[metric["name"]] = {"value": float(val),
                                       "unit": metric["unit"]}
    device = {"busy_s": red.busy_s, "window_s": red.window[1] - red.window[0]}
    breakdown = {"device_ops": red.device_ops, "idle_gaps": red.idle_gaps}
    log(f"[bench] trace: {len(parsed.ops)} device ops, "
        f"{len(parsed.modules)} executions, {len(red.steps)} steps "
        f"classified over {len(red.ticks)} ticks; unmatched ticks "
        f"{dict(list(red.unmatched.items())[:4])}")
    return metrics, device, breakdown


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, root: Path = ROOT,
        control: bool = False, breaker=None) -> dict:
    """One run; returns the result line's object. ``breaker(engine)``,
    for tests, breaks the timed path after set-up."""
    import jax

    cellspec = load_cell(workload, root)
    cell, model, mix = cellspec["cell"], cellspec["model"], cellspec["mix"]
    dev = device_info(require_tpu, cell["chips"])
    peak = None
    if dev["platform"] == "tpu":
        from bench.peaks import peaks_for
        peak = peaks_for(dev["kind"])

    st = build(model, seed)
    engine = st.engine
    if breaker is not None:
        breaker(engine)
    plan = traffic_mod.plan(mix, seed, seconds, model["vocab_size"],
                            extra_s=DRAIN_CAP_S)
    compiles0 = dict(engine.sentinel.counts())
    logdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    trace_at = trace_window(plan, seconds) + (logdir,) if trace else None
    setup_s = time.perf_counter() - t_start
    log(f"[bench] {workload} seed {seed}: set-up {setup_s:.2f} s "
        + " ".join(f"{k} {v:.2f}" for k, v in st.phases.items()))

    backlog = mix.get("backlog") if mix["arrivals"] == "backlog" else None
    rec = serve_window(engine, plan, seconds, model, backlog=backlog,
                       trace_at=trace_at)
    if engine.sentinel.counts() != compiles0:
        raise BenchError(f"compiled inside the window: {compiles0} -> "
                         f"{engine.sentinel.counts()}")
    names = [m["name"] for m in cellspec["end_to_end"]]
    e2e = e2e_metrics(rec, names)
    stats = e2e["stats"]
    log(f"[bench] window {seconds} s: {stats['attempted']} attempted, "
        f"{stats['failed']} failed, {len(rec.ticks)} ticks, drained at "
        f"{rec.end_s:.2f} s, generator late p95 "
        f"{stats['generator_late_p95_ms']:.3f} ms")

    stats_mem = jax.devices()[0].memory_stats() or {}
    device = dict(dev, memory_peak_bytes=int(
        stats_mem.get("peak_bytes_in_use", 0)))

    metrics, breakdown = {}, None
    if trace:
        if peak is None:
            raise BenchError("device metrics need a chip in the table of "
                             "peaks")
        metrics, dev_trace, breakdown = _layer_metrics(
            cellspec, rec, logdir, model, peak)
        device.update(dev_trace)
        shutil.rmtree(logdir, ignore_errors=True)
    else:
        for name, (val, unit) in e2e["metrics"].items():
            metrics[name] = {"value": val, "unit": unit}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    # the comparison, once the program's state is freed
    outputs = {r: list(engine.outputs.get(r, ())) for r in rec.in_window}
    rids = pick_compared(rec, outputs, seed)
    tokens, targets, mask = compared_batch(rids, rec.planned, outputs,
                                           model["max_len"])
    first_pos = np.zeros((N_COMPARED,), np.int32)
    first_pos[:len(rids)] = [len(rec.planned[r].prompt) - 1 for r in rids]
    first_logits = np.stack([np.asarray(engine.first_logits[r], np.float32)
                             .reshape(-1) for r in rids]) if rids else \
        np.zeros((0, model["vocab_size"]), np.float32)
    make_weights, phases = st.make_weights, st.phases
    del engine, st
    gc.collect()
    t0 = time.perf_counter()
    cmp_ = compare(make_weights, seed, model, tokens, targets, mask,
                   first_pos, first_logits, control=control)
    log(f"[bench] reference over {len(rids)} requests, "
        f"{cmp_['positions']} served tokens: {time.perf_counter() - t0:.2f} s")
    for side in ("program", "control"):
        if side in cmp_:
            log(f"[bench] readings {side} " + json.dumps(cmp_[side]))
    correct, checks = judge(cmp_["program"], model["check"], bool(rids))
    result = {"correct": correct, "attempted": stats["attempted"],
              "failed": stats["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["setup_phases_s"] = phases
    result["readings"] = {k: v for k, v in cmp_.items()
                          if k in ("program", "control")}
    if control:
        ctl_correct, ctl_checks = judge(cmp_["control"], model["check"],
                                        bool(rids))
        result["control"] = {"correct": ctl_correct, "checks": ctl_checks}
        for name, c in ctl_checks.items():
            log(f"control check {name} {c['value']!r} limit {c['limit']!r}")
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result
