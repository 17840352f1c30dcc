"""Engine-level serving benchmark: prefill policies + admission schedules.

Runs the SAME deterministic workload trace (Poisson arrivals, mixed
prompt lengths, fixed seed) through serving.ServeEngine under every
prefill policy the arch supports, over the stacked joint-sparse path,
and emits ``BENCH_serve_engine.json``:

  * per-ENGINE-CALL-KIND modeled weight bytes (decode vs
    prefill_chunk_exact vs prefill_parallel — trip-aware jaxpr walk,
    runtime.jaxpr_cost.analyze_call_kinds; packed kernels charge stored
    bytes only) and the same normalized PER PROMPT TOKEN — the number
    the parallel-form SSD prefill attacks;
  * per-request steps-to-first-token, served tokens per device step,
    weight bytes per served token, TTFT/queue summaries per policy;
  * a FIFO-vs-SPF admission case on a bimodal (chat-vs-document)
    workload: mean TTFT under both schedules.

Guards (raise -> CI fails):
  1. exact policies (chunked with cfg.prefill_exact for SSM; chunked as
     is for attention) generate IDENTICAL tokens to the full-forward
     baseline — only the step schedule changes;
  2. every request with prompt_len > PREFILL_CHUNK takes STRICTLY fewer
     prefill steps chunked than full-forward;
  3. chunked served tokens/step >= the full-forward baseline;
  4. SSM parallel-form prefill: first-token logits within
     models.ssm.PARALLEL_PREFILL_ATOL of the sequential-decode baseline,
     and prefill weight bytes PER PROMPT TOKEN <= 0.35x the exact-chunk
     path at C=8 (the ~C x projection-read saving, measured not
     asserted);
  5. SPF mean TTFT <= FIFO mean TTFT on the bimodal workload, with the
     no-starvation skip bound (skips <= spf_age_cap) intact;
  6. a ZERO-fault FaultPlan *with the tracer attached* leaves outputs
     and device-call count exactly unchanged vs the bare fault-free run
     (the fault layer AND the obs layer are free when idle — the
     zero-overhead-when-off contract);
  7. under a seeded fault schedule containing every fault kind, every
     completed request's tokens are BITWISE identical to the fault-free
     run (recovery-by-replay), with >= 1 of each kind detected;
  8. goodput under that schedule >= 0.9;
  9. per-call-kind weight-traffic WATERFALL rows (repro.obs.waterfall,
     attribution by parameter path) sum EXACTLY to the call kind's
     weight_bytes — no byte is unattributed;
 10. the recompile sentinel reports exactly ONE compile per
     (call_kind, arch) after every engine run — the fixed-shape
     no-recompile contract, measured not assumed;
 11. durability is PASSIVE — with the write-ahead journal and periodic
     snapshots ON (no crash), outputs and device-call count are exactly
     the bare run's;
 12. kill-chaos warm restart — the engine is killed (EngineCrash) at
     two seeded ticks, restored from the latest snapshot + journal
     tail, and every completed request's tokens are BITWISE identical
     to the uninterrupted run, on BOTH smoke archs (attention, and SSM
     under cfg.prefill_exact where chunk==decode must be exact);
 13. bounded redo — each restore's journal-evidenced re-prefilled
     tokens <= snapshot_every x slots restored (the cadence-vs-
     replay-work contract);
 14. paged continuous batching is BITWISE — a >= 1000-request long-tail
     workload (lognormal prompts, zipf generations) through the paged
     engine generates streams identical to the contiguous engine,
     preemption-resumes included;
 15. >= 1 preemption actually fired and goodput >= 0.9 under pressure;
 16. the paged KV pool is strictly smaller than the static cache;
 17. page churn causes ZERO recompiles (the table is a per-call
     operand, not a traced shape).

The chaos run is traced end to end; its span/event/interval stream plus
the waterfall is dumped to ``TRACE_serve_chaos.jsonl`` (a CI artifact)
and rendered through ``repro.launch.report`` as a smoke test. The
restart case dumps its own artifacts the same way — one tracer spans
the kill/restore chain (``TRACE_serve_restart.jsonl``) and the
recovered journal is preserved as ``JOURNAL_serve_restart.jsonl``.

    PYTHONPATH=src python -m benchmarks.serve_engine_bench [--smoke] \
        [--out BENCH_serve_engine.json] [--trace-out TRACE.jsonl]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.models import init_cache, init_params
from repro.models.ssm import PARALLEL_PREFILL_ATOL
from repro.obs import Tracer, serving_cost_by_kind, validate
from repro.serving import (EngineCrash, FaultPlan, ServeEngine,
                           WorkloadSpec, make_trace)
from repro.serving.faults import INJECTABLE_KINDS
from repro.serving.faults import FaultEvent
from repro.serving.journal import fold_records, read_journal
from repro.sparsity.sparse_linear import (build_stacked_tables,
                                          strip_packed_projections)
from .common import emit

#: arctic is the MoE chunked-prefill case: no sliding window, so the
#: per-position capacity dispatch (models.moe.apply_moe per_position)
#: chunk-prefills — guard 1 holds it to generations IDENTICAL to
#: stepwise prefill, guard 2 to strictly fewer steps-to-first-token.
ARCHS = ("tinyllama-1.1b", "mamba2-1.3b", "arctic-480b")
PREFILL_CHUNK = 8
N_SLOTS = 4
MAX_LEN = 48
SPEC = WorkloadSpec(n_requests=6, arrival_rate=1.0, prompt_len=(4, 24),
                    gen_len=(4, 8), dist="uniform", seed=7)
#: guard 4 threshold: parallel-form SSM prefill weight bytes per prompt
#: token vs the exact-chunk path at C=8 — the CI-enforced >= 4x
#: reduction. The raw projection saving is ~1/C = 0.125; the unembedding
#: (once per chunk either way) dilutes it to a measured 0.174, which
#: leaves deterministic (modeled-bytes, no timing) headroom under 0.25.
SSM_PARALLEL_MAX_RATIO = 0.25
#: bimodal schedule case: short chats vs long documents competing for
#: two slots — the mix where shortest-prompt-first pays.
SCHED_SPEC = WorkloadSpec(n_requests=10, arrival_rate=2.0,
                          prompt_len=(3, 24), gen_len=(4, 6),
                          dist="bimodal", seed=13)
SCHED_SLOTS = 2
SPF_AGE_CAP = 4
#: chaos case: a Poisson trace under an injected fault schedule. The
#: arch is attention-family (tinyllama) so every prefill chunk —
#: recovery replays included — is BITWISE identical to sequential
#: decode, which is what makes the recovered-vs-fault-free equality an
#: exact guard, not a tolerance. seed/rate are picked so the sampled
#: plan contains every fault kind (asserted, so a regeneration that
#: loses one fails loudly).
CHAOS_SPEC = WorkloadSpec(n_requests=8, arrival_rate=0.8,
                          prompt_len=(3, 18), gen_len=(4, 8),
                          dist="uniform", seed=21)
CHAOS_FAULT_SEED = 3
CHAOS_FAULT_RATE = 0.2
CHAOS_GOODPUT_MIN = 0.9
#: kill-chaos restart case: same workload shape as chaos but its own
#: seed, the engine killed at two ticks derived from the uninterrupted
#: run's length (1/3 and 2/3 through — mid-prefill-and-decode, the
#: worst case for a restart). Snapshot cadence bounds redone work:
#: each restore may re-prefill at most RESTART_SNAPSHOT_EVERY journal-
#: evidenced tokens per restored slot (guard 13).
RESTART_SPEC = WorkloadSpec(n_requests=6, arrival_rate=0.5,
                            prompt_len=(3, 18), gen_len=(4, 8),
                            dist="uniform", seed=17)
RESTART_SNAPSHOT_EVERY = 4
#: continuous-batching case: a LONG-TAIL workload (lognormal prompts,
#: zipf generation lengths — most requests tiny, a heavy tail of big
#: ones) through the PAGED engine with a pool deliberately smaller than
#: the static worst-case cache. The shape is the argument for paging:
#: static slots reserve max_len for everyone, the pool reserves for the
#: traffic actually seen, and pressure spills into preemption instead
#: of rejection. CB_N_PAGES=9 vs the static 4x8=32 pages keeps the
#: pool at ~28% of worst case while goodput stays 1.0.
CB_SPEC = WorkloadSpec(n_requests=1000, arrival_rate=1.0,
                       prompt_len=(3, 16), gen_len=(3, 8),
                       dist="lognormal", gen_dist="zipf", seed=29)
CB_MAX_LEN = 32
CB_PAGE_SIZE = 4
CB_N_PAGES = 9
CB_GOODPUT_MIN = 0.9


def _mk_cache(cfg):
    cache = init_cache(cfg, N_SLOTS, MAX_LEN)
    cache["pos"] = jnp.zeros((N_SLOTS,), jnp.int32)
    if "attn" in cache:
        cache["attn"]["pos"] = jnp.zeros((N_SLOTS,), jnp.int32)
    return cache


def _weight_bytes_by_kind(cfg, mesh, params, tables) -> tuple:
    """(per-call weight bytes, per-parameter-path waterfall) for each
    engine call kind, keyed by the step builders' call_kind tags
    (repro.obs.waterfall.serving_cost_by_kind). Guard 9: each kind's
    waterfall rows must sum EXACTLY to its weight_bytes."""
    costs = serving_cost_by_kind(
        cfg, mesh, params, _mk_cache(cfg), n_slots=N_SLOTS,
        prefill_chunk=PREFILL_CHUNK, tables=tables,
        include_exact_fallback=True)
    wb = {kind: float(acc["weight_bytes"]) for kind, acc in costs.items()}
    waterfall = {kind: dict(acc["weight_bytes_by_path"])
                 for kind, acc in costs.items()}
    for kind, rows in waterfall.items():
        total = sum(rows.values())
        if total != wb[kind]:              # integer bytes: exact equality
            raise RuntimeError(
                f"{cfg.name}/{kind}: waterfall rows sum to {total}, "
                f"weight_bytes is {wb[kind]} — "
                f"{wb[kind] - total:+.0f} bytes unattributed")
    return wb, waterfall


def _per_prompt_token(wb_by_kind: dict) -> dict:
    """Normalize per-call weight bytes to PER PROMPT TOKEN for each way a
    prompt token can enter the cache: stepwise (decode call, 1 token per
    slot) or chunked (C tokens per slot)."""
    out = {}
    for kind, wb in wb_by_kind.items():
        tokens = N_SLOTS * (1 if kind == "decode" else PREFILL_CHUNK)
        out[kind] = wb / tokens
    return out


def _run_engine(cfg, params, mesh, tables, trace, prefill_mode):
    engine = ServeEngine(cfg, params, mesh=mesh, n_slots=N_SLOTS,
                         max_len=MAX_LEN, prefill_chunk=PREFILL_CHUNK,
                         prefill_mode=prefill_mode, stacked_tables=tables)
    outputs = engine.run(trace)
    return engine, outputs


def _check_sentinel(engine, label: str) -> dict:
    """Guard 10: after a full engine run, every registered jitted step
    compiled exactly once. check() already ran per tick; this pins the
    terminal counts into the BENCH record (0 = never called is fine for
    steps the policy skips, e.g. chunk prefill in "full" mode)."""
    counts = engine.sentinel.counts()
    over = {k: c for k, c in counts.items() if c > 1}
    if over:
        raise RuntimeError(f"{label}: steps recompiled: {over} — the "
                           f"fixed-shape no-recompile contract broke")
    return counts


def bench_arch(arch: str) -> dict:
    cfg = get_config(arch, reduced=True, dbpim_mode="joint")
    mesh = make_test_mesh()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tables = build_stacked_tables(params, cfg)
    if tables is None:
        raise RuntimeError(f"{arch}: no stacked joint path — the serving "
                           "integration this bench measures is missing")
    params = strip_packed_projections(params, cfg)
    wb, waterfall = _weight_bytes_by_kind(cfg, mesh, params, tables)
    wb_per_tok = _per_prompt_token(wb)

    trace = make_trace(SPEC, cfg.vocab_size)
    # policies: "chunked" is the arch's default chunk math (parallel SSD
    # for SSM, exact for attention); SSM adds the exact-chunk fallback.
    policies = {"chunked": cfg, "full": cfg}
    if cfg.serving_capabilities().parallel_prefill:
        policies = {"chunked": cfg,
                    "chunked_exact": cfg.scaled(prefill_exact=True),
                    "full": cfg}
    runs = {}
    recompile_counts = {}
    for mode, mode_cfg in policies.items():
        prefill_mode = "full" if mode == "full" else "chunked"
        engine, outputs = _run_engine(mode_cfg, params, mesh, tables,
                                      trace, prefill_mode)
        recompile_counts[mode] = _check_sentinel(engine, f"{arch}/{mode}")
        s = engine.metrics.summary()
        kind = engine.prefill_kind or "decode"
        total_wb = (s["decode_calls"] * wb["decode"]
                    + s["prefill_calls"] * wb.get(kind, 0.0))
        runs[mode] = {
            "prefill_kind": engine.prefill_kind,
            "outputs": outputs,
            "first_logits": engine.first_logits,
            "summary": s,
            "per_request": engine.metrics.per_request(),
            "weight_bytes_per_served_token":
                total_wb / max(s["generated_tokens"], 1),
        }

    # guard 1: exact chunk policy generates IDENTICAL tokens to full —
    # the schedule changed, the math not ("chunked_exact" for SSM, plain
    # "chunked" for attention where chunks are always exact)
    exact_mode = ("chunked_exact" if "chunked_exact" in runs else "chunked")
    if runs[exact_mode]["outputs"] != runs["full"]["outputs"]:
        raise RuntimeError(f"{arch}: {exact_mode} and full-forward prefill "
                           "generated different tokens")

    # guard 2: strict prefill-step reduction for prompts > one chunk
    chunk_steps = {r["rid"]: r["prefill_steps"]
                   for r in runs["chunked"]["per_request"]}
    for r in runs["full"]["per_request"]:
        if r["prompt_len"] > PREFILL_CHUNK and \
                chunk_steps[r["rid"]] >= r["prefill_steps"]:
            raise RuntimeError(
                f"{arch}: req{r['rid']} (prompt {r['prompt_len']} > chunk "
                f"{PREFILL_CHUNK}) took {chunk_steps[r['rid']]} chunked "
                f"prefill steps vs {r['prefill_steps']} full — no "
                f"steps-to-first-token reduction")

    # guard 3: chunked tokens/step >= the full-forward baseline
    tps_c = runs["chunked"]["summary"]["tokens_per_step"]
    tps_f = runs["full"]["summary"]["tokens_per_step"]

    record = {
        "arch": cfg.name, "family": cfg.family,
        "prefill_chunk": PREFILL_CHUNK, "n_slots": N_SLOTS,
        "max_len": MAX_LEN,
        "workload": {"n_requests": SPEC.n_requests,
                     "arrival_rate": SPEC.arrival_rate,
                     "prompt_len": SPEC.prompt_len, "gen_len": SPEC.gen_len,
                     "dist": SPEC.dist, "seed": SPEC.seed},
        "per_call_weight_bytes": wb,
        "weight_waterfall": waterfall,
        "recompile_counts": recompile_counts,
        "prefill_weight_bytes_per_prompt_token": wb_per_tok,
        "tokens_per_step_chunked": tps_c,
        "tokens_per_step_full": tps_f,
        "ttft_ticks_mean_chunked":
            runs["chunked"]["summary"]["ttft_ticks_mean"],
        "ttft_ticks_mean_full": runs["full"]["summary"]["ttft_ticks_mean"],
        "pass": tps_c >= tps_f,
    }
    for mode, run_ in runs.items():
        record[mode] = {k: v for k, v in run_.items()
                        if k not in ("outputs", "first_logits")}

    # guard 4 (SSM only): parallel-form equivalence + traffic contract
    if cfg.serving_capabilities().parallel_prefill:
        atol = PARALLEL_PREFILL_ATOL[cfg.dtype]
        dmax = 0.0
        for rid, lg in runs["full"]["first_logits"].items():
            lp = runs["chunked"]["first_logits"][rid]
            dmax = max(dmax, float(np.max(np.abs(
                np.asarray(lg, np.float32) - np.asarray(lp, np.float32)))))
        ratio = (wb_per_tok["prefill_parallel"]
                 / wb_per_tok["prefill_chunk_exact"])
        record["parallel_max_abs_dlogits"] = dmax
        record["parallel_atol"] = atol
        record["parallel_over_exact_weight_ratio"] = ratio
        if dmax > atol:
            raise RuntimeError(
                f"{arch}: parallel-form prefill first-token logits drifted "
                f"max|d|={dmax:.4f} > atol={atol} from sequential decode")
        if ratio > SSM_PARALLEL_MAX_RATIO:
            raise RuntimeError(
                f"{arch}: parallel-form prefill weight bytes/prompt token "
                f"= {ratio:.3f}x of the exact chunk path at C="
                f"{PREFILL_CHUNK} (guard: <= {SSM_PARALLEL_MAX_RATIO})")
    return record


def bench_schedule(arch: str = "tinyllama-1.1b") -> dict:
    """FIFO vs shortest-prompt-first admission on a bimodal workload:
    more requests than slots, short chats queued behind long documents.
    Guard 5: SPF mean TTFT <= FIFO's, and no request is queue-jumped more
    than spf_age_cap times (the no-starvation bound)."""
    cfg = get_config(arch, reduced=True, dbpim_mode="joint")
    mesh = make_test_mesh()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tables = build_stacked_tables(params, cfg)
    params = strip_packed_projections(params, cfg)
    trace = make_trace(SCHED_SPEC, cfg.vocab_size)
    out = {"arch": cfg.name, "n_slots": SCHED_SLOTS,
           "spf_age_cap": SPF_AGE_CAP,
           "workload": {"n_requests": SCHED_SPEC.n_requests,
                        "arrival_rate": SCHED_SPEC.arrival_rate,
                        "prompt_len": SCHED_SPEC.prompt_len,
                        "dist": SCHED_SPEC.dist, "seed": SCHED_SPEC.seed}}
    for schedule in ("fifo", "spf"):
        engine = ServeEngine(cfg, params, mesh=mesh, n_slots=SCHED_SLOTS,
                             max_len=MAX_LEN, prefill_chunk=PREFILL_CHUNK,
                             schedule=schedule, spf_age_cap=SPF_AGE_CAP,
                             stacked_tables=tables)
        engine.run(trace)
        s = engine.metrics.summary()
        out[schedule] = {"ttft_ticks_mean": s["ttft_ticks_mean"],
                         "ttft_ticks_p95": s["ttft_ticks_p95"],
                         "n_completed": s["n_completed"],
                         # skip entries are dropped at admission; the
                         # final counts live in per-request metrics
                         "max_skips": max(
                             (r.skips
                              for r in engine.metrics.requests.values()),
                             default=0)}
        if s["n_completed"] != SCHED_SPEC.n_requests:
            raise RuntimeError(f"schedule={schedule}: only "
                               f"{s['n_completed']} of "
                               f"{SCHED_SPEC.n_requests} completed")
    if out["spf"]["ttft_ticks_mean"] > out["fifo"]["ttft_ticks_mean"]:
        raise RuntimeError(
            f"spf mean TTFT {out['spf']['ttft_ticks_mean']:.2f} > fifo "
            f"{out['fifo']['ttft_ticks_mean']:.2f} on the bimodal workload")
    if out["spf"]["max_skips"] > SPF_AGE_CAP:
        raise RuntimeError(
            f"spf queue-jumped a request {out['spf']['max_skips']} times "
            f"> cap {SPF_AGE_CAP} — starvation bound broken")
    out["pass"] = True
    return out


def bench_chaos(arch: str = "tinyllama-1.1b",
                trace_out: str = "TRACE_serve_chaos.jsonl") -> dict:
    """Fault-tolerance + observability guard (BENCH key ``chaos``): the
    same Poisson trace runs fault-free (bare), under a ZERO-fault plan
    with the TRACER ATTACHED, and under a seeded fault schedule with
    every fault kind (also traced). Guards:

      6. zero-overhead-when-off — the traced zero-fault run's outputs
         AND device-call count are exactly the bare fault-free run's
         (neither the fault layer nor the obs layer may perturb the
         engine);
      7. bitwise recovery-by-replay — every request completed under
         faults carries IDENTICAL generated tokens to the fault-free
         run (the PR 3 chunk==decode invariant, weaponized as the
         recovery mechanism), with >= 1 of each fault kind actually
         landing (step exception, NaN logits, corrupted slot cache);
      8. goodput (completed / submitted) >= CHAOS_GOODPUT_MIN under the
         bench fault rate.

    The chaos run's trace (spans, lifecycle events, slot intervals,
    waterfall) is structurally validated, dumped to ``trace_out``, and
    rendered through the report CLI as a smoke test.
    """
    cfg = get_config(arch, reduced=True, dbpim_mode="joint")
    mesh = make_test_mesh()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tables = build_stacked_tables(params, cfg)
    params = strip_packed_projections(params, cfg)
    trace = make_trace(CHAOS_SPEC, cfg.vocab_size)

    def run_once(plan, tracer=None):
        engine = ServeEngine(cfg, params, mesh=mesh, n_slots=N_SLOTS,
                             max_len=MAX_LEN, prefill_chunk=PREFILL_CHUNK,
                             stacked_tables=tables, fault_plan=plan,
                             tracer=tracer)
        outputs = engine.run(trace)
        return engine, outputs

    ref_engine, ref_out = run_once(None)
    ref_s = ref_engine.metrics.summary()

    # guard 6: a zero-fault plan + an attached tracer must BOTH be free
    zero_engine, zero_out = run_once(FaultPlan.none(),
                                     tracer=Tracer(arch=cfg.name))
    zero_s = zero_engine.metrics.summary()
    if zero_out != ref_out:
        raise RuntimeError(f"{arch}: a ZERO-fault FaultPlan + tracer "
                           "changed the generated tokens — the fault/obs "
                           "layer is not free when idle")
    if zero_s["device_calls"] != ref_s["device_calls"]:
        raise RuntimeError(
            f"{arch}: a ZERO-fault FaultPlan + tracer changed the "
            f"device-call count ({zero_s['device_calls']} vs "
            f"{ref_s['device_calls']}) — the fault/obs layer is not free")

    # the schedule outlives the fault-free run: recovery replays stretch
    # the faulted run past ref ticks, and faults must keep landing there
    plan = FaultPlan.generate(seed=CHAOS_FAULT_SEED,
                              n_ticks=2 * ref_s["engine_ticks"],
                              rate=CHAOS_FAULT_RATE, n_slots=N_SLOTS)
    # the sampler only ever emits the three INJECTABLE kinds —
    # engine_crash is scheduled explicitly by the restart case below
    missing = set(INJECTABLE_KINDS) - {e.kind for e in plan.events}
    if missing:
        raise RuntimeError(f"chaos plan (seed={CHAOS_FAULT_SEED}) lost "
                           f"fault kinds {missing} — re-pick the seed")
    chaos_tracer = Tracer(arch=cfg.name, meta={
        "case": "chaos", "n_slots": N_SLOTS,
        "prefill_chunk": PREFILL_CHUNK,
        "fault_seed": CHAOS_FAULT_SEED, "fault_rate": CHAOS_FAULT_RATE})
    chaos_engine, chaos_out = run_once(plan, tracer=chaos_tracer)
    s = chaos_engine.metrics.summary()

    # guard 7: bitwise recovery + every fault kind actually landed
    for rid, toks in chaos_out.items():
        if chaos_engine.metrics.requests[rid].outcome == "done" \
                and toks != ref_out[rid]:
            raise RuntimeError(
                f"{arch}: req{rid} recovered tokens differ from the "
                f"fault-free run — recovery-by-replay is not bitwise")
    detected = s["faults"]
    for needed in ("step_exception", "cache_corruption",
                   "nonfinite_logits"):
        if detected.get(needed, 0) < 1:
            raise RuntimeError(
                f"{arch}: chaos run detected no {needed!r} fault "
                f"(detected: {detected}) — the schedule missed a kind")

    # guard 8: goodput under faults
    if s["goodput"] < CHAOS_GOODPUT_MIN:
        raise RuntimeError(
            f"{arch}: chaos goodput {s['goodput']:.2f} < "
            f"{CHAOS_GOODPUT_MIN} at fault rate {CHAOS_FAULT_RATE}")

    # the chaos trace is the CI artifact: attach the waterfall, validate
    # structurally, dump, and render through the report CLI (smoke)
    from repro.obs import engine_waterfall
    for kind, wf in engine_waterfall(chaos_engine).items():
        chaos_tracer.waterfall(kind, wf["rows"], wf["total"])
    trace_stats = validate(chaos_tracer.records)
    if trace_out:
        chaos_tracer.dump(trace_out)
        from repro.launch.report import main as report_main
        print(f"[serve_engine_bench] chaos trace -> {trace_out} "
              f"({trace_stats}); report:")
        report_main([trace_out])

    return {
        "arch": cfg.name, "n_slots": N_SLOTS, "max_len": MAX_LEN,
        "prefill_chunk": PREFILL_CHUNK,
        "workload": {"n_requests": CHAOS_SPEC.n_requests,
                     "arrival_rate": CHAOS_SPEC.arrival_rate,
                     "prompt_len": CHAOS_SPEC.prompt_len,
                     "gen_len": CHAOS_SPEC.gen_len,
                     "dist": CHAOS_SPEC.dist, "seed": CHAOS_SPEC.seed},
        "fault_plan": {"seed": CHAOS_FAULT_SEED, "rate": CHAOS_FAULT_RATE,
                       "n_events": len(plan.events),
                       "by_kind": {k: sum(e.kind == k for e in plan.events)
                                   for k in INJECTABLE_KINDS}},
        "goodput": s["goodput"],
        "goodput_min": CHAOS_GOODPUT_MIN,
        "bitwise_recovery": True,
        "zero_overhead_traced": True,
        "trace_out": trace_out or None,
        "trace_stats": trace_stats,
        "recompile_counts": _check_sentinel(chaos_engine,
                                            f"{arch}/chaos"),
        "retries_by_kind": s["retries_by_kind"],
        "call_latency_ms": s["call_latency_ms"],
        "slot_busy_frac": s["slot_busy_frac"],
        "faults_detected": detected,
        "retries": s["retries"], "replays": s["replays"],
        "n_shed": s["n_shed"],
        "calls_by_kind": s["calls_by_kind"],
        "engine_ticks_fault_free": ref_s["engine_ticks"],
        "engine_ticks_chaos": s["engine_ticks"],
        "device_calls_fault_free": ref_s["device_calls"],
        "device_calls_chaos": s["device_calls"],
        "pass": True,
    }


def bench_restart(arch: str = "tinyllama-1.1b",
                  trace_out: str = "",
                  journal_out: str = "") -> dict:
    """Crash-safe serving guard (BENCH key ``restart``): the engine is
    KILLED at two seeded ticks (FaultPlan ``engine_crash`` ->
    EngineCrash between ticks) and brought back with
    ``ServeEngine.restore`` from the latest snapshot + write-ahead
    journal tail. Guards 11-13:

     11. durability passive — journal + snapshots ON, no crash: outputs
         and device-call count exactly the bare run's;
     12. bitwise warm restart — after >= 2 kill/restore cycles every
         request's tokens are IDENTICAL to the uninterrupted run (the
         chunk==decode invariant driving the restore re-prefill; the
         SSM arch runs under cfg.prefill_exact so its chunks are exact
         too);
     13. bounded redo — per restore, journal-evidenced re-prefilled
         tokens <= RESTART_SNAPSHOT_EVERY x slots restored.

    One tracer spans the whole kill/restore chain (crash, restore and
    snapshot events interleaved with the serving spans) and is dumped
    to ``trace_out``; the recovered journal — the single file that
    tells the run's whole story — is copied to ``journal_out``.
    """
    cfg = get_config(arch, reduced=True, dbpim_mode="joint")
    if cfg.serving_capabilities().parallel_prefill:
        # restart re-prefill must be BITWISE, so the SSM serves exact
        # per-token chunks (the parallel form is tolerance-equivalent)
        cfg = cfg.scaled(prefill_exact=True)
    mesh = make_test_mesh()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tables = build_stacked_tables(params, cfg)
    params = strip_packed_projections(params, cfg)
    trace = make_trace(RESTART_SPEC, cfg.vocab_size)

    def mk(**kw):
        return ServeEngine(cfg, params, mesh=mesh, n_slots=N_SLOTS,
                           max_len=MAX_LEN, prefill_chunk=PREFILL_CHUNK,
                           stacked_tables=tables, **kw)

    ref_engine = mk()
    ref_out = ref_engine.run(trace)
    ref_s = ref_engine.metrics.summary()

    with tempfile.TemporaryDirectory() as tmp:
        # guard 11: durability ON, no crash — exactly the bare run
        eng = mk(journal=os.path.join(tmp, "passive.jsonl"),
                 snapshot_dir=os.path.join(tmp, "passive-snaps"),
                 snapshot_every=RESTART_SNAPSHOT_EVERY)
        out = eng.run(trace)
        s = eng.metrics.summary()
        if out != ref_out:
            raise RuntimeError(
                f"{arch}: journal + snapshots changed the generated "
                "tokens — the durability layer is not passive")
        if s["device_calls"] != ref_s["device_calls"]:
            raise RuntimeError(
                f"{arch}: journal + snapshots changed the device-call "
                f"count ({s['device_calls']} vs {ref_s['device_calls']}) "
                "— the durability layer is not passive")

        # guard 12/13: kill at two ticks mid-run, restore, finish
        ticks = ref_s["engine_ticks"]
        crash_ticks = sorted({max(2, ticks // 3),
                              max(4, (2 * ticks) // 3)})
        plan = FaultPlan(events=tuple(
            FaultEvent(tick=t, kind="engine_crash") for t in crash_ticks))
        tracer = Tracer(arch=cfg.name, meta={
            "case": "restart", "n_slots": N_SLOTS,
            "prefill_chunk": PREFILL_CHUNK,
            "snapshot_every": RESTART_SNAPSHOT_EVERY,
            "crash_ticks": list(crash_ticks)})
        jpath = os.path.join(tmp, "journal.jsonl")
        snapdir = os.path.join(tmp, "snaps")
        engine = mk(journal=jpath, snapshot_dir=snapdir,
                    snapshot_every=RESTART_SNAPSHOT_EVERY,
                    fault_plan=plan, tracer=tracer)
        crashes, outputs, restores = 0, None, []
        try:
            outputs = engine.run(trace)
        except EngineCrash:
            crashes += 1
        while outputs is None:
            engine = ServeEngine.restore(
                cfg, params, snapshot_dir=snapdir, journal_path=jpath,
                mesh=mesh, stacked_tables=tables, fault_plan=plan,
                tracer=tracer)
            st = engine.restore_stats
            restores.append(st)
            if st["replayed_prefill_tokens"] > \
                    RESTART_SNAPSHOT_EVERY * max(st["slots_restored"], 1):
                raise RuntimeError(
                    f"{arch}: restore replayed "
                    f"{st['replayed_prefill_tokens']} prefill tokens for "
                    f"{st['slots_restored']} slots — over the "
                    f"snapshot_every={RESTART_SNAPSHOT_EVERY} bound")
            try:
                outputs = engine.resume()
            except EngineCrash:
                crashes += 1
        if crashes != len(crash_ticks):
            raise RuntimeError(
                f"{arch}: {crashes} crashes fired, expected "
                f"{len(crash_ticks)} at ticks {crash_ticks}")
        if outputs != ref_out:
            raise RuntimeError(
                f"{arch}: restarted run's tokens differ from the "
                "uninterrupted run — warm restart is not bitwise")

        # the recovered journal alone must replay the full token story
        recs, _, torn = read_journal(jpath)
        if torn:
            raise RuntimeError(f"{arch}: final journal has a torn tail")
        if {r: t for r, t in fold_records(recs)["tokens"].items()} \
                != ref_out:
            raise RuntimeError(
                f"{arch}: journal token records do not reproduce the "
                "generated streams")

        trace_stats = validate(tracer.records)
        if journal_out:
            shutil.copyfile(jpath, journal_out)
            print(f"[serve_engine_bench] restart journal -> {journal_out} "
                  f"({len(recs)} records)")
    if trace_out:
        tracer.dump(trace_out)
        print(f"[serve_engine_bench] restart trace -> {trace_out} "
              f"({trace_stats})")

    return {
        "arch": cfg.name, "n_slots": N_SLOTS, "max_len": MAX_LEN,
        "prefill_chunk": PREFILL_CHUNK,
        "prefill_exact": bool(cfg.serving_capabilities().parallel_prefill),
        "snapshot_every": RESTART_SNAPSHOT_EVERY,
        "workload": {"n_requests": RESTART_SPEC.n_requests,
                     "arrival_rate": RESTART_SPEC.arrival_rate,
                     "prompt_len": RESTART_SPEC.prompt_len,
                     "gen_len": RESTART_SPEC.gen_len,
                     "dist": RESTART_SPEC.dist, "seed": RESTART_SPEC.seed},
        "engine_ticks_uninterrupted": ticks,
        "crash_ticks": list(crash_ticks),
        "n_crashes": crashes,
        "restores": restores,
        "replayed_prefill_tokens": sum(
            st["replayed_prefill_tokens"] for st in restores),
        "journal_records": len(recs),
        "durability_passive": True,
        "bitwise_restart": True,
        "trace_out": trace_out or None,
        "journal_out": journal_out or None,
        "trace_stats": trace_stats,
        "pass": True,
    }


def _cache_bytes(cache, keys) -> int:
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if str(getattr(path[-1], "key", path[-1])) in keys:
            total += leaf.size * leaf.dtype.itemsize
    return total


def bench_continuous_batching(arch: str = "tinyllama-1.1b",
                              n_requests: int = 0) -> dict:
    """Paged-cache continuous batching (BENCH key ``continuous``): the
    long-tail CB_SPEC workload (>= 1000 requests by default) through the
    paged engine with a pool ~3.5x smaller than the static cache, vs the
    contiguous engine on the SAME trace. Guards:

     14. bitwise paging — the paged run's generated streams are
         IDENTICAL to the contiguous run's, preemptions included (a
         preempted stream re-enters via the journaled-replay record and
         resumes on the chunk==decode invariant);
     15. pressure is survivable — >= 1 preemption actually happened
         (else the pool was not small enough to test anything) AND
         goodput >= CB_GOODPUT_MIN;
     16. the pool is genuinely smaller — paged KV pool bytes < the
         contiguous engine's static KV cache bytes;
     17. zero recompiles — page churn (tables are per-call operands)
         never retriggers compilation, per the sentinel.
    """
    cfg = get_config(arch, reduced=True, dbpim_mode="joint")
    mesh = make_test_mesh()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tables = build_stacked_tables(params, cfg)
    params = strip_packed_projections(params, cfg)
    spec = CB_SPEC
    if n_requests and n_requests != spec.n_requests:
        from dataclasses import replace
        spec = replace(spec, n_requests=n_requests)
    trace = make_trace(spec, cfg.vocab_size)

    def mk(**kw):
        return ServeEngine(cfg, params, mesh=mesh, n_slots=N_SLOTS,
                           max_len=CB_MAX_LEN, prefill_chunk=PREFILL_CHUNK,
                           stacked_tables=tables,
                           queue_cap=spec.n_requests, **kw)

    ref = mk()
    ref_out = ref.run(trace)
    ref_s = ref.metrics.summary()
    eng = mk(paged=True, page_size=CB_PAGE_SIZE, n_pages=CB_N_PAGES)
    out = eng.run(trace)
    s = eng.metrics.summary()

    # guard 14: bitwise paging, preemption-resumes included
    if out != ref_out:
        bad = [r for r in ref_out if out.get(r) != ref_out[r]]
        raise RuntimeError(
            f"{arch}: paged run diverged from contiguous on "
            f"{len(bad)} streams (first: {bad[:5]}) — paging is not "
            f"bitwise")
    # guard 15: the pool was actually under pressure, and survived it
    if s["n_preemptions"] < 1:
        raise RuntimeError(
            f"{arch}: no preemption in {spec.n_requests} requests at "
            f"n_pages={CB_N_PAGES} — the pool is too big to exercise "
            f"page pressure")
    if s["goodput"] < CB_GOODPUT_MIN:
        raise RuntimeError(f"{arch}: continuous-batching goodput "
                           f"{s['goodput']:.3f} < {CB_GOODPUT_MIN}")
    # guard 16: the pool undercuts the static worst-case reservation
    pool_bytes = _cache_bytes(eng.cache, {"pk", "pv"})
    static_bytes = _cache_bytes(ref.cache, {"k", "v"})
    if not pool_bytes or not static_bytes or pool_bytes >= static_bytes:
        raise RuntimeError(
            f"{arch}: paged KV pool {pool_bytes}B >= static KV cache "
            f"{static_bytes}B — paging saved nothing")
    recompiles = _check_sentinel(eng, f"{arch}/continuous")  # guard 17

    return {
        "arch": cfg.name, "n_slots": N_SLOTS, "max_len": CB_MAX_LEN,
        "prefill_chunk": PREFILL_CHUNK,
        "page_size": CB_PAGE_SIZE, "n_pages": CB_N_PAGES,
        "workload": {"n_requests": spec.n_requests,
                     "arrival_rate": spec.arrival_rate,
                     "prompt_len": spec.prompt_len,
                     "gen_len": spec.gen_len, "dist": spec.dist,
                     "gen_dist": spec.gen_dist, "seed": spec.seed},
        "goodput": s["goodput"], "goodput_min": CB_GOODPUT_MIN,
        "n_preemptions": s["n_preemptions"],
        "page_alloc_failures": s["page_alloc_failures"],
        "pages_used_mean": s["pages_used_mean"],
        "pages_used_max": s["pages_used_max"],
        "pages_total": s["pages_total"],
        "pool_kv_bytes": pool_bytes,
        "static_kv_bytes": static_bytes,
        "pool_over_static": pool_bytes / static_bytes,
        "engine_ticks_paged": s["engine_ticks"],
        "engine_ticks_contiguous": ref_s["engine_ticks"],
        "tokens_per_step_paged": s["tokens_per_step"],
        "tokens_per_step_contiguous": ref_s["tokens_per_step"],
        "ttft_ticks_mean_paged": s["ttft_ticks_mean"],
        "ttft_ticks_mean_contiguous": ref_s["ttft_ticks_mean"],
        "recompile_counts": recompiles,
        "bitwise_paging": True,
        "pass": True,
    }


def run(smoke: bool = False, out: str = "BENCH_serve_engine.json",
        trace_out: str = "TRACE_serve_chaos.jsonl",
        restart_trace_out: str = "TRACE_serve_restart.jsonl",
        restart_journal_out: str = "JOURNAL_serve_restart.jsonl",
        cb_n_requests: int = 0):
    # smoke covers BOTH archs: mamba2's parallel-prefill traffic contract
    # (guard 4) is a CI guard, not a local-only measurement
    archs = ARCHS
    rows, records = [], {}
    for arch in archs:
        r = bench_arch(arch)
        records[r["arch"]] = r
        extra = ""
        if "parallel_over_exact_weight_ratio" in r:
            extra = (f"  parallel/exact wB/ptok "
                     f"{r['parallel_over_exact_weight_ratio']:.3f}x "
                     f"max|dlogit| {r['parallel_max_abs_dlogits']:.3f}")
        rows.append((
            f"serve_engine.{r['arch']}", 0.0,
            f"tok/step chunked={r['tokens_per_step_chunked']:.3f} "
            f"full={r['tokens_per_step_full']:.3f}  "
            f"ttft_ticks {r['ttft_ticks_mean_chunked']:.1f} vs "
            f"{r['ttft_ticks_mean_full']:.1f}{extra}"))
    sched = bench_schedule()
    rows.append((
        "serve_engine.schedule.bimodal", 0.0,
        f"ttft_ticks fifo={sched['fifo']['ttft_ticks_mean']:.2f} "
        f"spf={sched['spf']['ttft_ticks_mean']:.2f} "
        f"max_skips={sched['spf']['max_skips']}/{SPF_AGE_CAP}"))
    chaos = bench_chaos(trace_out=trace_out)
    rows.append((
        "serve_engine.chaos", 0.0,
        f"goodput={chaos['goodput']:.2f} (min {CHAOS_GOODPUT_MIN}) "
        f"faults={chaos['faults_detected']} replays={chaos['replays']} "
        f"bitwise_recovery={chaos['bitwise_recovery']} "
        f"traced_zero_overhead={chaos['zero_overhead_traced']}"))
    # kill-chaos restart on both smoke archs (attention + exact SSM);
    # artifacts come from the attention run
    restart = {}
    for arch in ("tinyllama-1.1b", "mamba2-1.3b"):
        first = arch == "tinyllama-1.1b"
        r = bench_restart(
            arch,
            trace_out=restart_trace_out if first else "",
            journal_out=restart_journal_out if first else "")
        restart[r["arch"]] = r
        rows.append((
            f"serve_engine.restart.{r['arch']}", 0.0,
            f"crashes={r['n_crashes']}@{r['crash_ticks']} "
            f"replayed_prefill_tokens={r['replayed_prefill_tokens']} "
            f"(cadence {r['snapshot_every']}) "
            f"bitwise_restart={r['bitwise_restart']} "
            f"durability_passive={r['durability_passive']}"))
    cb = bench_continuous_batching(n_requests=cb_n_requests)
    rows.append((
        "serve_engine.continuous", 0.0,
        f"n_requests={cb['workload']['n_requests']} "
        f"goodput={cb['goodput']:.2f} preemptions={cb['n_preemptions']} "
        f"pool/static={cb['pool_over_static']:.2f} "
        f"pages_used_max={cb['pages_used_max']}/{cb['pages_total']} "
        f"bitwise_paging={cb['bitwise_paging']}"))
    emit(rows)
    payload = {"smoke": smoke, "archs": records, "schedule": sched,
               "chaos": chaos, "restart": restart, "continuous": cb,
               "pass": all(r["pass"] for r in records.values())
               and sched["pass"] and chaos["pass"] and cb["pass"]
               and all(r["pass"] for r in restart.values())}
    if out:
        with open(out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"[serve_engine_bench] wrote {out}")
    failures = [a for a, r in records.items() if not r["pass"]]
    if failures:
        raise RuntimeError(
            f"chunked prefill served fewer tokens/step than the "
            f"full-forward baseline for {failures} — see {out}")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI engine-path guard (same archs, marks the "
                         "JSON as a smoke artifact)")
    ap.add_argument("--out", default="BENCH_serve_engine.json")
    ap.add_argument("--trace-out", default="TRACE_serve_chaos.jsonl",
                    help="chaos-case trace artifact (JSONL; '' disables)")
    ap.add_argument("--restart-trace-out",
                    default="TRACE_serve_restart.jsonl",
                    help="restart-case trace artifact spanning the "
                         "kill/restore chain (JSONL; '' disables)")
    ap.add_argument("--restart-journal-out",
                    default="JOURNAL_serve_restart.jsonl",
                    help="restart-case recovered write-ahead journal "
                         "artifact ('' disables)")
    ap.add_argument("--n-requests", type=int, default=0,
                    help="continuous-batching case request count "
                         "(0 = the spec default, >= 1000)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(smoke=args.smoke, out=args.out, trace_out=args.trace_out,
        restart_trace_out=args.restart_trace_out,
        restart_journal_out=args.restart_journal_out,
        cb_n_requests=args.n_requests)
