"""Serving CLI — a thin shell over serving.engine.ServeEngine.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --dbpim-mode joint --prefill-chunk 16

The engine runs an admission queue over a static batch of ``--batch``
cache slots (QUEUED -> PREFILLING -> DECODING -> DONE), with chunked
cache-filling prefill interleaved between decode steps. ``--paged``
switches the KV cache from per-slot worst-case strips to a shared pool
of fixed-size pages (``--n-pages`` x ``--page-size`` tokens): slots
borrow pages as their sequences grow, admission is gated on free pages,
and under oversubscription the youngest request is preempted (pages
released, later re-admitted head-of-line and resumed bitwise from its
journaled record). Decode outputs are bitwise identical to the
contiguous cache; SSM/conv states stay slot-resident. A new request's
prompt advances ``--prefill-chunk`` tokens per device call while
in-flight requests keep emitting a token every tick. All steps are
fixed-shape and compiled once — no recompilation per request.

``--dbpim-mode joint`` packs every layer's projections into the
uniform-MAXB joint-sparse stacked layout once at startup and threads
them through BOTH the decode scan and the prefill chunks — the whole
network serves off the DB-PIM kernel ((1 - value_sparsity) * 0.5 of
dense bf16 weight traffic). ``--dbpim-mode value`` serves the bf16-
payload variant of the same layout ((1 - vs), value level only).

SSM prefill chunks default to the parallel SSD form — one read of the
stacked in/out projections per chunk instead of per token
(models.ssm.prefill_ssm_parallel; tolerance-equivalent to decode) —
``--prefill-exact`` restores the bit-identical per-token recurrence.
``--schedule spf`` admits shortest-prompt-first (starvation bounded by
``--spf-age-cap``) instead of FIFO.

Load is a deterministic trace (serving.workload): Poisson arrivals at
``--arrival-rate`` requests/tick, prompt lengths from ``--prompt-len LO
HI`` under ``--dist``, fixed ``--seed`` — no wall-clock in the trace.

Fault tolerance / SLO (serving.faults, serving.engine): ``--fault-rate
R`` injects a seeded fault schedule (step exceptions, NaN logits,
corrupted slot caches) — faulted slots quarantine and recover by
replaying their durable record, bitwise on exact prefill paths.
``--deadline-slack K`` gives every request the SLO ``arrival + K``
ticks; requests that can no longer meet it are shed (recorded, never
raised), and ``--queue-cap`` bounds the admission queue with explicit
load-shedding. ``--strict-admission`` restores the hard ValueError on
oversized requests instead of a recorded rejection.

A request that is not served (rejected, shed, or lost to a fault) makes
the CLI exit non-zero unless ``--fault-rate``, ``--deadline-slack`` or
``--queue-cap`` was given: without them, a lost request is a failure,
not a policy.

Observability: ``--trace-out trace.jsonl`` records the full two-clock
span/event stream (repro.obs.Tracer) plus the per-call-kind weight
waterfall and dumps it as JSONL — render with ``python -m
repro.launch.report trace.jsonl`` or convert for Perfetto with
``--chrome``. Tracing is passive: outputs and device-call count are
bitwise identical to an untraced run.

Durability (serving.journal, serving.snapshot): ``--journal PATH``
appends a CRC-framed write-ahead record of every request transition
(fsync'd once per tick); ``--snapshot-dir DIR --snapshot-every N``
writes an atomic engine snapshot every N ticks. After a crash,
``--restore`` (with the same journal/snapshot flags) rebuilds the
engine from the latest snapshot + journal tail and resumes every
stream bitwise where the dead process left off (``--prefill-exact``
required for bitwise SSM restarts). Both layers are passive: with
them on, outputs and device-call count are identical to a bare run.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import init_params
from repro.models.transformer import encode
from repro.serving import ServeEngine, WorkloadSpec, make_trace


def _spec_from(args) -> WorkloadSpec:
    return WorkloadSpec(n_requests=args.requests,
                        arrival_rate=args.arrival_rate,
                        prompt_len=tuple(args.prompt_len),
                        gen_len=(args.gen_len, args.gen_len),
                        dist=args.dist,
                        gen_dist=getattr(args, "gen_dist", "uniform"),
                        seed=args.seed,
                        deadline_slack=getattr(args, "deadline_slack",
                                               None))


def build_engine_and_trace(args, cfg):
    """Shared by the CLI and benchmarks: engine + trace from parsed args.
    Prints the set-up seconds: random weights on the device ("init") and
    stacked-table packing with stripping ("pack")."""
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_params(cfg, jax.random.PRNGKey(args.seed)))
    t_init = time.perf_counter() - t0
    t0 = time.perf_counter()

    stacked_tables = None
    if cfg.dbpim and cfg.dbpim_mode != "dense":
        from repro.sparsity.sparse_linear import (build_stacked_tables,
                                                  strip_packed_projections)
        stacked_tables = build_stacked_tables(
            params, cfg, value_sparsity=args.value_sparsity)
        if stacked_tables is None:
            print(f"[serve] {cfg.name}: no stacked path for this "
                  f"family/mode; serving dense")
        else:
            # the packed tables now serve these matmuls — drop the dense
            # copies so serving HBM shrinks instead of doubling
            params = strip_packed_projections(params, cfg)
            nbytes = sum(int(a.size * a.dtype.itemsize)
                         for t in stacked_tables.arrays.values()
                         for a in t.values())
            print(f"[serve] dbpim_mode={cfg.dbpim_mode}: "
                  f"{len(stacked_tables.arrays)} projection families "
                  f"packed, {nbytes/1e6:.2f} MB stacked tables "
                  f"(dense copies stripped)")
    jax.block_until_ready((params, stacked_tables))
    print(f"[serve] set-up: init {t_init:.2f} s, pack "
          f"{time.perf_counter() - t0:.2f} s")

    enc_out = None
    if cfg.is_encdec:
        rng = np.random.default_rng(args.seed)
        frames = jnp.asarray(rng.normal(
            0, 1, (args.batch, cfg.encoder_seq, cfg.d_model)), jnp.bfloat16)
        enc_out = encode(params, frames, cfg)

    fault_plan = None
    if getattr(args, "fault_rate", 0.0) > 0:
        from repro.serving import FaultPlan
        fault_plan = FaultPlan.generate(
            seed=args.fault_seed, n_ticks=args.fault_ticks,
            rate=args.fault_rate, n_slots=args.batch)
        print(f"[serve] fault plan: {len(fault_plan.events)} events over "
              f"{args.fault_ticks} ticks (seed={args.fault_seed}, "
              f"rate={args.fault_rate})")

    tracer = None
    if getattr(args, "trace_out", None):
        from repro.obs import Tracer
        # path= makes EngineStuckError dump the trace pre-raise, so a
        # wedged run is diagnosable after the process is gone
        tracer = Tracer(arch=cfg.name, meta={
            "n_slots": args.batch, "prefill_chunk": args.prefill_chunk,
            "schedule": args.schedule, "seed": args.seed},
            path=args.trace_out)

    if getattr(args, "restore", False):
        if not getattr(args, "snapshot_dir", None):
            raise SystemExit("--restore requires --snapshot-dir")
        engine = ServeEngine.restore(
            cfg, params, snapshot_dir=args.snapshot_dir,
            journal_path=getattr(args, "journal", None),
            stacked_tables=stacked_tables, enc_out=enc_out,
            fault_plan=fault_plan, tracer=tracer)
        print(f"[serve] restored from snapshot step "
              f"{engine.restore_stats['from_step']}: "
              f"{engine.restore_stats}")
        return engine, make_trace(
            _spec_from(args), cfg.vocab_size)

    engine = ServeEngine(cfg, params, n_slots=args.batch,
                         max_len=args.max_len,
                         prefill_chunk=args.prefill_chunk,
                         prefill_mode=args.prefill_mode,
                         schedule=args.schedule,
                         spf_age_cap=args.spf_age_cap,
                         stacked_tables=stacked_tables, enc_out=enc_out,
                         strict=getattr(args, "strict_admission", False),
                         queue_cap=getattr(args, "queue_cap", None),
                         fault_plan=fault_plan,
                         max_step_retries=getattr(args, "max_step_retries",
                                                  2),
                         max_replays=getattr(args, "max_replays", 3),
                         tracer=tracer,
                         paged=getattr(args, "paged", False),
                         page_size=getattr(args, "page_size", 16),
                         n_pages=getattr(args, "n_pages", None),
                         journal=getattr(args, "journal", None),
                         snapshot_dir=getattr(args, "snapshot_dir", None),
                         snapshot_every=getattr(args, "snapshot_every", 0),
                         snapshot_keep=getattr(args, "snapshot_keep", 2))
    return engine, make_trace(_spec_from(args), cfg.vocab_size)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="engine slots (static decode batch)")
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="prompt tokens per chunked-prefill device call")
    ap.add_argument("--prefill-mode", default="chunked",
                    choices=["chunked", "full"],
                    help="'full' = token-by-token baseline prefill")
    ap.add_argument("--prefill-exact", action="store_true",
                    help="SSM chunks: force the exact per-token recurrence "
                         "(bit-identical to decode, C x the projection "
                         "traffic) instead of the default parallel SSD "
                         "form (one stacked-weight read per chunk, "
                         "tolerance-equivalent)")
    ap.add_argument("--schedule", default="fifo", choices=["fifo", "spf"],
                    help="admission order: fifo, or shortest-prompt-first "
                         "(spf; starvation bounded by --spf-age-cap)")
    ap.add_argument("--spf-age-cap", type=int, default=8,
                    help="spf: max times a request may be queue-jumped "
                         "before it becomes urgent")
    ap.add_argument("--prompt-len", type=int, nargs=2, default=[4, 24],
                    metavar=("LO", "HI"))
    ap.add_argument("--deadline-slack", type=float, default=None,
                    help="SLO: every request must complete within this "
                         "many ticks of its arrival or be shed (recorded "
                         "in metrics, never raised); default: no SLO")
    ap.add_argument("--queue-cap", type=int, default=None,
                    help="bounded admission queue: submissions beyond the "
                         "cap are rejected (recorded load-shedding)")
    ap.add_argument("--strict-admission", action="store_true",
                    help="raise ValueError on oversized requests instead "
                         "of recording a rejection")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="inject a deterministic fault schedule: per-tick "
                         "probability of one fault (step exception, NaN "
                         "logits, or corrupted slot cache); faulted slots "
                         "quarantine and recover by replay")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the injected fault schedule")
    ap.add_argument("--fault-ticks", type=int, default=1000,
                    help="horizon (ticks) the fault schedule covers")
    ap.add_argument("--max-step-retries", type=int, default=2,
                    help="bounded retry of a failed device call before "
                         "every participating slot quarantines")
    ap.add_argument("--max-replays", type=int, default=3,
                    help="per-request fault budget: past it the request "
                         "is shed instead of replayed again")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="Poisson arrivals per engine tick (0 = all at t0)")
    ap.add_argument("--dist", default="uniform",
                    choices=["uniform", "bimodal", "fixed", "lognormal",
                             "zipf"],
                    help="prompt-length distribution; lognormal/zipf give "
                         "the long-tail mixes that make paged pools win")
    ap.add_argument("--gen-dist", default="uniform",
                    choices=["uniform", "bimodal", "fixed", "lognormal",
                             "zipf"],
                    help="generation-length distribution over --gen-len")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache + continuous batching: slots "
                         "borrow fixed-size pages from a shared pool "
                         "(admission gated on free pages, decode bitwise "
                         "the contiguous path)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (must divide --max-len)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="pool size in pages; < batch * max_len/page_size "
                         "oversubscribes (page pressure preempts the "
                         "youngest request, bitwise resume later); "
                         "default: full static capacity")
    ap.add_argument("--dbpim-mode", default=None,
                    choices=["dense", "value", "bit", "joint"],
                    help="serve through the DB-PIM kernel path (joint = "
                         "value x bit sparse, the paper's headline config)")
    ap.add_argument("--value-sparsity", type=float, default=None,
                    help="tile-granular value sparsity for --dbpim-mode "
                         "joint/value (default: cfg.dbpim_value_sparsity)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="dump the structured two-clock trace (spans, "
                         "events, slot intervals, weight waterfall) as "
                         "JSONL; render with python -m repro.launch.report")
    ap.add_argument("--journal", default=None, metavar="PATH",
                    help="write-ahead request journal (CRC-framed JSONL, "
                         "fsync'd once per tick); with --restore, the "
                         "journal to fold over the snapshot")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="directory for periodic atomic engine snapshots "
                         "(cache + state machine + queue + metrics)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot cadence in ticks (0 = never); bounds "
                         "post-crash redo work to this many tokens per "
                         "active slot")
    ap.add_argument("--snapshot-keep", type=int, default=2,
                    help="published snapshots retained on disk")
    ap.add_argument("--restore", action="store_true",
                    help="warm-restart: rebuild from the latest snapshot "
                         "in --snapshot-dir plus the --journal tail and "
                         "resume every stream bitwise (skips submission "
                         "— the trace is already in the journal)")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced,
                     dbpim_mode=args.dbpim_mode,
                     prefill_exact=args.prefill_exact or None)
    engine, trace = build_engine_and_trace(args, cfg)
    if engine.prefill_mode != args.prefill_mode:
        print(f"[serve] {cfg.name}: chunked prefill unsupported for this "
              f"family; falling back to stepwise (full) prefill")
    if engine.prefill_kind is not None:
        print(f"[serve] prefill chunk math: {engine.prefill_kind} "
              f"(schedule={engine.schedule})")

    outputs = engine.resume() if args.restore else engine.run(trace)
    s = engine.metrics.summary()
    print(f"[serve] {s['n_completed']}/{s['n_requests']} requests, "
          f"{s['generated_tokens']} tokens in {s['engine_ticks']} ticks / "
          f"{s['device_calls']} device calls "
          f"({s['decode_calls']} decode + {s['prefill_calls']} prefill)")
    ttft = (f"mean={s['ttft_ticks_mean']:.1f} p95={s['ttft_ticks_p95']}"
            if s["ttft_ticks_mean"] is not None else "n/a")
    print(f"[serve] tokens/step={s['tokens_per_step']:.3f}  "
          f"ttft_ticks {ttft}  queue_depth "
          f"mean={s['queue_depth_mean']:.2f} max={s['queue_depth_max']}")
    if s["tokens_per_sec"]:
        print(f"[serve] wall {s['wall_s']:.2f}s  "
              f"{s['tokens_per_sec']:.1f} tok/s  "
              f"{s['per_token_latency_ms']:.2f} ms/token")
    if s["n_faults"] or s["n_rejected"] or s["n_shed"]:
        print(f"[serve] goodput {s['goodput']:.2f}  faults {s['faults']}  "
              f"retries {s['retries']}  replays {s['replays']}  "
              f"rejected {s['n_rejected']}  shed {s['n_shed']}")
    if engine.paged:
        pu = (f"{s['pages_used_mean']:.2f}"
              if s["pages_used_mean"] is not None else "n/a")
        print(f"[serve] page pool: {engine.n_pages} x "
              f"{engine.page_size}-token pages  "
              f"used mean={pu} max={s['pages_used_max']}  "
              f"preemptions {s['n_preemptions']}  "
              f"alloc_failures {s['page_alloc_failures']}")
    if s["slot_busy_frac"] is not None:
        print(f"[serve] slot_busy_frac {s['slot_busy_frac']:.2f}  "
              f"per-slot "
              f"{[round(o, 2) for o in s['slot_occupancy']]}")
    for kind, h in s["call_latency_ms"].items():
        print(f"[serve] latency {kind}: p50={h['p50_ms']:.2f} "
              f"p95={h['p95_ms']:.2f} p99={h['p99_ms']:.2f} ms "
              f"({h['count']} calls)")
    if engine.sentinel is not None:
        print(f"[serve] recompile sentinel: {engine.sentinel.counts()}")
    if engine.tracer is not None:
        from repro.obs import engine_waterfall
        for kind, wf in engine_waterfall(engine).items():
            engine.tracer.waterfall(kind, wf["rows"], wf["total"])
        engine.tracer.dump(args.trace_out)
        print(f"[serve] trace: {len(engine.tracer.records)} records -> "
              f"{args.trace_out} (render: python -m repro.launch.report "
              f"{args.trace_out})")
    for rid in sorted(outputs):
        print(f"  req{rid}: {outputs[rid][:8]}...")
    lost = s["n_requests"] - s["n_completed"]
    may_lose = (args.fault_rate > 0 or args.deadline_slack is not None
                or args.queue_cap is not None)
    if lost and not may_lose:
        raise SystemExit(
            f"[serve] {lost}/{s['n_requests']} requests not served "
            f"(rejected {s['n_rejected']}, shed {s['n_shed']}, faults "
            f"{s['faults']}) and no --fault-rate, --deadline-slack or "
            f"--queue-cap allows losing any")
    return outputs


if __name__ == "__main__":
    main()
