"""Request-level serving engine: admission queue + per-slot state machine
+ fixed-shape jitted steps.

The engine owns a static batch of ``n_slots`` cache slots. Each request
moves through

    QUEUED -> PREFILLING -> DECODING -> DONE

with all scheduling host-side and all math in exactly TWO compiled
executables (three with slot reset), fixed-shape so NO recompilation ever
happens per request:

  * decode step   (B, 1) tokens + (B,) active mask
    (launch.steps.build_step("decode") — inactive slots' K/V writes are
    dropped in the step, their position and SSM state updates discarded
    by models.decode.merge_slots);
  * prefill chunk (B, C) tokens + (B,) n_valid
    (serving.prefill.build_chunk_step — only in "chunked" mode);
  * slot reset — zeroes a freed slot's KV/SSM cache slices and position
    before admission (models.decode.reset_slots), so a refilled slot is
    indistinguishable from a fresh batch.

One engine TICK = admit -> (prefill chunk, if any slot is prefilling) ->
(decode step, if any slot is decoding). Prefill and decode are separate
device calls, so prefilling a newly admitted request NEVER stalls
in-flight decodes — decoding slots emit a token every tick regardless of
arrivals. In "full" prefill mode (the baseline), prompt tokens instead
ride the decode call one at a time.

Admission order (``schedule``):

  * "fifo" (default) — strictly arrival order from one queue;
  * "spf" — shortest-prompt-first among ARRIVED requests: under mixed
    (bimodal) loads, short prompts stop queueing behind long prefills
    and mean TTFT drops. Starvation is bounded by ``spf_age_cap``:
    every shortest-first admission raises the skip count of every other
    arrived request it passed over; at the cap a request becomes urgent
    and is admitted before any non-urgent request (oldest-arrival
    first; urgent admissions are forced fairness, not jumps, and raise
    no counts). A non-urgent pick only happens when NOBODY is urgent,
    so skips <= spf_age_cap is a hard bound — no request is ever passed
    over by shortest-first picks more than ``spf_age_cap`` times, even
    when every request arrives at once — the invariant
    tests/test_serving_engine.py holds the scheduler to. Admission is
    O(arrived): the queue is arrival-sorted, so the arrived set is a
    prefix, picks are index-based deque deletes within it, and a
    request's skip entry is dropped the moment it is admitted (the
    final count lands in metrics.requests[rid].skips).

Per-slot cache positions: cache["pos"] is a (B,) vector — slots hold
requests at different depths, which is what the vectorized
decode_attention / decode_chunk paths exist for.

Fault tolerance — the contract is **blast radius <= one tick, recovery
bitwise-verifiable** (serving.faults is the injection harness that
holds the engine to it; runtime.fault plays the same role for the
training loop at checkpoint granularity):

  * DETECTION — every device call runs under bounded retry
    (``max_step_retries``); after the call, a finite-guard checks each
    PARTICIPATING slot's logits row and fails only the offending slot
    (non-finite logits are also how corrupted cache state surfaces —
    NaN poison propagates to the slot's next logits, and only that
    slot's, because the batch math is per-slot independent).
  * CONTAINMENT — a faulted slot is QUARANTINED: its tick's token is
    discarded, its cache slices are zeroed, and no other slot's stream
    is touched. If a device call stays down past the retry budget,
    every slot in that call quarantines — still one tick of blast
    radius, per slot.
  * RECOVERY-BY-REPLAY — the quarantined slot re-prefills from its
    durable record (original prompt + tokens emitted so far). Chunked
    prefill is bit-identical to sequential decode (the PR 3 invariant),
    so the replayed cache — and every token after it — is BITWISE what
    a fault-free run would have produced; the chaos benchmark asserts
    exactly that. (On the SSM parallel-SSD prefill path the replay is
    tolerance-equal like any other chunk; serve with
    ``cfg.prefill_exact`` where bitwise recovery must hold.) A request
    that faults more than ``max_replays`` times is shed
    ("fault_budget") instead of livelocking — a deterministically-NaN
    model converges to shedding, never to an infinite replay loop.
  * SLO SHEDDING — requests carry an optional ``deadline`` tick. A
    bounded queue (``queue_cap``) rejects at submit, hopeless queued
    requests (optimistic completion estimate past the deadline) are
    shed before ever taking a slot, and in-flight requests are
    preempted the tick their deadline becomes unreachable. All of it is
    RECORDED (metrics.on_reject / on_shed), never raised mid-trace.
  * A zero-fault plan is free: no extra device calls, bitwise-identical
    outputs (the chaos bench's no-overhead guard).

Observability (repro.obs) — all of it PASSIVE; with ``tracer=None``
(default) outputs and device-call count are bitwise identical to a
traced run (the zero-overhead contract the chaos bench guards):

  * ``tracer=Tracer()`` records two-clock spans that split each tick
    by host phase: "tick", and inside it "schedule" (fault injection,
    shedding, admission, page growth), one "call" per device call
    (call_kind/arch/occupancy/replay attrs, plus rows/rows_valid on
    prefill chunks and slots_written on decode steps; an SSM model's
    calls also carry state_slots, its "schedule" state_resets) running
    from input assembly until the logits are on the host, its child
    "logits" (the wait for the step and the
    device-to-host copy), one "sample" per call (argmax, finite guard,
    slot updates), and "commit" (journal commit, snapshot). It also
    records slot lifecycle events (submit / admit / prefill /
    first_token / quarantine / replay / preempt / shed / reject /
    release / fault / retry) and the closed SlotIntervals — JSONL via
    tracer.dump, Chrome trace via obs.chrome, rendered by ``python -m
    repro.launch.report``. Each span is also a profiler annotation
    (``engine.<name>``), so a device trace shows what the host was
    doing in each idle gap.
  * the RECOMPILE SENTINEL (on by default) registers every jitted step
    under its (call_kind, arch) key and raises obs.RecompileError the
    tick any of them compiles more than once — the fixed-shape
    no-recompile contract above, enforced instead of assumed.
  * every device call's wall latency, from dispatch until its logits
    are on the host, feeds a log-bucketed per-kind histogram
    (metrics.summary()["call_latency_ms"]: p50/p95/p99 without storing
    raw samples).

Durability (serving.journal + serving.snapshot) — crash-safe serving,
PASSIVE like the tracer (``journal=None`` is bitwise/count-identical):

  * ``journal=<path>`` appends a CRC-framed record for every
    request-visible transition (submit/admit/token/done/shed/reject),
    fsync'd ONCE per tick; ``snapshot_dir`` + ``snapshot_every`` write
    periodic atomic snapshots (cache + state machine + queue + metrics)
    via the checkpoint layer's tmp-dir + fsync + os.replace publish.
  * ``ServeEngine.restore(cfg, params, snapshot_dir=...,
    journal_path=...)`` rebuilds from the latest snapshot, folds the
    journal tail over it, and re-prefills each active slot's durable
    record through the PR 7 replay path — then ``resume()`` continues
    the streams BITWISE where the dead process left off (the chunk ==
    decode invariant again; ``cfg.prefill_exact`` for SSM parallel
    prefill). Redone work is bounded by snapshot cadence: at most
    ``snapshot_every`` journal-evidenced tokens per active slot
    (restore_stats["replayed_prefill_tokens"], metered under
    "<kind>+restore").
  * the kill-chaos harness: a FaultPlan ``engine_crash`` event kills
    the engine (EngineCrash) between ticks after the journal commit;
    benchmarks/serve_engine_bench.py's restart case kills/restores at
    seeded ticks and guards stream equality + the replay bound.
"""

from __future__ import annotations

import enum
import math
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_test_mesh
from repro.launch.steps import build_step
from repro.models import init_cache, reset_slots
from repro.obs import RecompileSentinel, Tracer
from repro.runtime import sharding as shr
from repro.serving.faults import EngineCrash, FaultPlan, corrupt_cache
from repro.serving.journal import Journal
from repro.serving.metrics import MetricsRecorder
from repro.serving.paging import PageAllocator
from repro.serving.prefill import (PREFILL_MODES, assemble_chunk,
                                   build_chunk_step)
from repro.serving.workload import Request


class SlotState(enum.Enum):
    FREE = "free"
    PREFILLING = "prefilling"
    DECODING = "decoding"


@dataclass
class _Slot:
    state: SlotState = SlotState.FREE
    rid: Optional[int] = None
    prompt: Optional[np.ndarray] = None  # current prefill target (replay
    #                                      record after a fault)
    durable: Optional[np.ndarray] = None  # original prompt, never mutated
    cursor: int = 0                      # prompt tokens already in cache
    gen_len: int = 0
    pending_token: int = 0               # next decode input
    deadline: Optional[float] = None
    fault_count: int = 0                 # quarantines charged to this slot
    replay: bool = False                 # prefilling a post-fault record
    #                                      (suppress first-token metrics)
    restore: bool = False                # prefilling a warm-restart record
    #                                      (meter calls under "+restore")
    admit_seq: int = -1                  # monotonic admission order —
    #                                      page-pressure preemption picks
    #                                      the YOUNGEST victim by this


@dataclass
class _Preempted:
    """A request evicted from its slot under page pressure, waiting to
    re-enter. Its emitted tokens stay in ``engine.outputs`` — on
    re-admission the replay record is ``durable + outputs[rid]``, so the
    resumed stream continues BITWISE (the same chunk == decode invariant
    fault recovery and warm restart lean on)."""
    rid: int
    durable: np.ndarray
    gen_len: int
    deadline: Optional[float]
    fault_count: int


@dataclass
class SlotInterval:
    """Audit record: slot s served rid from admit_tick until release_tick
    (exclusive). Tests verify intervals on one slot never overlap."""
    slot: int
    rid: int
    admit_tick: int
    release_tick: Optional[int] = None


class EngineStuckError(RuntimeError):
    """max_ticks exceeded — the scheduler wedged. Carries everything a
    post-mortem needs: completed outputs so far, the slot audit log, the
    metrics summary (the bare RuntimeError used to discard all three),
    and — when the engine was configured with a journal / a tracer that
    knows its dump path — the ON-DISK artifact paths, committed/dumped
    before the raise so the hang is diagnosable after the process is
    gone."""

    def __init__(self, msg: str, *, outputs: Dict[int, List[int]],
                 slot_log: List[SlotInterval], summary: dict,
                 journal_path: Optional[str] = None,
                 trace_path: Optional[str] = None):
        super().__init__(msg)
        self.outputs = outputs
        self.slot_log = slot_log
        self.summary = summary
        self.journal_path = journal_path
        self.trace_path = trace_path


class ServeEngine:
    """See module docstring. Typical use:

        engine = ServeEngine(cfg, params, n_slots=4, max_len=64,
                             prefill_chunk=16, stacked_tables=tables)
        results = engine.run(make_trace(spec, cfg.vocab_size))
        print(engine.metrics.summary())
    """

    SCHEDULES = ("fifo", "spf")

    def __init__(self, cfg, params, *, mesh=None, n_slots: int = 4,
                 max_len: int = 64, prefill_chunk: int = 16,
                 prefill_mode: str = "chunked", schedule: str = "fifo",
                 spf_age_cap: int = 8, stacked_tables=None,
                 enc_out=None, max_ticks: int = 100_000,
                 strict: bool = False, queue_cap: Optional[int] = None,
                 fault_plan: Optional[FaultPlan] = None,
                 max_step_retries: int = 2, max_replays: int = 3,
                 tracer: Optional[Tracer] = None,
                 recompile_sentinel: bool = True,
                 journal=None, snapshot_dir: Optional[str] = None,
                 snapshot_every: int = 0, snapshot_keep: int = 2,
                 paged: bool = False, page_size: int = 16,
                 n_pages: Optional[int] = None):
        if prefill_mode not in PREFILL_MODES:
            raise ValueError(f"prefill_mode {prefill_mode!r} not in "
                             f"{PREFILL_MODES}")
        if schedule not in self.SCHEDULES:
            raise ValueError(f"schedule {schedule!r} not in "
                             f"{self.SCHEDULES}")
        if prefill_mode == "chunked" and \
                not cfg.serving_capabilities().chunked_prefill:
            # sliding-window families only: the ring cache needs stepwise
            # writes — every other family (MoE, hybrid, enc-dec included)
            # chunk-prefills through the segmented decode_chunk path
            prefill_mode = "full"
        self.cfg = cfg
        self.mesh = mesh or make_test_mesh()
        self.n_slots = n_slots
        self.max_len = max_len
        # -- paged cache (continuous batching) ---------------------------
        # n_pages defaults to full static capacity (no oversubscription);
        # the interesting regime is n_pages < n_slots * max_len/page_size,
        # where admitted concurrency exceeds what worst-case contiguous
        # slots could back and page pressure drives preemption.
        self.paged = bool(paged)
        if self.paged:
            if max_len % page_size != 0:
                raise ValueError(
                    f"paged engine needs max_len % page_size == 0 "
                    f"(got {max_len} % {page_size}) — equality "
                    f"max_pages_per_slot * page_size == max_len is what "
                    f"makes paged decode bitwise the contiguous path")
            self.page_size = int(page_size)
            self.max_pages_per_slot = max_len // page_size
            self.n_pages = (int(n_pages) if n_pages is not None
                            else n_slots * self.max_pages_per_slot)
            self.page_alloc: Optional[PageAllocator] = PageAllocator(
                self.n_pages, n_slots, self.max_pages_per_slot,
                self.page_size)
        else:
            self.page_size = self.max_pages_per_slot = self.n_pages = 0
            self.page_alloc = None
        self._ptab_cached = None
        self._ptab_version = -1
        self.preempted: deque = deque()   # _Preempted, FIFO re-admission
        self._admit_seq = 0
        self.prefill_chunk = prefill_chunk
        self.prefill_mode = prefill_mode
        self.schedule = schedule
        self.spf_age_cap = spf_age_cap
        self.max_ticks = max_ticks
        self.strict = strict
        self.queue_cap = queue_cap
        self.fault_plan = fault_plan
        self.max_step_retries = max_step_retries
        self.max_replays = max_replays
        self.tracer = tracer
        # SSM conv/state per slot: counted on the tracer's spans
        self._ssm_state = any(seg.mixer == "ssm" for seg in
                              cfg.serving_capabilities().segments)
        # -- durability layer (all host-side: journaling/snapshotting
        # never issue device calls, so journal=None vs a live journal is
        # bitwise-output- and device-call-count-identical — the same
        # passivity contract the tracer carries) ------------------------
        if snapshot_every and not snapshot_dir:
            raise ValueError("snapshot_every set without snapshot_dir")
        self.journal: Optional[Journal] = (
            journal if isinstance(journal, Journal) or journal is None
            else Journal(str(journal)))
        self.snapshot_dir = snapshot_dir
        self.snapshot_every = snapshot_every
        self.snapshot_keep = snapshot_keep
        self.restore_stats: Optional[dict] = None

        self.params = params
        with self.mesh:
            cache = init_cache(
                cfg, n_slots, max_len, enc_out=enc_out,
                n_pages=self.n_pages if self.paged else None,
                page_size=self.page_size if self.paged else None)
            # per-slot positions from the start (merge_slots vectorizes
            # them anyway; starting scalar would recompile after tick 0)
            cache["pos"] = jnp.zeros((n_slots,), jnp.int32)
            if "attn" in cache and "pos" in cache["attn"]:
                cache["attn"]["pos"] = jnp.zeros((n_slots,), jnp.int32)
            self.cache = cache

            decode_fn, shard_fn = build_step(cfg, self.mesh, "decode",
                                             paged=self.paged)
            dec_args = (params, stacked_tables, cache,
                        jnp.zeros((n_slots, 1), jnp.int32),
                        jnp.zeros((n_slots,), bool))
            if self.paged:
                dec_args = dec_args + (jnp.full(
                    (n_slots, self.max_pages_per_slot), -1, jnp.int32),)
            dec_in = tuple(shr.named(s, self.mesh)
                           for s in shard_fn(*dec_args))
            cache_sh = dec_in[2]
            # the packed tables are step ARGUMENTS, placed once here: a
            # table closed over by the step would be baked into each
            # executable as a constant (and re-sent every call if left
            # on the host)
            self.stacked_tables = (None if stacked_tables is None else
                                   jax.device_put(stacked_tables, dec_in[1]))
            # COMMIT the fresh cache to its serving sharding up front:
            # otherwise the first jitted call returns committed outputs
            # whose signature differs from the uncommitted init arrays,
            # and reset/prefill each compile a second, steady-state
            # variant at tick 1 (the recompile sentinel caught this)
            self.cache = jax.device_put(self.cache, cache_sh)
            # kept for restore: a snapshot's host cache re-enters the
            # device under the exact serving sharding
            self._cache_sharding = cache_sh
            # out_shardings pin the returned cache to the SAME spec the
            # steps take it with: left to propagation, XLA hands attn
            # k/v back replicated, and every consumer (reset, prefill)
            # compiles a second steady-state variant at tick 1 — the
            # recompile sentinel caught this
            self._decode = jax.jit(
                decode_fn,
                in_shardings=dec_in,
                out_shardings=(None, cache_sh),
                donate_argnums=(2,))
            self._prefill = None
            if prefill_mode == "chunked":
                self._prefill = build_chunk_step(
                    cfg, self.mesh, params, stacked_tables, cache, n_slots,
                    prefill_chunk, paged=self.paged,
                    max_pages=self.max_pages_per_slot)
            if self.paged:
                self._reset = jax.jit(
                    lambda c, m, pt: reset_slots(c, m, cfg, ptab=pt),
                    out_shardings=cache_sh,
                    donate_argnums=(0,))
            else:
                self._reset = jax.jit(
                    lambda c, m: reset_slots(c, m, cfg),
                    out_shardings=cache_sh,
                    donate_argnums=(0,))

        # which chunk math this engine's prefill executable compiles to
        # ("prefill_parallel" / "prefill_chunk_exact"; None in "full" mode
        # where prompt tokens ride the decode call)
        self.prefill_kind = (self._prefill.call_kind
                             if self._prefill is not None else None)

        # the fixed-shape no-recompile contract, enforced: each jitted
        # step gets ONE compile; check() runs every tick (obs.sentinel)
        self.sentinel = None
        if recompile_sentinel:
            self.sentinel = RecompileSentinel()
            self.sentinel.register(RecompileSentinel.key("decode", cfg.name),
                                   self._decode)
            if self._prefill is not None:
                self.sentinel.register(
                    RecompileSentinel.key(self.prefill_kind, cfg.name),
                    self._prefill)
            self.sentinel.register(RecompileSentinel.key("reset", cfg.name),
                                   self._reset)

        self.queue: deque = deque()
        self.skips: Dict[int, int] = {}   # QUEUED rid -> times jumped (spf);
        #                                   entries die at admission
        self.slots = [_Slot() for _ in range(n_slots)]
        self.tick_count = 0
        self.outputs: Dict[int, List[int]] = {}
        self.first_logits: Dict[int, np.ndarray] = {}
        self.rejected: Dict[int, str] = {}   # rid -> rejection reason
        self.duplicate_rids: List[int] = []  # re-submitted rids (rejected
        #                                      without touching the
        #                                      original's row or outputs)
        self.slot_log: List[SlotInterval] = []
        self._open_interval: Dict[int, SlotInterval] = {}
        self._has_deadlines = False
        self.metrics = MetricsRecorder()

    # ------------------------------------------------------------------ API

    def submit(self, request: Request) -> bool:
        """Queue a request; returns False if it was REJECTED instead
        (oversized, the bounded queue is full, or the rid was already
        submitted — accepting a duplicate rid would silently merge two
        requests' token streams in ``self.outputs`` and corrupt journal
        keying). Rejections are recorded (metrics.on_reject,
        ``self.rejected`` / ``self.duplicate_rids``), never raised — one
        malformed request must not abort a whole trace. Construct the
        engine with ``strict=True`` to get the hard ValueError back
        (tests / offline traces). Submissions become DURABLE at the next
        journal commit (run() commits once after queueing a trace;
        direct submit() callers inherit the next tick's commit)."""
        if request.rid in self.metrics.requests:
            if self.strict:
                raise ValueError(
                    f"request {request.rid}: duplicate rid (already "
                    f"submitted)")
            return self._reject(request, "duplicate_rid")
        total = request.prompt_len + request.gen_len
        # capacity is PAGED capacity when paged: a slot can back at most
        # max_pages_per_slot * page_size tokens, and no request may need
        # more pages than the whole pool holds (otherwise admission
        # could never satisfy it and page-pressure preemption would
        # livelock trying)
        if self.paged:
            cap = self.max_pages_per_slot * self.page_size
            oversized = (total > cap or
                         self.page_alloc.pages_for(total) > self.n_pages)
        else:
            cap = self.max_len
            oversized = total > cap
        if oversized:
            if self.strict:
                raise ValueError(
                    f"request {request.rid}: prompt {request.prompt_len} + "
                    f"gen {request.gen_len} exceeds capacity {cap}"
                    + (f" (page pool {self.n_pages} pages)"
                       if self.paged else ""))
            return self._reject(request, "oversized")
        if self.queue_cap is not None and len(self.queue) >= self.queue_cap:
            return self._reject(request, "queue_full")
        self.queue.append(request)
        self.skips[request.rid] = 0
        if request.deadline is not None:
            self._has_deadlines = True
        self.metrics.on_submit(request.rid, request.prompt_len,
                               request.gen_len, request.arrival,
                               deadline=request.deadline)
        if self.tracer is not None:
            self.tracer.event("submit", self.tick_count, rid=request.rid)
        if self.journal is not None:
            self.journal.append(
                "submit", self.tick_count, rid=int(request.rid),
                prompt=[int(t) for t in request.prompt],
                gen_len=int(request.gen_len),
                arrival=float(request.arrival),
                deadline=(None if request.deadline is None
                          else float(request.deadline)))
        return True

    def _reject(self, request: Request, reason: str) -> bool:
        if reason == "duplicate_rid":
            # the rid's ORIGINAL request is live (or finished) — don't
            # let the duplicate's reason clobber its results entry
            self.duplicate_rids.append(request.rid)
        else:
            self.rejected[request.rid] = reason
        self.metrics.on_reject(request.rid, request.prompt_len,
                               request.gen_len, request.arrival, reason,
                               deadline=request.deadline)
        if self.journal is not None:
            self.journal.append(
                "reject", self.tick_count, rid=int(request.rid),
                reason=reason, prompt_len=int(request.prompt_len),
                gen_len=int(request.gen_len),
                arrival=float(request.arrival),
                deadline=(None if request.deadline is None
                          else float(request.deadline)))
        if self.tracer is not None:
            self.tracer.event("reject", self.tick_count, rid=request.rid,
                              reason=reason)
        return False

    def run(self, requests: List[Request]):
        """Serve a trace to completion; returns {rid: generated tokens}
        for every request that held a slot (rejected ones appear in
        ``self.rejected`` / metrics instead)."""
        for r in sorted(requests, key=lambda r: (r.arrival, r.rid)):
            self.submit(r)
        if self.journal is not None:
            self.journal.commit()   # the accepted trace is durable
            #                         before any serving work happens
        return self._serve_loop()

    def resume(self):
        """Continue serving after ``ServeEngine.restore`` — the same
        loop as run() without re-submitting anything (the queue and
        slots were rebuilt from the snapshot + journal tail; calling
        run() on a restored engine would just reject every request as
        ``duplicate_rid``)."""
        return self._serve_loop()

    def _serve_loop(self):
        self.metrics.start()
        while self.queue or self.preempted or \
                any(s.state is not SlotState.FREE for s in self.slots):
            self.tick()
            if self.fault_plan is not None and \
                    self.fault_plan.crash_at(self.tick_count - 1):
                # simulated process kill BETWEEN ticks: the completed
                # tick's journal batch is already committed (tick() ends
                # with the commit), so a restored engine resumes at
                # tick_count — strictly past the event, which therefore
                # never re-fires
                if self.tracer is not None:
                    self.tracer.event("crash", self.tick_count - 1)
                raise EngineCrash(
                    f"injected engine crash after tick "
                    f"{self.tick_count - 1}", tick=self.tick_count - 1)
            if self.tick_count > self.max_ticks:
                self._record_slot_log()
                self.metrics.stop()
                journal_path = trace_path = None
                if self.journal is not None:
                    self.journal.commit()
                    journal_path = self.journal.path
                if self.tracer is not None and self.tracer.path:
                    self.tracer.dump(self.tracer.path)
                    trace_path = self.tracer.path
                raise EngineStuckError(
                    f"engine exceeded max_ticks={self.max_ticks}; "
                    f"scheduler stuck?",
                    outputs=dict(self.outputs),
                    slot_log=list(self.slot_log),
                    summary=self.metrics.summary(),
                    journal_path=journal_path, trace_path=trace_path)
        self._record_slot_log()
        self.metrics.stop()
        if self.journal is not None:
            self.journal.commit()
        return self.outputs

    def _record_slot_log(self):
        """Hand the slot audit log to the recorder so summary() can
        aggregate slot_busy_frac / per-slot occupancy from it."""
        self.metrics.record_slot_log(
            [(iv.slot, iv.admit_tick, iv.release_tick)
             for iv in self.slot_log], self.n_slots)

    # ------------------------------------------------- durability layer

    def save_snapshot(self) -> str:
        """Write one atomic engine snapshot (serving.snapshot) — called
        automatically every ``snapshot_every`` ticks, or manually at any
        between-ticks point. Host-side only plus a device->host copy of
        the cache: no device calls, so snapshotting never perturbs the
        token streams."""
        from repro.serving.snapshot import save_snapshot
        path = save_snapshot(self)
        if self.tracer is not None:
            self.tracer.event("snapshot", self.tick_count,
                              step=self.tick_count, path=path)
        return path

    @classmethod
    def restore(cls, cfg, params, *, snapshot_dir: str,
                journal_path: Optional[str] = None,
                step: Optional[int] = None, mesh=None,
                stacked_tables=None, enc_out=None,
                fault_plan: Optional[FaultPlan] = None,
                tracer: Optional[Tracer] = None,
                recompile_sentinel: bool = True,
                journal_fsync: bool = True, **overrides) -> "ServeEngine":
        """Bring up a replacement engine from the latest (or ``step``)
        snapshot plus the journal tail — the warm-restart path after a
        crash (EngineCrash in tests/benches; a real kill in production).

        Geometry and policy knobs (n_slots, max_len, prefill_chunk,
        prefill_mode, schedule, ...) come from the snapshot manifest;
        ``overrides`` can replace the policy ones, but the cache
        geometry must match or restore refuses. The caller re-supplies
        what is NOT durable state: cfg/params/tables (weights are the
        training checkpoint's business, not the serving snapshot's) and
        runtime objects (fault_plan, tracer — pass the same tracer to
        span the restart in one trace).

        The journal is reopened in resume mode (torn tail truncated at
        the first bad frame) and further records append after the last
        good one. Call ``resume()`` on the returned engine to continue
        serving; every active slot finishes a chunked re-prefill of
        ``prompt + journaled tokens`` and the streams continue bitwise
        (cfg.prefill_exact where the SSM parallel path must be exact).
        ``restore_stats`` carries the replay-work accounting the
        kill-chaos bench bounds by snapshot cadence."""
        from repro.serving.snapshot import (read_snapshot_meta,
                                            restore_engine_state)
        step, extra = read_snapshot_meta(snapshot_dir, step)
        kw = {k: extra["engine"][k] for k in
              ("n_slots", "max_len", "prefill_chunk", "prefill_mode",
               "schedule", "spf_age_cap", "max_ticks", "strict",
               "queue_cap", "max_step_retries", "max_replays",
               "snapshot_every", "snapshot_keep")}
        # paged keys arrived with snapshot v2; .get keeps v1 restorable
        kw["paged"] = extra["engine"].get("paged", False)
        if kw["paged"]:
            kw["page_size"] = extra["engine"]["page_size"]
            kw["n_pages"] = extra["engine"]["n_pages"]
        kw.update(overrides)
        engine = cls(cfg, params, mesh=mesh, stacked_tables=stacked_tables,
                     enc_out=enc_out, fault_plan=fault_plan, tracer=tracer,
                     recompile_sentinel=recompile_sentinel,
                     journal=None, snapshot_dir=snapshot_dir, **kw)
        restore_engine_state(engine, snapshot_dir, step,
                             journal_path=journal_path,
                             journal_fsync=journal_fsync)
        return engine

    # ------------------------------------------------------------- one tick

    def tick(self):
        tick = self.tick_count
        tr = self.tracer
        span = tr.begin("tick", tick) if tr is not None else None
        sched = tr.begin("schedule", tick) if tr is not None else None
        calls = 0
        if self.fault_plan is not None:
            self._inject_cache_faults(tick)
        if self._has_deadlines:
            self._shed_hopeless_slots(tick)
        resets = self._admit(tick)
        if self.paged:
            # every occupied slot must own the pages this tick's writes
            # land in BEFORE the device calls go out; pressure resolves
            # by preempting the youngest-admitted slot
            self._page_growth(tick)
            self.page_alloc.check()
        if sched is not None:
            tr.end(sched, **({"state_resets": resets}
                             if self._ssm_state else {}))
        if self.prefill_mode == "chunked":
            calls += self._prefill_phase(tick)
        calls += self._decode_phase(tick)
        qd = len(self.queue)
        n_pre = sum(s.state is SlotState.PREFILLING for s in self.slots)
        n_dec = sum(s.state is SlotState.DECODING for s in self.slots)
        pages_used = pages_total = None
        if self.paged:
            pages_used = self.page_alloc.used_pages
            pages_total = self.n_pages
        self.metrics.on_tick(tick, queue_depth=qd, n_prefilling=n_pre,
                             n_decoding=n_dec, device_calls=calls,
                             pages_used=pages_used,
                             pages_total=pages_total)
        self.tick_count += 1
        snapshot = bool(self.snapshot_every) and \
            self.tick_count % self.snapshot_every == 0
        commit = (tr.begin("commit", tick) if tr is not None and
                  (self.journal is not None or snapshot) else None)
        if self.journal is not None:
            # ONE write + fsync for the whole tick's batch (admits,
            # tokens, terminal events) — durability costs one fsync per
            # tick however many requests moved; a kill can only lose
            # the current tick's uncommitted records, which restore
            # re-derives bitwise
            self.journal.commit()
        if self.sentinel is not None:
            self.sentinel.check()
        if snapshot:
            self.save_snapshot()
        if commit is not None:
            tr.end(commit)
        if span is not None:
            attrs = dict(queue_depth=qd, n_prefilling=n_pre,
                         n_decoding=n_dec, device_calls=calls)
            if self.paged:
                attrs.update(pages_used=pages_used,
                             pages_total=pages_total)
            tr.end(span, **attrs)

    # -------------------------------------------------------------- phases

    def _pop_next(self, tick: int, can_admit=None):
        """Next request to admit, or None. "fifo" pops the head once it
        has arrived. "spf" picks the shortest ARRIVED prompt — unless a
        request has already been passed over ``spf_age_cap`` times, in
        which case the oldest such urgent request goes first. Every
        NON-urgent (shortest-first) pick raises the skip count of every
        other arrived request; urgent picks raise none (forced fairness
        is not a jump). Since a non-urgent pick requires the urgent set
        to be empty, a request at the cap can never be incremented
        again: skips[rid] <= spf_age_cap always, and deferral is bounded
        even when all requests arrive simultaneously.

        The queue is arrival-sorted, so the arrived set is a PREFIX:
        one O(arrived) scan finds the pick's index and the deque delete
        shifts at most that prefix — no full-queue equality scan.

        ``can_admit(req) -> bool`` is the paged admission gate (enough
        free pages for the prompt). A gated-out pick stays at the head
        with NO side effects — no skip increments, no reorder: page
        waits are head-of-line blocking, not queue jumping, so FIFO
        order survives page pressure and the spf skip bound is
        unaffected by it."""
        arrived = []
        for i, r in enumerate(self.queue):
            if r.arrival > tick:
                break
            arrived.append((i, r))
        if not arrived:
            return None
        if self.schedule == "fifo":
            idx, req = arrived[0]
        else:
            urgent = [(i, r) for i, r in arrived
                      if self.skips[r.rid] >= self.spf_age_cap]
            if urgent:
                idx, req = urgent[0]      # oldest urgent arrival
            else:
                idx, req = min(arrived, key=lambda ir: (
                    ir[1].prompt_len, ir[1].arrival, ir[1].rid))
        if can_admit is not None and not can_admit(req):
            return None
        if self.schedule != "fifo" and not \
                (self.skips[req.rid] >= self.spf_age_cap):
            for _, r in arrived:
                if r is not req:
                    self.skips[r.rid] += 1
        del self.queue[idx]
        return req

    def _admit(self, tick: int):
        """QUEUED -> PREFILLING: pop arrived requests into free slots and
        ZERO the slots' stale cache slices (the previous occupant's
        KV/SSM state must not leak into the new request).

        Paged engines admit PREEMPTED requests first (FIFO — they are
        the oldest admitted work), then the queue, each gated on free
        pages for the full (re-)prefill record rather than merely a free
        slot. A gate miss is head-of-line blocking: nothing younger
        jumps it (jumping would re-trigger the very preemptions that
        freed the pages). Returns the number of slots reset."""
        if self._has_deadlines:
            self._shed_hopeless_queue(tick)
        mask = np.zeros((self.n_slots,), bool)
        for s, slot in enumerate(self.slots):
            if slot.state is not SlotState.FREE:
                continue
            if self.preempted:
                ent = self.preempted[0]
                emitted = self.outputs.get(ent.rid, [])
                record = (np.concatenate(
                              [ent.durable,
                               np.asarray(emitted, np.int32)])
                          if emitted else ent.durable)
                need = self.page_alloc.pages_for(len(record))
                if need > self.page_alloc.free_pages:
                    self.metrics.on_alloc_failure()
                    break                 # head-of-line: wait for pages
                self.preempted.popleft()
                self.page_alloc.grow(s, need)
                self.slots[s] = _Slot(
                    state=SlotState.PREFILLING, rid=ent.rid, prompt=record,
                    durable=ent.durable, gen_len=ent.gen_len,
                    deadline=ent.deadline, fault_count=ent.fault_count,
                    replay=bool(emitted), admit_seq=self._admit_seq)
                self._admit_seq += 1
                mask[s] = True
                self.metrics.on_admit(ent.rid, tick, skips=0)
                if self.journal is not None:
                    self.journal.append("admit", tick, rid=int(ent.rid),
                                        slot=s, skips=0)
                if self.tracer is not None:
                    self.tracer.event("admit", tick, rid=ent.rid, slot=s,
                                      wait=0, skips=0, resumed=True)
                iv = SlotInterval(slot=s, rid=ent.rid, admit_tick=tick)
                self.slot_log.append(iv)
                self._open_interval[s] = iv
                continue
            can_admit = None
            if self.paged:
                def can_admit(r):
                    need = self.page_alloc.pages_for(r.prompt_len)
                    if need > self.page_alloc.free_pages:
                        self.metrics.on_alloc_failure()
                        return False
                    return True
            req = self._pop_next(tick, can_admit)
            if req is None:
                break
            prompt = np.asarray(req.prompt, np.int32)
            self.slots[s] = _Slot(
                state=SlotState.PREFILLING, rid=req.rid, prompt=prompt,
                durable=prompt, gen_len=req.gen_len, deadline=req.deadline,
                admit_seq=self._admit_seq)
            self._admit_seq += 1
            if self.paged:
                self.page_alloc.grow(
                    s, self.page_alloc.pages_for(len(prompt)))
            mask[s] = True
            self.outputs[req.rid] = []
            skips = self.skips.pop(req.rid, 0)
            self.metrics.on_admit(req.rid, tick, skips=skips)
            if self.journal is not None:
                self.journal.append("admit", tick, rid=int(req.rid),
                                    slot=s, skips=skips)
            if self.tracer is not None:
                self.tracer.event("admit", tick, rid=req.rid, slot=s,
                                  wait=tick - req.arrival, skips=skips)
            iv = SlotInterval(slot=s, rid=req.rid, admit_tick=tick)
            self.slot_log.append(iv)
            self._open_interval[s] = iv
        if mask.any():
            self.cache = self._reset_call(mask)
        return int(mask.sum())

    # ------------------------------------------------------- page pressure

    def _ptab(self):
        """Device copy of the allocator's page table, refreshed only
        when the allocator actually mutated (version counter) — the
        common decode tick reuses the cached array."""
        if self._ptab_version != self.page_alloc.version:
            self._ptab_cached = jnp.asarray(self.page_alloc.table())
            self._ptab_version = self.page_alloc.version
        return self._ptab_cached

    def _reset_call(self, mask):
        if self.paged:
            return self._reset(self.cache, jnp.asarray(mask), self._ptab())
        return self._reset(self.cache, jnp.asarray(mask))

    def _slot_pages_needed(self, s: int) -> int:
        """Pages slot ``s`` must own BEFORE this tick's device calls: a
        prefilling slot writes up to its next chunk's end; a decoding
        slot writes exactly one token at position
        len(durable) + len(outputs) - 1."""
        slot = self.slots[s]
        if slot.state is SlotState.PREFILLING:
            step = (self.prefill_chunk if self.prefill_mode == "chunked"
                    else 1)
            tokens = min(slot.cursor + step, len(slot.prompt))
            if self.prefill_mode == "chunked" and \
                    tokens == len(slot.prompt):
                # the chunk that finishes the prompt flips the slot to
                # DECODING within this same tick, and that first decode
                # step writes one position PAST the prompt
                tokens += 1
        else:                              # DECODING
            tokens = len(slot.durable) + len(self.outputs[slot.rid])
        return self.page_alloc.pages_for(tokens)

    def _page_growth(self, tick: int):
        """Grow each occupied slot to the pages this tick's writes need,
        OLDEST admission first. Page pressure preempts the YOUNGEST
        occupied slot strictly younger than the needer (a needer with no
        younger neighbor preempts itself — it cannot steal from its
        elders, which is what makes the oldest admitted request always
        runnable and the policy livelock-free: submit() guarantees its
        total need fits the pool)."""
        order = sorted((s for s in range(self.n_slots)
                        if self.slots[s].state is not SlotState.FREE),
                       key=lambda s: self.slots[s].admit_seq)
        for s in order:
            slot = self.slots[s]
            if slot.state is SlotState.FREE:
                continue                   # preempted earlier in the walk
            need = self._slot_pages_needed(s)
            while not self.page_alloc.grow(s, need):
                self.metrics.on_alloc_failure()
                younger = [v for v in range(self.n_slots)
                           if v != s
                           and self.slots[v].state is not SlotState.FREE
                           and self.slots[v].admit_seq > slot.admit_seq]
                if younger:
                    victim = max(younger,
                                 key=lambda v: self.slots[v].admit_seq)
                    self._preempt(victim, tick)
                else:
                    self._preempt(s, tick)
                    break

    def _preempt(self, s: int, tick: int):
        """Evict slot ``s`` under page pressure: free its pages, push it
        onto the FIFO re-admission deque, and journal the transition (a
        "preempt" record — restore must know the slot's pages were
        surrendered). The emitted tokens stay in ``outputs``; the
        re-admitted record is durable + outputs, and because chunked
        prefill == sequential decode, the resumed stream is BITWISE the
        unpreempted one."""
        slot = self.slots[s]
        rid = slot.rid
        freed = self.page_alloc.release(s)
        self.metrics.on_preempt(rid, tick)
        if self.journal is not None:
            self.journal.append("preempt", tick, rid=int(rid), slot=s)
        if self.tracer is not None:
            self.tracer.event("preempt", tick, rid=rid, slot=s,
                              freed_pages=freed)
        self._close_interval(s, tick)
        self.preempted.append(_Preempted(
            rid=rid, durable=slot.durable, gen_len=slot.gen_len,
            deadline=slot.deadline, fault_count=slot.fault_count))
        self.slots[s] = _Slot()

    def _prefill_phase(self, tick: int) -> int:
        prefilling = {s: slot.prompt for s, slot in enumerate(self.slots)
                      if slot.state is SlotState.PREFILLING}
        if not prefilling:
            return 0
        tr = self.tracer
        replaying = any(self.slots[s].replay for s in prefilling)
        restoring = any(self.slots[s].restore for s in prefilling)
        span = (tr.begin("call", tick, phase="prefill",
                         kind=self.prefill_kind, arch=self.cfg.name,
                         participants=sorted(prefilling),
                         occupancy=len(prefilling) / self.n_slots,
                         replay=replaying, restore=restoring)
                if tr is not None else None)
        cursors = {s: self.slots[s].cursor for s in prefilling}
        tokens, n_valid = assemble_chunk(prefilling, cursors, self.n_slots,
                                         self.prefill_chunk)
        c0 = time.monotonic()
        args = (self.params, self.stacked_tables, self.cache,
                jnp.asarray(tokens), jnp.asarray(n_valid))
        if self.paged:
            args = args + (self._ptab(),)
        res = self._device_call("prefill", self.prefill_kind,
                                self._prefill, *args)
        if res is not None:
            logits, self.cache = res
            lg = self._host_logits(logits, tick, "prefill")
        dur_s = time.monotonic() - c0
        if span is not None:
            attrs = dict(ok=res is not None, rows=tokens.size,
                         rows_valid=int(n_valid.sum()))
            if self._ssm_state:
                # the chunk advances the state of slots with prompt tokens
                attrs["state_slots"] = (int((n_valid > 0).sum())
                                        if res is not None else 0)
            tr.end(span, **attrs)
        if res is None:                   # persistent step failure:
            for s in prefilling:          # quarantine every participant
                self._quarantine(s, tick, "step_exception")
            return 0
        self.metrics.on_device_call("prefill", kind=self.prefill_kind,
                                    replay=replaying, restore=restoring,
                                    dur_s=dur_s)
        sample = tr.begin("sample", tick) if tr is not None else None
        nxt = lg.argmax(axis=-1)
        for s in prefilling:
            if not np.isfinite(lg[s]).all():
                self._quarantine(s, tick, "nonfinite_logits")
                continue
            slot = self.slots[s]
            slot.cursor += int(n_valid[s])
            self.metrics.on_prefill_step(slot.rid)
            if self.tracer is not None:
                self.tracer.event("prefill", tick, rid=slot.rid, slot=s,
                                  cursor=slot.cursor,
                                  prompt_len=len(slot.prompt),
                                  replay=slot.replay)
            if slot.cursor >= len(slot.prompt):
                # the chunk containing the last prompt token yields the
                # first generated token — TTFT lands here
                self._finish_prefill(s, int(nxt[s]),
                                     np.asarray(logits[s]), tick)
        if sample is not None:
            tr.end(sample)
        return 1

    def _decode_phase(self, tick: int) -> int:
        stepwise_prefill = (self.prefill_mode == "full")
        tokens = np.zeros((self.n_slots, 1), np.int32)
        active = np.zeros((self.n_slots,), bool)
        for s, slot in enumerate(self.slots):
            if slot.state is SlotState.DECODING:
                tokens[s, 0] = slot.pending_token
                active[s] = True
            elif stepwise_prefill and slot.state is SlotState.PREFILLING:
                tokens[s, 0] = slot.prompt[slot.cursor]
                active[s] = True
        if not active.any():
            return 0
        tr = self.tracer
        span = (tr.begin("call", tick, phase="decode", kind="decode",
                         arch=self.cfg.name,
                         participants=[s for s in range(self.n_slots)
                                       if active[s]],
                         occupancy=float(active.mean()))
                if tr is not None else None)
        c0 = time.monotonic()
        args = (self.params, self.stacked_tables, self.cache,
                jnp.asarray(tokens), jnp.asarray(active))
        if self.paged:
            args = args + (self._ptab(),)
        res = self._device_call("decode", "decode", self._decode, *args)
        if res is not None:
            logits, self.cache = res
            lg = self._host_logits(logits, tick, "decode")
        dur_s = time.monotonic() - c0
        if span is not None:
            # the step writes K/V rows and advances SSM state for its
            # active slots only
            n = int(active.sum()) if res is not None else 0
            tr.end(span, ok=res is not None, slots_written=n,
                   **({"state_slots": n} if self._ssm_state else {}))
        if res is None:
            for s in range(self.n_slots):
                if active[s]:
                    self._quarantine(s, tick, "step_exception")
            return 0
        self.metrics.on_device_call("decode", kind="decode", dur_s=dur_s)
        sample = tr.begin("sample", tick) if tr is not None else None
        nxt = lg.argmax(axis=-1)
        for s, slot in enumerate(self.slots):
            if not active[s]:
                continue
            if not np.isfinite(lg[s]).all():
                self._quarantine(s, tick, "nonfinite_logits")
                continue
            if slot.state is SlotState.PREFILLING:
                slot.cursor += 1
                self.metrics.on_prefill_step(slot.rid)
                if self.tracer is not None:
                    self.tracer.event("prefill", tick, rid=slot.rid,
                                      slot=s, cursor=slot.cursor,
                                      prompt_len=len(slot.prompt),
                                      replay=slot.replay)
                if slot.cursor >= len(slot.prompt):
                    self._finish_prefill(s, int(nxt[s]),
                                         np.asarray(logits[s]), tick)
                continue
            tok = int(nxt[s])
            self.outputs[slot.rid].append(tok)
            slot.pending_token = tok
            self.metrics.on_token(slot.rid)
            if self.journal is not None:
                self.journal.append("token", tick, rid=int(slot.rid),
                                    token=tok)
            if len(self.outputs[slot.rid]) >= slot.gen_len:
                self._release(s, tick)
        if sample is not None:
            tr.end(sample)
        return 1

    # ----------------------------------------------- fault containment ----

    def _device_call(self, call: str, kind: str, fn, *args):
        """Run a device call under the fault contract: injected or real
        exceptions get ``max_step_retries`` re-issues (the injection
        layer raises BEFORE dispatch, so the donated cache buffer is
        intact for the retry); past the budget, returns None and the
        caller quarantines every participating slot. With no fault plan
        installed, real exceptions propagate unchanged — containment
        must never hide a programming error in a plain run.

        ``call`` is the fault-plan phase key ("prefill" / "decode");
        ``kind`` the compiled call_kind retries are attributed to."""
        attempt = 0
        while True:
            try:
                if self.fault_plan is not None:
                    self.fault_plan.check_step(self.tick_count, call,
                                               attempt)
                return fn(*args)
            except Exception as e:  # noqa: BLE001 — any step failure
                if self.fault_plan is None:
                    raise
                self.metrics.on_fault("step_exception", None,
                                      self.tick_count)
                if self.tracer is not None:
                    self.tracer.event("fault", self.tick_count,
                                      kind="step_exception", call=kind,
                                      attempt=attempt, error=str(e))
                attempt += 1
                if attempt > self.max_step_retries:
                    return None
                self.metrics.on_retry(kind)
                if self.tracer is not None:
                    self.tracer.event("retry", self.tick_count, call=kind,
                                      attempt=attempt)

    def _host_logits(self, logits, tick: int, call: str) -> np.ndarray:
        """Host-side (B, V) f32 logits for argmax + the finite-guard;
        the fault plan's nan_logits events poison rows here (the
        corruption a real device would hand back). Blocks until the
        step is done: the "logits" span."""
        span = (self.tracer.begin("logits", tick)
                if self.tracer is not None else None)
        lg = np.asarray(logits[:, 0, :], np.float32)
        if self.fault_plan is not None:
            bad = self.fault_plan.logit_slots(tick, call)
            if bad:
                lg = lg.copy()
                for s in bad:
                    lg[s] = np.nan
        if span is not None:
            self.tracer.end(span)
        return lg

    def _inject_cache_faults(self, tick: int):
        slots = [s for s in self.fault_plan.cache_slots(tick)
                 if self.slots[s].state is not SlotState.FREE]
        if not slots:
            return
        # eager corruption hands back arrays off the serving sharding;
        # re-place them so the next step does not compile a new variant
        self.cache = jax.device_put(
            corrupt_cache(self.cache, slots, self.n_slots, self.cfg),
            self._cache_sharding)
        for s in slots:
            self.metrics.on_fault("cache_corruption", self.slots[s].rid,
                                  tick)
            if self.tracer is not None:
                self.tracer.event("fault", tick, kind="cache_corruption",
                                  rid=self.slots[s].rid, slot=s)

    def _quarantine(self, s: int, tick: int, kind: str):
        """Contain a fault to slot ``s`` and schedule recovery-by-replay:
        zero the slot's cache and re-prefill its durable record (prompt +
        tokens emitted so far). Because chunked prefill == sequential
        decode, the replayed stream continues bitwise as if the fault
        never happened. Past ``max_replays`` the request is shed
        ("fault_budget") — a slot that faults deterministically must
        converge to shedding, not livelock."""
        slot = self.slots[s]
        rid = slot.rid
        self.metrics.on_fault(kind, rid, tick)
        slot.fault_count += 1
        if self.tracer is not None:
            self.tracer.event("quarantine", tick, rid=rid, slot=s,
                              kind=kind, fault_count=slot.fault_count)
        if slot.fault_count > self.max_replays:
            self.metrics.on_shed(rid, tick, "fault_budget")
            if self.journal is not None:
                self.journal.append("shed", tick, rid=int(rid),
                                    reason="fault_budget")
            if self.tracer is not None:
                self.tracer.event("shed", tick, rid=rid, slot=s,
                                  reason="fault_budget")
            self._close_interval(s, tick)
            if self.paged:
                self.page_alloc.release(s)
            self.slots[s] = _Slot()
            return
        self.metrics.on_replay(rid)
        emitted = self.outputs[rid]
        record = (np.concatenate([slot.durable,
                                  np.asarray(emitted, np.int32)])
                  if emitted else slot.durable)
        slot.prompt = record
        slot.cursor = 0
        slot.pending_token = 0
        slot.replay = bool(emitted)
        slot.restore = False              # a fault replay, not restart work
        slot.state = SlotState.PREFILLING
        if self.tracer is not None:
            self.tracer.event("replay", tick, rid=rid, slot=s,
                              record_len=int(len(record)))
        mask = np.zeros((self.n_slots,), bool)
        mask[s] = True
        self.cache = self._reset_call(mask)

    # ------------------------------------------------------ SLO shedding --

    def _min_ticks_to_done(self, prompt_left: int, gen_left: int,
                           queued: bool = False) -> int:
        """OPTIMISTIC ticks (including the current one) until the
        request finishes: the last prefill chunk emits the first of the
        remaining tokens, then one token per tick. A lower bound, so a
        request is only ever shed when its deadline is provably
        unreachable.

        ``queued=True`` on a paged engine adds the page-wait floor: when
        the free pool cannot cover the prompt's pages, admission cannot
        happen THIS tick — at least one tick must pass for any release
        to free pages. Exactly +1 keeps the estimate a lower bound (one
        release could free everything needed)."""
        est = (((math.ceil(prompt_left / self.prefill_chunk)
                 if self.prefill_mode == "chunked" else prompt_left)
                + max(gen_left - 1, 0))
               if prompt_left > 0 else max(gen_left, 1))
        if queued and self.paged and \
                self.page_alloc.pages_for(prompt_left) > \
                self.page_alloc.free_pages:
            est += 1
        return est

    def _shed_hopeless_queue(self, tick: int):
        """Drop arrived queued requests whose deadline is unreachable
        even if admitted RIGHT NOW — load shedding before they waste a
        slot. O(arrived): the arrived prefix is popped, filtered, and
        pushed back."""
        kept = []
        while self.queue and self.queue[0].arrival <= tick:
            r = self.queue.popleft()
            est = self._min_ticks_to_done(r.prompt_len, r.gen_len,
                                          queued=True)
            if r.deadline is not None and tick + est - 1 > r.deadline:
                self.skips.pop(r.rid, None)
                self.metrics.on_shed(r.rid, tick, "deadline")
                if self.journal is not None:
                    self.journal.append("shed", tick, rid=int(r.rid),
                                        reason="deadline")
                if self.tracer is not None:
                    self.tracer.event("shed", tick, rid=r.rid,
                                      reason="deadline", where="queue")
            else:
                kept.append(r)
        self.queue.extendleft(reversed(kept))

    def _shed_hopeless_slots(self, tick: int):
        """Preempt in-flight requests the tick their deadline becomes
        unreachable — the slot is worth more to the queue than to a
        request that can no longer meet its SLO."""
        for s, slot in enumerate(self.slots):
            if slot.state is SlotState.FREE or slot.deadline is None:
                continue
            gen_left = slot.gen_len - len(self.outputs[slot.rid])
            prompt_left = (len(slot.prompt) - slot.cursor
                           if slot.state is SlotState.PREFILLING else 0)
            if tick + self._min_ticks_to_done(prompt_left, gen_left) - 1 \
                    > slot.deadline:
                self.metrics.on_shed(slot.rid, tick, "deadline")
                if self.journal is not None:
                    self.journal.append("shed", tick, rid=int(slot.rid),
                                        reason="deadline")
                if self.tracer is not None:
                    self.tracer.event("shed", tick, rid=slot.rid, slot=s,
                                      reason="deadline", where="slot")
                self._close_interval(s, tick)
                if self.paged:
                    self.page_alloc.release(s)
                self.slots[s] = _Slot()   # cache zeroed at next admit

    # ------------------------------------------------------------- helpers

    def _finish_prefill(self, s: int, token: int, logits: np.ndarray,
                        tick: int):
        slot = self.slots[s]
        slot.state = SlotState.DECODING
        slot.pending_token = token
        self.outputs[slot.rid].append(token)
        if not slot.replay:
            # a replayed record's final chunk yields the NEXT token of an
            # already-started stream, not the request's first — TTFT and
            # first_logits were recorded before the fault
            self.first_logits[slot.rid] = logits
            self.metrics.on_first_token(slot.rid, tick)
            if self.tracer is not None:
                self.tracer.event("first_token", tick, rid=slot.rid,
                                  slot=s)
        slot.replay = False
        slot.restore = False
        self.metrics.on_token(slot.rid)
        if self.journal is not None:
            self.journal.append("token", tick, rid=int(slot.rid),
                                token=int(token))
        if len(self.outputs[slot.rid]) >= slot.gen_len:
            self._release(s, tick)

    def _close_interval(self, s: int, tick: int):
        iv = self._open_interval.pop(s, None)
        if iv is not None:
            iv.release_tick = tick + 1
            if self.tracer is not None:
                self.tracer.interval(iv.slot, iv.rid, iv.admit_tick,
                                     iv.release_tick)

    def _release(self, s: int, tick: int):
        slot = self.slots[s]
        self.metrics.on_done(slot.rid, tick)
        if self.journal is not None:
            self.journal.append("done", tick, rid=int(slot.rid))
        if self.tracer is not None:
            self.tracer.event("release", tick, rid=slot.rid, slot=s,
                              tokens=len(self.outputs[slot.rid]))
        self._close_interval(s, tick)
        if self.paged:
            self.page_alloc.release(s)
        self.slots[s] = _Slot()           # FREE; cache zeroed at next admit
