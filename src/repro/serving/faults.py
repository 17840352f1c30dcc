"""Deterministic fault injection for the serving engine.

The engine's fault contract (serving.engine) is: **the blast radius of
any single fault is at most one tick, and recovery is bitwise-
verifiable**. This module is the harness that lets CI hold it to that —
a seeded :class:`FaultPlan` schedules adverse events at exact engine
ticks, so a faulted run and a fault-free run of the SAME trace can be
compared token-for-token (benchmarks/serve_engine_bench.py, BENCH key
``chaos``). Same seed + same parameters => identical schedule, always;
the plan itself is stateless at inject time (the engine passes the
attempt number in), so one plan can drive many runs.

Four fault kinds, covering the places a serving step can go wrong on
real hardware plus the process itself (cf. runtime.fault's
``failure_hook`` for the training loop — same philosophy, request-level
granularity):

  * ``step_exception`` — the device call raises (host runtime /
    collective failure). Injected BEFORE dispatch, so the engine's
    bounded retry re-issues the call against intact buffers; an event
    with ``repeat > max_step_retries`` models a persistent failure and
    exercises the quarantine-all path.
  * ``nan_logits``    — one slot's logits come back non-finite
    (overflow, corrupted accumulator). Injected host-side after the
    call; the engine's finite-guard must fail ONLY that slot.
  * ``cache_corruption`` — one slot's KV/SSM cache slices are poisoned
    with NaN at the start of a tick (bit flips, lost DMA). There is no
    direct detector — the poison surfaces as non-finite logits at the
    next device call that reads the slot, which is exactly how the
    engine is meant to catch it (detection-by-propagation).
  * ``engine_crash``  — the whole PROCESS dies (OOM kill, node
    preemption). Raised as :class:`EngineCrash` BETWEEN ticks, after
    the completed tick's journal batch committed, so it models the
    clean kill-point the write-ahead journal is fsync'd at; mid-tick
    loss (a torn journal tail) is covered separately by the journal's
    truncate-at-first-bad-frame recovery. The harness catches the
    exception, abandons the engine object, and brings up a replacement
    via ``ServeEngine.restore`` — the kill-chaos restart case in
    benchmarks/serve_engine_bench.py guards that the restored streams
    are bitwise identical to an uninterrupted run. Unlike the three
    injectable kinds the engine survives in-place, ``engine_crash`` is
    never sampled by :meth:`FaultPlan.generate` (see
    ``INJECTABLE_KINDS``): a crash schedule is a harness-level choice,
    and keeping it out of the sampler keeps every existing seeded
    chaos schedule bit-identical.

Poisoning is layout-generic slot surgery (``corrupt_cache``): float
leaves carry the batch on axis 1, ``pos`` stays valid (a corrupted cache with a trashed position
would be a *different* fault), ``enc_out`` is shared and passes
through.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: kinds the engine absorbs in-place (retry / quarantine / replay) —
#: the only kinds FaultPlan.generate samples
INJECTABLE_KINDS = ("step_exception", "nan_logits", "cache_corruption")
#: all valid event kinds; "engine_crash" kills the process between ticks
FAULT_KINDS = INJECTABLE_KINDS + ("engine_crash",)
#: which engine device call an event may target
FAULT_CALLS = ("decode", "prefill", "any")


class InjectedFault(RuntimeError):
    """Raised by FaultPlan.check_step in place of a device-call failure."""


class EngineCrash(RuntimeError):
    """Simulated whole-process kill (fault kind "engine_crash"): raised
    by the engine between ticks, after the finished tick's journal
    batch was committed. Nothing about the engine object is usable
    afterwards — the harness discards it and rebuilds with
    ``ServeEngine.restore(snapshot_dir, journal_path)``."""

    def __init__(self, msg: str, *, tick: int):
        super().__init__(msg)
        self.tick = tick


@dataclass(frozen=True)
class FaultEvent:
    """One scheduled fault.

    ``tick`` is the engine tick it fires on. ``call`` scopes
    step_exception / nan_logits events to a device-call kind ("decode",
    "prefill", or "any"); cache_corruption ignores it (the poison lands
    before either call). ``slot`` targets nan_logits/cache_corruption;
    an event aimed at a slot that is idle that tick is a no-op (the
    schedule is deterministic, the *effect* depends on engine state —
    the plan never peeks at the engine). ``repeat`` is how many
    consecutive attempts of the same tick's call a step_exception
    fails: 1 (default) is a transient blip one retry absorbs, anything
    above the engine's ``max_step_retries`` is a persistent outage.
    ``engine_crash`` events use only ``tick`` — the process dies after
    that tick completes; ``call``/``slot``/``repeat`` are ignored."""
    tick: int
    kind: str
    call: str = "any"
    slot: int = 0
    repeat: int = 1

    def __post_init__(self):
        if self.kind not in FAULT_KINDS:
            raise ValueError(f"kind {self.kind!r} not in {FAULT_KINDS}")
        if self.call not in FAULT_CALLS:
            raise ValueError(f"call {self.call!r} not in {FAULT_CALLS}")


@dataclass(frozen=True)
class FaultPlan:
    """A deterministic fault schedule. Build one explicitly from events,
    or sample one with :meth:`generate`. ``FaultPlan.none()`` is the
    no-overhead control: an engine driven with it must produce exactly
    the outputs AND device-call count of an engine with no plan at all
    (CI-guarded in the chaos bench)."""
    events: Tuple[FaultEvent, ...] = ()

    @classmethod
    def none(cls) -> "FaultPlan":
        return cls(events=())

    @classmethod
    def generate(cls, seed: int, n_ticks: int, rate: float, n_slots: int,
                 kinds: Tuple[str, ...] = INJECTABLE_KINDS) -> "FaultPlan":
        """Sample a schedule: each tick independently hosts one fault
        with probability ``rate``, uniform over ``kinds``, slots, and
        (for step/logit faults) the two call kinds. Same arguments =>
        identical plan, bit-for-bit — the determinism contract
        tests/test_fault_tolerance.py pins. Defaults to the three
        INJECTABLE kinds (never "engine_crash": crashes are scheduled
        explicitly by restart harnesses, and sampling them here would
        silently change every existing seeded schedule)."""
        rng = np.random.default_rng(seed)
        events: List[FaultEvent] = []
        for tick in range(n_ticks):
            if rng.random() >= rate:
                continue
            kind = kinds[int(rng.integers(len(kinds)))]
            call = ("decode", "prefill")[int(rng.integers(2))]
            slot = int(rng.integers(n_slots))
            events.append(FaultEvent(tick=tick, kind=kind, call=call,
                                     slot=slot))
        return cls(events=tuple(events))

    # ------------------------------------------------------------ queries

    def _at(self, tick: int, kind: str) -> List[FaultEvent]:
        return [e for e in self.events if e.tick == tick and e.kind == kind]

    def check_step(self, tick: int, call: str, attempt: int):
        """Raise InjectedFault if a step_exception event targets this
        tick's ``call`` and has failures left for this ``attempt``
        (0-based). Stateless: the engine's retry loop supplies the
        attempt number, so replaying a run replays the faults."""
        for e in self._at(tick, "step_exception"):
            if e.call in ("any", call) and attempt < e.repeat:
                raise InjectedFault(
                    f"injected step fault: tick={tick} call={call} "
                    f"attempt={attempt}/{e.repeat}")

    def logit_slots(self, tick: int, call: str) -> List[int]:
        """Slots whose logits this tick's ``call`` should NaN-poison."""
        return [e.slot for e in self._at(tick, "nan_logits")
                if e.call in ("any", call)]

    def cache_slots(self, tick: int) -> List[int]:
        """Slots whose cache slices to poison at the start of ``tick``."""
        return [e.slot for e in self._at(tick, "cache_corruption")]

    def crash_at(self, tick: int) -> bool:
        """True if the process should die after completing ``tick``
        (the engine raises EngineCrash between ticks; a restored
        engine resumes at tick+1, so the same event never re-fires)."""
        return bool(self._at(tick, "engine_crash"))


def corrupt_logits(logits: np.ndarray, slots: List[int]) -> np.ndarray:
    """NaN-poison the given batch rows of a host-side logits array."""
    out = np.array(logits, copy=True)
    for s in slots:
        out[s] = np.nan
    return out


def corrupt_cache(cache, slots: List[int], n_slots: int, cfg):
    """NaN-poison every inexact cache leaf's slices for ``slots``.

    Per-slot leaves (k/v, conv, state) carry the batch on axis 1; ``pos``
    and integer leaves stay intact (position corruption would be a
    different fault class), and ``enc_out`` is shared, not per-slot
    state. A paged pool has no slot axis and is poisoned whole."""
    mask = np.zeros((n_slots,), bool)
    for s in slots:
        mask[s] = True

    def poison(path, leaf):
        key = str(getattr(path[-1], "key", path[-1]))
        if key == "enc_out" or not jnp.issubdtype(leaf.dtype, jnp.inexact):
            return leaf
        bad = jnp.full_like(leaf, jnp.nan)
        if key in ("pk", "pv"):
            return bad
        sel = mask.reshape((1, n_slots) + (1,) * (leaf.ndim - 2))
        return jnp.where(sel, bad, leaf)

    return jax.tree_util.tree_map_with_path(poison, cache)
