"""Paging lane: page allocator, paged == contiguous, preempt/resume.

Pins the paged-cache contract (serving.paging + ServeEngine paged mode):

  * the allocator's invariants (no double ownership, conservation,
    ordered slot pages, all-or-nothing grow) survive seeded churn;
  * a paged engine with an ample pool serves a trace BITWISE identical
    to the contiguous engine, with ZERO extra recompiles — the page
    table is a fixed-shape per-call operand, not a shape change;
  * an oversubscribed pool preempts under page pressure and every
    stream — including the preempted ones, resumed by journaled-record
    replay — still finishes bitwise identical to contiguous;
  * oversized requests are judged against PAGED capacity (slot cap AND
    whole-pool cap), so page-pressure preemption can never livelock;
  * the queue-side completion estimate stays a lower bound but adds the
    page-wait floor when the free pool cannot cover a prompt;
  * a paged engine killed between ticks restores from snapshot +
    journal tail (page tables, admission ages, preempted deque) and
    resumes bitwise (also in the durability lane).

Fast lane: run alone with ``pytest -m paging``.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serving import (EngineCrash, FaultPlan, PageAllocError,
                           PageAllocator, Request, ServeEngine,
                           WorkloadSpec, make_trace)
from repro.serving.faults import FaultEvent

pytestmark = pytest.mark.paging

SPEC = WorkloadSpec(n_requests=10, arrival_rate=1.0, prompt_len=(3, 10),
                    gen_len=(3, 6), dist="uniform", seed=7)
ENGINE_KW = dict(n_slots=3, max_len=24, prefill_chunk=4)
PAGE_SIZE = 4
TIGHT_PAGES = 8        # < n_slots * max_len/page_size = 18: oversubscribed
SNAPSHOT_EVERY = 6
CRASH_TICKS = (8, 13)  # both past the first snapshot tick


# ------------------------------------------------- allocator unit tests

def test_allocator_deterministic_lowest_first():
    a = PageAllocator(n_pages=6, n_slots=2, max_pages_per_slot=4,
                      page_size=4)
    assert a.grow(0, 2) and a.grow(1, 1)
    assert a.slot_pages() == [[0, 1], [2]]
    a.release(0)
    assert a.grow(1, 3)               # released ids are reused low-first
    assert a.slot_pages() == [[], [2, 0, 1]]
    assert a.free_pages + a.used_pages == a.n_pages
    a.check()


def test_allocator_grow_is_all_or_nothing():
    a = PageAllocator(n_pages=4, n_slots=2, max_pages_per_slot=4,
                      page_size=4)
    assert a.grow(0, 3)
    v = a.version
    assert not a.grow(1, 2)           # needs 2, only 1 free: takes NOTHING
    assert a.version == v and a.free_pages == 1
    assert not a.grow(0, 5)           # slot cap: 5 > max_pages_per_slot
    assert a.grow(0, 3)               # no-op grow succeeds, no version bump
    assert a.version == v
    a.check()


def test_allocator_churn_invariants():
    """Seeded random alloc/grow/release churn never breaks check()."""
    rng = np.random.default_rng(13)
    a = PageAllocator(n_pages=12, n_slots=4, max_pages_per_slot=6,
                      page_size=4)
    for _ in range(500):
        s = int(rng.integers(0, 4))
        op = rng.random()
        if op < 0.55:
            a.grow(s, int(rng.integers(1, 8)))
        elif op < 0.85:
            a.release(s)
        else:
            a.load_slot_pages(a.slot_pages())   # snapshot round-trip
        a.check()
        assert a.free_pages + a.used_pages == a.n_pages
        tab = a.table()
        for s2 in range(4):
            own = a.slot_pages()[s2]
            assert list(tab[s2, :len(own)]) == own
            assert (tab[s2, len(own):] == -1).all()


def test_allocator_rejects_corrupt_snapshot_tables():
    a = PageAllocator(n_pages=4, n_slots=2, max_pages_per_slot=4,
                      page_size=4)
    with pytest.raises(PageAllocError):
        a.load_slot_pages([[0, 1], [1]])        # shared page
    with pytest.raises(PageAllocError):
        a.load_slot_pages([[0], [9]])           # out of range
    with pytest.raises(PageAllocError):
        a.load_slot_pages([[0]])                # wrong slot count


# ----------------------------------------------------------- engine lane

@pytest.fixture(scope="module")
def served():
    cfg = get_config("tinyllama-1.1b", reduced=True).scaled(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    trace = make_trace(SPEC, cfg.vocab_size)
    engine = ServeEngine(cfg, params, **ENGINE_KW)
    ref_out = engine.run(trace)
    return cfg, params, trace, ref_out


def test_paged_ample_pool_is_bitwise_with_zero_recompiles(served):
    """Full static capacity in pages: no preemption possible, outputs
    bitwise the contiguous engine's, and every step compiles exactly
    once — paging moves page ids, never shapes."""
    cfg, params, trace, ref_out = served
    engine = ServeEngine(cfg, params, paged=True, page_size=PAGE_SIZE,
                         **ENGINE_KW)
    out = engine.run(trace)
    assert out == ref_out
    s = engine.metrics.summary()
    assert s["n_preemptions"] == 0 and s["page_alloc_failures"] == 0
    assert engine.sentinel is not None
    assert all(n == 1 for n in engine.sentinel.counts().values()), \
        engine.sentinel.counts()
    engine.page_alloc.check()


def test_tight_pool_preempts_and_resumes_bitwise(served):
    """The oversubscribed pool: page pressure must actually preempt at
    least once, and EVERY stream — preempted ones resumed by journaled-
    record replay — still matches the contiguous run bitwise."""
    cfg, params, trace, ref_out = served
    engine = ServeEngine(cfg, params, paged=True, page_size=PAGE_SIZE,
                         n_pages=TIGHT_PAGES, **ENGINE_KW)
    out = engine.run(trace)
    s = engine.metrics.summary()
    assert s["n_preemptions"] >= 1
    assert s["page_alloc_failures"] >= 1
    assert out == ref_out                     # all streams, bitwise
    assert s["pages_used_max"] <= TIGHT_PAGES
    engine.page_alloc.check()
    assert engine.page_alloc.free_pages == TIGHT_PAGES  # all released


def test_traced_tight_pool_preempts_and_validates(served):
    """A traced engine over the oversubscribed pool records its
    preemptions as "preempt" events, and its records pass
    obs.trace.validate; the streams stay bitwise."""
    from repro.obs import Tracer, validate
    cfg, params, trace, ref_out = served
    tracer = Tracer(arch=cfg.name)
    engine = ServeEngine(cfg, params, paged=True, page_size=PAGE_SIZE,
                         n_pages=TIGHT_PAGES, tracer=tracer, **ENGINE_KW)
    out = engine.run(trace)
    assert out == ref_out
    validate(tracer.records)
    preempts = [r for r in tracer.records
                if r.get("type") == "event" and r["name"] == "preempt"]
    assert len(preempts) == engine.metrics.summary()["n_preemptions"] >= 1


def test_oversized_judged_against_paged_capacity(served):
    """A request whose total exceeds the POOL (even though it fits the
    per-slot cap) must be rejected at submit — admitting it would make
    page-pressure preemption livelock."""
    cfg, params, _, _ = served
    engine = ServeEngine(cfg, params, paged=True, page_size=PAGE_SIZE,
                         n_pages=2, **ENGINE_KW)
    big = Request(rid=0, prompt=tuple(range(1, 10)), gen_len=4)  # 13 > 8
    assert not engine.submit(big)
    assert engine.rejected[0] == "oversized"
    small = Request(rid=1, prompt=(1, 2, 3), gen_len=2)
    assert engine.submit(small)
    strict = ServeEngine(cfg, params, paged=True, page_size=PAGE_SIZE,
                         n_pages=2, strict=True, **ENGINE_KW)
    with pytest.raises(ValueError, match="page pool"):
        strict.submit(big)


def test_min_ticks_to_done_adds_page_wait_floor(served):
    """queued=True adds exactly +1 tick when the free pool cannot cover
    the prompt's pages — admission can't happen this tick, but one
    release could free everything, so the estimate stays a lower
    bound."""
    cfg, params, _, _ = served
    engine = ServeEngine(cfg, params, paged=True, page_size=PAGE_SIZE,
                         n_pages=3, **ENGINE_KW)
    base = engine._min_ticks_to_done(8, 3)
    assert engine._min_ticks_to_done(8, 3, queued=True) == base  # fits
    engine.page_alloc.grow(0, 2)      # 1 page left < pages_for(8) = 2
    assert engine._min_ticks_to_done(8, 3, queued=True) == base + 1
    assert engine._min_ticks_to_done(8, 3) == base    # in-flight: no wait
    engine.page_alloc.release(0)
    assert engine._min_ticks_to_done(8, 3, queued=True) == base


@pytest.mark.durability
def test_paged_kill_chaos_restart_is_bitwise(served, tmp_path):
    """Paged + oversubscribed + killed at two seeded ticks: restore
    rebuilds the page tables, admission ages, and preempted deque from
    snapshot v2 + journal tail, and every stream finishes bitwise the
    contiguous run, with replayed prefill bounded by the cadence."""
    cfg, params, trace, ref_out = served
    jpath = str(tmp_path / "j.jsonl")
    snapdir = str(tmp_path / "snaps")
    plan = FaultPlan(events=tuple(
        FaultEvent(tick=t, kind="engine_crash") for t in CRASH_TICKS))
    kw = dict(paged=True, page_size=PAGE_SIZE, n_pages=TIGHT_PAGES,
              **ENGINE_KW)
    engine = ServeEngine(cfg, params, journal=jpath, snapshot_dir=snapdir,
                         snapshot_every=SNAPSHOT_EVERY, fault_plan=plan,
                         **kw)
    crashes, outputs = 0, None
    try:
        outputs = engine.run(trace)
    except EngineCrash as e:
        crashes, last_tick = 1, e.tick
    while outputs is None:
        engine = ServeEngine.restore(cfg, params, snapshot_dir=snapdir,
                                     journal_path=jpath, fault_plan=plan)
        assert engine.paged and engine.page_size == PAGE_SIZE
        assert engine.n_pages == TIGHT_PAGES
        engine.page_alloc.check()
        assert engine.tick_count > last_tick   # the crash never re-fires
        st = engine.restore_stats
        assert st["replayed_prefill_tokens"] \
            <= SNAPSHOT_EVERY * max(st["slots_restored"], 1)
        try:
            outputs = engine.resume()
        except EngineCrash as e:
            crashes, last_tick = crashes + 1, e.tick
    assert crashes == len(CRASH_TICKS)
    assert outputs == ref_out


def test_paged_snapshot_geometry_mismatch_refused(served, tmp_path):
    """A snapshot from a paged engine must not restore into a different
    page geometry — silently remapping page ids would cross-wire KV."""
    from repro.checkpoint import latest_step
    from repro.serving.snapshot import SnapshotError, restore_engine_state
    cfg, params, trace, _ = served
    jpath = str(tmp_path / "j.jsonl")
    snapdir = str(tmp_path / "snaps")
    engine = ServeEngine(cfg, params, journal=jpath, snapshot_dir=snapdir,
                         snapshot_every=SNAPSHOT_EVERY, paged=True,
                         page_size=PAGE_SIZE, n_pages=TIGHT_PAGES,
                         **ENGINE_KW)
    engine.run(trace)
    contiguous = ServeEngine(cfg, params, **ENGINE_KW)
    with pytest.raises(SnapshotError, match="paged"):
        restore_engine_state(contiguous, snapdir, latest_step(snapdir),
                             journal_path=jpath)


def test_workload_longtail_dists():
    """lognormal / zipf generation stays in-range, skews short, and the
    default gen_dist keeps older traces bit-identical."""
    base = WorkloadSpec(n_requests=200, prompt_len=(3, 16), gen_len=(3, 8),
                        dist="lognormal", gen_dist="zipf", seed=5)
    trace = make_trace(base, vocab_size=100)
    plens = [r.prompt_len for r in trace]
    glens = [r.gen_len for r in trace]
    assert all(3 <= p <= 16 for p in plens)
    assert all(3 <= g <= 8 for g in glens)
    # right-skew: the median sits in the bottom half of the range
    assert sorted(plens)[len(plens) // 2] < (3 + 16) / 2
    assert sorted(glens)[len(glens) // 2] < (3 + 8) / 2
    legacy = WorkloadSpec(n_requests=20, seed=3)
    assert legacy.gen_dist == "uniform"
    explicit = dataclasses.replace(legacy, gen_dist="uniform")
    assert make_trace(legacy, 64) == make_trace(explicit, 64)
