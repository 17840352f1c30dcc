"""The engine's host spans against the device trace, on a synthetic
nested trace and on the trace recorded on a TPU v5e (``fixtures/``),
which predates them; and the readings of the tracer's records."""

from pathlib import Path

import pytest

from bench import host_phases as hp
from bench import trace
from bench.trace import Parsed, Span

FIXTURES = Path(__file__).with_name("fixtures")


def _nested():
    """Tick 7 split by the engine's spans (a prefill call then a decode
    call), tick 8 from a program without them."""
    host = [Span("bench.tick 7", 0.1, 1.1),
            Span("bench.bookkeeping", 1.1, 1.2),
            Span("bench.tick 8", 2.0, 2.6)]
    engine = [Span("engine.tick", 0.12, 1.08),
              Span("engine.schedule", 0.12, 0.19),
              Span("engine.call", 0.19, 0.65),
              Span("engine.logits", 0.25, 0.65),
              Span("engine.sample", 0.65, 0.7),
              Span("engine.call", 0.7, 1.02),
              Span("engine.logits", 0.72, 1.02),
              Span("engine.sample", 1.02, 1.08)]
    modules = [Span("jit_step_fn(1)", 0.2, 0.6),
               Span("jit_step_fn(2)", 0.7, 1.0),
               Span("jit_step_fn(2)", 2.1, 2.5)]
    ops = [Span("fusion.1", 0.15, 0.18), Span("fusion.2", 0.2, 0.58),
           Span("fusion.3", 0.7, 1.0), Span("fusion.4", 2.1, 2.5)]
    return Parsed(host, modules, ops), engine


def test_idle_by_span_charges_the_innermost_span_and_adds_up():
    parsed, engine = _nested()
    red = trace.reduce(parsed, {7: ("prefill", "decode"), 8: ("decode",)})
    idle = hp.idle_by_span(parsed, engine)
    assert set(idle) == {"bench.tick", "engine.schedule", "engine.call",
                         "engine.logits", "engine.sample"}
    assert idle["engine.schedule"] == pytest.approx(0.04)
    assert idle["engine.call"] == pytest.approx(0.01)
    assert idle["engine.logits"] == pytest.approx(0.07 + 0.02)
    assert idle["engine.sample"] == pytest.approx(0.05 + 0.06)
    assert idle["bench.tick"] == pytest.approx(0.02 + 0.02 + 0.2)
    assert sum(idle.values()) == pytest.approx(
        red.tick_s - red.tick_busy_s, abs=1e-12)


def test_tiles_cover_the_outer_span_once():
    parsed, engine = _nested()
    pieces = hp.tiles(parsed.host[0], engine)
    assert pieces[0][0] == 0.1 and pieces[-1][1] == 1.1
    assert all(a[1] == b[0] for a, b in zip(pieces, pieces[1:]))
    # a child that runs past its parent is clipped to it
    out = hp.tiles(Span("bench.tick 1", 0.0, 1.0),
                   [Span("engine.call", 0.2, 0.5),
                    Span("engine.logits", 0.4, 0.7)])
    assert out == [(0.0, 0.2, "bench.tick"), (0.2, 0.4, "engine.call"),
                   (0.4, 0.5, "engine.logits"), (0.5, 1.0, "bench.tick")]


def test_idle_gaps_are_named_by_the_innermost_span():
    parsed, engine = _nested()
    gaps = hp.idle_gaps(parsed, engine)
    assert gaps[0] == ["outside spans", pytest.approx(1.1)]
    owners = {o for o, _ in gaps}
    assert owners == {"outside spans", "engine.schedule", "engine.call",
                      "engine.logits", "bench.tick"}


def test_tick_idle_pct_reads_nothing_without_engine_spans():
    parsed, engine = _nested()
    idle = hp.idle_by_span(parsed, engine)
    assert hp.tick_idle_pct(idle, 1.6, "logits") == pytest.approx(
        100 * 0.09 / 1.6)
    assert hp.tick_idle_pct(idle, 1.6, "commit") == 0.0
    bare = hp.idle_by_span(parsed, [])
    assert set(bare) == {"bench.tick"}
    assert hp.tick_idle_pct(bare, 1.6, "logits") is None
    assert hp.tick_idle_pct({}, 0.0, "sample") is None


def test_recorded_v5e_trace_has_no_engine_spans():
    """The fixture predates the engine's spans: every idle second
    inside its ticks is charged to the harness's tick span."""
    parsed, engine = hp.load(FIXTURES / "v5e_ticks.xplane.pb")
    assert engine == []
    red = trace.reduce(parsed, {})
    idle = hp.idle_by_span(parsed, engine)
    assert list(idle) == ["bench.tick"]
    assert idle["bench.tick"] == pytest.approx(
        red.tick_s - red.tick_busy_s, abs=1e-9)


def _event(name, ts, rid):
    return {"type": "event", "name": name, "tick": 0, "ts_us": ts,
            "attrs": {"rid": rid}}


def _call(rows, valid):
    attrs = {"phase": "prefill", "kind": "prefill_chunk_exact"}
    if rows is not None:
        attrs.update(rows=rows, rows_valid=valid)
    return {"type": "span", "name": "call", "tick": 0, "ts_us": 0.0,
            "dur_us": 1.0, "attrs": attrs}


def test_request_readings_from_records():
    records = [{"type": "meta", "version": 1}]
    for rid, (sub, adm, first) in {1: (0, 2000, 300000),
                                   2: (100, 5100, 405100),
                                   3: (200, 200, 500200)}.items():
        records += [_event("submit", sub, rid), _event("admit", adm, rid),
                    _event("first_token", first, rid)]
    records += [_event("submit", 900, 4), _event("admit", 1000, 4),
                _event("admit", 9000, 4)]        # resumed: first admit
    records += [_call(32, 8), _call(32, 4), _call(None, None)]
    assert hp.queue_wait_p50_ms(records, [1, 2, 3]) == pytest.approx(2.0)
    assert hp.queue_wait_p50_ms(records, [4]) == pytest.approx(0.1)
    assert hp.prefill_p50_ms(records, [1, 2, 3]) == pytest.approx(400.0)
    assert hp.prefill_p50_ms(records, [4]) is None
    assert hp.prefill_row_use_pct(records) == pytest.approx(100 * 12 / 64)


def test_request_readings_find_nothing_to_read():
    records = [{"type": "meta", "version": 1}, _call(None, None)]
    assert hp.queue_wait_p50_ms(records, [1]) is None
    assert hp.prefill_p50_ms(records, [1]) is None
    assert hp.prefill_row_use_pct(records) is None
