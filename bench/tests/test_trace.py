"""The trace reduction on synthetic event lists and on a small trace
recorded on a TPU v5e (``fixtures/``)."""

import json
from pathlib import Path

import pytest

from bench import trace, work
from bench.trace import Parsed, Span

FIXTURES = Path(__file__).with_name("fixtures")
MODEL = {"value_sparsity": 0.6, "tile": [128, 128]}


def kernel(i, rows, k=2048, n=2048):
    """The joint kernel's op text as a TPU v5e trace shows it."""
    return (f'%_joint_sparse_matmul.{i} = bf16[{rows},{n}]{{1,0}} '
            f'custom-call(s32[16,6]{{1,0}} %copy.1, bf16[{rows},{k}]{{1,0}} '
            f'%fusion.2, s8[16,6,128,128]{{3,2,1,0}} %w.3, f32[1,{n}]{{1,0}} '
            f'%s.4), custom_call_target="tpu_custom_call"')


def test_union_merges_overlaps_and_clips_to_window():
    spans = [Span("a", 0.0, 2.0), Span("b", 1.0, 3.0), Span("c", 5.0, 6.0),
             Span("d", 5.5, 5.7), Span("e", 9.0, 12.0)]
    assert trace.union(spans, 0.5, 10.0) == [(0.5, 3.0), (5.0, 6.0),
                                              (9.0, 10.0)]
    assert trace.covered(trace.union(spans, 0.5, 10.0), 0.5, 10.0) == 4.5


def test_gaps_at_window_edges():
    busy = [(1.0, 2.0), (3.0, 4.0)]
    assert trace.gaps(busy, 0.0, 5.0) == [(0.0, 1.0), (2.0, 3.0),
                                          (4.0, 5.0)]
    assert trace.gaps(busy, 1.0, 4.0) == [(2.0, 3.0)]
    assert trace.gaps([], 0.0, 1.0) == [(0.0, 1.0)]
    assert trace.gaps([(0.0, 9.0)], 1.0, 2.0) == []


def _synthetic():
    host = [Span("bench.bookkeeping", 0.0, 0.1),
            Span("bench.tick 7", 0.1, 1.1),        # prefill then decode
            Span("bench.bookkeeping", 1.1, 1.2),
            Span("bench.wait", 1.2, 2.0),
            Span("bench.tick 8", 2.0, 2.6)]        # decode only
    modules = [Span("jit_step_fn(11)", 0.2, 0.6),       # prefill
               Span("jit_step_fn(22)", 0.7, 1.0),       # decode
               Span("jit__lambda(33)", 0.15, 0.18),     # a reset: no step
               Span("jit_step_fn(22)", 2.1, 2.5)]
    ops = [Span("fusion.1", 0.15, 0.18),
           Span(kernel(3, 2048), 0.2, 0.5), Span("fusion.2", 0.5, 0.6),
           Span(kernel(3, 16), 0.7, 0.8), Span("copy.9", 0.8, 1.0),
           Span(kernel(4, 16), 2.1, 2.2), Span("fusion.7", 2.2, 2.5),
           Span("fusion.8", 2.45, 2.55)]             # overlaps fusion.7
    return Parsed(host, modules, ops)


def test_reduce_classifies_steps_by_tick_record():
    red = trace.reduce(_synthetic(), {7: ("prefill", "decode"),
                                      8: ("decode",)})
    got = [(s.kind, s.tick, round(s.kernel_s, 9)) for s in red.steps]
    assert got == [("prefill", 7, 0.3), ("decode", 7, 0.1),
                   ("decode", 8, 0.1)]
    assert [[c[:3] for c in s.calls] for s in red.steps] == [
        [(2048, 2048, 2048)], [(16, 2048, 2048)], [(16, 2048, 2048)]]
    assert red.unmatched == {}
    assert red.window == (0.0, 2.6)
    assert red.busy_s == pytest.approx(0.03 + 0.4 + 0.3 + 0.45)
    assert red.tick_s == pytest.approx(1.6)
    assert red.tick_busy_s == pytest.approx(0.03 + 0.4 + 0.3 + 0.45)
    assert red.idle_gaps[0] == ["bench.wait", pytest.approx(1.1)]
    labels = dict((k, v) for k, v in red.device_ops)
    assert labels["_joint_sparse_matmul"] == pytest.approx(0.5)
    assert labels["fusion"] == pytest.approx(0.03 + 0.1 + 0.3 + 0.1)


def test_reduce_tolerates_device_clock_skew():
    """A step whose start the device's clock puts a little before its
    tick span still belongs to that tick."""
    p = _synthetic()
    p.modules[3] = Span("jit_step_fn(22)", 1.9995, 2.5)
    red = trace.reduce(p, {7: ("prefill", "decode"), 8: ("decode",)})
    assert [(s.kind, s.tick) for s in red.steps][-1] == ("decode", 8)


def test_reduce_leaves_out_a_tick_whose_steps_differ_from_its_record():
    """A tick's steps take their kinds from the harness's record, in
    order; where the counts differ the tick is reported and left out."""
    p = _synthetic()
    p.host.append(Span("bench.tick 9", 3.0, 3.5))
    p.modules.append(Span("jit_step_fn(22)", 3.1, 3.4))
    p.ops.append(Span(kernel(5, 128), 3.1, 3.2))
    red = trace.reduce(p, {7: ("prefill", "decode"), 8: ("decode",),
                           9: ("prefill", "decode")})
    assert [(s.kind, s.tick) for s in red.steps] == [
        ("prefill", 7), ("decode", 7), ("decode", 8)]
    assert red.unmatched == {9: (("prefill", "decode"), 1)}


def test_kernel_shape_reads_rows_k_n():
    assert trace.kernel_shape(Span(kernel(1, 16, 5632, 2048), 0, 1)) == (
        16, 5632, 2048)
    assert trace.kernel_shape(Span(kernel(1, 2048, 2048, 5632), 0, 1)) == (
        2048, 2048, 5632)
    bare = '%_joint_sparse_matmul.1 = bf16[16,2048] custom-call()'
    assert trace.kernel_shape(Span(bare, 0, 1)) is None


def test_joint_roofline_follows_each_calls_own_rows():
    """Calls that each take twice their own roofline time read 50 %,
    whatever rows each computes: a chunk that computes fewer rows is
    charged fewer bytes and operations."""
    from bench.harness import load_reader
    from bench.tests.conftest import ROOT
    from bench.work import joint_call
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    shapes = [(2048, 2048, 2048), (128, 2048, 5632), (128, 5632, 2048),
              (16, 2048, 2048)]
    host, modules, ops, t = [], [], [], 0.0
    for i, (rows, k, n) in enumerate(shapes):
        dur = 2 * joint_call(rows, k, n, 0.6, (128, 128)).roofline_s(peak)[0]
        host.append(Span(f"bench.tick {i}", t, t + dur + 0.02))
        modules.append(Span("jit_step_fn(1)", t + 0.01, t + dur + 0.01))
        ops.append(Span(kernel(i, rows, k, n), t + 0.01, t + dur + 0.01))
        t += dur + 0.02
    kinds = ["prefill"] * 3 + ["decode"]
    red = trace.reduce(Parsed(host, modules, ops),
                       {i: (kind,) for i, kind in enumerate(kinds)})
    ctx = {"reduced": red, "ticks": {}, "peak": peak, "model": MODEL}
    d = ROOT / "bench" / "layer_metrics"
    for kind in ("prefill", "decode"):
        assert load_reader(d, f"joint_roofline.{kind}")(ctx) == \
            pytest.approx(50.0)


def test_readers_on_synthetic_steps():
    from bench.harness import Tick, load_reader
    from bench.tests.conftest import ROOT
    red = trace.reduce(_synthetic(), {7: ("prefill", "decode"),
                                      8: ("decode",)})
    ticks = {7: Tick(1.1, ("prefill", "decode"), pf_flops=4e9,
                     dc_flops=1e8),
             8: Tick(2.6, ("decode",), dc_flops=2e8)}
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    ctx = {"reduced": red, "ticks": ticks, "peak": peak, "model": MODEL}
    d = ROOT / "bench" / "layer_metrics"
    assert load_reader(d, "step_mfu.decode")(ctx) == pytest.approx(
        100 * 3e8 / (0.3 + 0.4) / 1e12)
    assert load_reader(d, "step_mfu.prefill")(ctx) == pytest.approx(
        100 * 4e9 / 0.4 / 1e12)
    assert load_reader(d, "tick_idle_pct")(ctx) == pytest.approx(
        100 * (1.6 - 1.18) / 1.6)
    least = {r: work.joint_call(r, 2048, 2048, 0.6, (128, 128))
             .roofline_s(peak)[0] for r in (16, 2048)}
    assert load_reader(d, "joint_roofline.decode")(ctx) == pytest.approx(
        100 * 2 * least[16] / 0.2)
    assert load_reader(d, "joint_roofline.prefill")(ctx) == pytest.approx(
        100 * least[2048] / 0.3)


def test_readers_find_nothing_to_read():
    from bench.harness import load_reader
    from bench.tests.conftest import ROOT
    empty = trace.reduce(Parsed([Span("bench.wait", 0.0, 1.0)], [], []), {})
    ctx = {"reduced": empty, "ticks": {}, "peak": {}, "model": {}}
    d = ROOT / "bench" / "layer_metrics"
    for name in ("joint_roofline.decode", "joint_roofline.prefill",
                 "step_mfu.decode", "step_mfu.prefill", "tick_idle_pct"):
        assert load_reader(d, name)(ctx) is None


def test_recorded_v5e_trace():
    """A trace of one decode tick of stablelm-chat on a TPU v5e, with the
    harness's record of which calls the tick made: one step execution
    holding the joint kernel's 7 projections x 24 layers."""
    parsed = trace.load(FIXTURES / "v5e_ticks.xplane.pb")
    want = json.loads((FIXTURES / "v5e_ticks.json").read_text())
    calls = {int(k): tuple(v) for k, v in want["tick_calls"].items()}
    red = trace.reduce(parsed, calls)
    got = {"steps": [[s.kind, s.tick] for s in red.steps],
           "n_ops": len(parsed.ops), "n_modules": len(parsed.modules),
           "busy_s": red.busy_s, "tick_s": red.tick_s,
           "kernel_s": sum(s.kernel_s for s in red.steps)}
    assert got["steps"] == want["steps"]
    assert got["n_ops"] == want["n_ops"]
    assert got["n_modules"] == want["n_modules"]
    for k in ("busy_s", "tick_s", "kernel_s"):
        assert got[k] == pytest.approx(want[k], rel=1e-9)
    assert sum(trace.is_kernel(op) for op in parsed.ops) == 7 * 24
    assert all(s.kernel_s > 0 for s in red.steps)
    assert sorted({c[:3] for s in red.steps for c in s.calls}) == [
        (16, 2048, 2048), (16, 2048, 5632), (16, 5632, 2048)]
    assert len(red.steps[0].calls) == 7 * 24
    assert 0 < red.tick_busy_s <= red.tick_s
