"""Cells are found by name from data files; the benchmark refuses to
report from a CPU run."""

import json
import subprocess
import sys
import time

import pytest

from bench.tests.conftest import ROOT, TINY, TINY_LIMITS, add_cell


@pytest.mark.parametrize("name", sorted(TINY))
def test_cell_defined_only_by_new_files_runs(bench_copy, name):
    """A configuration file, a mix file and entries in BENCHMARK.json are
    all a new cell needs: the harness finds and runs it (reduced preset,
    CPU, interpreted kernels) and its output matches the reference."""
    from bench.harness import run
    arch, sizes = TINY[name]
    cell = add_cell(bench_copy, name, f"{arch}.json", sizes)
    res = run(cell, 2 ** 33 + 5, 3.0, False, t_start=time.perf_counter(),
              require_tpu=False, root=bench_copy)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] == 12
    assert set(res["metrics"]) == {"ttft_p50_ms", "itl_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert {k: c["limit"] for k, c in res["checks"].items()} == TINY_LIMITS


def test_backlog_cell_reports_throughput(bench_copy):
    """An offline mix keeps ``backlog`` requests waiting for a slot; its
    cell reports output tokens per second."""
    import json as _json
    from bench.harness import run
    from bench.tests.conftest import TINY_MIX
    arch, sizes = TINY["tiny-lm"]
    mix = dict(TINY_MIX, arrivals="backlog", backlog=8, pool=400)
    del mix["rate_rps"]
    cell = add_cell(bench_copy, "tiny-lm", f"{arch}.json", sizes, mix=mix)
    spec = _json.loads((bench_copy / "BENCHMARK.json").read_text())
    spec["end_to_end"].append({"name": "output_tok_s", "unit": "tokens/s",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock", "workloads": [cell]})
    for m in spec["end_to_end"]:
        if m["name"].startswith("ttft"):
            m["workloads"].remove(cell)
    (bench_copy / "BENCHMARK.json").write_text(_json.dumps(spec))
    res = run(cell, 4, 3.0, False, t_start=time.perf_counter(),
              require_tpu=False, root=bench_copy)
    assert set(res["metrics"]) == {"itl_p95_ms", "output_tok_s", "setup_s"}
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert res["correct"] is True and res["failed"] == 0


def test_unknown_device_kind_raises():
    from bench.peaks import UnknownDevice, peaks_for
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")


def test_cpu_run_reports_nothing(bench_copy):
    """No CPU run reports a device metric: the harness refuses before
    set-up, and the command exits non-zero with no result line."""
    from bench.harness import BenchError, run
    with pytest.raises(BenchError, match="needs a TPU"):
        run("stablelm-chat", 1, 1.0, True, t_start=time.perf_counter(),
            root=bench_copy)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "stablelm-chat",
         "--seed", "1", "--seconds", "1", "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_named_file_exists():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert (ROOT / c["file"]).exists()
    for w in spec["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
    for m in spec["per_layer"]:
        assert (ROOT / "bench" / "layer_metrics" / f"{m['name']}.py").exists()


def test_trace_starts_before_a_due_request():
    """The traced seconds open just before the first request due after
    0.4 of the window, whatever the seed, so every traced run holds a
    prefill as well as decode steps."""
    from bench.harness import TRACE_LEAD_S, load_cell, trace_window
    from bench import traffic
    spec = load_cell("stablelm-chat")
    for seed in (1, 2 ** 31 + 7, 3000000412):
        plan = traffic.plan(spec["mix"], seed, 51.0, 1000, extra_s=45.0)
        a, b = trace_window(plan, 51.0)
        first = min(p.due_s for p in plan if p.due_s >= 0.4 * 51.0)
        assert a == pytest.approx(first - TRACE_LEAD_S)
        assert b == pytest.approx(a + 3.0)
    backlog = [traffic.Planned(rid=0, prompt=(1,), gen_len=1, due_s=None,
                               in_window=True)]
    assert trace_window(backlog, 10.0) == (4.0, 7.0)
