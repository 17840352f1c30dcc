"""The mamba2-chat cell: its configuration file against the program's
preset, and the SSM state's roofline reader on by-hand numbers."""

import json

import pytest

from bench.harness import load_reader, program_config
from bench.tests.conftest import ROOT
from bench.trace import Reduced, Span, StepExec

CONFIGS = ROOT / "bench" / "configs"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _model():
    return json.loads((CONFIGS / "mamba2-1.3b-v5e.json").read_text())


def _reduced(steps):
    return Reduced(window=(0.0, 1.0), busy_s=0.0, ticks={}, steps=steps,
                   tick_busy_s=0.0, tick_s=0.0, idle_gaps=[],
                   device_ops=[], unmatched={})


def _step(kind, dur, kernel_s):
    return StepExec(kind, 0, Span("jit_step_fn(1)", 0.0, dur), kernel_s, [])


def test_cell_config_is_the_program_preset():
    """The cell's file passes the harness's size check, names the
    program's published-width preset, and differs from the kept file
    only in its limits and their readings."""
    from repro.configs import get_config
    model = _model()
    cfg = program_config(model)
    want = get_config("mamba2-1.3b", dbpim_mode="joint")
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == (48, 2048, 50280)
    assert (cfg.ssm_state, cfg.ssm_expand, cfg.ssm_head_dim,
            cfg.ssm_conv_width) == (128, 2, 64, 4)
    assert cfg == want
    kept = json.loads((CONFIGS / "mamba2-1.3b.json").read_text())
    assert {k for k in model if model[k] != kept[k]} == {"check", "assumed"}
    assert {k for k in model["assumed"]
            if model["assumed"][k] != kept["assumed"][k]} == {"check"}
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in spec["configs"]}["mamba2-1.3b"]
    assert sorted(entry["reduced"]) == sorted(model["reduced"])


def test_state_roofline_reads_state_bytes_over_time_outside_the_kernel():
    """Two decode steps of 20 ms, 4 ms of each in the joint kernel:
    every slot's f32 state (48 layers x 16 slots x 4096 x 128 x 4 B)
    and bf16 conv window (x 3 x 4352 x 2 B) read and written once a
    step at 819 GB/s, over the 32 ms outside the kernel. A prefill
    step does not count."""
    read = load_reader(ROOT / "bench" / "layer_metrics",
                       "state_roofline.decode")
    per_step = 2 * 48 * 16 * (4096 * 128 * 4 + 3 * 4352 * 2)
    assert per_step == 3_261_333_504
    red = _reduced([_step("decode", 0.020, 0.004),
                    _step("prefill", 0.120, 0.090),
                    _step("decode", 0.020, 0.004)])
    got = read({"reduced": red, "model": _model(), "peak": PEAK})
    assert got == pytest.approx(100 * 2 * per_step / 819e9 / 0.032)
    assert got == pytest.approx(24.888, abs=1e-3)


def test_state_roofline_reads_nothing_without_decode_or_state():
    read = load_reader(ROOT / "bench" / "layer_metrics",
                       "state_roofline.decode")
    prefill_only = _reduced([_step("prefill", 0.120, 0.090)])
    assert read({"reduced": prefill_only, "model": _model(),
                 "peak": PEAK}) is None
    dense = json.loads((CONFIGS / "stablelm-1.6b.json").read_text())
    decode = _reduced([_step("decode", 0.020, 0.004)])
    assert read({"reduced": decode, "model": dense, "peak": PEAK}) is None
