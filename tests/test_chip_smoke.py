"""chip_smoke.py's served-path check at the reduced stablelm preset: the
same engine entry points, logits probe and serving assertions the chip
run makes at published widths, here with interpreted kernels."""

import importlib.util
from pathlib import Path

from repro.configs import get_config


def _chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_served_path_check_at_reduced_preset():
    smoke = _chip_smoke()
    cfg = get_config("stablelm-1.6b", reduced=True, dbpim_mode="joint")
    lines = []
    rep = smoke.check_served_path(cfg, n_slots=2, max_len=64,
                                  prefill_chunk=8, n_requests=4,
                                  prompt_len=(6, 20), gen_len=4,
                                  log=lines.append)
    s = rep["summary"]
    assert s["n_completed"] == s["n_requests"] == 4
    assert s["n_faults"] == s["n_shed"] == s["n_rejected"] == 0
    assert set(rep["compiles"]) == {"decode@stablelm-smoke",
                                    "prefill_chunk_exact@stablelm-smoke",
                                    "reset@stablelm-smoke"}
    assert all(c == 1 for c in rep["compiles"].values())
    assert rep["prefill_rel_err"] <= smoke.LOGITS_REL_TOL
    assert rep["decode_rel_err"] <= smoke.LOGITS_REL_TOL
    assert any("rel L2 err" in line for line in lines)
