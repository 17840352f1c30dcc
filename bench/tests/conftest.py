"""Shared set-up of the benchmark's own tests (run with
``python -m pytest bench/tests`` from the root of the checkout, on the
CPU: Pallas kernels run interpreted there)."""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: reduced stand-ins of the two configurations: the same families and
#: code paths at sizes a CPU test holds
TINY = {
    "tiny-lm": ("stablelm-1.6b", dict(
        num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=4, intermediate_size=128, vocab_size=256)),
    "tiny-ssm": ("mamba2-1.3b", dict(
        num_hidden_layers=2, hidden_size=64, state_size=16, head_dim=16,
        vocab_size=256)),
}
#: limits of the tiny cells, from CPU runs of the reduced presets on
#: three seeds: the program's mean gap reads 0 to 4.1e-4 and its first
#: logits' relative L2 error 0.005 to 0.014; the float8 control reads
#: 1.4e-3 to 5.6e-3 and 0.054 to 0.12
TINY_LIMITS = {"mean_logit_gap": 1e-3, "first_logits_rel_l2": 0.03}
TINY_MIX = {"arrivals": "poisson", "rate_rps": 4.0,
            "prompt": {"dist": "lognormal", "lo": 8, "hi": 40},
            "output": {"dist": "lognormal", "lo": 4, "hi": 16},
            "base_seed": 0}


def add_cell(root: Path, name: str, arch_file: str, sizes: dict,
             mix: dict = TINY_MIX, limits: dict = TINY_LIMITS) -> str:
    """Add a reduced-preset configuration, a mix and a cell to the
    benchmark copy at ``root`` using only new files and new entries;
    returns the cell's name."""
    base = json.loads((root / "bench" / "configs" / arch_file).read_text())
    base.update(sizes, preset="reduced", n_slots=4, max_len=64,
                prefill_chunk=16, check=dict(limits))
    (root / "bench" / "configs" / f"{name}.json").write_text(
        json.dumps(base))
    (root / "bench" / "traffic" / f"{name}-mix.json").write_text(
        json.dumps(mix))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": name, "source": "test",
                            "file": f"bench/configs/{name}.json",
                            "reduced": [], "why": "test"})
    cell = f"{name}.chat"
    spec["workloads"].append({"name": cell, "config": name,
                              "traffic": f"{name}-mix", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and m.get("name") != "output_tok_s":
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return cell


@pytest.fixture
def bench_copy(tmp_path):
    """A copy of BENCHMARK.json and bench/ in a scratch directory."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path
