"""Registry of the 10 assigned architectures (filled in by arch modules)."""

from __future__ import annotations

import importlib
from typing import Dict, List

from repro.models.config import KERNEL_MODES

ARCHS: List[str] = [
    "qwen3-8b", "tinyllama-1.1b", "gemma-7b", "stablelm-1.6b",
    "arctic-480b", "mixtral-8x7b", "mamba2-1.3b", "pixtral-12b",
    "whisper-base", "jamba-v0.1-52b",
]

_MODULES: Dict[str, str] = {a: a.replace("-", "_").replace(".", "_")
                            for a in ARCHS}


def get_config(arch: str, reduced: bool = False,
               dbpim_mode: str = None, prefill_exact: bool = None):
    """Load the ModelConfig for `arch`. reduced=True returns the small
    smoke-test variant of the same family. dbpim_mode selects the DB-PIM
    kernel mode (one of models.config.KERNEL_MODES) the serving stack
    packs for: launch.serve builds uniform-MAXB stacked tables
    (sparsity.sparse_linear.build_stacked_tables) and threads them
    through every family's segment scans, so "joint"/"bit" (INT8/FTA
    payload) and "value" (bf16 payload, value level only) change the
    compiled serving HLO end to end. prefill_exact=True forces SSM chunked
    prefill onto the exact per-token recurrence (bit-identical to
    decode, C x the projection traffic) instead of the default parallel
    SSD form (one stacked-weight read per chunk, tolerance-equivalent —
    models.ssm.PARALLEL_PREFILL_ATOL)."""
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; choose from {ARCHS}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    cfg = mod.reduced_config() if reduced else mod.config()
    if dbpim_mode is not None:
        if dbpim_mode not in KERNEL_MODES:
            raise KeyError(f"unknown dbpim_mode {dbpim_mode!r}; "
                           f"choose from {KERNEL_MODES}")
        cfg = cfg.scaled(dbpim=True, dbpim_mode=dbpim_mode)
    if prefill_exact is not None:
        cfg = cfg.scaled(prefill_exact=prefill_exact)
    return cfg


def list_archs() -> List[str]:
    return list(ARCHS)
