"""Unified model zoo for the 10 assigned architectures."""

from .config import ModelConfig, param_count  # noqa: F401
from .transformer import init_params, forward, loss_fn, encode  # noqa: F401
from .decode import (decode_chunk, decode_step, init_cache, merge_slots,
                     prefill, reset_slots)  # noqa: F401
