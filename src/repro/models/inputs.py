"""Concrete random batches for smoke tests / examples: a training batch
(tokens, labels and any frontend input the family needs) and one decode
token per row."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import dtype_of


def make_train_batch(cfg: ModelConfig, batch: int, seq: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    out = {
        "tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32),
        "labels": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32),
    }
    if cfg.is_encdec:
        out["frames"] = jnp.asarray(
            rng.normal(0, 1, (batch, cfg.encoder_seq, cfg.d_model)),
            dtype_of(cfg))
    if cfg.frontend == "vision_stub":
        out["frontend"] = jnp.asarray(
            rng.normal(0, 1, (batch, cfg.n_patches, cfg.d_model)),
            dtype_of(cfg))
    return out


def make_decode_token(cfg: ModelConfig, batch: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, 1)), jnp.int32)
