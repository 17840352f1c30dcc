"""Gradient compression: block-wise INT8 quantization with error feedback.

Distributed-optimization trick for the DP all-reduce: gradients are
quantized to INT8 (4x less all-reduce traffic than f32) with per-256-block
scales; the quantization residual is carried in an error-feedback buffer
so the compression bias vanishes over steps (Seide et al. / EF-SGD line).

`compress_tree` (stateless, build_train_step's grad_compression) quantizes and
immediately dequantizes — the all-reduce then operates on values that are
exactly representable in INT8 blocks, modeling the traffic reduction while
keeping the pjit program simple. `EFCompressor` is the stateful
error-feedback variant for the real training loop.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

BLOCK = 256


def _quant_dequant(g: jnp.ndarray) -> jnp.ndarray:
    if g.size < BLOCK:
        return g
    flat = g.reshape(-1)
    pad = (-flat.size) % BLOCK
    fp = jnp.pad(flat, (0, pad)).reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(fp), axis=1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(fp / scale), -127, 127)
    out = (q * scale).reshape(-1)[:flat.size]
    return out.reshape(g.shape).astype(g.dtype)


def compress_tree(grads):
    return jax.tree_util.tree_map(_quant_dequant, grads)


class EFCompressor(NamedTuple):
    """Error-feedback state: one residual buffer per gradient leaf."""
    residual: dict

    @staticmethod
    def init(grads) -> "EFCompressor":
        return EFCompressor(jax.tree_util.tree_map(
            lambda g: jnp.zeros_like(g, jnp.float32), grads))

    def compress(self, grads):
        def one(g, r):
            target = g.astype(jnp.float32) + r
            q = _quant_dequant(target)
            return q.astype(g.dtype), target - q
        pairs = jax.tree_util.tree_map(one, grads, self.residual)
        comp = jax.tree_util.tree_map(lambda p: p[0], pairs,
                                      is_leaf=lambda x: isinstance(x, tuple))
        res = jax.tree_util.tree_map(lambda p: p[1], pairs,
                                     is_leaf=lambda x: isinstance(x, tuple))
        return comp, EFCompressor(res)
