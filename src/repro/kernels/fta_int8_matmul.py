"""FTA/INT8 weight matmul Pallas TPU kernel — the bit-level sparsity path.

The PIM macro stores only Comp patterns; on TPU the equivalent saving is
bandwidth: FTA-projected weights are EXACTLY representable as INT8 x
per-filter scale, so they stay INT8 in HBM (2x less weight traffic than
bf16 — decode is weight-bound, so this is ~2x decode speedup) and are
dequantized tile-by-tile in VMEM before hitting the MXU in bf16.

The per-filter scale is applied once per output tile after the K
reduction (scales commute with the K sum), not per K-block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._compat import resolve_interpret

BM, BK, BN = 128, 512, 128


def _kernel(x_ref, w_ref, scale_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(jnp.bfloat16)      # VMEM dequant: int8 -> bf16
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.bfloat16), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        o_ref[...] = (acc_ref[...] * scale_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def fta_int8_matmul(x, w_q, scales, *, out_dtype=jnp.bfloat16,
                    interpret: bool = None):
    """x (M, K) bf16/f32 @ (w_q (K, N) int8 * scales (1, N) f32) -> (M, N).

    interpret=None resolves to the backend default (compile on TPU),
    outside the jit boundary so the resolved bool is the cache key."""
    return _fta_int8_matmul(x, w_q, scales, out_dtype=out_dtype,
                            interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype"))
def _fta_int8_matmul(x, w_q, scales, *, out_dtype, interpret: bool):
    M, K = x.shape
    _, N = w_q.shape
    nk = K // BK
    grid = (M // BM, N // BN, nk)

    # weight traffic is the INT8 bytes (the bit-level saving vs bf16)
    cost = pl.CostEstimate(
        flops=2 * M * K * N,
        bytes_accessed=(M * K * x.dtype.itemsize + K * N + N * 4
                        + M * N * jnp.dtype(out_dtype).itemsize),
        transcendentals=0)

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BK), lambda m, n, k: (m, k)),
            pl.BlockSpec((BK, BN), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, BN), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        cost_estimate=cost,
    )(x, w_q, scales)
