"""Block-sparse matmul Pallas TPU kernel — the value-level sparsity path.

DB-PIM's sparse allocation network skips 1 x alpha pruned weight blocks.
On TPU the same insight maps to MXU-tile-granular block sparsity: weights
are stored COMPACTED — for every N-column tile only its surviving K-blocks
— plus an index table. HBM traffic and MXU work scale with (1 - sparsity),
exactly like the PIM array only storing surviving rows.

Layout (packed by ops.pack_block_sparse):
  w_blocks: (NT, MAXB, BK, BN)  surviving K-blocks per N tile, zero-padded
  idx:      (NT, MAXB) int32    source K-block index per slot (0-padded)

Kernel: grid (M/BM, NT, MAXB) with the K-block index scalar-prefetched so
the x BlockSpec can gather the matching activation block. Padded slots
multiply zero blocks (adds 0). The accumulator lives in the output tile
across the MAXB-innermost grid dim.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._compat import resolve_interpret

BM, BK, BN = 128, 128, 128


def _kernel(idx_ref, x_ref, w_ref, o_ref, acc_ref, *, maxb: int):
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(b == maxb - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def block_sparse_matmul(x, w_blocks, idx, *, interpret: bool = None):
    """x (M, K) @ block-sparse W -> (M, N). N = NT * BN.

    interpret=None resolves to the backend default (compile on TPU),
    outside the jit boundary so the resolved bool is the cache key."""
    return _block_sparse_matmul(x, w_blocks, idx,
                                interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _block_sparse_matmul(x, w_blocks, idx, *, interpret: bool):
    M, K = x.shape
    NT, MAXB, _, _ = w_blocks.shape
    N = NT * BN
    grid = (M // BM, NT, MAXB)

    # work scales with the STORED blocks only (the value-sparsity saving)
    stored = NT * MAXB * BK * BN
    cost = pl.CostEstimate(
        flops=2 * M * stored,
        bytes_accessed=(M * K * x.dtype.itemsize
                        + stored * w_blocks.dtype.itemsize
                        + NT * MAXB * 4 + M * N * x.dtype.itemsize),
        transcendentals=0)

    return pl.pallas_call(
        functools.partial(_kernel, maxb=MAXB),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((BM, BK),
                             lambda m, n, b, idx_ref: (m, idx_ref[n, b])),
                pl.BlockSpec((None, None, BK, BN),
                             lambda m, n, b, idx_ref: (n, b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((BM, BN), lambda m, n, b, idx_ref: (m, n)),
            scratch_shapes=[pltpu.VMEM((BM, BN), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        cost_estimate=cost,
    )(idx, x, w_blocks)
