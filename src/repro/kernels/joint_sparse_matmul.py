"""Joint value-level x bit-level sparse matmul — the fused DB-PIM kernel.

This is the kernel the paper's headline gain rests on: value sparsity and
bit sparsity are exploited on the SAME layer, in one pass. The weight
operand is simultaneously

  * COMPACTED (value level): for every N-column tile only its surviving
    K-blocks are stored — the pruned 1 x alpha blocks of the paper's
    sparse allocation network become MXU-tile-granular skipped blocks, so
    HBM weight traffic and MXU work scale with (1 - value_sparsity);
  * QUANTIZED (bit level): the surviving block payload is INT8 (the FTA
    projection makes the weights exactly representable as INT8 x one
    per-filter scale), so each surviving byte is 2x cheaper than bf16 and
    4x cheaper than f32.

Net weight traffic: ``(1 - value_sparsity) * 0.5`` of dense bf16.

Packed layout (produced by ``ops.pack_joint_sparse``):

  w_blocks : (NT, MAXB, BK, BN) int8   surviving K-blocks per N tile.
                                       Slots beyond a tile's real block
                                       count are ZERO payload (see below).
  idx      : (NT, MAXB) int32          source K-block index per slot;
                                       padded slots hold 0.
  scales   : (1, N) float32            per-filter (output-channel) scale;
                                       W_dense = scatter(w_blocks) * scales.

Kernel: grid (M/BM, NT, MAXB) with ``idx`` scalar-prefetched so the x
BlockSpec gathers the activation K-block matching each stored weight
block. The INT8 payload is dequantized tile-wise in VMEM to the
activation dtype, accumulated in fp32 across the MAXB-innermost grid dim,
and the per-filter scale is applied ONCE at the final store (scales
commute with the K reduction). Padded slots multiply an all-zero INT8
block — they contribute exactly 0 to the fp32 accumulator regardless of
which activation block ``idx`` points at.

Equivalence guarantee: on FTA-projected weights the INT8 x scale grid is
exact, so for f32 activations the kernel matches the dense reference
(``ref.joint_sparse_matmul_ref``) to fp32 accumulation tolerance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._compat import resolve_interpret

BM, BK, BN = 128, 128, 128


def _kernel(idx_ref, x_ref, w_ref, scale_ref, o_ref, acc_ref, *, maxb: int):
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # VMEM dequant: int8 -> activation dtype (int8 values are exact in
    # bf16 and f32). Padded slots are all-zero payload => contribute 0.
    w = w_ref[...].astype(x_ref.dtype)
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(b == maxb - 1)
    def _store():
        o_ref[...] = (acc_ref[...] * scale_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def _cost(M, K, NT, MAXB, bk, bn, x_itemsize, out_itemsize, w_itemsize):
    """Static CostEstimate: work scales with the STORED blocks only."""
    stored = NT * MAXB * bk * bn
    return pl.CostEstimate(
        flops=2 * M * stored,
        bytes_accessed=(M * K * x_itemsize        # activations
                        + stored * w_itemsize     # payload (int8/bf16)
                        + NT * MAXB * 4           # index table
                        + NT * bn * 4             # scales
                        + M * NT * bn * out_itemsize),
        transcendentals=0,
    )


def joint_sparse_matmul(x, w_blocks, idx, scales, *, out_dtype=None,
                        bm: int = BM, interpret: bool = None):
    """x (M, K) @ joint-packed W -> (M, N). N = NT * BN.

    ``w_blocks`` (NT, MAXB, BK, BN) int8, ``idx`` (NT, MAXB) int32,
    ``scales`` (1, N) f32 — see module docstring for the layout contract.
    ``bm`` may be any sublane multiple (8 f32 / 16 bf16) — the decode path
    uses a small row tile so a batch-4 step does not pad to 128 MXU rows.
    interpret=None resolves to the backend default (compile on TPU,
    interpret elsewhere; REPRO_PALLAS_INTERPRET overrides). Resolution
    happens OUTSIDE the jit boundary so the resolved bool is the cache
    key — flipping the env var mid-process cannot hit a stale executable.
    """
    return _joint_sparse_matmul(x, w_blocks, idx, scales,
                                out_dtype=out_dtype, bm=bm,
                                interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "bm", "interpret"))
def _joint_sparse_matmul(x, w_blocks, idx, scales, *, out_dtype,
                         bm: int, interpret: bool):
    M, K = x.shape
    NT, MAXB, bk, bn = w_blocks.shape
    N = NT * bn
    if M % bm:
        raise ValueError(f"M={M} must be a multiple of bm={bm} "
                         "(ops.joint_dense pads ragged batches)")
    out_dtype = x.dtype if out_dtype is None else out_dtype
    grid = (M // bm, NT, MAXB)

    return pl.pallas_call(
        functools.partial(_kernel, maxb=MAXB),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk),
                             lambda m, n, b, idx_ref: (m, idx_ref[n, b])),
                pl.BlockSpec((None, None, bk, bn),
                             lambda m, n, b, idx_ref: (n, b, 0, 0)),
                pl.BlockSpec((1, bn), lambda m, n, b, idx_ref: (0, n)),
            ],
            out_specs=pl.BlockSpec((bm, bn),
                                   lambda m, n, b, idx_ref: (m, n)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        cost_estimate=_cost(M, K, NT, MAXB, bk, bn, x.dtype.itemsize,
                            jnp.dtype(out_dtype).itemsize,
                            w_blocks.dtype.itemsize),
    )(idx, x, w_blocks, scales)
