"""Pure-jnp oracles for every kernel in this package."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.dyadic import unpack_terms


def joint_sparse_matmul_ref(x, q_dense, mask, scales,
                            out_dtype=jnp.float32):
    """Oracle for joint_sparse_matmul: dense matmul against the pruned,
    dequantized INT8 weights (q * mask * per-filter scale)."""
    w = (jnp.asarray(q_dense, jnp.float32) * jnp.asarray(mask, jnp.float32)
         * jnp.asarray(scales, jnp.float32))
    return (x.astype(jnp.float32) @ w).astype(out_dtype)


def joint_packed_ref(x, packed, out_dtype=jnp.float32):
    """Oracle from the packed artifact itself (via unpack_joint_sparse)."""
    from . import ops
    w = jnp.asarray(ops.unpack_joint_sparse(packed))
    return (x.astype(jnp.float32) @ w).astype(out_dtype)


def dbmu_matmul_ref(x_int8, packed):
    """Oracle for dbmu_sim: integer matmul against the unpacked weights."""
    w = unpack_terms(np.asarray(packed))              # (K, N) int32
    return np.asarray(x_int8, np.int64) @ w.astype(np.int64)
