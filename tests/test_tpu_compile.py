"""Compile for a described TPU v5e, without a chip attached.

The TPU compiler is installed next to jax, so the kernel and the serving
step compile here for a ``v5e:2x2`` topology that is described, not
present: what the chip's compiler would refuse (a misaligned block, too
much VMEM, an unpartitionable kernel) fails here at no chip time. Nothing
runs, so these tests say nothing about results or speed.

The topology is described inside a fixture (never at import time): only
one process may hold the TPU library, and the test workers each import
every test file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels._compat import INTERPRET_ENV
from repro.kernels.joint_sparse_matmul import _joint_sparse_matmul
from repro.launch.steps import build_step
from repro.models import init_cache, init_params
from repro.sparsity.sparse_linear import (build_stacked_tables,
                                          strip_packed_projections)

ARCH = "stablelm-1.6b"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("k,n", [(2048, 2048), (2048, 5632), (5632, 2048)])
@pytest.mark.parametrize("bm,dtype", [(16, jnp.bfloat16),   # decode rows
                                      (128, jnp.bfloat16)])  # prefill rows
def test_joint_kernel_compiles_at_stablelm_widths(one_chip, k, n, bm, dtype):
    """stablelm-1.6b's projections, packed at the default value sparsity
    (column-balanced: kt - round(0.6 kt) blocks per column)."""
    kt, nt = k // ops.BK, n // ops.BN
    maxb = kt - round(0.6 * kt)
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((bm, k), dtype),
        jax.ShapeDtypeStruct((nt, maxb, ops.BK, ops.BN), jnp.int8),
        jax.ShapeDtypeStruct((nt, maxb), jnp.int32),
        jax.ShapeDtypeStruct((1, n), jnp.float32)))
    compiled = _joint_sparse_matmul.lower(
        *args, out_dtype=None, bm=bm, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def one_layer_joint():
    """One published-width stablelm layer (embedding and head included),
    packed and stripped the way the serving CLI does it."""
    cfg = get_config(ARCH, dbpim_mode="joint").scaled(n_layers=1)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tables = build_stacked_tables(params, cfg)
    return cfg, strip_packed_projections(params, cfg), tables


def test_decode_step_compiles_with_kernel_and_tables_as_arguments(
        one_chip, one_layer_joint, monkeypatch):
    monkeypatch.setenv(INTERPRET_ENV, "0")
    cfg, params, tables = one_layer_joint
    n_slots, max_len = 8, 1024
    cache = jax.eval_shape(lambda: init_cache(cfg, n_slots, max_len))
    cache["pos"] = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
    cache["attn"]["pos"] = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
    step, _ = build_step(cfg, None, "decode")
    args = _on(one_chip, (params, tables, cache,
                          jax.ShapeDtypeStruct((n_slots, 1), jnp.int32),
                          jax.ShapeDtypeStruct((n_slots,), jnp.bool_)))
    compiled = jax.jit(step, donate_argnums=(2,)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    table_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(tables))
    code = compiled.memory_analysis().generated_code_size_in_bytes
    # the int8 payload rides in as an argument, not as baked constants
    assert code < table_bytes, (code, table_bytes)
