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
import re

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


# -------------------------------------------- the K/V cache stays in place --

_DEF = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
_ARR = re.compile(r"(\w+)\[([\d,]*)\]")
_OPERAND = re.compile(r"%([\w.\-]+)")


def _hlo_defs(text):
    """name -> (result arrays [(dtype, elements)], opcode, operand names)
    for every instruction of an optimized HLO module, fused computations
    included (instruction names are unique within a module)."""
    defs = {}
    for line in text.splitlines():
        m = _DEF.match(line)
        if not m:
            continue
        name, result, op, rest = m.groups()
        arrays = [(dt, int(np.prod([int(d) for d in dims.split(",") if d])))
                  for dt, dims in _ARR.findall(result)]
        defs[name] = (arrays, op, _OPERAND.findall(rest.split("), ")[0]))
    return defs


def _whole_kv_ops(text, kv_elems, dtype="bf16"):
    """Copies and selects whose result, and dynamic-update-slices whose
    UPDATE, is a whole layer's or the whole stack's K or V, in any layout
    or shape. The in-place write of a step's own rows (a scatter, or a
    dynamic-update-slice of a few rows into the stack) passes."""
    defs = _hlo_defs(text)

    def whole(arrays):
        return any(dt == dtype and n in kv_elems for dt, n in arrays)

    bad = []
    for name, (arrays, op, operands) in defs.items():
        if op in ("copy", "select") and whole(arrays):
            bad.append(name)
        elif op == "dynamic-update-slice" and len(operands) > 1 and \
                operands[1] in defs and whole(defs[operands[1]][0]):
            bad.append(name)
    return bad


def test_serving_steps_write_the_kv_cache_in_place(one_chip, monkeypatch):
    """Two published-width stablelm layers, 16 slots x 1024, dense: the
    decode step and the prefill chunk step update the donated K/V cache
    where it lies. No copy, select or dynamic-update-slice moves a whole
    layer's or the whole stack's K/V (the per-layer relayout copies and
    the whole-cache select this replaced), and neither step's scratch
    holds a layer's K or V. A prefill chunk's scratch does hold its
    float32 attention scores (slots x heads x chunk x cache positions)."""
    monkeypatch.setenv(INTERPRET_ENV, "0")
    cfg = get_config(ARCH, dbpim_mode="dense").scaled(n_layers=2)
    n_slots, max_len, chunk = 16, 1024, 128
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: init_cache(cfg, n_slots, max_len))
    cache["pos"] = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
    cache["attn"]["pos"] = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
    layer = n_slots * max_len * cfg.n_kv_heads * cfg.hd
    kv_elems = {layer, cfg.n_layers * layer}
    layer_bytes = 2 * layer
    scores = 4 * n_slots * cfg.n_heads * chunk * max_len
    for kind, last, scratch in (
            ("decode", (jax.ShapeDtypeStruct((n_slots, 1), jnp.int32),
                        jax.ShapeDtypeStruct((n_slots,), jnp.bool_)), 0),
            ("prefill_chunk",
             (jax.ShapeDtypeStruct((n_slots, chunk), jnp.int32),
              jax.ShapeDtypeStruct((n_slots,), jnp.int32)), scores)):
        step, _ = build_step(cfg, None, kind)
        args = _on(one_chip, (params, None, cache) + last)
        compiled = jax.jit(step, donate_argnums=(2,)).lower(*args).compile()
        assert _whole_kv_ops(compiled.as_text(), kv_elems) == [], kind
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < scratch + layer_bytes, (kind, temp)
