"""Joint value x bit sparse kernel: pack/unpack round-trip, kernel-vs-
dense-reference equivalence across sparsity ratios and odd shapes, the
padded-slot zero guard, and the value-only (bf16 payload) layout through
the same kernel.

Property tests need hypothesis; everything else runs without it.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.joint_sparse_matmul import joint_sparse_matmul

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


_tile_mask = ops.random_tile_mask


def _dense_quant(w, mask):
    """Independent dense recomputation of the pack's quantization step."""
    from repro.core import fta
    m = np.asarray(mask, np.int32)
    amax = np.abs(w * m).max(axis=0)
    scales = (amax / 127.0 + 1e-12).astype(np.float32)
    q = np.clip(np.round(w * m / scales), -127, 127).astype(np.int32)
    q, _ = fta.fta_quantize(q, m)
    return np.asarray(q) * m, scales.reshape(1, -1)


# ------------------------------------------------- pack/unpack round-trip --

@pytest.mark.parametrize("K,N", [(256, 256), (200, 100), (512, 384),
                                 (128, 130)])
@pytest.mark.parametrize("sparsity", [0.0, 0.5, 0.9])
def test_pack_unpack_roundtrip(K, N, sparsity):
    rng = np.random.default_rng(0)
    w = rng.laplace(0, 0.02, (K, N)).astype(np.float32)
    mask = _tile_mask(rng, K, N, sparsity)
    packed = ops.pack_joint_sparse(w, mask)
    got = ops.unpack_joint_sparse(packed)
    q, scales = _dense_quant(w, mask)
    np.testing.assert_allclose(got, q.astype(np.float32) * scales,
                               rtol=0, atol=1e-7)


def test_pack_compacts_dead_tiles():
    rng = np.random.default_rng(1)
    K, N = 512, 256
    mask = np.zeros((K, N), np.int32)
    mask[:128] = 1                       # 1 of 4 K-blocks survives
    w = rng.normal(0, 0.02, (K, N)).astype(np.float32)
    packed = ops.pack_joint_sparse(w, mask)
    assert packed.w_blocks.shape[1] == 1             # MAXB == survivors
    assert packed.w_blocks.dtype == jnp.int8         # bit-level payload
    assert ops.joint_storage_bytes(packed) < 2 * K * N * (1 / 4)


if HAVE_HYPOTHESIS:
    @given(st.integers(min_value=1, max_value=40),
           st.integers(min_value=1, max_value=40),
           st.integers(min_value=0, max_value=2 ** 31 - 1))
    @settings(max_examples=25, deadline=None)
    def test_pack_unpack_roundtrip_property(K, N, seed):
        rng = np.random.default_rng(seed)
        w = rng.laplace(0, 0.05, (K, N)).astype(np.float32)
        mask = (rng.random((K, N)) > 0.3).astype(np.int32)
        packed = ops.pack_joint_sparse(w, mask, bk=8, bn=8)
        got = ops.unpack_joint_sparse(packed)
        q, scales = _dense_quant(w, mask)
        np.testing.assert_allclose(got, q.astype(np.float32) * scales,
                                   rtol=0, atol=1e-7)


# ------------------------------------------- kernel vs dense reference ----

@pytest.mark.parametrize("M,K,N", [(128, 256, 256), (96, 200, 100),
                                   (1, 384, 130), (256, 512, 384)])
@pytest.mark.parametrize("sparsity", [0.0, 0.5, 0.9])
def test_joint_matches_dense_reference(M, K, N, sparsity):
    """The acceptance guarantee: on FTA-projected weights the joint kernel
    equals the dense reference to fp32 accumulation tolerance."""
    rng = np.random.default_rng(2)
    w = rng.laplace(0, 0.02, (K, N)).astype(np.float32)
    mask = _tile_mask(rng, K, N, sparsity)
    packed = ops.pack_joint_sparse(w, mask)
    x = jnp.asarray(rng.normal(0, 1, (M, K)), jnp.float32)
    got = np.asarray(ops.joint_dense(x, packed), np.float32)
    q, scales = _dense_quant(w, mask)
    want = np.asarray(ref.joint_sparse_matmul_ref(x, q, mask, scales),
                      np.float32)
    assert got.shape == (M, N)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * max(np.abs(want).max(), 1.0))


def test_joint_bf16_activations():
    rng = np.random.default_rng(3)
    w = rng.laplace(0, 0.02, (256, 128)).astype(np.float32)
    packed = ops.pack_joint_sparse(w, _tile_mask(rng, 256, 128, 0.5))
    x = jnp.asarray(rng.normal(0, 1, (128, 256)), jnp.bfloat16)
    got = ops.joint_dense(x, packed)
    assert got.dtype == jnp.bfloat16
    want = ref.joint_packed_ref(x, packed)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=0.3)


def test_joint_3d_activations():
    rng = np.random.default_rng(4)
    w = rng.laplace(0, 0.02, (256, 128)).astype(np.float32)
    packed = ops.pack_joint_sparse(w, _tile_mask(rng, 256, 128, 0.5))
    x = jnp.asarray(rng.normal(0, 1, (2, 32, 256)), jnp.float32)
    got = ops.joint_dense(x, packed)
    assert got.shape == (2, 32, 128)
    flat = ops.joint_dense(x.reshape(64, 256), packed)
    np.testing.assert_array_equal(np.asarray(got).reshape(64, 128),
                                  np.asarray(flat))


# ------------------------------------------------- padded-slot guard ------

def test_padded_slots_contribute_exactly_zero():
    """Tiles with fewer than MAXB surviving blocks pad with idx=0 and a
    zero INT8 payload; whatever activation block the padded slot gathers,
    its contribution must be exactly 0."""
    rng = np.random.default_rng(5)
    bk = bn = bm = 128
    # column tile 0 keeps K-blocks {0, 1}; column tile 1 keeps only {1}
    # => MAXB = 2 and tile 1 slot 1 is a padded slot pointing at block 0.
    mask = np.zeros((2 * bk, 2 * bn), np.int32)
    mask[:, :bn] = 1
    mask[bk:, bn:] = 1
    w = rng.laplace(0, 0.02, mask.shape).astype(np.float32)
    packed = ops.pack_joint_sparse(w, mask)
    assert packed.w_blocks.shape[1] == 2
    assert int(packed.nblocks[1]) == 1
    assert int(packed.idx[1, 1]) == 0                  # padded slot
    assert not np.any(np.asarray(packed.w_blocks)[1, 1])  # zero payload

    # huge activations in K-block 0: any padded-slot leakage would blow up
    # the second output tile far beyond fp32 rounding of the true value.
    x = np.ones((bm, 2 * bk), np.float32)
    x[:, :bk] = 1e6
    got = np.asarray(joint_sparse_matmul(
        jnp.asarray(x), packed.w_blocks, packed.idx, packed.scales))
    want = x @ ops.unpack_joint_sparse(packed)
    # tolerance scaled to the 1e6-magnitude probe (fp32 accumulation
    # order differs between kernel and reference); real leakage would be
    # off by ~1e6 x weight scale, orders of magnitude beyond this.
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())
    # the decisive guard: tile-1 columns depend ONLY on K-block 1, so
    # flipping the block-0 activations the padded slot gathers must leave
    # them BIT-IDENTICAL (0 payload x anything == exact fp32 zero).
    x2 = x.copy()
    x2[:, :bk] = -1e6
    got2 = np.asarray(joint_sparse_matmul(
        jnp.asarray(x2), packed.w_blocks, packed.idx, packed.scales))
    np.testing.assert_array_equal(got[:, bn:], got2[:, bn:])


# ------------------------------------------- value-only (bf16) payload ----

@pytest.mark.parametrize("M,K,N", [(128, 256, 128), (256, 512, 256),
                                   (128, 128, 384)])
@pytest.mark.parametrize("sparsity", [0.0, 0.5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_value_payload_matches_reference(M, K, N, sparsity, dtype):
    """The value-only layout (bf16 payload, unit scales) through the joint
    kernel: the stored blocks are exactly bf16(w * tile_mask), and the
    kernel matches the dense matmul over them."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (M, K)), dtype)
    w = rng.normal(0, 1, (K, N)).astype(np.float32)
    tile_alive = rng.random((K // 128, N // 128)) > sparsity
    tile_mask = np.repeat(np.repeat(tile_alive, 128, 0), 128, 1)
    p = ops.pack_joint_sparse_stacked(w[None], masks=tile_mask[None],
                                      payload="bf16")
    layer = ops.slice_joint_stacked(p, 0)
    assert layer.w_blocks.dtype == jnp.bfloat16
    want_w = np.asarray(jnp.asarray(w * tile_mask, jnp.bfloat16), np.float32)
    np.testing.assert_array_equal(ops.unpack_joint_sparse(layer), want_w)
    got = ops.joint_dense(x, layer)
    want = ref.joint_packed_ref(x, layer)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 8)


def test_value_payload_stores_only_alive_blocks():
    """At 75 % dead tiles (one K-block of four per column survives) the
    bf16 payload stores one block per column: MAXB == 1."""
    rng = np.random.default_rng(1)
    w = rng.normal(0, 1, (512, 256)).astype(np.float32)
    tile_alive = np.zeros((4, 2), bool)
    tile_alive[0, :] = True
    tile_mask = np.repeat(np.repeat(tile_alive, 128, 0), 128, 1)
    p = ops.pack_joint_sparse_stacked(w[None], masks=tile_mask[None],
                                      payload="bf16")
    assert p.maxb == 1
    assert p.w_blocks.dtype == jnp.bfloat16


def test_registry_selects_joint_mode():
    from repro.configs.registry import get_config
    cfg = get_config("tinyllama-1.1b", reduced=True, dbpim_mode="joint")
    assert cfg.dbpim and cfg.dbpim_mode == "joint"
    with pytest.raises(KeyError):
        get_config("tinyllama-1.1b", dbpim_mode="nope")


# ------------------------------------------------- cost accounting --------

def test_jaxpr_cost_charges_packed_traffic():
    """The roofline walker must charge the pallas_call its stored-bytes
    traffic and the CostEstimate FLOPs (2 flops per stored INT8 weight
    per activation row)."""
    from repro.runtime.jaxpr_cost import analyze
    rng = np.random.default_rng(7)
    w = rng.laplace(0, 0.02, (512, 256)).astype(np.float32)
    packed = ops.pack_joint_sparse(w, _tile_mask(rng, 512, 256, 0.5))
    x = jnp.zeros((128, 512), jnp.float32)
    cost = analyze(lambda x: ops.joint_dense(x, packed), x)
    stored = int(packed.w_blocks.size)
    assert cost["pallas_flops"] == 2 * 128 * stored
    assert cost["pallas_bytes"] >= stored              # payload charged...
    assert cost["pallas_bytes"] < stored + 4 * x.size + 4 * 128 * 256 + 4096
    assert cost["dot_flops"] >= cost["pallas_flops"]
