"""Stacked joint-sparse serving path: uniform-MAXB pack round-trip,
scan-stacked forward/decode vs the dense FTA reference on reduced
tinyllama (dense family) and mamba2 (SSM family), the ragged-batch
small-M decode tile, and the serving-graph/weight-traffic guarantees.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels._compat import INTERPRET_ENV, default_interpret
from repro.models import decode_step, forward, init_cache, init_params
from repro.runtime.jaxpr_cost import analyze
from repro.sparsity.sparse_linear import (build_stacked_tables,
                                          reconstruct_stacked_params,
                                          strip_packed_projections)

ARCHS = ("tinyllama-1.1b", "mamba2-1.3b")


def _quant_ref(w, mask):
    """Independent dense recomputation of the pack's quantization step."""
    from repro.core import fta
    m = np.asarray(mask, np.int32)
    amax = np.abs(w * m).max(axis=0)
    scales = (amax / 127.0 + 1e-12).astype(np.float32)
    q = np.clip(np.round(w * m / scales), -127, 127).astype(np.int32)
    q, _ = fta.fta_quantize(q, m)
    return (np.asarray(q) * m).astype(np.float32) * scales.reshape(1, -1)


def _setup(arch, vs=0.5, dtype="float32", mode="joint"):
    cfg = get_config(arch, reduced=True, dbpim_mode=mode).scaled(
        dtype=dtype, dbpim_value_sparsity=vs)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tables = build_stacked_tables(params, cfg, bk=32, bn=32)
    assert tables is not None
    return cfg, params, tables


# ------------------------------------------------ stacked pack layout -----

def test_stacked_pack_shares_maxb_and_zero_pads_short_layers():
    """Ragged per-layer masks: MAXB is the max survivor count over the
    whole stack; layers with fewer survivors pad with zero-payload slots
    (the exact-zero contribution the kernel guarantees)."""
    rng = np.random.default_rng(0)
    L, K, N, bk = 3, 128, 64, 32
    masks = np.ones((L, K, N), np.int32)
    masks[0, bk:] = 0                      # layer 0 keeps 1 of 4 K-blocks
    masks[1, 2 * bk:] = 0                  # layer 1 keeps 2
    ws = rng.laplace(0, 0.02, (L, K, N)).astype(np.float32)
    p = ops.pack_joint_sparse_stacked(ws, masks, bk=bk, bn=32)
    assert p.maxb == 4                     # layer 2 keeps all 4
    nb = np.asarray(p.nblocks)
    assert nb[0].max() == 1 and nb[1].max() == 2 and (nb[2] == 4).all()
    wb = np.asarray(p.w_blocks)
    for l in range(L):
        for n_t in range(wb.shape[1]):
            assert not wb[l, n_t, nb[l, n_t]:].any()   # padded slots zero
    # round-trip: each layer reproduces its own pruned/quantized dense ref
    dense = ops.unpack_joint_sparse_stacked(p)
    assert dense.shape == (L, K, N)
    for l in range(L):
        np.testing.assert_allclose(dense[l], _quant_ref(ws[l], masks[l]),
                                   rtol=0, atol=1e-7)


@pytest.mark.parametrize("K,N", [(256, 256), (200, 100)])
def test_stacked_balanced_prune_has_no_padded_slots(K, N):
    """Column-balanced pruning => every (layer, column) stores exactly
    MAXB real blocks: the stacked layout carries zero padding and stored
    bytes scale with (1 - vs) exactly."""
    rng = np.random.default_rng(1)
    ws = rng.laplace(0, 0.02, (4, K, N)).astype(np.float32)
    p = ops.pack_joint_sparse_stacked(ws, value_sparsity=0.5, bk=32, bn=32)
    nb = np.asarray(p.nblocks)
    assert (nb == p.maxb).all()
    kt = p.k_pad // 32
    assert p.maxb == kt - int(round(0.5 * kt))
    assert p.w_blocks.shape[2] == p.maxb


def test_stacked_pack_rejects_bad_shapes():
    with pytest.raises(ValueError):
        ops.pack_joint_sparse_stacked(np.zeros((4, 4)), value_sparsity=0.5)


# ------------------------------------- forward / decode vs reference ------

@pytest.mark.parametrize("arch", ARCHS)
def test_stacked_forward_matches_dense_fta_reference(arch):
    """The acceptance guarantee: the scan-stacked joint forward equals a
    plain forward over the FTA-reconstructed (pruned + dequantized)
    weights to fp32 tolerance — for the dense and SSM families."""
    cfg, params, tables = _setup(arch)
    recon = reconstruct_stacked_params(params, tables, cfg)
    toks = jnp.asarray(np.random.default_rng(2).integers(
        1, cfg.vocab_size, (2, 32)), jnp.int32)
    got = forward(params, toks, cfg, tables=tables)
    want = forward(recon, toks, cfg)
    assert got.shape == want.shape
    tol = 1e-4 * max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    # and the compressed path is genuinely different from uncompressed
    assert float(jnp.max(jnp.abs(want - forward(params, toks, cfg)))) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_ragged_batch_decode_step_matches_reference(arch):
    """Batch-4 decode (ragged M, far below the 128 MXU row tile) through
    the stacked tables: logits and caches match the FTA reference."""
    cfg, params, tables = _setup(arch)
    recon = reconstruct_stacked_params(params, tables, cfg)
    cache = init_cache(cfg, 4, 16)
    tok = jnp.asarray([[3], [5], [7], [11]], jnp.int32)
    got, cache_j = decode_step(params, cache, tok, cfg, tables=tables)
    want, cache_r = decode_step(recon, cache, tok, cfg)
    tol = 1e-4 * max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    # serving drops the dense projection copies: placeholders + tables
    # must produce bit-identical logits (mm never reads the weight arg)
    stripped = strip_packed_projections(params, cfg)
    sbytes = sum(l.size * l.dtype.itemsize
                 for l in jax.tree_util.tree_leaves(stripped))
    pbytes = sum(l.size * l.dtype.itemsize
                 for l in jax.tree_util.tree_leaves(params))
    assert sbytes < pbytes
    got_s, _ = decode_step(stripped, cache, tok, cfg, tables=tables)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(got))
    for leaf_j, leaf_r in zip(jax.tree_util.tree_leaves(cache_j),
                              jax.tree_util.tree_leaves(cache_r)):
        np.testing.assert_allclose(
            np.asarray(leaf_j, np.float32), np.asarray(leaf_r, np.float32),
            atol=1e-4 * max(float(np.abs(np.asarray(leaf_r)).max()), 1.0))


def test_small_m_row_tile_selection():
    """The decode-tuned tile: small batches pad to the sublane minimum
    (8 f32 / 16 bf16), not to 128 MXU rows; large M keeps full tiles."""
    assert ops.pick_row_tile(4, jnp.float32) == 8
    assert ops.pick_row_tile(4, jnp.bfloat16) == 16
    assert ops.pick_row_tile(8, jnp.float32) == 8
    assert ops.pick_row_tile(100, jnp.float32) == 104
    assert ops.pick_row_tile(128, jnp.float32) == 128
    assert ops.pick_row_tile(1000, jnp.bfloat16) == 128
    # correctness at M=4 (internally padded to one 8-row tile)
    rng = np.random.default_rng(3)
    w = rng.laplace(0, 0.02, (64, 96)).astype(np.float32)
    packed = ops.pack_joint_sparse(w, value_sparsity=0.5, bk=32, bn=32)
    x = jnp.asarray(rng.normal(0, 1, (4, 64)), jnp.float32)
    got = ops.joint_dense(x, packed)
    want = x @ jnp.asarray(ops.unpack_joint_sparse(packed))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ----------------------------------------------- value-only (bf16) --------

@pytest.mark.parametrize("mode", ["dense", "value", "bit", "joint"])
def test_stacked_tables_by_mode(mode):
    """What each dbpim_mode packs: "dense" nothing; "value" a bf16 payload
    with unit scales; "bit" an INT8 payload keeping every K-block; "joint"
    an INT8 payload keeping the balanced rule's count per column."""
    vs = 0.5
    cfg = get_config("tinyllama-1.1b", reduced=True, dbpim_mode=mode).scaled(
        dtype="float32", dbpim_value_sparsity=vs)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tables = build_stacked_tables(params, cfg, bk=32, bn=32)
    if mode == "dense":
        assert tables is None
        return
    for name, t in tables.arrays.items():
        kt = tables.static[name][2] // 32
        maxb = t["w_blocks"].shape[-3]
        if mode == "value":
            assert t["w_blocks"].dtype == jnp.bfloat16
            assert np.asarray(t["scales"] == 1.0).all()
        else:
            assert t["w_blocks"].dtype == jnp.int8
        if mode == "bit":
            assert maxb == kt
        if mode == "joint":
            assert maxb == kt - min(int(round(vs * kt)), kt - 1)


@pytest.mark.parametrize("mode", ["value", "bit"])
@pytest.mark.parametrize("arch", ARCHS)
def test_value_mode_packs_bf16_payload_and_serves(arch, mode):
    """The single-level modes serve forward + decode through the scan to
    the same tolerance contract as joint: "value" (compacted blocks hold
    the raw pruned bf16 weights, unit scales — no bit-level grid) and
    "bit" (INT8/FTA payload, no value pruning)."""
    cfg, params, tables = _setup(arch, mode=mode)
    recon = reconstruct_stacked_params(params, tables, cfg)
    toks = jnp.asarray(np.random.default_rng(6).integers(
        1, cfg.vocab_size, (2, 16)), jnp.int32)
    got = forward(params, toks, cfg, tables=tables)
    want = forward(recon, toks, cfg)
    tol = 1e-4 * max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    cache = init_cache(cfg, 2, 8)
    tok = jnp.asarray([[3], [5]], jnp.int32)
    gl, _ = decode_step(params, cache, tok, cfg, tables=tables)
    wl, _ = decode_step(recon, cache, tok, cfg)
    np.testing.assert_allclose(
        np.asarray(gl, np.float32), np.asarray(wl, np.float32),
        atol=1e-4 * max(float(jnp.max(jnp.abs(wl))), 1.0))


def test_value_mode_payload_is_unquantized_and_halves_traffic_vs_dense():
    """The value payload is the PRUNED weights themselves (bf16 cast, not
    the INT8 grid), and at 0.5 value sparsity the decode weight traffic
    lands strictly between joint (x0.25 on eligible bytes) and dense."""
    cfg, params, tables = _setup("tinyllama-1.1b", mode="value")
    # unpacked value tables == bf16(weights) * mask, NOT a 127-level grid
    name, t = next(iter(tables.arrays.items()))
    k, n, k_pad = tables.static[name]
    packed = ops.JointPackedStacked(t["w_blocks"], t["idx"], t["scales"],
                                    t["nblocks"], k, n, k_pad)
    dense = ops.unpack_joint_sparse_stacked(packed)
    kept = dense[dense != 0]
    w0 = np.asarray(params["blocks"]["attn"][name]
                    if name in ("wq", "wk", "wv", "wo")
                    else params["blocks"]["mlp"][name], np.float32)
    bf16_vals = np.asarray(jnp.asarray(w0, jnp.bfloat16), np.float32)
    assert np.isin(kept, bf16_vals).all()

    cache = init_cache(cfg, 4, 16)
    tok = jnp.ones((4, 1), jnp.int32)
    dense_wb = analyze(lambda p, c, t_: decode_step(p, c, t_, cfg),
                       params, cache, tok)["weight_bytes"]
    value_wb = analyze(
        lambda p, c, t_: decode_step(p, c, t_, cfg, tables=tables),
        params, cache, tok)["weight_bytes"]
    _, _, joint_tables = _setup("tinyllama-1.1b", mode="joint")
    joint_wb = analyze(
        lambda p, c, t_: decode_step(p, c, t_, cfg, tables=joint_tables),
        params, cache, tok)["weight_bytes"]
    assert joint_wb < value_wb < dense_wb


# ----------------------------------------- serving graph + traffic --------

def test_joint_mode_changes_compiled_serving_graph():
    """dbpim_mode="joint" must change the decode-step HLO: the joint
    pallas kernel appears in the jaxpr, and weight bytes per decode step
    drop to <= 0.55x dense at 0.5 value sparsity (the (1 - vs) * 0.5
    contract plus index/scale overhead and the mode-independent
    unembedding)."""
    cfg, params, tables = _setup("tinyllama-1.1b")
    cache = init_cache(cfg, 4, 16)
    tok = jnp.ones((4, 1), jnp.int32)

    dense_jaxpr = str(jax.make_jaxpr(
        lambda p, c, t: decode_step(p, c, t, cfg))(params, cache, tok))
    joint_jaxpr = str(jax.make_jaxpr(
        lambda p, c, t: decode_step(p, c, t, cfg, tables=tables))(
            params, cache, tok))
    assert "pallas_call" not in dense_jaxpr
    assert "pallas_call" in joint_jaxpr

    dense_cost = analyze(lambda p, c, t: decode_step(p, c, t, cfg),
                         params, cache, tok)
    joint_cost = analyze(
        lambda p, c, t: decode_step(p, c, t, cfg, tables=tables),
        params, cache, tok)
    assert dense_cost["weight_bytes"] > 0
    ratio = joint_cost["weight_bytes"] / dense_cost["weight_bytes"]
    assert ratio <= 0.55, f"joint/dense weight traffic {ratio:.3f} > 0.55"


def test_mismatched_tables_raise_instead_of_misserving():
    """Every family packs now (segmented per-kind scans), so the guard
    moved: tables packed for one segment layout must be rejected by a
    model with a different one — a single-"blocks" tinyllama pack handed
    to jamba's seg00..seg03 stack, or a pre-segmentation raw
    StackedKernelTables object, would otherwise die as a cryptic scan
    shape error deep inside the kernel."""
    cfg = get_config("jamba-v0.1-52b", reduced=True, dbpim_mode="joint")
    params = init_params(cfg, jax.random.PRNGKey(0))
    jt = build_stacked_tables(params, cfg)
    assert jt is not None and set(jt.segments) == \
        {"seg00", "seg01", "seg02", "seg03"}
    cfg_t, params_t, tables = _setup("tinyllama-1.1b")
    with pytest.raises(ValueError, match="segment layout"):
        decode_step(params, init_cache(cfg, 1, 8),
                    jnp.ones((1, 1), jnp.int32), cfg, tables=tables)
    with pytest.raises(ValueError, match="segment layout"):
        forward(params, jnp.ones((1, 8), jnp.int32), cfg, tables=tables)
    # a bare per-segment pack (no .segments) is not servable either
    with pytest.raises(ValueError, match="segmented pack"):
        forward(params_t, jnp.ones((1, 8), jnp.int32), cfg_t,
                tables=tables.segments["blocks"])


# ------------------------------------------------ interpret default -------

def test_backend_aware_interpret_default(monkeypatch):
    monkeypatch.delenv(INTERPRET_ENV, raising=False)
    # this suite runs on CPU: the default must be interpret, not compile
    assert default_interpret() is (jax.default_backend() != "tpu")
    monkeypatch.setenv(INTERPRET_ENV, "0")
    assert default_interpret() is False
    monkeypatch.setenv(INTERPRET_ENV, "true")
    assert default_interpret() is True
    monkeypatch.setenv(INTERPRET_ENV, "bogus")
    with pytest.raises(ValueError):
        default_interpret()
