"""Grouped (layer x expert) joint-sparse serving for MoE, the fixed
rank-3 expert-weight accounting in the jaxpr cost walker, and the
capacity clamp for tiny decode batches.

Mirrors tests/test_stacked_serving.py for the grouped pack: round-trip
identity per (layer, expert) slice, padded-slot-zero guard, forward /
decode vs the dense FTA reference, serving-graph + weight-traffic
guarantees — on reduced mixtral (plain MoE) and arctic (MoE + dense
residual MLP).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.models import decode_step, forward, init_cache, init_params
from repro.models import moe as moe_mod
from repro.runtime.jaxpr_cost import analyze
from repro.sparsity.sparse_linear import (build_stacked_tables,
                                          reconstruct_stacked_params,
                                          strip_packed_projections)

ARCH = "mixtral-8x7b"


def _quant_ref(w, mask):
    """Independent dense recomputation of the pack's quantization step."""
    from repro.core import fta
    m = np.asarray(mask, np.int32)
    amax = np.abs(w * m).max(axis=0)
    scales = (amax / 127.0 + 1e-12).astype(np.float32)
    q = np.clip(np.round(w * m / scales), -127, 127).astype(np.int32)
    q, _ = fta.fta_quantize(q, m)
    return (np.asarray(q) * m).astype(np.float32) * scales.reshape(1, -1)


def _setup(arch=ARCH, vs=0.5, dtype="float32", mode="joint"):
    cfg = get_config(arch, reduced=True, dbpim_mode=mode).scaled(
        dtype=dtype, dbpim_value_sparsity=vs)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tables = build_stacked_tables(params, cfg, bk=32, bn=32)
    assert tables is not None
    return cfg, params, tables


# ------------------------------------------------- grouped pack layout ----

@pytest.mark.parametrize("K,N", [(256, 256), (200, 100)])
@pytest.mark.parametrize("vs", [0.0, 0.5])
def test_grouped_pack_roundtrip_per_expert(K, N, vs):
    """pack -> unpack reproduces each (layer, expert) slice's pruned +
    quantized dense reference bitwise, across value sparsities and odd
    (ragged-tile) shapes."""
    rng = np.random.default_rng(0)
    L, E, bk, bn = 2, 3, 32, 32
    ws = rng.laplace(0, 0.02, (L, E, K, N)).astype(np.float32)
    p = ops.pack_joint_sparse_grouped(ws, value_sparsity=vs or None,
                                      bk=bk, bn=bn)
    dense = ops.unpack_joint_sparse_grouped(p)
    assert dense.shape == (L, E, K, N)
    for l in range(L):
        for e in range(E):
            mask = (ops.tile_prune_mask_balanced(ws[l, e], vs, bk, bn)
                    if vs else np.ones((K, N), np.int32))
            np.testing.assert_array_equal(dense[l, e],
                                          _quant_ref(ws[l, e], mask))
    if vs:
        # balanced pruning: one shared MAXB, zero padded slots group-wide
        nb = np.asarray(p.nblocks)
        assert (nb == p.maxb).all()


def test_grouped_pack_shares_maxb_and_zero_pads_short_members():
    """Ragged explicit masks: MAXB is the max survivor count over every
    (layer, expert) pair; short members pad with zero-payload slots."""
    rng = np.random.default_rng(1)
    L, E, K, N, bk = 2, 2, 128, 64, 32
    masks = np.ones((L, E, K, N), np.int32)
    masks[0, 0, bk:] = 0                  # (0,0) keeps 1 of 4 K-blocks
    masks[0, 1, 2 * bk:] = 0              # (0,1) keeps 2
    ws = rng.laplace(0, 0.02, (L, E, K, N)).astype(np.float32)
    p = ops.pack_joint_sparse_grouped(ws, masks, bk=bk, bn=32)
    assert p.maxb == 4                    # layer 1 keeps all 4
    nb = np.asarray(p.nblocks)
    assert nb[0, 0].max() == 1 and nb[0, 1].max() == 2
    assert (nb[1] == 4).all()
    wb = np.asarray(p.w_blocks)
    for l in range(L):
        for e in range(E):
            for n_t in range(wb.shape[2]):
                assert not wb[l, e, n_t, nb[l, e, n_t]:].any()
    dense = ops.unpack_joint_sparse_grouped(p)
    for l in range(L):
        for e in range(E):
            np.testing.assert_array_equal(dense[l, e],
                                          _quant_ref(ws[l, e],
                                                     masks[l, e]))


def test_grouped_pack_rejects_bad_shapes():
    with pytest.raises(ValueError):
        ops.pack_joint_sparse_grouped(np.zeros((2, 4, 4)),
                                      value_sparsity=0.5)


# ------------------------------------------------------- family gates -----

def test_moe_family_gates():
    """Segmented per-kind scans closed the family matrix: every family
    packs stacked tables (jamba included), and chunked prefill gates only
    on sliding windows — arctic (no window, per-position capacity
    dispatch) chunks; mixtral's reduced config keeps window=32 and stays
    stepwise (ring-buffer writes need the sequential walk)."""
    mixtral = get_config("mixtral-8x7b", reduced=True)
    arctic = get_config("arctic-480b", reduced=True)
    jamba = get_config("jamba-v0.1-52b", reduced=True)
    assert mixtral.serving_capabilities().stacked_tables
    assert arctic.serving_capabilities().stacked_tables
    assert jamba.serving_capabilities().stacked_tables
    assert not mixtral.serving_capabilities().chunked_prefill
    assert arctic.serving_capabilities().chunked_prefill
    assert jamba.serving_capabilities().chunked_prefill


# ------------------------------------- forward / decode vs reference ------

@pytest.mark.parametrize("arch", ["mixtral-8x7b", "arctic-480b"])
def test_moe_stacked_forward_matches_dense_fta_reference(arch):
    """The scan-stacked joint forward (grouped expert dispatch + packed
    attention, and arctic's packed dense residual MLP) equals a plain
    forward over the FTA-reconstructed weights to fp32 tolerance."""
    cfg, params, tables = _setup(arch)
    recon = reconstruct_stacked_params(params, tables, cfg)
    toks = jnp.asarray(np.random.default_rng(2).integers(
        1, cfg.vocab_size, (2, 16)), jnp.int32)
    got = forward(params, toks, cfg, tables=tables)
    want = forward(recon, toks, cfg)
    assert got.shape == want.shape
    tol = 1e-4 * max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    # and the compressed path is genuinely different from uncompressed
    assert float(jnp.max(jnp.abs(want - forward(params, toks, cfg)))) > 0


def test_moe_ragged_decode_step_matches_reference():
    """Batch-4 decode through grouped tables: logits + caches match the
    FTA reference, and the stripped-params serving configuration (dense
    copies replaced by placeholders) is bitwise identical."""
    cfg, params, tables = _setup()
    recon = reconstruct_stacked_params(params, tables, cfg)
    cache = init_cache(cfg, 4, 16)
    tok = jnp.asarray([[3], [5], [7], [11]], jnp.int32)
    got, cache_j = decode_step(params, cache, tok, cfg, tables=tables)
    want, cache_r = decode_step(recon, cache, tok, cfg)
    tol = 1e-4 * max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
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


# ----------------------------------------- serving graph + traffic --------

def test_moe_joint_mode_changes_compiled_serving_graph():
    """The acceptance bar: dbpim_mode="joint" on the MoE smoke arch puts
    pallas_call into the decode jaxpr (expert projections run the DB-PIM
    kernel) and drops weight bytes to <= 0.55x dense — measured with the
    fixed accounting, whose dense baseline now counts the experts."""
    cfg, params, tables = _setup()
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
    # the dense baseline must include the experts (the silently-zero bug)
    E, d, f, L = cfg.n_experts, cfg.d_model, cfg.d_ff, cfg.n_layers
    expert_bytes = L * E * 3 * d * f * 4          # f32 gate/up/down
    assert dense_cost["weight_bytes"] > expert_bytes > 0
    ratio = joint_cost["weight_bytes"] / dense_cost["weight_bytes"]
    assert ratio <= 0.55, f"joint/dense weight traffic {ratio:.3f} > 0.55"


# --------------------------------------------- fixed weight accounting ----

def _analytic_weight_bytes(cfg):
    """What one decode step's projections weigh, per the cost-model
    coverage contract (README): attention q/k/v/o + router + per-expert
    gate/up/down (+ arctic's dense residual MLP) per layer, + the
    unembedding. Nothing else — no activation einsums, caches, norms."""
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    bpe = 2 if cfg.dtype == "bfloat16" else 4
    if cfg.family == "ssm":
        from repro.models.ssm import ssm_dims
        d_in, nh, N, _ = ssm_dims(cfg)
        return cfg.n_layers * (d * (2 * d_in + 2 * N + nh)
                               + d_in * d) * bpe + d * cfg.vocab_size * bpe
    per_layer = (d * cfg.q_dim + 2 * d * cfg.kv_dim + cfg.q_dim * d) * bpe
    if E:
        n_mlp = 3 if cfg.mlp_type in ("swiglu", "geglu") else 2
        per_layer += d * E * 4                    # router (f32)
        per_layer += E * n_mlp * d * f * bpe      # expert stacks
        if cfg.dense_residual:
            per_layer += n_mlp * d * f * bpe
    else:
        per_layer += 3 * d * f * bpe
    return cfg.n_layers * per_layer + d * cfg.vocab_size * bpe


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "arctic-480b",
                                  "tinyllama-1.1b", "mamba2-1.3b"])
def test_decode_weight_bytes_exact(arch):
    """The headline regression: a dense MoE decode step charges nonzero —
    and exactly correct — expert weight bytes (rank-3 einsum weights were
    silently zero before the provenance fix), while attention/SSM
    ACTIVATION einsums stay excluded (equality would break if any KV/SSM
    state dot were charged)."""
    cfg = get_config(arch, reduced=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = init_cache(cfg, 4, 16)
    tok = jnp.ones((4, 1), jnp.int32)
    cost = analyze(lambda p, c, t: decode_step(p, c, t, cfg),
                   params, cache, tok)
    assert int(cost["weight_bytes"]) == _analytic_weight_bytes(cfg)
    if cfg.n_experts:
        bpe = 2 if cfg.dtype == "bfloat16" else 4
        expert_bytes = (cfg.n_layers * cfg.n_experts * 3
                        * cfg.d_model * cfg.d_ff * bpe)
        assert int(cost["weight_bytes"]) > expert_bytes > 0


# -------------------------------------------------------- capacity --------

def test_capacity_clamps_to_assignment_count():
    """n_tokens * top_k assignments bound the per-expert slots: tiny
    decode batches no longer allocate 8 phantom slots per expert, while
    larger pools keep the multiple-of-8 round-up."""
    cfg = get_config(ARCH, reduced=True)          # E=4, top_k=2
    assert moe_mod.capacity(cfg, 1) == 2          # 2 assignments total
    assert moe_mod.capacity(cfg, 3) == 6
    assert moe_mod.capacity(cfg, 4) == 8          # at the floor exactly
    c64 = moe_mod.capacity(cfg, 64)               # 40 = ceil-to-8 of 40
    assert c64 == 40 and c64 % 8 == 0
    assert c64 <= 64 * cfg.top_k


def test_moe_single_token_decode_runs_with_clamped_capacity():
    """B=1 decode: capacity == top_k slots per expert; the step still
    produces finite logits of the right shape."""
    cfg = get_config(ARCH, reduced=True).scaled(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = init_cache(cfg, 1, 8)
    logits, new_cache = decode_step(params, cache, jnp.ones((1, 1),
                                                            jnp.int32), cfg)
    assert logits.shape == (1, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert int(new_cache["pos"]) == 1
