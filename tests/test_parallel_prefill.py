"""Parallel-form (SSD) chunked SSM prefill: tolerance-equivalence to the
sequential decode recurrence across chunk sizes and stacked-table modes,
the prefill_exact bitwise fallback, and the per-call-kind cost tags."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.launch.steps import build_step
from repro.models import decode_chunk, init_cache, init_params
from repro.models.ssm import PARALLEL_PREFILL_ATOL
from repro.sparsity.sparse_linear import build_stacked_tables

ARCH = "mamba2-1.3b"


def _cfg(mode=None, **kw):
    cfg = get_config(ARCH, reduced=True, dbpim_mode=mode)
    return cfg.scaled(dtype="float32", dbpim_value_sparsity=0.5, **kw)


def _tables(cfg, params):
    if not cfg.dbpim or cfg.dbpim_mode == "dense":
        return None
    tables = build_stacked_tables(params, cfg, bk=32, bn=32)
    assert tables is not None
    return tables


from conftest import chunked_prefill as _chunked
from conftest import stepwise_prefill as _stepwise


def _assert_close(tree_a, tree_b, atol):
    for a, b in zip(jax.tree_util.tree_leaves(tree_a),
                    jax.tree_util.tree_leaves(tree_b)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=atol)


# ----------------------------------------- equivalence contract ----------

@pytest.mark.parametrize("mode", [None, "value", "joint"])
@pytest.mark.parametrize("chunk,plen", [(1, 5), (4, 8), (8, 8), (4, 11)])
def test_parallel_prefill_matches_sequential_decode(mode, chunk, plen):
    """The tentpole contract: the parallel SSD chunk (ONE read of the
    stacked in/out projections per chunk) lands within
    PARALLEL_PREFILL_ATOL of feeding the prompt through sequential decode
    steps — logits, SSM state, conv window, and positions — for dense,
    value-payload, and joint stacked tables, including ragged prompts
    (plen not a chunk multiple)."""
    cfg = _cfg(mode)
    assert not cfg.prefill_exact          # parallel is the default
    params = init_params(cfg, jax.random.PRNGKey(0))
    tables = _tables(cfg, params)
    prompts = np.random.default_rng(1).integers(
        1, cfg.vocab_size, (3, plen)).astype(np.int32)
    atol = PARALLEL_PREFILL_ATOL[cfg.dtype]
    ls, cs = _stepwise(params, cfg, prompts, 16, tables=tables)
    lp, cp = _chunked(params, cfg, prompts, 16, chunk, tables=tables)
    np.testing.assert_allclose(np.asarray(ls, np.float32),
                               np.asarray(lp, np.float32), atol=atol)
    np.testing.assert_array_equal(np.asarray(cs["pos"]),
                                  np.asarray(cp["pos"]))
    _assert_close(cs["ssm"], cp["ssm"], atol)


def test_prefill_exact_restores_bit_identity():
    """cfg.prefill_exact=True routes the chunk back through the per-token
    recurrence: BITWISE equal to sequential decode, at C x the
    projection traffic."""
    cfg = _cfg(None, prefill_exact=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(2).integers(
        1, cfg.vocab_size, (2, 7)).astype(np.int32)
    ls, cs = _stepwise(params, cfg, prompts, 16)
    lc, cc = _chunked(params, cfg, prompts, 16, chunk=4)
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(lc))
    for a, b in zip(jax.tree_util.tree_leaves(cs),
                    jax.tree_util.tree_leaves(cc)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_parallel_chunk_zero_valid_slot_exactly_untouched():
    """Invalid slots (n_valid=0) are masked by zeroing dt — an EXACT
    identity on the state (state * exp(0) + 0), and the conv gather at
    cursor 0 returns the carried window bit-for-bit."""
    cfg = _cfg(None)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(3).integers(
        1, cfg.vocab_size, (2, 4)).astype(np.int32)
    _, cache = _stepwise(params, cfg, prompts, 16)
    toks = np.zeros((2, 4), np.int32)
    toks[0] = prompts[0]
    _, cache2 = decode_chunk(params, cache, jnp.asarray(toks),
                             jnp.asarray([4, 0], jnp.int32), cfg)
    assert int(cache2["pos"][0]) == 8 and int(cache2["pos"][1]) == 4
    for key in ("conv", "state"):
        np.testing.assert_array_equal(
            np.asarray(cache["ssm"][key])[:, 1],
            np.asarray(cache2["ssm"][key])[:, 1])


def test_parallel_prefill_mixed_ragged_slots():
    """Slots at DIFFERENT cursors in one chunk (the engine's steady
    state): each slot's trajectory matches its own sequential decode."""
    cfg = _cfg("joint")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tables = _tables(cfg, params)
    rng = np.random.default_rng(4)
    atol = PARALLEL_PREFILL_ATOL[cfg.dtype]
    p0 = rng.integers(1, cfg.vocab_size, (1, 6)).astype(np.int32)
    p1 = rng.integers(1, cfg.vocab_size, (1, 3)).astype(np.int32)
    # batch run: slot0 advances 4 then 2; slot1 advances 3 then idles
    cache = init_cache(cfg, 2, 16)
    cache["pos"] = jnp.zeros((2,), jnp.int32)
    toks = np.zeros((2, 4), np.int32)
    toks[0] = p0[0, :4]
    toks[1, :3] = p1[0]
    _, cache = decode_chunk(params, cache, jnp.asarray(toks),
                            jnp.asarray([4, 3], jnp.int32), cfg,
                            tables=tables)
    toks = np.zeros((2, 4), np.int32)
    toks[0, :2] = p0[0, 4:]
    logits, cache = decode_chunk(params, cache, jnp.asarray(toks),
                                 jnp.asarray([2, 0], jnp.int32), cfg,
                                 tables=tables)
    assert cache["pos"].tolist() == [6, 3]
    # per-slot sequential references
    l0, c0 = _stepwise(params, cfg, p0, 16, tables=tables)
    l1, c1 = _stepwise(params, cfg, p1, 16, tables=tables)
    np.testing.assert_allclose(np.asarray(logits[0], np.float32),
                               np.asarray(l0[0], np.float32), atol=atol)
    for key in ("conv", "state"):
        np.testing.assert_allclose(
            np.asarray(cache["ssm"][key], np.float32)[:, 0],
            np.asarray(c0["ssm"][key], np.float32)[:, 0], atol=atol)
        np.testing.assert_allclose(
            np.asarray(cache["ssm"][key], np.float32)[:, 1],
            np.asarray(c1["ssm"][key], np.float32)[:, 0], atol=atol)


# ------------------------------------------------- config + cost tags ----

def test_supports_parallel_prefill_predicate():
    assert _cfg(None).serving_capabilities().parallel_prefill
    assert not get_config(
        "tinyllama-1.1b", reduced=True).serving_capabilities().parallel_prefill
    assert not get_config(
        "mixtral-8x7b", reduced=True).serving_capabilities().parallel_prefill


def test_get_config_prefill_exact_kwarg():
    assert get_config(ARCH, reduced=True, prefill_exact=True).prefill_exact
    assert not get_config(ARCH, reduced=True).prefill_exact


def test_step_builders_tag_call_kinds():
    """Cost attribution (jaxpr_cost.analyze_call_kinds) keys off the step
    builders' call_kind tags: SSM chunks are "prefill_parallel" by
    default, "prefill_chunk_exact" under cfg.prefill_exact, attention
    chunks always exact, decode steps "decode"."""
    mesh = make_test_mesh()
    ssm = _cfg(None)
    fn, _ = build_step(ssm, mesh, "prefill_chunk")
    assert fn.call_kind == "prefill_parallel"
    fn, _ = build_step(ssm.scaled(prefill_exact=True), mesh, "prefill_chunk")
    assert fn.call_kind == "prefill_chunk_exact"
    attn = get_config("tinyllama-1.1b", reduced=True)
    fn, _ = build_step(attn, mesh, "prefill_chunk")
    assert fn.call_kind == "prefill_chunk_exact"
    fn, _ = build_step(ssm, mesh, "decode")
    assert fn.call_kind == "decode"


def test_parallel_chunk_reads_projections_once():
    """The perf contract, measured on the jaxpr: the parallel chunk's
    weight bytes are far below the exact chunk's (which re-reads the
    in/out projections once per token) — and the decode step reads the
    same weights as one parallel chunk (both read once)."""
    from repro.runtime.jaxpr_cost import analyze_call_kinds
    mesh = make_test_mesh()
    # the CI bench config (bf16 + default value sparsity + kernel tiles):
    # the >= 4x contract is stated there — an f32 unembedding would
    # dilute the ratio (it is paid once per chunk on BOTH paths)
    cfg = get_config(ARCH, reduced=True, dbpim_mode="joint")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tables = build_stacked_tables(params, cfg)
    assert tables is not None
    from repro.sparsity.sparse_linear import strip_packed_projections
    params = strip_packed_projections(params, cfg)
    B, C = 2, 8
    cache = init_cache(cfg, B, 16)
    cache["pos"] = jnp.zeros((B,), jnp.int32)
    toks = jnp.zeros((B, C), jnp.int32)
    nv = jnp.full((B,), C, jnp.int32)
    slots = jnp.arange(B, dtype=jnp.int32)
    par_fn, _ = build_step(cfg, mesh, "prefill_chunk")
    ex_fn, _ = build_step(cfg.scaled(prefill_exact=True), mesh,
                          "prefill_chunk")
    kinds = analyze_call_kinds({
        par_fn.call_kind: (par_fn, (params, tables, cache, toks, nv, slots)),
        ex_fn.call_kind: (ex_fn, (params, tables, cache, toks, nv, slots))},
        weight_argnums=(0, 1))
    par = kinds["prefill_parallel"]["weight_bytes"]
    ex = kinds["prefill_chunk_exact"]["weight_bytes"]
    assert par < ex / 2, (par, ex)
    # per prompt token the parallel chunk must beat the exact chunk >= 4x
    assert par / ex <= 0.25, (par, ex)
