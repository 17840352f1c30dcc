"""Segmented per-kind layer scans: the families the segment descriptor
API brought into the stacked joint-sparse serving matrix — hybrid
(jamba: mixed attention / SSM / MoE sublayer runs packed per segment)
and enc-dec (whisper: decoder + cross-attention packed, run-once encoder
dense) — plus MoE chunked prefill (per-position capacity dispatch), the
hybrid refill-slot regression, the serving_capabilities() API, and the
unified launch.steps.build_step builder.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.launch.steps import build_step
from repro.models import (decode_chunk, decode_step, forward, init_cache,
                          init_params)
from repro.models.segments import (decoder_layout, packable_projections,
                                   projection_param_path,
                                   serving_capabilities)
from repro.models.ssm import PARALLEL_PREFILL_ATOL
from repro.models.transformer import encode
from repro.sparsity.sparse_linear import (build_stacked_tables,
                                          reconstruct_stacked_params,
                                          strip_packed_projections)


def _setup(arch, vs=0.5, mode="joint", **scale):
    cfg = get_config(arch, reduced=True, dbpim_mode=mode).scaled(
        dtype="float32", dbpim_value_sparsity=vs, **scale)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tables = build_stacked_tables(params, cfg, bk=32, bn=32)
    assert tables is not None
    return cfg, params, tables


def _whisper_enc_out(cfg, params, batch):
    frames = jax.random.normal(jax.random.PRNGKey(5),
                               (batch, cfg.encoder_seq, cfg.d_model),
                               dtype=jnp.float32)
    return encode(params, frames, cfg)


# --------------------------------------------------- segment layouts ------

def test_decoder_layouts_per_family():
    """Run-length segment descriptors: single-kind stacks keep the
    historical "blocks" name (param/cache back-compat); jamba's mixed
    periods become per-kind seg00.. runs."""
    ll = decoder_layout(get_config("tinyllama-1.1b", reduced=True))
    assert [(s.name, s.mixer, s.ffn, s.length)
            for s in ll] == [("blocks", "attn", "mlp", 2)]
    mm = decoder_layout(get_config("mamba2-1.3b", reduced=True))
    assert [(s.name, s.mixer, s.ffn, s.cache)
            for s in mm] == [("blocks", "ssm", "none", "ssm")]
    wh = decoder_layout(get_config("whisper-base", reduced=True))
    assert [(s.name, s.mixer, s.ffn, s.cross)
            for s in wh] == [("blocks", "attn", "mlp", True)]
    # jamba reduced: attn_period=4, attn_index=2, moe_every=2 over 4 layers
    jb = decoder_layout(get_config("jamba-v0.1-52b", reduced=True))
    assert [(s.name, s.mixer, s.ffn, s.length, s.cache) for s in jb] == [
        ("seg00", "ssm", "mlp", 1, "seg00"),
        ("seg01", "ssm", "moe", 1, "seg01"),
        ("seg02", "attn", "mlp", 1, "seg02"),
        ("seg03", "ssm", "moe", 1, "seg03")]


def test_serving_capabilities_and_deprecated_shims():
    """serving_capabilities() is the single source of truth for what the
    serving stack supports per config. Every family packs stacked
    tables; only sliding windows gate chunked prefill; parallel prefill
    means an SSM segment exists."""
    for arch, chunked, par in [("tinyllama-1.1b", True, False),
                               ("mamba2-1.3b", True, True),
                               ("mixtral-8x7b", False, False),
                               ("arctic-480b", True, False),
                               ("jamba-v0.1-52b", True, True),
                               ("whisper-base", True, False)]:
        cfg = get_config(arch, reduced=True)
        caps = cfg.serving_capabilities()
        assert caps.stacked_tables
        assert caps.chunked_prefill is chunked
        assert caps.parallel_prefill is par
        assert caps.prefill_modes == (("chunked", "full") if chunked
                                      else ("full",))
    # packable projections carry exact segment-qualified paths
    wh = serving_capabilities(get_config("whisper-base", reduced=True))
    assert "blocks/xattn/wq" in wh.packable
    assert "blocks/w_gate" not in wh.packable       # gelu MLP has no gate
    jb = serving_capabilities(get_config("jamba-v0.1-52b", reduced=True))
    assert "seg02/wq" in jb.packable
    assert "seg01/moe/w_gate" in jb.packable
    assert "seg00/in_proj" in jb.packable


def test_projection_param_paths_disambiguate_hooks():
    """The hook-name -> param-path map resolves the ambiguous bare MLP
    names: a "w_gate" hook inside a MoE segment is arctic's dense
    residual MLP (nested under moe/dense_mlp), not a plain mlp."""
    segs = {s.name: s for s in decoder_layout(
        get_config("jamba-v0.1-52b", reduced=True))}
    assert projection_param_path(segs["seg02"], "wq") == "seg02/attn/wq"
    assert projection_param_path(segs["seg00"], "in_proj") == \
        "seg00/ssm/in_proj"
    assert projection_param_path(segs["seg01"], "moe/w_up") == \
        "seg01/moe/w_up"
    arctic = decoder_layout(get_config("arctic-480b", reduced=True))[0]
    assert projection_param_path(arctic, "w_gate") == \
        "blocks/moe/dense_mlp/w_gate"
    whisper = decoder_layout(get_config("whisper-base", reduced=True))[0]
    assert projection_param_path(whisper, "xattn/wo") == "blocks/xattn/wo"
    assert projection_param_path(whisper, "w_up") == "blocks/mlp/w_up"


# ------------------------------------- jamba / whisper stacked serving ----

def test_jamba_stacked_serving_matches_reference():
    """Hybrid acceptance: jamba serves with dbpim_mode="joint" — the
    per-segment packs thread each segment's scan, the decode jaxpr grows
    pallas_call (graph change), logits match the dense FTA reference,
    and the stripped-params serving configuration is bitwise identical."""
    cfg, params, tables = _setup("jamba-v0.1-52b")
    assert set(tables.segments) == {"seg00", "seg01", "seg02", "seg03"}
    recon = reconstruct_stacked_params(params, tables, cfg)
    toks = jnp.asarray(np.random.default_rng(2).integers(
        1, cfg.vocab_size, (2, 16)), jnp.int32)
    got = forward(params, toks, cfg, tables=tables)
    want = forward(recon, toks, cfg)
    tol = 1e-4 * max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    assert float(jnp.max(jnp.abs(want - forward(params, toks, cfg)))) > 0

    cache = init_cache(cfg, 4, 16)
    tok = jnp.asarray([[3], [5], [7], [11]], jnp.int32)
    got_l, _ = decode_step(params, cache, tok, cfg, tables=tables)
    want_l, _ = decode_step(recon, cache, tok, cfg)
    tol = 1e-4 * max(float(jnp.max(jnp.abs(want_l))), 1.0)
    np.testing.assert_allclose(np.asarray(got_l, np.float32),
                               np.asarray(want_l, np.float32), atol=tol)
    stripped = strip_packed_projections(params, cfg)
    got_s, _ = decode_step(stripped, cache, tok, cfg, tables=tables)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(got_l))
    jaxpr = str(jax.make_jaxpr(
        lambda p, c, t: decode_step(p, c, t, cfg, tables=tables))(
            stripped, cache, tok))
    assert "pallas_call" in jaxpr


def test_whisper_stacked_serving_and_exact_path_strip():
    """Enc-dec acceptance: the whisper decoder packs (cross-attention
    included), the encoder stays dense — strip matches exact param paths,
    so the encoder's identically-suffixed wq/wk/wv/wo survive — and the
    served decode matches the FTA reference with pallas_call in the
    jaxpr."""
    cfg, params, tables = _setup("whisper-base")
    names = set(tables.segments["blocks"].arrays)
    assert {"xattn/wq", "xattn/wk", "xattn/wv", "xattn/wo"} <= names
    assert "w_gate" not in names                     # gelu MLP
    stripped = strip_packed_projections(params, cfg)
    for n in ("wq", "wk", "wv", "wo"):
        np.testing.assert_array_equal(
            np.asarray(stripped["enc_blocks"]["attn"][n]),
            np.asarray(params["enc_blocks"]["attn"][n]))
        assert stripped["blocks"]["attn"][n].shape == \
            (cfg.n_layers, 1, 1)
        assert stripped["blocks"]["xattn"][n].shape == \
            (cfg.n_layers, 1, 1)

    enc_out = _whisper_enc_out(cfg, params, 2)
    recon = reconstruct_stacked_params(params, tables, cfg)
    cache = init_cache(cfg, 2, 16, enc_out=enc_out)
    tok = jnp.asarray([[3], [5]], jnp.int32)
    got, _ = decode_step(stripped, cache, tok, cfg, tables=tables)
    want, _ = decode_step(recon, cache, tok, cfg)
    tol = 1e-4 * max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    jaxpr = str(jax.make_jaxpr(
        lambda p, c, t: decode_step(p, c, t, cfg, tables=tables))(
            stripped, cache, tok))
    assert "pallas_call" in jaxpr


# --------------------------------------------- chunked prefill parity -----

def _stepwise(params, cache, toks, cfg, n):
    logits = None
    for t in range(n):
        logits, cache = decode_step(params, cache, toks[:, t:t + 1], cfg)
    return logits, cache


def test_whisper_chunk_prefill_bitwise_equals_stepwise():
    """Attention + cross-attention chunks are exact: one decode_chunk
    call over 5 prompt tokens reproduces 5 decode_step calls bitwise —
    logits AND the decode steps that continue from the resulting cache
    (the transitive cache-correctness check). rope_pct == 0 rides the
    shared _sinusoidal_at position math."""
    cfg = get_config("whisper-base", reduced=True).scaled(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    enc_out = _whisper_enc_out(cfg, params, 2)
    toks = jnp.asarray(np.random.default_rng(3).integers(
        1, cfg.vocab_size, (2, 7)), jnp.int32)
    lg_s, cache_s = _stepwise(params, init_cache(cfg, 2, 16,
                                                 enc_out=enc_out),
                              toks, cfg, 5)
    lg_c, cache_c = decode_chunk(params, init_cache(cfg, 2, 16,
                                                    enc_out=enc_out),
                                 toks[:, :5], jnp.full((2,), 5, jnp.int32),
                                 cfg)
    np.testing.assert_array_equal(np.asarray(lg_c), np.asarray(lg_s))
    for t in range(5, 7):
        lg_s, cache_s = decode_step(params, cache_s, toks[:, t:t + 1], cfg)
        lg_c, cache_c = decode_step(params, cache_c, toks[:, t:t + 1], cfg)
        np.testing.assert_array_equal(np.asarray(lg_c), np.asarray(lg_s))


def test_jamba_chunk_prefill_exact_bitwise_and_parallel_tolerance():
    """Hybrid chunks: with prefill_exact the SSM segments walk the exact
    recurrence and the whole chunk is bitwise-identical to stepwise; the
    default parallel SSD form stays within PARALLEL_PREFILL_ATOL."""
    cfg = get_config("jamba-v0.1-52b", reduced=True).scaled(
        dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(4).integers(
        1, cfg.vocab_size, (2, 8)), jnp.int32)
    nv = jnp.full((2,), 6, jnp.int32)
    lg_s, cache_s = _stepwise(params, init_cache(cfg, 2, 16), toks, cfg, 6)
    lg_prefill = lg_s

    cfg_e = cfg.scaled(prefill_exact=True)
    lg_e, cache_e = decode_chunk(params, init_cache(cfg_e, 2, 16),
                                 toks[:, :6], nv, cfg_e)
    np.testing.assert_array_equal(np.asarray(lg_e), np.asarray(lg_s))
    for t in range(6, 8):
        lg_s, cache_s = decode_step(params, cache_s, toks[:, t:t + 1], cfg)
        lg_e, cache_e = decode_step(params, cache_e, toks[:, t:t + 1], cfg)
        np.testing.assert_array_equal(np.asarray(lg_e), np.asarray(lg_s))

    lg_p, _ = decode_chunk(params, init_cache(cfg, 2, 16), toks[:, :6],
                           nv, cfg)
    assert float(jnp.max(jnp.abs(lg_p - lg_prefill))) <= \
        PARALLEL_PREFILL_ATOL[cfg.dtype]


def test_moe_chunk_prefill_identical_to_stepwise():
    """MoE chunked prefill (the decode_chunk gate that used to reject
    n_experts): per-position capacity dispatch routes each chunk position
    against exactly one decode step's token pool, and at decode-batch
    scale capacity() clamps to B * top_k — drop-free — so the chunk is
    bitwise identical to stepwise prefill, continuation included."""
    cfg = get_config("arctic-480b", reduced=True).scaled(dtype="float32")
    assert cfg.serving_capabilities().chunked_prefill
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(5).integers(
        1, cfg.vocab_size, (3, 7)), jnp.int32)
    lg_s, cache_s = _stepwise(params, init_cache(cfg, 3, 16), toks, cfg, 5)
    lg_c, cache_c = decode_chunk(params, init_cache(cfg, 3, 16),
                                 toks[:, :5], jnp.full((3,), 5, jnp.int32),
                                 cfg)
    np.testing.assert_array_equal(np.asarray(lg_c), np.asarray(lg_s))
    for t in range(5, 7):
        lg_s, cache_s = decode_step(params, cache_s, toks[:, t:t + 1], cfg)
        lg_c, cache_c = decode_step(params, cache_c, toks[:, t:t + 1], cfg)
        np.testing.assert_array_equal(np.asarray(lg_c), np.asarray(lg_s))


def test_windowed_arch_still_rejects_chunked_prefill():
    cfg = get_config("mixtral-8x7b", reduced=True)   # window=32
    assert not cfg.serving_capabilities().chunked_prefill
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="sliding-window"):
        decode_chunk(params, init_cache(cfg, 2, 16),
                     jnp.ones((2, 4), jnp.int32),
                     jnp.full((2,), 4, jnp.int32), cfg)


# -------------------------------------------- hybrid refill regression ----

def test_hybrid_engine_refill_slots_match_fresh_slots():
    """The refill-slot regression on the hybrid cache layout: an engine
    whose 2 slots are reset and refilled mid-trace (4 requests) must
    generate exactly what a 4-slot engine (every request on a fresh slot)
    generates — reset_slots/merge_slots walk the per-segment seg00..
    caches with uniform batch axis 1, no family-switched axis math."""
    from repro.serving import ServeEngine, WorkloadSpec, make_trace
    cfg = get_config("jamba-v0.1-52b", reduced=True,
                     prefill_exact=True).scaled(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    spec = WorkloadSpec(n_requests=4, arrival_rate=10.0, prompt_len=(3, 9),
                        gen_len=(3, 5), dist="uniform", seed=11)
    trace = make_trace(spec, cfg.vocab_size)
    outs = {}
    for n_slots in (2, 4):
        engine = ServeEngine(cfg, params, n_slots=n_slots, max_len=24,
                             prefill_chunk=4)
        outs[n_slots] = engine.run(trace)
    assert outs[2] == outs[4]


# ------------------------------------------------- unified step builder ---

def test_build_step_tags_and_validation():
    mesh = make_test_mesh()
    llama = get_config("tinyllama-1.1b", reduced=True)
    jamba = get_config("jamba-v0.1-52b", reduced=True)
    whisper = get_config("whisper-base", reduced=True)

    decode_fn, _ = build_step(llama, mesh, "decode")
    assert decode_fn.call_kind == "decode"
    chunk_j, _ = build_step(jamba, mesh, "prefill_chunk")
    assert chunk_j.call_kind == "prefill_parallel"
    chunk_je, _ = build_step(jamba.scaled(prefill_exact=True), mesh,
                             "prefill_chunk")
    assert chunk_je.call_kind == "prefill_chunk_exact"
    chunk_w, _ = build_step(whisper, mesh, "prefill_chunk")
    assert chunk_w.call_kind == "prefill_chunk_exact"

    with pytest.raises(ValueError, match="call_kind"):
        build_step(llama, mesh, "train")
    with pytest.raises(ValueError, match="call_kind"):
        build_step(llama, mesh, "serve")
