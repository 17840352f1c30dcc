"""Serving path: cache init, prefill, and single-token decode for every
family (dense/MoE/VLM, SSM, hybrid, enc-dec).

The decoder is a list of per-kind segments (models.segments): decode
walks it, scanning each segment's stacked layer params (and packed-table
slices) as scan xs — the HLO stays O(segments) in depth, and every
composition of attention / SSM / MoE / cross-attention sublayers flows
through the same four bodies. A contiguous K/V stack rides the scan as
carry and each layer writes its own rows into it in place; paged pools
and SSM states ride as per-layer xs/ys. Caches are static-shape; SWA
archs allocate only the window (ring buffer).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from .config import ModelConfig
from .layers import (apply_mlp, apply_norm, embed_tokens, logits_from_hidden)
from .segments import decoder_layout
from .transformer import _block_tail, _sinusoidal, encode, segment_tables


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               enc_out: Optional[jnp.ndarray] = None, *,
               n_pages: Optional[int] = None,
               page_size: Optional[int] = None) -> Dict:
    """Per-segment caches: attention segments hold stacked (L_seg, B, A,
    Hkv, hd) k/v, SSM segments stacked (L_seg, B, ...) conv/state — the
    batch axis is 1 EVERYWHERE (the old hybrid layout nested SSM slices
    as (periods, P-1, B, ...), which forced family-switched axis math in
    merge_slots). Single-segment stacks keep the historical "attn"/"ssm"
    cache keys; hybrid stacks key by segment name.

    PAGED mode (n_pages + page_size set): attention segments hold a
    pooled {"pk","pv"} (L_seg, n_pages, page_size, Hkv, hd) instead —
    every attention segment indexes the SAME page-id space through the
    per-slot page table the serving engine passes into each step. SSM
    conv/state are O(1) per slot and stay slot-resident unchanged; so
    does "pos"."""
    paged = n_pages is not None
    if paged and page_size is None:
        raise ValueError("paged init_cache needs both n_pages and "
                         "page_size")
    cache: Dict = {"pos": jnp.zeros((), jnp.int32)}
    for seg in decoder_layout(cfg):
        if seg.mixer == "attn":
            if paged:
                cache[seg.cache] = attn_mod.init_paged_cache(
                    cfg, n_pages, page_size, seg.length)
                continue
            c = attn_mod.init_cache(cfg, batch, max_len, seg.length)
            if seg.cache != "attn":
                # multi-segment stacks track one global position only
                c.pop("pos")
            cache[seg.cache] = c
        else:
            cache[seg.cache] = ssm_mod.init_ssm_cache(cfg, batch,
                                                      seg.length)
    if cfg.is_encdec and enc_out is not None:
        cache["enc_out"] = enc_out
    return cache


def _sinusoidal_at(positions, d: int):
    """Sinusoidal position embedding at explicit positions (B, S) ->
    (B, S, d) float32. Same per-element math whether S is 1 (decode
    step) or a chunk — what keeps chunked prefill bit-identical to
    stepwise decode for rope_pct == 0 archs (whisper)."""
    posf = positions.astype(jnp.float32)
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)
    ang = posf[..., None] / (10000.0 ** (dim / d))
    pe = jnp.zeros(posf.shape + (d,), jnp.float32)
    return pe.at[..., 0::2].set(jnp.sin(ang)).at[..., 1::2].set(jnp.cos(ang))


def _page_table(c, ptab):
    """The page table an attention segment's cache needs: ptab for a
    paged pool ({"pk","pv"}), None for contiguous k/v."""
    if "pk" not in c:
        return None
    if ptab is None:
        raise ValueError("paged cache requires a page table (ptab) operand")
    return ptab


def _attn_scan(layer, x, p_stack, c, txs, mk):
    """Scan an attention segment's layers: ``layer(h, p, ck, cv, mm, li)``
    -> (h, ck, cv). Returns (x, the segment's new k/v leaves).

    Contiguous k/v ride the scan as CARRY: every layer gets the whole
    stacked buffer and its index li, writes its rows in place and reads
    its own slice — the stack is never sliced out, re-laid out or
    restacked, so a donated cache is updated where it lies. A paged pool
    rides as xs/ys, one layer's pool per step (li is None)."""
    if "pk" in c:
        def step(h, inp):
            p, ck, cv, slices = inp
            h, ck, cv = layer(h, p, ck, cv, mk(slices), None)
            return h, (ck, cv)
        x, (ks, vs) = jax.lax.scan(step, x, (p_stack, c["pk"], c["pv"], txs))
        return x, {"pk": ks, "pv": vs}

    def step(carry, inp):
        h, ck, cv = carry
        li, p, slices = inp
        return layer(h, p, ck, cv, mk(slices), li), None
    n = c["k"].shape[0]
    (x, ks, vs), _ = jax.lax.scan(step, (x, c["k"], c["v"]),
                                  (jnp.arange(n), p_stack, txs))
    return x, {"k": ks, "v": vs}


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------

def decode_step(params, cache, token, cfg: ModelConfig, tables=None,
                ptab=None, write_mask=None):
    """token (B, 1) int32 -> (logits (B, 1, V), new cache).

    tables: sparsity.sparse_linear.SegmentedKernelTables — per-segment
    uniform-MAXB joint-sparse projection packs whose arrays ride each
    segment's layer scan as xs (next to the per-layer cache slices), so
    every decode-step projection of every family runs the DB-PIM kernel
    (MoE: grouped expert packs dispatch one kernel call per expert
    slice; enc-dec: cross-attention packs next to self-attention; hybrid
    segments pack independently). None keeps the plain matmuls.

    write_mask (B,) bool drops the K/V writes of slots where it is False,
    in-step: their attention cache rows stay bitwise as they were, so
    merge_slots has no K/V to select (SSM and "pos" leaves still merge
    there). None writes every slot.

    ptab (B, max_pages) int32 switches attention segments to the PAGED
    cache (pooled {"pk","pv"} leaves): KV writes route through the page
    table. The table rides every segment's scan as a broadcast operand:
    one global page-id space across segments.
    """
    segs = decoder_layout(cfg)
    seg_tables = segment_tables(tables, segs, cfg)
    pos = cache["pos"]
    B = token.shape[0]
    x = embed_tokens(params["embed"], token, cfg)
    if cfg.rope_pct == 0:
        posv = attn_mod._per_slot_pos(pos, B)
        x = x + _sinusoidal_at(posv[:, None], cfg.d_model).astype(x.dtype)
    enc_out = cache.get("enc_out")
    new_cache = dict(cache)

    for seg in segs:
        st = seg_tables.get(seg.name)
        txs = st.arrays if st is not None else None
        mk = (lambda slices, st=st:
              st.dense_fn(slices) if st is not None else None)
        c = cache[seg.cache]
        if seg.mixer == "attn":
            def layer(h, p, ck, cv, mm, li, seg=seg, pt=_page_table(c, ptab)):
                hn = apply_norm(p["norm1"], h, cfg)
                y, ck, cv = attn_mod.decode_attention(
                    p["attn"], hn, ck, cv, pos, cfg, dense_fn=mm, ptab=pt,
                    write_mask=write_mask, layer=li)
                h = _block_tail(seg, p, h + y, cfg, mm, enc_out)
                return h, ck, cv
            x, nc = _attn_scan(layer, x, params[seg.name], c, txs, mk)
            if "pos" in c:
                nc["pos"] = pos + 1
            new_cache[seg.cache] = nc
        else:
            def step(h, inp, seg=seg, mk=mk):
                p, conv, state, slices = inp
                mm = mk(slices)
                hn = apply_norm(p["norm1"], h, cfg)
                y, conv, state = ssm_mod.decode_ssm(
                    p["ssm"], hn, conv, state, cfg, dense_fn=mm)
                h = _block_tail(seg, p, h + y, cfg, mm, enc_out)
                return h, (conv, state)
            x, (convs, states) = jax.lax.scan(
                step, x, (params[seg.name], c["conv"], c["state"], txs))
            new_cache[seg.cache] = {"conv": convs, "state": states}

    new_cache["pos"] = pos + 1
    x = apply_norm(params["final_norm"], x, cfg)
    return logits_from_hidden(params["embed"], x, cfg), new_cache


def decode_chunk(params, cache, tokens, n_valid, cfg: ModelConfig,
                 tables=None, ptab=None):
    """Chunked cache-filling prefill: C prompt tokens per slot, one step.

    ptab (B, max_pages) int32 switches attention segments to the PAGED
    cache ({"pk","pv"} pool leaves) — chunk writes scatter through the
    page table with the same drop-sentinel idiom as the contiguous path
    (idle slots' n_valid = 0 already gates their writes in place, so no
    separate write mask is needed here).

    tokens (B, C) int32; n_valid (B,) int32 in [0, C] — the number of real
    prompt tokens per slot this chunk (ragged tail chunks and idle slots
    pass fewer/0; their cache slices are left untouched). cache["pos"] is
    the per-slot fill depth ((B,) vector, or a scalar broadcast).

    Returns (logits (B, 1, V) of each slot's LAST VALID token — the
    first-generated-token logits when the chunk completes a prompt — and
    the cache advanced by n_valid per slot). The chunk is one fixed-shape
    device step: time-to-first-token is ceil(P/C) steps instead of P, and
    the unembedding runs once per chunk instead of once per prompt token.

    Per-token math vs running `decode_step` n_valid times: bit-identical
    for attention segments (self- and cross-attention chunks project all
    C tokens in one row-stable matmul), for MoE segments whenever the
    per-position capacity covers every assignment (capacity() clamps to
    B * top_k at decode-batch scale, so it always does — each chunk
    position routes against exactly one decode step's token pool), and
    for SSM segments with cfg.prefill_exact=True. The default SSM path
    is the parallel SSD form (ssm.prefill_ssm_parallel) — the in/out
    projections are read ONCE per chunk instead of once per token, at
    the cost of tolerance-level (ssm.PARALLEL_PREFILL_ATOL) instead of
    bitwise equivalence.

    Requires full causal attention (cfg.window == 0): a sliding-window
    ring buffer overwrites slots within a chunk, which only a sequential
    walk reproduces.

    Like decode_step, `tables` threads the per-segment uniform-MAXB
    joint-sparse packs through each segment's scan, so prompt chunks run
    the DB-PIM kernel too.
    """
    if cfg.window:
        raise ValueError(f"chunked prefill is not supported for {cfg.name}"
                         f": sliding-window ring caches need stepwise "
                         f"prefill")
    segs = decoder_layout(cfg)
    seg_tables = segment_tables(tables, segs, cfg)
    B, C = tokens.shape
    pos = attn_mod._per_slot_pos(cache["pos"], B)
    n_valid = jnp.asarray(n_valid, jnp.int32)

    x = embed_tokens(params["embed"], tokens, cfg)
    if cfg.rope_pct == 0:
        qpos = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        x = x + _sinusoidal_at(qpos, cfg.d_model).astype(x.dtype)
    enc_out = cache.get("enc_out")
    new_cache = dict(cache)

    ssm_prefill = (ssm_mod.prefill_ssm if cfg.prefill_exact
                   else ssm_mod.prefill_ssm_parallel)

    for seg in segs:
        st = seg_tables.get(seg.name)
        txs = st.arrays if st is not None else None
        mk = (lambda slices, st=st:
              st.dense_fn(slices) if st is not None else None)
        c = cache[seg.cache]
        if seg.mixer == "attn":
            def layer(h, p, ck, cv, mm, li, seg=seg, pt=_page_table(c, ptab)):
                hn = apply_norm(p["norm1"], h, cfg)
                y, ck, cv = attn_mod.prefill_attention(
                    p["attn"], hn, ck, cv, pos, n_valid, cfg, dense_fn=mm,
                    ptab=pt, layer=li)
                h = _block_tail(seg, p, h + y, cfg, mm, enc_out,
                                per_position=True)
                return h, ck, cv
            x, nc = _attn_scan(layer, x, params[seg.name], c, txs, mk)
            if "pos" in c:
                nc["pos"] = pos + n_valid
            new_cache[seg.cache] = nc
        else:
            def step(h, inp, seg=seg, mk=mk):
                p, conv, state, slices = inp
                mm = mk(slices)
                hn = apply_norm(p["norm1"], h, cfg)
                y, conv, state = ssm_prefill(
                    p["ssm"], hn, conv, state, n_valid, cfg, dense_fn=mm)
                h = _block_tail(seg, p, h + y, cfg, mm, enc_out,
                                per_position=True)
                return h, (conv, state)
            x, (convs, states) = jax.lax.scan(
                step, x, (params[seg.name], c["conv"], c["state"], txs))
            new_cache[seg.cache] = {"conv": convs, "state": states}

    new_cache["pos"] = pos + n_valid
    x = apply_norm(params["final_norm"], x, cfg)
    last = jnp.clip(n_valid - 1, 0, C - 1)
    x_last = x[jnp.arange(B), last][:, None]                  # (B, 1, D)
    return logits_from_hidden(params["embed"], x_last, cfg), new_cache


# ---------------------------------------------------------------------------
# Per-slot cache surgery (the serving engine's slot scheduler)
# ---------------------------------------------------------------------------

def _select_batch(mask, new, old, axis: int):
    shape = [1] * new.ndim
    shape[axis] = mask.shape[0]
    return jnp.where(mask.reshape(shape), new, old)


def merge_slots(new_cache, old_cache, keep_mask, cfg: ModelConfig):
    """Per-slot cache select: slots where keep_mask (B,) is True take the
    updated cache, the rest keep their previous contents and position.

    This is what lets ONE fixed-shape decode step serve a batch where
    only some slots are actively decoding (others are mid-prefill, free,
    or draining): the step computes updates for every slot, and the merge
    discards the updates of inactive ones. Positions come out as (B,)
    vectors regardless of input shape.

    Only "pos" and the SSM conv/state leaves ((L_seg, B, ...), batch on
    axis 1) are selected. Attention K/V — contiguous "k"/"v" and paged
    "pk"/"pv" alike — pass through updated: the step gated their writes
    per slot in place (decode_attention's write_mask, prefill's n_valid),
    so an inactive slot's rows were never touched, and a select over the
    whole cache would only copy it. Encoder output (enc-dec) is shared
    across the batch and passes through unchanged."""
    B = keep_mask.shape[0]

    def visit(path, new, old):
        key = str(getattr(path[-1], "key", path[-1]))
        if key == "pos":
            return jnp.where(keep_mask, attn_mod._per_slot_pos(new, B),
                             attn_mod._per_slot_pos(old, B))
        if key in ("conv", "state"):
            return _select_batch(keep_mask, new, old, axis=1)
        return new

    return jax.tree_util.tree_map_with_path(visit, new_cache, old_cache)


def _zero_slots(leaf, slot_mask):
    """Zero leaf[:, b] (batch on axis 1) for each b where slot_mask is
    set: a dynamic-update-slice of zeros per masked slot, skipped for
    the others, which XLA performs in place on a donated buffer."""
    zero = jnp.zeros((leaf.shape[0], 1) + leaf.shape[2:], leaf.dtype)

    def body(b, x):
        start = (0, b) + (0,) * (x.ndim - 2)
        return jax.lax.cond(
            slot_mask[b],
            lambda x: jax.lax.dynamic_update_slice(x, zero, start),
            lambda x: x, x)

    return jax.lax.fori_loop(0, slot_mask.shape[0], body, leaf)


def reset_slots(cache, slot_mask, cfg: ModelConfig, ptab=None):
    """Zero the KV/SSM cache rows and position of the slots where
    slot_mask (B,) is True — the admission step before a freed slot takes
    a new request. Without this, a refilled slot's attention would still
    mask correctly (pos restarts at 0) but SSM states and ring buffers
    carry the PREVIOUS request's activations into the new one. Encoder
    output (enc-dec) is shared and not per-request; callers that rotate
    enc-dec requests must swap it themselves.

    Contiguous k/v are zeroed in place, one slot at a time and only
    where the mask is set (_zero_slots), so an admission writes its own
    slots and never reads or selects the whole cache.
    Paged caches additionally take ``ptab`` (B, max_pages): the reset is
    PAGE-TABLE SURGERY — only the pages the masked slots' table rows
    point at are zeroed (fixed-shape scatter; -1 rows route to the drop
    sentinel), so admitting one request never touches another slot's
    pages. SSM/"pos" leaves are per-slot and reset by select."""
    B = slot_mask.shape[0]
    if ptab is not None:
        sel = slot_mask[:, None] & (ptab >= 0)               # (B, MP)

    def visit(path, leaf):
        key = str(getattr(path[-1], "key", path[-1]))
        if key == "pos":
            return jnp.where(slot_mask, 0, attn_mod._per_slot_pos(leaf, B))
        if key in ("conv", "state"):
            return _select_batch(slot_mask, jnp.zeros_like(leaf), leaf,
                                 axis=1)
        if key in ("k", "v"):
            return _zero_slots(leaf, slot_mask)
        if key in ("pk", "pv") and ptab is not None:
            pids = jnp.where(sel, ptab, leaf.shape[1]).reshape(-1)
            return leaf.at[:, pids].set(jnp.zeros((), leaf.dtype),
                                        mode="drop")
        return leaf

    return jax.tree_util.tree_map_with_path(visit, cache)


def prefill(params, tokens, cfg: ModelConfig,
            frames: Optional[jnp.ndarray] = None, tables=None):
    """Prefill returns last-position logits. (The dry-run lowers the full
    forward; serving fills caches through the engine — chunked
    `decode_chunk` steps, or stepwise decode for families without chunked
    support. See serving.prefill.)"""
    from .transformer import forward
    enc_out = encode(params, frames, cfg) if cfg.is_encdec else None
    return forward(params, tokens, cfg, enc_out=enc_out, last_only=True,
                   tables=tables)
