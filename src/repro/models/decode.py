"""Serving path: cache init, chunked prefill, and single-token decode for every
family (dense/MoE/VLM, SSM, hybrid, enc-dec).

The decoder is a list of per-kind segments (models.segments): decode
walks it, scanning each segment's stacked layer params (and packed-table
slices) as scan xs — the HLO stays O(segments) in depth, and every
composition of attention / SSM / MoE / cross-attention sublayers flows
through the same four bodies. In the decode step a contiguous K/V stack
and an SSM segment's conv/state stacks ride the scan as carry: each
layer writes its own rows into them in place, masked per slot, so an
inactive slot's cache and position stay as they were without any
select of the whole cache. Paged pools ride as per-layer xs/ys, as do
the SSM states of a prefill chunk. Caches are static-shape; SWA archs
allocate only the window (ring buffer).
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
from .transformer import _block_tail, _sinusoidal, segment_tables


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               enc_out: Optional[jnp.ndarray] = None, *,
               n_pages: Optional[int] = None,
               page_size: Optional[int] = None) -> Dict:
    """Per-segment caches: attention segments hold stacked (L_seg, B, A,
    Hkv, hd) k/v, SSM segments stacked (L_seg, B, ...) conv/state — the
    batch axis is 1 EVERYWHERE, so per-slot writes and resets index every
    leaf the same way. Single-segment stacks keep the historical
    "attn"/"ssm" cache keys; hybrid stacks key by segment name.

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


def _ssm_scan(layer, x, p_stack, c, txs, mk, write_mask):
    """Scan an SSM segment's layers in the decode step:
    ``layer(h, p, conv, state, mm)`` -> (h, conv, state) for one layer's
    (B, ...) slices. Returns (x, the segment's new conv/state leaves).

    The conv and state stacks ride the scan as CARRY, as contiguous K/V
    do in _attn_scan: each layer reads its own slice once, keeps the old
    conv/state of slots where write_mask (B,) is False, and writes the
    layer back at its index — the stacks are updated where they lie and
    never restacked or selected whole. None writes every slot."""
    def step(carry, inp):
        h, convs, states = carry
        li, p, slices = inp
        conv = jax.lax.dynamic_index_in_dim(convs, li, keepdims=False)
        state = jax.lax.dynamic_index_in_dim(states, li, keepdims=False)
        h, new_conv, new_state = layer(h, p, conv, state, mk(slices))
        if write_mask is not None:
            new_conv = jnp.where(write_mask[:, None, None], new_conv, conv)
            new_state = jnp.where(write_mask[:, None, None, None],
                                  new_state, state)
        convs = jax.lax.dynamic_update_index_in_dim(convs, new_conv, li, 0)
        states = jax.lax.dynamic_update_index_in_dim(states, new_state, li,
                                                     0)
        return (h, convs, states), None
    n = c["state"].shape[0]
    (x, convs, states), _ = jax.lax.scan(
        step, (x, c["conv"], c["state"]), (jnp.arange(n), p_stack, txs))
    return x, {"conv": convs, "state": states}


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

    write_mask (B,) bool leaves the slots where it is False as they were,
    in-step: their K/V rows, SSM conv/state and position stay bitwise
    unchanged (the position comes out as a (B,) vector), so one
    fixed-shape step serves a batch where only some slots decode. None
    writes every slot.

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
    if write_mask is None:
        new_pos = pos + 1
    else:
        posv = attn_mod._per_slot_pos(pos, B)
        new_pos = jnp.where(write_mask, posv + 1, posv)
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
                nc["pos"] = new_pos
        else:
            def layer(h, p, conv, state, mm, seg=seg):
                hn = apply_norm(p["norm1"], h, cfg)
                y, conv, state = ssm_mod.decode_ssm(
                    p["ssm"], hn, conv, state, cfg, dense_fn=mm)
                h = _block_tail(seg, p, h + y, cfg, mm, enc_out)
                return h, conv, state
            x, nc = _ssm_scan(layer, x, params[seg.name], c, txs, mk,
                              write_mask)
        new_cache[seg.cache] = nc

    new_cache["pos"] = new_pos
    x = apply_norm(params["final_norm"], x, cfg)
    return logits_from_hidden(params["embed"], x, cfg), new_cache


def _n_slots(cache, segs, ptab) -> int:
    """Slots the cache holds: the page table's rows when paged, else the
    batch axis (1) of the first segment's per-slot leaf, its k (an
    attention segment) or its conv window (an SSM segment)."""
    if ptab is not None:
        return ptab.shape[0]
    seg = segs[0]
    return cache[seg.cache]["k" if seg.mixer == "attn" else "conv"].shape[1]


def decode_chunk(params, cache, tokens, n_valid, cfg: ModelConfig,
                 tables=None, ptab=None, slots=None):
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

    COMPACT rows: with slots (P,) int32 and P smaller than the cache's
    B slots, tokens and n_valid are (P, C) and (P,), row r prefilling
    slot slots[r]. The step gathers only those slots' position, K/V
    rows (contiguous), page-table rows (paged) and SSM conv/state, runs
    the same per-row math on P rows, and writes back only what they
    changed, in place: K/V windows as above, SSM conv/state and the
    position scattered at slots. A row with n_valid 0 (padding up to
    P; its slot index may be B, out of range) writes nothing. Logits
    are (P, 1, V), row r for slot slots[r]. With P == B the rows are the
    slots in order and slots is not read (a caller passes arange(B),
    as serving.prefill.assemble_chunk does): the whole-batch chunk,
    with no gather or scatter.
    """
    if cfg.window:
        raise ValueError(f"chunked prefill is not supported for {cfg.name}"
                         f": sliding-window ring caches need stepwise "
                         f"prefill")
    segs = decoder_layout(cfg)
    seg_tables = segment_tables(tables, segs, cfg)
    R, C = tokens.shape
    n_valid = jnp.asarray(n_valid, jnp.int32)
    B = _n_slots(cache, segs, ptab) if slots is not None else R
    pos_all = attn_mod._per_slot_pos(cache["pos"], B)
    enc_out = cache.get("enc_out")
    if R < B:
        # gathers read in-range slots (padding rows read the last slot
        # and compute nothing that is kept); writes go to dst, where a
        # row with no tokens names no slot and is dropped
        sl = jnp.clip(jnp.asarray(slots, jnp.int32), 0, B - 1)
        dst = jnp.where(n_valid > 0, sl, B)
        pos = pos_all[sl]
        if ptab is not None:
            ptab = ptab[sl]
        if enc_out is not None and enc_out.shape[0] == B:
            enc_out = enc_out[sl]
    else:
        sl = dst = None
        pos = pos_all

    def put(old, rows):                  # per-slot leaf, batch on axis 1
        if dst is None:
            return rows
        return old.at[:, dst].set(rows, mode="drop")

    x = embed_tokens(params["embed"], tokens, cfg)
    if cfg.rope_pct == 0:
        qpos = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        x = x + _sinusoidal_at(qpos, cfg.d_model).astype(x.dtype)
    new_pos = (pos + n_valid if dst is None
               else pos_all.at[dst].set(pos + n_valid, mode="drop"))
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
                    ptab=pt, layer=li, slots=None if pt is not None else sl)
                h = _block_tail(seg, p, h + y, cfg, mm, enc_out,
                                per_position=True)
                return h, ck, cv
            x, nc = _attn_scan(layer, x, params[seg.name], c, txs, mk)
            if "pos" in c:
                nc["pos"] = new_pos
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
            conv, state = c["conv"], c["state"]
            if sl is not None:           # only the P rows ride the scan
                conv, state = conv[:, sl], state[:, sl]
            x, (convs, states) = jax.lax.scan(
                step, x, (params[seg.name], conv, state, txs))
            new_cache[seg.cache] = {"conv": put(c["conv"], convs),
                                    "state": put(c["state"], states)}

    new_cache["pos"] = new_pos
    x = apply_norm(params["final_norm"], x, cfg)
    last = jnp.clip(n_valid - 1, 0, C - 1)
    x_last = x[jnp.arange(R), last][:, None]                  # (R, 1, D)
    return logits_from_hidden(params["embed"], x_last, cfg), new_cache


# ---------------------------------------------------------------------------
# Per-slot cache surgery (the serving engine's slot scheduler)
# ---------------------------------------------------------------------------

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

    Contiguous k/v and SSM conv/state are zeroed in place, one slot at a
    time and only where the mask is set (_zero_slots), so an admission
    writes its own slots and never reads or selects the whole cache.
    Paged caches additionally take ``ptab`` (B, max_pages): the reset is
    PAGE-TABLE SURGERY — only the pages the masked slots' table rows
    point at are zeroed (fixed-shape scatter; -1 rows route to the drop
    sentinel), so admitting one request never touches another slot's
    pages. "pos" is reset by select (a (B,) vector)."""
    B = slot_mask.shape[0]
    if ptab is not None:
        sel = slot_mask[:, None] & (ptab >= 0)               # (B, MP)

    def visit(path, leaf):
        key = str(getattr(path[-1], "key", path[-1]))
        if key == "pos":
            return jnp.where(slot_mask, 0, attn_mod._per_slot_pos(leaf, B))
        if key in ("k", "v", "conv", "state"):
            return _zero_slots(leaf, slot_mask)
        if key in ("pk", "pv") and ptab is not None:
            pids = jnp.where(sel, ptab, leaf.shape[1]).reshape(-1)
            return leaf.at[:, pids].set(jnp.zeros((), leaf.dtype),
                                        mode="drop")
        return leaf

    return jax.tree_util.tree_map_with_path(visit, cache)

