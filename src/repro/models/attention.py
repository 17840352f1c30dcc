"""Attention: GQA / MQA / MHA with RoPE, qk-norm, sliding windows (SWA),
cross-attention, and a static-shape KV cache for prefill/decode.

Shapes: x (B, S, D); q (B, S, Hq, hd); k/v (B, S, Hkv, hd).
Cache: stacked {"k","v"} (L, B, Hkv // G, S_max, G * hd) with G KV heads
per lane row (kv_group) + integer write index — or, paged, a pooled
{"pk","pv"} (L, n_pages, page_size, Hkv, hd) indexed through a per-slot
page table (init_paged_cache; serving.paging owns the table).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import apply_rope, dtype_of, rms_head_norm, rope_frequencies
from repro.runtime.act_sharding import constrain


def init_attention(cfg: ModelConfig, key, cross: bool = False):
    dt = dtype_of(cfg)
    d = cfg.d_model
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    p = {"wq": (jax.random.normal(k1, (d, cfg.q_dim)) * s).astype(dt),
         "wk": (jax.random.normal(k2, (d, cfg.kv_dim)) * s).astype(dt),
         "wv": (jax.random.normal(k3, (d, cfg.kv_dim)) * s).astype(dt),
         "wo": (jax.random.normal(k4, (cfg.q_dim, d))
                * cfg.q_dim ** -0.5).astype(dt)}
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.hd,), jnp.float32)
        p["k_norm"] = jnp.ones((cfg.hd,), jnp.float32)
    return p


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def _sdpa(q, k, v, mask, dtype):
    """q (B,Sq,H,hd), k/v (B,Skv,H,hd), mask broadcast (B,1,Sq,Skv).

    The probs @ v contraction is written as a plain batched matmul
    (``bhqk,bhkd->bhqd`` on pre-transposed v) rather than the fused
    ``bhqk,bkhd->bqhd`` form: the fused output transpose makes XLA pick
    Sq-dependent loop orders, so a 1-token decode and a C-token prefill
    chunk would disagree in the last float bit. The batched-matmul form
    is row-stable across Sq — what lets chunked prefill reproduce
    sequential decode bit-for-bit.
    """
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, jnp.moveaxis(v, 2, 1))
    return jnp.moveaxis(out, 1, 2)


CHUNKED_ATTN_THRESHOLD = 16384


def _chunked_sdpa(q, k, v, cfg: ModelConfig, dtype, chunk: int = 2048):
    """Flash-style two-level blocked attention with online softmax.

    Never materializes (S, S): outer scan over query chunks, inner scan
    over key chunks with running (max, sum, acc). Causal masking at block
    granularity (upper-triangular blocks are masked, not skipped — the 2x
    block waste is a recorded §Perf item). q/k/v: (B, S, H, hd).
    """
    B, S, H, hd = q.shape
    nq = S // chunk
    scale = hd ** -0.5
    qc = jnp.moveaxis(q.reshape(B, nq, chunk, H, hd), 1, 0)
    kc = jnp.moveaxis(k.reshape(B, nq, chunk, H, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nq, chunk, H, hd), 1, 0)

    base = jnp.arange(chunk)

    def q_block(_, qi_q):
        qi, qb = qi_q
        qpos = qi * chunk + base

        def kv_block(carry, kj_kv):
            m_prev, l_prev, acc = carry
            kj, kb, vb = kj_kv
            kpos = kj * chunk + base
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb).astype(jnp.float32)
            s = s * scale
            mask = kpos[None, :] <= qpos[:, None]
            if cfg.window:
                mask &= kpos[None, :] > qpos[:, None] - cfg.window
            s = jnp.where(mask[None, None], s, -1e30)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(dtype), vb)
            acc = acc * corr[..., None].astype(dtype) + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, H, chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((B, H, chunk), jnp.float32)
        a0 = jnp.zeros((B, H, chunk, hd), dtype)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0),
            (jnp.arange(nq), kc, vc))
        out = acc / jnp.maximum(l, 1e-30)[..., None].astype(dtype)
        return None, jnp.moveaxis(out, 1, 2)        # (B, chunk, H, hd)

    _, blocks = jax.lax.scan(q_block, None, (jnp.arange(nq), qc))
    return jnp.moveaxis(blocks, 0, 1).reshape(B, S, H, hd)


def causal_mask(sq: int, skv: int, window: int = 0):
    """(1, 1, sq, skv) bool; offsets assume q positions are the last sq of
    skv (prefill: sq == skv)."""
    qpos = jnp.arange(sq)[:, None] + (skv - sq)
    kpos = jnp.arange(skv)[None, :]
    m = kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return m[None, None]


def attention(p, x, cfg: ModelConfig, positions, causal: bool = True,
              dense_fn=None):
    """Full-sequence attention (training / encoder). positions (B, S)."""
    mm = dense_fn or (lambda w, v, name: v @ w)
    B, S, _ = x.shape
    q = _split_heads(mm(p["wq"], x, "wq"), cfg.n_heads, cfg.hd)
    k = _split_heads(mm(p["wk"], x, "wk"), cfg.n_kv_heads, cfg.hd)
    v = _split_heads(mm(p["wv"], x, "wv"), cfg.n_kv_heads, cfg.hd)
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q)
        k = rms_head_norm(p["k_norm"], k)
    if cfg.rope_pct > 0:
        cos, sin = rope_frequencies(cfg, positions)
        q = apply_rope(q, cos, sin, cfg)
        k = apply_rope(k, cos, sin, cfg)
    k = _repeat_kv(k, cfg.n_heads // cfg.n_kv_heads)
    v = _repeat_kv(v, cfg.n_heads // cfg.n_kv_heads)
    q = constrain(q, "dp", None, "tp", None)
    k = constrain(k, "dp", None, "tp", None)
    v = constrain(v, "dp", None, "tp", None)
    if causal and S >= CHUNKED_ATTN_THRESHOLD and S % 2048 == 0:
        out = _chunked_sdpa(q, k, v, cfg, x.dtype)
    else:
        if causal:
            mask = causal_mask(S, S, cfg.window)
        else:
            mask = jnp.ones((1, 1, S, S), bool)
        out = _sdpa(q, k, v, mask, x.dtype)
    return mm(p["wo"], out.reshape(B, S, cfg.q_dim), "wo")


def cross_attention(p, x, enc_out, cfg: ModelConfig, dense_fn=None):
    """Decoder cross-attention over encoder output (whisper). Hook names
    carry the "xattn/" prefix: a decoder block's self- and cross-
    attention projections pack as distinct table entries within the same
    segment, so the dense_fn lookup must not collide."""
    mm = dense_fn or (lambda w, v, name: v @ w)
    B, S, _ = x.shape
    Se = enc_out.shape[1]
    q = _split_heads(mm(p["wq"], x, "xattn/wq"), cfg.n_heads, cfg.hd)
    k = _split_heads(mm(p["wk"], enc_out, "xattn/wk"),
                     cfg.n_kv_heads, cfg.hd)
    v = _split_heads(mm(p["wv"], enc_out, "xattn/wv"),
                     cfg.n_kv_heads, cfg.hd)
    k = _repeat_kv(k, cfg.n_heads // cfg.n_kv_heads)
    v = _repeat_kv(v, cfg.n_heads // cfg.n_kv_heads)
    mask = jnp.ones((1, 1, S, Se), bool)
    out = _sdpa(q, k, v, mask, x.dtype)
    return mm(p["wo"], out.reshape(B, S, cfg.q_dim), "xattn/wo")


# ------------------------------------------------------------- cache -------

LANES = 128     # a TPU vector register row: the minor dim of a dense tile


def kv_group(cfg: ModelConfig) -> int:
    """KV heads that share one lane row of the contiguous cache: the
    largest divisor of n_kv_heads whose heads fit in LANES lanes (1 when
    hd alone fills them). With hd = 64 two heads share a 128-lane row,
    so the cache is lane-dense with no padding."""
    g = max(1, min(cfg.n_kv_heads, LANES // cfg.hd))
    while cfg.n_kv_heads % g:
        g -= 1
    return g


def init_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int):
    """Stacked KV cache for a layer stack: k/v (n_layers, B, Hkv // G, A,
    G * hd), G = kv_group(cfg) heads per row — head-group major, so each
    (slot, group) is one (A, G * hd) matrix for the attention matmuls,
    and a token's K or V is one lane row per group that the step writes
    in place. SWA archs allocate only the window (ring buffer) — that is
    what makes long_500k decode O(window)."""
    dt = dtype_of(cfg)
    alloc = min(max_len, cfg.window) if cfg.window else max_len
    g = kv_group(cfg)
    shape = (n_layers, batch, cfg.n_kv_heads // g, alloc, g * cfg.hd)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
            "pos": jnp.zeros((), jnp.int32)}


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     n_layers: int):
    """Paged KV pool for a layer stack: ``{"pk","pv"}`` of shape
    (n_layers, n_pages, page_size, Hkv, hd). There is no batch dim — a
    slot's cache is whatever pages its page-table row points at, which
    is what lets short requests stop reserving max_len worth of HBM.
    The page table itself is HOST state (serving.paging.PageAllocator)
    passed into each step as a fixed-shape operand, never cache-resident.

    Sliding-window archs keep the contiguous ring cache: the ring
    overwrite pattern is already O(window) and pages would only re-add
    the indirection without saving memory."""
    if cfg.window:
        raise ValueError(f"paged KV cache does not support sliding-window "
                         f"ring caches ({cfg.name}); serve contiguous")
    dt = dtype_of(cfg)
    shape = (n_layers, n_pages, page_size, cfg.n_kv_heads, cfg.hd)
    return {"pk": jnp.zeros(shape, dt), "pv": jnp.zeros(shape, dt)}


def _grouped(kv, g: int):
    """(B, S, Hkv, hd) -> (B, Hkv // g, S, g * hd): the contiguous
    cache's row layout."""
    B, S, H, hd = kv.shape
    return jnp.swapaxes(kv.reshape(B, S, H // g, g * hd), 1, 2)


def _write_token(stack, layer, slot, kv, ok):
    """Write one token's kv (B, 1, Hkv, hd) into one layer of a
    contiguous cache stack (L, B, Hg, A, W) at position slot (B,), in
    place: one W-lane row per (slot, head group). A slot where ok (B,)
    is False gets the out-of-range position A, and its write is DROPPED
    — its cache rows stay bitwise as they were."""
    B, Hg, A = stack.shape[1], stack.shape[2], stack.shape[3]
    upd = _grouped(kv, stack.shape[4] // kv.shape[3])[:, :, 0]  # (B,Hg,W)
    pos = jnp.where(ok, slot, A)[:, None]
    return stack.at[layer, jnp.arange(B)[:, None], jnp.arange(Hg)[None, :],
                    pos].set(upd, mode="drop")


def _write_chunk(stack, layer, start, kv, n, slots=None):
    """Write a chunk's kv (R, C, Hkv, hd) into one layer of a contiguous
    cache stack (L, B, Hg, A, W), in place: row r's first n[r] tokens
    go to slot slots[r] (r itself when slots is None) at positions
    start[r] .. start[r] + n[r] - 1, as a dynamic-update-slice of its
    contiguous (C, W) window in each head group, in which every other
    row keeps what it held (a window that would run past the cache's
    end starts earlier, its rows shifted to match). A row with
    n[r] == 0 is skipped: its slot's cache stays bitwise as it was, and
    the step touches only the windows of the slots that prefill."""
    L, B, Hg, A, W = stack.shape
    R, C = kv.shape[0], kv.shape[1]
    if C > A:
        raise ValueError(f"a chunk of {C} tokens does not fit a cache of "
                         f"{A} positions")
    upd = _grouped(kv, W // kv.shape[3])                       # (B,Hg,C,W)
    at = jnp.minimum(start, A - C)

    def write(b, st):
        src = jnp.arange(C) - (start[b] - at[b])
        keep = ((src >= 0) & (src < n[b]))[:, None]
        new = jnp.take(upd[b], jnp.clip(src, 0, C - 1), axis=1)
        for g in range(Hg):              # one contiguous (C, W) window each
            idx = (layer, b if slots is None else slots[b], g, at[b], 0)
            old = jax.lax.dynamic_slice(st, idx, (1, 1, 1, C, W))[0, 0, 0]
            blk = jnp.where(keep, new[g], old)
            st = jax.lax.dynamic_update_slice(st, blk[None, None, None], idx)
        return st

    return jax.lax.fori_loop(
        0, R, lambda b, st: jax.lax.cond(n[b] > 0, write,
                                         lambda b, st: st, b, st), stack)


def _cached_sdpa(q, k, v, valid, dtype):
    """Attention of q (B, Sq, Hq, hd) over one layer's cache k/v
    (B, Hg, A, W), W = G * hd; valid (B, Sq, A) bool.

    Each q head sits in its KV head's hd lanes of a W-lane row, zeros in
    the other G - 1 heads' lanes, so QK and PV are plain batched matmuls
    over (slot, group) on the cache as it is stored: no relayout, no
    copy. The zero lanes add exact zeros to QK; PV keeps each head's own
    lanes. Every query row is its own matmul row, which keeps a C-token
    chunk bit-identical to C single-token steps (see _sdpa)."""
    B, Sq, Hq, hd = q.shape
    Hg, A, W = k.shape[1], k.shape[2], k.shape[3]
    G = W // hd
    rep = Hq // (Hg * G)
    q6 = jnp.transpose(q.reshape(B, Sq, Hg, G, rep, hd),
                       (0, 2, 3, 4, 1, 5))               # (B,Hg,G,rep,Sq,hd)
    own = jnp.eye(G, dtype=bool)[:, None, None, :, None]  # (G,1,1,G,1)
    qm = jnp.where(own, q6[:, :, :, :, :, None], jnp.zeros((), q.dtype))
    M = G * rep * Sq
    qm = qm.reshape(B, Hg, M, W)
    logits = jnp.einsum("bgmw,bgaw->bgma", qm, k).astype(jnp.float32)
    logits = logits * hd ** -0.5
    mask = jnp.broadcast_to(valid[:, None, None, None],
                            (B, 1, G, rep, Sq, A)).reshape(B, 1, M, A)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    r = jnp.einsum("bgma,bgaw->bgmw", probs, v)
    r = r.reshape(B, Hg, G, rep, Sq, G, hd)
    out = jnp.stack([r[:, :, e, :, :, e] for e in range(G)], axis=2)
    return jnp.transpose(out, (0, 4, 1, 2, 3, 5)).reshape(B, Sq, Hq, hd)


def _paged_view(pool, ptab, g: int):
    """Gather a per-slot contiguous view (B, Hkv // g, MP*PS, g * hd) —
    the contiguous cache's layout — out of the page pool through the
    page table. Unallocated entries (-1) clamp to page 0 — their columns
    are beyond every query's position, so the causal mask zeroes them
    exactly (softmax of -1e30 underflows to 0.0f) and the garbage values
    never reach an output bit."""
    n_pages = pool.shape[0]
    gid = jnp.clip(ptab, 0, n_pages - 1)                 # (B, MP)
    view = pool[gid]                                     # (B, MP, PS, H, hd)
    B, MP, PS, H, hd = view.shape
    return _grouped(view.reshape(B, MP * PS, H, hd), g)


def _per_slot_pos(pos, B: int):
    """Normalize a cache position to per-slot (B,) int32. Serving keeps a
    scalar position for lock-step batches and a vector when slots hold
    requests at different depths (the serving engine's continuous-batching
    regime); both shapes flow through the same vectorized math."""
    return jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(pos, jnp.int32)),
                            (B,))


def _project_qkv(p, x, qpos, cfg: ModelConfig, mm):
    q = _split_heads(mm(p["wq"], x, "wq"), cfg.n_heads, cfg.hd)
    k = _split_heads(mm(p["wk"], x, "wk"), cfg.n_kv_heads, cfg.hd)
    v = _split_heads(mm(p["wv"], x, "wv"), cfg.n_kv_heads, cfg.hd)
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q)
        k = rms_head_norm(p["k_norm"], k)
    if cfg.rope_pct > 0:
        cos, sin = rope_frequencies(cfg, qpos)
        q = apply_rope(q, cos, sin, cfg)
        k = apply_rope(k, cos, sin, cfg)
    return q, k, v


def decode_attention(p, x, cache_k, cache_v, pos, cfg: ModelConfig,
                     dense_fn=None, ptab=None, write_mask=None, layer=None):
    """Single-token decode for one layer.

    x (B, 1, D); pos = number of tokens already in the cache — a scalar
    (lock-step batch) or a (B,) vector (per-slot depths). write_mask
    (B,) bool, if given, drops the writes of slots where it is False:
    their cache rows stay bitwise as they were. Returns (out, new_k,
    new_v).

    CONTIGUOUS mode (ptab is None): cache_k/v are the segment's whole
    stacked cache (L, B, Hg, A, W) and ``layer`` indexes it. The new
    token's rows are scattered into that buffer in place (position
    pos % A for a sliding-window ring, else pos) and the attention reads
    the layer's slice of it as stored — the step never copies, re-lays
    out or selects a layer's K/V.

    PAGED mode (ptab is not None): cache_k/v are instead one layer's
    page POOL (n_pages, page_size, Hkv, hd) shared by every slot, and
    ptab (B, max_pages) int32 maps each slot's token positions to pages
    (-1 = unallocated). The write scatters through the table (negative
    page ids and masked slots route to the out-of-range sentinel and are
    DROPPED); the read gathers the slot's pages back into the contiguous
    layout (B, Hg, max_pages * page_size, W). When max_pages * page_size
    equals the contiguous alloc A, the post-gather math is LITERALLY the
    contiguous computation — same values, same shapes, same reduction
    order — so paged decode is bitwise-identical to the contiguous path.
    """
    if ptab is not None and cfg.window:
        raise ValueError("paged attention does not support sliding-window "
                         "ring caches; serve contiguous")
    mm = dense_fn or (lambda w, v, name: v @ w)
    B = x.shape[0]
    posv = _per_slot_pos(pos, B)                                   # (B,)
    q, k, v = _project_qkv(p, x, posv[:, None], cfg, mm)
    ok = (jnp.ones((B,), bool) if write_mask is None
          else jnp.asarray(write_mask, bool))
    if ptab is None:
        A = cache_k.shape[3]
        slot = jnp.mod(posv, A) if cfg.window else jnp.minimum(posv, A - 1)
        new_k = _write_token(cache_k, layer, slot, k, ok)
        new_v = _write_token(cache_v, layer, slot, v, ok)
        kk, vv = new_k[layer], new_v[layer]
        kpos = jnp.arange(A)[None, :]                              # (1, A)
        if cfg.window:
            # ring buffer: all valid once full
            valid = (kpos <= slot[:, None]) | (posv[:, None] >= A)
        else:
            valid = kpos <= posv[:, None]
    else:
        NP, PS = cache_k.shape[0], cache_k.shape[1]
        A = ptab.shape[1] * PS
        wpos = jnp.minimum(posv, A - 1)
        pid = ptab[jnp.arange(B), wpos // PS]                      # (B,)
        pid_w = jnp.where(ok & (pid >= 0), pid, NP)   # NP: out of range
        new_k = cache_k.at[pid_w, wpos % PS].set(k[:, 0], mode="drop")
        new_v = cache_v.at[pid_w, wpos % PS].set(v[:, 0], mode="drop")
        g = kv_group(cfg)
        kk = _paged_view(new_k, ptab, g)
        vv = _paged_view(new_v, ptab, g)
        valid = jnp.arange(A)[None, :] <= posv[:, None]
    out = _cached_sdpa(q, kk, vv, valid[:, None], x.dtype)
    return mm(p["wo"], out.reshape(B, 1, cfg.q_dim), "wo"), new_k, new_v


def _read_rows(stack, layer, slots):
    """(R, Hg, A, W): the rows of slots[0..R-1] in one layer of a
    contiguous stack (L, B, Hg, A, W), each read by a dynamic slice. A
    gather of them (``stack[layer, slots]``) compiles on TPU to a tiled
    gather about ten times slower than the attention that reads it."""
    _, _, Hg, A, W = stack.shape
    return jnp.concatenate([
        jax.lax.dynamic_slice(stack, (layer, slots[r], 0, 0, 0),
                              (1, 1, Hg, A, W))[0]
        for r in range(slots.shape[0])])


def prefill_attention(p, x, cache_k, cache_v, pos, n_valid,
                      cfg: ModelConfig, dense_fn=None, ptab=None,
                      layer=None, slots=None):
    """Chunked cache-filling attention: C prompt tokens in one step.

    x (R, C, D), one row per slot; pos (R,) tokens already in the cache
    per row's slot; n_valid (R,) in [0, C] real tokens in this chunk
    (the tail chunk of a prompt is ragged; rows not prefilling pass 0).
    Writes the valid tokens' k/v at positions pos..pos+n_valid-1 and
    nothing else (a row with n_valid 0 is skipped, so its slot's cache
    rows are untouched) and attends each query to every cached position
    <= its own — bit-identical per token to running `decode_attention`
    n_valid times, but one MXU-shaped step. Returns (out, new_k, new_v).

    cache_k/v are, as in `decode_attention`, the segment's whole
    contiguous stack (L, B, Hg, A, W) written in place at ``layer``
    (_write_chunk), or with ptab (R, max_pages) one layer's page pool
    (n_pages, page_size, Hkv, hd); paged writes scatter through the
    table, invalid columns routed to the out-of-range sentinel and
    DROPPED, and reads gather the contiguous layout. Bitwise-identical
    to the contiguous chunk when max_pages * page_size == A.

    slots (R,) int32, in range, names each row's slot in a contiguous
    stack of B >= R slots: only those slots' windows are written and
    only their K/V rows are read. None means row r is slot r (R == B).

    Requires cfg.window == 0: a sliding-window ring buffer overwrites
    slots within the chunk, which only a sequential walk reproduces.
    """
    if cfg.window:
        raise ValueError("chunked prefill does not support sliding-window "
                         "ring caches; use stepwise (full-forward) prefill")
    mm = dense_fn or (lambda w, v, name: v @ w)
    B, C, _ = x.shape
    A = cache_k.shape[3] if ptab is None else ptab.shape[1] * cache_k.shape[1]
    posv = _per_slot_pos(pos, B)                                   # (B,)
    qpos = posv[:, None] + jnp.arange(C)[None, :]                  # (B, C)
    q, k, v = _project_qkv(p, x, qpos, cfg, mm)
    if ptab is None:
        new_k = _write_chunk(cache_k, layer, posv, k, n_valid, slots)
        new_v = _write_chunk(cache_v, layer, posv, v, n_valid, slots)
        if slots is None:
            kk, vv = new_k[layer], new_v[layer]
        else:
            kk = _read_rows(new_k, layer, slots)
            vv = _read_rows(new_v, layer, slots)
    else:
        tok_valid = jnp.arange(C)[None, :] < n_valid[:, None]      # (B, C)
        wpos = jnp.minimum(qpos, A - 1)                            # (B, C)
        NP, PS = cache_k.shape[0], cache_k.shape[1]
        pid = jnp.take_along_axis(ptab, wpos // PS, axis=1)        # (B, C)
        pid_w = jnp.where(tok_valid & (pid >= 0), pid, NP)
        new_k = cache_k.at[pid_w, wpos % PS].set(k, mode="drop")
        new_v = cache_v.at[pid_w, wpos % PS].set(v, mode="drop")
        g = kv_group(cfg)
        kk = _paged_view(new_k, ptab, g)
        vv = _paged_view(new_v, ptab, g)
    valid = jnp.arange(A)[None, None, :] <= qpos[:, :, None]       # (B,C,A)
    out = _cached_sdpa(q, kk, vv, valid, x.dtype)
    return mm(p["wo"], out.reshape(B, C, cfg.q_dim), "wo"), new_k, new_v
