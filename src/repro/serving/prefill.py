"""Chunked cache-filling prefill for the serving engine.

Two prefill policies over the SAME per-slot caches:

  * "chunked" — fixed-shape (rows, prefill_chunk) chunks through
    ``launch.steps.build_step("prefill_chunk")`` (-> models.decode_chunk):
    each prefilling slot advances up to ``prefill_chunk`` prompt tokens
    per device call, so time-to-first-token is ceil(P/C) calls. A chunk
    runs on the smallest row bucket (chunk_buckets: 1, 2, 4, ...,
    n_slots) that holds the prefilling slots, one executable per bucket.
    Chunks ride the stacked joint-sparse tables exactly like decode
    steps.
  * "full" — the full-forward baseline: prompt tokens feed the ordinary
    (B, 1) decode step one at a time (P calls to first token). Prefilling
    slots share the decode call with in-flight decodes, so this is the
    honest continuous-batching baseline, not a strawman.

Within "chunked", the per-token math comes in two flavors, dispatched by
ModelConfig (the compiled step's ``call_kind`` tag says which):

  * exact ("prefill_chunk_exact") — attention families (a chunk already
    projects all C tokens in one matmul) and SSM with
    ``cfg.prefill_exact=True``: bit-identical to sequential decode.
  * parallel SSD ("prefill_parallel") — the SSM default: the chunk is
    evaluated in the training-style matrix form
    (models.ssm.prefill_ssm_parallel), reading the stacked in/out
    projections ONCE per chunk instead of once per token (~C x less SSM
    prefill weight traffic), tolerance-equal to sequential decode
    (models.ssm.PARALLEL_PREFILL_ATOL), not bitwise.

Exact policies never change generated tokens — only step counts move.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.launch.steps import build_step
from repro.models.moe import capacity
from repro.runtime import sharding as shr

PREFILL_MODES = ("chunked", "full")


def chunk_buckets(cfg, n_slots: int, chunk: int) -> Tuple[int, ...]:
    """Row buckets of the chunk step for an engine of ``n_slots``: the
    powers of two below n_slots, then n_slots (the whole batch). A chunk
    runs on the smallest bucket that holds its prefilling slots, so its
    rows (and the kernel's row tiles) scale with the slots that prefill,
    not with the batch.

    Compact rows compute what the whole batch computes for each slot
    only where a row's result does not depend on the other rows:

      * MoE capacity dispatch breaks that when an expert's capacity at
        some bucket does not cover every (token, choice) pair: which
        tokens drop then depends on who else is in the call. Such a
        configuration keeps the whole-batch step alone.
      * A projection of a single row is a matrix-vector product, which a
        backend may sum in another order than the same row inside a
        matrix product (XLA's CPU backend does), and exact chunks must
        stay bitwise with the decode step. The exact SSM recurrence
        projects one token per row at a time, other chunks all of a
        row's ``chunk`` tokens at once; buckets whose projections would
        have one row are left out."""
    buckets = []
    b = 1
    while b < n_slots:
        buckets.append(b)
        b *= 2
    buckets.append(n_slots)
    segs = cfg.serving_capabilities().segments
    if any(seg.ffn == "moe" for seg in segs) and \
            any(capacity(cfg, b) < b * cfg.top_k for b in buckets):
        return (n_slots,)
    per_token = cfg.prefill_exact and any(seg.mixer == "ssm"
                                          for seg in segs)
    tokens = 1 if per_token else chunk
    return tuple(b for b in buckets if b * tokens > 1 or b == n_slots)


def assemble_chunk(prompts: Dict[int, np.ndarray], cursors: Dict[int, int],
                   n_slots: int, chunk: int, rows: Optional[int] = None
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fixed-shape chunk batch from ragged per-slot prompt cursors.

    prompts/cursors map slot -> prompt array / tokens already prefilled.
    Returns (tokens (rows, chunk) int32, n_valid (rows,) int32, slots
    (rows,) int32), rows defaulting to n_slots. With rows == n_slots,
    row s is slot s (slots = arange); a smaller bucket holds the slots
    of `prompts` in slot order, then padding rows with slot index
    n_slots (out of range). Rows of slots absent from `prompts`, and
    padding, get n_valid 0 (the chunk step writes nothing for them).
    Tail chunks are ragged: n_valid < chunk."""
    rows = n_slots if rows is None else rows
    if rows == n_slots:
        slots = np.arange(n_slots, dtype=np.int32)
    else:
        slots = np.full((rows,), n_slots, np.int32)
        slots[:len(prompts)] = sorted(prompts)
    tokens = np.zeros((rows, chunk), np.int32)
    n_valid = np.zeros((rows,), np.int32)
    for r, s in enumerate(slots.tolist()):
        if s not in prompts:
            continue
        cur = cursors[s]
        n = min(chunk, len(prompts[s]) - cur)
        if n <= 0:
            continue
        tokens[r, :n] = prompts[s][cur:cur + n]
        n_valid[r] = n
    return tokens, n_valid, slots


def build_chunk_step(cfg, mesh, params, tables, cache, n_slots: int,
                     chunk: int, rows: int, paged: bool = False,
                     max_pages: int = 0):
    """Jit the chunk prefill step for one row bucket (chunk_buckets) with
    serving shardings. The jitted step is called ``(params, tables,
    cache, tokens (rows, chunk), n_valid (rows,), slots (rows,)
    [, ptab])``; ``tables`` (None for dense serving) fixes the table
    layout the step is sharded for.

    Compiles ONCE for (rows, chunk) — every chunk that fits the bucket,
    whatever its prompts' lengths and slots, flows through this single
    executable (ragged tails via n_valid, the slots as data), which is
    what keeps admission latency flat under load.

    paged=True compiles the page-table variant: one extra trailing
    ``ptab`` (n_slots, max_pages) int32 operand (the host allocator's
    table) the KV writes scatter through. The table is per-call data,
    not cache state — page churn between calls never recompiles."""
    import jax.numpy as jnp

    step_fn, shard_fn = build_step(cfg, mesh, "prefill_chunk", paged=paged)
    args = (params, tables, cache, jnp.zeros((rows, chunk), jnp.int32),
            jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), jnp.int32))
    if paged:
        args = args + (jnp.full((n_slots, max_pages), -1, jnp.int32),)
    specs = shard_fn(*args)
    cspec = specs[2]
    jitted = jax.jit(step_fn,
                     in_shardings=tuple(shr.named(s, mesh) for s in specs),
                     # pin the returned cache to the spec it arrives
                     # with; propagated (replicated) output shardings
                     # make downstream steps recompile at tick 1
                     out_shardings=(None, shr.named(cspec, mesh)),
                     donate_argnums=(2,))
    # per-kind cost attribution rides along (jaxpr_cost.analyze_call_kinds)
    jitted.call_kind = step_fn.call_kind
    jitted.arch = cfg.name
    return jitted
