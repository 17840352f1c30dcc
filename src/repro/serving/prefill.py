"""Chunked cache-filling prefill for the serving engine.

Two prefill policies over the SAME per-slot caches:

  * "chunked" — fixed-shape (B, prefill_chunk) chunks through
    ``launch.steps.build_step("prefill_chunk")`` (-> models.decode_chunk):
    each prefilling slot advances up to ``prefill_chunk`` prompt tokens
    per device call, so time-to-first-token is ceil(P/C) calls. Chunks
    ride the stacked joint-sparse tables exactly like decode steps.
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
from repro.runtime import sharding as shr

PREFILL_MODES = ("chunked", "full")


def assemble_chunk(prompts: Dict[int, np.ndarray], cursors: Dict[int, int],
                   n_slots: int, chunk: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Fixed-shape chunk batch from ragged per-slot prompt cursors.

    prompts/cursors map slot -> prompt array / tokens already prefilled.
    Returns (tokens (n_slots, chunk) int32, n_valid (n_slots,) int32);
    slots absent from `prompts` get n_valid 0 (their cache is untouched
    by the chunk step). Tail chunks are ragged: n_valid < chunk."""
    tokens = np.zeros((n_slots, chunk), np.int32)
    n_valid = np.zeros((n_slots,), np.int32)
    for s, prompt in prompts.items():
        cur = cursors[s]
        n = min(chunk, len(prompt) - cur)
        if n <= 0:
            continue
        tokens[s, :n] = prompt[cur:cur + n]
        n_valid[s] = n
    return tokens, n_valid


def build_chunk_step(cfg, mesh, params, tables, cache, n_slots: int,
                     chunk: int, paged: bool = False, max_pages: int = 0):
    """Jit the fixed-shape chunk prefill step with serving shardings.
    The jitted step is called ``(params, tables, cache, tokens, n_valid
    [, ptab])``; ``tables`` (None for dense serving) fixes the table
    layout the step is sharded for.

    Compiles ONCE for (n_slots, chunk) — every request, whatever its
    prompt length, flows through this single executable (ragged tails via
    n_valid), which is what keeps admission latency flat under load.

    paged=True compiles the page-table variant: one extra trailing
    ``ptab`` (n_slots, max_pages) int32 operand (the host allocator's
    table) the KV writes scatter through. The table is per-call data,
    not cache state — page churn between calls never recompiles."""
    import jax.numpy as jnp

    step_fn, shard_fn = build_step(cfg, mesh, "prefill_chunk", paged=paged)
    args = (params, tables, cache, jnp.zeros((n_slots, chunk), jnp.int32),
            jnp.zeros((n_slots,), jnp.int32))
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
