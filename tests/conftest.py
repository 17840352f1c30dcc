"""Shared serving-test drivers (imported by test_serving_engine.py and
test_parallel_prefill.py): feed a prompt batch into a fresh per-slot
cache through sequential decode steps, or through fixed-shape
decode_chunk calls with ragged tails — the two prefill paths every
equivalence test compares."""

import os

# The chunk-vs-stepwise tests compare logits bitwise. XLA:CPU's
# multi-threaded Eigen matmul picks its summation order from the operand
# shapes and the host's thread pool, so a (B, C) chunk and a (B, 1) step
# can round differently on one machine and identically on another; one
# thread per matmul fixes the order everywhere. Set before any backend
# starts.
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"), "--xla_cpu_multi_thread_eigen=false")))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.models import decode_chunk, decode_step, init_cache  # noqa: E402


def stepwise_prefill(params, cfg, prompts, max_len, tables=None):
    """Reference: every prompt token through the (B, 1) decode step."""
    B, P = prompts.shape
    cache = init_cache(cfg, B, max_len)
    cache["pos"] = jnp.zeros((B,), jnp.int32)
    logits = None
    for t in range(P):
        logits, cache = decode_step(params, cache,
                                    jnp.asarray(prompts[:, t:t + 1]), cfg,
                                    tables=tables)
    return logits, cache


def chunked_prefill(params, cfg, prompts, max_len, chunk, tables=None):
    """The prompt through ceil(P/chunk) decode_chunk calls (ragged tail
    via n_valid); chunk math is cfg-dispatched (exact vs parallel SSD)."""
    B, P = prompts.shape
    cache = init_cache(cfg, B, max_len)
    cache["pos"] = jnp.zeros((B,), jnp.int32)
    logits = None
    for s in range(0, P, chunk):
        n = min(chunk, P - s)
        toks = np.zeros((B, chunk), np.int32)
        toks[:, :n] = prompts[:, s:s + n]
        logits, cache = decode_chunk(params, cache, jnp.asarray(toks),
                                     jnp.full((B,), n, jnp.int32), cfg,
                                     tables=tables)
    return logits, cache
