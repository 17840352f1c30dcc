"""Per-call-kind weight-traffic waterfall for the serving engine.

One scalar ``weight_bytes`` per call kind says WHETHER a run regressed;
the waterfall says WHERE: every byte is attributed to the parameter path
that moved it — dense projections by their pytree path
("blocks/attn/wq", "seg01/blocks/ssm/w_out", "blocks/moe/w1"), packed
stacked tables by table family and part ("tables/wq/w_blocks",
"tables/wq/idx", ...), and shape-fallback charges in explicit
"(untagged ...)" rows. Rows sum to the per-call ``weight_bytes``
EXACTLY (runtime.jaxpr_cost charges both at the same site with integer
byte values), which the serving benchmark equality-tests.

This is the instrumented-characterization layer the PIM benchmarking
literature (PAPERS.md: Gómez-Luna et al., CIMinus) argues real PIM
throughput work needs: modeled bytes are only trustworthy when you can
see which structure pays them.
"""

from __future__ import annotations

from typing import Dict

from repro.launch.steps import build_step
from repro.runtime.jaxpr_cost import analyze_call_kinds


def serving_cost_by_kind(cfg, mesh, params, cache, *, n_slots: int,
                         prefill_chunk: int, tables=None,
                         include_exact_fallback: bool = False,
                         paged: bool = False, max_pages: int = 0
                         ) -> Dict[str, Dict]:
    """Full jaxpr_cost accounting (weight_bytes + weight_bytes_by_path +
    flops/bytes) for one device call of every serving call kind ``cfg``
    supports, keyed by the step builders' call_kind tags.

    include_exact_fallback: for parallel-SSD archs, also analyze the
    exact-chunk step the parallel form is benchmarked against.
    paged/max_pages: analyze the page-table step variants (``cache`` must
    then be a pooled paged cache) — the extra ptab operand rides along."""
    import jax.numpy as jnp

    extra = ()
    if paged:
        extra = (jnp.full((n_slots, max_pages), -1, jnp.int32),)
    decode_fn, _ = build_step(cfg, mesh, "decode", paged=paged)
    tok1 = jnp.zeros((n_slots, 1), jnp.int32)
    act = jnp.ones((n_slots,), bool)
    calls = {decode_fn.call_kind:
             (decode_fn, (params, tables, cache, tok1, act) + extra)}
    caps = cfg.serving_capabilities()
    if caps.chunked_prefill:
        tokc = jnp.zeros((n_slots, prefill_chunk), jnp.int32)
        nv = jnp.full((n_slots,), prefill_chunk, jnp.int32)
        slots = jnp.arange(n_slots, dtype=jnp.int32)  # the whole batch
        chunk_fn, _ = build_step(cfg, mesh, "prefill_chunk", paged=paged)
        calls[chunk_fn.call_kind] = (chunk_fn, (params, tables, cache, tokc,
                                                nv, slots) + extra)
        if include_exact_fallback and caps.parallel_prefill \
                and not cfg.prefill_exact:
            exact_fn, _ = build_step(cfg.scaled(prefill_exact=True), mesh,
                                     "prefill_chunk", paged=paged)
            calls[exact_fn.call_kind] = (exact_fn,
                                         (params, tables, cache, tokc, nv,
                                          slots) + extra)
    # params and the packed tables (args 0 and 1) seed the provenance
    # tags: table leaves label as "tables/<family>/<part>"
    return analyze_call_kinds(calls, weight_argnums=(0, 1))


def engine_waterfall(engine) -> Dict[str, Dict[str, object]]:
    """{call_kind: {"total": weight_bytes, "rows": {path: bytes}}} for a
    constructed ServeEngine — the traffic attribution a --trace-out run
    embeds in its trace (Tracer.waterfall) for the report CLI."""
    costs = serving_cost_by_kind(
        engine.cfg, engine.mesh, engine.params, engine.cache,
        n_slots=engine.n_slots, prefill_chunk=engine.prefill_chunk,
        tables=engine.stacked_tables, paged=engine.paged,
        max_pages=getattr(engine, "max_pages_per_slot", 0))
    return {kind: {"total": float(acc["weight_bytes"]),
                   "rows": dict(acc["weight_bytes_by_path"])}
            for kind, acc in costs.items()}
