"""jit-able production steps: train (grad-accumulation + AdamW + schedule)
and the serving engine's decode and chunked-prefill steps — with explicit
in/out shardings for a given mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import decode_chunk, decode_step, loss_fn, merge_slots
from repro.models.config import ModelConfig
from repro.optim import AdamWState, adamw_update, cosine_with_warmup
from repro.runtime import sharding as shr


def build_train_step(cfg: ModelConfig, mesh: Mesh, *,
                     microbatches: int = 1, int8_opt_state: bool = False,
                     grad_compression: bool = False):
    """Returns (train_step, in_shardings builder). The step:
      grads = mean over `microbatches` scan iterations (activation memory
      control); AdamW with the paper's cosine schedule; ZeRO-1-sharded
      optimizer state.
    """
    dpa = shr.dp_axes(mesh)
    dpa = dpa if len(dpa) > 1 else (dpa[0] if dpa else None)

    def train_step(params, opt_state: AdamWState, batch):
        def micro_loss(p, mb):
            return loss_fn(p, mb, cfg)

        if microbatches > 1:
            def reshard(x):
                x = x.reshape((microbatches, x.shape[0] // microbatches)
                              + x.shape[1:])
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, P(None, dpa)))
            mbatch = jax.tree_util.tree_map(reshard, batch)

            def acc_fn(carry, mb):
                loss, g = jax.value_and_grad(micro_loss)(params, mb)
                acc_loss, acc_g = carry
                return (acc_loss + loss,
                        jax.tree_util.tree_map(jnp.add, acc_g, g)), None

            zero = (jnp.zeros((), jnp.float32),
                    jax.tree_util.tree_map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params))
            (loss_sum, grad_sum), _ = jax.lax.scan(acc_fn, zero, mbatch)
            loss = loss_sum / microbatches
            grads = jax.tree_util.tree_map(lambda g: g / microbatches,
                                           grad_sum)
        else:
            loss, grads = jax.value_and_grad(micro_loss)(params, batch)

        if grad_compression:
            from repro.runtime.compression import compress_tree
            grads = compress_tree(grads)

        lr = cosine_with_warmup(opt_state.step)
        new_params, new_state = adamw_update(params, grads, opt_state, lr=lr)
        return new_params, new_state, loss

    def shardings(params, opt_state, batch):
        # FSDP only when a TP-sharded replica would strain HBM: for small
        # models the per-(microbatch x layer) FSDP all-gathers cost far
        # more than the single DP grad all-reduce they displace (mamba2:
        # 1.38 TB/step of gathers for 2.6 GB of params). ZeRO-1 moment
        # sharding is kept either way (touched once per step).
        pspec = shr.param_specs(params, mesh, fsdp=_needs_fsdp(params, mesh))
        mv_spec = _moment_specs(params, pspec, opt_state.m, mesh)
        ospec = AdamWState(step=P(), m=mv_spec, v=mv_spec)
        bspec = shr.batch_specs(batch, mesh)
        return pspec, ospec, bspec

    return train_step, shardings


def _needs_fsdp(params, mesh, budget_bytes: float = 4e9) -> bool:
    pbytes = sum(leaf.size * getattr(leaf.dtype, "itemsize", 2)
                 for leaf in jax.tree_util.tree_leaves(params))
    return (pbytes / mesh.shape.get("model", 1)) > budget_bytes


def _moment_specs(params, pspecs, moments, mesh):
    """ZeRO-1 moment sharding. fp32 moments mirror the param spec extended
    over the DP axes; int8 block-quantized moments ({q, scale}) shard their
    block dim over DP."""
    dpa = shr.dp_axes(mesh)
    dpa = dpa if len(dpa) > 1 else (dpa[0] if dpa else None)
    dpn = shr.axis_size(mesh, dpa)

    leaves_p, treedef = jax.tree_util.tree_flatten(params)
    leaves_s = treedef.flatten_up_to(pspecs)
    leaves_m = treedef.flatten_up_to(moments)
    out = []
    for p, spec, m in zip(leaves_p, leaves_s, leaves_m):
        if isinstance(m, dict):            # int8 {q, scale}
            blk_spec = P(dpa) if m["q"].shape[0] % dpn == 0 else P()
            out.append({"q": blk_spec, "scale": blk_spec})
        else:
            out.append(shr.zero1_spec(spec, p.shape, mesh))
    return treedef.unflatten(out)


SERVE_CALL_KINDS = ("decode", "prefill_chunk")

#: Call-kind tag suffix for RECOVERY traffic: the serving engine reuses
#: the compiled prefill executables for recovery-by-replay re-prefills
#: (a faulted slot's durable record re-enters through the same
#: fixed-shape chunk steps — no extra compilation for the rare path),
#: but meters those calls separately by suffixing the step's
#: call_kind tag, e.g. "prefill_parallel+replay". Benchmarks multiply
#: metrics.calls_by_kind["<kind>+replay"] by the per-call weight bytes
#: of the base kind to price recovery overhead.
REPLAY_TAG = "+replay"

#: Same idea for WARM-RESTART traffic: after ServeEngine.restore, every
#: active slot re-prefills its durable record (prompt + journaled
#: tokens) through the same executable, and those calls are metered
#: "<kind>+restore". Restart replay is the cost snapshot cadence trades
#: against (work redone <= ticks since the last snapshot), so it must
#: be attributable separately from in-engine fault replays.
RESTORE_TAG = "+restore"


def build_step(cfg: ModelConfig, mesh: Mesh, call_kind: str, *,
               paged: bool = False):
    """One entry point for every fixed-shape serving step. Returns
    (step_fn, shardings_fn); step_fn carries a ``call_kind`` tag that
    runtime.jaxpr_cost.analyze_call_kinds and the serving engine consume
    for per-kind cost attribution.

    Every step takes the stacked joint-sparse tables
    (sparsity.sparse_linear.SegmentedKernelTables, from
    build_stacked_tables(params, cfg)) as its SECOND argument, right
    after params — ``None`` serves the plain dense matmuls. The tables are
    a pytree whose layout is static, so a step compiles once per layout
    and the packed payload stays an argument buffer instead of a constant
    baked into the executable. With tables, every projection of every
    layer runs the DB-PIM Pallas kernel (weight traffic (1 - vs) * 0.5 of
    dense bf16 for joint; (1 - vs) for the bf16-payload value tables).

    call_kind selects the step:

      * "decode" — the serving engine's slot decode step,
        ``(params, tables, cache, token, active)``: inactive slots (free,
        draining, or mid-prefill while their neighbors decode) compute
        alongside the batch but their K/V writes are dropped in-step
        (decode_step's write_mask) and their position and SSM state
        advances discarded (models.decode.merge_slots) — continuous
        batching with ZERO per-request recompilation. Positions come from
        cache["pos"], a (B,) vector of per-slot depths. Tag "decode".
      * "prefill_chunk" — chunked cache-filling prefill,
        ``(params, tables, cache, tokens, n_valid, slots)``: C prompt
        tokens per row in ONE fixed-shape device call
        (models.decode.decode_chunk), so time-to-first-token is
        ceil(P/C) steps instead of P. tokens (R, C), n_valid (R,) and
        slots (R,) int32: row r carries n_valid[r] real tokens of slot
        slots[r] (0 = a padding row; its slot index may be out of range
        and nothing is written). R is the step's row bucket, fixed by
        the shapes it is compiled for: R == n_slots is the whole batch
        in slot order (slots = arange), a smaller R gathers and writes
        back only the named slots' cache. Tag "prefill_parallel"
        when SSM segments run the parallel SSD chunk form (one read of
        the stacked in/out projections per chunk;
        models.ssm.prefill_ssm_parallel), "prefill_chunk_exact" when
        every segment's chunk math is bit-identical to sequential decode
        (attention chunks always are; SSM with cfg.prefill_exact).
        Recovery-by-replay re-prefills run THIS executable too; the
        engine meters them under "<call_kind>+replay" (REPLAY_TAG).

    paged=True switches "decode"/"prefill_chunk" to the PAGED cache
    (pooled {"pk","pv"} leaves from models.init_cache(n_pages=...)): the
    steps take one extra trailing operand ``ptab`` (n_slots, max_pages)
    int32 — the host allocator's page table — through which every KV
    gather/scatter resolves in-graph. The table is a fixed-shape
    per-call operand (never cache-resident), so page churn between ticks
    costs ZERO recompiles. As in the contiguous "decode" step,
    ``active`` is the attention write mask: inactive slots' writes are
    dropped at the scatter.
    """
    if call_kind not in SERVE_CALL_KINDS:
        raise ValueError(f"call_kind {call_kind!r} not in "
                         f"{SERVE_CALL_KINDS}")
    if call_kind == "decode" and paged:
        def step_fn(params, tables, cache, token, active, ptab):
            logits, new_cache = decode_step(params, cache, token, cfg,
                                            tables=tables,
                                            ptab=ptab, write_mask=active)
            return logits, merge_slots(new_cache, cache, active, cfg)
        step_fn.call_kind = "decode"

        def shardings(params, tables, cache, token, active, ptab):
            pspec = _serving_param_specs(params, mesh)
            cspec = shr.cache_specs(cache, cfg, mesh)
            bspec = shr.batch_specs({"token": token, "active": active},
                                    mesh)
            # page table: tiny int32, replicated — sharding it would
            # only add a gather before every pool lookup
            return (pspec, _table_specs(tables), cspec, bspec["token"],
                    bspec["active"], P())

    elif call_kind == "decode":
        def step_fn(params, tables, cache, token, active):
            logits, new_cache = decode_step(params, cache, token, cfg,
                                            tables=tables,
                                            write_mask=active)
            return logits, merge_slots(new_cache, cache, active, cfg)
        step_fn.call_kind = "decode"

        def shardings(params, tables, cache, token, active):
            pspec = _serving_param_specs(params, mesh)
            cspec = shr.cache_specs(cache, cfg, mesh)
            bspec = shr.batch_specs({"token": token, "active": active},
                                    mesh)
            return (pspec, _table_specs(tables), cspec, bspec["token"],
                    bspec["active"])

    elif paged:                            # "prefill_chunk", paged
        def step_fn(params, tables, cache, tokens, n_valid, slots, ptab):
            return decode_chunk(params, cache, tokens, n_valid, cfg,
                                tables=tables, ptab=ptab, slots=slots)
        step_fn.call_kind = _chunk_kind(cfg)

        def shardings(params, tables, cache, tokens, n_valid, slots, ptab):
            return _chunk_specs(cfg, mesh, params, tables, cache, tokens,
                                n_valid, slots) + (P(),)

    else:                                  # "prefill_chunk"
        def step_fn(params, tables, cache, tokens, n_valid, slots):
            return decode_chunk(params, cache, tokens, n_valid, cfg,
                                tables=tables, slots=slots)
        step_fn.call_kind = _chunk_kind(cfg)

        def shardings(params, tables, cache, tokens, n_valid, slots):
            return _chunk_specs(cfg, mesh, params, tables, cache, tokens,
                                n_valid, slots)

    # which model family compiled this step — paired with call_kind it
    # forms the recompile sentinel's registry key and the tracer's
    # call-span arch attribute
    step_fn.arch = cfg.name
    return step_fn, shardings


def _chunk_specs(cfg, mesh, params, tables, cache, tokens, n_valid, slots):
    bspec = shr.batch_specs({"tokens": tokens, "n_valid": n_valid,
                             "slots": slots}, mesh)
    return (_serving_param_specs(params, mesh), _table_specs(tables),
            shr.cache_specs(cache, cfg, mesh), bspec["tokens"],
            bspec["n_valid"], bspec["slots"])


def _chunk_kind(cfg: ModelConfig) -> str:
    caps = cfg.serving_capabilities()
    return ("prefill_parallel"
            if caps.parallel_prefill and not cfg.prefill_exact
            else "prefill_chunk_exact")


def _table_specs(tables):
    # packed tables stay whole on every device: a tensor-parallel split
    # would have to shard each tile's compacted blocks and index table
    # together, which no mesh here needs yet
    return jax.tree_util.tree_map(lambda _: P(), tables)


def _serving_param_specs(params, mesh: Mesh):
    # Serving keeps weights RESIDENT (TP-sharded, replicated over DP):
    # FSDP would re-all-gather the full model every decoded token.
    # Only models whose TP shard exceeds the HBM budget (arctic-class)
    # keep FSDP and pay the gathers.
    pbytes = sum(
        leaf.size * getattr(leaf.dtype, "itemsize", 2)
        for leaf in jax.tree_util.tree_leaves(params))
    tp = mesh.shape.get("model", 1)
    fsdp = (pbytes / tp) > 12e9
    return shr.param_specs(params, mesh, fsdp=fsdp)
