"""Mixture-of-Experts with sort-based capacity dispatch.

Design goals (in order):
  1. Static shapes (pjit friendly).
  2. FLOPs proportional to top_k/n_experts (roofline-faithful) — the
     dispatch is scatter/gather, NOT a (T, E, C) einsum, so the compiled
     compute term reflects the real expert math.
  3. Expert-parallel shardable: the (E, C, D) buffers carry the expert dim
     explicitly; the sharding rules put E (or the FFN dim) on the model
     axis and XLA inserts the all-to-all-style collectives.

Tokens beyond an expert's capacity C = ceil(T * top_k / E * cap_factor)
are dropped (standard Switch behaviour); the combine step re-normalizes
gates over surviving assignments.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import apply_mlp, dtype_of, init_mlp
from repro.runtime.act_sharding import constrain_any


def init_moe(cfg: ModelConfig, key):
    dt = dtype_of(cfg)
    kr, ke = jax.random.split(key)
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    s = d ** -0.5
    p = {"router": (jax.random.normal(kr, (d, E)) * s).astype(jnp.float32)}
    keys = jax.random.split(ke, 3)
    if cfg.mlp_type in ("swiglu", "geglu"):
        p["w_gate"] = (jax.random.normal(keys[0], (E, d, f)) * s).astype(dt)
        p["w_up"] = (jax.random.normal(keys[1], (E, d, f)) * s).astype(dt)
    else:
        p["w_up"] = (jax.random.normal(keys[1], (E, d, f)) * s).astype(dt)
    p["w_down"] = (jax.random.normal(keys[2], (E, f, d))
                   * f ** -0.5).astype(dt)
    return p


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    """Per-expert token slots. Rounded up to a multiple of 8 (sublane
    alignment) but never beyond the total assignment count: an expert can
    receive at most every (token, choice) pair, so a tiny decode batch
    (n_tokens * top_k < 8) allocates exactly that many slots instead of 8
    phantom ones per expert."""
    assignments = n_tokens * cfg.top_k
    c = int(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return min(max(8, -(-c // 8) * 8), assignments)


def _expert_matmul(w, x, name, expert_fn=None):
    """Per-expert contraction x (..., E, C, K) @ w (E, K, F) ->
    (..., E, C, F). ``expert_fn`` (sparse_linear.StackedKernelTables
    dense_fn().expert) reroutes it through one joint-kernel call per
    packed expert slice — the DB-PIM serving path for grouped expert
    stacks."""
    if expert_fn is not None:
        return expert_fn(w, x, name)
    return jnp.einsum("...eck,ekf->...ecf", x, w)


def _group_dispatch(xt, gate_idx, gate_vals, E: int, C: int):
    """Per-group dispatch (Tg tokens). Returns (xin (E,C,D), slot, w).

    vmapped over groups: the scatter then carries an explicit batch dim
    aligned with the token sharding, so GSPMD partitions it instead of
    replicating (the flat global scatter forced involuntary full
    rematerialization — see EXPERIMENTS.md §Perf iteration 2)."""
    Tg, D = xt.shape
    K = gate_idx.shape[-1]
    flat_e = gate_idx.reshape(-1)                          # (Tg*K,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot)       # exclusive rank
    rank = jnp.take_along_axis(pos_in_e, flat_e[:, None], 1)[:, 0]
    keep = rank < C
    # dropped assignments get an out-of-bounds slot: scatter mode="drop"
    # discards them, gather mode="fill" returns zeros.
    slot = jnp.where(keep, flat_e * C + rank, E * C)
    buf = jnp.zeros((E * C, D), dtype=xt.dtype)
    updates = jnp.broadcast_to(xt[:, None, :], (Tg, K, D)).reshape(Tg * K, D)
    buf = buf.at[slot].set(updates, mode="drop", unique_indices=True)
    w = gate_vals * keep.reshape(Tg, K)
    return buf.reshape(E, C, D), slot, w


def _group_combine(out_ec, slot, w, Tg: int):
    """Inverse gather for one group. out_ec (E, C, D) -> (Tg, D)."""
    E, C, D = out_ec.shape
    flat = out_ec.reshape(E * C, D)
    gathered = jnp.take(flat, slot, axis=0, mode="fill",
                        fill_value=0).reshape(Tg, -1, D)
    return jnp.einsum("tkd,tk->td", gathered, w.astype(out_ec.dtype))


def apply_moe(p, x, cfg: ModelConfig, expert_fn=None,
              per_position: bool = False):
    """x (B, S, D) -> (B, S, D), plus aux losses dict.

    Grouped dispatch: tokens are split into G = B groups (sequences) with
    per-group capacity; dispatch/combine are vmapped so every scatter/
    gather is local to a data shard. Expert compute runs as one batched
    einsum over (G, E, C, D) with the FFN dim tensor-parallel — or, when
    ``expert_fn`` is hooked (stacked joint-sparse serving), as one
    DB-PIM kernel call per packed expert slice.

    per_position=True (chunked prefill) groups by SEQUENCE POSITION
    instead: G = S groups of the B slot tokens at that position, with
    capacity(cfg, B) — exactly the pool one serving decode step routes
    against, so a C-token chunk reproduces C decode steps' expert
    assignments whenever capacity covers all assignments (it always does
    at decode-batch scale, where capacity() clamps to B * top_k).
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    if per_position:
        G, Tg = S, B
        xg = jnp.swapaxes(x, 0, 1)                         # (S, B, D)
    elif S >= 64:
        G, Tg = B, S
        xg = x
    else:
        # Decode (S small): one flat group — per-sequence groups of 1
        # token would pad every expert's capacity to the minimum and
        # waste E*C_min slots per token (512x for arctic).
        G, Tg = 1, B * S
        xg = x.reshape(G, Tg, D)
    C = capacity(cfg, Tg)

    logits = (xg.astype(jnp.float32) @ p["router"])        # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)          # (G, Tg, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    xin, slot, w = jax.vmap(
        lambda a, b, c: _group_dispatch(a, b, c, E, C))(xg, gate_idx,
                                                        gate_vals)
    # E-sharded (expert parallel) when E divides the model axis (arctic,
    # jamba), else tokens-only (mixtral keeps E whole, F tensor-parallel).
    xin = constrain_any(xin, ("dp", "tp", None, None),
                        ("dp", None, None, None))          # (G, E, C, D)

    out = _expert_ffn_grouped(p, xin, cfg, expert_fn)      # (G, E, C, D)
    out = constrain_any(out, ("dp", "tp", None, None),
                        ("dp", None, None, None))

    yg = jax.vmap(lambda a, b, c: _group_combine(a, b, c, Tg))(out, slot, w)

    # load-balancing auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(jax.nn.one_hot(gate_idx[..., 0], E, dtype=jnp.float32),
                  axis=(0, 1))
    keep_frac = jnp.mean((slot < E * C).astype(jnp.float32))
    aux = {"load_balance": E * jnp.sum(me * ce),
           "dropped_frac": 1.0 - keep_frac}
    y = jnp.swapaxes(yg, 0, 1) if per_position else yg.reshape(B, S, D)
    return y, aux


def _expert_ffn_grouped(p, xin, cfg: ModelConfig, expert_fn=None):
    """xin (G, E, C, D) -> (G, E, C, D); experts sharded over `model`
    when E divides it, otherwise the FFN dim is tensor-parallel."""
    mm = lambda w, x, name: _expert_matmul(w, x, name, expert_fn)
    cst = lambda t: constrain_any(t, ("dp", "tp", None, None),
                                  ("dp", None, None, "tp"))
    if cfg.mlp_type in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else \
            (lambda v: jax.nn.gelu(v, approximate=True))
        g = act(cst(mm(p["w_gate"], xin, "moe/w_gate")))
        h = g * cst(mm(p["w_up"], xin, "moe/w_up"))
    else:
        h = jax.nn.gelu(cst(mm(p["w_up"], xin, "moe/w_up")),
                        approximate=True)
    return mm(p["w_down"], h, "moe/w_down")


def apply_moe_block(p, x, cfg: ModelConfig, dense_fn=None,
                    per_position: bool = False):
    """MoE (+ optional arctic dense residual MLP in parallel).

    ``dense_fn`` is the per-layer DB-PIM hook
    (StackedKernelTables.dense_fn(slices) on the serving path): its
    ``expert`` attribute serves the grouped expert projections through
    the joint kernel, and the hook itself serves the arctic dense
    residual MLP. Plain None keeps every matmul dense. per_position
    groups capacity dispatch by sequence position (chunked prefill —
    see apply_moe)."""
    y, aux = apply_moe(p, x, cfg,
                       expert_fn=getattr(dense_fn, "expert", None),
                       per_position=per_position)
    if cfg.dense_residual:
        y = y + apply_mlp(p["dense_mlp"], x, cfg, dense_fn)
    return y, aux


def init_moe_block(cfg: ModelConfig, key):
    p = init_moe(cfg, key)
    if cfg.dense_residual:
        p["dense_mlp"] = init_mlp(cfg, jax.random.fold_in(key, 7),
                                  cfg.d_model, cfg.d_ff)
    return p
