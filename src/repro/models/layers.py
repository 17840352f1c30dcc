"""Shared layer primitives: norms, RoPE, MLPs, embeddings, initializers.

Everything is a pure function over explicit param pytrees (no framework).
Params are created by `init_*` functions that only use jax.random — they
can run under `jax.eval_shape` for allocation-free shape inference.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .config import ModelConfig
from repro.runtime.act_sharding import constrain


def dtype_of(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ------------------------------------------------------------- norms -------

def init_norm(cfg: ModelConfig, d: int):
    if cfg.norm_type == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    init = jnp.zeros if cfg.norm_plus_one else jnp.ones
    return {"scale": init((d,), jnp.float32)}


def apply_norm(p, x, cfg: ModelConfig, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        scale = (1.0 + p["scale"]) if cfg.norm_plus_one else p["scale"]
        out = xf * jax.lax.rsqrt(var + eps) * scale
    return out.astype(x.dtype)


def rms_head_norm(scale, x, eps: float = 1e-6):
    """Per-head qk-norm (qwen3): x (..., head_dim)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


# -------------------------------------------------------------- RoPE -------

def rope_frequencies(cfg: ModelConfig, positions):
    """positions (...,) int32 -> (cos, sin) of shape (..., rot_dim//2)."""
    rot = int(cfg.hd * cfg.rope_pct)
    rot -= rot % 2
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rot, 2, jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin, cfg: ModelConfig):
    """x (..., H, hd); cos/sin broadcastable (..., rot//2)."""
    rot = 2 * cos.shape[-1]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(xr.astype(jnp.float32), 2, axis=-1)
    c = cos[..., None, :] if x.ndim == cos.ndim + 1 else cos
    s = sin[..., None, :] if x.ndim == sin.ndim + 1 else sin
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


# --------------------------------------------------------------- MLP -------

def init_mlp(cfg: ModelConfig, key, d: int, f: int):
    dt = dtype_of(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_out = d ** -0.5, f ** -0.5
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {"w_gate": (jax.random.normal(k1, (d, f)) * s_in).astype(dt),
                "w_up": (jax.random.normal(k2, (d, f)) * s_in).astype(dt),
                "w_down": (jax.random.normal(k3, (f, d)) * s_out).astype(dt)}
    return {"w_up": (jax.random.normal(k1, (d, f)) * s_in).astype(dt),
            "w_down": (jax.random.normal(k2, (f, d)) * s_out).astype(dt)}


def apply_mlp(p, x, cfg: ModelConfig, dense_fn=None):
    """dense_fn(w, x, name) lets the DB-PIM sparse path intercept matmuls."""
    mm = dense_fn or (lambda w, v, name: v @ w)
    cst = lambda t: constrain(t, *(["dp"] + [None] * (t.ndim - 2) + ["tp"]))
    if cfg.mlp_type == "swiglu":
        g = jax.nn.silu(cst(mm(p["w_gate"], x, "w_gate")))
        return mm(p["w_down"], g * cst(mm(p["w_up"], x, "w_up")), "w_down")
    if cfg.mlp_type == "geglu":
        g = jax.nn.gelu(cst(mm(p["w_gate"], x, "w_gate")), approximate=True)
        return mm(p["w_down"], g * cst(mm(p["w_up"], x, "w_up")), "w_down")
    h = jax.nn.gelu(cst(mm(p["w_up"], x, "w_up")), approximate=True)
    return mm(p["w_down"], h, "w_down")


# --------------------------------------------------------- embeddings ------

def init_embeddings(cfg: ModelConfig, key):
    dt = dtype_of(cfg)
    k1, k2 = jax.random.split(key)
    p = {"tok": (jax.random.normal(k1, (cfg.vocab_size, cfg.d_model))
                 .astype(dt))}
    if not cfg.tie_embeddings:
        p["out"] = (jax.random.normal(k2, (cfg.d_model, cfg.vocab_size))
                    * cfg.d_model ** -0.5).astype(dt)
    return p


def embed_tokens(p, tokens, cfg: ModelConfig):
    x = jnp.take(p["tok"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def logits_from_hidden(p, x, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return x @ p["tok"].T
    return x @ p["out"]


def cross_entropy(logits, labels):
    """Mean token cross-entropy; labels < 0 are masked.

    The gold logit is extracted with a one-hot CONTRACTION, not
    take_along_axis: a gather across the vocab dim would force GSPMD to
    all-gather vocab-sharded logits (multi-GB per device at 150k vocab),
    while the contraction reduces over the sharded dim with a cheap
    all-reduce."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(jnp.maximum(labels, 0), logits.shape[-1],
                            dtype=jnp.float32)
    gold = jnp.einsum("...v,...v->...", logits, onehot)
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
