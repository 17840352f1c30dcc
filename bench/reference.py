"""Plain float32 reference of the configurations, and its low-precision
control.

Written from the published architectures in straightforward
``jax.numpy``, with no kernel, cache, batching or packed table, and
importing nothing of the program. It reads the weights that
``bench.weights`` makes from the seed (the same arrays the program was
handed) and the sizes of the configuration file. Every matmul runs at
``precision=HIGHEST``: on a TPU a float32 matmul otherwise runs in
bfloat16 passes.

``lowp=True`` is the control: the same model with every matmul operand
rounded to float8 e4m3 under a per-tensor scale (the step below the
bfloat16 the configurations state). A comparison that cannot tell it
from the reference would not catch a program that computed in it.

Departures from the published models, which the program makes too and
the configuration files record: stablelm-2 has no q/k/v bias here and
its norms use eps 1e-6; mamba2 norms use eps 1e-6.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _fp8(x):
    """Round to float8 e4m3 under a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(eq, a, b, lowp):
    a, b = a.astype(F32), b.astype(F32)
    if lowp:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


def _layernorm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def dense_logits(p, tokens, m, lowp=False):
    """Decoder-only transformer (stablelm-2): pre-LayerNorm blocks, MHA
    with partial rotary embeddings (rotate-half on the first
    ``partial_rotary_factor`` of each head), SiLU-gated MLP, untied
    output head. tokens (B, S) -> logits (B, S, V) float32."""
    B, S = tokens.shape
    d, H = m["hidden_size"], m["num_attention_heads"]
    Hkv = m["num_key_value_heads"]
    hd = d // H
    eps = m["layer_norm_eps"]
    rot = int(hd * m["partial_rotary_factor"])
    rot -= rot % 2
    inv = m["rope_theta"] ** (-jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def rope(t):
        r, rest = t[..., :rot], t[..., rot:]
        a, b = r[..., :rot // 2], r[..., rot // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                               axis=-1)

    def layer(x, lp):
        h = _layernorm(x, lp["norm1"]["scale"], lp["norm1"]["bias"], eps)
        a = lp["attn"]
        q = _mm("bsd,de->bse", h, a["wq"], lowp).reshape(B, S, H, hd)
        k = _mm("bsd,de->bse", h, a["wk"], lowp).reshape(B, S, Hkv, hd)
        v = _mm("bsd,de->bse", h, a["wv"], lowp).reshape(B, S, Hkv, hd)
        q, k = rope(q), rope(k)
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
        s = _mm("bqhd,bkhd->bhqk", q, k, lowp) / jnp.sqrt(F32(hd))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, lowp)
        x = x + _mm("bse,ed->bsd", o.reshape(B, S, H * hd), a["wo"], lowp)
        h = _layernorm(x, lp["norm2"]["scale"], lp["norm2"]["bias"], eps)
        f = lp["mlp"]
        g = jax.nn.silu(_mm("bsd,df->bsf", h, f["w_gate"], lowp))
        u = _mm("bsd,df->bsf", h, f["w_up"], lowp)
        return x + _mm("bsf,fd->bsd", g * u, f["w_down"], lowp), None

    x = p["embed"]["tok"].astype(F32)[tokens]
    x, _ = jax.lax.scan(layer, x, p["blocks"])
    x = _layernorm(x, p["final_norm"]["scale"], p["final_norm"]["bias"], eps)
    return _mm("bsd,dv->bsv", x, p["embed"]["out"], lowp)


def ssm_logits(p, tokens, m, lowp=False):
    """Mamba2 (arXiv:2405.21060), one group: RMSNorm, in-projection to
    (z, x, B, C, dt), depthwise causal conv + SiLU over (x, B, C), the
    selective state recurrence run token by token
    s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T, y_t = s_t C_t + D x_t,
    gated RMSNorm y * silu(z), out-projection; tied output head."""
    B, S = tokens.shape
    d = m["hidden_size"]
    di = m["expand"] * d
    P = m["head_dim"]
    nh, N, W = di // P, m["state_size"], m["conv_kernel"]
    eps = m["layer_norm_eps"]

    def layer(x, lp):
        s_ = lp["ssm"]
        h = _rmsnorm(x, lp["norm1"]["scale"], eps)
        proj = _mm("bsd,de->bse", h, s_["in_proj"], lowp)
        z, xbc, dtr = (proj[..., :di], proj[..., di:2 * di + 2 * N],
                       proj[..., 2 * di + 2 * N:])
        pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
        cw = s_["conv_w"].astype(F32)
        conv = sum(pad[:, i:i + S] * cw[i] for i in range(W))
        xbc = jax.nn.silu(conv + s_["conv_b"].astype(F32))
        xs = xbc[..., :di].reshape(B, S, nh, P)
        bm, cm = xbc[..., di:di + N], xbc[..., di + N:]
        dt = jax.nn.softplus(dtr + s_["dt_bias"])              # (B,S,nh)
        A = -jnp.exp(s_["A_log"])

        def step(state, t):
            xt, bt, ct, dtt = t
            state = (state * jnp.exp(dtt * A)[:, :, None, None]
                     + dtt[:, :, None, None] * xt[..., None]
                     * bt[:, None, None, :])
            y = jnp.einsum("bhpn,bn->bhp", state, ct, precision=HIGHEST)
            return state, y

        state0 = jnp.zeros((B, nh, P, N), F32)
        seq = tuple(jnp.moveaxis(t, 1, 0) for t in (xs, bm, cm, dt))
        _, ys = jax.lax.scan(step, state0, seq)
        y = jnp.moveaxis(ys, 0, 1) + xs * s_["D"][None, None, :, None]
        g = y.reshape(B, S, di) * jax.nn.silu(z)
        g = _rmsnorm(g, s_["norm_scale"], eps)
        return x + _mm("bse,ed->bsd", g, s_["out_proj"], lowp), None

    x = p["embed"]["tok"].astype(F32)[tokens]
    x, _ = jax.lax.scan(layer, x, p["blocks"])
    x = _rmsnorm(x, p["final_norm"]["scale"], eps)
    return _mm("bsd,vd->bsv", x, p["embed"]["tok"], lowp)


def logits_fn(m: dict):
    return {"dense": dense_logits, "ssm": ssm_logits}[m["family"]]


@functools.lru_cache(maxsize=None)
def _jitted(key):
    m = dict(key)
    fwd = logits_fn(m)

    def reference(params, tokens, targets, pos):
        """Per position, the reference's best logit minus its logit of
        ``targets``; and the reference's logits at ``pos[r]`` of row r."""
        ref = fwd(params, tokens, m, lowp=False)
        got = jnp.take_along_axis(ref, targets[..., None], axis=-1)[..., 0]
        return ref.max(-1) - got, ref[jnp.arange(ref.shape[0]), pos]

    def control(params, tokens, pos):
        """The control's first choice at every position, and its logits
        at ``pos[r]`` of row r."""
        c = fwd(params, tokens, m, lowp=True)
        return c.argmax(-1), c[jnp.arange(c.shape[0]), pos]

    return jax.jit(reference), jax.jit(control)


def _key(m: dict):
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


def reference_pass(params, tokens, targets, pos, m: dict):
    """((R, S) gaps of ``targets``, (R, V) logits at ``pos``) under the
    float32 reference."""
    return _jitted(_key(m))[0](params, tokens, targets, pos)


def control_pass(params, tokens, pos, m: dict):
    """((R, S) tokens the float8 control puts first, (R, V) its logits at
    ``pos``)."""
    return _jitted(_key(m))[1](params, tokens, pos)
