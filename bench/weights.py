"""Seeded weights for a configuration, made on the device in one jitted call.

The weights are the benchmark's, not the program's: the program is
handed them to pack and serve, and the reference regenerates the same
arrays from the same seed after the program's state is freed.

Every projection that the joint kernel serves is made already in the
form the configuration states, so that the program's compression is
exact and the reference can use the weights as they are:

  * value level: per layer and per column of (bk, bn) tiles, exactly
    ``round(vs * kt)`` K-tiles are zero (column-balanced tile pruning at
    the configuration's ``value_sparsity``; at least one tile is kept);
  * bit level: every kept weight is ``q * 2**-e`` with ``q`` an INT8
    value of exactly two non-zero canonical-signed-digit terms (the FTA
    threshold a column of such values selects), each column holds one
    ``|q| = 127`` so that its symmetric INT8 scale is exactly ``2**-e``,
    and ``e`` varies by column so that per-column scales matter.

All other leaves (embeddings, norms, SSM parameters) are dense random
values in the dtype the program serves them in. The tree has the
program's parameter layout; ``harness`` checks it against the program's
own ``init_params`` shapes before use.
"""

from __future__ import annotations

import math

import numpy as np


def naf_weight(v: int) -> int:
    """Non-zero digits of ``v`` in canonical signed-digit (non-adjacent)
    form."""
    v, n = int(v), 0
    while v:
        if v & 1:
            v -= 2 - (v % 4)
            n += 1
        v //= 2
    return n


#: INT8 values with exactly two non-zero CSD digits, |v| <= 127
TWO_TERM = np.array([v for v in range(-127, 128) if naf_weight(v) == 2],
                    np.int32)


def tile_dims(k: int, n: int, tile) -> tuple:
    """Tile shape for a (k, n) projection: the configured tile, clamped
    to the dimension rounded up to a multiple of 8 (small test shapes)."""
    r8 = lambda d: max(8, 8 * -(-d // 8))
    return min(tile[0], r8(k)), min(tile[1], r8(n))


def kept_k_tiles(k: int, n: int, vs: float, tile) -> tuple:
    """(bk, bn, kt, keep): K-tiles per column and how many survive."""
    bk, bn = tile_dims(k, n, tile)
    kt = -(-k // bk)
    drop = min(int(round(vs * kt)), kt - 1) if vs else 0
    return bk, bn, kt, kt - drop


def projections(m: dict) -> dict:
    """Param path -> (K, N) of every projection the joint kernel serves,
    per layer (the stacked leaves carry a leading layer axis)."""
    d = m["hidden_size"]
    if m["family"] == "dense":
        hd = d // m["num_attention_heads"]
        q = m["num_attention_heads"] * hd
        kv = m["num_key_value_heads"] * hd
        f = m["intermediate_size"]
        return {"blocks/attn/wq": (d, q), "blocks/attn/wk": (d, kv),
                "blocks/attn/wv": (d, kv), "blocks/attn/wo": (q, d),
                "blocks/mlp/w_gate": (d, f), "blocks/mlp/w_up": (d, f),
                "blocks/mlp/w_down": (f, d)}
    if m["family"] == "ssm":
        di, nh, n = ssm_dims(m)
        return {"blocks/ssm/in_proj": (d, 2 * di + 2 * n + nh),
                "blocks/ssm/out_proj": (di, d)}
    raise ValueError(f"unknown family {m['family']!r}")


def ssm_dims(m: dict) -> tuple:
    """(d_inner, heads, state) of a Mamba2 layer (one group)."""
    di = m["expand"] * m["hidden_size"]
    return di, di // m["head_dim"], m["state_size"]


def layout(m: dict) -> dict:
    """Param path -> (shape, dtype name) of the whole tree."""
    d, L, V = m["hidden_size"], m["num_hidden_layers"], m["vocab_size"]
    out = {"embed/tok": ((V, d), "bfloat16")}
    if not m["tie_word_embeddings"]:
        out["embed/out"] = ((d, V), "bfloat16")
    for path, (k, n) in projections(m).items():
        out[path] = ((L, k, n), "bfloat16")
    if m["family"] == "dense":
        for norm in ("final_norm", "blocks/norm1", "blocks/norm2"):
            lead = () if norm == "final_norm" else (L,)
            out[f"{norm}/scale"] = (lead + (d,), "float32")
            out[f"{norm}/bias"] = (lead + (d,), "float32")
    else:
        di, nh, n = ssm_dims(m)
        ch = di + 2 * n
        out["final_norm/scale"] = ((d,), "float32")
        out["blocks/norm1/scale"] = ((L, d), "float32")
        out["blocks/ssm/conv_w"] = ((L, m["conv_kernel"], ch), "bfloat16")
        out["blocks/ssm/conv_b"] = ((L, ch), "bfloat16")
        for leaf in ("A_log", "D", "dt_bias"):
            out[f"blocks/ssm/{leaf}"] = ((L, nh), "float32")
        out["blocks/ssm/norm_scale"] = ((L, di), "float32")
    return out


def _grid_projection(key, k: int, n: int, vs: float, tile):
    """One layer's (k, n) projection in the stated compressed form."""
    import jax
    import jax.numpy as jnp

    bk, bn, kt, keep = kept_k_tiles(k, n, vs, tile)
    nt = -(-n // bn)
    k1, k2, k3 = jax.random.split(key, 3)
    # which K-tiles survive in each tile column: the `keep` lowest ranks
    u = jax.random.uniform(k1, (kt, nt))
    rank = jnp.argsort(jnp.argsort(u, axis=0), axis=0)
    alive = rank < keep                                        # (kt, nt)
    rows = jnp.arange(k)
    cols = jnp.arange(n)
    alive_full = alive[rows // bk][:, cols // bn]              # (k, n)
    q = jnp.asarray(TWO_TERM)[jax.random.randint(
        k2, (k, n), 0, TWO_TERM.size)]
    # one |q| = 127 per column, in its first surviving tile
    first = jnp.argmax(alive, axis=0)                          # (nt,)
    t0 = first[cols // bn]
    in_tile = jnp.minimum(bk, k - t0 * bk)
    r0 = t0 * bk + cols % in_tile
    q = jnp.where(rows[:, None] == r0[None, :],
                  jnp.where(q[r0, cols] < 0, -127, 127)[None, :], q)
    # per-column power-of-two scale around the init's output variance
    std = (k ** -0.5) / math.sqrt(keep / kt)
    e = int(round(-math.log2(std / float(np.sqrt(
        (TWO_TERM.astype(np.float64) ** 2).mean())))))
    e_col = e + jax.random.randint(k3, (n,), 0, 2)
    scale = jnp.exp2(-e_col.astype(jnp.float32))
    w = jnp.where(alive_full, q.astype(jnp.float32), 0.0) * scale
    return w.astype(jnp.bfloat16)


def _leaf(key, path: str, shape, dtype, m: dict, vs: float, tile):
    import jax
    import jax.numpy as jnp

    name = path.rsplit("/", 1)[-1]
    if path in projections(m):
        L, k, n = shape
        return jax.lax.map(
            lambda kk: _grid_projection(kk, k, n, vs, tile),
            jax.random.split(key, L))
    normal = jax.random.normal(key, shape, jnp.float32)
    if path == "embed/tok":
        # a tied head reads the input embedding back out: at unit scale
        # every position would predict its own input token by a margin
        # no rounding can close, so use the usual 0.02 there
        val = normal * (0.02 if m["tie_word_embeddings"] else 1.0)
    elif path == "embed/out":
        val = normal * shape[0] ** -0.5
    elif name == "scale" or name == "norm_scale":
        val = 1.0 + 0.1 * normal
    elif name == "bias" or name == "conv_b":
        val = 0.1 * normal
    elif name == "conv_w":
        val = 0.2 * normal
    elif name == "A_log":        # A = -exp(A_log) in [-16, -1]
        val = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif name == "D":
        val = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
    elif name == "dt_bias":      # softplus(dt_bias) in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        val = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"no rule for weight {path!r}")
    return val.astype(dtype)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return tree


def make_fn(m: dict, vs: float, tile):
    """A jitted ``f(seed) -> params`` for model sizes ``m``: one device
    call makes every leaf. The seed is a traced uint32 pair, so one
    compile serves every seed."""
    import jax
    import jax.numpy as jnp

    lay = layout(m)

    def make(seed_words):
        key = jax.random.wrap_key_data(seed_words, impl="threefry2x32")
        keys = jax.random.split(key, len(lay))
        return _nest({path: _leaf(k, path, shape, jnp.dtype(dt), m, vs,
                                  tile)
                      for k, (path, (shape, dt)) in zip(keys, lay.items())})

    jitted = jax.jit(make)
    return lambda seed: jitted(seed_words(seed))


def seed_words(seed: int):
    """A non-negative seed of up to 64 bits as a threefry key's data."""
    import jax.numpy as jnp
    s = int(seed)
    if s < 0 or s >= 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jnp.asarray([s >> 32, s & 0xFFFFFFFF], jnp.uint32)
