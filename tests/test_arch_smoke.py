"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, output shapes + finiteness; one decode step against a small cache."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.models import (decode_step, forward, init_cache, init_params,
                          loss_fn, param_count)
from repro.models.inputs import make_decode_token, make_train_batch
from repro.models.transformer import encode

ARCHS = list_archs()
B, S = 2, 32


@pytest.fixture(scope="module")
def arch_state():
    cache = {}
    def get(arch):
        if arch not in cache:
            cfg = get_config(arch, reduced=True)
            params = init_params(cfg, jax.random.PRNGKey(0))
            cache[arch] = (cfg, params)
        return cache[arch]
    return get


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_loss(arch, arch_state):
    cfg, params = arch_state(arch)
    batch = make_train_batch(cfg, B, S)
    loss = loss_fn(params, batch, cfg)
    assert loss.shape == ()
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_grads(arch, arch_state):
    cfg, params = arch_state(arch)
    batch = make_train_batch(cfg, B, S)
    loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg)
    assert np.isfinite(float(loss))
    flat, _ = jax.tree_util.tree_flatten(grads)
    assert all(np.all(np.isfinite(np.asarray(g, dtype=np.float32)))
               for g in flat)
    # at least one non-zero gradient tensor
    assert any(float(jnp.max(jnp.abs(g.astype(jnp.float32)))) > 0
               for g in flat)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step(arch, arch_state):
    cfg, params = arch_state(arch)
    enc_out = None
    if cfg.is_encdec:
        frames = make_train_batch(cfg, B, S)["frames"]
        enc_out = encode(params, frames, cfg)
    cache = init_cache(cfg, B, max_len=64, enc_out=enc_out)
    token = make_decode_token(cfg, B)
    logits, cache = decode_step(params, cache, token, cfg)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, dtype=np.float32)))
    logits2, cache = decode_step(params, cache, token, cfg)
    assert int(cache["pos"]) == 2
    assert np.all(np.isfinite(np.asarray(logits2, dtype=np.float32)))


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_analytic_close(arch, arch_state):
    cfg, params = arch_state(arch)
    actual = sum(int(np.prod(x.shape))
                 for x in jax.tree_util.tree_leaves(params))
    approx = param_count(cfg)
    assert abs(actual - approx) / actual < 0.15, (actual, approx)


def test_full_config_param_counts():
    """Full (non-reduced) configs land near their advertised sizes."""
    targets = {"qwen3-8b": (8e9, 0.3), "tinyllama-1.1b": (1.1e9, 0.25),
               "gemma-7b": (8.5e9, 0.3), "mixtral-8x7b": (46e9, 0.15),
               "arctic-480b": (480e9, 0.15), "mamba2-1.3b": (1.3e9, 0.3),
               "jamba-v0.1-52b": (52e9, 0.25), "pixtral-12b": (12e9, 0.25)}
    for arch, (target, tol) in targets.items():
        n = param_count(get_config(arch))
        assert abs(n - target) / target < tol, (arch, n, target)
