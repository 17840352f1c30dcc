"""End-to-end behaviour tests: training convergence, checkpoint-resume
equivalence, serving, DB-PIM LM compression, fault-tolerant loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import SyntheticLMDataset
from repro.launch.mesh import make_test_mesh
from repro.launch.steps import build_train_step
from repro.models import decode_step, init_cache, init_params, loss_fn
from repro.optim import adamw_init
from repro.runtime import sharding as shr
from repro.sparsity import dequant_tree, pim_speedup_estimate, \
    sparsify_params


def _train(cfg, steps, seed=0, microbatches=1, grad_compression=False,
           params=None, opt_state=None, start=0):
    mesh = make_test_mesh()
    if params is None:
        params = init_params(cfg, jax.random.PRNGKey(seed))
        opt_state = adamw_init(params)
    ds = SyntheticLMDataset(cfg, 8, 64, seed=seed)
    step_fn, shard_fn = build_train_step(cfg, mesh,
                                         microbatches=microbatches,
                                         grad_compression=grad_compression)
    with mesh:
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))
        losses = []
        for s in range(start, steps):
            batch = {k: jnp.asarray(v) for k, v in ds.batch_at(s).items()}
            params, opt_state, loss = jitted(params, opt_state, batch)
            losses.append(float(loss))
    return params, opt_state, losses


def test_training_reduces_loss():
    cfg = get_config("tinyllama-1.1b", reduced=True)
    _, _, losses = _train(cfg, 60)
    assert losses[-1] < losses[0] - 0.05
    assert all(np.isfinite(l) for l in losses)


def test_microbatched_matches_full_batch():
    cfg = get_config("tinyllama-1.1b", reduced=True)
    _, _, l1 = _train(cfg, 3, microbatches=1)
    _, _, l4 = _train(cfg, 3, microbatches=4)
    # same data, same params: identical loss up to accumulation order
    np.testing.assert_allclose(l1, l4, rtol=2e-2, atol=2e-2)


def test_grad_compression_trains():
    cfg = get_config("tinyllama-1.1b", reduced=True)
    _, _, losses = _train(cfg, 40, grad_compression=True)
    assert losses[-1] < losses[0]


def test_checkpoint_resume_bit_identical(tmp_path):
    from repro.checkpoint import load_checkpoint, save_checkpoint
    cfg = get_config("tinyllama-1.1b", reduced=True)
    p1, o1, _ = _train(cfg, 5)
    save_checkpoint(str(tmp_path), 5, (p1, o1))
    (p2, o2), step, _ = load_checkpoint(str(tmp_path), (p1, o1))
    p2 = jax.tree_util.tree_map(jnp.asarray, p2)
    o2 = jax.tree_util.tree_map(jnp.asarray, o2)
    # continue both for 3 steps: identical trajectories
    pa, _, la = _train(cfg, 8, params=p1, opt_state=o1, start=5)
    pb, _, lb = _train(cfg, 8, params=p2, opt_state=o2, start=5)
    np.testing.assert_allclose(la, lb, rtol=1e-5)


def test_serve_decode_consistency():
    """Decode step by step == prefill logits at the same position."""
    cfg = get_config("tinyllama-1.1b", reduced=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
    from repro.models.transformer import forward
    full = forward(params, toks, cfg)                     # (2, 8, V)
    cache = init_cache(cfg, 2, max_len=16)
    outs = []
    for i in range(8):
        logits, cache = decode_step(params, cache, toks[:, i:i + 1], cfg)
        outs.append(np.asarray(logits[:, 0], np.float32))
    np.testing.assert_allclose(np.stack(outs, 1),
                               np.asarray(full, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_dbpim_compression_preserves_function():
    cfg = get_config("tinyllama-1.1b", reduced=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    ds = SyntheticLMDataset(cfg, 4, 64, seed=0)
    batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}
    base = float(loss_fn(params, batch, cfg))
    comp = sparsify_params(params, cfg, value_sparsity=0.0)
    loss_c = float(loss_fn(dequant_tree(params, comp), batch, cfg))
    assert abs(loss_c - base) < 0.5          # FTA-only: mild perturbation
    est = pim_speedup_estimate(comp, cfg)
    assert est["speedup"] > 2.0              # bit-level >= ~4x ideal
    rep = list(comp.report.values())
    assert all(r["bit_sparsity"] >= 0.75 - 1e-6 for r in rep)


def test_fta_aware_training_loop():
    """Fig.4 stage 2 at LM scale: periodic FTA projection inside the
    training loop still reduces loss (the paper's FTA-aware QAT claim,
    reduced scale)."""
    from repro.launch.train import main as train_main
    losses = train_main(["--arch", "tinyllama-1.1b", "--reduced",
                         "--steps", "40", "--batch", "8", "--seq", "64",
                         "--dbpim-every", "10", "--log-every", "100"])
    assert losses[-1] < losses[0]
    assert all(np.isfinite(l) for l in losses)
