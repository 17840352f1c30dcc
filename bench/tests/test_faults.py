"""A run with its timed path broken underneath reads ``correct`` false;
the unbroken run of the same seed reads true (``test_cells``). The
faults a served cell can have: a step that hands back its state
unchanged, and a token altered where it is produced. (Half a batch
left out of a mean, and a lost exchange between chips, belong to
training and to cells on several chips; neither exists here.)"""

import time

import pytest

from bench.tests.conftest import TINY, TINY_LIMITS, add_cell


def _stale_cache(engine):
    import jax
    import jax.numpy as jnp
    step = engine._decode

    def decode(params, tables, cache, *rest):
        copy = jax.tree_util.tree_map(jnp.copy, cache)
        logits, _ = step(params, tables, copy, *rest)
        return logits, cache
    engine._decode = decode


def _altered_token(engine):
    step = engine._decode
    calls = [0]

    def decode(*args):
        logits, cache = step(*args)
        calls[0] += 1
        if calls[0] % 3 == 0:
            logits = logits.at[:, :, 5].set(1e4)
        return logits, cache
    engine._decode = decode


@pytest.mark.parametrize("breaker", [_stale_cache, _altered_token],
                         ids=["state_unchanged", "token_altered"])
@pytest.mark.parametrize("name", sorted(TINY))
def test_broken_path_reads_incorrect(bench_copy, name, breaker):
    from bench.harness import run
    arch, sizes = TINY[name]
    cell = add_cell(bench_copy, name, f"{arch}.json", sizes)
    res = run(cell, 2 ** 33 + 5, 3.0, False, t_start=time.perf_counter(),
              require_tpu=False, root=bench_copy, breaker=breaker)
    assert res["correct"] is False
    gap = res["checks"]["mean_logit_gap"]
    assert gap["value"] > 10 * gap["limit"]
