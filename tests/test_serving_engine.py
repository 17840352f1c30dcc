"""Serving engine: chunked cache-filling prefill (bit-identical to
stepwise decode), in-place per-slot cache writes and resets, slot
scheduler invariants under randomized traces, stale-cache zeroing on
slot refill, and the thin serve CLI."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.steps import build_step
from repro.models import decode_chunk, init_cache, init_params, reset_slots
from repro.models.transformer import encode
from repro.obs import Tracer
from repro.serving import (Request, ServeEngine, WorkloadSpec, assemble_chunk,
                           make_trace)
from repro.sparsity.sparse_linear import build_stacked_tables

ARCHS = ("tinyllama-1.1b", "mamba2-1.3b")


def _cfg(arch, dtype="float32", mode=None, **kw):
    cfg = get_config(arch, reduced=True, dbpim_mode=mode)
    return cfg.scaled(dtype=dtype, dbpim_value_sparsity=0.5, **kw)


def _exact(cfg):
    """BITWISE chunk==stepwise tests pin the exact per-token recurrence:
    the SSM default is the parallel SSD form, which is tolerance-equal
    only (tests/test_parallel_prefill.py owns that contract)."""
    return cfg.scaled(prefill_exact=True) if cfg.family == "ssm" else cfg


from conftest import chunked_prefill as _chunked
from conftest import stepwise_prefill as _stepwise


# ------------------------------------------------- chunked == stepwise ----

@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("plen", [3, 5, 8])      # 5, 3: NOT chunk multiples
def test_chunked_prefill_bit_identical_to_stepwise(arch, plen):
    """The acceptance guarantee: a chunked prefill (chunk=4, ragged tail)
    produces BIT-IDENTICAL caches and first-token logits to feeding the
    prompt through sequential decode steps — transformer and SSM (on the
    exact-recurrence path; the parallel SSD default is tolerance-equal
    and tested in test_parallel_prefill.py)."""
    cfg = _exact(_cfg(arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(1).integers(
        1, cfg.vocab_size, (3, plen)).astype(np.int32)
    ls, cs = _stepwise(params, cfg, prompts, 16)
    lc, cc = _chunked(params, cfg, prompts, 16, chunk=4)
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(lc))
    for a, b in zip(jax.tree_util.tree_leaves(cs),
                    jax.tree_util.tree_leaves(cc)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ARCHS)
def test_chunked_prefill_bit_identical_through_joint_tables(arch):
    """Same guarantee with the stacked joint-sparse tables threaded
    through both paths (prompt chunks run the DB-PIM kernel too)."""
    cfg = _exact(_cfg(arch, mode="joint"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    tables = build_stacked_tables(params, cfg, bk=32, bn=32)
    assert tables is not None
    prompts = np.random.default_rng(2).integers(
        1, cfg.vocab_size, (2, 7)).astype(np.int32)
    ls, cs = _stepwise(params, cfg, prompts, 16, tables=tables)
    lc, cc = _chunked(params, cfg, prompts, 16, chunk=4, tables=tables)
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(lc))
    for a, b in zip(jax.tree_util.tree_leaves(cs),
                    jax.tree_util.tree_leaves(cc)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ARCHS)
def test_chunk_with_zero_valid_leaves_cache_untouched(arch):
    """Slots with n_valid=0 (idle while neighbors prefill) must come out
    of a chunk step with their cache slices and position unchanged."""
    cfg = _cfg(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(3).integers(
        1, cfg.vocab_size, (2, 4)).astype(np.int32)
    _, cache = _stepwise(params, cfg, prompts, 16)          # both slots at 4
    toks = np.zeros((2, 4), np.int32)
    toks[0] = prompts[0]
    _, cache2 = decode_chunk(params, cache, jnp.asarray(toks),
                             jnp.asarray([4, 0], jnp.int32), cfg)
    assert int(cache2["pos"][0]) == 8 and int(cache2["pos"][1]) == 4
    # slot 1's slices (batch axis 1 in both cache families) are untouched
    sub = cache.get("attn") or cache["ssm"]
    sub2 = cache2.get("attn") or cache2["ssm"]
    for key in sub:
        a, b = np.asarray(sub[key]), np.asarray(sub2[key])
        if a.ndim >= 2:
            np.testing.assert_array_equal(a[:, 1], b[:, 1])


def test_chunk_window_clamped_at_cache_end_bit_identical():
    """A chunk that would run past the cache's last position is written
    as a window shifted back to end there; the rows it shifts over keep
    the tokens already cached. Prompt 9 in a 10-position cache, chunk 4:
    the last chunk (1 token at position 8) writes inside the window
    6..9."""
    cfg = _cfg("tinyllama-1.1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(11).integers(
        1, cfg.vocab_size, (2, 9)).astype(np.int32)
    ls, cs = _stepwise(params, cfg, prompts, 10)
    lc, cc = _chunked(params, cfg, prompts, 10, chunk=4)
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(lc))
    for a, b in zip(jax.tree_util.tree_leaves(cs),
                    jax.tree_util.tree_leaves(cc)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_chunked_prefill_rejects_unsupported_families():
    cfg = get_config("mixtral-8x7b", reduced=True)          # MoE + window
    assert not cfg.serving_capabilities().chunked_prefill
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = init_cache(cfg, 1, 8)
    with pytest.raises(ValueError):
        decode_chunk(params, cache, jnp.ones((1, 4), jnp.int32),
                     jnp.asarray([4], jnp.int32), cfg)


# ------------------------------------------------------ engine behaviour --

def test_engine_chunked_and_full_modes_generate_identically():
    """Prefill policy changes the schedule, never the tokens."""
    cfg = _cfg("tinyllama-1.1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    trace = make_trace(WorkloadSpec(n_requests=5, arrival_rate=1.0,
                                    prompt_len=(2, 10), gen_len=(2, 5),
                                    seed=4), cfg.vocab_size)
    outs = {}
    for mode in ("chunked", "full"):
        eng = ServeEngine(cfg, params, n_slots=2, max_len=24,
                          prefill_chunk=4, prefill_mode=mode)
        outs[mode] = eng.run(trace)
        s = eng.metrics.summary()
        assert s["n_completed"] == 5
    assert outs["chunked"] == outs["full"]


def test_engine_scheduler_invariants_random_trace():
    """Randomized arrivals: every admitted request completes with exactly
    gen_len tokens, each request is admitted exactly once, and no slot
    ever hosts two requests at the same time."""
    cfg = _cfg("tinyllama-1.1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    trace = make_trace(WorkloadSpec(n_requests=10, arrival_rate=2.0,
                                    prompt_len=(1, 9), gen_len=(1, 6),
                                    dist="uniform", seed=11),
                       cfg.vocab_size)
    eng = ServeEngine(cfg, params, n_slots=3, max_len=16, prefill_chunk=4)
    outputs = eng.run(trace)

    assert sorted(outputs) == [r.rid for r in trace]        # all complete
    for r in trace:
        assert len(outputs[r.rid]) == r.gen_len
    admits = [iv.rid for iv in eng.slot_log]
    assert sorted(admits) == sorted(r.rid for r in trace)   # exactly once
    by_slot = {}
    for iv in eng.slot_log:
        assert iv.release_tick is not None
        by_slot.setdefault(iv.slot, []).append(iv)
    for ivs in by_slot.values():
        ivs.sort(key=lambda iv: iv.admit_tick)
        for a, b in zip(ivs, ivs[1:]):
            assert a.release_tick <= b.admit_tick           # no overlap
    # queue depth was recorded and drains to zero
    assert eng.metrics.ticks[-1].queue_depth == 0


@pytest.mark.parametrize("arch", ARCHS)
def test_refilled_slot_matches_fresh_batch(arch):
    """The stale-cache regression: a request served by a REUSED slot
    (previous occupant's KV/SSM state must be zeroed at admission) gets
    bit-identical first-token logits and tokens to the same request
    served by a fresh engine. SSM states have no causal mask — without
    the zeroing, mamba2 fails this."""
    cfg = _cfg(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=tuple(
        int(t) for t in rng.integers(1, cfg.vocab_size, 6)),
        gen_len=4, arrival=0.0) for i in range(2)]

    shared = ServeEngine(cfg, params, n_slots=1, max_len=16,
                         prefill_chunk=4)
    out_shared = shared.run(reqs)
    assert len(shared.slot_log) == 2 and \
        {iv.slot for iv in shared.slot_log} == {0}          # slot reused

    fresh = ServeEngine(cfg, params, n_slots=1, max_len=16,
                        prefill_chunk=4)
    out_fresh = fresh.run([reqs[1]])
    assert out_shared[1] == out_fresh[1]
    np.testing.assert_array_equal(
        np.asarray(shared.first_logits[1], np.float32),
        np.asarray(fresh.first_logits[1], np.float32))


def test_spf_scheduler_invariants_random_trace():
    """SPF admission keeps every scheduler invariant FIFO holds: all
    requests complete with exactly gen_len tokens, one admission each, no
    slot overlap — and the queue-jump count never exceeds the age cap."""
    cfg = _cfg("tinyllama-1.1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    trace = make_trace(WorkloadSpec(n_requests=10, arrival_rate=2.0,
                                    prompt_len=(1, 9), gen_len=(1, 6),
                                    dist="bimodal", seed=11),
                       cfg.vocab_size)
    eng = ServeEngine(cfg, params, n_slots=2, max_len=16, prefill_chunk=4,
                      schedule="spf", spf_age_cap=3)
    outputs = eng.run(trace)
    assert sorted(outputs) == [r.rid for r in trace]
    for r in trace:
        assert len(outputs[r.rid]) == r.gen_len
    admits = [iv.rid for iv in eng.slot_log]
    assert sorted(admits) == sorted(r.rid for r in trace)
    by_slot = {}
    for iv in eng.slot_log:
        assert iv.release_tick is not None
        by_slot.setdefault(iv.slot, []).append(iv)
    for ivs in by_slot.values():
        ivs.sort(key=lambda iv: iv.admit_tick)
        for a, b in zip(ivs, ivs[1:]):
            assert a.release_tick <= b.admit_tick
    # skip entries die at admission (bounded scheduler state); the final
    # counts land in per-request metrics and respect the age cap
    assert eng.skips == {}
    assert max(r.skips for r in eng.metrics.requests.values()) <= 3


def test_spf_no_starvation_under_short_prompt_stream():
    """The starvation bound: a long prompt that keeps being queue-jumped
    by later-arriving short prompts becomes urgent after spf_age_cap
    jumps and is admitted ahead of the remaining shorts — it can never
    be deferred indefinitely."""
    cfg = _cfg("tinyllama-1.1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(6)
    cap = 2
    # rid0 occupies the single slot at t=0, so the long prompt (rid1,
    # also t=0) must QUEUE while later shorts keep arriving — each
    # admission that picks a later-arriving short over it is one jump
    blocker = Request(rid=0, prompt=tuple(
        int(t) for t in rng.integers(1, cfg.vocab_size, 2)),
        gen_len=2, arrival=0.0)
    long_req = Request(rid=1, prompt=tuple(
        int(t) for t in rng.integers(1, cfg.vocab_size, 10)),
        gen_len=2, arrival=0.0)
    shorts = [Request(rid=i, prompt=tuple(
        int(t) for t in rng.integers(1, cfg.vocab_size, 2)),
        gen_len=2, arrival=float(i - 1)) for i in range(2, 9)]
    eng = ServeEngine(cfg, params, n_slots=1, max_len=16, prefill_chunk=4,
                      schedule="spf", spf_age_cap=cap)
    outputs = eng.run([blocker, long_req] + shorts)
    assert sorted(outputs) == list(range(9))             # all complete
    assert eng.metrics.requests[1].skips == cap          # jumped cap times
    # urgent after `cap` jumps: only the blocker plus at most `cap`
    # shorts ran before the long prompt — it is never deferred past that
    order = [iv.rid for iv in sorted(eng.slot_log,
                                     key=lambda iv: iv.admit_tick)]
    assert order.index(1) <= cap + 1


def test_spf_no_starvation_simultaneous_arrivals():
    """The closed-loop batch corner (arrival_rate=0: every request at
    t=0): skip counts must still rise on every shortest-first pass-over,
    so a long prompt in an all-at-once batch is admitted after at most
    spf_age_cap shorter requests, never last-by-default."""
    cfg = _cfg("tinyllama-1.1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(8)
    cap = 2
    reqs = [Request(rid=0, prompt=tuple(
        int(t) for t in rng.integers(1, cfg.vocab_size, 10)),
        gen_len=2, arrival=0.0)]
    reqs += [Request(rid=i, prompt=tuple(
        int(t) for t in rng.integers(1, cfg.vocab_size, 2)),
        gen_len=2, arrival=0.0) for i in range(1, 6)]
    eng = ServeEngine(cfg, params, n_slots=1, max_len=16, prefill_chunk=4,
                      schedule="spf", spf_age_cap=cap)
    outputs = eng.run(reqs)
    assert sorted(outputs) == list(range(6))
    assert max(r.skips for r in eng.metrics.requests.values()) <= cap
    order = [iv.rid for iv in sorted(eng.slot_log,
                                     key=lambda iv: iv.admit_tick)]
    assert order.index(0) <= cap              # urgent after cap pass-overs


def test_spf_fifo_equal_results_same_trace():
    """Scheduling changes ADMISSION ORDER only: the token streams per
    request are identical under fifo and spf (each request's math is
    independent of when its slot was granted)."""
    cfg = _cfg("tinyllama-1.1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    trace = make_trace(WorkloadSpec(n_requests=6, arrival_rate=1.5,
                                    prompt_len=(2, 12), gen_len=(2, 4),
                                    dist="bimodal", seed=9),
                       cfg.vocab_size)
    outs = {}
    for schedule in ("fifo", "spf"):
        eng = ServeEngine(cfg, params, n_slots=2, max_len=24,
                          prefill_chunk=4, schedule=schedule)
        outs[schedule] = eng.run(trace)
    assert outs["fifo"] == outs["spf"]


def test_engine_rejects_bad_schedule():
    cfg = _cfg("tinyllama-1.1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        ServeEngine(cfg, params, n_slots=1, max_len=8, schedule="lifo")


def test_engine_rejects_oversized_requests():
    """Default: an oversized request is a RECORDED rejection (one
    malformed request must not abort a trace); strict=True restores the
    hard raise. tests/test_fault_tolerance.py covers the recorded-
    rejection path end-to-end."""
    cfg = _cfg("tinyllama-1.1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, n_slots=1, max_len=8, prefill_chunk=4)
    assert eng.submit(Request(rid=0, prompt=(1,) * 6, gen_len=4)) is False
    assert eng.rejected[0] == "oversized"
    strict = ServeEngine(cfg, params, n_slots=1, max_len=8,
                         prefill_chunk=4, strict=True)
    with pytest.raises(ValueError):
        strict.submit(Request(rid=0, prompt=(1,) * 6, gen_len=4))


def test_assemble_chunk_ragged():
    prompts = {0: np.arange(1, 6, dtype=np.int32),       # 5 tokens
               2: np.arange(10, 13, dtype=np.int32)}     # 3 tokens
    tokens, n_valid, slots = assemble_chunk(prompts, {0: 4, 2: 0}, 3, 4)
    assert tokens.shape == (3, 4) and n_valid.tolist() == [1, 0, 3]
    assert tokens[0, 0] == 5 and tokens[2, :3].tolist() == [10, 11, 12]
    assert not tokens[1].any() and slots.tolist() == [0, 1, 2]
    # a smaller bucket holds the prefilling slots in order, then padding
    # rows with the out-of-range slot index and no tokens
    tokens, n_valid, slots = assemble_chunk(prompts, {0: 4, 2: 0}, 8, 4, 4)
    assert slots.tolist() == [0, 2, 8, 8] and n_valid.tolist() == [1, 3, 0, 0]
    assert tokens[0, 0] == 5 and tokens[1, :3].tolist() == [10, 11, 12]
    assert not tokens[2:].any()


# ------------------------------------------------------------- serve CLI --

def test_serve_cli_drives_engine(capsys, monkeypatch, tmp_path):
    from repro.launch.serve import main
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    out = main(["--arch", "tinyllama-1.1b", "--reduced", "--batch", "2",
                "--max-len", "16", "--requests", "3", "--gen-len", "3",
                "--prompt-len", "2", "6", "--prefill-chunk", "4",
                "--dbpim-mode", "joint"])
    assert len(out) == 3 and all(len(v) == 3 for v in out.values())
    assert "tokens/step" in capsys.readouterr().out


@pytest.mark.parametrize("slo_flags,lost_ok", [
    ([], False),
    (["--queue-cap", "8"], True)])
def test_serve_cli_exits_nonzero_on_lost_requests(monkeypatch, tmp_path,
                                                   slo_flags, lost_ok):
    """Prompts longer than --max-len are rejected as oversized. With no
    flag that allows losing requests, the CLI must fail instead of
    exiting 0 with a partial result."""
    from repro.launch.serve import main
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    argv = ["--arch", "tinyllama-1.1b", "--reduced", "--batch", "2",
            "--max-len", "16", "--requests", "3", "--gen-len", "2",
            "--prompt-len", "15", "20", "--prefill-chunk", "4"] + slo_flags
    if lost_ok:
        assert main(argv) == {}
    else:
        with pytest.raises(SystemExit, match="requests not served"):
            main(argv)


# ------------------------------------------- in-place slot cache surgery --

def _filled(cfg, params, n_slots, max_len, steps, seed):
    """A cache after ``steps`` decode steps of random tokens in every
    slot, each slot at its own depth (slot b skips its first b steps)."""
    step, _ = build_step(cfg, None, "decode")
    step = jax.jit(step)
    cache = init_cache(cfg, n_slots, max_len)
    cache["pos"] = jnp.zeros((n_slots,), jnp.int32)
    if "pos" in cache.get("attn", {}):
        cache["attn"]["pos"] = jnp.zeros((n_slots,), jnp.int32)
    rng = np.random.default_rng(seed)
    for t in range(steps):
        tok = rng.integers(1, cfg.vocab_size, (n_slots, 1)).astype(np.int32)
        active = np.arange(n_slots) <= t
        _, cache = step(params, None, cache, jnp.asarray(tok),
                        jnp.asarray(active))
    return step, cache


@pytest.mark.parametrize("window", [0, 8])       # 8: a ring that wraps
def test_decode_step_writes_only_active_slots_rows(window):
    """A decode step writes one position of each ACTIVE slot in place:
    an inactive slot's K/V rows and position come out bitwise unchanged,
    and an active slot changes at its write position only (pos, or
    pos % alloc on a sliding-window ring)."""
    cfg = _cfg("tinyllama-1.1b", window=window)
    params = init_params(cfg, jax.random.PRNGKey(0))
    step, cache = _filled(cfg, params, 3, 16, 11, seed=6)
    before = jax.tree_util.tree_map(np.asarray, cache)
    active = np.array([True, False, True])
    tok = jnp.asarray([[5], [6], [7]], jnp.int32)
    _, after = step(params, None, cache, tok, jnp.asarray(active))
    alloc = before["attn"]["k"].shape[3]
    for b in range(3):
        p = int(before["pos"][b])
        assert int(after["pos"][b]) == p + int(active[b])
        for key in ("k", "v"):
            old = before["attn"][key][:, b]
            new = np.asarray(after["attn"][key][:, b])
            if not active[b]:
                np.testing.assert_array_equal(new, old)
                continue
            at = p % alloc if window else p
            rest = np.arange(alloc) != at
            np.testing.assert_array_equal(new[:, :, rest], old[:, :, rest])
            assert not np.array_equal(new[:, :, at], old[:, :, at])


@pytest.mark.parametrize("arch", ARCHS)
def test_reset_slots_zeroes_exactly_the_masked_slots(arch):
    """reset_slots zeroes every cache row and the position of the masked
    slots and leaves every other slot bitwise as it was."""
    cfg = _cfg(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    _, cache = _filled(cfg, params, 4, 16, 6, seed=7)
    before = jax.tree_util.tree_map(np.asarray, cache)
    mask = np.array([False, True, False, True])
    after = jax.jit(lambda c, m: reset_slots(c, m, cfg))(
        cache, jnp.asarray(mask))
    np.testing.assert_array_equal(
        np.asarray(after["pos"]), np.where(mask, 0, before["pos"]))
    leaves = jax.tree_util.tree_leaves_with_path(before)
    got = jax.tree_util.tree_leaves(after)
    for (path, old), new in zip(leaves, got):
        new = np.asarray(new)
        if old.ndim < 2:                                   # positions
            continue
        for b in range(4):
            if mask[b]:
                assert not new[:, b].any(), path
            else:
                np.testing.assert_array_equal(new[:, b], old[:, b])


@pytest.mark.parametrize("arch", ("mamba2-1.3b", "jamba-v0.1-52b"))
def test_masked_decode_step_advances_only_active_ssm_state(arch):
    """Through the serving decode step, a mixed active mask gives the
    active slots the logits, conv window and SSM state of a step in which
    every slot is active, and leaves the inactive slots' conv, state and
    position bitwise as they were. reset_slots then zeroes exactly the
    masked slots' conv and state and leaves the others bitwise equal."""
    cfg = _cfg(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    step, cache = _filled(cfg, params, 4, 16, 5, seed=9)
    before = jax.tree_util.tree_map(np.asarray, cache)
    tok = jnp.asarray([[3], [4], [5], [6]], jnp.int32)
    active = np.array([True, False, True, False])
    ssm = [k for k, c in before.items()
           if isinstance(c, dict) and "state" in c]
    assert ssm
    logits_all, every = step(params, None, jax.tree_util.tree_map(
        jnp.asarray, before), tok, jnp.ones((4,), bool))
    logits, after = step(params, None, cache, tok, jnp.asarray(active))
    np.testing.assert_array_equal(np.asarray(logits)[active],
                                  np.asarray(logits_all)[active])
    np.testing.assert_array_equal(
        np.asarray(after["pos"]), before["pos"] + active)
    for key in ssm:
        for leaf in ("conv", "state"):
            new = np.asarray(after[key][leaf])
            np.testing.assert_array_equal(
                new[:, active], np.asarray(every[key][leaf])[:, active])
            np.testing.assert_array_equal(new[:, ~active],
                                          before[key][leaf][:, ~active])
            assert not np.array_equal(new[:, active],
                                      before[key][leaf][:, active])

    kept = jax.tree_util.tree_map(np.asarray, after)
    mask = np.array([False, True, True, False])
    reset = jax.jit(lambda c, m: reset_slots(c, m, cfg))(after,
                                                        jnp.asarray(mask))
    for key in ssm:
        for leaf in ("conv", "state"):
            new = np.asarray(reset[key][leaf])
            assert not new[:, mask].any(), (key, leaf)
            np.testing.assert_array_equal(new[:, ~mask],
                                          kept[key][leaf][:, ~mask])


def test_decode_spans_count_slots_written():
    """Each decode call span carries slots_written: the slots whose K/V
    rows the step wrote, which are exactly its active participants."""
    cfg = _cfg("tinyllama-1.1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    trace = make_trace(WorkloadSpec(n_requests=5, arrival_rate=2.0,
                                    prompt_len=(2, 8), gen_len=(2, 6),
                                    seed=8), cfg.vocab_size)
    tracer = Tracer(arch=cfg.name)
    eng = ServeEngine(cfg, params, n_slots=3, max_len=24, prefill_chunk=4,
                      tracer=tracer)
    eng.run(trace)
    dec = [r["attrs"] for r in tracer.records
           if r.get("type") == "span" and r["name"] == "call"
           and r["attrs"].get("kind") == "decode"]
    assert dec
    for a in dec:
        assert a["slots_written"] == len(a["participants"]) > 0
    assert sum(a["slots_written"] for a in dec) < 3 * len(dec)


# ---------------------------------------------- compact chunk row buckets --

N_SLOTS, MAX_LEN, CHUNK, PAGE = 8, 16, 4, 4

COMPACT_CASES = {
    "dense": lambda: _cfg("tinyllama-1.1b"),
    "paged": lambda: _cfg("tinyllama-1.1b"),
    "ssm-parallel": lambda: _cfg("mamba2-1.3b"),
    "ssm-exact": lambda: _cfg("mamba2-1.3b", prefill_exact=True),
    # 4 experts, top-2: a capacity factor of 4 covers every assignment
    # at every bucket, so no row's experts depend on the other rows
    "moe": lambda: _cfg("arctic-480b", capacity_factor=4.0),
    # SSM, attention and MoE segments in one cache, the first an SSM's
    "hybrid": lambda: _cfg("jamba-v0.1-52b", capacity_factor=4.0),
    # a decoder whose cross-attention reads each slot's own encoder output
    "encdec": lambda: _cfg("whisper-base"),
}
#: cases whose chunk runs the parallel SSD form: equal within its
#: tolerance, not bitwise
PARALLEL_SSD = ("ssm-parallel", "hybrid")


@pytest.fixture(scope="module")
def compact_setups():
    """Per case: config, params, the jitted chunk step and a cache in
    which every slot holds a first chunk of its own length (0-4)."""
    built = {}

    def get(case):
        if case in built:
            return built[case]
        cfg = COMPACT_CASES[case]()
        params = init_params(cfg, jax.random.PRNGKey(0))
        paged = case == "paged"
        ptab = None
        if paged:
            n_pages = N_SLOTS * MAX_LEN // PAGE
            cache = init_cache(cfg, N_SLOTS, MAX_LEN, n_pages=n_pages,
                               page_size=PAGE)
            perm = np.random.default_rng(3).permutation(n_pages)
            ptab = jnp.asarray(perm.reshape(N_SLOTS, -1).astype(np.int32))
        elif cfg.is_encdec:
            frames = jax.random.normal(
                jax.random.PRNGKey(5), (N_SLOTS, cfg.encoder_seq,
                                        cfg.d_model), jnp.float32)
            cache = init_cache(cfg, N_SLOTS, MAX_LEN,
                               enc_out=encode(params, frames, cfg))
        else:
            cache = init_cache(cfg, N_SLOTS, MAX_LEN)
        cache["pos"] = jnp.zeros((N_SLOTS,), jnp.int32)
        if "pos" in cache.get("attn", {}):
            cache["attn"]["pos"] = jnp.zeros((N_SLOTS,), jnp.int32)
        step = jax.jit(build_step(cfg, None, "prefill_chunk",
                                  paged=paged)[0])
        extra = () if ptab is None else (ptab,)
        rng = np.random.default_rng(9)
        first = {s: rng.integers(1, cfg.vocab_size, s % (CHUNK + 1))
                 for s in range(N_SLOTS)}
        args = assemble_chunk(first, dict.fromkeys(first, 0), N_SLOTS, CHUNK)
        _, cache = step(params, None, cache, *map(jnp.asarray, args), *extra)
        built[case] = (cfg, params, step, cache, extra)
        return built[case]

    return get


def _slot_rows(cache, s, extra):
    """Every cache array of slot s: its position, its K/V rows (or, paged,
    the pages its table row names), its SSM conv and state and its
    encoder output."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        key = jax.tree_util.keystr(path)
        leaf = np.asarray(leaf)
        if leaf.ndim == 1 or key == "['enc_out']":
            out[key] = leaf[s]
        elif key.endswith("['pk']") or key.endswith("['pv']"):
            out[key] = leaf[:, np.asarray(extra[0][s])]
        else:
            out[key] = leaf[:, s]
    return out


@pytest.mark.parametrize("case", sorted(COMPACT_CASES))
@pytest.mark.parametrize("prefilling,rows", [
    ((5,), 1), ((2, 7), 2), ((0, 3, 4, 6), 4),
    ((1, 4, 6), 4)])                        # padded: 3 slots in a 4-bucket
def test_compact_chunk_rows_match_the_whole_batch(compact_setups, case,
                                                  prefilling, rows):
    """A chunk on a row bucket smaller than the batch computes, for each
    slot it names, the logits and cache of the whole-batch chunk (today's
    call): bitwise for attention (cross-attention to each slot's own
    encoder output included), MoE and the exact SSM recurrence, within
    the parallel SSD form's tolerance (the SSM and the hybrid stack).
    Every other slot (the one padding rows read included) comes out
    bitwise untouched."""
    if case == "ssm-exact" and rows == 1:
        # chunk_buckets leaves this bucket out (one-row projections);
        # one slot then runs padded in the 2-row bucket
        rows = 2
    from repro.models.ssm import PARALLEL_PREFILL_ATOL
    cfg, params, step, cache, extra = compact_setups(case)
    rng = np.random.default_rng(11)
    prompts = {s: rng.integers(1, cfg.vocab_size, 2 + s % 3)
               for s in prefilling}
    cursors = dict.fromkeys(prompts, 0)
    full = assemble_chunk(prompts, cursors, N_SLOTS, CHUNK)
    compact = assemble_chunk(prompts, cursors, N_SLOTS, CHUNK, rows)
    lf, cf = step(params, None, cache, *map(jnp.asarray, full), *extra)
    lc, cc = step(params, None, cache, *map(jnp.asarray, compact), *extra)
    assert lc.shape == (rows, 1, cfg.vocab_size)
    if case in PARALLEL_SSD:
        same = lambda a, b: np.testing.assert_allclose(
            a, b, rtol=0, atol=PARALLEL_PREFILL_ATOL["float32"])
    else:
        same = np.testing.assert_array_equal
    slots = compact[2].tolist()
    assert slots[len(prefilling):] == [N_SLOTS] * (rows - len(prefilling))
    for r, s in enumerate(slots[:len(prefilling)]):
        same(np.asarray(lc[r]), np.asarray(lf[s]))
    for s in range(N_SLOTS):
        got = _slot_rows(cc, s, extra)
        want = _slot_rows(cf if s in prompts else cache, s, extra)
        for key in want:
            if s in prompts:
                same(got[key], want[key])
            else:
                np.testing.assert_array_equal(got[key], want[key], key)


def test_chunk_buckets_follow_slots_and_moe_capacity():
    """Powers of two below n_slots, then n_slots; a MoE configuration
    whose expert capacity at some bucket drops assignments keeps the
    whole batch alone, and no bucket leaves a projection one row."""
    from repro.serving.prefill import chunk_buckets
    dense = _cfg("tinyllama-1.1b")
    assert chunk_buckets(dense, 16, 4) == (1, 2, 4, 8, 16)
    assert chunk_buckets(dense, 6, 4) == (1, 2, 4, 6)
    assert chunk_buckets(dense, 1, 4) == (1,)
    assert chunk_buckets(dense, 4, 1) == (2, 4)   # one token a row
    moe = _cfg("arctic-480b")                     # E=4, top-2, factor 1.25
    assert chunk_buckets(moe, 4, 4) == (1, 2, 4)  # capacity covers all
    assert chunk_buckets(moe, 8, 4) == (8,)       # 8 slots of 16 pairs
    assert chunk_buckets(moe.scaled(capacity_factor=4.0), 8, 4) == \
        (1, 2, 4, 8)
    ssm = _cfg("mamba2-1.3b")
    assert chunk_buckets(ssm, 8, 4) == (1, 2, 4, 8)
    # the exact recurrence projects one token of each row at a time
    assert chunk_buckets(ssm.scaled(prefill_exact=True), 8, 4) == (2, 4, 8)


@pytest.mark.parametrize("arch", ARCHS)
def test_engine_row_buckets_serve_the_same_tokens(arch):
    """Eight requests whose prompts take 1-8 chunks, all due at once:
    8, 7, ..., 1 slots prefill together, so the chunks run on every
    bucket. Every bucket compiled once, at construction; the tokens are
    those of stepwise prefill through the decode step."""
    cfg = _exact(_cfg(arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(12)
    trace = [Request(rid=i, prompt=tuple(int(t) for t in rng.integers(
                 1, cfg.vocab_size, CHUNK * (i + 1))), gen_len=3, arrival=0)
             for i in range(8)]
    tracer = Tracer(arch=cfg.name)
    eng = ServeEngine(cfg, params, n_slots=8, max_len=40,
                      prefill_chunk=CHUNK, tracer=tracer)
    built = {k: n for k, n in eng.sentinel.counts().items()
             if k.startswith(eng.prefill_kind)}
    built_rows = sorted(int(k.rsplit("/", 1)[1]) for k in built)
    # the exact SSM recurrence has no 1-row bucket (chunk_buckets)
    assert built_rows == ([2, 4, 8] if cfg.family == "ssm" else [1, 2, 4, 8])
    assert set(built.values()) == {1}
    out = eng.run(trace)
    assert {k: eng.sentinel.counts()[k] for k in built} == built
    used = [r["attrs"]["bucket"] for r in tracer.records
            if r.get("type") == "span" and r["name"] == "call"
            and r["attrs"]["phase"] == "prefill"]
    assert used == [8, 8, 8, 8, 4, 4, 2, min(built_rows)]
    ref = ServeEngine(cfg, params, n_slots=8, max_len=40,
                      prefill_chunk=CHUNK, prefill_mode="full").run(trace)
    assert out == ref
