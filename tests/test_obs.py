"""Observability subsystem (repro.obs): the zero-overhead-when-off
contract, trace structural invariants under a seeded fault plan, the
recompile sentinel, exact waterfall attribution, log-bucketed latency
histograms, the Chrome-trace converter, and the report CLI."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.models import init_cache, init_params
from repro.obs import (LogHistogram, RecompileError, RecompileSentinel,
                       Tracer, engine_waterfall, serving_cost_by_kind,
                       to_chrome_trace, validate)
from repro.obs.trace import TraceError, load
from repro.serving import FaultPlan, Request, ServeEngine
from repro.sparsity.sparse_linear import (build_stacked_tables,
                                          strip_packed_projections)

N_SLOTS = 2
MAX_LEN = 48
CHUNK = 4


def _cfg(arch="tinyllama-1.1b", **kw):
    return get_config(arch, reduced=True, **kw).scaled(
        n_layers=2, d_model=32, vocab_size=64, **{})


def _requests(n=5, gen=5):
    return [Request(rid=i, prompt=list(range(1, 5 + i)), gen_len=gen,
                    arrival=i) for i in range(n)]


def _run(cfg, params, *, tracer=None, fault_plan=None, n=5):
    engine = ServeEngine(cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                         prefill_chunk=CHUNK, tracer=tracer,
                         fault_plan=fault_plan)
    outputs = engine.run(_requests(n))
    return engine, outputs


@pytest.fixture(scope="module")
def tiny():
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def traced(tiny):
    """One fault-free traced run shared by the host-phase span tests."""
    cfg, params = tiny
    tracer = Tracer(arch=cfg.name)
    engine, outputs = _run(cfg, params, tracer=tracer)
    return engine, tracer


def _spans(tracer, name):
    return [r for r in tracer.records
            if r.get("type") == "span" and r["name"] == name]


def _within(inner, outer) -> bool:
    return (outer["ts_us"] <= inner["ts_us"] and
            inner["ts_us"] + inner["dur_us"] <=
            outer["ts_us"] + outer["dur_us"])


@pytest.fixture(scope="module")
def chaos_traced(tiny):
    """One seeded-fault traced run shared by the structural tests."""
    cfg, params = tiny
    plan = FaultPlan.generate(seed=3, n_ticks=60, rate=0.3,
                              n_slots=N_SLOTS)
    tracer = Tracer(arch=cfg.name, meta={"case": "test"})
    engine, outputs = _run(cfg, params, tracer=tracer, fault_plan=plan)
    return engine, tracer


# ------------------------------------------------ zero-overhead-when-off --

def test_tracer_off_is_bitwise_free(tiny):
    """The tentpole contract: tracer attached vs not — SAME generated
    tokens (bitwise) and SAME device-call count. Instrumentation must
    observe the engine, never steer it."""
    cfg, params = tiny
    traced_engine, traced_out = _run(cfg, params, tracer=Tracer(cfg.name))
    bare_engine, bare_out = _run(cfg, params)
    assert traced_out == bare_out
    ts, bs = traced_engine.metrics.summary(), bare_engine.metrics.summary()
    assert ts["device_calls"] == bs["device_calls"]
    assert ts["calls_by_kind"] == bs["calls_by_kind"]
    assert ts["engine_ticks"] == bs["engine_ticks"]


# ---------------------------------------------------- trace invariants ----

def test_trace_validates_under_faults(chaos_traced):
    """A chaotic traced run still satisfies every structural invariant:
    meta-first, monotone clocks, closed LIFO spans, call-within-tick
    containment, exclusive per-slot intervals."""
    engine, tracer = chaos_traced
    stats = validate(tracer.records)
    assert stats["spans"] > 0 and stats["intervals"] > 0
    s = engine.metrics.summary()
    names = [r["name"] for r in tracer.records if r.get("type") == "event"]
    # the fault plan landed -> the lifecycle events must be in the trace
    assert s["n_faults"] > 0 and "fault" in names
    assert s["replays"] == names.count("replay")
    assert names.count("admit") >= 5          # every request admitted
    # one tick span per engine tick, device calls covered by call spans
    ticks = [r for r in tracer.records
             if r.get("type") == "span" and r["name"] == "tick"]
    calls = [r for r in tracer.records
             if r.get("type") == "span" and r["name"] == "call"]
    assert len(ticks) == s["engine_ticks"]
    assert len(calls) == s["device_calls"]


def test_trace_roundtrip_and_report(chaos_traced, tmp_path, capsys):
    """dump -> load roundtrips; the report CLI renders the trace and the
    Chrome converter emits a loadable Perfetto JSON."""
    engine, tracer = chaos_traced
    for kind, wf in engine_waterfall(engine).items():
        tracer.waterfall(kind, wf["rows"], wf["total"])
    path = tmp_path / "trace.jsonl"
    tracer.dump(str(path))
    records = load(str(path))
    assert validate(records) == validate(tracer.records)

    from repro.launch.report import main as report_main
    chrome = tmp_path / "chrome.json"
    assert report_main([str(path), "--chrome", str(chrome)]) == 0
    out = capsys.readouterr().out
    for section in ("TIMELINE", "SLOTS", "QUEUE DEPTH", "WATERFALL",
                    "FAULTS"):
        assert section in out, f"report missing {section} section"
    ct = json.loads(chrome.read_text())
    assert any(e.get("ph") == "X" for e in ct["traceEvents"])


# ------------------------------------------------ host-phase spans -------

def test_host_phase_spans_nest_in_their_tick(traced):
    """Every span other than "tick" lies inside its own tick's span,
    and every "logits" inside a "call" of the same tick."""
    engine, tracer = traced
    ticks = {t["tick"]: t for t in _spans(tracer, "tick")}
    names = {r["name"] for r in tracer.records if r.get("type") == "span"}
    assert names == {"tick", "schedule", "call", "logits", "sample"}
    assert len(_spans(tracer, "schedule")) == len(ticks)
    for r in tracer.records[1:]:
        if r.get("type") == "span" and r["name"] != "tick":
            assert _within(r, ticks[r["tick"]]), r
    calls = _spans(tracer, "call")
    for lg in _spans(tracer, "logits"):
        assert any(c["tick"] == lg["tick"] and _within(lg, c)
                   for c in calls), lg


def test_call_span_covers_its_logits_and_sample_follows(traced):
    """One "logits" per call, inside it, so a call never times less
    than its wait for the logits; one "sample" after each call."""
    engine, tracer = traced
    calls, logits = _spans(tracer, "call"), _spans(tracer, "logits")
    samples = _spans(tracer, "sample")
    assert len(calls) == len(logits) == len(samples) == \
        engine.metrics.device_calls
    for c, lg, sm in zip(calls, logits, samples):
        assert c["dur_us"] >= lg["dur_us"]
        assert sm["ts_us"] >= c["ts_us"] + c["dur_us"]


def test_prefill_rows_valid_add_up_to_prompt_tokens(traced):
    """The prefill calls' rows_valid count every prompt token served
    once; each chunk computes bucket x chunk rows, its bucket the
    smallest that holds the call's prefilling slots."""
    engine, tracer = traced
    pre = [c["attrs"] for c in _spans(tracer, "call")
           if c["attrs"]["phase"] == "prefill"]
    assert pre and all(a["rows"] == a["bucket"] * CHUNK for a in pre)
    assert all(a["bucket"] == min(b for b in engine._prefill
                                  if b >= len(a["participants"]))
               for a in pre)
    assert sum(a["rows_valid"] for a in pre) == \
        sum(len(r.prompt) for r in _requests())
    decode = [c["attrs"] for c in _spans(tracer, "call")
              if c["attrs"]["phase"] == "decode"]
    assert decode and not any("rows" in a for a in decode)


def test_call_latency_includes_the_wait_for_logits(traced):
    """The recorder's call latency runs from dispatch until the logits
    are on the host: per call kind it sums to at least the logits spans
    and at most the call spans."""
    engine, tracer = traced
    calls, logits = _spans(tracer, "call"), _spans(tracer, "logits")
    for tag, hist in engine.metrics.call_latency.items():
        mine = [i for i, c in enumerate(calls) if c["attrs"]["kind"] == tag]
        assert len(mine) == hist.count
        lo = sum(logits[i]["dur_us"] for i in mine) * 1e-6
        hi = sum(calls[i]["dur_us"] for i in mine) * 1e-6
        assert lo <= hist.total <= hi, (tag, lo, hist.total, hi)


def test_submit_admit_first_token_pair_per_request(traced):
    """Each request's submit, admit and first_token events carry its
    rid, in that order on the wall clock."""
    engine, tracer = traced
    at = {}
    for r in tracer.records:
        if r.get("type") == "event" and \
                r["name"] in ("submit", "admit", "first_token"):
            at.setdefault(r["attrs"]["rid"], {})[r["name"]] = r["ts_us"]
    assert sorted(at) == [r.rid for r in _requests()]
    for rid, t in at.items():
        assert t["submit"] <= t["admit"] <= t["first_token"], rid


def test_commit_span_covers_journal_and_snapshot(tiny, tmp_path):
    """With a journal and snapshots, each tick ends in a "commit" span
    inside it, and the trace still validates."""
    cfg, params = tiny
    tracer = Tracer(arch=cfg.name)
    engine = ServeEngine(cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                         prefill_chunk=CHUNK, tracer=tracer,
                         journal=str(tmp_path / "j.jsonl"),
                         snapshot_dir=str(tmp_path / "snaps"),
                         snapshot_every=3)
    engine.run(_requests(3))
    validate(tracer.records)
    ticks = _spans(tracer, "tick")
    commits = _spans(tracer, "commit")
    assert len(commits) == len(ticks)
    names = [r["name"] for r in tracer.records if r.get("type") == "event"]
    assert names.count("snapshot") == len(ticks) // 3


def test_report_self_times_add_up_to_the_tick(traced):
    """The report's host-phase self times split each tick exactly: the
    names' self times of one tick add up to its span's duration."""
    from repro.launch.report import render, self_times
    engine, tracer = traced
    per_tick = self_times([r for r in tracer.records
                           if r.get("type") == "span"])
    for t in _spans(tracer, "tick"):
        got = per_tick[t["tick"]]
        assert sum(got.values()) == pytest.approx(t["dur_us"], abs=1e-6)
        assert all(v >= -1e-6 for v in got.values()), got
    out = render(tracer.records)
    for label in ("schedule", "call (dispatch)", "logits", "sample",
                  "tick (rest)"):
        assert label in out


def test_engine_spans_reach_the_profiler_trace(tiny, tmp_path):
    """Under jax.profiler a traced engine leaves its spans as
    ``engine.*`` host events in the .xplane.pb, one per record; an
    engine with no tracer leaves none."""
    import sys
    from pathlib import Path
    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from jax.profiler import ProfileData
    from bench.host_phases import engine_spans

    cfg, params = tiny

    def profiled(tracer, logdir):
        with jax.profiler.trace(str(logdir)):
            _run(cfg, params, tracer=tracer)
        files = list(Path(logdir).rglob("*.xplane.pb"))
        assert len(files) == 1
        return engine_spans(ProfileData.from_file(str(files[0])))

    tracer = Tracer(arch=cfg.name)
    got = profiled(tracer, tmp_path / "on")
    recorded = [r["name"] for r in tracer.records if r.get("type") == "span"]
    names = [s.name for s in got]
    for name in ("tick", "schedule", "call", "logits", "sample"):
        assert names.count(f"engine.{name}") == recorded.count(name), name
    assert profiled(None, tmp_path / "off") == []


def test_validate_rejects_a_span_outside_its_tick():
    tr = Tracer()
    t = tr.begin("tick", 0)
    tr.end(t)
    s = tr.begin("sample", 0)
    tr.end(s)
    with pytest.raises(TraceError, match="sample span"):
        validate(tr.records)
    tr2 = Tracer()
    tr2.event("preempt", 0, rid=1, slot=0)
    tr2.event("submit", 0, rid=1)
    assert validate(tr2.records)["events"] == 2


def test_span_nesting_is_lifo_enforced():
    tr = Tracer()
    t = tr.begin("tick", 0)
    c = tr.begin("call", 0)
    with pytest.raises(TraceError):
        tr.end(t)                  # closing the outer span first
    tr.end(c)
    tr.end(t)
    with pytest.raises(TraceError):
        tr.end(t)                  # double close


def test_dump_refuses_open_spans(tmp_path):
    tr = Tracer()
    tr.begin("tick", 0)
    with pytest.raises(TraceError):
        tr.dump(str(tmp_path / "x.jsonl"))


def test_validate_rejects_malformed():
    tr = Tracer()
    s = tr.begin("tick", 0)
    tr.end(s)
    bad = [dict(r) for r in tr.records]
    bad[1]["name"] = "mystery"
    with pytest.raises(TraceError):
        validate(bad)
    with pytest.raises(TraceError):
        validate(tr.records[1:])   # no meta record
    # ticks must be monotone
    tr2 = Tracer()
    a = tr2.begin("tick", 5)
    tr2.end(a)
    b = tr2.begin("tick", 4)
    tr2.end(b)
    with pytest.raises(TraceError):
        validate(tr2.records)


# ------------------------------------------------------------- sentinel ---

def test_sentinel_catches_shape_varying_jit():
    """A jitted fn fed two shapes compiles twice; check() must raise.
    The same fn fed one shape repeatedly stays at one compile."""
    fixed = jax.jit(lambda x: x * 2)
    varying = jax.jit(lambda x: x + 1)
    sent = RecompileSentinel()
    sent.register("fixed@test", fixed)
    sent.register("varying@test", varying)
    for _ in range(3):
        fixed(jnp.zeros((4,)))
    varying(jnp.zeros((4,)))
    sent.check()                              # 1 compile each: fine
    varying(jnp.zeros((8,)))                  # shape change -> recompile
    with pytest.raises(RecompileError, match="varying@test"):
        sent.check()
    assert sent.counts()["varying@test"] == 2
    assert sent.counts()["fixed@test"] == 1


def test_engine_sentinel_one_compile_per_step(tiny):
    """After a full serve, every registered (call_kind, arch) key sits
    at exactly one compile — the fixed-shape no-recompile contract."""
    cfg, params = tiny
    engine, _ = _run(cfg, params)
    counts = engine.sentinel.counts()
    assert counts and all(c <= 1 for c in counts.values()), counts
    assert any(k.startswith("decode@") for k in counts)


# ------------------------------------------------------------ waterfall ---

@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mamba2-1.3b"])
def test_waterfall_rows_sum_exactly_to_weight_bytes(arch):
    """Every modeled weight byte lands in exactly one parameter-path row:
    sum(rows) == weight_bytes with NO tolerance, stacked tables
    included (attributed through the step's table argument)."""
    cfg = get_config(arch, reduced=True, dbpim_mode="joint").scaled(
        n_layers=2, d_model=64, vocab_size=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tables = build_stacked_tables(params, cfg)
    assert tables is not None
    params = strip_packed_projections(params, cfg)
    mesh = make_test_mesh()
    cache = init_cache(cfg, N_SLOTS, MAX_LEN)
    cache["pos"] = jnp.zeros((N_SLOTS,), jnp.int32)
    if "attn" in cache and "pos" in cache["attn"]:
        cache["attn"]["pos"] = jnp.zeros((N_SLOTS,), jnp.int32)
    costs = serving_cost_by_kind(cfg, mesh, params, cache,
                                 n_slots=N_SLOTS, prefill_chunk=CHUNK,
                                 tables=tables,
                                 include_exact_fallback=True)
    assert "decode" in costs
    for kind, acc in costs.items():
        rows = acc["weight_bytes_by_path"]
        assert rows, f"{kind}: empty waterfall"
        assert sum(rows.values()) == acc["weight_bytes"], kind
        # stacked serving: the packed tables must be attributed by name,
        # not lumped into a fallback bucket
        assert any(p.startswith("tables/") for p in rows), (kind, rows)


# ------------------------------------------------------------ histogram ---

def test_log_histogram_percentiles_and_merge():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-3.0, sigma=1.0, size=4000)
    h = LogHistogram()
    for v in vals:
        h.add(float(v))
    for q in (0.5, 0.95, 0.99):
        exact = float(np.quantile(vals, q))
        est = h.percentile(q)
        # log-bucketed: estimate within one bucket (growth factor ~9%)
        assert abs(est - exact) / exact < 0.10, (q, est, exact)
    # merge(a, b) == histogram of concatenation
    h1, h2 = LogHistogram(), LogHistogram()
    for v in vals[:2000]:
        h1.add(float(v))
    for v in vals[2000:]:
        h2.add(float(v))
    h1.merge(h2)
    d1, d = h1.to_dict(), h.to_dict()
    assert d1["buckets"] == d["buckets"] and d1["count"] == d["count"]
    # raw-value running sums differ only by float addition order
    assert d1["total"] == pytest.approx(d["total"])
    # dict roundtrip
    h3 = LogHistogram.from_dict(h.to_dict())
    assert h3.percentile(0.5) == h.percentile(0.5)
    s = h.summary_ms()
    assert s["count"] == 4000 and s["p50_ms"] > 0


# ---------------------------------------------------------------- chrome --

def test_chrome_trace_structure():
    tr = Tracer(arch="x")
    t = tr.begin("tick", 0)
    c = tr.begin("call", 0, kind="decode")
    tr.end(c)
    tr.end(t)
    tr.event("admit", 0, rid=7, slot=1)
    tr.interval(slot=1, rid=7, admit_tick=0, release_tick=3)
    ct = to_chrome_trace(tr.records)
    phases = {e["ph"] for e in ct["traceEvents"]}
    assert {"X", "i", "M"} <= phases
    # the interval lands on the slot's own track (tid = slot + 1)
    ivs = [e for e in ct["traceEvents"]
           if e["ph"] == "X" and e.get("tid") == 2]
    assert len(ivs) == 1 and "rid7" in ivs[0]["name"]
    json.dumps(ct)                            # must be serializable


# ------------------------------------------------------- SSM state counters --

@pytest.fixture(scope="module")
def ssm_traced():
    """One traced run of the reduced mamba2 preset: five admissions into
    two slots, the first two in the same tick."""
    cfg = _cfg("mamba2-1.3b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tracer = Tracer(arch=cfg.name)
    engine, outputs = _run_ssm(cfg, params, tracer)
    return cfg, params, engine, outputs, tracer


def _run_ssm(cfg, params, tracer=None):
    engine = ServeEngine(cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                         prefill_chunk=CHUNK, tracer=tracer)
    reqs = [Request(rid=i, prompt=list(range(1, 5 + i)), gen_len=5,
                    arrival=max(0, i - 1)) for i in range(5)]
    return engine, engine.run(reqs)


def test_ssm_call_spans_count_state_slots(ssm_traced):
    """Every decode and prefill call of an SSM model carries
    state_slots: the slots whose conv and SSM state the step advanced,
    which are exactly its participants."""
    *_, tracer = ssm_traced
    calls = [c["attrs"] for c in _spans(tracer, "call")]
    assert {a["phase"] for a in calls} == {"prefill", "decode"}
    for a in calls:
        assert a["state_slots"] == len(a["participants"]) > 0


def test_ssm_schedule_spans_count_state_resets(ssm_traced):
    """Each tick's schedule span carries state_resets: the slots whose
    state admission zeroed, one per admission of that tick."""
    *_, tracer = ssm_traced
    admits = {}
    for r in tracer.records:
        if r.get("type") == "event" and r["name"] == "admit":
            admits[r["tick"]] = admits.get(r["tick"], 0) + 1
    sched = _spans(tracer, "schedule")
    assert len(sched) == len(_spans(tracer, "tick"))
    for s in sched:
        assert s["attrs"]["state_resets"] == admits.get(s["tick"], 0)
    assert sum(s["attrs"]["state_resets"] for s in sched) == 5
    assert sched[0]["attrs"]["state_resets"] == 2


def test_report_prints_ssm_state_counters(ssm_traced, traced):
    """launch.report prints the SSM counters where the spans carry
    them, and an attention model's spans carry none."""
    from repro.launch.report import render
    *_, tracer = ssm_traced
    out = render(tracer.records)
    assert "SSM states advanced per call: " in out
    assert "SSM states zeroed at admission: 5 slots over" in out
    _, bare = traced
    for r in bare.records:
        if r.get("type") == "span":
            assert not {"state_slots", "state_resets"} & set(r["attrs"])
    assert "SSM states" not in render(bare.records)


def test_ssm_tracer_off_is_bitwise_free(ssm_traced):
    """The SSM counters observe the engine and never steer it: the same
    tokens (bitwise) and device calls with the tracer off."""
    cfg, params, traced_engine, traced_out, _ = ssm_traced
    bare_engine, bare_out = _run_ssm(cfg, params)
    assert traced_out == bare_out
    ts, bs = traced_engine.metrics.summary(), bare_engine.metrics.summary()
    assert ts["calls_by_kind"] == bs["calls_by_kind"]
    assert ts["engine_ticks"] == bs["engine_ticks"]
