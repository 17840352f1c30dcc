"""MetricsRecorder.summary() contract: requests that never reach a first
token are counted explicitly (never silently folded into or dropped from
the TTFT aggregates), the all-queued-at-shutdown edge cannot crash,
percentiles are nearest-rank, retries are attributed by call kind,
per-request rows carry deadline/admission-wait, and the slot audit log
aggregates into utilization.
"""

from repro.serving.metrics import MetricsRecorder


def _submit(m, rid, arrival=0):
    m.on_submit(rid, prompt_len=4, gen_len=2, arrival=arrival)


def test_all_queued_at_shutdown_summary_is_explicit_not_a_crash():
    """Engine shut down with every request still queued: no TTFT exists.
    summary() must report that state explicitly — None aggregates plus an
    n_no_first_token count — rather than crashing or averaging over an
    empty/placeholder population."""
    m = MetricsRecorder()
    for rid in range(3):
        _submit(m, rid)
    s = m.summary()
    assert s["n_requests"] == 3
    assert s["ttft_n"] == 0
    assert s["n_no_first_token"] == 3
    assert s["ttft_ticks_mean"] is None
    assert s["ttft_ticks_p50"] is None
    assert s["ttft_ticks_p95"] is None
    assert s["prefill_steps_per_request_mean"] is None
    assert s["n_completed"] == 0


def test_partial_first_tokens_aggregate_over_reached_only():
    """Mixed population: TTFT aggregates cover exactly the requests that
    reached a first token; the rest are counted, not imputed. Prefill
    steps average over every ADMITTED request — half-prefilled requests
    did real device work."""
    m = MetricsRecorder()
    for rid in range(4):
        _submit(m, rid)
        m.on_admit(rid, tick=0)
    # rids 0/1 reach first token at ticks 3 and 5; 2/3 never do, but rid 2
    # burned 2 prefill steps before shutdown
    m.on_prefill_step(0)
    m.on_first_token(0, 3)
    m.on_prefill_step(1)
    m.on_first_token(1, 5)
    m.on_prefill_step(2)
    m.on_prefill_step(2)
    s = m.summary()
    assert s["ttft_n"] == 2 and s["n_no_first_token"] == 2
    assert s["ttft_ticks_mean"] == 4.0            # (3 + 5) / 2, not /4
    assert s["ttft_ticks_p50"] == 3
    assert s["ttft_ticks_p95"] == 5
    assert s["prefill_steps_per_request_mean"] == 1.0   # 4 steps / 4 admitted
    assert s["ttft_n"] + s["n_no_first_token"] == s["n_requests"]


def test_percentiles_are_nearest_rank():
    """p95 of 20 samples is the 19th order statistic, not the max; p50 of
    an odd count is the middle element."""
    m = MetricsRecorder()
    for rid in range(20):
        _submit(m, rid, arrival=0)
        m.on_first_token(rid, rid + 1)            # ttfts 1..20
    s = m.summary()
    assert s["ttft_ticks_p95"] == 19
    assert s["ttft_ticks_p50"] == 10
    assert s["ttft_ticks_mean"] == 10.5


def test_retries_attributed_by_call_kind():
    """on_retry(kind) lands in retries_by_kind — the old recorder took
    the argument and dropped it, so "which executable kept failing" was
    unanswerable from a summary."""
    m = MetricsRecorder()
    m.on_retry("decode")
    m.on_retry("decode")
    m.on_retry("prefill_parallel")
    s = m.summary()
    assert s["retries"] == 3
    assert s["retries_by_kind"] == {"decode": 2, "prefill_parallel": 1}


def test_per_request_carries_deadline_and_admission_wait():
    """per_request() rows expose the SLO inputs: the deadline a request
    was submitted with, and how long it queued before admission (the
    queueing share of TTFT)."""
    m = MetricsRecorder()
    m.on_submit(0, prompt_len=4, gen_len=2, arrival=3, deadline=20)
    m.on_submit(1, prompt_len=4, gen_len=2, arrival=0)
    m.on_admit(0, tick=7)
    rows = {r["rid"]: r for r in m.per_request()}
    assert rows[0]["deadline"] == 20
    assert rows[0]["admission_wait_ticks"] == 4      # admitted 7, arrived 3
    assert rows[1]["deadline"] is None
    assert rows[1]["admission_wait_ticks"] is None   # never admitted


def test_slot_log_aggregates_into_utilization():
    """record_slot_log turns the engine's interval audit log into
    slot_busy_frac / per-slot occupancy; open intervals (still serving
    at shutdown) count busy through the last tick."""
    m = MetricsRecorder()
    for tick in range(10):
        m.on_tick(tick, queue_depth=0, n_prefilling=0, n_decoding=0,
                  device_calls=1)
    # slot 0: [0,4) then [6,10); slot 1: [2, open) -> busy to tick 10
    m.record_slot_log([(0, 0, 4), (0, 6, 10), (1, 2, None)], n_slots=2)
    s = m.summary()
    assert s["slot_occupancy"] == [0.8, 0.8]
    assert s["slot_busy_frac"] == 0.8


def test_slot_metrics_none_without_log():
    """Until the engine installs its audit log, utilization is an
    explicit None, not a fabricated zero."""
    s = MetricsRecorder().summary()
    assert s["slot_busy_frac"] is None
    assert s["slot_occupancy"] is None


def test_device_call_latency_histogram_by_kind():
    """Per-call dur_s lands in a per-kind log histogram; replay calls
    are tagged separately so recovery latency is attributable."""
    m = MetricsRecorder()
    for _ in range(8):
        m.on_device_call("decode", kind="decode", dur_s=0.010)
    m.on_device_call("prefill", kind="prefill_parallel", replay=True,
                     dur_s=0.040)
    s = m.summary()
    lat = s["call_latency_ms"]
    assert set(lat) == {"decode", "prefill_parallel+replay"}
    assert lat["decode"]["count"] == 8
    assert abs(lat["decode"]["p50_ms"] - 10.0) / 10.0 < 0.10
    assert s["calls_by_kind"]["prefill_parallel+replay"] == 1


def test_state_from_before_the_straggler_count_loads():
    """A snapshot's metrics state written while the recorder still kept
    a straggler count loads unchanged, and the count is gone from the
    summary."""
    m = MetricsRecorder()
    _submit(m, 0)
    m.on_admit(0, tick=1, skips=0)
    old = dict(m.state_dict(), straggler_ticks=4)
    back = MetricsRecorder()
    back.load_state_dict(old)
    assert back.state_dict() == m.state_dict()
    assert "straggler_ticks" not in back.summary()
