"""The engine's own host spans in a profiler trace, and the readings
taken from them and from the engine's tracer records.

With a ``repro.obs.Tracer`` attached, the engine enters each of its
spans as a profiler annotation named ``engine.<span>`` (``tick``,
``schedule``, ``call``, ``logits``, ``sample``, ``commit``; see
``repro.obs.trace``). They nest inside the harness's ``bench.tick <n>``
spans, on the host's clock that the profiler puts the device's events
on. This module

  * reads them from a trace (``engine_spans``, ``load``);
  * charges the device's idle time inside the harness's tick spans to
    the innermost span that covers it (``idle_by_span``): an
    ``engine.*`` span where one does, else ``bench.tick``, so the values
    add up to ``tick_s - tick_busy_s`` of ``bench.trace.reduce``;
  * names each idle gap of the traced loop by the innermost span at its
    midpoint (``idle_gaps``);
  * reduces the tracer's records of a window to the median queue wait
    (``admit`` less ``submit``), the median prefill wall time
    (``first_token`` less ``admit``) and the share of prefill rows that
    hold a prompt token (``rows_valid`` over ``rows`` of the prefill
    ``call`` spans).

A trace or a record list without the engine's spans (an engine run with
no tracer, or a program that predates them) gives ``None`` for every
reading that needs them.
"""

from __future__ import annotations

import bisect
import statistics
from typing import Dict, Iterable, List, Optional

from bench import trace
from bench.trace import Parsed, Span

PREFIX = "engine."
TICK = "bench.tick"


def engine_spans(profile) -> List[Span]:
    """The engine's annotations on the host planes of a
    ``jax.profiler.ProfileData``, in start order (a parent before a
    child that starts with it)."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(Span(ev.name, ev.start_ns * 1e-9,
                                    (ev.start_ns + ev.duration_ns) * 1e-9))
    out.sort(key=lambda s: (s.start, -s.end))
    return out


def load(path, device: str = "/device:TPU:0") -> tuple:
    """(``bench.trace.Parsed``, engine spans) of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(str(path))
    return trace.parse(profile, device), engine_spans(profile)


def _owner(name: str) -> str:
    return name if name.startswith(PREFIX) else name.split()[0]


def tiles(outer: Span, inner: List[Span]) -> List[tuple]:
    """(start, end, owner) pieces that tile ``outer``, each owned by the
    innermost of ``inner`` (start-ordered, nested) covering it, else by
    ``outer``. A span is clipped to its parent."""
    out = []
    stack = [(outer.end, _owner(outer.name))]
    t = outer.start
    for sp in inner:
        a = max(sp.start, t)
        while len(stack) > 1 and stack[-1][0] <= a:
            end, owner = stack.pop()
            out.append((t, end, owner))
            t = end
        a = max(a, t)
        if a >= stack[-1][0]:
            continue                      # starts where its parent ended
        out.append((t, a, stack[-1][1]))
        t = a
        stack.append((min(sp.end, stack[-1][0]), _owner(sp.name)))
    while stack:
        end, owner = stack.pop()
        out.append((t, end, owner))
        t = end
    return [p for p in out if p[1] > p[0]]


def _pieces(parsed: Parsed, engine: List[Span],
            ticks_only: bool = False) -> List[tuple]:
    """The harness's spans (its tick spans alone with ``ticks_only``),
    each tiled by the engine spans inside it, in start order."""
    starts = [s.start for s in engine]
    out = []
    for host in parsed.host:
        if ticks_only and host.name.split()[0] != TICK:
            continue
        i = bisect.bisect_left(starts, host.start)
        j = bisect.bisect_left(starts, host.end)
        out.extend(tiles(host, engine[i:j]))
    out.sort()
    return out


def _busy(parsed: Parsed):
    lo = parsed.host[0].start
    hi = max(s.end for s in parsed.host)
    return trace.union(parsed.ops, lo, hi), lo, hi


def _covered(busy, starts, a: float, b: float) -> float:
    i = max(0, bisect.bisect_right(starts, a) - 1)
    got = 0.0
    while i < len(busy) and busy[i][0] < b:
        got += max(0.0, min(busy[i][1], b) - max(busy[i][0], a))
        i += 1
    return got


def idle_by_span(parsed: Parsed, engine: List[Span]) -> Dict[str, float]:
    """Device-idle seconds inside the harness's tick spans by the
    innermost span covering them."""
    busy, _, _ = _busy(parsed)
    starts = [a for a, _ in busy]
    out: Dict[str, float] = {}
    for a, b, owner in _pieces(parsed, engine, ticks_only=True):
        out[owner] = out.get(owner, 0.0) + (b - a) - _covered(
            busy, starts, a, b)
    return out


def idle_gaps(parsed: Parsed, engine: List[Span], n: int = 10) -> list:
    """The ``n`` longest idle gaps of the traced loop as [owner,
    seconds], each owned by the innermost span at its midpoint."""
    busy, lo, hi = _busy(parsed)
    pieces = _pieces(parsed, engine)
    p_starts = [p[0] for p in pieces]
    out = []
    for a, b in trace.gaps(busy, lo, hi):
        mid = 0.5 * (a + b)
        i = bisect.bisect_right(p_starts, mid) - 1
        owner = (pieces[i][2] if i >= 0 and mid < pieces[i][1]
                 else "outside spans")
        out.append([owner, b - a])
    out.sort(key=lambda x: -x[1])
    return out[:n]


def tick_idle_pct(idle: Dict[str, float], tick_s: float,
                  span: str) -> Optional[float]:
    """Device-idle time inside ``engine.<span>`` over the ticks' time,
    in %; None where the trace holds no engine span."""
    if tick_s <= 0 or not any(k.startswith(PREFIX) for k in idle):
        return None
    return 100.0 * idle.get(PREFIX + span, 0.0) / tick_s


# -- the tracer's records -----------------------------------------------------

def request_times(records: List[dict], rids: Iterable[int]
                  ) -> Dict[int, Dict[str, float]]:
    """rid -> {event: wall us} of the first ``submit``, ``admit`` and
    ``first_token`` event of each of ``rids``."""
    want = set(rids)
    out: Dict[int, Dict[str, float]] = {}
    for r in records:
        if r.get("type") != "event" or \
                r["name"] not in ("submit", "admit", "first_token"):
            continue
        rid = r["attrs"].get("rid")
        if rid in want:
            out.setdefault(rid, {}).setdefault(r["name"], r["ts_us"])
    return out


def _median_ms(records, rids, since: str, until: str) -> Optional[float]:
    xs = [t[until] - t[since]
          for t in request_times(records, rids).values()
          if since in t and until in t]
    return statistics.median(xs) * 1e-3 if xs else None


def queue_wait_p50_ms(records: List[dict], rids) -> Optional[float]:
    """Median of admission less submission over ``rids``, in ms."""
    return _median_ms(records, rids, "submit", "admit")


def prefill_p50_ms(records: List[dict], rids) -> Optional[float]:
    """Median of first token less admission over ``rids``, in ms."""
    return _median_ms(records, rids, "admit", "first_token")


def prefill_row_use_pct(records: List[dict]) -> Optional[float]:
    """Rows that hold a prompt token over all rows the prefill chunks
    computed, in %."""
    calls = [r["attrs"] for r in records
             if r.get("type") == "span" and r["name"] == "call"
             and "rows" in r["attrs"]]
    rows = sum(c["rows"] for c in calls)
    if rows <= 0:
        return None
    return 100.0 * sum(c["rows_valid"] for c in calls) / rows
