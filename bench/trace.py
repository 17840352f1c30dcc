"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

Three kinds of interval are taken from the trace, all in seconds on the
host's clock, which the profiler puts the device's events on:

  * the harness's own host spans, ``bench.tick <n>``, ``bench.wait`` and
    ``bench.bookkeeping`` (``harness`` writes them with
    ``jax.profiler.TraceAnnotation``); together they tile the traced
    part of the serving loop;
  * device module executions (the ``XLA Modules`` line of a device
    plane): one per executable run;
  * device operations (the ``XLA Ops`` line): busy time is their union.

A module execution is a serving step when its name holds ``step_fn``
(the program's serving steps are jitted functions of that name). Each
is charged to the tick span it starts in, and a tick's steps take their
kinds, in order, from the harness's record of the calls that tick made.
A tick whose count of steps differs from its record is reported, and
its steps are left out. Each joint-kernel event inside a step keeps the
shape of its call, (rows, k, n), as its op text shows it.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass
from typing import Dict, List, Optional

STEP_MODULE = re.compile(r"step_fn")
#: the joint kernel's device events: Mosaic custom calls, whose op text on
#: a TPU v5e reads ``%_joint_sparse_matmul.56 = ... custom-call(...),
#: custom_call_target="tpu_custom_call"`` (the joint kernel is the only
#: Pallas kernel on the served path)
KERNEL_OP = re.compile(r"tpu_custom_call")
#: ops that contain other ops' events (a scan's loop): left out of the
#: breakdown, which would otherwise count their bodies twice
CONTAINER_OP = re.compile(r"^(while|conditional|call)$")
HOST_PREFIX = "bench."
CLOCK_SLACK_S = 0.002


@dataclass
class Span:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Parsed:
    host: List[Span]
    modules: List[Span]
    ops: List[Span]


def parse(profile, device: str = "/device:TPU:0") -> Parsed:
    """Host spans of the harness and one device's modules and ops, from a
    ``jax.profiler.ProfileData``."""
    host, modules, ops = [], [], []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append(Span(ev.name, ev.start_ns * 1e-9,
                                         (ev.start_ns + ev.duration_ns)
                                         * 1e-9))
        elif plane.name == device:
            for line in plane.lines:
                sink = {"XLA Modules": modules, "XLA Ops": ops}.get(line.name)
                if sink is None:
                    continue
                for ev in line.events:
                    sink.append(Span(ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9))
    for xs in (host, modules, ops):
        xs.sort(key=lambda s: s.start)
    return Parsed(host, modules, ops)


def load(path, device: str = "/device:TPU:0") -> Parsed:
    from jax.profiler import ProfileData
    return parse(ProfileData.from_file(str(path)), device)


# -- interval arithmetic ----------------------------------------------------

def union(spans, lo: float, hi: float) -> List[tuple]:
    """Merged (start, end) intervals of ``spans`` clipped to [lo, hi]."""
    out: List[list] = []
    for s in sorted(spans, key=lambda s: s.start):
        a, b = max(s.start, lo), min(s.end, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def covered(intervals, lo: float, hi: float) -> float:
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in intervals)


def gaps(intervals, lo: float, hi: float) -> List[tuple]:
    """The parts of [lo, hi] no interval covers."""
    out, t = [], lo
    for a, b in intervals:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


# -- the reduction ------------------------------------------------------------

@dataclass
class StepExec:
    kind: str           # "prefill" | "decode"
    tick: int
    span: Span
    kernel_s: float     # summed device time of the joint kernel's events
    calls: List[tuple]  # (rows, k, n, seconds) of each event whose shape reads


@dataclass
class Reduced:
    window: tuple                   # (start, end) of the traced loop
    busy_s: float
    ticks: Dict[int, Span]
    steps: List[StepExec]
    tick_busy_s: float
    tick_s: float
    idle_gaps: List[list]
    device_ops: List[list]
    unmatched: Dict[int, tuple]     # tick -> (its calls, its step count)


def _tick_of(name: str) -> Optional[int]:
    parts = name.split()
    if parts[0] == "bench.tick" and len(parts) == 2 and parts[1].isdigit():
        return int(parts[1])
    return None


def is_kernel(op: Span) -> bool:
    return bool(KERNEL_OP.search(op.name))


def op_label(op: Span) -> str:
    """A name to add device time up under: the HLO instruction's name
    without its instance number (``%fusion.66 = ...`` -> ``fusion``)."""
    m = re.match(r"%?([\w.-]+?)(\.\d+)*( =|$)", op.name)
    return m.group(1) if m else op.name


_OUT_SHAPE = re.compile(r"= \w+\[(\d+),(\d+)\]")
_OPERAND = re.compile(r"(bf16|f16|f32)\[(\d+),(\d+)\]")


def kernel_shape(op: Span) -> Optional[tuple]:
    """(rows, k, n) of a joint-kernel call from its op text: rows and n
    from the output, k from the activation operand, the first 2-D float
    operand with as many rows (``%_joint_sparse_matmul.61 =
    bf16[16,5632]... custom-call(s32[44,6] %a, bf16[16,2048] %x, ...)``
    -> (16, 2048, 5632))."""
    out = _OUT_SHAPE.search(op.name)
    if out is None or "custom-call(" not in op.name:
        return None
    rows, n = int(out.group(1)), int(out.group(2))
    args = op.name.split("custom-call(", 1)[1]
    for m in _OPERAND.finditer(args):
        if int(m.group(2)) == rows:
            return rows, int(m.group(3)), n
    return None


def reduce(parsed: Parsed, tick_calls: Dict[int, tuple]) -> Reduced:
    """``tick_calls`` maps a tick number to the step kinds the harness
    saw it run, in order, e.g. ("prefill", "decode")."""
    if not parsed.host:
        raise ValueError("no harness spans in the trace")
    lo = parsed.host[0].start
    hi = max(s.end for s in parsed.host)
    busy = union(parsed.ops, lo, hi)
    ticks = {t: s for s in parsed.host
             if (t := _tick_of(s.name)) is not None}
    tick_list = sorted(ticks.items(), key=lambda kv: kv[1].start)

    tick_starts = [s.start for _, s in tick_list]

    def tick_at(t: float) -> Optional[int]:
        # the device's clock is aligned to the host's only to within a
        # fraction of a millisecond: a step that starts just before its
        # tick span on the device's clock still belongs to that tick
        # (the previous tick's steps all end before its span does)
        i = bisect.bisect_right(tick_starts, t + CLOCK_SLACK_S) - 1
        if i >= 0 and t < tick_list[i][1].end + CLOCK_SLACK_S:
            return tick_list[i][0]
        return None

    by_tick: Dict[int, List[Span]] = {}
    for mod in parsed.modules:
        if STEP_MODULE.search(mod.name):
            n = tick_at(mod.start)
            if n is not None:
                by_tick.setdefault(n, []).append(mod)
    for mods in by_tick.values():
        mods.sort(key=lambda m: m.start)
    kernels = [op for op in parsed.ops if is_kernel(op)]
    k_starts = [op.start for op in kernels]
    steps, unmatched = [], {}
    for n in ticks:
        mods = by_tick.get(n, [])
        record = tuple(tick_calls.get(n, ()))
        if len(mods) != len(record):
            unmatched[n] = (record, len(mods))
            continue
        for kind, mod in zip(record, mods):
            i = bisect.bisect_left(k_starts, mod.start)
            j = bisect.bisect_left(k_starts, mod.end)
            k_s, calls = 0.0, []
            for op in kernels[i:j]:
                dur = min(op.end, mod.end) - op.start
                k_s += dur
                shape = kernel_shape(op)
                if shape is not None:
                    calls.append(shape + (dur,))
            steps.append(StepExec(kind, n, mod, k_s, calls))
    steps.sort(key=lambda st: st.span.start)

    tick_s = sum(s.dur for s in ticks.values())
    b_starts = [a for a, _ in busy]
    tick_busy = 0.0
    for s in ticks.values():
        i = max(0, bisect.bisect_right(b_starts, s.start) - 1)
        while i < len(busy) and busy[i][0] < s.end:
            tick_busy += max(0.0, min(busy[i][1], s.end)
                             - max(busy[i][0], s.start))
            i += 1

    h_starts = [s.start for s in parsed.host]
    idle = []
    for a, b in gaps(busy, lo, hi):
        mid = 0.5 * (a + b)
        i = bisect.bisect_right(h_starts, mid) - 1
        owner = (parsed.host[i].name.split()[0]
                 if i >= 0 and mid < parsed.host[i].end else "outside spans")
        idle.append([owner, b - a])
    idle.sort(key=lambda x: -x[1])

    per_op: Dict[str, float] = {}
    for op in parsed.ops:
        d = min(op.end, hi) - max(op.start, lo)
        label = op_label(op)
        if d > 0 and not CONTAINER_OP.match(label):
            per_op[label] = per_op.get(label, 0.0) + d
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return Reduced(window=(lo, hi), busy_s=covered(busy, lo, hi),
                   ticks=ticks, steps=steps, tick_busy_s=tick_busy,
                   tick_s=tick_s, idle_gaps=idle[:10],
                   device_ops=[[k, v] for k, v in top], unmatched=unmatched)
