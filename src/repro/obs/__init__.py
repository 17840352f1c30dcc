"""Serving-engine observability: two-clock tracing (engine ticks + wall
time), Chrome-trace export, the recompilation sentinel, log-bucketed
latency histograms, and the per-parameter-path traffic waterfall.

Everything here is PASSIVE instrumentation: with the tracer off the
engine's outputs and device-call count are bitwise unchanged, and with
it on no extra device work is issued (the zero-overhead contract the
chaos benchmark guards)."""

from .chrome import to_chrome_trace  # noqa: F401
from .histogram import LogHistogram  # noqa: F401
from .sentinel import RecompileError, RecompileSentinel  # noqa: F401
from .trace import (EVENT_NAMES, SPAN_NAMES, TRACE_VERSION,  # noqa: F401
                    TraceError, Tracer, load, validate)
from .waterfall import engine_waterfall, serving_cost_by_kind  # noqa: F401
