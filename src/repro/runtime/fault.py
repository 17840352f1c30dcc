"""Fault tolerance & elasticity harness.

On a real 1000+-node fleet, failures surface as (a) raised exceptions from
collectives / host runtime, (b) missing heartbeats, (c) stragglers. The
framework's contract:

  * every state mutation flows through the checkpoint manager (atomic,
    async) — the blast radius of any failure is <= `every` steps;
  * `run_resilient` wraps the step loop: on failure it restores the last
    checkpoint, optionally REBUILDS the mesh from the surviving device set
    (elastic re-mesh: drop a data-parallel slice, keep model-parallel
    groups intact), re-lowers the step, and continues;
  * `StragglerMonitor` tracks per-step wall time and flags outliers
    (slow hosts) for the scheduler to evict — mitigation on TPU pods is
    eviction + re-mesh, not work stealing, because lockstep collectives
    make one slow chip everyone's problem.

The container is single-process, so failures are injected in tests via
the `failure_hook`; the control flow is identical on real fleets.

The SERVING engine has its own request-granular fault layer
(serving.faults + serving.engine: per-slot quarantine and
recovery-by-replay instead of checkpoint restore). It keeps no
straggler count: a tick that carries a prefill chunk is slower than a
decode-only tick by design, so a median threshold would flag every one.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np


@dataclass
class StragglerMonitor:
    window: int = 50
    threshold: float = 2.0          # x median => straggler
    warmup: int = 10                # samples before flagging starts
    times: List[float] = field(default_factory=list)
    flagged: int = 0                # total stragglers seen (monotonic)

    def record(self, dt: float) -> bool:
        """Returns True if this step is a straggler outlier."""
        self.times.append(dt)
        if len(self.times) > self.window:
            self.times.pop(0)
        if len(self.times) < self.warmup:
            return False
        med = float(np.median(self.times))
        if dt > self.threshold * med:
            self.flagged += 1
            return True
        return False

    @property
    def p50(self) -> float:
        return float(np.median(self.times)) if self.times else 0.0


@dataclass
class ElasticMeshPlan:
    """How to shrink the mesh when a slice dies: drop along the data axis
    (model-parallel groups must stay complete — a lost TP peer loses the
    weights' shards; a lost DP slice only loses throughput)."""
    data_parallel: int
    model_parallel: int

    def degrade(self) -> "ElasticMeshPlan":
        if self.data_parallel <= 1:
            raise RuntimeError("cannot degrade below 1 data-parallel slice")
        return ElasticMeshPlan(self.data_parallel // 2, self.model_parallel)


def run_resilient(train_loop: Callable[[int, Optional[ElasticMeshPlan]], int],
                  *, total_steps: int, restore_step: Callable[[], int],
                  max_failures: int = 5,
                  plan: Optional[ElasticMeshPlan] = None,
                  on_failure: Optional[Callable[[BaseException], None]] = None
                  ) -> int:
    """Drive `train_loop(start_step, plan)` to completion with restarts.

    train_loop runs until done or raises; restore_step() returns the step
    to resume from (last durable checkpoint). Each failure optionally
    degrades the mesh plan (elastic downscale).
    """
    failures = 0
    step = restore_step()
    while step < total_steps:
        try:
            step = train_loop(step, plan)
        except Exception as e:   # noqa: BLE001 — any step failure
            failures += 1
            if on_failure:
                on_failure(e)
            if failures > max_failures:
                raise RuntimeError(
                    f"exceeded {max_failures} failures; last: {e}") from e
            step = restore_step()
            if plan is not None and failures >= 2:
                plan = plan.degrade()   # repeated failures: shed capacity
    return step
