"""Recompilation sentinel: the fixed-shape no-recompile contract, guarded.

The serving engine's whole performance story rests on every request
flowing through a handful of compiled-once executables (engine.py module
docstring). That property used to be folklore — a shape regression (a
scalar position sneaking back in, a cache dtype flip between calls)
would silently recompile every tick and only surface as a wall-clock
anomaly. The sentinel turns it into a hard invariant: each jitted step
is registered under a ``(call_kind, arch[, rows])`` key with a compile
budget (default: ONE), and ``check()`` — called once per engine tick —
raises ``RecompileError`` the tick the budget is exceeded, naming the
offender and its compile count.

Counting uses the jit cache size (``PjitFunction._cache_size``), i.e.
the number of distinct (shape, dtype, sharding) signatures the
executable has compiled for — exactly "how many times did XLA compile
this step". On a jax build without the introspection hook the sentinel
degrades to inert (counts report -1, ``check`` passes) rather than
taking the engine down; ``available`` says which mode it is in.
"""

from __future__ import annotations

from typing import Dict


class RecompileError(RuntimeError):
    """A registered step compiled more often than its budget — the
    fixed-shape serving contract is broken."""


def _cache_size(fn) -> int:
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return -1
    return int(probe())


class RecompileSentinel:
    """Registry of jitted step functions + per-key compile budgets."""

    def __init__(self, budget: int = 1):
        self.budget = budget
        self._fns: Dict[str, object] = {}

    @staticmethod
    def key(call_kind: str, arch: str, rows: int = 0) -> str:
        """Registry key; ``rows`` names one row bucket of a step that
        compiles an executable per bucket (the prefill chunk)."""
        return f"{call_kind}@{arch}" + (f"/{rows}" if rows else "")

    def register(self, key: str, jitted):
        """Track ``jitted`` (a jax.jit result) under ``key``. Re-registering
        a key replaces the function (engines rebuild steps on reconfig)."""
        self._fns[key] = jitted

    @property
    def available(self) -> bool:
        """False when the jax build exposes no jit-cache introspection —
        the sentinel is then inert, not wrong."""
        return all(_cache_size(f) >= 0 for f in self._fns.values())

    def counts(self) -> Dict[str, int]:
        """Compile count per registered key (-1: introspection missing)."""
        return {k: _cache_size(f) for k, f in self._fns.items()}

    def check(self):
        """Raise RecompileError if any registered step exceeded its
        budget. Cheap (one int read per step), intended per-tick."""
        over = {k: n for k, n in self.counts().items() if n > self.budget}
        if over:
            raise RecompileError(
                f"step(s) recompiled past the budget of {self.budget} "
                f"compile(s): " +
                ", ".join(f"{k} compiled {n}x" for k, n in over.items()) +
                " — a fixed-shape serving step changed its input "
                "signature between calls")
