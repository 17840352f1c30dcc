"""Runtime flag for the Pallas kernels: compiled on TPU, interpreted
elsewhere, with an environment override."""

from __future__ import annotations

import os

#: env override for the interpret default: "1"/"true" forces interpret
#: mode everywhere, "0"/"false" forces compiled kernels even off-TPU.
INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")


def default_interpret() -> bool:
    """Backend-aware interpret default for every Pallas kernel.

    TPU backends compile the kernels; everything else (CPU CI, GPU dev
    boxes) interprets them, since Mosaic only lowers for TPU. The
    ``REPRO_PALLAS_INTERPRET`` env var overrides in either direction.
    """
    env = os.environ.get(INTERPRET_ENV)
    if env is not None:
        v = env.strip().lower()
        if v in _TRUTHY:
            return True
        if v in _FALSY:
            return False
        raise ValueError(
            f"{INTERPRET_ENV}={env!r} not understood; use one of "
            f"{_TRUTHY + _FALSY}")
    import jax
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    """None -> backend default; everything else passes through as bool."""
    return default_interpret() if interpret is None else bool(interpret)
