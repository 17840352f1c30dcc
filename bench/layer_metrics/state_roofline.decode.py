"""Share of its roofline the SSM state traffic reaches inside decode
steps: the least time to read and write every slot's SSM state (float32,
as the program keeps it) and conv window (the configuration's dtype)
once at the chip's HBM peak, per decode step, over the decode steps'
device time outside the joint kernel. A model with no SSM state, or a
trace with no decode step, reads nothing."""

import jax.numpy as jnp

#: bytes of one element of the SSM state, which the program keeps in f32
STATE_BYTES = 4


def state_bytes(m: dict) -> float:
    """Bytes of every slot's SSM state and conv window, all layers."""
    di = m["expand"] * m["hidden_size"]
    n = m["state_size"]
    state = di * n * STATE_BYTES
    conv = ((m["conv_kernel"] - 1) * (di + 2 * n)
            * jnp.dtype(m["dtype"]).itemsize)
    return float(m["num_hidden_layers"] * m["n_slots"] * (state + conv))


def read(ctx):
    m = ctx["model"]
    if m.get("family") != "ssm":
        return None
    steps = [s for s in ctx["reduced"].steps if s.kind == "decode"]
    outside = sum(s.span.dur - s.kernel_s for s in steps)
    if not steps or outside <= 0:
        return None
    least = len(steps) * 2.0 * state_bytes(m) / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / outside
