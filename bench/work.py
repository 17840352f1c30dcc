"""The work each call needs, from the configuration's shapes and its value
sparsity, never from the implementation: a later kernel is read against
the same operations and bytes.

``joint_call`` is the least one joint-sparse projection call has to do:
read the activations once, the stored INT8 blocks once, their index
table and per-column scales, and write the output; multiply-add every
stored weight with every row. ``token_flops`` is the model's own work
for one served token: the surviving projection weights, the output
head, and attention over the token's real context (Mamba2: the state
update and read-out instead).
"""

from __future__ import annotations

from dataclasses import dataclass

from bench.weights import kept_k_tiles, projections, ssm_dims


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def roofline_s(self, peak) -> tuple:
        """(least seconds on the chip, which bound sets it)."""
        t_c = self.flops / peak["bf16_flops_per_s"]
        t_m = self.bytes / peak["hbm_bytes_per_s"]
        return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def joint_call(m_rows: int, k: int, n: int, vs: float, tile,
               x_bytes: int = 2, out_bytes: int = 2,
               w_bytes: int = 1) -> Work:
    """One (m_rows, k) @ (k, n) call of the joint kernel."""
    bk, bn, kt, keep = kept_k_tiles(k, n, vs, tile)
    nt = -(-n // bn)
    stored = nt * keep * bk * bn
    return Work(flops=2.0 * m_rows * stored,
                bytes=float(m_rows * kt * bk * x_bytes + stored * w_bytes
                            + nt * keep * 4 + nt * bn * 4
                            + m_rows * nt * bn * out_bytes))


def surviving_weights(m: dict, vs: float, tile) -> int:
    """Projection weights per layer that survive value pruning (within
    the logical shapes; padding is not work)."""
    total = 0
    for k, n in projections(m).values():
        bk, _, kt, keep = kept_k_tiles(k, n, vs, tile)
        total += min(keep * bk, k) * n
    return total


def token_flops(m: dict, vs: float, tile, context: int) -> float:
    """Model FLOPs of one token that attends ``context`` positions
    (itself included): 2 per surviving projection weight and per output
    head weight; attention 4 * d per position per layer (scores and the
    weighted sum); Mamba2 5 per state element per layer (decay, input
    outer product and add, read-out) and 2 * conv taps per channel."""
    d, L, V = m["hidden_size"], m["num_hidden_layers"], m["vocab_size"]
    per_layer = 2.0 * surviving_weights(m, vs, tile)
    if m["family"] == "dense":
        per_layer += 4.0 * d * context
    else:
        di, nh, n = ssm_dims(m)
        per_layer += 5.0 * di * n + 2.0 * m["conv_kernel"] * (di + 2 * n)
    return L * per_layer + 2.0 * d * V


def calls_roofline_s(calls, m: dict, peak: dict) -> float:
    """Least seconds of the joint calls ``calls``, each (rows, k, n, ...)
    as the trace shows it, each bounded by its own compute or memory
    time at the configuration's value sparsity."""
    vs, tile = m["value_sparsity"], tuple(m["tile"])
    return sum(joint_call(rows, k, n, vs, tile).roofline_s(peak)[0]
               for rows, k, n, *_ in calls)
