"""Kernel micro-benchmarks (interpret mode — correctness + derived
traffic/compression stats; wall time on CPU is NOT a TPU metric, the
derived column reports the structural savings the kernel realizes).

``--smoke`` runs tiny shapes only — the CI guard that the kernels still
compile and match their references without TPU hardware.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.configs.paper_cnns import joint_bench_shapes
from repro.kernels import ops, ref
from .common import emit, timed


_tile_mask = ops.random_tile_mask        # shared with tests: one semantics


def _joint_cases(rows, smoke: bool):
    """dense / value-only / bit-only / joint weight traffic on the paper's
    layer shapes (largest conv per CNN + AlexNet fc), plus a joint-vs-
    dense-reference correctness probe."""
    rng = np.random.default_rng(7)
    shapes = ([("smoke", 128, 256, 256)] if smoke
              else joint_bench_shapes(max_m=256))
    sparsity = 0.5
    for name, M, K, N in shapes:
        w = rng.laplace(0, 0.02, (K, N)).astype(np.float32)
        mask = _tile_mask(rng, K, N, sparsity)
        survival = mask.mean()

        dense_bytes = 2 * K * N                       # bf16 baseline
        value_bytes = int(2 * K * N * survival)       # compacted bf16
        bit_bytes = K * N                             # dense int8
        packed = ops.pack_joint_sparse(w, mask)
        joint_bytes = ops.joint_storage_bytes(packed)

        x = jnp.asarray(rng.normal(0, 1, (M, K)), jnp.float32)
        (y,), us = timed(lambda: (ops.joint_dense(x, packed),))
        want = ref.joint_packed_ref(x, packed)
        err = float(jnp.max(jnp.abs(y - want))
                    / jnp.maximum(jnp.max(jnp.abs(want)), 1e-6))
        # the CI guard: a kernel-vs-reference mismatch must FAIL the run
        # even under `python -O` (which strips bare asserts)
        if not err < 1e-4:
            raise RuntimeError(f"joint kernel diverged on {name}: "
                               f"rel_err={err}")
        rows.append((f"kernel.joint.{name}", us,
                     f"bytes dense={dense_bytes} value={value_bytes} "
                     f"bit={bit_bytes} joint={joint_bytes} "
                     f"({joint_bytes/dense_bytes:.2f}x) rel_err={err:.1e}"))


def _stacked_case(rows):
    """Uniform-MAXB stacked pack driven through a layer scan — the smoke
    guard for the stacked serving path: every per-layer slice must match
    the dense reference of ITS layer, and balanced pruning must produce
    zero padded slots."""
    rng = np.random.default_rng(11)
    L, M, K, N = 3, 8, 256, 256
    ws = rng.laplace(0, 0.02, (L, K, N)).astype(np.float32)
    packed = ops.pack_joint_sparse_stacked(ws, value_sparsity=0.5)
    nb = np.asarray(packed.nblocks)
    if not (nb == packed.maxb).all():
        raise RuntimeError(f"stacked pack has padded slots: nblocks={nb} "
                           f"vs MAXB={packed.maxb}")
    dense = ops.unpack_joint_sparse_stacked(packed)
    x = jnp.asarray(rng.normal(0, 1, (M, K)), jnp.float32)

    def body(carry, slices):
        wb, idx, sc, nbl = slices
        layer = ops.JointPacked(wb, idx, sc, nbl, packed.k, packed.n,
                                packed.k_pad)
        return carry, ops.joint_dense(carry, layer)

    import jax
    xs = (packed.w_blocks, packed.idx, packed.scales, packed.nblocks)
    (ys,), us = timed(lambda: (jax.lax.scan(body, x, xs)[1],))
    err = 0.0
    for l in range(L):
        want = x @ jnp.asarray(dense[l])
        err = max(err, float(jnp.max(jnp.abs(ys[l] - want))
                             / jnp.maximum(jnp.max(jnp.abs(want)), 1e-6)))
    if not err < 1e-4:
        raise RuntimeError(f"stacked joint scan diverged: rel_err={err}")
    stored = ops.joint_storage_bytes(packed)
    dense_bytes = 2 * L * K * N
    rows.append(("kernel.joint.stacked_scan", us,
                 f"L={L} MAXB={packed.maxb} bytes={stored} vs "
                 f"dense_bf16={dense_bytes} ({stored/dense_bytes:.2f}x) "
                 f"rel_err={err:.1e}"))


def _grouped_case(rows):
    """Grouped (L, E) expert pack driven through a layer scan with a
    per-expert dispatch loop — the MoE serving layout smoke guard: every
    (layer, expert) slice must match the dense reference of ITS slice,
    and balanced pruning must leave zero padded slots group-wide."""
    import jax
    rng = np.random.default_rng(17)
    L, E, M, K, N = 2, 4, 8, 256, 256
    ws = rng.laplace(0, 0.02, (L, E, K, N)).astype(np.float32)
    packed = ops.pack_joint_sparse_grouped(ws, value_sparsity=0.5)
    nb = np.asarray(packed.nblocks)
    if not (nb == packed.maxb).all():
        raise RuntimeError(f"grouped pack has padded slots: nblocks={nb} "
                           f"vs MAXB={packed.maxb}")
    dense = ops.unpack_joint_sparse_grouped(packed)
    x = jnp.asarray(rng.normal(0, 1, (M, K)), jnp.float32)

    def body(carry, slices):
        wb, idx, sc, nbl = slices               # (E, ...) per layer
        ys = [ops.joint_dense(
            carry, ops.JointPacked(wb[e], idx[e], sc[e], nbl[e],
                                   packed.k, packed.n, packed.k_pad))
            for e in range(E)]
        return carry, jnp.stack(ys)

    xs = (packed.w_blocks, packed.idx, packed.scales, packed.nblocks)
    (ys,), us = timed(lambda: (jax.lax.scan(body, x, xs)[1],))
    err = 0.0
    for l in range(L):
        for e in range(E):
            want = x @ jnp.asarray(dense[l, e])
            err = max(err, float(jnp.max(jnp.abs(ys[l, e] - want))
                                 / jnp.maximum(jnp.max(jnp.abs(want)),
                                               1e-6)))
    if not err < 1e-4:
        raise RuntimeError(f"grouped joint scan diverged: rel_err={err}")
    stored = ops.joint_storage_bytes(packed)
    dense_bytes = 2 * L * E * K * N
    rows.append(("kernel.joint.grouped_experts", us,
                 f"L={L} E={E} MAXB={packed.maxb} bytes={stored} vs "
                 f"dense_bf16={dense_bytes} ({stored/dense_bytes:.2f}x) "
                 f"rel_err={err:.1e}"))


def _ssm_parallel_prefill_case(rows):
    """Stacked-SSM parallel-form prefill driven through the Pallas joint
    path: one decode_chunk with the default parallel SSD chunk
    (models.ssm.prefill_ssm_parallel — in/out projections read once per
    chunk) vs the exact per-token recurrence, both over the SAME stacked
    joint tables. Guards the tolerance contract
    (models.ssm.PARALLEL_PREFILL_ATOL) on the kernel path itself."""
    import jax
    from repro.configs import get_config
    from repro.models import decode_chunk, init_cache, init_params
    from repro.models.ssm import PARALLEL_PREFILL_ATOL
    from repro.sparsity.sparse_linear import build_stacked_tables

    cfg = get_config("mamba2-1.3b", reduced=True, dbpim_mode="joint")
    cfg = cfg.scaled(dtype="float32", dbpim_value_sparsity=0.5)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tables = build_stacked_tables(params, cfg, bk=32, bn=32)
    rng = np.random.default_rng(5)
    B, C = 2, 8
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, C)), jnp.int32)
    nv = jnp.full((B,), C, jnp.int32)
    cache = init_cache(cfg, B, 32)
    cache["pos"] = jnp.zeros((B,), jnp.int32)

    (lp, cache_p), us = timed(
        lambda: decode_chunk(params, cache, toks, nv, cfg, tables=tables))
    le, cache_e = decode_chunk(params, cache, toks, nv,
                               cfg.scaled(prefill_exact=True),
                               tables=tables)
    atol = PARALLEL_PREFILL_ATOL[cfg.dtype]
    dl = float(jnp.max(jnp.abs(lp.astype(jnp.float32)
                               - le.astype(jnp.float32))))
    ds = float(jnp.max(jnp.abs(cache_p["ssm"]["state"]
                               - cache_e["ssm"]["state"])))
    if not (dl <= atol and ds <= atol):
        raise RuntimeError(
            f"stacked-SSM parallel prefill diverged from the exact chunk: "
            f"max|dlogit|={dl:.2e} max|dstate|={ds:.2e} > atol={atol}")
    rows.append(("kernel.ssm_parallel_prefill", us,
                 f"C={C} proj_reads 1 vs {C} (parallel vs exact) "
                 f"max|dlogit|={dl:.1e} max|dstate|={ds:.1e} atol={atol}"))


def run(smoke: bool = False):
    rows = []
    rng = np.random.default_rng(0)

    # joint value x bit: the paper's headline configuration, beside the
    # dense / value-only / bit-only weight bytes of the same shapes
    _joint_cases(rows, smoke)

    # stacked joint pack driven through a scan — the serving layout
    _stacked_case(rows)

    # grouped (layer x expert) pack — the MoE serving layout
    _grouped_case(rows)

    # parallel-form SSM prefill through the stacked Pallas path
    _ssm_parallel_prefill_case(rows)

    # dbmu bit-true sim
    from repro.core import fta as fta_mod, dyadic
    q = rng.integers(-127, 128, (128, 128), dtype=np.int32)
    q_fta, _ = fta_mod.fta_quantize(q, np.ones_like(q))
    packed = dyadic.pack_terms(q_fta)
    xi = rng.integers(-127, 128, (16, 128), dtype=np.int32)
    got, us = timed(lambda: np.asarray(ops.dbmu_reference_check(xi, packed)))
    exact = bool((got == ref.dbmu_matmul_ref(xi, packed)).all())
    if not exact:
        raise RuntimeError("DBMU bit-true equivalence broken")
    rows.append(("kernel.dbmu_sim", us, f"bit_true_exact={exact}"))
    return emit(rows)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, interpret mode — CI kernel guard")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(smoke=args.smoke)
