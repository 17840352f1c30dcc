"""The work model against the kernel's own cost estimate and a hand count,
and the weights against the program's packing."""

import numpy as np
import pytest

from bench import weights, work
from bench.tests.conftest import TINY

TILE = (128, 128)
VS = 0.6
#: every projection shape of both configurations at published widths
SHAPES = [(2048, 2048), (2048, 5632), (5632, 2048),      # stablelm-1.6b
          (2048, 8512), (4096, 2048)]                     # mamba2-1.3b


@pytest.mark.parametrize("m_rows", [16, 128, 2048])
@pytest.mark.parametrize("k,n", SHAPES)
def test_joint_call_matches_kernel_cost_estimate(k, n, m_rows):
    from repro.kernels.joint_sparse_matmul import _cost
    bk, bn, kt, keep = weights.kept_k_tiles(k, n, VS, TILE)
    nt = -(-n // bn)
    est = _cost(m_rows, kt * bk, nt, keep, bk, bn, 2, 2, 1)
    got = work.joint_call(m_rows, k, n, VS, TILE)
    assert got.flops == est.flops
    assert got.bytes == est.bytes_accessed


@pytest.mark.parametrize("k,n", [(2048, 2048), (5632, 2048), (4096, 2048)])
def test_kept_tiles_match_the_programs_pack(k, n):
    """The program's column-balanced pack of one bench-made layer keeps
    exactly the tiles ``kept_k_tiles`` counts, and reproduces the weights
    bit for bit (the compression is exact by construction)."""
    import jax
    from repro.kernels import ops
    w = np.asarray(jax.jit(lambda key: weights._grid_projection(
        key, k, n, VS, TILE))(jax.random.PRNGKey(3)), np.float32)
    packed = ops.pack_joint_sparse_stacked(w[None], value_sparsity=VS)
    _, _, _, keep = weights.kept_k_tiles(k, n, VS, TILE)
    assert packed.maxb == keep
    back = ops.unpack_joint_sparse_stacked(packed)[0]
    np.testing.assert_array_equal(back, w)


def test_two_term_values_are_the_fta_threshold_two_set():
    from repro.core.csd import INT8_MIN, PHI_TABLE
    ref = np.arange(-127, 128)[PHI_TABLE[np.arange(-127, 128) - INT8_MIN]
                               == 2]
    np.testing.assert_array_equal(weights.TWO_TERM, ref)


def _tiny(name):
    import json
    from bench.tests.conftest import ROOT
    arch, sizes = TINY[name]
    cfg_file = {"tiny-lm": "stablelm-1.6b.json",
                "tiny-ssm": "mamba2-1.3b.json"}[name]
    m = json.loads((ROOT / "bench" / "configs" / cfg_file).read_text())
    m.update(sizes)
    return m


def test_token_flops_dense_by_hand():
    """tiny-lm: d 64, 4 heads, d_ff 128, 2 layers, vocab 256. Tiles clamp
    to (64, 64) / (64, 128) / (128, 64): one K-tile each, none pruned."""
    m = _tiny("tiny-lm")
    proj = 4 * 64 * 64 + 3 * 64 * 128          # all weights survive
    ctx = 10
    want = 2 * (2 * proj + 4 * 64 * ctx) + 2 * 64 * 256
    assert work.token_flops(m, VS, TILE, ctx) == want


def test_token_flops_ssm_by_hand():
    """tiny-ssm: d 64, expand 2 (d_inner 128), state 16, head 16 (8
    heads), conv 4, 2 layers, vocab 256."""
    m = _tiny("tiny-ssm")
    n_in = 2 * 128 + 2 * 16 + 8                 # z, x, B, C, dt
    proj = 64 * n_in + 128 * 64
    per_layer = 2 * proj + 5 * 128 * 16 + 2 * 4 * (128 + 2 * 16)
    want = 2 * per_layer + 2 * 64 * 256
    assert work.token_flops(m, VS, TILE, 999) == want


def test_roofline_picks_the_larger_bound():
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert work.Work(2e12, 1e6).roofline_s(peak) == (2.0, "compute")
    assert work.Work(1e9, 3e9).roofline_s(peak) == (3.0, "memory")
