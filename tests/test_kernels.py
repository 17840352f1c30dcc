"""The DBMU bit-serial datapath must equal the integer matmul EXACTLY for
any FTA-compliant weights (hardware equivalence of the whole compression
pipeline). The joint kernel's tests are in test_joint_sparse.py.
"""

import numpy as np
import pytest

try:  # optional: only the seeded property test below needs hypothesis
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import dyadic, fta
from repro.kernels import ops, ref


# ------------------------------------------------------------- DBMU --------

def test_dbmu_bit_true_equivalence():
    """Bit-serial AND + CSD adder tree == integer matmul, exactly."""
    rng = np.random.default_rng(4)
    q = rng.integers(-127, 128, (64, 128), dtype=np.int32)
    mask = np.ones_like(q)
    q_fta, _ = fta.fta_quantize(q, mask)
    packed = dyadic.pack_terms(q_fta)
    x = rng.integers(-127, 128, (16, 64), dtype=np.int32)
    got = np.asarray(ops.dbmu_reference_check(x, packed))
    want = ref.dbmu_matmul_ref(x, packed)
    np.testing.assert_array_equal(got, want.astype(np.int32))


if HAVE_HYPOTHESIS:
    @given(st.integers(min_value=0, max_value=2 ** 31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_dbmu_bit_true_random_seeds(seed):
        rng = np.random.default_rng(seed)
        q = rng.integers(-127, 128, (8, 128), dtype=np.int32)
        q_fta, _ = fta.fta_quantize(q, np.ones_like(q))
        packed = dyadic.pack_terms(q_fta)
        x = rng.integers(-127, 128, (8, 8), dtype=np.int32)
        got = np.asarray(ops.dbmu_reference_check(x, packed))
        np.testing.assert_array_equal(got, ref.dbmu_matmul_ref(x, packed))
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_dbmu_bit_true_random_seeds():
        pass
