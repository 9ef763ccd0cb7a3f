"""Conformance fuzz layer: depthwise_conv and pool against their oracles.

Seeded hypothesis sweeps over the runners in ``conformance_runners.py``:
``FUZZ_BUDGETS`` examples per kernel in the fast lane and, under
``-m slow``, the full grid, each case drawn from its own fixed seed with no
example database.  Skipped cleanly where hypothesis isn't installed.
"""
import os

import jax.numpy as jnp
import pytest

import conformance_runners as cr

try:
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # container without hypothesis: fuzz layer skips
    HAVE_HYPOTHESIS = False

KERNELS = cr.FUZZ_FILES[os.path.basename(__file__)]

if HAVE_HYPOTHESIS:
    _dw_params = st.tuples(
        st.integers(0, 10_000), st.integers(5, 16), st.integers(5, 16),
        st.integers(1, 12), st.sampled_from([1, 2]),
        st.sampled_from(["SAME", "VALID"]),
        st.sampled_from(["none", "relu", "relu6"]),
    )
    _pool_params = st.tuples(
        st.integers(0, 10_000), st.integers(2, 20), st.integers(2, 20),
        st.integers(1, 12),
        st.sampled_from(["max", "avg", "global_avg"]),
        st.sampled_from([2, 3]), st.sampled_from([1, 2, 3]),
        st.sampled_from([jnp.float32, jnp.int8]),
    )

    def _fuzz_dw(p):
        seed, h, w, c, stride, padding, act = p
        cr.run_depthwise(seed, h, w, c, stride, padding, act)

    def _fuzz_pool(p):
        seed, h, w, c, op, k, stride, dtype = p
        cr.run_pool(seed, h, w, c, op, k, stride, dtype)

    _FUZZERS = {"depthwise_conv": (_fuzz_dw, _dw_params),
                "pool": (_fuzz_pool, _pool_params)}
    assert set(_FUZZERS) == set(KERNELS)
    _IDS = [_FUZZERS[k][0].__name__ for k in KERNELS]

    # the long cases first: xdist hands a worker its next file once two
    # of this file's cases are left, and those should be the short ones
    @pytest.mark.slow
    @pytest.mark.parametrize("kernel", KERNELS, ids=_IDS)
    def test_conformance_fuzz_full(kernel):
        cr.fuzz(kernel, "full", *_FUZZERS[kernel])

    @pytest.mark.parametrize("kernel", KERNELS, ids=_IDS)
    def test_conformance_fuzz_fast(kernel):
        cr.fuzz(kernel, "fast", *_FUZZERS[kernel])
else:  # keep the skip visible in every lane's report
    @pytest.mark.skip(reason="hypothesis not installed; fuzz layer runs in CI")
    def test_conformance_fuzz_fast():
        pass
