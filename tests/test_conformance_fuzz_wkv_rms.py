"""Conformance fuzz layer: wkv_chunk and residual_rmsnorm against their oracles.

Seeded hypothesis sweeps over the runners in ``conformance_runners.py``:
``FUZZ_BUDGETS`` examples per kernel in the fast lane and, under
``-m slow``, the full grid, each case drawn from its own fixed seed with no
example database.  Skipped cleanly where hypothesis isn't installed.
"""
import os

import pytest

import conformance_runners as cr

try:
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # container without hypothesis: fuzz layer skips
    HAVE_HYPOTHESIS = False

KERNELS = cr.FUZZ_FILES[os.path.basename(__file__)]

if HAVE_HYPOTHESIS:
    _wkv_params = st.tuples(
        st.integers(0, 10_000), st.sampled_from([1, 2]),
        st.integers(1, 3), st.sampled_from([4, 8, 16]),  # heads, n
        st.sampled_from([4, 8, 16]), st.integers(1, 4),  # chunk, n_chunks
    )
    _rms_params = st.tuples(
        st.integers(0, 10_000), st.integers(1, 140), st.integers(8, 300),
    )

    def _fuzz_wkv(p):
        seed, b, heads, n, chunk, nc = p
        cr.run_wkv_chunk(seed, b, chunk * nc, heads, n, chunk)

    def _fuzz_rmsnorm(p):
        cr.run_residual_rmsnorm(*p)

    _FUZZERS = {"wkv_chunk": (_fuzz_wkv, _wkv_params),
                "residual_rmsnorm": (_fuzz_rmsnorm, _rms_params)}
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
