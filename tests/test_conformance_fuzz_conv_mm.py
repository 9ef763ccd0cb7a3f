"""Conformance fuzz layer: fused_conv and matmul_epilogue against their oracles.

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
    _conv_params = st.tuples(
        st.integers(0, 10_000),                      # seed
        st.integers(5, 18), st.integers(5, 18),      # h, w
        st.integers(1, 12), st.integers(1, 12),      # cin, cout
        st.sampled_from([1, 2, 3, 5]),               # k
        st.sampled_from([1, 2]),                     # stride
        st.sampled_from(["SAME", "VALID"]),
        st.sampled_from(["none", "relu", "relu6"]),
        st.booleans(),                               # residual
    )
    _mm_params = st.tuples(
        st.integers(0, 10_000), st.integers(1, 40), st.integers(1, 70),
        st.integers(1, 40), st.sampled_from(["none", "relu", "silu"]),
        st.booleans(),
    )

    def _fuzz_conv(p):
        seed, h, w, cin, cout, k, stride, padding, act, res = p
        if k > min(h, w):  # degenerate handled by the guard tests
            padding = "SAME"
        cr.run_fused_conv(seed, h, w, cin, cout, k, stride, padding, act,
                          res)

    def _fuzz_mm(p):
        seed, m, k, n, act, res = p
        cr.run_matmul_epilogue(seed, m, k, n, act, residual=res)

    _FUZZERS = {"fused_conv": (_fuzz_conv, _conv_params),
                "matmul_epilogue": (_fuzz_mm, _mm_params)}
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
