"""Conformance fuzz layer: flash_attention and mac_matmul_int8 against their oracles.

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
    _attn_params = st.tuples(
        st.integers(0, 10_000), st.sampled_from([1, 2]),
        st.sampled_from([16, 33, 64, 130]),            # sq (crosses bq=128)
        st.integers(1, 3), st.integers(1, 3),          # kv heads, group size
        st.sampled_from([8, 16, 32]),                  # dh
        st.booleans(),                                 # int8-KV path
    )
    _mac_params = st.tuples(
        st.integers(0, 10_000), st.integers(1, 150), st.integers(1, 300),
        st.integers(1, 150),
    )

    def _fuzz_attn(p):
        seed, b, sq, kheads, g, dh, int8_kv = p
        cr.run_flash_attention(seed, b, sq, kheads, g, dh, int8_kv=int8_kv)

    def _fuzz_mac(p):
        cr.run_mac_matmul(*p)

    _FUZZERS = {"flash_attention": (_fuzz_attn, _attn_params),
                "mac_matmul_int8": (_fuzz_mac, _mac_params)}
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
