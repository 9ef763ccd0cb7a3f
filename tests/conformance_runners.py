"""Conformance runners: one per registered pallas pattern, and the seeded
hypothesis driver of the fuzz layer.

Shared by the deterministic grid (``test_conformance.py``) and the fuzz
layer (``test_conformance_fuzz_*.py``).  The fuzz layer is spread over four
files, two kernels each, because xdist's ``--dist loadfile`` runs each file
whole on one worker: one file holding all eight kernels set the suite's
wall clock.  ``FUZZ_FILES`` says which file fuzzes which kernel; each file
checks its own entry at import and the completeness test checks the table.
"""
import contextlib
import zlib

import jax
import jax.numpy as jnp
import numpy as np

import kernel_cases as kc
from repro.kernels import ops, ref


def run_mac_matmul(seed=0, m=64, k=96, n=32):
    from repro.kernels.mac_matmul import mac_matmul_int8

    x, w, s = kc.mac_case(seed, m, k, n)
    got = mac_matmul_int8(x, w, s)
    want = ref.mac_matmul_int8_ref(x, w, s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **kc.tol_from_acc(jnp.int32, k))


def run_fused_conv(seed=0, h=13, w_sp=11, cin=5, cout=9, k=3, stride=1,
                   padding="SAME", act="relu", residual=False):
    x, w, b, s, t = kc.conv_case(seed, h, w_sp, cin, cout, k)
    res = None
    if residual:
        want_shape = jax.eval_shape(
            lambda a, b: ref.fused_conv_ref(a, b, None, stride=stride,
                                            padding=padding), x, w,
        ).shape
        res = jax.random.normal(jax.random.PRNGKey(seed + 1), want_shape)
    got = ops._pallas_fused_conv(x, w, b, stride=stride, padding=padding,
                                 groups=1, act=act, scale=s, shift=t,
                                 residual=res)
    want = kc.quant_conv_oracle(x, w, b, s, t, stride=stride,
                                padding=padding, act=act, residual=res)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **kc.tol_from_acc(jnp.int32, k * k * cin))


def run_depthwise(seed=0, h=13, w_sp=11, c=5, stride=1, padding="SAME",
                  act="relu"):
    x, w, b, s, t = kc.dw_case(seed, h, w_sp, c)
    got = ops._pallas_depthwise_conv(x, w, b, stride=stride, padding=padding,
                                     act=act, scale=s, shift=t)
    want = kc.quant_dw_oracle(x, w, b, s, t, stride=stride, padding=padding,
                              act=act)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **kc.tol_from_acc(jnp.int32, 9))


def run_sep_block(seed=0, h=13, w_sp=11, c=5, cout=9, stride=1,
                  dw_act="relu", pw_act="none"):
    x, wd, wp, ds, dt, ps, pt = kc.sep_case(seed, h, w_sp, c, cout)
    got = ops._pallas_sep_block(x, wd, wp, stride=stride, dw_scale=ds,
                                dw_shift=dt, dw_act=dw_act, pw_scale=ps,
                                pw_shift=pt, pw_act=pw_act)
    want = kc.quant_sep_oracle(x, wd, wp, ds, dt, ps, pt, stride=stride,
                               dw_act=dw_act, pw_act=pw_act)
    assert got.shape == want.shape
    # dw stage: int32 acc; pw stage: f32 acc over C — 2x slack for the chain
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **kc.tol_from_acc(jnp.int32, c, slack=2.0))


def run_matmul_epilogue(seed=0, m=37, k=64, n=48, act="relu",
                        dtype=jnp.float32, residual=False, affine=True):
    x, w, b, r = kc.matmul_case(seed, m, k, n, dtype)
    s = 0.5 + jax.random.uniform(jax.random.PRNGKey(seed + 2), (n,))
    got = ops._pallas_matmul_epilogue(
        x, w, b, act=act, scale=s if affine else None, shift=None,
        residual=r if residual else None,
    )
    want = ref.matmul_epilogue_ref(
        x, w, b, act=act, scale=s if affine else None, shift=None,
        residual=r if residual else None,
    )
    assert got.shape == want.shape
    # f32 accumulator, but a low-precision operand dtype floors the tol
    tol = kc.tol_from_acc(dtype, k)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def run_pool(seed=0, h=13, w_sp=11, c=5, op="max", k=2, stride=2,
             dtype=jnp.float32):
    x = kc.pool_case(seed, h, w_sp, c, dtype)
    got = ops._pallas_pool(x, op=op, k=k, stride=stride)
    want = ref.pool_ref(x, op=op, k=k, stride=stride)
    assert got.shape == want.shape and got.dtype == want.dtype
    window = h * w_sp if op == "global_avg" else k * k
    if op == "max":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **kc.tol_from_acc(jnp.float32, window))


def run_residual_rmsnorm(seed=0, rows=33, d=96):
    res, x, scale = kc.rmsnorm_case(seed, rows, d)
    new_res, normed = ops._pallas_residual_rmsnorm(res, x, scale)
    want_res, want_norm = ref.residual_rmsnorm_ref(res, x, scale)
    tol = kc.tol_from_acc(jnp.float32, d)
    np.testing.assert_allclose(np.asarray(new_res), np.asarray(want_res),
                               **tol)
    np.testing.assert_allclose(np.asarray(normed), np.asarray(want_norm),
                               **tol)


def run_flash_attention(seed=0, b=1, sq=64, kheads=2, g=2, dh=16,
                        int8_kv=False):
    from repro.models.layers import _flash_attention_ref

    q, k, v, k_s, v_s = kc.attn_case(seed, b, sq, kheads, g, dh,
                                     int8_kv=int8_kv)
    got = ops._pallas_flash_attention(q, k, v, causal=True,
                                      k_scale=k_s, v_scale=v_s)
    want = _flash_attention_ref(q, k, v, causal=True,
                                k_scale=k_s, v_scale=v_s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **kc.tol_from_acc(jnp.float32, sq, slack=4.0))


def run_wkv_chunk(seed=0, b=1, s=32, heads=2, n=8, chunk=16):
    r, k, v, lw, u, s0 = kc.wkv_case(seed, b, s, heads, n)
    got, got_state = ops._pallas_wkv_chunk(r, k, v, lw, u, s0, chunk)
    want, want_state = ref.wkv_ref_sequential(r, k, v, lw, u, s0)
    tol = kc.tol_from_acc(jnp.float32, s, slack=8.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)
    np.testing.assert_allclose(np.asarray(got_state), np.asarray(want_state),
                               **tol)


# every registered pallas pattern -> its conformance runner
RUNNERS = {
    "mac_matmul_int8": run_mac_matmul,
    "fused_conv": run_fused_conv,
    "depthwise_conv": run_depthwise,
    "sep_block": run_sep_block,
    "matmul_epilogue": run_matmul_epilogue,
    "pool": run_pool,
    "residual_rmsnorm": run_residual_rmsnorm,
    "flash_attention": run_flash_attention,
    "wkv_chunk": run_wkv_chunk,
}


# ---------------------------------------------------------------------------
# the fuzz layer: which file fuzzes which pattern, and the seeded driver
# ---------------------------------------------------------------------------

# all four LM kernels included; only sep_block rides solely the
# deterministic grid + guards
FUZZ_FILES = {
    "test_conformance_fuzz_conv_mm.py": ("fused_conv", "matmul_epilogue"),
    "test_conformance_fuzz_dw_pool.py": ("depthwise_conv", "pool"),
    "test_conformance_fuzz_attn_mac.py": ("flash_attention",
                                          "mac_matmul_int8"),
    "test_conformance_fuzz_wkv_rms.py": ("wkv_chunk", "residual_rmsnorm"),
}
FUZZ_COVERED = tuple(k for pair in FUZZ_FILES.values() for k in pair)

# examples per (pattern, lane): the fast lane's budget, and the full grid
# the CI slow lane runs
FUZZ_BUDGETS = {"fast": 8, "full": 60}


def fuzz_seed(pattern: str, lane: str) -> int:
    """The fixed hypothesis seed of one (pattern, lane) case: the lanes
    draw different examples, every run of a tree the same ones."""
    return zlib.crc32(f"{pattern}/{lane}".encode())


@contextlib.contextmanager
def _no_local_constants():
    """Hypothesis mixes integer constants from every loaded non-test module
    into its draws, so the examples of a seed would depend on what ran
    earlier in the worker; hold that pool empty while a case draws."""
    from unittest import mock

    from hypothesis.internal.conjecture import providers

    if not hasattr(providers, "_get_local_constants"):  # older hypothesis
        yield
        return
    empty = providers.Constants()
    providers.CONSTANTS_CACHE.cache.clear()
    try:
        with mock.patch.object(providers, "_get_local_constants",
                               lambda: empty):
            yield
    finally:
        providers.CONSTANTS_CACHE.cache.clear()


def fuzz(pattern: str, lane: str, adapter, params) -> None:
    """Run ``adapter`` on ``FUZZ_BUDGETS[lane]`` examples of the strategy
    ``params``, drawn from ``fuzz_seed(pattern, lane)`` with no example
    database, so no local store decides what runs."""
    from hypothesis import given, seed, settings

    @seed(fuzz_seed(pattern, lane))
    @settings(max_examples=FUZZ_BUDGETS[lane], deadline=None,
              database=None)
    @given(params)
    def case(p):
        adapter(p)

    with _no_local_constants():
        case()
