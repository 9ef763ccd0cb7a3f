"""Differential kernel-conformance suite: every Pallas impl vs its oracle.

Three layers, all driven off the runners in ``conformance_runners.py`` and
the shared case library in ``kernel_cases.py``:

1. **Completeness** — every pattern registered with a ``pallas`` impl in
   repro.core.dispatch must have conformance cases here; a new kernel that
   lands without them fails the suite by construction.
2. **Deterministic grid** — a hand-picked shape/stride/padding/act/dtype
   grid per kernel family (odd sizes, >128-lane channel counts, every
   supported act), wrapper output vs the bit-faithful quantized oracle,
   tolerances derived from the accumulator dtype.  Runs in every lane.
3. **Hypothesis fuzzing** — seeded randomized shapes/strides/acts over the
   same runners, in ``test_conformance_fuzz_*.py`` (two kernels a file, so
   that xdist's ``--dist loadfile`` spreads them over workers).

Fallback-guard cases assert that inputs a kernel declines (grouped weights,
exotic padding, degenerate outputs, mis-shaped residuals, unsupported pool
windows) still *dispatch* — they take the jnp fallback and match the
baseline, instead of crashing.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kernel_cases as kc
from conformance_runners import FUZZ_COVERED, FUZZ_FILES, RUNNERS
from repro.core import dispatch
from repro.kernels import ops, ref
from repro.models import cnn


def test_every_registered_pallas_impl_has_conformance_cases():
    """A kernel registered without conformance cases fails by construction."""
    registered = set(dispatch.registered_patterns("pallas"))
    assert registered, "pallas backend registered nothing?"
    missing = registered - set(RUNNERS)
    assert not missing, (
        f"registered pallas impls without conformance cases: {sorted(missing)}"
        " — add a runner to tests/conformance_runners.py::RUNNERS"
    )
    # every LM kernel has grid AND fuzz coverage, not just a runner
    gridded = {impl for impl, _ in GRID}
    lm_kernels = {"mac_matmul_int8", "residual_rmsnorm", "flash_attention",
                  "wkv_chunk"}
    assert lm_kernels <= gridded
    assert lm_kernels <= set(FUZZ_COVERED) <= set(RUNNERS)
    assert len(set(FUZZ_COVERED)) == len(FUZZ_COVERED)
    # every fuzz file is in the table, and every file the table names exists
    here = os.path.dirname(__file__)
    on_disk = {f for f in os.listdir(here)
               if f.startswith("test_conformance_fuzz_") and f.endswith(".py")}
    assert on_disk == set(FUZZ_FILES)


# ---------------------------------------------------------------------------
# deterministic grid (runs in every lane; defined once in kernel_cases.py so
# benchmarks/bench_ratio.py gates measured perf on exactly these shapes)
# ---------------------------------------------------------------------------

GRID = kc.GRID


@pytest.mark.parametrize(
    "idx,impl,case",
    [(i, impl, case) for i, (impl, case) in enumerate(GRID)],
    ids=[kc.case_id(impl, case) for impl, case in GRID],
)
def test_conformance_grid(idx, impl, case):
    RUNNERS[impl](seed=idx, **case)


# ---------------------------------------------------------------------------
# fallback guards: declined inputs dispatch to the baseline, never crash
# ---------------------------------------------------------------------------


def _assert_matches_baseline(got, want, exact=True):
    tol = ({"rtol": 1e-5, "atol": 1e-6} if exact
           else {"rtol": 5e-2, "atol": 5e-2})
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def guard_fused_conv_grouped():
    x, w, b, s, t = kc.conv_case(0, 10, 10, 4, 8, 3)
    w = w[:, :, :2, :]  # groups=2 weight shape
    got = ops._pallas_fused_conv(x, w, b, stride=1, padding="SAME", groups=2,
                                 act="relu", scale=s, shift=t)
    want = ref.fused_conv_ref(x, w, b, stride=1, padding="SAME", groups=2,
                              act="relu", scale=s, shift=t)
    _assert_matches_baseline(got, want)


def guard_fused_conv_exotic_padding():
    x, w, b, _, _ = kc.conv_case(1, 9, 9, 4, 6, 3)
    pad = ((2, 1), (0, 3))
    got = ops._pallas_fused_conv(x, w, b, stride=1, padding=pad, groups=1,
                                 act="none")
    want = ref.fused_conv_ref(x, w, b, stride=1, padding=pad, groups=1,
                              act="none")
    _assert_matches_baseline(got, want)


def guard_fused_conv_degenerate_empty():
    x = jnp.ones((1, 4, 4, 2))
    w = jnp.ones((6, 6, 2, 3))
    got = ops._pallas_fused_conv(x, w, None, stride=2, padding="VALID",
                                 groups=1, act="none")
    assert got.shape == (1, 0, 0, 3)


def guard_fused_conv_unsupported_act():
    x, w, b, _, _ = kc.conv_case(2, 9, 9, 4, 6, 3)
    got = ops._pallas_fused_conv(x, w, b, stride=1, padding="SAME", groups=1,
                                 act="silu")
    want = ref.fused_conv_ref(x, w, b, stride=1, padding="SAME", groups=1,
                              act="silu")
    _assert_matches_baseline(got, want)


def guard_fused_conv_broadcast_residual_falls_back():
    """A residual that is broadcast-compatible but not output-shaped can't
    tile into the kernel epilogue — the site must fall back to the baseline
    (which broadcasts it), not crash or mis-add."""
    x, w, b, _, _ = kc.conv_case(3, 9, 9, 4, 6, 3)
    res = jnp.full((x.shape[0], 1, 1, 6), 0.25)
    got = ops._pallas_fused_conv(x, w, b, stride=1, padding="SAME", groups=1,
                                 act="relu", residual=res)
    want = ref.fused_conv_ref(x, w, b, stride=1, padding="SAME", groups=1,
                              act="relu", residual=res)
    _assert_matches_baseline(got, want)


def guard_matmul_epilogue_broadcast_residual_falls_back():
    x, w, b, _ = kc.matmul_case(4, 12, 16, 8)
    res = jnp.full((1, 8), -0.5)
    got = ops._pallas_matmul_epilogue(x, w, b, act="relu", residual=res)
    want = ref.matmul_epilogue_ref(x, w, b, act="relu", residual=res)
    _assert_matches_baseline(got, want)


def guard_depthwise_grouped_not_depthwise():
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], (1, 10, 10, 8), jnp.float32)
    w = jax.random.normal(ks[1], (3, 3, 2, 8), jnp.float32)
    got = ops._pallas_depthwise_conv(x, w, None, stride=1, padding="SAME",
                                     act="relu")
    want = ref.fused_conv_ref(x, w, None, stride=1, padding="SAME", groups=4,
                              act="relu")
    _assert_matches_baseline(got, want)


def guard_depthwise_degenerate_empty():
    got = ops._pallas_depthwise_conv(jnp.ones((1, 2, 2, 4)),
                                     jnp.ones((3, 3, 1, 4)), None,
                                     stride=1, padding="VALID", act="none")
    assert got.shape == (1, 0, 0, 4)


def guard_sep_block_decomposes_on_exotic_padding():
    x, wd, wp, ds, dt, ps, pt = kc.sep_case(5, 9, 9, 6, 10)
    pad = ((1, 1), (1, 1))
    got = ops._pallas_sep_block(x, wd, wp, stride=1, padding=pad,
                                dw_scale=ds, dw_shift=dt, dw_act="relu",
                                pw_scale=ps, pw_shift=pt, pw_act="none")
    want = ref.sep_block_ref(x, wd, wp, stride=1, padding=pad, dw_scale=ds,
                             dw_shift=dt, dw_act="relu", pw_scale=ps,
                             pw_shift=pt, pw_act="none")
    _assert_matches_baseline(got, want, exact=False)


def guard_pool_unsupported_window():
    x = kc.pool_case(0, 12, 12, 6)
    for op, k, stride in [("max", 4, 2), ("avg", 3, 1), ("max", 2, 3)]:
        got = ops._pallas_pool(x, op=op, k=k, stride=stride)
        want = ref.pool_ref(x, op=op, k=k, stride=stride)
        _assert_matches_baseline(got, want)


def guard_pool_vmem_slab_cap():
    """A native-resolution f32 pool whose padded image slab exceeds the
    VMEM budget must fall back to the baseline (the slab would fail to
    compile on a real TPU), while the int8 form of the same extent — 4x
    smaller — still fits."""
    from repro.kernels import pooling as pk

    big = jax.ShapeDtypeStruct((1, 224, 224, 64), jnp.float32)
    assert not pk.fits_vmem(big, 2, 2, "max")
    assert not pk.fits_vmem(big, op="global_avg")
    assert pk.fits_vmem(jax.ShapeDtypeStruct((1, 224, 224, 64), jnp.int8),
                        2, 2, "max")
    assert pk.fits_vmem(jax.ShapeDtypeStruct((1, 64, 64, 64), jnp.float32),
                        2, 2, "max")
    # the oversized site dispatches through the fallback, bit-exact
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 224, 224, 8))
    got = ops._pallas_pool(x, op="max", k=2, stride=2)
    want = ref.pool_ref(x, op="max", k=2, stride=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def guard_pool_degenerate():
    # window larger than the image: empty output, like the baseline
    x = kc.pool_case(1, 2, 1, 3)
    got = ops._pallas_pool(x, op="max", k=3, stride=2)
    want = ref.pool_ref(x, op="max", k=3, stride=2)
    assert got.shape == want.shape and 0 in got.shape
    # empty batch dispatches too
    got = ops._pallas_pool(jnp.zeros((0, 8, 8, 4)), op="global_avg")
    assert got.shape == (0, 4)


def guard_matmul_epilogue_empty_gemm():
    x = jnp.zeros((0, 8))
    w = jnp.ones((8, 4))
    got = ops._pallas_matmul_epilogue(x, w, None, act="relu")
    assert got.shape == (0, 4)


GUARDS = [
    guard_fused_conv_grouped,
    guard_fused_conv_exotic_padding,
    guard_fused_conv_degenerate_empty,
    guard_fused_conv_unsupported_act,
    guard_fused_conv_broadcast_residual_falls_back,
    guard_matmul_epilogue_broadcast_residual_falls_back,
    guard_depthwise_grouped_not_depthwise,
    guard_depthwise_degenerate_empty,
    guard_sep_block_decomposes_on_exotic_padding,
    guard_pool_unsupported_window,
    guard_pool_vmem_slab_cap,
    guard_pool_degenerate,
    guard_matmul_epilogue_empty_gemm,
]


@pytest.mark.parametrize("guard", GUARDS, ids=lambda g: g.__name__)
def test_fallback_guards_dispatch_not_crash(guard):
    guard()


# ---------------------------------------------------------------------------
# model-level sanity: every model-emitted pooling form has a kernel case
# ---------------------------------------------------------------------------


def test_model_pool_forms_covered_by_kernel_fast_path(monkeypatch):
    """The pools the six CNNs actually emit (2/3-window stride-2 VALID +
    global-avg) are exactly the kernel fast path — none silently rides the
    fallback."""
    forms = set()
    orig = cnn._pool_ref

    def spying(x, *, op, k=2, stride=2):
        forms.add((op, k, stride))
        return orig(x, op=op, k=k, stride=stride)

    monkeypatch.setattr(cnn, "_pool_ref", spying)
    for name in cnn.CNN_MODELS:
        init, apply, in_shape = cnn.get_cnn(name)
        p = init(jax.random.PRNGKey(0))
        jax.eval_shape(lambda x: apply(p, x), jnp.zeros((1, *in_shape)))
    from repro.kernels import pooling as pk

    assert forms  # five of the six CNNs pool
    for op, k, stride in forms:
        if op == "global_avg":
            continue
        assert k in pk.SUPPORTED_WINDOWS and stride in pk.SUPPORTED_STRIDES, (
            f"model emits pool form ({op}, k={k}, stride={stride}) outside "
            "the kernel fast path"
        )
