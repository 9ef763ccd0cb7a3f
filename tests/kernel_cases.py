"""Shared kernel-conformance cases: builders, quant mirrors, tolerances.

Single source of truth for the differential suite (conformance_runners.py,
test_conformance.py and test_conformance_fuzz_*.py) and
the per-kernel test files (test_fused_conv.py, test_depthwise.py), so nobody
hand-rolls a slightly-different int8 quantization mirror or tolerance again.

Tolerances are *derived from the accumulator dtype*: an int32 MAC
accumulator makes the integer math exact, so the only error source is the
f32 epilogue (dequant/bias/act) — a fixed small tolerance; an f32
accumulator's error grows with the reduction length, so the tolerance
scales with ``k_reduce * eps``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref


def tol_from_acc(acc_dtype, k_reduce: int = 128, slack: float = 1.0) -> dict:
    """kwargs for ``np.testing.assert_allclose`` given the kernel's
    accumulator (or lowest-precision operand) dtype and reduction length."""
    if jnp.issubdtype(jnp.dtype(acc_dtype), jnp.integer):
        # integer MAC is exact; error comes only from the f32 epilogue
        return {"rtol": 1e-3 * slack, "atol": 1e-3 * slack}
    # accumulation-order slack grows with the reduction length (in f32
    # units); a low-precision operand dtype floors it at its own eps
    eps32 = float(jnp.finfo(jnp.float32).eps)
    eps = float(jnp.finfo(acc_dtype).eps)
    t = max(max(32, k_reduce) * eps32 * 8, eps * 4, 1e-5) * slack
    return {"rtol": t, "atol": t}


def quantize(a, axes):
    """Dequantized int8 mirror of the ops.py wrappers' symmetric
    quantization (``axes=None``: per-tensor; a tuple: per-channel)."""
    s = jnp.maximum(jnp.max(jnp.abs(a.astype(jnp.float32)), axis=axes),
                    1e-8) / 127.0
    return jnp.clip(jnp.round(a / s), -127, 127) * s


# ---------------------------------------------------------------------------
# case builders (one per kernel family)
# ---------------------------------------------------------------------------


def conv_case(seed, h, w_sp, cin, cout, k, batch=2):
    """(x, w, b, scale, shift) for a fused_conv / conv-epilogue case."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (batch, h, w_sp, cin), jnp.float32)
    w = jax.random.normal(ks[1], (k, k, cin, cout), jnp.float32)
    w = w / np.sqrt(k * k * cin)
    b = jax.random.normal(ks[2], (cout,)) * 0.1
    s = 0.5 + jax.random.uniform(ks[3], (cout,))
    t = jax.random.normal(ks[4], (cout,)) * 0.1
    return x, w, b, s, t


def dw_case(seed, h, w_sp, c, k=3, batch=2):
    """(x, w, b, scale, shift) for a depthwise case; w is HWIO (k, k, 1, c)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (batch, h, w_sp, c), jnp.float32)
    w = jax.random.normal(ks[1], (k, k, 1, c), jnp.float32) / float(k)
    b = jax.random.normal(ks[2], (c,)) * 0.1
    s = 0.5 + jax.random.uniform(ks[3], (c,))
    t = jax.random.normal(ks[4], (c,)) * 0.1
    return x, w, b, s, t


def sep_case(seed, h, w_sp, c, cout, batch=2):
    """(x, w_dw, w_pw, dw_scale, dw_shift, pw_scale, pw_shift)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (batch, h, w_sp, c), jnp.float32)
    wd = jax.random.normal(ks[1], (3, 3, 1, c), jnp.float32) / 3.0
    wp = jax.random.normal(ks[2], (1, 1, c, cout), jnp.float32) / np.sqrt(c)
    ds = 0.5 + jax.random.uniform(ks[3], (c,))
    dt = jax.random.normal(ks[4], (c,)) * 0.1
    ps = 0.5 + jax.random.uniform(ks[5], (cout,))
    pt = jax.random.normal(ks[6], (cout,)) * 0.1
    return x, wd, wp, ds, dt, ps, pt


def matmul_case(seed, m, k, n, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = (jax.random.normal(ks[0], (m, k)) * 0.5).astype(dtype)
    w = (jax.random.normal(ks[1], (k, n)) * 0.1).astype(dtype)
    b = (jax.random.normal(ks[2], (n,)) * 0.1).astype(dtype)
    r = jax.random.normal(ks[3], (m, n)).astype(dtype)
    return x, w, b, r


def pool_case(seed, h, w_sp, c, dtype=jnp.float32, batch=2):
    key = jax.random.PRNGKey(seed)
    if jnp.issubdtype(dtype, jnp.integer):
        return jax.random.randint(key, (batch, h, w_sp, c), -127, 128, dtype)
    return jax.random.normal(key, (batch, h, w_sp, c), dtype)


# --- LM-kernel cases (mac / add2i / zol rungs of the LM class ladders) ---


def mac_case(seed, m, k, n):
    """(x_int8, w_int8, scale) for a mac_matmul_int8 (int8 GEMM) case."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.randint(ks[0], (m, k), -127, 128, jnp.int8)
    w = jax.random.randint(ks[1], (k, n), -127, 128, jnp.int8)
    s = jax.random.uniform(ks[2], (n,), jnp.float32) * 0.02
    return x, w, s


def rmsnorm_case(seed, rows, d):
    """(res, x, scale) for a fused residual+RMSNorm epilogue case."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    res = jax.random.normal(ks[0], (rows, d))
    x = jax.random.normal(ks[1], (rows, d))
    scale = 0.5 + jax.random.uniform(ks[2], (d,))
    return res, x, scale


def attn_case(seed, b, sq, kheads, g, dh, skv=None, int8_kv=False):
    """(q, k, v, k_scale, v_scale): q grouped (B,Sq,K,G,dh); with
    ``int8_kv`` the KV comes back as int8 codes + per-(position, head)
    f32 scale planes — the serving tier's quantized-cache layout."""
    skv = skv or sq
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, sq, kheads, g, dh))
    k = jax.random.normal(ks[1], (b, skv, kheads, dh))
    v = jax.random.normal(ks[2], (b, skv, kheads, dh))
    if not int8_kv:
        return q, k, v, None, None
    from repro.models.layers import quantize_kv_int8

    kq, k_s = quantize_kv_int8(k)
    vq, v_s = quantize_kv_int8(v)
    return q, kq, vq, k_s, v_s


def wkv_case(seed, b, s, heads, n):
    """(r, k, v, lw, u, s0) for a chunked WKV recurrence case; ``lw`` is a
    strictly-negative log-decay, as the model's low-rank tanh path emits."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    r, k, v = (jax.random.normal(ks[i], (b, s, heads, n)) * 0.3
               for i in range(3))
    lw = -jnp.exp(jax.random.normal(ks[3], (b, s, heads, n)) * 0.3)
    u = jax.random.normal(ks[4], (heads, n)) * 0.3
    s0 = jnp.zeros((b, heads, n, n))
    return r, k, v, lw, u, s0


# ---------------------------------------------------------------------------
# quantized oracles (bit-faithful to the wrappers' on-the-fly quantization)
# ---------------------------------------------------------------------------


def quant_conv_oracle(x, w, b, s, t, *, stride, padding, act, residual=None):
    """Mirror ops._pallas_fused_conv's int8 quantization, then run the float
    oracle on the dequantized operands — bit-faithful to the kernel up to
    f32 conv accumulation order."""
    return ref.fused_conv_ref(
        quantize(x, None), quantize(w, (0, 1, 2)), b, stride=stride,
        padding=padding, groups=1, act=act, scale=s, shift=t,
        residual=residual,
    )


def quant_dw_oracle(x, w, b, s, t, *, stride, padding, act):
    """Mirror ops._pallas_depthwise_conv's quantization through the float
    depthwise oracle."""
    return ref.depthwise_conv_ref(
        quantize(x, None), quantize(w, (0, 1, 2)), b, stride=stride,
        padding=padding, act=act, scale=s, shift=t,
    )


def quant_sep_oracle(x, wd, wp, ds, dt, ps, pt, *, stride, dw_act, pw_act,
                     padding="SAME"):
    """Mirror ops._pallas_sep_block's quantization through the two-stage
    float oracle."""
    return ref.sep_block_ref(
        quantize(x, None), quantize(wd, (0, 1, 2)), quantize(wp, (0, 1, 2)),
        stride=stride, padding=padding, dw_scale=ds, dw_shift=dt,
        dw_act=dw_act, pw_scale=ps, pw_shift=pt, pw_act=pw_act,
    )


# ---------------------------------------------------------------------------
# the deterministic conformance grid: (impl, runner kwargs) per case.
# Shared by test_conformance.py (differential assertions) and
# benchmarks/bench_ratio.py (measured pallas-vs-ref ratio rows) so the
# perf gate covers exactly the shapes the correctness suite covers.
# ---------------------------------------------------------------------------

GRID = [
    ("mac_matmul_int8", dict(m=130, k=257, n=140)),
    ("mac_matmul_int8", dict(m=64, k=96, n=32)),
    # odd spatial/channel sizes, both paddings/strides, every epilogue act,
    # the residual epilogue, and multi-tile Cin/Cout (> the 128 block)
    ("fused_conv", dict(stride=1, padding="SAME", act="none")),
    ("fused_conv", dict(stride=2, padding="VALID", act="relu")),
    ("fused_conv", dict(stride=2, padding="SAME", act="relu6")),
    ("fused_conv", dict(stride=1, padding="VALID", act="relu",
                        residual=True)),
    ("fused_conv", dict(stride=2, padding="SAME", act="relu",
                        residual=True)),
    ("fused_conv", dict(h=8, w_sp=9, cin=130, cout=140, stride=2,
                        act="relu")),
    ("depthwise_conv", dict(stride=1, padding="SAME", act="none")),
    ("depthwise_conv", dict(stride=2, padding="VALID", act="relu")),
    ("depthwise_conv", dict(h=10, w_sp=9, c=130, stride=2, act="relu6")),
    ("sep_block", dict(stride=1, dw_act="relu", pw_act="relu")),
    ("sep_block", dict(stride=2, dw_act="relu6", pw_act="none")),
    ("sep_block", dict(h=8, w_sp=9, c=130, cout=140, stride=2)),
    ("matmul_epilogue", dict(act="silu")),
    ("matmul_epilogue", dict(act="gelu", dtype=jnp.bfloat16)),
    ("matmul_epilogue", dict(m=130, k=257, n=140, act="relu",
                             residual=True)),
    ("matmul_epilogue", dict(act="none", residual=True, affine=False)),
    ("pool", dict(op="max", k=2)),
    ("pool", dict(op="max", k=3)),
    ("pool", dict(op="avg", k=2)),
    ("pool", dict(op="avg", k=3)),
    ("pool", dict(op="max", k=3, dtype=jnp.int8)),
    ("pool", dict(op="avg", k=2, dtype=jnp.int8)),
    ("pool", dict(op="global_avg")),
    ("pool", dict(op="global_avg", dtype=jnp.int8)),
    ("pool", dict(h=16, w_sp=16, c=130, op="max", k=2)),
    # LM-kernel grid (the LM class ladders' mac / add2i / zol rungs):
    # decode-step GEMM (m=1), multi-tile / odd shapes, multi-block q,
    # grouped-query layouts, the int8-KV dequant path, and multi-chunk
    # vs single-chunk WKV scans
    ("mac_matmul_int8", dict(m=1, k=256, n=128)),
    ("residual_rmsnorm", dict()),
    ("residual_rmsnorm", dict(rows=130, d=257)),
    ("flash_attention", dict()),
    ("flash_attention", dict(sq=200, dh=32)),
    ("flash_attention", dict(b=2, kheads=1, g=4, dh=8)),
    ("flash_attention", dict(int8_kv=True)),
    ("flash_attention", dict(sq=130, kheads=3, g=1, int8_kv=True)),
    ("wkv_chunk", dict()),
    ("wkv_chunk", dict(s=64, chunk=16, heads=3, n=16)),
    ("wkv_chunk", dict(b=2, s=48, chunk=48)),
]


def case_id(impl: str, case: dict) -> str:
    """Stable human-readable id for one grid case (pytest ids and the
    bench_ratio row names use the same spelling)."""
    if not case:
        return impl
    parts = "-".join(f"{k}{getattr(v, '__name__', v)}"
                     for k, v in case.items())
    return f"{impl}-{parts}"
