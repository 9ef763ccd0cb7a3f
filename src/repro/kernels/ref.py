"""Pure-jnp oracles for every kernel (the per-kernel allclose references)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import _chunked_attention, _rms_norm_ref
from repro.models.rwkv import _wkv_chunk_ref

_ACTS = {
    "none": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "relu6": lambda x: jnp.clip(x, 0.0, 6.0),
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
}


def mac_matmul_int8_ref(x_int8, w_int8, scale, out_dtype=jnp.float32):
    acc = x_int8.astype(jnp.int32) @ w_int8.astype(jnp.int32)
    return (acc.astype(jnp.float32) * scale.reshape(1, -1)).astype(out_dtype)


def matmul_epilogue_ref(x, w, b=None, act="none", scale=None, shift=None,
                        residual=None, pre_scale=None, pre_shift=None):
    """GEMM oracle: the pre-activation prologue ``relu(x*pre_scale +
    pre_shift)`` where either is given, then x @ w + bias + folded-BN
    affine (+ residual) + act in f32."""
    xf = x.astype(jnp.float32)
    if pre_scale is not None or pre_shift is not None:
        xf = preact_ref(xf, pre_scale, pre_shift)
    y = jnp.einsum("...k,kn->...n", xf, w.astype(jnp.float32))
    if b is not None:
        y = y + b.astype(jnp.float32)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    if shift is not None:
        y = y + shift.astype(jnp.float32)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    return _ACTS[act](y).astype(x.dtype)


def preact_ref(x, pre_scale=None, pre_shift=None):
    """The pre-activation prologue: a per-channel affine (a folded
    batchnorm) and a relu on the GEMM's input."""
    if pre_scale is not None:
        x = x * pre_scale.astype(x.dtype)
    if pre_shift is not None:
        x = x + pre_shift.astype(x.dtype)
    return jnp.maximum(x, 0.0)


def fused_conv_ref(x, w, b=None, *, stride=1, padding="SAME", groups=1,
                   act="none", scale=None, shift=None, residual=None):
    """Fused-conv oracle: conv + bias + folded-BN affine (+ residual-add
    accumulate, the acc_mac epilogue) + act in f32."""
    dn = jax.lax.conv_dimension_numbers(
        x.shape, w.shape, ("NHWC", "HWIO", "NHWC")
    )
    y = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32), (stride, stride),
        padding, dimension_numbers=dn, feature_group_count=groups,
    )
    if b is not None:
        y = y + b.astype(jnp.float32)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    if shift is not None:
        y = y + shift.astype(jnp.float32)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    return _ACTS[act](y).astype(x.dtype)


def pool_ref(x, *, op, k=2, stride=2):
    """Pooling oracle: windowed max/avg (VALID) or the global-avg reduction,
    accumulated in f32.  Integer-typed avg pools return f32 (an integer mean
    is not an integer); max pools keep the input dtype."""
    xf = x.astype(jnp.float32)
    avg_dtype = (jnp.float32 if jnp.issubdtype(x.dtype, jnp.integer)
                 else x.dtype)
    if op == "global_avg":
        return jnp.mean(xf, axis=(1, 2)).astype(avg_dtype)
    if op == "max":
        y = jax.lax.reduce_window(
            xf, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, stride, stride, 1),
            "VALID",
        )
        return y.astype(x.dtype)
    if op == "avg":
        y = jax.lax.reduce_window(
            xf, 0.0, jax.lax.add, (1, k, k, 1), (1, stride, stride, 1),
            "VALID",
        ) / float(k * k)
        return y.astype(avg_dtype)
    raise ValueError(f"unknown pool op {op!r}")


def depthwise_conv_ref(x, w, b=None, *, stride=1, padding="SAME",
                       act="none", scale=None, shift=None):
    """Depthwise-conv oracle (groups == channels); w is (KH, KW, 1, C) HWIO
    or the squeezed (KH, KW, C) tap stack the kernel takes."""
    if w.ndim == 3:
        w = w[:, :, None, :]
    return fused_conv_ref(x, w, b, stride=stride, padding=padding,
                          groups=x.shape[-1], act=act, scale=scale,
                          shift=shift)


def sep_block_ref(x, w_dw, w_pw, *, stride=1, padding="SAME", dw_scale=None,
                  dw_shift=None, dw_act="relu", pw_bias=None, pw_scale=None,
                  pw_shift=None, pw_act="none"):
    """Separable-block oracle: depthwise (+epilogue) -> 1x1 pointwise
    (+epilogue), the unfused two-pass form of sep_block_int8."""
    y = depthwise_conv_ref(x, w_dw, None, stride=stride, padding=padding,
                           act=dw_act, scale=dw_scale, shift=dw_shift)
    return fused_conv_ref(y, w_pw, pw_bias, stride=1, padding="SAME",
                          groups=1, act=pw_act, scale=pw_scale,
                          shift=pw_shift)


def residual_rmsnorm_ref(res, x, scale, eps=1e-6):
    new_res = (res.astype(jnp.float32) + x.astype(jnp.float32)).astype(res.dtype)
    return new_res, _rms_norm_ref(new_res, scale, eps)


def flash_attention_ref(q, k, v, causal=True):
    """q,k,v: (BH, S, d) -> exact softmax attention in f32."""
    BH, Sq, d = q.shape
    Skv = k.shape[1]
    s = jnp.einsum(
        "bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / math.sqrt(d)
    if causal:
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Skv)[None, :]
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def wkv_ref_sequential(r, k, v, lw, u, s0):
    """Token-by-token WKV recurrence (the ground-truth oracle)."""
    B, S, H, N = r.shape

    def step(s, inputs):
        rt, kt, vt, lwt = inputs  # (B,H,N)
        kv = jnp.einsum("bhi,bhj->bhij", kt, vt)
        o = jnp.einsum("bhi,bhij->bhj", rt, s + u[None, :, :, None] * kv)
        s = jnp.exp(lwt)[..., None] * s + kv
        return s, o

    xs = tuple(t.transpose(1, 0, 2, 3) for t in (r, k, v, lw))
    s_final, outs = jax.lax.scan(step, s0, xs)
    return outs.transpose(1, 0, 2, 3), s_final


# the chunked-jnp form (itself validated against wkv_ref_sequential)
wkv_chunk_ref = _wkv_chunk_ref


def chunked_attention_ref(q, k, v, **kw):
    out, _lse = _chunked_attention(q, k, v, **kw)
    return out
