"""add2i kernel: fused residual-add + RMSNorm.

The paper's ``add2i`` fuses two consecutive immediate adds (two register
updates, one slot).  TPU analogue: the residual update and the normalized
stream are produced in one VMEM pass — two tensor "registers" written, one
HBM round-trip instead of three (add out, norm in, norm out).

Ladder rung: ``add2i`` v2 on the CNN and RMSNorm-bearing LM ladders; the
``rnn_lm`` ladder skips it (RWKV is a LayerNorm model — no fused residual+
RMSNorm epilogue sites), see ``core.extensions.CLASS_LADDERS``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common
from repro.kernels.common import pad_to

BR = 256  # rows per block (at most)
# elements per (rows, D) buffer: four such arrays, double-buffered, stay
# within half of v5e's 16 MiB default scoped VMEM in f32
_BLOCK_ELEMS = 1 << 18


def _kernel(res_ref, x_ref, scale_ref, newres_ref, normed_ref, *, eps):
    r = res_ref[...].astype(jnp.float32) + x_ref[...].astype(jnp.float32)
    newres_ref[...] = r.astype(newres_ref.dtype)
    var = jnp.mean(jnp.square(r), axis=-1, keepdims=True)
    y = r * jax.lax.rsqrt(var + eps) * scale_ref[...].astype(jnp.float32)
    normed_ref[...] = y.astype(normed_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps",))
def residual_rmsnorm(res, x, scale, eps=1e-6):
    """res, x: (..., D); scale: (D,). Returns (res + x, rmsnorm(res + x))."""
    orig_shape = res.shape
    D = orig_shape[-1]
    r2 = res.reshape(-1, D)
    x2 = x.reshape(-1, D)
    br = max(8, min(BR, _BLOCK_ELEMS // D // 8 * 8))
    r2, R = pad_to(r2, 0, br)
    x2, _ = pad_to(x2, 0, br)
    Rp = r2.shape[0]
    newres, normed = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(Rp // br,),
        in_specs=[
            pl.BlockSpec((br, D), lambda r: (r, 0)),
            pl.BlockSpec((br, D), lambda r: (r, 0)),
            pl.BlockSpec((1, D), lambda r: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, D), lambda r: (r, 0)),
            pl.BlockSpec((br, D), lambda r: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, D), res.dtype),
            jax.ShapeDtypeStruct((Rp, D), res.dtype),
        ],
        name="residual_rmsnorm",
        interpret=common.interpret_mode(),
    )(r2, x2, scale.reshape(1, D))
    return newres[:R].reshape(orig_shape), normed[:R].reshape(orig_shape)
