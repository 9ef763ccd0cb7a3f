"""fusedmac kernel: GEMM + bias + activation epilogue in one VMEM pass.

The paper's ``fusedmac`` folds the mac *and* its bookkeeping (two addi) into
one instruction; on TPU the analogue folds the GEMM's elementwise epilogue
(bias add + nonlinearity) into the kernel so the GEMM output never round-trips
through HBM before activation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common
from repro.kernels.common import pad_to

BM, BN, BK = 128, 128, 128

_ACTS = {
    "none": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "relu6": lambda x: jnp.clip(x, 0.0, 6.0),
    "silu": lambda x: x * jax.nn.sigmoid(x),
    "gelu": jax.nn.gelu,
}


def _kernel(x_ref, w_ref, es_ref, eb_ref, *refs, act, has_residual):
    if has_residual:
        r_ref, o_ref, acc_ref = refs
    else:
        (o_ref, acc_ref), r_ref = refs, None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        # bias + folded-BN affine pre-folded into one (scale, bias) pair;
        # the acc_mac residual-add accumulates in-register before the act
        y = acc_ref[...] * es_ref[...] + eb_ref[...]
        if has_residual:
            y = y + r_ref[...].astype(jnp.float32)
        o_ref[...] = _ACTS[act](y).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "bm", "bn", "bk"))
def matmul_epilogue(x, w, b=None, act="none", scale=None, shift=None,
                    residual=None, *, bm=BM, bn=BN, bk=BK):
    """x: (..., K); w: (K, N); b/scale/shift: (N,) or None; residual:
    optional (..., N) skip tensor ->
    ``act((x@w + b)*scale + shift [+ residual])``.  The whole epilogue folds
    into one per-column (scale, bias) pair — ``act(acc*scale + (b*scale +
    shift))`` — applied in-register; the residual-add (the ``acc_mac``
    extension) rides the same epilogue, so a skip connection costs one VMEM
    read instead of an HBM round-trip of the GEMM output.

    ``bm``/``bn``/``bk`` are the autotunable M/N/K tile sizes (defaults:
    the MXU-native 128s; the dispatch wrapper overrides them from the
    active tuning table)."""
    orig_shape = x.shape
    n_out = w.shape[1]
    x2 = x.reshape(-1, orig_shape[-1])
    es = jnp.ones((n_out,), jnp.float32) if scale is None else scale.astype(jnp.float32)
    eb = jnp.zeros((n_out,), jnp.float32) if b is None else b.astype(jnp.float32) * es
    if shift is not None:
        eb = eb + shift.astype(jnp.float32)
    es, eb = es.reshape(1, -1), eb.reshape(1, -1)
    r2 = None if residual is None else residual.reshape(-1, n_out)
    if 0 in x2.shape or 0 in w.shape:
        # degenerate GEMM (e.g. a 1x1 conv over an empty spatial grid):
        # nothing to tile — the empty-safe jnp contraction is exact
        y = x2.astype(jnp.float32) @ w.astype(jnp.float32) * es + eb
        if r2 is not None:
            y = y + r2.astype(jnp.float32)
        return _ACTS[act](y).astype(x.dtype).reshape(*orig_shape[:-1], n_out)
    x2, M = pad_to(x2, 0, bm)
    x2, _ = pad_to(x2, 1, bk)
    w, _ = pad_to(w, 0, bk)
    w, N = pad_to(w, 1, bn)
    es, _ = pad_to(es, 1, bn)
    eb, _ = pad_to(eb, 1, bn)
    Mp, Kp = x2.shape
    Np = w.shape[1]
    operands = [x2, w, es, eb]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
        pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
        pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
    ]
    if r2 is not None:
        r2, _ = pad_to(r2, 0, bm)
        r2, _ = pad_to(r2, 1, bn)
        operands.append(r2)
        in_specs.append(pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)))
    out = pl.pallas_call(
        functools.partial(_kernel, act=act, has_residual=r2 is not None),
        grid=(Mp // bm, Np // bn, Kp // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="matmul_epilogue",
        interpret=common.interpret_mode(),
    )(*operands)
    return out[:M, :N].reshape(*orig_shape[:-1], N)
