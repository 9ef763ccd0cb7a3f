"""fusedmac kernel: GEMM + bias + activation epilogue in one VMEM pass.

The paper's ``fusedmac`` folds the mac *and* its bookkeeping (two addi) into
one instruction; on TPU the analogue folds the GEMM's elementwise epilogue
(bias add + nonlinearity) into the kernel so the GEMM output never round-trips
through HBM before activation.

A pre-activation layer (DenseNet's BN-ReLU-conv) puts its elementwise work
*before* the GEMM instead.  Given ``pre_scale``/``pre_shift``, the
``preact_matmul`` variant applies ``relu(x * pre_scale + pre_shift)`` to each
x tile in VMEM ahead of the contraction, and reads x as it lies in HBM:
unpadded, K whole where it fits, the last M block ragged.  So neither an
activated copy nor a padded copy of x is written.
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
# the prologue variant: rows of x per grid step (a ragged last block costs
# nothing, so the step is large enough to amortise the per-step overhead at
# DenseNet's narrow K), and the widest K it reads as one block
PRE_BM = 512
WHOLE_K = 1024

_ACTS = {
    "none": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "relu6": lambda x: jnp.clip(x, 0.0, 6.0),
    "silu": lambda x: x * jax.nn.sigmoid(x),
    "gelu": jax.nn.gelu,
}


def _kernel(x_ref, w_ref, es_ref, eb_ref, *refs, act, has_residual,
            has_prologue=False):
    if has_prologue:
        ps_ref, pt_ref, *refs = refs
    if has_residual:
        r_ref, o_ref, acc_ref = refs
    else:
        (o_ref, acc_ref), r_ref = refs, None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if has_prologue:
        # the pre-activation BN-ReLU, on the tile in VMEM before the MXU
        x = jnp.maximum(x * ps_ref[...] + pt_ref[...], 0.0).astype(x.dtype)
    acc_ref[...] += jax.lax.dot_general(
        x, w_ref[...], (((1,), (0,)), ((), ())),
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
                    residual=None, pre_scale=None, pre_shift=None, *,
                    bm=BM, bn=BN, bk=BK):
    """x: (..., K); w: (K, N); b/scale/shift: (N,) or None; residual:
    optional (..., N) skip tensor ->
    ``act((x@w + b)*scale + shift [+ residual])``.  The whole epilogue folds
    into one per-column (scale, bias) pair — ``act(acc*scale + (b*scale +
    shift))`` — applied in-register; the residual-add (the ``acc_mac``
    extension) rides the same epilogue, so a skip connection costs one VMEM
    read instead of an HBM round-trip of the GEMM output.

    ``pre_scale``/``pre_shift`` ((K,) or None) are the pre-activation
    prologue: where either is given, x is ``relu(x*pre_scale +
    pre_shift)`` before the contraction, computed per tile by the
    ``preact_matmul`` variant (:func:`_preact_call`).  Without them the
    kernel is the epilogue-only ``matmul_epilogue``.

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
    prologue = pre_scale is not None or pre_shift is not None
    if prologue:
        k_in = w.shape[0]
        ps = (jnp.ones((k_in,), jnp.float32) if pre_scale is None
              else pre_scale.astype(jnp.float32)).reshape(1, -1)
        pt = (jnp.zeros((k_in,), jnp.float32) if pre_shift is None
              else pre_shift.astype(jnp.float32)).reshape(1, -1)
    if 0 in x2.shape or 0 in w.shape:
        # degenerate GEMM (e.g. a 1x1 conv over an empty spatial grid):
        # nothing to tile — the empty-safe jnp contraction is exact
        xf = x2.astype(jnp.float32)
        if prologue:
            xf = jnp.maximum(xf * ps + pt, 0.0)
        y = xf @ w.astype(jnp.float32) * es + eb
        if r2 is not None:
            y = y + r2.astype(jnp.float32)
        return _ACTS[act](y).astype(x.dtype).reshape(*orig_shape[:-1], n_out)
    if prologue:
        out = _preact_call(x2, w, es, eb, ps, pt, r2, act=act, bn=bn, bk=bk)
        return out.reshape(*orig_shape[:-1], n_out)
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


def _preact_call(x2, w, es, eb, ps, pt, r2, *, act, bn, bk):
    """The ``preact_matmul`` variant on 2-D operands: ``act(relu(x2*ps +
    pt) @ w * es + eb [+ r2])``.

    x2 is read as it lies: K is one block up to :data:`WHOLE_K` (above it
    x2 is zero-padded to ``bk``, and so are ``ps``/``pt``, so a padded
    column gives relu(0) = 0), and M steps :data:`PRE_BM` rows with the
    last block ragged (Pallas never writes its rows past M; each output row
    reads only its own x row, so what lies past M reaches nothing)."""
    m_rows, k_in = x2.shape
    if k_in > WHOLE_K:
        x2, _ = pad_to(x2, 1, bk)
        w, _ = pad_to(w, 0, bk)
        ps, _ = pad_to(ps, 1, bk)
        pt, _ = pad_to(pt, 1, bk)
    else:
        bk = k_in
    w, n_out = pad_to(w, 1, bn)
    es, _ = pad_to(es, 1, bn)
    eb, _ = pad_to(eb, 1, bn)
    kp, np_ = w.shape
    bm = m_rows if m_rows <= PRE_BM else PRE_BM
    operands = [x2, w, es, eb, ps, pt]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
        pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
        pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        pl.BlockSpec((1, bk), lambda m, n, k: (0, k)),
        pl.BlockSpec((1, bk), lambda m, n, k: (0, k)),
    ]
    if r2 is not None:
        r2, _ = pad_to(r2, 1, bn)
        operands.append(r2)
        in_specs.append(pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)))
    out = pl.pallas_call(
        functools.partial(_kernel, act=act, has_residual=r2 is not None,
                          has_prologue=True),
        grid=(pl.cdiv(m_rows, bm), np_ // bn, kp // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((m_rows, np_), x2.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="preact_matmul",
        interpret=common.interpret_mode(),
    )(*operands)
    return out[:, :n_out] if np_ != n_out else out
