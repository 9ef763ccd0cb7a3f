"""dw_mac kernels: depthwise int8 conv + the fused separable block.

Depthwise 3x3s dominate the mobile CNN class (MobileNetV1/V2), yet they are
the one conv form an implicit-GEMM datapath cannot express: each output
channel contracts over only its own (KH, KW) window, so the MXU K dimension
collapses to KH*KW*1 and the op is VPU-bound.  The ``dw_mac`` extension is
the per-channel MAC form of the paper's ``mac``: for every channel lane the
(KH, KW) taps are multiply-accumulated int8 x int8 -> int32 in VMEM, and the
same pre-folded dequant + bias + folded-BN + relu/relu6 epilogue as
``fused_conv`` is applied in-register before the single HBM write.

:func:`depthwise_conv_int8` — the standalone depthwise kernel.  Grid
``(n, oh_block, c_block)``: the (kh, kw) taps are unrolled inside each step
into a ``(BM, BC)`` int32 accumulator; the activation tile for each tap is
carved out of the VMEM-resident, stride-phase-split padded image (same
implicit-im2col slicing as fused_conv, minus the channel contraction).  The
tap weights travel as a ``(KH*KW, 1, C)`` stack so each tap is a lane row.

:func:`sep_block_int8` — the fused separable block (dw -> 1x1 pw) that the
mobile models emit as ONE dispatch site.  The depthwise output tile never
round-trips through HBM: for each (cin-block) contraction step the kernel
recomputes the depthwise tile in VMEM (taps unrolled — KH, KW are static),
applies the depthwise epilogue in-register, and immediately contracts it
against the int8 pointwise weight block on the MXU, accumulating f32 into
the output tile.  The pointwise epilogue (per-channel weight dequant + bias
+ folded BN + act) runs at the last cin step.  The depthwise tile is
recomputed once per cout block — VMEM recompute is the price of never
materializing the (N, Ho, Wo, C) intermediate in HBM (the
``dw_hbm_bytes_saved`` column in bench_kernels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common
from repro.kernels.common import (
    EPILOGUE_ACTS, conv_tap, conv_tile_plan, pad_to, padded_phases,
)

BM, BN, BC = 128, 128, 128

_ACTS = EPILOGUE_ACTS


def _dw_taps(x_ref, w_ref, *, kh, kw, stride, boh, wo):
    """int32 per-channel MAC over the static (kh, kw) taps of this output
    block: one int8 tap per lane, no channel contraction (the VPU form of
    the mac_matmul pattern)."""
    row0 = pl.program_id(1) * boh
    acc = None
    for i in range(kh):
        for j in range(kw):
            patch = conv_tap(x_ref, row0, i, j, sh=stride, sw=stride,
                             boh=boh, wo=wo).astype(jnp.int32)
            term = patch * w_ref[i * kw + j].astype(jnp.int32)
            acc = term if acc is None else acc + term
    return acc


def _dw_kernel(x_ref, w_ref, es_ref, eb_ref, o_ref, *,
               kh, kw, stride, boh, wo, act):
    # grid: (n, oh_block, c_block); the taps are unrolled in _dw_taps
    acc = _dw_taps(x_ref, w_ref, kh=kh, kw=kw, stride=stride, boh=boh, wo=wo)
    # dequant + bias + folded-BN affine pre-folded into (es, eb)
    y = acc.astype(jnp.float32) * es_ref[...] + eb_ref[...]
    o_ref[0] = _ACTS[act](y).reshape(boh, wo, -1).astype(o_ref.dtype)


def _tap_stack(w_int8, bc):
    """(KH, KW, C) taps -> (KH*KW, 1, Cp): one lane row per tap, so a
    channel block is a ``(KH*KW, 1, bc)`` block (full trailing dims)."""
    kh, kw, c = w_int8.shape
    w, _ = pad_to(w_int8.reshape(kh * kw, 1, c), 2, bc)
    return w


@functools.partial(jax.jit, static_argnames=("stride", "padding", "act",
                                             "out_dtype", "bm", "bc"))
def depthwise_conv_int8(x_int8, w_int8, eff_scale, eff_bias, *, stride=1,
                        padding="SAME", act="none", out_dtype=jnp.float32,
                        bm=BM, bc=BC):
    """x: (N, H, W, C) int8; w: (KH, KW, C) int8 (one tap stack per channel);
    eff_scale/eff_bias: (C,) f32 -> act(acc*eff_scale + eff_bias), returned
    as (N, Ho, Wo, C) ``out_dtype``.

    ``bm``/``bc`` are the autotunable tile sizes: output-pixel block and
    channel block (defaults: the VPU-native 128s; the dispatch wrapper
    overrides them from the active tuning table)."""
    n, h, w_in, c = x_int8.shape
    kh, kw, _ = w_int8.shape
    ho, wo, boh, ohb, top, left, hp_req, wp_req = conv_tile_plan(
        h, w_in, kh, kw, stride, padding, bm
    )
    x_ph = padded_phases(x_int8, top, left, hp_req, wp_req, stride, bc)
    w_p = _tap_stack(w_int8, bc)
    es, _ = pad_to(eff_scale.reshape(1, -1).astype(jnp.float32), 1, bc)
    eb, _ = pad_to(eff_bias.reshape(1, -1).astype(jnp.float32), 1, bc)
    _, nph, hq, wq, cp = x_ph.shape
    out = pl.pallas_call(
        functools.partial(_dw_kernel, kh=kh, kw=kw, stride=stride, boh=boh,
                          wo=wo, act=act),
        grid=(n, ohb, cp // bc),
        in_specs=[
            pl.BlockSpec((1, nph, hq, wq, bc),
                         lambda ni, oi, ci: (ni, 0, 0, 0, ci)),
            pl.BlockSpec((kh * kw, 1, bc), lambda ni, oi, ci: (0, 0, ci)),
            pl.BlockSpec((1, bc), lambda ni, oi, ci: (0, ci)),
            pl.BlockSpec((1, bc), lambda ni, oi, ci: (0, ci)),
        ],
        out_specs=pl.BlockSpec(
            (1, boh, wo, bc), lambda ni, oi, ci: (ni, oi, 0, ci)
        ),
        out_shape=jax.ShapeDtypeStruct((n, ohb * boh, wo, cp), out_dtype),
        name="depthwise_conv",
        interpret=common.interpret_mode(),
    )(x_ph, w_p, es, eb)
    return out[:, :ho, :, :c]


def _sep_kernel(x_ref, wd_ref, ds_ref, db_ref, wp_ref, ps_ref, pb_ref,
                o_ref, acc_ref, *, stride, boh, wo, kh, kw, dw_act, pw_act):
    # grid: (n, oh_block, cout_block, cin_block); cin is the innermost
    # contraction dim so the f32 pointwise accumulator carries across it
    ci = pl.program_id(3)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # depthwise tile for this cin block, taps unrolled (KH, KW static) —
    # int32 MAC in registers, never written to HBM
    dw = _dw_taps(x_ref, wd_ref, kh=kh, kw=kw, stride=stride, boh=boh, wo=wo)
    # depthwise epilogue in-register (dequant + bias + folded BN + act) ...
    dwf = _ACTS[dw_act](dw.astype(jnp.float32) * ds_ref[...] + db_ref[...])
    # ... feeds the MXU pointwise contraction directly from VMEM
    acc_ref[...] += jax.lax.dot_general(
        dwf, wp_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ci == pl.num_programs(3) - 1)
    def _epilogue():
        y = acc_ref[...] * ps_ref[...] + pb_ref[...]
        o_ref[0] = _ACTS[pw_act](y).reshape(boh, wo, -1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("stride", "padding", "dw_act",
                                             "pw_act", "out_dtype",
                                             "bm", "bn", "bc"))
def sep_block_int8(x_int8, w_dw_int8, dw_scale, dw_bias, w_pw_int8,
                   pw_scale, pw_bias, *, stride=1, padding="SAME",
                   dw_act="relu", pw_act="none", out_dtype=jnp.float32,
                   bm=BM, bn=BN, bc=BC):
    """Fused depthwise -> pointwise block, one HBM write.

    x: (N, H, W, C) int8; w_dw: (KH, KW, C) int8; w_pw: (C, Cout) int8;
    dw_scale/dw_bias: (C,) f32 depthwise epilogue (act'd in-register);
    pw_scale/pw_bias: (Cout,) f32 pointwise epilogue.  Returns
    ``pw_act((dw_act(dwconv(x)) @ w_pw) * pw_scale + pw_bias)`` as
    (N, Ho, Wo, Cout) ``out_dtype`` — the depthwise intermediate stays in
    VMEM.

    ``bm``/``bn``/``bc`` are the autotunable tile sizes: output-pixel
    block, Cout block, C contraction block (the dispatch wrapper overrides
    the 128 defaults from the active tuning table).
    """
    n, h, w_in, _ = x_int8.shape
    kh, kw, _ = w_dw_int8.shape
    cout = w_pw_int8.shape[1]
    ho, wo, boh, ohb, top, left, hp_req, wp_req = conv_tile_plan(
        h, w_in, kh, kw, stride, padding, bm
    )
    x_ph = padded_phases(x_int8, top, left, hp_req, wp_req, stride, bc)
    wd = _tap_stack(w_dw_int8, bc)
    ds, _ = pad_to(dw_scale.reshape(1, -1).astype(jnp.float32), 1, bc)
    db, _ = pad_to(dw_bias.reshape(1, -1).astype(jnp.float32), 1, bc)
    wp, _ = pad_to(w_pw_int8, 0, bc)
    wp, _ = pad_to(wp, 1, bn)
    ps, _ = pad_to(pw_scale.reshape(1, -1).astype(jnp.float32), 1, bn)
    pb, _ = pad_to(pw_bias.reshape(1, -1).astype(jnp.float32), 1, bn)
    _, nph, hq, wq, cp = x_ph.shape
    nb = wp.shape[1] // bn
    out = pl.pallas_call(
        functools.partial(_sep_kernel, stride=stride, boh=boh, wo=wo,
                          kh=kh, kw=kw, dw_act=dw_act, pw_act=pw_act),
        grid=(n, ohb, nb, cp // bc),
        in_specs=[
            pl.BlockSpec((1, nph, hq, wq, bc),
                         lambda ni, oi, nbi, ci: (ni, 0, 0, 0, ci)),
            pl.BlockSpec((kh * kw, 1, bc), lambda ni, oi, nbi, ci: (0, 0, ci)),
            pl.BlockSpec((1, bc), lambda ni, oi, nbi, ci: (0, ci)),
            pl.BlockSpec((1, bc), lambda ni, oi, nbi, ci: (0, ci)),
            pl.BlockSpec((bc, bn), lambda ni, oi, nbi, ci: (ci, nbi)),
            pl.BlockSpec((1, bn), lambda ni, oi, nbi, ci: (0, nbi)),
            pl.BlockSpec((1, bn), lambda ni, oi, nbi, ci: (0, nbi)),
        ],
        out_specs=pl.BlockSpec(
            (1, boh, wo, bn), lambda ni, oi, nbi, ci: (ni, oi, 0, nbi)
        ),
        out_shape=jax.ShapeDtypeStruct((n, ohb * boh, wo, nb * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((boh * wo, bn), jnp.float32)],
        name="sep_block",
        interpret=common.interpret_mode(),
    )(x_ph, wd, ds, db, wp, ps, pb)
    return out[:, :ho, :, :cout]
