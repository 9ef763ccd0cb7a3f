"""conv_mac kernel: int8 implicit-GEMM conv with the full epilogue fused.

The paper's CNN inner loops are ``mac``/``fusedmac`` sites: an int8
multiply-accumulate over the KH*KW*Cin reduction followed by bias, folded-BN
affine, and relu/relu6 — four HBM round-trips when run unfused.  The TPU
analogue is an implicit-GEMM conv: the NHWC activation tile for each
(kernel-row, kernel-col, cin-block) contraction step is carved out of the
VMEM-resident padded image *inside the kernel* (no HBM-materialized im2col),
multiply-accumulated as an int8 x int8 -> int32 MXU GEMM into a VMEM
accumulator (the ``mac_matmul`` pattern), and the whole epilogue — per-channel
dequant scale, bias, BN affine, activation, algebraically pre-folded into one
(scale, bias) pair — is applied in-register before the single HBM write.

GEMM view: M = a block of output rows x the full output width (BM ~= 128
output pixels), N = a BN block of output channels, K = KH*KW*Cin walked as
Cin/BK grid steps, each unrolling the KH*KW taps.  Grouped/depthwise convs
and exotic paddings stay on the jnp reference via the dispatch wrapper in
ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common
from repro.kernels.common import (
    EPILOGUE_ACTS, conv_tap, conv_tile_plan, pad_to, stride_phases,
)

BM, BN, BK = 128, 128, 128

_ACTS = EPILOGUE_ACTS


def _kernel(x_ref, w_ref, es_ref, eb_ref, *refs,
            sh, sw, boh, wo, act, has_residual):
    # grid: (n, oh_block, cout_block, cin_block); the cin-block contraction
    # is innermost so the accumulator carries across it, and the (kh, kw)
    # taps are unrolled (static) inside each step
    if has_residual:
        r_ref, o_ref, acc_ref = refs
    else:
        (o_ref, acc_ref), r_ref = refs, None
    kc = pl.program_id(3)

    @pl.when(kc == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # implicit im2col: each (boh*wo, BK) patch tile is a contiguous window
    # of one stride phase of the VMEM-resident padded image
    row0 = pl.program_id(1) * boh
    acc = acc_ref[...]
    for i in range(w_ref.shape[0]):
        for j in range(w_ref.shape[1]):
            patch = conv_tap(x_ref, row0, i, j, sh=sh, sw=sw, boh=boh, wo=wo)
            acc += jax.lax.dot_general(
                patch, w_ref[i, j], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
    acc_ref[...] = acc

    @pl.when(kc == pl.num_programs(3) - 1)
    def _epilogue():
        # dequant + bias + folded-BN affine pre-folded into (es, eb); the
        # acc_mac residual-add accumulates in-register before the activation
        y = acc_ref[...].astype(jnp.float32) * es_ref[...] + eb_ref[...]
        if has_residual:
            y = y + r_ref[0].reshape(y.shape).astype(jnp.float32)
        o_ref[0] = _ACTS[act](y).reshape(boh, wo, -1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("stride", "padding", "act",
                                             "out_dtype", "bm", "bn", "bk"))
def fused_conv_int8(x_int8, w_int8, eff_scale, eff_bias, residual=None, *,
                    stride=1, padding="SAME", act="none",
                    out_dtype=jnp.float32, bm=BM, bn=BN, bk=BK):
    """x: (N, H, W, Cin) int8; w: (KH, KW, Cin, Cout) int8;
    eff_scale/eff_bias: (Cout,) f32; residual: optional (N, Ho, Wo, Cout)
    skip tensor -> act(acc*eff_scale + eff_bias [+ residual]), returned as
    (N, Ho, Wo, Cout) ``out_dtype``.  The residual-add (the ``acc_mac``
    extension) happens in-register on the accumulator tile, so the skip
    connection costs one extra VMEM read instead of a full HBM round-trip
    of the conv output.

    Strided convs read stride phases of the padded image
    (:func:`repro.kernels.common.stride_phases`).  When the KW taps of a
    row fit one Cin block (``KW*Cin <= bk``: the 3-channel stems), they are
    folded into the channel axis first, so the kernel contracts KH taps of
    KW*Cin real channels instead of KH*KW taps of Cin channels each padded
    to ``bk`` — same image bytes, KWx fewer MXU passes.

    ``bm``/``bn``/``bk`` are the autotunable tile sizes: output-pixel block,
    Cout block, Cin contraction block (defaults: the MXU-native 128s; the
    dispatch wrapper overrides them from the active tuning table)."""
    n, h, w_in, cin = x_int8.shape
    kh, kw, _, cout = w_int8.shape
    ho, wo, boh, ohb, top, left, hp_req, wp_req = conv_tile_plan(
        h, w_in, kh, kw, stride, padding, bm
    )
    # pad so every tap is in bounds; zero padding is exact for symmetric
    # int8 (zero-point 0)
    x_p = jnp.pad(x_int8, ((0, 0), (top, max(hp_req - h - top, 0)),
                           (left, max(wp_req - w_in - left, 0)), (0, 0)))
    sw = stride
    if kw > 1 and kw * cin <= bk:
        # fold the KW taps (and the column stride) into the channel axis
        x_p = jnp.concatenate(
            [x_p[:, :, j:j + (wo - 1) * stride + 1:stride, :]
             for j in range(kw)], axis=-1)
        w_int8 = w_int8.reshape(kh, 1, kw * cin, cout)
        sw = 1
    x_p, _ = pad_to(x_p, 3, bk)
    x_ph = stride_phases(x_p, stride, sw)
    w_p, _ = pad_to(w_int8, 2, bk)
    w_p, _ = pad_to(w_p, 3, bn)
    es, _ = pad_to(eff_scale.reshape(1, -1).astype(jnp.float32), 1, bn)
    eb, _ = pad_to(eff_bias.reshape(1, -1).astype(jnp.float32), 1, bn)
    _, nph, hq, wq, cp = x_ph.shape
    kh_, kw_ = w_p.shape[:2]
    nb = w_p.shape[3] // bn
    operands = [x_ph, w_p, es, eb]
    in_specs = [
        pl.BlockSpec((1, nph, hq, wq, bk),
                     lambda ni, oi, nbi, kci: (ni, 0, 0, 0, kci)),
        pl.BlockSpec((kh_, kw_, bk, bn),
                     lambda ni, oi, nbi, kci: (0, 0, kci, nbi)),
        pl.BlockSpec((1, bn), lambda ni, oi, nbi, kci: (0, nbi)),
        pl.BlockSpec((1, bn), lambda ni, oi, nbi, kci: (0, nbi)),
    ]
    if residual is not None:
        # skip tensor tiled exactly like the output block
        r_p = jnp.pad(residual.astype(jnp.float32),
                      ((0, 0), (0, ohb * boh - ho), (0, 0), (0, 0)))
        r_p, _ = pad_to(r_p, 3, bn)
        operands.append(r_p)
        in_specs.append(pl.BlockSpec(
            (1, boh, wo, bn), lambda ni, oi, nbi, kci: (ni, oi, 0, nbi),
        ))
    out = pl.pallas_call(
        functools.partial(_kernel, sh=stride, sw=sw, boh=boh, wo=wo, act=act,
                          has_residual=residual is not None),
        grid=(n, ohb, nb, cp // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, boh, wo, bn), lambda ni, oi, nbi, kci: (ni, oi, 0, nbi),
        ),
        out_shape=jax.ShapeDtypeStruct((n, ohb * boh, wo, nb * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((boh * wo, bn), jnp.int32)],
        name="fused_conv",
        interpret=common.interpret_mode(),
    )(*operands)
    return out[:, :ho, :, :cout]
