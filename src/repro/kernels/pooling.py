"""pool kernels: windowed int8/fp32 max/avg pooling + the global-avg reduce.

Pooling is the last CNN-class op family still dispatched to the XLA baseline:
every ``reduce_window`` reads the activation from HBM, writes the pooled
tensor back, and (for average pooling) a separate elementwise pass re-reads
it to apply the ``1/k^2`` rescale.  The paper's pool extension (cf. the
MAC/pool custom-instruction set of the FPGA RISC-V edge-inference line) folds
the windowed reduce and the rescale into one datapath pass; the TPU analogue
is a Pallas kernel that carves each (kh, kw) tap tile out of the
VMEM-resident image (the same implicit-im2col slicing as the conv kernels,
shared via :func:`repro.kernels.common.conv_tile_plan`), reduces across the
taps in registers, applies the rescale in-register, and issues one HBM write.

All kernels accumulate in f32 — exact for int8 inputs (every int8 value and
any sum of <= 2^24 of them is representable), so one kernel body serves both
the int8 and fp32 deployments.  Max pooling preserves the input dtype;
average pooling of an integer-typed input returns f32 (an integer mean is
not an integer).

Fast-path coverage (the dispatch wrapper in ops.py guards the rest onto the
jnp oracle): 4-D NHWC input, VALID padding, window 2 or 3, stride 2 — the
only pooling forms the six paper CNNs emit — plus the global-avg reduction
at any spatial extent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common
from repro.kernels.common import (
    conv_out_size, conv_tap, conv_tile_plan, pad_to, padded_phases,
)

BM, BC = 128, 128

# window sizes the Pallas fast path unrolls (matches the paper CNNs: 2x2
# stride-2 VGG/DenseNet pools, 3x3 stride-2 ResNet/DenseNet stem pools)
SUPPORTED_WINDOWS = (2, 3)
SUPPORTED_STRIDES = (2,)

# the kernels hold one whole (Hp, Wp, BC) image slab per grid step in VMEM
# (like the conv kernels — but a float model's slab is f32, 4x an int8
# conv's); cap it at half the 16 MB v5e VMEM so native-resolution inputs
# (e.g. 224x224 f32: ~25.7 MB padded) fall back instead of failing to
# compile on a real TPU.  The paper's 64x64 models stay far under this.
VMEM_SLAB_LIMIT = 8 * 2**20


def fits_vmem(x, k=2, stride=2, op="max") -> bool:
    """Would the padded image slab of this pool fit the VMEM budget?"""
    n, h, w_in, _ = x.shape
    if op == "global_avg":
        hp, wp = h, w_in
    else:
        ho, wo, boh, ohb, _, _, hp_req, wp_req = conv_tile_plan(
            h, w_in, k, k, stride, "VALID", BM
        )
        hp, wp = max(hp_req, h), max(wp_req, w_in)
    return hp * wp * BC * jnp.dtype(x.dtype).itemsize <= VMEM_SLAB_LIMIT


def fast_path_supported(x, *, op, k=2, stride=2) -> bool:
    """Would ops._pallas_pool run a Pallas pool kernel on this site (vs the
    jnp oracle)?  ONE predicate shared by the dispatch wrapper and the
    profiler's pool-credit mirror, so they cannot drift."""
    if len(getattr(x, "shape", ())) != 4 or 0 in x.shape:
        return False
    if op == "global_avg":
        return fits_vmem(x, op="global_avg")
    return (
        op in ("max", "avg")
        and k in SUPPORTED_WINDOWS and stride in SUPPORTED_STRIDES
        and conv_out_size(x.shape[1], k, stride, "VALID") > 0
        and conv_out_size(x.shape[2], k, stride, "VALID") > 0
        and fits_vmem(x, k, stride, op)
    )


def _pool_kernel(x_ref, o_ref, *, k, stride, boh, wo, op):
    # grid: (n, oh_block, c_block); the k*k taps are unrolled (k is static
    # and tiny), so the whole reduce + rescale happens in registers
    row0 = pl.program_id(1) * boh
    acc = None
    for kh in range(k):
        for kw in range(k):
            t = conv_tap(x_ref, row0, kh, kw, sh=stride, sw=stride, boh=boh,
                         wo=wo).astype(jnp.float32)
            if acc is None:
                acc = t
            else:
                acc = jnp.maximum(acc, t) if op == "max" else acc + t
    if op == "avg":
        acc = acc * (1.0 / (k * k))  # the rescale never round-trips HBM
    o_ref[0] = acc.reshape(boh, wo, -1).astype(o_ref.dtype)


def _gap_kernel(x_ref, o_ref, *, hw):
    # grid: (n, c_block); one pass over the full (H, W, BC) image per lane
    img = x_ref[0].astype(jnp.float32)
    o_ref[0] = (jnp.sum(jnp.sum(img, axis=0), axis=0, keepdims=True)
                * (1.0 / hw)).astype(o_ref.dtype)


def _avg_out_dtype(dtype):
    return jnp.float32 if jnp.issubdtype(dtype, jnp.integer) else dtype


def _windowed_pool(x, k, stride, op):
    n, h, w_in, c = x.shape
    ho, wo, boh, ohb, _, _, hp_req, wp_req = conv_tile_plan(
        h, w_in, k, k, stride, "VALID", BM
    )
    # rows/cols beyond the VALID extent only feed discarded output rows
    # (sliced off below), so the zero pad value never reaches a kept output
    x_ph = padded_phases(x, 0, 0, hp_req, wp_req, stride, BC)
    _, nph, hq, wq, cp = x_ph.shape
    out_dtype = x.dtype if op == "max" else _avg_out_dtype(x.dtype)
    out = pl.pallas_call(
        functools.partial(_pool_kernel, k=k, stride=stride, boh=boh, wo=wo,
                          op=op),
        grid=(n, ohb, cp // BC),
        in_specs=[
            pl.BlockSpec((1, nph, hq, wq, BC),
                         lambda ni, oi, ci: (ni, 0, 0, 0, ci)),
        ],
        out_specs=pl.BlockSpec(
            (1, boh, wo, BC), lambda ni, oi, ci: (ni, oi, 0, ci)
        ),
        out_shape=jax.ShapeDtypeStruct((n, ohb * boh, wo, cp), out_dtype),
        name=f"{op}pool",
        interpret=common.interpret_mode(),
    )(x_ph)
    return out[:, :ho, :, :c]


@functools.partial(jax.jit, static_argnames=("k", "stride"))
def maxpool2d(x, *, k=2, stride=2):
    """x: (N, H, W, C) int8/fp32 -> (N, Ho, Wo, C) VALID max pool, x.dtype."""
    return _windowed_pool(x, k, stride, "max")


@functools.partial(jax.jit, static_argnames=("k", "stride"))
def avgpool2d(x, *, k=2, stride=2):
    """x: (N, H, W, C) int8/fp32 -> (N, Ho, Wo, C) VALID avg pool with the
    1/k^2 rescale applied in-register (f32 accumulate; integer inputs
    return f32)."""
    return _windowed_pool(x, k, stride, "avg")


@jax.jit
def global_avgpool(x):
    """x: (N, H, W, C) -> (N, C) mean over the spatial extent (f32
    accumulate; integer inputs return f32)."""
    n, h, w_in, c = x.shape
    x_p, _ = pad_to(x, 3, BC)
    cp = x_p.shape[3]
    # (N, 1, Cp) output: a (1, 1, BC) block has full trailing dims
    out = pl.pallas_call(
        functools.partial(_gap_kernel, hw=h * w_in),
        grid=(n, cp // BC),
        in_specs=[
            pl.BlockSpec((1, h, w_in, BC), lambda ni, ci: (ni, 0, 0, ci)),
        ],
        out_specs=pl.BlockSpec((1, 1, BC), lambda ni, ci: (ni, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((n, 1, cp), _avg_out_dtype(x.dtype)),
        name="global_avgpool",
        interpret=common.interpret_mode(),
    )(x_p)
    return out[:, 0, :c]
