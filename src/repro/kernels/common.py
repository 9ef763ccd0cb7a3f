"""Shared kernel plumbing: interpret-mode switch + padding helpers.

The kernels target the TPU: there Pallas lowers them through Mosaic and
``interpret_mode()`` is False.  On any other backend (the CPU test lane)
they run with ``interpret=True``, which executes the kernel body in Python
for correctness validation against the pure-jnp oracles in ref.py.  Kernel
modules read the switch as ``common.interpret_mode()`` so a test can steer
every kernel at once (tests/test_tpu_compile.py compiles them for a
described TPU from a CPU process).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def interpret_mode() -> bool:
    return jax.default_backend() != "tpu"


def conv_out_size(size: int, k: int, stride: int, padding: str) -> int:
    """Spatial output size of a conv (may be <= 0 for degenerate VALID)."""
    if padding == "SAME":
        return -(-size // stride)
    return (size - k) // stride + 1


# the epilogue activations the int8 conv-family kernels (fused_conv,
# depthwise_conv, sep_block) implement in-register; ops.py guards fall back
# to the jnp references for anything else
EPILOGUE_ACTS = {
    "none": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "relu6": lambda x: jnp.clip(x, 0.0, 6.0),
}


def conv_tile_plan(h: int, w_in: int, kh: int, kw: int, stride: int,
                   padding: str, bm: int):
    """Shared implicit-im2col tiling plan for the conv-family kernels.

    Returns ``(ho, wo, boh, ohb, top, left, hp_req, wp_req)``: output
    sizes, output rows per M tile, M-tile count, the SAME-padding split
    (low = total // 2, matching lax), and the padded image extent that
    keeps every (kh, kw, row-block) slice in bounds.
    """
    ho = conv_out_size(h, kh, stride, padding)
    wo = conv_out_size(w_in, kw, stride, padding)
    boh = max(1, min(ho, bm // max(wo, 1)))
    ohb = -(-ho // boh)
    if padding == "SAME":
        top = max((ho - 1) * stride + kh - h, 0) // 2
        left = max((wo - 1) * stride + kw - w_in, 0) // 2
    else:
        top = left = 0
    hp_req = (ohb * boh - 1) * stride + kh
    wp_req = (wo - 1) * stride + kw
    return ho, wo, boh, ohb, top, left, hp_req, wp_req


def conv_kernel_eligible(x, w, *, stride, padding, groups, act) -> bool:
    """Would ops._pallas_fused_conv run the implicit-GEMM kernel on this
    site (vs falling back to the jnp oracle)?  ONE predicate shared by the
    dispatch wrapper and the profiler's credit mirrors, so they cannot
    drift."""
    if (groups != 1 or getattr(x, "ndim", len(getattr(x, "shape", ()))) != 4
            or len(getattr(w, "shape", ())) != 4
            or padding not in ("SAME", "VALID") or act not in EPILOGUE_ACTS):
        return False
    return (conv_out_size(x.shape[1], w.shape[0], stride, padding) > 0
            and conv_out_size(x.shape[2], w.shape[1], stride, padding) > 0)


def conv_residual_fusable(x, w, res, *, stride, padding, groups, act) -> bool:
    """Is ``res`` an exactly-output-shaped skip tensor on a kernel-eligible
    conv site (the acc_mac epilogue's contract)?"""
    if not conv_kernel_eligible(x, w, stride=stride, padding=padding,
                                groups=groups, act=act):
        return False
    return getattr(res, "shape", None) == (
        x.shape[0],
        conv_out_size(x.shape[1], w.shape[0], stride, padding),
        conv_out_size(x.shape[2], w.shape[1], stride, padding),
        w.shape[-1],
    )


def gemm_residual_fusable(x, w, res) -> bool:
    """Is ``res`` an exactly-output-shaped skip tensor for the GEMM-epilogue
    kernel (matmul_epilogue's acc_mac contract)?"""
    return (len(getattr(w, "shape", ())) == 2
            and getattr(res, "shape", None) == (*x.shape[:-1], w.shape[1]))


def stride_phases(x_p, sh: int, sw: int):
    """(N, Hp, Wp, C) -> (N, sh*sw, ceil(Hp/sh), ceil(Wp/sw), C): the
    padded image split into its stride phases, phase ``(i % sh) * sw +
    (j % sw)`` holding rows ``i::sh`` and columns ``j::sw``.  A strided tap
    then reads a contiguous window of one phase, so the kernels never issue
    a strided or dynamically-offset sublane load (Mosaic has neither for
    8/16-bit data)."""
    n, hp, wp, c = x_p.shape
    hq, wq = -(-hp // sh), -(-wp // sw)
    x_p = jnp.pad(x_p, ((0, 0), (0, hq * sh - hp), (0, wq * sw - wp), (0, 0)))
    x = x_p.reshape(n, hq, sh, wq, sw, c).transpose(0, 2, 4, 1, 3, 5)
    return x.reshape(n, sh * sw, hq, wq, c)


def conv_tap(x_ref, row0, kh: int, kw: int, *, sh: int, sw: int, boh: int,
             wo: int):
    """The (boh*wo, C) tile of static tap (kh, kw) for the output-row block
    starting at (traced) output row ``row0``, read straight from the
    VMEM-resident phase-split image ``x_ref`` of block shape
    (1, sh*sw, Hq, Wq, C) — the shared implicit-im2col slice of the conv,
    depthwise and pooling kernels.  Only the leading (row) offset is
    dynamic; the column offset ``kw // sw`` is static."""
    tile = x_ref[0, (kh % sh) * sw + kw % sw,
                 pl.ds(row0 + kh // sh, boh), pl.ds(kw // sw, wo), :]
    return tile.reshape(boh * wo, tile.shape[-1])


def padded_phases(x, top: int, left: int, hp_req: int, wp_req: int,
                  stride: int, c_block: int):
    """Zero-pad (exact for symmetric int8, and only feeding discarded
    outputs for VALID pools) so every tap is in bounds (extents from
    :func:`conv_tile_plan`), pad channels to ``c_block``, and split into
    stride phases."""
    _, h, w_in, _ = x.shape
    x_p = jnp.pad(x, ((0, 0), (top, max(hp_req - h - top, 0)),
                      (left, max(wp_req - w_in - left, 0)), (0, 0)))
    x_p, _ = pad_to(x_p, 3, c_block)
    return stride_phases(x_p, stride, stride)


def pad_to(x: jax.Array, axis: int, multiple: int, value=0.0):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), size
