"""mac kernel: int8 x int8 -> int32 tiled MAC GEMM with fused dequant.

The paper's ``mac`` instruction executes mul+accumulate in one issue slot on
fixed registers; the TPU analogue is an MXU GEMM that multiply-accumulates
int8 tiles into an int32 VMEM accumulator in one pass (2x bf16 rate), with
the per-output-channel dequant scale applied in the epilogue — no separate
accumulate or dequant round-trip through HBM.

Fixed 128-aligned tile shapes play the role of the paper's hardcoded
x20-x22 registers: one compiled kernel variant, reused everywhere.

Ladder rung: ``mac`` v1 on every class ladder (``core.extensions.
CLASS_LADDERS``) — for LM classes this is the int8 decode-step GEMM rung,
the first rung their ladders share with the CNN ladder.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common
from repro.kernels.common import pad_to

BM, BN, BK = 128, 128, 128


def _kernel(x_ref, w_ref, scale_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32) * scale_ref[...]
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def mac_matmul_int8(x_int8, w_int8, scale, out_dtype=jnp.float32):
    """x: (M, K) int8, w: (K, N) int8, scale: (N,) or (1, N) f32 -> (M, N)."""
    scale = scale.reshape(1, -1)
    x_int8, M = pad_to(x_int8, 0, BM)
    x_int8, _ = pad_to(x_int8, 1, BK)
    w_int8, _ = pad_to(w_int8, 0, BK)
    w_int8, N = pad_to(w_int8, 1, BN)
    scale, _ = pad_to(scale, 1, BN)
    Mp, Kp = x_int8.shape
    Np = w_int8.shape[1]
    grid = (Mp // BM, Np // BN, Kp // BK)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BK), lambda m, n, k: (m, k)),
            pl.BlockSpec((BK, BN), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, BN), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[_vmem((BM, BN), jnp.int32)],
        name="mac_matmul_int8",
        interpret=common.interpret_mode(),
    )(x_int8, w_int8, scale)
    return out[:M, :N]


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)
