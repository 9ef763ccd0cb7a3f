"""zol kernel (attention class): causal flash attention, grid-pipelined.

The paper's ``zol`` hardware loops eliminate per-iteration branch/bookkeeping
(blt, counter increments) by moving loop control into the PCU.  The TPU
analogue moves the KV loop into the Pallas *grid*: the Mosaic sequencer
iterates KV blocks with double-buffered DMA, running softmax statistics live
in VMEM scratch — no per-iteration scalar code, no S^2 HBM spill.

Ladder rung: ``zol`` v4 on every attention-bearing LM ladder (dense/moe/
ssm/hybrid/enc_dec — see ``core.extensions.CLASS_LADDERS``); at v4 the
dispatcher also feeds this kernel dequantized int8-KV pages (per-(position,
head) scale planes from the decode cache), so the attention matmuls join
the int8 rate in the cost model.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, bq, bk, causal):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal block skip: KV blocks entirely above the diagonal never run
    if causal:
        needed = ki * bk <= qi * bq + bq - 1
    else:
        needed = ki >= 0  # always

    @pl.when(needed)
    def _block():
        q = q_ref[0]  # (bq, d)
        k = k_ref[0]  # (bk, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def flash_attention(q, k, v, causal=True, bq=128, bk=128):
    """q: (BH, Sq, d); k, v: (BH, Skv, d) -> (BH, Sq, d).

    Sq/Skv must be multiples of bq/bk (wrappers pad).
    """
    BH, Sq, d = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    grid = (BH, Sq // bq, Skv // bk)
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, bq=bq, bk=bk, causal=causal
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        name="flash_attention",
        interpret=common.interpret_mode(),
    )(q, k, v)
