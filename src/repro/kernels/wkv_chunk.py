"""zol kernel (SSM/linear-attention class): RWKV-6 chunked WKV recurrence.

Class-aware extension selection in action: for attention-free models the
profiler recommends fusing the *recurrence* loop instead of attention.  The
chunk dimension is the innermost grid axis, so the (N,N) state lives in VMEM
scratch across chunk iterations — the sequencer runs the loop, zero scalar
overhead, state never spills per-chunk.

Ladder rung: ``zol`` v4 on the ``rnn_lm`` ladder (``core.extensions.
CLASS_LADDERS``) — the wkv recurrence is that class's hot pattern, playing
the role flash attention plays for the attention classes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref, sout_ref,
            s_ref, *, chunk):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0]

    r = r_ref[0, 0, 0].astype(jnp.float32)  # (c, N)
    kk = k_ref[0, 0, 0].astype(jnp.float32)
    vv = v_ref[0, 0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0, 0].astype(jnp.float32)  # log-decay, < 0
    u = u_ref[0]  # (1, N)
    s = s_ref[...]  # (N, N)

    # inclusive prefix sum over the chunk as a lower-triangular ones matmul
    # (Mosaic has no cumsum)
    c = chunk
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    cum = jax.lax.dot_general(
        jnp.where(row >= col, 1.0, 0.0), lw, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    cum_excl = cum - lw
    # from-state: r_t decayed back to chunk start
    rq = r * jnp.exp(cum_excl)
    o_state = jax.lax.dot_general(
        rq, s, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # intra-chunk: A[t,s] = sum_i r_t[i] k_s[i] exp(cum_excl[t]-cum[s]), s<t
    diff = cum_excl[:, None, :] - cum[None, :, :]  # (t, s, N)
    strict = (jax.lax.broadcasted_iota(jnp.int32, diff.shape, 0)
              > jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1))
    D = jnp.exp(jnp.where(strict, diff, -1e30))
    # A[t,s] = sum_i r[t,i] k[s,i] D[t,s,i] — elementwise form (Mosaic-safe)
    A = jnp.sum(r[:, None, :] * kk[None, :, :] * D, axis=-1)
    o_intra = jax.lax.dot_general(
        A, vv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    bonus = jnp.sum(r * u * kk, axis=-1, keepdims=True)
    o_ref[0, 0, 0] = (o_state + o_intra + bonus * vv).astype(o_ref.dtype)
    # state update: decay everything to chunk end; the per-key-row decay of
    # the carried state is a diagonal matmul (no lane->sublane transpose)
    last = cum[c - 1:c, :]  # (1, N)
    dec_end = jnp.exp(last - cum)  # (c, N)
    n = s.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    decay = jnp.where(eye, jnp.exp(last), 0.0)
    s_new = jax.lax.dot_general(
        decay, s, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ) + jax.lax.dot_general(
        kk * dec_end, vv, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s_ref[...] = s_new

    @pl.when(ci == pl.num_programs(2) - 1)
    def _emit_state():
        sout_ref[0, 0] = s_new


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv_chunk(r, k, v, lw, u, s0, chunk=64):
    """r,k,v,lw: (B, S, H, N) f32; u: (H, N); s0: (B, H, N, N).

    Returns (out (B,S,H,N) f32, s_final (B,H,N,N)). S % chunk == 0.
    """
    B, S, H, N = r.shape
    nc = S // chunk
    # layout: (B, H, nc, chunk, N) so (b, h) are outer grid dims
    to_bh = lambda t: t.transpose(0, 2, 1, 3).reshape(B, H, nc, chunk, N)
    rb, kb, vb, lwb = map(to_bh, (r, k, v, lw))
    out, s_final = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, N), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, N), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, N), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, N), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, N), lambda b, h, c: (h, 0, 0)),
            pl.BlockSpec((1, 1, N, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, N), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, N, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, chunk, N), jnp.float32),
            jax.ShapeDtypeStruct((B, H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        name="wkv_chunk",
        interpret=common.interpret_mode(),
    )(rb, kb, vb, lwb, u.reshape(H, 1, N), s0)
    out = out.reshape(B, H, S, N).transpose(0, 2, 1, 3)
    return out, s_final
