"""jit'd wrappers + dispatch registration: the ``pallas`` backend.

Importing this module registers every kernel under its MARVEL pattern name,
so ``marvel.compile(..., backend="pallas")`` — or an ambient
``dispatch.use_table(resolve_table(level, "pallas", model_class=...))`` —
swaps them in without any model-code change (chess_rewrite property).
Wrappers adapt the model-layer calling conventions (grouped GQA heads,
optional bias, quant dicts) to the kernels' 2D/3D tile layouts, falling back
to the jnp reference for cases a kernel doesn't cover (cross-attention,
windows, decode with kv_len).

Registrations carry ``platforms=("tpu",)``: ``backend="auto"`` only picks a
Pallas kernel where it is the production form (Mosaic on TPU); on CPU the
kernels still run — forced via ``backend="pallas"`` — but in interpret mode,
which is correctness emulation, not a serving path.
"""
from __future__ import annotations

import collections

import jax.numpy as jnp

from repro.core import dispatch
from repro.kernels import depthwise_conv as dw
from repro.kernels import flash_attention as fa
from repro.kernels import fused_conv as fc
from repro.kernels import mac_matmul as mm
from repro.kernels import pooling as pk
from repro.kernels import ref
from repro.kernels import matmul_epilogue as me
from repro.kernels import residual_rmsnorm as rr
from repro.kernels import tuning
from repro.kernels import wkv_chunk as wk
from repro.kernels.common import (
    conv_kernel_eligible, conv_out_size, conv_residual_fusable,
    gemm_residual_fusable, pad_to,
)
from repro.models.layers import _flash_attention_ref

# dispatch sites whose wrapper took its jnp-reference branch (a kernel lost
# to a guard), counted per pattern at trace time; chip_smoke.py requires
# zero on the model it serves
REF_FALLBACKS: collections.Counter = collections.Counter()


def _ref_fallback(pattern, fn, *args, **kwargs):
    REF_FALLBACKS[pattern] += 1
    return fn(*args, **kwargs)


def _pallas_mac_matmul_int8(x, quant):
    w_int8, scale = quant["w_int8"], quant["scale"]
    orig = x.shape
    x2 = x.reshape(-1, orig[-1])
    # dynamic per-row activation quantization (paper: full int8 inference)
    absmax = jnp.max(jnp.abs(x2.astype(jnp.float32)), axis=1, keepdims=True)
    xs = jnp.maximum(absmax, 1e-8) / 127.0
    x_int8 = jnp.clip(jnp.round(x2.astype(jnp.float32) / xs), -127, 127
                      ).astype(jnp.int8)
    out = mm.mac_matmul_int8(x_int8, w_int8, scale.reshape(-1))
    out = out * xs
    return out.reshape(*orig[:-1], w_int8.shape[-1]).astype(x.dtype)


def _pallas_fused_conv(x, w, b=None, *, stride=1, padding="SAME", groups=1,
                       act="none", scale=None, shift=None, residual=None):
    """conv_mac: quantize to int8 on the fly, run the implicit-GEMM kernel.

    Grouped/depthwise convs, exotic paddings, and acts the kernel epilogue
    doesn't implement fall back to the fused jnp oracle (still one dispatch
    site; the cost model owns the perf delta).  ``residual`` (the acc_mac
    epilogue) must match the conv output shape or the site falls back too.
    """
    # one shared predicate (kernels/common.py) decides kernel eligibility +
    # residual fusability — the profiler's acc_mac credit mirrors the same
    # functions, so dispatch and cost accounting cannot drift
    eligible = conv_kernel_eligible(x, w, stride=stride, padding=padding,
                                    groups=groups, act=act)
    res_ok = residual is None or conv_residual_fusable(
        x, w, residual, stride=stride, padding=padding, groups=groups,
        act=act,
    )
    if not eligible or not res_ok:
        return _ref_fallback(
            "fused_conv", ref.fused_conv_ref, x, w, b, stride=stride,
            padding=padding, groups=groups, act=act, scale=scale,
            shift=shift, residual=residual,
        )
    # dynamic per-tensor activation quant + per-output-channel weight quant
    # (paper: full int8 inference; dequant folds into the kernel epilogue)
    x_int8, xs = _quant_int8(x)
    w_int8, ws = _quant_int8(w, axes=(0, 1, 2))
    cout = w.shape[-1]
    dq = xs * ws  # per-channel dequant, (Cout,)
    bias = jnp.zeros((cout,), jnp.float32) if b is None else b.astype(jnp.float32)
    s = jnp.ones((cout,), jnp.float32) if scale is None else scale.astype(jnp.float32)
    t = jnp.zeros((cout,), jnp.float32) if shift is None else shift.astype(jnp.float32)
    # fold dequant + bias + BN affine into one in-register (scale, bias) pair:
    #   act((acc*dq + bias)*s + t + res) = act(acc*(dq*s) + (bias*s + t) + res)
    # (the residual rides unscaled — it is already in output units)
    cfg = tuning.lookup("fused_conv", tuning.conv_dims(x.shape, w.shape))
    out = fc.fused_conv_int8(
        x_int8, w_int8, dq * s, bias * s + t, residual,
        stride=stride, padding=padding, act=act,
        bm=cfg["bm"], bn=cfg["bn"], bk=cfg["bk"],
    )
    return out.astype(x.dtype)


def _quant_int8(a, axes=None):
    """Symmetric int8 quantization: (int8 values, f32 scale).  ``axes=None``
    is per-tensor (activations); a reduction-axes tuple is per-channel
    (weights)."""
    af = a.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(af), axis=axes), 1e-8) / 127.0
    return jnp.clip(jnp.round(af / s), -127, 127).astype(jnp.int8), s


def _is_depthwise(x, w):
    """True depthwise: HWIO weights (KH, KW, 1, C) over a (N, H, W, C) x —
    channel multiplier 1 (grouped-but-not-depthwise stays on the baseline)."""
    return (x.ndim == 4 and w.ndim == 4 and w.shape[2] == 1
            and w.shape[3] == x.shape[-1])


def _dw_degenerate(x, w, stride, padding):
    return (conv_out_size(x.shape[1], w.shape[0], stride, padding) <= 0
            or conv_out_size(x.shape[2], w.shape[1], stride, padding) <= 0)


def _pallas_depthwise_conv(x, w, b=None, *, stride=1, padding="SAME",
                           act="none", scale=None, shift=None):
    """dw_mac: quantize to int8 on the fly, run the per-channel MAC kernel.

    Non-depthwise weight shapes, exotic paddings, acts the epilogue doesn't
    implement, and degenerate outputs fall back to the fused jnp oracle
    (still one dispatch site; the cost model owns the perf delta).
    """
    if getattr(w, "ndim", 0) == 3:  # squeezed (KH, KW, C) tap stack — the
        w = w[:, :, None, :]  # form the oracle accepts; normalize to HWIO
    if (not _is_depthwise(x, w) or padding not in ("SAME", "VALID")
            or act not in dw._ACTS or _dw_degenerate(x, w, stride, padding)):
        groups = 1  # grouped-but-not-depthwise: infer groups from HWIO shape
        if (x.ndim == 4 and getattr(w, "ndim", 0) == 4 and w.shape[2]
                and x.shape[-1] % w.shape[2] == 0):
            groups = x.shape[-1] // w.shape[2]
        return _ref_fallback(
            "depthwise_conv", ref.fused_conv_ref, x, w, b, stride=stride,
            padding=padding, groups=groups, act=act, scale=scale,
            shift=shift,
        )
    c = x.shape[-1]
    x_int8, xs = _quant_int8(x)
    w_int8, ws = _quant_int8(w[:, :, 0, :], axes=(0, 1))  # (KH, KW, C)
    dq = xs * ws  # per-channel dequant, (C,)
    bias = jnp.zeros((c,), jnp.float32) if b is None else b.astype(jnp.float32)
    s = jnp.ones((c,), jnp.float32) if scale is None else scale.astype(jnp.float32)
    t = jnp.zeros((c,), jnp.float32) if shift is None else shift.astype(jnp.float32)
    # same epilogue fold as fused_conv: act(acc*(dq*s) + (bias*s + t))
    cfg = tuning.lookup("depthwise_conv", tuning.dw_dims(x.shape))
    out = dw.depthwise_conv_int8(
        x_int8, w_int8, dq * s, bias * s + t, stride=stride, padding=padding,
        act=act, bm=cfg["bm"], bc=cfg["bc"],
    )
    return out.astype(x.dtype)


def _pallas_sep_block(x, w_dw, w_pw, *, stride=1, padding="SAME",
                      dw_scale=None, dw_shift=None, dw_act="relu",
                      pw_bias=None, pw_scale=None, pw_shift=None,
                      pw_act="none"):
    """sep_block: fused depthwise -> pointwise, one HBM write.

    Guard failures (non-depthwise dw weights, non-1x1 pointwise, exotic
    padding/acts, degenerate output) decompose into the two stage wrappers,
    so the depthwise and pointwise kernels still run where they can.
    """
    pw_1x1 = (w_pw.ndim == 4 and w_pw.shape[0] == w_pw.shape[1] == 1
              and w_pw.shape[2] == x.shape[-1])
    if (not _is_depthwise(x, w_dw) or not pw_1x1
            or padding not in ("SAME", "VALID")
            or dw_act not in dw._ACTS or pw_act not in dw._ACTS
            or _dw_degenerate(x, w_dw, stride, padding)):
        REF_FALLBACKS["sep_block"] += 1  # the fused block is lost
        y = _pallas_depthwise_conv(x, w_dw, None, stride=stride,
                                   padding=padding, act=dw_act,
                                   scale=dw_scale, shift=dw_shift)
        return _pallas_fused_conv(y, w_pw, pw_bias, stride=1, padding="SAME",
                                  groups=1, act=pw_act, scale=pw_scale,
                                  shift=pw_shift)
    c, cout = x.shape[-1], w_pw.shape[-1]
    x_int8, xs = _quant_int8(x)
    wd_int8, wds = _quant_int8(w_dw[:, :, 0, :], axes=(0, 1))
    wp_int8, wps = _quant_int8(w_pw.reshape(c, cout), axes=(0,))
    ds = jnp.ones((c,), jnp.float32) if dw_scale is None else dw_scale.astype(jnp.float32)
    dt = jnp.zeros((c,), jnp.float32) if dw_shift is None else dw_shift.astype(jnp.float32)
    pb = jnp.zeros((cout,), jnp.float32) if pw_bias is None else pw_bias.astype(jnp.float32)
    ps = jnp.ones((cout,), jnp.float32) if pw_scale is None else pw_scale.astype(jnp.float32)
    pt = jnp.zeros((cout,), jnp.float32) if pw_shift is None else pw_shift.astype(jnp.float32)
    # dw epilogue fold: dw_act(acc_dw*(xs*wds*ds) + dt); the pointwise stage
    # contracts that f32 tile against int8 weights, so its fold is
    # pw_act(acc_pw*(wps*ps) + (pb*ps + pt))
    cfg = tuning.lookup("sep_block", tuning.sep_dims(x.shape, cout))
    out = dw.sep_block_int8(
        x_int8, wd_int8, xs * wds * ds, dt, wp_int8, wps * ps, pb * ps + pt,
        stride=stride, padding=padding, dw_act=dw_act, pw_act=pw_act,
        bm=cfg["bm"], bn=cfg["bn"], bc=cfg["bc"],
    )
    return out.astype(x.dtype)


def _pallas_matmul_epilogue(x, w, b=None, act="none", scale=None, shift=None,
                            residual=None, pre_scale=None, pre_shift=None):
    """fusedmac: the GEMM with its epilogue; with ``pre_scale``/
    ``pre_shift`` (a pre-activation BN-ReLU on x) the kernel's
    ``preact_matmul`` variant applies that prologue per tile."""
    if residual is not None and not gemm_residual_fusable(x, w, residual):
        # mis-shaped skip tensor: stay on the algorithmically-fused oracle
        return _ref_fallback("matmul_epilogue", ref.matmul_epilogue_ref,
                             x, w, b, act=act, scale=scale, shift=shift,
                             residual=residual, pre_scale=pre_scale,
                             pre_shift=pre_shift)
    cfg = tuning.lookup("matmul_epilogue",
                        tuning.gemm_dims(x.shape, w.shape))
    return me.matmul_epilogue(x, w, b, act=act, scale=scale, shift=shift,
                              residual=residual, pre_scale=pre_scale,
                              pre_shift=pre_shift,
                              bm=cfg["bm"], bn=cfg["bn"], bk=cfg["bk"])


def _pallas_pool(x, *, op, k=2, stride=2):
    """pool: windowed int8/fp32 max/avg pooling + the global-avg reduce.

    The kernels cover the forms the paper CNNs emit (4-D NHWC, VALID,
    window 2/3, stride 2, and global-avg over any spatial extent); exotic
    windows/strides and degenerate shapes fall back to the jnp oracle
    (still one dispatch site; the cost model owns the perf delta).
    """
    if not pk.fast_path_supported(x, op=op, k=k, stride=stride):
        return _ref_fallback("pool", ref.pool_ref, x, op=op, k=k,
                             stride=stride)
    if op == "global_avg":
        return pk.global_avgpool(x)
    if op == "max":
        return pk.maxpool2d(x, k=k, stride=stride)
    return pk.avgpool2d(x, k=k, stride=stride)


def _pallas_residual_rmsnorm(res, x, scale, eps=1e-6):
    return rr.residual_rmsnorm(res, x, scale, eps=eps)


def _pallas_flash_attention(q, k, v, *, causal=True, q_offset=0,
                            impl="chunked", chunk=512, window=None,
                            kv_len=None, k_scale=None, v_scale=None):
    B, Sq, K, G, dh = q.shape
    dv = v.shape[-1]
    # kernel covers the self-attention fast path; everything else -> ref
    Skv = k.shape[1]
    cfg = tuning.lookup("flash_attention",
                        tuning.attn_dims(q.shape, k.shape))
    bq = min(cfg["bq"], Sq)
    bk = min(cfg["bk"], Skv)
    # non-causal with ragged KV would let zero-padded keys contribute
    pad_unsafe = (not causal) and (Skv % bk != 0)
    if window is not None or kv_len is not None or Sq == 1 or dh != dv \
            or pad_unsafe:
        # decode (Sq==1), ragged decode, windows, cross-attention: ref path
        # (which also dequants int8 KV when k_scale is set)
        return _ref_fallback(
            "flash_attention", _flash_attention_ref, q, k, v, causal=causal,
            q_offset=q_offset, impl=impl, chunk=chunk, window=window,
            kv_len=kv_len, k_scale=k_scale, v_scale=v_scale,
        )
    if k_scale is not None:
        # int8-KV dequant path (zol v4): the serving tier stores KV as int8
        # codes with per-(position, head) f32 scale planes (PR 7's
        # quantize_kv_int8); the dequant is a rank-1 broadcast at the
        # kernel boundary, so the cache stays int8 in HBM and the streaming
        # kernel consumes the dequantized tiles
        k = (k.astype(jnp.float32) * k_scale[..., None]).astype(q.dtype)
        v = (v.astype(jnp.float32) * v_scale[..., None]).astype(q.dtype)
    # flatten (B, K, G) -> BH; repeat kv per group
    qf = q.transpose(0, 2, 3, 1, 4).reshape(B * K * G, Sq, dh)
    kf = jnp.repeat(
        k.transpose(0, 2, 1, 3).reshape(B * K, Skv, dh), G, axis=0
    )
    vf = jnp.repeat(
        v.transpose(0, 2, 1, 3).reshape(B * K, Skv, dh), G, axis=0
    )
    qf, Sq0 = pad_to(qf, 1, bq)
    kf, _ = pad_to(kf, 1, bk)
    vf, _ = pad_to(vf, 1, bk)
    # padded KV columns must not contribute: they are masked by causality
    # when Sq == Skv (self-attention); assert that contract here
    out = fa.flash_attention(qf, kf, vf, causal=causal, bq=bq, bk=bk)
    out = out[:, :Sq0]
    return out.reshape(B, K, G, Sq0, dh).transpose(0, 3, 1, 2, 4)


def _pallas_wkv_chunk(r, k, v, lw, u, s0, chunk):
    return wk.wkv_chunk(r, k, v, lw, u, s0, chunk=chunk)


def register():
    tpu = ("tpu",)
    dispatch.register_impl("mac_matmul_int8", "pallas", _pallas_mac_matmul_int8,
                           platforms=tpu)
    dispatch.register_impl("fused_conv", "pallas", _pallas_fused_conv,
                           platforms=tpu)
    dispatch.register_impl("depthwise_conv", "pallas",
                           _pallas_depthwise_conv, platforms=tpu)
    dispatch.register_impl("sep_block", "pallas", _pallas_sep_block,
                           platforms=tpu)
    dispatch.register_impl("matmul_epilogue", "pallas", _pallas_matmul_epilogue,
                           platforms=tpu)
    dispatch.register_impl("pool", "pallas", _pallas_pool, platforms=tpu)
    dispatch.register_impl("residual_rmsnorm", "pallas",
                           _pallas_residual_rmsnorm, platforms=tpu)
    dispatch.register_impl("flash_attention", "pallas",
                           _pallas_flash_attention, platforms=tpu)
    dispatch.register_impl("wkv_chunk", "pallas", _pallas_wkv_chunk,
                           platforms=tpu)


register()
