"""The paper's own model class: CNNs, for the faithful reproduction.

LeNet-5* follows Table 9 exactly; the other five follow the paper's setup:
64x64x3 inputs, binary Car/NotCar head (transfer-learning head, paper §II.A.2),
inference graphs with BN folded to affine scale/shift (post-training deploy).
Convs and dense layers go through the dispatch patterns so the MARVEL flow
(profile -> extensions -> rewrite) applies to them exactly as to the LMs.
The mobile models emit their depthwise-separable blocks as single
``sep_block`` sites (fusable dw->pw at v3+, stage-wise dw_mac/conv_mac
below), and 1x1 stride-1 convs dispatch as matmul_epilogue GEMMs.  All
pooling (windowed max/avg + global-avg) goes through ``pool`` sites (pool
extension, v2+), and ResNet50's bottleneck skip-adds ride the conv/GEMM
epilogues as ``residual=`` operands (acc_mac, fused in-register at v3+).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import dispatch
from repro.models.layers import ACTS, dense_init


# ---------------------------------------------------------------------------
# primitives (dispatch-routed)
# ---------------------------------------------------------------------------


def _conv_ref(x, w, b, *, stride, padding, groups, act, scale=None,
              shift=None, residual=None):
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape, ("NHWC", "HWIO", "NHWC"))
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding, dimension_numbers=dn,
        feature_group_count=groups,
    )
    if b is not None:
        y = y + b
    if scale is not None:
        y = y * scale
    if shift is not None:
        y = y + shift
    if residual is not None:
        y = y + residual
    return ACTS[act](y)


def _conv1x1_as_matmul(x, w, b, *, act, scale, shift, residual=None,
                       pre_scale=None, pre_shift=None):
    """A 1x1 stride-1 conv IS a GEMM over pixels — dispatch it as one.

    The (1, 1, Cin, Cout) kernel becomes a (Cin, Cout) matrix contracted
    over the channel axis (``x @ w`` batches over N, H; the Pallas wrapper
    flattens NHWC -> (N*H*W, Cin) internally), and the bias/BN epilogue
    rides along in the pattern, so the site dispatches as matmul_epilogue
    (fusedmac) instead of an im2col conv (DenseNet/ResNet bottlenecks,
    MobileNetV2 expansions)."""
    return dense(x, w.reshape(w.shape[2], w.shape[3]), b, act=act,
                 scale=scale, shift=shift, residual=residual,
                 pre_scale=pre_scale, pre_shift=pre_shift)


def conv2d(x, w, b=None, *, stride=1, padding="SAME", groups=1, act="none",
           scale=None, shift=None, residual=None, pre_scale=None,
           pre_shift=None):
    """Conv + bias + folded-BN affine (+ residual-add) + act: one
    conv_mac/fusedmac site.

    ``scale``/``shift`` carry the folded batchnorm so the whole post-conv
    epilogue sits *inside* the dispatch pattern and can fuse into the
    fused_conv kernel (one HBM round-trip instead of four).  ``residual``
    carries a skip tensor of the conv's output shape: the add happens
    before ``act`` inside the pattern, so at v3+ the acc_mac epilogue
    accumulates it in-register instead of round-tripping the conv output
    through HBM.  1x1 stride-1 convs are rerouted to the matmul_epilogue
    pattern at trace time (see :func:`_conv1x1_as_matmul`) — they are
    GEMMs, not convolutions.  ``pre_scale``/``pre_shift`` carry a
    pre-activation BN-ReLU of the conv's *input* (DenseNet's BN-ReLU-conv)
    into the GEMM as its prologue; only those 1x1 GEMMs take one.
    """
    if (groups == 1 and x.ndim == 4 and stride == 1
            and w.shape[0] == w.shape[1] == 1
            and padding in ("SAME", "VALID")):
        return _conv1x1_as_matmul(x, w, b, act=act, scale=scale, shift=shift,
                                  residual=residual, pre_scale=pre_scale,
                                  pre_shift=pre_shift)
    if pre_scale is not None or pre_shift is not None:
        raise ValueError("a pre-activation prologue rides only 1x1 "
                         "stride-1 convs (GEMMs)")
    return dispatch.call(
        "fused_conv", _conv_ref, x, w, b,
        stride=stride, padding=padding, groups=groups, act=act,
        scale=scale, shift=shift, residual=residual,
    )


def _depthwise_ref(x, w, b, *, stride, padding, act, scale=None, shift=None):
    return _conv_ref(x, w, b, stride=stride, padding=padding,
                     groups=x.shape[-1], act=act, scale=scale, shift=shift)


def depthwise_conv2d(x, w, b=None, *, stride=1, padding="SAME", act="none",
                     scale=None, shift=None):
    """Depthwise conv (+ fused epilogue): one dw_mac site.

    ``groups == channels`` is implied by the (KH, KW, 1, C) weight shape;
    the per-channel (KH, KW) MAC is the loop form generic GEMM datapaths
    cannot express, so it carries its own extension (``dw_mac``, v2+).
    """
    return dispatch.call(
        "depthwise_conv", _depthwise_ref, x, w, b,
        stride=stride, padding=padding, act=act, scale=scale, shift=shift,
    )


def _sep_block_ref(x, w_dw, w_pw, *, stride, padding, dw_scale, dw_shift,
                   dw_act, pw_bias, pw_scale, pw_shift, pw_act):
    # the unfused form decomposes into the two stage *patterns*, so below
    # v3 the depthwise (v2+) and pointwise (v1+) kernels still apply and the
    # only cost of not fusing is the HBM round-trip of the intermediate
    y = depthwise_conv2d(x, w_dw, stride=stride, padding=padding, act=dw_act,
                         scale=dw_scale, shift=dw_shift)
    return dispatch.call(
        "fused_conv", _conv_ref, y, w_pw, pw_bias, stride=1, padding="SAME",
        groups=1, act=pw_act, scale=pw_scale, shift=pw_shift,
    )


def sep_block(x, w_dw, w_pw, *, stride=1, padding="SAME", dw_scale=None,
              dw_shift=None, dw_act="relu", pw_bias=None, pw_scale=None,
              pw_shift=None, pw_act="none"):
    """Depthwise-separable block (dw 3x3 -> 1x1 pw) as ONE dispatch site.

    At v3+ the fused sep_block kernel keeps the depthwise output in VMEM and
    feeds the pointwise MXU contraction directly — the (N, Ho, Wo, C)
    intermediate never touches HBM.  Below v3 the baseline decomposition in
    :func:`_sep_block_ref` still dispatches each stage's own pattern.
    """
    return dispatch.call(
        "sep_block", _sep_block_ref, x, w_dw, w_pw,
        stride=stride, padding=padding, dw_scale=dw_scale, dw_shift=dw_shift,
        dw_act=dw_act, pw_bias=pw_bias, pw_scale=pw_scale, pw_shift=pw_shift,
        pw_act=pw_act,
    )


def _dense_ref(x, w, b, *, act, scale=None, shift=None, residual=None,
               pre_scale=None, pre_shift=None):
    if pre_scale is not None or pre_shift is not None:
        # the kernels' oracle owns the prologue's semantics (as pool_ref
        # owns the pools'); lazy for the same reason as in _pool_ref
        from repro.kernels.ref import preact_ref

        x = preact_ref(x, pre_scale, pre_shift)
    y = x @ w
    if b is not None:
        y = y + b
    if scale is not None:
        y = y * scale
    if shift is not None:
        y = y + shift
    if residual is not None:
        y = y + residual
    return ACTS[act](y)


def dense(x, w, b=None, *, act="none", scale=None, shift=None, residual=None,
          pre_scale=None, pre_shift=None):
    """GEMM + bias + optional folded-BN affine (+ residual-add) + act: one
    fusedmac site (the residual rides the acc_mac epilogue at v3+).
    ``pre_scale``/``pre_shift`` make ``relu(x*pre_scale + pre_shift)`` the
    GEMM's input inside the same site (a pre-activation prologue)."""
    return dispatch.call("matmul_epilogue", _dense_ref, x, w, b, act=act,
                         scale=scale, shift=shift, residual=residual,
                         pre_scale=pre_scale, pre_shift=pre_shift)


def _pool_ref(x, *, op, k=2, stride=2):
    # ref.pool_ref is the one source of truth for pool semantics (f32
    # accumulate; max keeps x.dtype, integer avg means return f32) — the
    # dispatch baseline and the kernel oracle must be the same function, so
    # v0/v1 can never drift from what the v2+ kernels are tested against.
    # Lazy import: model code otherwise depends only on repro.core.dispatch.
    from repro.kernels.ref import pool_ref

    return pool_ref(x, op=op, k=k, stride=stride)


def maxpool(x, k=2, stride=2):
    """Windowed max pool (VALID): one pool site (pool extension, v2+)."""
    return dispatch.call("pool", _pool_ref, x, op="max", k=k, stride=stride)


def avgpool_global(x):
    """Global average pool (N, H, W, C) -> (N, C): one pool site."""
    return dispatch.call("pool", _pool_ref, x, op="global_avg")


def avgpool2(x):
    """2x2 stride-2 average pool (VALID): one pool site."""
    return dispatch.call("pool", _pool_ref, x, op="avg", k=2, stride=2)


def _affine(x, s, b):  # folded batchnorm
    return x * s + b


# ---------------------------------------------------------------------------
# parameter init helpers
# ---------------------------------------------------------------------------


def _conv_init(key, kh, kw, cin, cout, groups=1):
    fan_in = kh * kw * cin // groups
    w = jax.random.normal(key, (kh, kw, cin // groups, cout)) / math.sqrt(fan_in)
    return w.astype(jnp.float32)


def _bn_init(c):
    return {"s": jnp.ones((c,), jnp.float32), "b": jnp.zeros((c,), jnp.float32)}


# ---------------------------------------------------------------------------
# LeNet-5* (paper Table 9)
# ---------------------------------------------------------------------------


def lenet5_init(key):
    ks = jax.random.split(key, 3)
    return {
        "c1": {"w": _conv_init(ks[0], 6, 6, 1, 12), "b": jnp.zeros((12,))},
        "c2": {"w": _conv_init(ks[1], 6, 6, 12, 32), "b": jnp.zeros((32,))},
        "fc": {"w": dense_init(ks[2], (512, 10), jnp.float32),
               "b": jnp.zeros((10,))},
    }


def lenet5_apply(p, x):
    """x: (B, 28, 28, 1) -> (B, 10)."""
    x = conv2d(x, p["c1"]["w"], p["c1"]["b"], stride=2, padding="VALID",
               act="relu")  # -> 12x12x12
    x = conv2d(x, p["c2"]["w"], p["c2"]["b"], stride=2, padding="VALID",
               act="relu")  # -> 4x4x32
    x = x.reshape(x.shape[0], -1)
    return dense(x, p["fc"]["w"], p["fc"]["b"])


# ---------------------------------------------------------------------------
# MobileNetV1 (depthwise separable; width 1.0, 64x64 input, 2-class head)
# ---------------------------------------------------------------------------

_MBV1_CFG = [  # (stride, cout) for each dw-separable block
    (1, 64), (2, 128), (1, 128), (2, 256), (1, 256),
    (2, 512), (1, 512), (1, 512), (1, 512), (1, 512), (1, 512),
    (2, 1024), (1, 1024),
]


def mobilenetv1_init(key):
    ks = iter(jax.random.split(key, 64))
    p = {"stem": {"w": _conv_init(next(ks), 3, 3, 3, 32), "bn": _bn_init(32)}}
    cin = 32
    blocks = []
    for stride, cout in _MBV1_CFG:
        blocks.append({
            "dw": {"w": _conv_init(next(ks), 3, 3, cin, cin, groups=cin),
                   "bn": _bn_init(cin)},
            "pw": {"w": _conv_init(next(ks), 1, 1, cin, cout),
                   "bn": _bn_init(cout)},
        })
        cin = cout
    p["blocks"] = blocks
    p["head"] = {"w": dense_init(next(ks), (cin, 2), jnp.float32),
                 "b": jnp.zeros((2,))}
    return p


def mobilenetv1_apply(p, x):
    x = conv2d(x, p["stem"]["w"], stride=2, scale=p["stem"]["bn"]["s"],
               shift=p["stem"]["bn"]["b"], act="relu")
    for blk, (stride, _) in zip(p["blocks"], _MBV1_CFG):
        x = sep_block(x, blk["dw"]["w"], blk["pw"]["w"], stride=stride,
                      dw_scale=blk["dw"]["bn"]["s"],
                      dw_shift=blk["dw"]["bn"]["b"], dw_act="relu",
                      pw_scale=blk["pw"]["bn"]["s"],
                      pw_shift=blk["pw"]["bn"]["b"], pw_act="relu")
    x = avgpool_global(x)
    return dense(x, p["head"]["w"], p["head"]["b"])


# ---------------------------------------------------------------------------
# VGG16 (64x64 input)
# ---------------------------------------------------------------------------

_VGG_CFG = [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
            512, 512, 512, "M", 512, 512, 512, "M"]


def vgg16_init(key):
    ks = iter(jax.random.split(key, 32))
    convs = []
    cin = 3
    for c in _VGG_CFG:
        if c == "M":
            continue
        convs.append({"w": _conv_init(next(ks), 3, 3, cin, c),
                      "b": jnp.zeros((c,))})
        cin = c
    return {
        "convs": convs,
        "fc1": {"w": dense_init(next(ks), (512 * 2 * 2, 512), jnp.float32),
                "b": jnp.zeros((512,))},
        "fc2": {"w": dense_init(next(ks), (512, 2), jnp.float32),
                "b": jnp.zeros((2,))},
    }


def vgg16_apply(p, x):
    ci = 0
    for c in _VGG_CFG:
        if c == "M":
            x = maxpool(x)
        else:
            blk = p["convs"][ci]
            x = conv2d(x, blk["w"], blk["b"], act="relu")
            ci += 1
    x = x.reshape(x.shape[0], -1)
    x = dense(x, p["fc1"]["w"], p["fc1"]["b"], act="relu")
    return dense(x, p["fc2"]["w"], p["fc2"]["b"])


# ---------------------------------------------------------------------------
# ResNet50 (bottlenecks; 64x64 input)
# ---------------------------------------------------------------------------

_R50_STAGES = [(3, 64, 1), (4, 128, 2), (6, 256, 2), (3, 512, 2)]


def resnet50_init(key):
    ks = iter(jax.random.split(key, 256))
    p = {"stem": {"w": _conv_init(next(ks), 7, 7, 3, 64), "bn": _bn_init(64)}}
    cin = 64
    stages = []
    for n_blocks, width, stride in _R50_STAGES:
        blocks = []
        for b in range(n_blocks):
            s = stride if b == 0 else 1
            cout = width * 4
            blk = {
                "c1": {"w": _conv_init(next(ks), 1, 1, cin, width),
                       "bn": _bn_init(width)},
                "c2": {"w": _conv_init(next(ks), 3, 3, width, width),
                       "bn": _bn_init(width)},
                "c3": {"w": _conv_init(next(ks), 1, 1, width, cout),
                       "bn": _bn_init(cout)},
            }
            if s != 1 or cin != cout:
                blk["proj"] = {"w": _conv_init(next(ks), 1, 1, cin, cout),
                               "bn": _bn_init(cout)}
            blocks.append(blk)
            cin = cout
        stages.append(blocks)
    p["stages"] = stages
    p["head"] = {"w": dense_init(next(ks), (cin, 2), jnp.float32),
                 "b": jnp.zeros((2,))}
    return p


def resnet50_apply(p, x):
    x = conv2d(x, p["stem"]["w"], stride=2, scale=p["stem"]["bn"]["s"],
               shift=p["stem"]["bn"]["b"], act="relu")
    x = maxpool(x, 3, 2)
    for stage, (n_blocks, width, stage_stride) in zip(p["stages"], _R50_STAGES):
        for bi, blk in enumerate(stage):
            s = stage_stride if bi == 0 else 1
            res = x
            y = conv2d(x, blk["c1"]["w"], scale=blk["c1"]["bn"]["s"],
                       shift=blk["c1"]["bn"]["b"], act="relu")
            y = conv2d(y, blk["c2"]["w"], stride=s, scale=blk["c2"]["bn"]["s"],
                       shift=blk["c2"]["bn"]["b"], act="relu")
            if "proj" in blk:
                res = conv2d(x, blk["proj"]["w"], stride=s,
                             scale=blk["proj"]["bn"]["s"],
                             shift=blk["proj"]["bn"]["b"])
            # the skip-add + relu ride INSIDE the c3 site (acc_mac epilogue):
            # at v3+ the add happens on the accumulator tile in-register —
            # no standalone skip-add HBM round-trip anywhere in the graph
            x = conv2d(y, blk["c3"]["w"], scale=blk["c3"]["bn"]["s"],
                       shift=blk["c3"]["bn"]["b"], act="relu", residual=res)
    x = avgpool_global(x)
    return dense(x, p["head"]["w"], p["head"]["b"])


# ---------------------------------------------------------------------------
# MobileNetV2 (inverted residuals)
# ---------------------------------------------------------------------------

_MBV2_CFG = [  # (expand, cout, n, stride)
    (1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2),
    (6, 64, 4, 2), (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1),
]
# flattened per-block static (expand, stride) list
_MBV2_FLAT = [
    (expand, stride if b == 0 else 1)
    for expand, cout, n, stride in _MBV2_CFG
    for b in range(n)
]


def mobilenetv2_init(key):
    ks = iter(jax.random.split(key, 256))
    p = {"stem": {"w": _conv_init(next(ks), 3, 3, 3, 32), "bn": _bn_init(32)}}
    cin = 32
    blocks = []
    for expand, cout, n, stride in _MBV2_CFG:
        for b in range(n):
            mid = cin * expand
            blk = {}
            if expand != 1:
                blk["ex"] = {"w": _conv_init(next(ks), 1, 1, cin, mid),
                             "bn": _bn_init(mid)}
            blk["dw"] = {"w": _conv_init(next(ks), 3, 3, mid, mid, groups=mid),
                         "bn": _bn_init(mid)}
            blk["pw"] = {"w": _conv_init(next(ks), 1, 1, mid, cout),
                         "bn": _bn_init(cout)}
            blocks.append(blk)
            cin = cout
    p["blocks"] = blocks
    p["last"] = {"w": _conv_init(next(ks), 1, 1, cin, 1280),
                 "bn": _bn_init(1280)}
    p["head"] = {"w": dense_init(next(ks), (1280, 2), jnp.float32),
                 "b": jnp.zeros((2,))}
    return p


def mobilenetv2_apply(p, x):
    x = conv2d(x, p["stem"]["w"], stride=2, scale=p["stem"]["bn"]["s"],
               shift=p["stem"]["bn"]["b"], act="relu6")
    for blk, (expand, stride) in zip(p["blocks"], _MBV2_FLAT):
        res = x
        y = x
        if expand != 1:
            y = conv2d(y, blk["ex"]["w"], scale=blk["ex"]["bn"]["s"],
                       shift=blk["ex"]["bn"]["b"], act="relu6")
        y = sep_block(y, blk["dw"]["w"], blk["pw"]["w"], stride=stride,
                      dw_scale=blk["dw"]["bn"]["s"],
                      dw_shift=blk["dw"]["bn"]["b"], dw_act="relu6",
                      pw_scale=blk["pw"]["bn"]["s"],
                      pw_shift=blk["pw"]["bn"]["b"], pw_act="none")
        if stride == 1 and res.shape == y.shape:
            y = y + res
        x = y
    x = conv2d(x, p["last"]["w"], scale=p["last"]["bn"]["s"],
               shift=p["last"]["bn"]["b"], act="relu6")
    x = avgpool_global(x)
    return dense(x, p["head"]["w"], p["head"]["b"])


# ---------------------------------------------------------------------------
# DenseNet121 (growth 32)
# ---------------------------------------------------------------------------

_DN_CFG = [6, 12, 24, 16]
_GROWTH = 32


def densenet121_init(key):
    ks = iter(jax.random.split(key, 512))
    p = {"stem": {"w": _conv_init(next(ks), 7, 7, 3, 64), "bn": _bn_init(64)}}
    cin = 64
    blocks = []
    for bi, n_layers in enumerate(_DN_CFG):
        layers_ = []
        for _ in range(n_layers):
            layers_.append({
                "bn1": _bn_init(cin),
                "c1": {"w": _conv_init(next(ks), 1, 1, cin, 4 * _GROWTH)},
                "bn2": _bn_init(4 * _GROWTH),
                "c2": {"w": _conv_init(next(ks), 3, 3, 4 * _GROWTH, _GROWTH)},
            })
            cin += _GROWTH
        block = {"layers": layers_}
        if bi < len(_DN_CFG) - 1:
            block["trans"] = {"bn": _bn_init(cin),
                              "w": _conv_init(next(ks), 1, 1, cin, cin // 2)}
            cin = cin // 2
        blocks.append(block)
    p["blocks"] = blocks
    p["bn_f"] = _bn_init(cin)
    p["head"] = {"w": dense_init(next(ks), (cin, 2), jnp.float32),
                 "b": jnp.zeros((2,))}
    return p


def densenet121_apply(p, x):
    # the dense layers are pre-activation (BN-relu-conv): each 1x1 GEMM
    # takes the BN-relu before it as its prologue and, in a bottleneck, the
    # BN-relu after it as its epilogue, so both ride one matmul_epilogue
    # site; the 3x3 conv and the concatenation stay outside it
    x = conv2d(x, p["stem"]["w"], stride=2, scale=p["stem"]["bn"]["s"],
               shift=p["stem"]["bn"]["b"], act="relu")
    x = maxpool(x, 3, 2)
    for block in p["blocks"]:
        for lyr in block["layers"]:
            y = conv2d(x, lyr["c1"]["w"], pre_scale=lyr["bn1"]["s"],
                       pre_shift=lyr["bn1"]["b"], scale=lyr["bn2"]["s"],
                       shift=lyr["bn2"]["b"], act="relu")
            y = conv2d(y, lyr["c2"]["w"])
            x = jnp.concatenate([x, y], axis=-1)
        if "trans" in block:
            x = conv2d(x, block["trans"]["w"],
                       pre_scale=block["trans"]["bn"]["s"],
                       pre_shift=block["trans"]["bn"]["b"])
            x = avgpool2(x)
    x = ACTS["relu"](_affine(x, p["bn_f"]["s"], p["bn_f"]["b"]))
    x = avgpool_global(x)
    return dense(x, p["head"]["w"], p["head"]["b"])


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

CNN_MODELS = {
    "lenet5": (lenet5_init, lenet5_apply, (28, 28, 1)),
    "mobilenetv1": (mobilenetv1_init, mobilenetv1_apply, (64, 64, 3)),
    "resnet50": (resnet50_init, resnet50_apply, (64, 64, 3)),
    "vgg16": (vgg16_init, vgg16_apply, (64, 64, 3)),
    "mobilenetv2": (mobilenetv2_init, mobilenetv2_apply, (64, 64, 3)),
    "densenet121": (densenet121_init, densenet121_apply, (64, 64, 3)),
}


def get_cnn(name: str):
    init, apply, in_shape = CNN_MODELS[name]
    return init, apply, in_shape
