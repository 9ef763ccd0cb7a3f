"""MobileNetV1 (width 1.0), plain float32, in the layout of
``repro.models.cnn.mobilenetv1_init``.

Each depthwise-separable block (3x3 depthwise, folded batchnorm, relu,
1x1 pointwise, folded batchnorm, relu) is one site, as the program serves
it with one ``sep_block`` kernel.  Sizes come from the configuration file:
``stem_width``, ``blocks`` as ``[stride, out_channels]``, ``num_classes``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.refs.common import (
    bn, bn_p, conv, conv_w, dense, head_p, relu, site,
)


def init(key, cfg):
    keys = iter(jax.random.split(key, 128))
    cin = cfg["stem_width"]
    p = {"stem": {"w": conv_w(next(keys), 3, 3, cfg["in_shape"][2], cin),
                  "bn": bn_p(next(keys), cin)}}
    blocks = []
    for _, cout in cfg["blocks"]:
        blocks.append({
            "dw": {"w": conv_w(next(keys), 3, 3, 1, cin),
                   "bn": bn_p(next(keys), cin)},
            "pw": {"w": conv_w(next(keys), 1, 1, cin, cout),
                   "bn": bn_p(next(keys), cout)},
        })
        cin = cout
    p["blocks"] = blocks
    p["head"] = head_p(next(keys), cin, cfg["num_classes"])
    return p


def forward(p, x, cfg, bits=None):
    with site("fused_conv"):
        x = relu(bn(conv(x, p["stem"]["w"], 2, bits=bits), p["stem"]["bn"]))
    for blk, (stride, _) in zip(p["blocks"], cfg["blocks"]):
        with site("sep_block"):
            y = relu(bn(conv(x, blk["dw"]["w"], stride, groups=x.shape[-1],
                             bits=bits), blk["dw"]["bn"]))
            x = relu(bn(conv(y, blk["pw"]["w"], bits=bits), blk["pw"]["bn"]))
    with site("global_avgpool"):
        x = jnp.mean(x, axis=(1, 2))
    with site("matmul_epilogue"):
        return dense(x, p["head"]["w"], p["head"]["b"], bits=bits)
