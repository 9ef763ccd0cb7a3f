"""ResNet-50 (torchvision v1.5 bottlenecks: the stride on the 3x3 conv),
plain float32, in the layout of ``repro.models.cnn.resnet50_init``.

Departures from torchvision, which are the program's and so the
reference's: every conv pads SAME (torchvision pads the 7x7 stem by 3),
the 3x3 stride-2 max pool pads nothing (112 -> 55, not 56), batchnorm is
folded to a per-channel affine, and the head has ``num_classes`` outputs.
Sizes come from the configuration file: ``stem_width``, ``stages`` as
``[blocks, width, stride]``, ``expansion``, ``num_classes``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.refs.common import (
    bn, bn_p, conv, conv_w, dense, head_p, maxpool, relu, site,
)


def init(key, cfg):
    keys = iter(jax.random.split(key, 512))
    cin = cfg["stem_width"]
    p = {"stem": {"w": conv_w(next(keys), 7, 7, cfg["in_shape"][2], cin),
                  "bn": bn_p(next(keys), cin)}}
    stages = []
    for n_blocks, width, stride in cfg["stages"]:
        blocks = []
        cout = width * cfg["expansion"]
        for b in range(n_blocks):
            s = stride if b == 0 else 1
            blk = {
                "c1": {"w": conv_w(next(keys), 1, 1, cin, width),
                       "bn": bn_p(next(keys), width)},
                "c2": {"w": conv_w(next(keys), 3, 3, width, width),
                       "bn": bn_p(next(keys), width)},
                # small scales on a block's last batchnorm, as training
                # leaves them, so the skip path keeps the signal's size
                "c3": {"w": conv_w(next(keys), 1, 1, width, cout),
                       "bn": bn_p(next(keys), cout, 0.1, 0.3)},
            }
            if s != 1 or cin != cout:
                blk["proj"] = {"w": conv_w(next(keys), 1, 1, cin, cout),
                               "bn": bn_p(next(keys), cout)}
            blocks.append(blk)
            cin = cout
        stages.append(blocks)
    p["stages"] = stages
    p["head"] = head_p(next(keys), cin, cfg["num_classes"])
    return p


def _gemm_or_conv(stride):
    # a 1x1 stride-1 conv is a GEMM over pixels: the program serves it with
    # matmul_epilogue, every other conv with fused_conv
    return "matmul_epilogue" if stride == 1 else "fused_conv"


def forward(p, x, cfg, bits=None):
    with site("fused_conv"):
        x = relu(bn(conv(x, p["stem"]["w"], 2, bits=bits), p["stem"]["bn"]))
    with site("maxpool"):
        x = maxpool(x, 3, 2)
    for stage, (_, _, stage_stride) in zip(p["stages"], cfg["stages"]):
        for b, blk in enumerate(stage):
            stride = stage_stride if b == 0 else 1
            with site("matmul_epilogue"):
                y = relu(bn(conv(x, blk["c1"]["w"], bits=bits),
                            blk["c1"]["bn"]))
            with site("fused_conv"):
                y = relu(bn(conv(y, blk["c2"]["w"], stride, bits=bits),
                            blk["c2"]["bn"]))
            res = x
            if "proj" in blk:
                with site(_gemm_or_conv(stride)):
                    res = bn(conv(x, blk["proj"]["w"], stride, bits=bits),
                             blk["proj"]["bn"])
            with site("matmul_epilogue"):
                x = relu(bn(conv(y, blk["c3"]["w"], bits=bits),
                            blk["c3"]["bn"]) + res)
    with site("global_avgpool"):
        x = jnp.mean(x, axis=(1, 2))
    with site("matmul_epilogue"):
        return dense(x, p["head"]["w"], p["head"]["b"], bits=bits)
