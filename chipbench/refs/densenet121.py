"""DenseNet-121 (Huang et al., arXiv:1608.06993, as torchvision builds it),
plain float32, in the layout of ``repro.models.cnn.densenet121_init``.

A dense layer is BN-relu-conv1x1(``bn_size`` x ``growth_rate``)-BN-relu-
conv3x3(``growth_rate``), its output concatenated onto every map before it
in the block; a transition is BN-relu-conv1x1 to ``compression`` of the
channels, then a 2x2/2 average pool; the trunk ends in BN-relu and a global
average pool.  The 1x1 GEMM's site holds the BN-relu before it (the
program's ``preact_matmul`` prologue) and, in a dense layer, the BN-relu
after it (its epilogue); the concatenation lies outside every site.

Departures from torchvision, which are the program's and so the
reference's: every conv pads SAME (torchvision pads the 7x7 stem by 3),
the 3x3 stride-2 max pool pads nothing (112 -> 55, not 56, so the blocks
run at 55, 27, 13 and 6), batchnorm is folded to a per-channel affine, and
the head has ``num_classes`` outputs.  Sizes come from the configuration
file, under torchvision's names: ``num_init_features``, ``growth_rate``,
``block_config``, ``bn_size``, and ``compression``, ``num_classes``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.refs.common import (
    bn, bn_p, conv, conv_w, dense, head_p, maxpool, relu, site,
)


def init(key, cfg):
    keys = iter(jax.random.split(key, 512))
    k, width = cfg["growth_rate"], cfg["bn_size"] * cfg["growth_rate"]
    cin = cfg["num_init_features"]
    p = {"stem": {"w": conv_w(next(keys), 7, 7, cfg["in_shape"][2], cin),
                  "bn": bn_p(next(keys), cin)}}
    blocks = []
    for b, n_layers in enumerate(cfg["block_config"]):
        layers = []
        for _ in range(n_layers):
            layers.append({
                "bn1": bn_p(next(keys), cin),
                "c1": {"w": conv_w(next(keys), 1, 1, cin, width)},
                "bn2": bn_p(next(keys), width),
                "c2": {"w": conv_w(next(keys), 3, 3, width, k)},
            })
            cin += k
        block = {"layers": layers}
        if b < len(cfg["block_config"]) - 1:
            cout = int(cin * cfg["compression"])
            block["trans"] = {"bn": bn_p(next(keys), cin),
                              "w": conv_w(next(keys), 1, 1, cin, cout)}
            cin = cout
        blocks.append(block)
    p["blocks"] = blocks
    p["bn_f"] = bn_p(next(keys), cin)
    p["head"] = head_p(next(keys), cin, cfg["num_classes"])
    return p


def avgpool(x, k: int, stride: int):
    return jax.lax.reduce_window(x, 0.0, jax.lax.add, (1, k, k, 1),
                                 (1, stride, stride, 1), "VALID") / (k * k)


def forward(p, x, cfg, bits=None):
    with site("fused_conv"):
        x = relu(bn(conv(x, p["stem"]["w"], 2, bits=bits), p["stem"]["bn"]))
    with site("maxpool"):
        x = maxpool(x, 3, 2)
    for block in p["blocks"]:
        for lyr in block["layers"]:
            with site("preact_matmul"):
                y = relu(bn(conv(relu(bn(x, lyr["bn1"])), lyr["c1"]["w"],
                                 bits=bits), lyr["bn2"]))
            with site("fused_conv"):
                y = conv(y, lyr["c2"]["w"], bits=bits)
            x = jnp.concatenate([x, y], axis=-1)
        if "trans" in block:
            with site("preact_matmul"):
                x = conv(relu(bn(x, block["trans"]["bn"])),
                         block["trans"]["w"], bits=bits)
            with site("avgpool"):
                x = avgpool(x, 2, 2)
    with site("global_avgpool"):
        x = jnp.mean(relu(bn(x, p["bn_f"])), axis=(1, 2))
    with site("matmul_epilogue"):
        return dense(x, p["head"]["w"], p["head"]["b"], bits=bits)
