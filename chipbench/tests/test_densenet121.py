"""The DenseNet-121 configuration and reference: the published parameter
count, and the work per image grouped by the kernel serving each site."""
import collections
import functools
import json
import math

import jax
import numpy as np
import pytest

from chipbench import work
from chipbench.spec import HERE, load_module

CFG = json.loads((HERE / "configs" / "densenet121-224.json").read_text())
REF = load_module(HERE, "refs", CFG["reference"])


def _params(cfg):
    return jax.eval_shape(functools.partial(REF.init, cfg=cfg),
                          jax.random.PRNGKey(0))


def test_parameters_are_torchvisions():
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(_params(CFG)))
    assert n == CFG["parameters"] == 7978856


@pytest.mark.parametrize("classes,gflop", [(1000, 5.237973), (2, 5.235929)])
def test_work_per_image_by_kernel(classes, gflop):
    """58 bottleneck and 3 transition GEMMs with their BN-ReLU prologue,
    the stem and 58 3x3 convs, the pools and the head."""
    cfg = dict(CFG, num_classes=classes)
    x = jax.ShapeDtypeStruct((1, *cfg["in_shape"]), np.float32)
    sites = work.sites(lambda p, x: REF.forward(p, x, cfg), _params(cfg), x)
    assert sum(s["flops"] for s in sites) / 1e9 == pytest.approx(gflop,
                                                                 rel=1e-6)
    assert collections.Counter(s["kernel"] for s in sites) == {
        "fused_conv": 59, "maxpool": 1, "preact_matmul": 61, "avgpool": 3,
        "global_avgpool": 1, "matmul_epilogue": 1}
    preact = sum(s["flops"] for s in sites if s["kernel"] == "preact_matmul")
    assert preact / 1e9 == pytest.approx(2.675253, rel=1e-6)
