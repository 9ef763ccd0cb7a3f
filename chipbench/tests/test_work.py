"""Work counts from the references' jaxprs, and the >100% rule."""
import collections
import functools
import json

import jax
import numpy as np
import pytest

from chipbench import work
from chipbench.context import ShareError, share_percent
from chipbench.spec import HERE, load_module


def _sites(config: str, batch: int, **override):
    cfg = json.loads((HERE / "configs" / f"{config}.json").read_text())
    cfg.update(override)
    ref = load_module(HERE, "refs", cfg["reference"])
    params = jax.eval_shape(functools.partial(ref.init, cfg=cfg),
                            jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((batch, *cfg["in_shape"]), np.float32)
    return cfg, work.sites(lambda p, x: ref.forward(p, x, cfg), params, x)


@pytest.mark.parametrize("config,gflop,kernels", [
    ("resnet50-224", 8.12, {"fused_conv": 20, "matmul_epilogue": 34,
                            "maxpool": 1, "global_avgpool": 1}),
    ("mobilenetv1-224", 1.135, {"fused_conv": 1, "sep_block": 13,
                                "global_avgpool": 1, "matmul_epilogue": 1}),
])
def test_flops_per_image_with_a_two_way_head(config, gflop, kernels):
    """The published trunk with the paper's 2-way head: 8.12 and 1.135
    GFLOP per image, and the program's kernel count per kernel."""
    _, sites = _sites(config, 1, num_classes=2)
    total = sum(s["flops"] for s in sites)
    assert total / 1e9 == pytest.approx(gflop, rel=1e-3)
    assert collections.Counter(s["kernel"] for s in sites) == kernels


@pytest.mark.parametrize("config", ["resnet50-224", "mobilenetv1-224"])
def test_flops_scale_with_batch_and_head(config):
    cfg, one = _sites(config, 1)
    _, four = _sites(config, 4)
    _, two_way = _sites(config, 1, num_classes=2)
    f1 = sum(s["flops"] for s in one)
    assert sum(s["flops"] for s in four) == pytest.approx(4 * f1)
    cin = {"resnet50-224": 2048, "mobilenetv1-224": 1024}[config]
    head = 2 * cin * (cfg["num_classes"] - 2)
    assert f1 - sum(s["flops"] for s in two_way) == pytest.approx(head)


def test_site_bytes_count_operands_and_results_once():
    """The stem: input, weights, batchnorm and output at one byte each."""
    _, sites = _sites("mobilenetv1-224", 2)
    stem = sites[0]
    assert stem["kernel"] == "fused_conv"
    n_in = 2 * 224 * 224 * 3
    n_w = 3 * 3 * 3 * 32 + 2 * 32
    n_out = 2 * 112 * 112 * 32
    assert stem["bytes"] == n_in + n_w + n_out
    assert stem["flops"] == 2 * n_out * 27


def test_least_time_takes_the_larger_bound():
    peak = {"int8_ops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    sites = [{"kernel": "k", "flops": 1000.0, "bytes": 50.0},
             {"kernel": "k", "flops": 100.0, "bytes": 200.0},
             {"kernel": "other", "flops": 1e9, "bytes": 1e9}]
    assert work.least_seconds(sites, "k", peak) == 10.0 + 20.0


def test_a_share_over_100_percent_is_an_error():
    assert share_percent(1.0, 4.0, "x") == 25.0
    assert share_percent(1.0, 1.0, "x") == 100.0
    assert share_percent(1.0, 0.0, "x") is None
    with pytest.raises(ShareError):
        share_percent(1.0001, 1.0, "x_roofline")
