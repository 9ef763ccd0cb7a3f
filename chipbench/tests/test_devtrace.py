"""The reduction from a profiler trace to busy time, kernel time and
labelled idle gaps."""
import pytest

from chipbench import devtrace
from chipbench.devtrace import Event, Plane


def _planes():
    host = Plane("/host:CPU", {"python": [
        Event(devtrace.WINDOW_SPAN, 1.0, 2.0),
        Event("chipbench.submit", 1.05, 1.2),
        Event("chipbench.receive", 1.6, 1.9),
        Event("PjitFunction", 1.3, 1.45),
    ]})
    dev = Plane("/device:TPU:0", {
        devtrace.OPS_LINE: [
            # starts before the window
            Event("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)", 0.9, 1.1),
            Event("%fused_conv.7 = f32[2,8,8,128]{3,2,1,0} custom-call("
                  "s8[2,1,10,10,128] %a), custom_call_target="
                  '"tpu_custom_call"', 1.2, 1.3),
            Event("%fused_conv.8 = f32[2,8,8,128]{3,2,1,0} custom-call()",
                  1.25, 1.4),
            Event("%fusion.12.clone = f32[8]{0} fusion()", 1.5, 1.6),
            # ends after the window
            Event("%copy.2 = f32[8]{0} copy(f32[8]{0} %x)", 1.95, 2.2),
        ],
        devtrace.MODULES_LINE: [Event("jit_f", 1.2, 1.6),
                                Event("jit_f", 1.95, 2.2)],
    })
    return [host, dev]


def test_busy_is_the_union_clipped_to_the_window():
    r = devtrace.reduce(_planes())
    assert r.window == (1.0, 2.0) and r.window_s == pytest.approx(1.0)
    # [1.0,1.1] + [1.2,1.4] + [1.5,1.6] + [1.95,2.0]
    assert r.busy_s == pytest.approx(0.1 + 0.2 + 0.1 + 0.05)
    assert r.executions == 2
    assert r.kernel_s["fused_conv"] == pytest.approx(0.1 + 0.15)
    assert r.kernel_s["fusion"] == pytest.approx(0.1 + 0.1)
    assert r.kernel_calls["fused_conv"] == (2, pytest.approx(0.25))
    # events that started before the window are not counted as calls
    assert r.kernel_calls["fusion"] == (1, pytest.approx(0.1))


def test_idle_gaps_are_labelled_by_the_host_span_over_them():
    r = devtrace.reduce(_planes())
    gaps = [(round(s, 6), label) for s, label in r.gaps]
    assert gaps[0] == (0.35, "chipbench.receive | -")  # 1.6 .. 1.95
    assert (0.1, "chipbench.submit | -") in gaps  # 1.1 .. 1.2
    assert (0.1, "- | PjitFunction") in gaps  # 1.4 .. 1.5
    b = r.breakdown()
    assert b["device_ops"][0] == ["fused_conv", pytest.approx(0.25)]
    assert b["idle_gaps"][0] == ["chipbench.receive | -",
                                 pytest.approx(0.35)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_trace_without_its_window_or_device_is_refused():
    host, dev = _planes()
    with pytest.raises(ValueError):
        devtrace.reduce([dev])
    with pytest.raises(ValueError):
        devtrace.reduce([host])


def test_union_merges_overlaps():
    assert devtrace.union([(3, 4), (1, 2), (1.5, 2.5), (4, 5)]) == [
        [1, 2.5], [3, 5]]


@pytest.fixture(scope="module")
def recorded():
    """A 0.3 s trace of mobilenetv1-224.closed on one TPU v5e."""
    from chipbench.spec import HERE

    return devtrace.reduce(devtrace.load(HERE / "testdata"))


def test_a_recorded_chip_trace_reduces(recorded):
    r = recorded
    assert r.devices == 1
    assert r.window_s == pytest.approx(0.300064078)
    assert r.busy_s == pytest.approx(0.16199719, rel=1e-6)
    assert r.executions == 15
    # 13 sep_block sites per step, one stem fused_conv, one head GEMM
    assert r.kernel_calls["sep_block"][0] == 182
    assert r.kernel_calls["fused_conv"][0] == 15
    assert r.kernel_calls["matmul_epilogue"][0] == 14
    ops = dict(r.breakdown()["device_ops"])
    assert max(ops, key=ops.get) == "sep_block"
    assert all(label.startswith("chipbench.") for label, _ in
               r.breakdown()["idle_gaps"])


def _ctx(recorded, scale=1.0):
    import functools
    import json
    from types import SimpleNamespace

    import jax
    import numpy as np

    from chipbench import work
    from chipbench.spec import HERE, load_module

    cfg = json.loads((HERE / "configs" / "mobilenetv1-224.json").read_text())
    ref = load_module(HERE, "refs", cfg["reference"])
    params = jax.eval_shape(functools.partial(ref.init, cfg=cfg),
                            jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((32, 224, 224, 3), np.float32)
    sites = [dict(s, flops=s["flops"] * scale, bytes=s["bytes"] * scale)
             for s in work.sites(lambda p, x: ref.forward(p, x, cfg),
                                 params, x)]
    peaks = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]
    return SimpleNamespace(
        trace=recorded, peaks=peaks, site_work=lambda b: sites,
        cell=SimpleNamespace(traffic={"serving": {"buckets": [32]}}))


def test_the_sep_block_roofline_of_the_recorded_trace(recorded):
    from chipbench.roofline import kernel_roofline

    share = kernel_roofline(_ctx(recorded), "sep_block")
    assert 2.5 < share < 3.5  # 3.06% read on the chip
    assert kernel_roofline(_ctx(recorded), "no_such_kernel") is None


def test_work_counted_too_high_is_an_error(recorded):
    from chipbench.context import ShareError
    from chipbench.roofline import kernel_roofline

    with pytest.raises(ShareError):
        kernel_roofline(_ctx(recorded, scale=40.0), "sep_block")
