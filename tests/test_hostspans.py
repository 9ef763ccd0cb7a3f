"""The benchmark's readers of the serving program's own spans and counters
(``chipbench/hostspans.py`` and the metrics that use it), on synthetic
traces and counters: known overlaps give known shares, and a program
without the spans or counters gives None, never 0."""
from types import SimpleNamespace

import pytest

from chipbench import devtrace, hostspans
from chipbench.devtrace import Event, Plane
from chipbench.spec import HERE, load_module


def _planes(serve=True, idle_device=False):
    """Window [1, 2]; the device runs [1.2, 1.4], [1.45, 1.5] and
    [1.9, 2.1], so it idles over [1, 1.2], [1.4, 1.45] and [1.5, 1.9]."""
    compute = [
        Event("marvel.serve.stack", 1.0, 1.1),
        Event("marvel.serve.dispatch", 1.1, 1.15),
        Event("marvel.serve.result_wait", 1.15, 1.5),
        Event("marvel.serve.post", 1.5, 1.55),
        Event("marvel.serve.handoff", 1.55, 1.56),
        Event("marvel.serve.stack", 1.56, 1.7),
        Event("marvel.serve.dispatch", 1.7, 1.75),
        Event("marvel.serve.result_wait", 1.75, 2.2),
    ]
    loop = [Event(devtrace.WINDOW_SPAN, 1.0, 2.0),
            Event("chipbench.submit", 1.6, 1.62),
            Event("marvel.serve.resolve", 1.56, 1.58)]
    host = {"loop": loop if serve else loop[:2]}
    if serve:
        host["compute"] = compute
    planes = [Plane("/host:CPU", host), Plane("/device:TPU:0", {
        devtrace.OPS_LINE: [Event("%fused_conv.1 = f32[8] custom-call()",
                                  a, b)
                            for a, b in ((1.2, 1.4), (1.45, 1.5),
                                         (1.9, 2.1))]})]
    if idle_device:
        planes.append(Plane("/device:TPU:1", {devtrace.OPS_LINE: []}))
    return planes


def test_idle_time_is_put_down_to_the_span_over_it():
    shares = hostspans.idle_shares(_planes())
    # host: [1, 1.15] and [1.5, 1.75] meet idle for 0.15 + 0.25 s
    assert shares["host"] == pytest.approx(40.0)
    # wait: [1.15, 1.5] and [1.75, 2] meet idle for 0.05 + 0.05 + 0.15 s
    assert shares["wait"] == pytest.approx(25.0)


def test_shares_average_over_devices():
    shares = hostspans.idle_shares(_planes(idle_device=True))
    # the idle second device: host 0.4 s, wait 0.35 + 0.25 s of the window
    assert shares["host"] == pytest.approx((40.0 + 40.0) / 2)
    assert shares["wait"] == pytest.approx((25.0 + 60.0) / 2)


def test_a_trace_without_the_programs_spans_reads_none():
    assert hostspans.idle_shares(_planes(serve=False)) is None
    ctx = SimpleNamespace(trace_dir=None)
    assert hostspans.idle_share(ctx, "host") is None


def _ctx(before, after):
    return SimpleNamespace(out={"engine_before": before,
                                "engine_after": after})


def _reader(name):
    return load_module(HERE, "metrics", name).read


def test_counter_readers_per_batch_and_set_up():
    before = {"batches": 10, "stack_s": 1.0, "dispatch_s": 0.5,
              "result_wait_s": 2.0, "post_s": 0.1, "build_s": 16.0}
    after = {"batches": 30, "stack_s": 1.2, "dispatch_s": 0.6,
             "result_wait_s": 2.4, "post_s": 0.12, "build_s": 16.0}
    ctx = _ctx(before, after)
    assert _reader("host_ms_per_batch")(ctx) == pytest.approx(
        1e3 * (0.2 + 0.1 + 0.02) / 20)
    assert _reader("result_wait_ms_per_batch")(ctx) == pytest.approx(
        1e3 * 0.4 / 20)
    assert _reader("bucket_build_s")(ctx) == 16.0


def test_counter_readers_without_the_counters_read_none():
    old = {"batches": 10, "completed": 320}
    new = {"batches": 30, "completed": 960}
    for name in ("host_ms_per_batch", "result_wait_ms_per_batch",
                 "bucket_build_s"):
        assert _reader(name)(_ctx(old, new)) is None, name
    same = {"batches": 10, "result_wait_s": 1.0}
    assert _reader("result_wait_ms_per_batch")(_ctx(same, same)) is None


def test_prefetch_share_reads_the_counters_and_none_without_them():
    before = {"batches": 10, "prefetched": 8}
    after = {"batches": 50, "prefetched": 44}
    assert _reader("prefetch_share")(_ctx(before, after)) == pytest.approx(
        100.0 * 36 / 40)
    parent = {"batches": 10, "completed": 320}  # no look-ahead counter
    assert _reader("prefetch_share")(
        _ctx(parent, dict(parent, batches=50))) is None


def test_staged_input_share_reads_the_counters_and_none_without_them():
    before = {"batches": 10, "staged_inputs": 9}
    after = {"batches": 50, "staged_inputs": 48}
    assert _reader("staged_input_share")(_ctx(before, after)) == (
        pytest.approx(100.0 * 39 / 40))
    parent = {"batches": 10, "prefetched": 8}  # no staging counter
    assert _reader("staged_input_share")(
        _ctx(parent, dict(parent, batches=50, prefetched=48))) is None


def test_staged_input_share_reads_none_without_batches_in_the_window():
    same = {"batches": 10, "staged_inputs": 10}
    assert _reader("staged_input_share")(_ctx(same, same)) is None


def test_ref_fallback_sites_reads_the_counter_and_none_without_it():
    before = {"batches": 0, "ref_fallbacks": 0, "build_s": 16.0}
    assert _reader("ref_fallback_sites")(_ctx(before, before)) == 0
    assert _reader("ref_fallback_sites")(
        _ctx(dict(before, ref_fallbacks=3), before)) == 3
    parent = {"batches": 0, "build_s": 16.0}  # no counter
    assert _reader("ref_fallback_sites")(_ctx(parent, parent)) is None


def _roofline_ctx(trace, sites):
    return SimpleNamespace(
        trace=trace, site_work=lambda batch: sites,
        peaks={"int8_ops_per_s": 100.0, "hbm_bytes_per_s": 10.0},
        cell=SimpleNamespace(traffic={"serving": {"buckets": [32]}}))


def test_preact_matmul_roofline_reads_the_kernel_and_none_without_it():
    # two sites a step, each least 2 s (bytes bound); 6 calls are 3 steps,
    # so 12 s of least time in 48 s of kernel time
    sites = [{"kernel": "preact_matmul", "flops": 100.0, "bytes": 20.0}] * 2 \
        + [{"kernel": "fused_conv", "flops": 1e6, "bytes": 1e6}]
    trace = SimpleNamespace(kernel_calls={"preact_matmul": (6, 48.0),
                                          "fused_conv": (3, 1e9)})
    read = _reader("preact_matmul_roofline")
    assert read(_roofline_ctx(trace, sites)) == pytest.approx(25.0)
    # an untraced run; a parent whose trace has no such kernel; a program
    # whose reference has no such site
    assert read(_roofline_ctx(None, sites)) is None
    parent = SimpleNamespace(kernel_calls={"matmul_epilogue": (6, 48.0)})
    assert read(_roofline_ctx(parent, sites)) is None
    assert read(_roofline_ctx(trace, sites[2:])) is None
