"""The async serving tier + shared batching core.

Unit tests run on CPU against a fake 1x1 "mesh" (a real jax mesh over the
single local device): admission control rejects over capacity, deadline
coalescing flushes partial batches, per-request futures resolve in
submission order within a bucket, metrics counters are monotone, and a
warmed program never recompiles under traffic.  The multi-device DP smoke
test only runs when ``jax.devices()`` has more than one entry.
"""
import asyncio
import threading
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro import marvel
from repro.models.cnn import get_cnn
from repro.runtime import batching, cnn_server
from repro.runtime.batching import AdmissionError


# ---------------------------------------------------------------------------
# batching core (no jax involved)
# ---------------------------------------------------------------------------


def test_pow2_buckets_and_lookup():
    assert batching.pow2_buckets(8) == (1, 2, 4, 8)
    assert batching.pow2_buckets(6) == (1, 2, 4, 6)
    assert batching.bucket_for((1, 2, 4, 8), 3) == 4
    assert batching.bucket_for((1, 2, 4, 8), 9) == 8  # clamp to largest


def test_round_up_buckets_for_dp():
    assert batching.round_up_buckets((1, 2, 4, 8), 4) == (4, 8)
    assert batching.round_up_buckets((1, 2, 4, 8), 3) == (3, 6, 9)
    assert batching.round_up_buckets((1, 2, 4, 8), 1) == (1, 2, 4, 8)


def test_pad_batch_adds_zero_lanes():
    x = np.ones((3, 2), np.float32)
    y = batching.pad_batch(x, 8)
    assert y.shape == (8, 2)
    np.testing.assert_array_equal(y[3:], 0)
    assert batching.pad_batch(x, 2) is x  # already big enough


def test_bounded_queue_admission():
    q = batching.BoundedQueue(capacity=2)
    q.push("a")
    q.push("b")
    with pytest.raises(AdmissionError, match="capacity"):
        q.push("c")
    assert q.rejected == 1 and len(q) == 2
    assert q.pop_up_to(5) == ["a", "b"]
    q.push("d")  # space again after draining


def test_engine_metrics_percentiles_and_occupancy():
    m = batching.EngineMetrics()
    for ms in range(1, 101):
        m.observe_latency(float(ms))
    m.observe_batch(3, 4)
    m.observe_batch(4, 4, deadline=True)
    snap = m.snapshot(queue_depth=7)
    assert snap["p50_latency_ms"] == pytest.approx(50, abs=2)
    assert snap["p99_latency_ms"] == pytest.approx(99, abs=2)
    assert snap["batch_occupancy"] == pytest.approx(7 / 8)
    assert snap["queue_depth"] == 7
    assert snap["deadline_flushes"] == 1 and snap["full_flushes"] == 1


def test_bucketed_compute_rounds_buckets_to_dp_shards():
    from repro.runtime.cnn_server import _BucketedCompute

    fake = SimpleNamespace(dp_shards=4)
    core = _BucketedCompute(fake, max_batch=8)
    assert core.buckets == (4, 8)
    assert core.max_batch == 8


# ---------------------------------------------------------------------------
# the async engine over a real compiled program on a fake 1x1 mesh
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lenet_prog():
    init, apply, in_shape = get_cnn("lenet5")
    params = init(jax.random.PRNGKey(0))
    x = np.zeros((1, *in_shape), np.float32)
    prog = marvel.compile(apply, x, params=params, precompile=False)
    mesh = jax.make_mesh((1,), ("data",))  # 1x1 "mesh": DP plumbing, 1 chip
    prog.shard(mesh)
    return prog, apply, params, in_shape


def _images(in_shape, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(in_shape).astype(np.float32)
            for _ in range(n)]


def test_shard_returns_self_and_reports_dp(lenet_prog):
    prog, _, _, _ = lenet_prog
    assert prog.dp_shards == 1
    assert prog.mesh is not None
    assert prog.metrics()["dp_shards"] == 1


def test_async_results_match_reference(lenet_prog):
    prog, apply, params, in_shape = lenet_prog
    imgs = _images(in_shape, 6)

    async def main():
        async with prog.serve(mode="async", max_batch=4) as engine:
            return await asyncio.gather(*[engine.submit(im) for im in imgs])

    results = asyncio.run(main())
    import jax.numpy as jnp

    want = np.argmax(np.asarray(apply(params, jnp.stack(imgs))), axis=-1)
    assert [r.label for r in results] == list(want)
    assert all(r.done and r.latency_ms > 0 for r in results)


def test_admission_rejects_over_capacity(lenet_prog):
    prog, _, _, in_shape = lenet_prog
    imgs = _images(in_shape, 3)

    async def main():
        engine = prog.serve(mode="async", max_batch=8, max_pending=2)
        async with engine:
            # no await between the three submits: the batcher can't drain,
            # so the third must bounce off the bounded queue
            f1 = engine.submit_nowait(imgs[0])
            f2 = engine.submit_nowait(imgs[1])
            with pytest.raises(AdmissionError, match="capacity"):
                engine.submit_nowait(imgs[2])
            done = await asyncio.gather(f1, f2)
        return done, engine.metrics()

    done, m = asyncio.run(main())
    assert all(r.done for r in done)
    assert m["rejected"] == 1 and m["completed"] == 2


def test_deadline_coalescing_flushes_partial_batches(lenet_prog):
    prog, _, _, in_shape = lenet_prog
    imgs = _images(in_shape, 3)

    async def main():
        engine = prog.serve(mode="async", max_batch=8, max_delay_ms=15.0)
        async with engine:
            results = await asyncio.gather(
                *[engine.submit(im) for im in imgs]
            )
        return results, engine.metrics()

    results, m = asyncio.run(main())
    assert len(results) == 3
    # a partial bucket (3 of 8) went out on the deadline, not on fill
    assert m["batches"] == 1
    assert m["deadline_flushes"] == 1 and m["full_flushes"] == 0
    assert m["batch_occupancy"] == pytest.approx(3 / 4)  # bucket_for(3) == 4


def test_full_bucket_flushes_before_deadline(lenet_prog):
    prog, _, _, in_shape = lenet_prog
    imgs = _images(in_shape, 4)

    async def main():
        # coalesce window long enough that only a full bucket can flush first
        engine = prog.serve(mode="async", max_batch=4, max_delay_ms=5_000.0)
        async with engine:
            return await asyncio.gather(*[engine.submit(im) for im in imgs])

    results = asyncio.run(main())
    assert len(results) == 4


def test_futures_resolve_in_submission_order(lenet_prog):
    prog, _, _, in_shape = lenet_prog
    imgs = _images(in_shape, 6)
    order = []

    async def main():
        async with prog.serve(mode="async", max_batch=8) as engine:
            futs = [engine.submit_nowait(im, uid=i)
                    for i, im in enumerate(imgs)]
            for fut in futs:
                fut.add_done_callback(lambda f: order.append(f.result().uid))
            await asyncio.gather(*futs)

    asyncio.run(main())
    assert order == list(range(6))  # one bucket -> submission order


def test_futures_resolve_in_batch_one_handoff_per_flush(lenet_prog):
    """The compute thread hands each FINISHED BATCH to the event loop with
    one ``call_soon_threadsafe`` (loop_handoffs == batches), never one
    round-trip per request — the small-model serving-overhead fix."""
    prog, _, _, in_shape = lenet_prog

    async def main():
        async with prog.serve(mode="async", max_batch=4) as engine:
            for _ in range(3):
                await asyncio.gather(*[
                    engine.submit(im) for im in _images(in_shape, 4)
                ])
            return engine.metrics()

    m = asyncio.run(main())
    assert m["completed"] == 12
    assert m["loop_handoffs"] == m["batches"] == 3
    assert m["loop_handoffs"] < m["completed"]


def test_metrics_counters_are_monotone(lenet_prog):
    prog, _, _, in_shape = lenet_prog
    monotone = ("submitted", "completed", "batches", "cache_misses")
    snaps = []

    async def main():
        async with prog.serve(mode="async", max_batch=4) as engine:
            snaps.append(engine.metrics())
            for wave in range(3):
                await asyncio.gather(*[
                    engine.submit(im)
                    for im in _images(in_shape, 2 + wave, seed=wave)
                ])
                snaps.append(engine.metrics())

    asyncio.run(main())
    for a, b in zip(snaps, snaps[1:]):
        for key in monotone:
            assert b[key] >= a[key], (key, a, b)
    assert snaps[-1]["completed"] == 2 + 3 + 4


def test_warmup_means_zero_recompiles_under_traffic(lenet_prog):
    prog, _, _, in_shape = lenet_prog

    async def main():
        async with prog.serve(mode="async", max_batch=4) as engine:
            engine.warmup(in_shape)
            warmed = prog.cache_misses
            for wave in range(3):  # odd sizes exercise every bucket
                await asyncio.gather(*[
                    engine.submit(im)
                    for im in _images(in_shape, 1 + 2 * wave, seed=wave)
                ])
            return warmed, engine.metrics()

    warmed, m = asyncio.run(main())
    assert m["cache_misses"] == warmed  # zero per-request recompiles
    assert m["cache_hits"] >= m["batches"]


def test_admission_and_metrics_after_the_drain(lenet_prog):
    """``max_pending`` bounds admitted-but-unresolved requests: a third
    submit bounces; after the drain the counters read two completed, one
    rejected, nothing queued, and capacity is back."""
    prog, _, _, in_shape = lenet_prog
    image = np.zeros(in_shape, np.float32)

    async def main():
        async with prog.serve(max_batch=4, max_pending=2) as engine:
            futs = [engine.submit_nowait(image), engine.submit_nowait(image)]
            with pytest.raises(AdmissionError):
                engine.submit_nowait(image)
            await asyncio.gather(*futs)
            drained = engine.metrics(), engine.outstanding
            await engine.submit(image)
            return drained, engine.metrics()

    (m, outstanding), after = asyncio.run(main())
    assert m["completed"] == 2 and m["rejected"] == 1
    assert m["queue_depth"] == 0 and outstanding == 0
    assert after["completed"] == 3 and after["rejected"] == 1


def test_submit_after_stop_raises_instead_of_hanging(lenet_prog):
    prog, _, _, in_shape = lenet_prog

    async def main():
        engine = prog.serve(mode="async", max_batch=4)
        with pytest.raises(RuntimeError, match="not started"):
            engine.submit_nowait(np.zeros(in_shape, np.float32))
        async with engine:
            await engine.submit(np.zeros(in_shape, np.float32))
        with pytest.raises(RuntimeError, match="not started"):
            engine.submit_nowait(np.zeros(in_shape, np.float32))

    asyncio.run(main())


def test_submit_racing_stop_is_rejected_not_dropped(lenet_prog):
    """A request admitted concurrently with stop() must error, never land
    behind the shutdown sentinel where its future would hang forever."""
    prog, _, _, in_shape = lenet_prog

    async def main():
        engine = prog.serve(mode="async", max_batch=4)
        await engine.start()
        stop_task = asyncio.create_task(engine.stop())
        await asyncio.sleep(0)  # stop() runs to its first suspension point;
        # the request plane is already closed by then
        with pytest.raises(RuntimeError, match="not started"):
            engine.submit_nowait(np.zeros(in_shape, np.float32))
        await stop_task

    asyncio.run(main())


PHASES = ("stack_s", "dispatch_s", "result_wait_s", "post_s")


def test_phase_counters_cover_every_batch(lenet_prog):
    """Every phase counter is on ``metrics()`` and positive after a few
    batches, and the compute thread's phases fit in the wall time."""
    prog, _, _, in_shape = lenet_prog

    async def main():
        async with prog.serve(mode="async", max_batch=4) as engine:
            for wave in range(3):
                await asyncio.gather(*[
                    engine.submit(im)
                    for im in _images(in_shape, 4, seed=wave)
                ])
            return engine.metrics()

    t0 = time.perf_counter()
    m = asyncio.run(main())
    wall = time.perf_counter() - t0
    assert m["batches"] >= 3
    for key in (*PHASES, "queue_wait_s", "executor_wait_s", "build_s"):
        assert m[key] > 0, key
    assert sum(m[k] for k in PHASES) <= wall


def test_build_seconds_grow_on_a_bucket_miss_not_on_a_hit(lenet_prog):
    prog, _, _, in_shape = lenet_prog
    spec = jax.ShapeDtypeStruct((3, *in_shape), np.float32)  # no bucket's
    misses, before = prog.cache_misses, prog.build_s
    prog.executable_for(spec)
    assert prog.cache_misses == misses + 1 and prog.build_s > before
    built = prog.build_s
    prog.executable_for(spec)
    assert prog.build_s == built and prog.metrics()["build_s"] == built


def test_serving_spans_in_a_profiler_trace(lenet_prog, tmp_path):
    """Under the profiler, with batches waiting so that the look-ahead
    launches each next batch before the current result is read, each batch
    leaves every span once, joined by its batch id: the compute thread's
    on one thread line, none enclosing another, and its ``stack`` on the
    event loop's line beside ``resolve``, closed before its ``dispatch``
    opens (a warmed engine stages every batch).  The trace stops only
    after the engine has stopped: the last batch's ``handoff`` span closes
    after its futures resolve."""
    prog, _, _, in_shape = lenet_prog
    compute = {f"marvel.serve.{p}" for p in (
        "dispatch", "result_wait", "post", "handoff")}
    loop = {"marvel.serve.stack", "marvel.serve.resolve"}

    async def main():
        engine = prog.serve(mode="async", max_batch=4)
        await engine.start()
        engine.warmup(in_shape)
        jax.profiler.start_trace(str(tmp_path))
        try:
            await _held_then_released(engine, _images(in_shape, 12), 3)
            await engine.stop()
        finally:
            jax.profiler.stop_trace()
        return engine.metrics()

    m = asyncio.run(main())
    batches = m["batches"]
    assert batches == 3 and m["prefetched"] == 2
    assert m["staged_inputs"] == batches
    (path,) = tmp_path.rglob("*.xplane.pb")
    spans = []  # ((plane, line), name, start, end, batch)
    for i, pl in enumerate(jax.profiler.ProfileData.from_file(str(path))
                           .planes):
        for j, ln in enumerate(pl.lines):
            spans += [((i, j), e.name, e.start_ns, e.start_ns + e.duration_ns,
                       {k: v for k, v in e.stats}["batch"])
                      for e in ln.events if e.name.startswith("marvel.serve.")]
    for name in (*compute, *loop):
        ids = sorted(b for _, n, _, _, b in spans if n == name)
        assert ids == list(range(batches)), name
    (line,) = {ln for ln, n, _, _, _ in spans if n in compute}
    (loop_line,) = {ln for ln, n, _, _, _ in spans if n in loop}
    assert loop_line != line
    on_line = sorted((s, e) for ln, n, s, e, _ in spans
                     if ln == line and n in compute)
    assert all(e <= s2 for (_, e), (s2, _) in zip(on_line, on_line[1:]))
    start = {(n, b): s for _, n, s, _, b in spans}
    end = {(n, b): e for _, n, _, e, b in spans}
    for b in range(batches):  # staged before its launch
        assert (end["marvel.serve.stack", b]
                <= start["marvel.serve.dispatch", b])
    for b in range(1, batches):  # launched before the result before it
        assert (start["marvel.serve.dispatch", b]
                < start["marvel.serve.result_wait", b - 1])


async def _held_then_released(engine, images, batches):
    """Submit ``images`` (uids 0, 1, ...) while the compute thread is held,
    so that ``batches`` batches are staged before it takes up the first;
    returns each request's result or exception."""
    gate = threading.Event()
    engine._pool.submit(gate.wait)
    futs = [engine.submit_nowait(im, uid=i) for i, im in enumerate(images)]
    for _ in range(5_000):
        if len(engine._staged) >= batches:
            break
        await asyncio.sleep(0.001)
    gate.set()
    assert len(engine._staged) >= batches
    return await asyncio.gather(*futs, return_exceptions=True)


class _Out:
    """A fake program output: records when it is read."""

    def __init__(self, logits, calls, batch):
        self.logits, self.calls, self.batch = logits, calls, batch

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        self.calls.append(("read", self.batch))
        return self.logits


def test_look_ahead_launches_the_next_batch_before_the_result_read(
        monkeypatch):
    """Fake program, fake spans: with batches staged, batch N+1's stack
    and dispatch come before batch N's result read, and ``prefetched``
    counts them; one batch at a time, each batch runs stack, dispatch,
    result wait, post, handoff in turn and ``prefetched`` stays 0."""
    calls = []

    class Span:
        def __init__(self, name, **tags):
            self.key = (name.removeprefix("marvel.serve."), tags["batch"])

        def __enter__(self):
            if self.key[0] != "resolve":  # the event loop's span
                calls.append(self.key)

        def __exit__(self, *exc):
            return False

    class Program:
        dp_shards = 1

        def __call__(self, x):
            batch = int(x[0, 0]) // 4  # request i's image is all i
            calls.append(("call", batch))
            return _Out(np.repeat(x[:, :1], 3, axis=1), calls, batch)

    monkeypatch.setattr(cnn_server, "TraceAnnotation", Span)
    images = [np.full((2,), i, np.float32) for i in range(12)]

    async def staged():
        async with cnn_server.AsyncCnnEngine(Program(), max_batch=4) as e:
            results = await _held_then_released(e, images, 3)
            return results, e

    results, engine = asyncio.run(staged())
    assert [r.logits[0] for r in results] == list(range(12))
    assert engine.metrics()["prefetched"] == 2
    assert engine.compute.launched == {}

    def launch(b):
        return [("stack", b), ("dispatch", b), ("call", b)]

    def finish(b):
        return [("result_wait", b), ("read", b), ("post", b), ("handoff", b)]

    assert calls == (launch(0) + launch(1) + finish(0)
                     + launch(2) + finish(1) + finish(2))
    calls.clear()

    async def one_at_a_time():
        async with cnn_server.AsyncCnnEngine(Program(), max_batch=4) as e:
            for i in (0, 4):  # one request per batch, each awaited
                await e.submit(images[i])
            return e.metrics()

    assert asyncio.run(one_at_a_time())["prefetched"] == 0
    assert calls == launch(0) + finish(0) + launch(1) + finish(1)


def test_look_ahead_logits_are_bit_identical_to_the_program(lenet_prog):
    """Batches stacked on the compute thread (an engine not warmed: no
    bucket's placement is known when the three are dispatched) and
    batches staged on the device at dispatch (a warmed engine) both give
    the program's logits on the same padded batches of four, bit for
    bit."""
    prog, _, _, in_shape = lenet_prog
    images = _images(in_shape, 12, seed=3)
    want = np.concatenate([
        np.asarray(prog(batching.pad_batch(np.stack(images[i:i + 4]), 4)))
        for i in range(0, 12, 4)
    ])

    async def main(warm):
        async with prog.serve(mode="async", max_batch=4) as engine:
            if warm:
                engine.warmup(in_shape)
            results = await _held_then_released(engine, images, 3)
            return results, engine.metrics(), engine.compute

    for warm, staged in ((False, 0), (True, 3)):
        results, m, compute = asyncio.run(main(warm))
        assert m["prefetched"] == 2 and m["staged_inputs"] == staged
        assert compute.launched == {} and compute.device_inputs == {}
        for i, r in enumerate(results):
            np.testing.assert_array_equal(r.logits, want[i])


def test_poison_pill_in_a_look_ahead_batch_is_isolated(lenet_prog):
    """uid 5 is in batch 1, which the look-ahead launched: bisection still
    fails exactly that request."""
    from repro.runtime.batching import RetryPolicy
    from repro.runtime.faults import FaultInjector, InjectedFault

    prog, _, _, in_shape = lenet_prog
    images = _images(in_shape, 12, seed=4)

    async def main():
        engine = prog.serve(
            mode="async", max_batch=4, faults=FaultInjector(poison_uids=(5,)),
            retry=RetryPolicy(max_retries=1, backoff_base_ms=0.1, jitter=0.0))
        async with engine:
            results = await _held_then_released(engine, images, 3)
        return results, engine.metrics(), engine.compute.launched

    results, m, launched = asyncio.run(main())
    assert [i for i, r in enumerate(results)
            if isinstance(r, Exception)] == [5]
    assert isinstance(results[5], InjectedFault)
    assert all(r.done for i, r in enumerate(results) if i != 5)
    assert m["prefetched"] == 2 and m["errors"] == 1 and launched == {}


def test_a_look_ahead_launch_that_raises_spares_the_batch_before_it(
        lenet_prog):
    """The program raises on its second call, the look-ahead launch of
    batch 1: batch 0 still answers, and batch 1 launches again on its own
    path and answers too."""
    from repro.runtime.cnn_server import AsyncCnnEngine

    prog, apply, params, in_shape = lenet_prog
    images = _images(in_shape, 8, seed=5)
    calls = []

    class SecondCallRaises:
        dp_shards = 1

        def __call__(self, x):
            calls.append(len(x))
            if len(calls) == 2:
                raise RuntimeError("launch failed")
            return prog(x)

    async def main():
        async with AsyncCnnEngine(SecondCallRaises(), max_batch=4) as engine:
            results = await _held_then_released(engine, images, 2)
        return results, engine.metrics(), engine.compute.launched

    results, m, launched = asyncio.run(main())
    want = np.argmax(np.asarray(apply(params, np.stack(images))), axis=-1)
    assert [r.label for r in results] == list(want)
    assert m["errors"] == 0 and m["retries"] == 0
    assert m["prefetched"] == 0 and launched == {} and len(calls) == 3


def test_every_dispatched_batch_is_staged_once_warmed(lenet_prog):
    """A warmed engine stages each batch it dispatches, full or partial,
    one at a time or several waiting, and leaves no launched output or
    staged input behind once drained."""
    prog, _, _, in_shape = lenet_prog

    async def main():
        async with prog.serve(mode="async", max_batch=4) as engine:
            engine.warmup(in_shape)
            for n in (1, 3, 4):  # buckets 1, 4 and 4, each awaited
                await asyncio.gather(*[engine.submit(im) for im in
                                       _images(in_shape, n, seed=n)])
            await _held_then_released(
                engine, _images(in_shape, 12, seed=9), 3)
            await engine.stop()
            return engine.metrics(), engine.compute

    m, compute = asyncio.run(main())
    assert m["batches"] == 6 and m["completed"] == 20
    assert m["staged_inputs"] == m["batches"]
    assert compute.launched == {} and compute.device_inputs == {}


@pytest.mark.timeout(120)
def test_staging_under_a_short_switch_interval(lenet_prog):
    """The event loop stages while the compute thread launches, with the
    interpreter switching threads every microsecond: every batch is staged
    once and launched from its input, no staged input or launched output
    is left, and the answers are the reference's."""
    import sys

    prog, apply, params, in_shape = lenet_prog
    images = _images(in_shape, 64, seed=13)

    async def main():
        async with prog.serve(mode="async", max_batch=4) as engine:
            engine.warmup(in_shape)
            results = await asyncio.gather(*[
                engine.submit(im) for im in images])
            await engine.stop()
            return results, engine.metrics(), engine.compute

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        results, m, compute = asyncio.run(main())
    finally:
        sys.setswitchinterval(interval)
    want = np.argmax(np.asarray(apply(params, np.stack(images))), axis=-1)
    assert [r.label for r in results] == list(want)
    assert m["completed"] == 64 and m["staged_inputs"] == m["batches"]
    assert compute.launched == {} and compute.device_inputs == {}


def test_kill_drops_the_staged_inputs(lenet_prog):
    """Batches staged behind a held compute thread hold device arrays;
    kill() drops them with the batches, and with any launched output."""
    from repro.runtime.batching import WorkerUnavailable

    prog, _, _, in_shape = lenet_prog

    async def main():
        engine = prog.serve(mode="async", max_batch=4)
        await engine.start()
        engine.warmup(in_shape)
        gate = threading.Event()
        engine._pool.submit(gate.wait)
        futs = [engine.submit_nowait(im, uid=i)
                for i, im in enumerate(_images(in_shape, 8, seed=11))]
        for _ in range(5_000):
            if len(engine.compute.device_inputs) == 2:
                break
            await asyncio.sleep(0.001)
        staged = len(engine.compute.device_inputs)
        engine.kill("drill")
        gate.set()
        results = await asyncio.gather(*futs, return_exceptions=True)
        return staged, results, engine.compute

    staged, results, compute = asyncio.run(main())
    assert staged == 2
    assert all(isinstance(r, WorkerUnavailable) for r in results)
    assert compute.launched == {} and compute.device_inputs == {}


def test_poison_pill_in_a_staged_batch_restacks_its_sub_batches(
        lenet_prog, monkeypatch):
    """uid 5 is in batch 1, staged on the device at dispatch: bisection
    still fails exactly that request, and its retries and sub-batches
    stack the host images again on the compute thread."""
    from repro.runtime.batching import RetryPolicy
    from repro.runtime.faults import FaultInjector, InjectedFault

    stacks = []  # (batch, attempt, size, thread) per stack span

    class Span:
        def __init__(self, name, **tags):
            if name == "marvel.serve.stack":
                stacks.append((tags["batch"], tags["attempt"], tags["size"],
                               threading.current_thread().name))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(cnn_server, "TraceAnnotation", Span)
    prog, _, _, in_shape = lenet_prog
    images = _images(in_shape, 12, seed=4)

    async def main():
        engine = prog.serve(
            mode="async", max_batch=4, faults=FaultInjector(poison_uids=(5,)),
            retry=RetryPolicy(max_retries=1, backoff_base_ms=0.1, jitter=0.0))
        async with engine:
            engine.warmup(in_shape)
            results = await _held_then_released(engine, images, 3)
        return results, engine.metrics(), engine.compute

    results, m, compute = asyncio.run(main())
    assert [i for i, r in enumerate(results)
            if isinstance(r, Exception)] == [5]
    assert isinstance(results[5], InjectedFault)
    assert all(r.done for i, r in enumerate(results) if i != 5)
    assert m["errors"] == 1 and m["staged_inputs"] == 3
    assert compute.launched == {} and compute.device_inputs == {}
    on_loop = [(b, a, n) for b, a, n, t in stacks
               if not t.startswith("cnn-compute")]
    assert on_loop == [(0, 0, 4), (1, 0, 4), (2, 0, 4)]
    # the fault fires before a launch, so of batch 1's calls only its
    # innocent sub-batches launch: uid 4 alone, then uids 6 and 7
    assert [(b, a, n) for b, a, n, t in stacks
            if t.startswith("cnn-compute")] == [(1, 4, 1), (1, 7, 2)]


def test_a_staged_input_is_placed_as_the_sharded_executable_takes_it(
        lenet_prog):
    """The program is sharded (``.shard`` over a 1x1 mesh): a staged
    batch's device array carries the input sharding of its bucket's
    executable, and the program's answer for it is the host array's."""
    prog, _, _, in_shape = lenet_prog
    engine = prog.serve(mode="async", max_batch=4)
    engine.warmup(in_shape)
    images = _images(in_shape, 3, seed=12)
    engine.compute.stage(images, (0, 1, 2), batch=0)
    x, seconds = engine.compute.device_inputs[(0, 1, 2)]
    exe = prog.executable_for(jax.ShapeDtypeStruct((4, *in_shape),
                                                   np.float32))
    assert x.sharding == exe.input_shardings[0][0]
    assert x.sharding.mesh == prog.mesh and seconds > 0
    host = batching.pad_batch(np.stack(images), 4)
    np.testing.assert_array_equal(np.asarray(x), host)
    np.testing.assert_array_equal(np.asarray(prog(x)), np.asarray(prog(host)))
    labels, _, _ = engine.compute.classify(images, uids=(0, 1, 2))
    assert engine.compute.device_inputs == {}
    assert engine.metrics()["staged_inputs"] == 1 and len(labels) == 3


def test_retry_after_hint_reads_compute_seconds_per_batch():
    from repro.runtime.cnn_server import AsyncCnnEngine

    engine = AsyncCnnEngine(SimpleNamespace(dp_shards=1), max_batch=4,
                            max_delay_ms=3.0)
    engine._live_reqs = 9  # three batches ahead
    assert engine._retry_after_hint_ms() == pytest.approx(3 * 3.0)
    m = engine._metrics
    m.batches = 2
    m.stack_s, m.dispatch_s, m.result_wait_s, m.post_s = (
        0.004, 0.002, 0.012, 0.002)
    m.observe_latency(500.0)  # queueing is no part of a batch's time
    assert engine._retry_after_hint_ms() == pytest.approx(3 * 10.0)


@pytest.mark.timeout(20)
def test_stop_drains_a_batch_whose_callbacks_are_still_queued():
    """stop() returns when an in-flight batch's future is done but the
    callback that drops it from the in-flight set has not run yet (awaiting
    a done future does not yield to the loop, so the drain must not rely on
    that callback)."""
    from repro.runtime.cnn_server import AsyncCnnEngine

    async def main():
        engine = AsyncCnnEngine(SimpleNamespace(dp_shards=1), max_batch=4)
        await engine.start()
        done = asyncio.get_running_loop().create_future()
        done.set_result(None)
        engine._inflight.add(done)  # done, its discard not yet run
        await engine.stop()
        return engine._inflight

    assert asyncio.run(main()) == set()


def test_serve_mode_validation(lenet_prog):
    prog, _, _, _ = lenet_prog
    for mode in ("threads", "sync"):
        with pytest.raises(ValueError, match="'async'"):
            prog.serve(mode=mode)
    assert isinstance(prog.serve(), cnn_server.AsyncCnnEngine)


@pytest.mark.slow
def test_serving_soak(lenet_prog):
    """300 requests in ragged waves: every future resolves, nothing
    recompiles after warmup, and the counters stay consistent."""
    prog, apply, params, in_shape = lenet_prog
    total = 300

    async def main():
        async with prog.serve(mode="async", max_batch=8,
                              max_delay_ms=1.0) as engine:
            engine.warmup(in_shape)
            warmed = prog.cache_misses
            results = []
            rng = np.random.default_rng(7)
            sent = 0
            while sent < total:
                n = int(rng.integers(1, 17))
                n = min(n, total - sent)
                wave = await asyncio.gather(*[
                    engine.submit(im)
                    for im in _images(in_shape, n, seed=sent)
                ])
                results.extend(wave)
                sent += n
            return warmed, results, engine.metrics()

    warmed, results, m = asyncio.run(main())
    assert len(results) == total and all(r.done for r in results)
    assert m["completed"] == total and m["submitted"] == total
    assert m["cache_misses"] == warmed
    assert m["p99_latency_ms"] >= m["p50_latency_ms"] > 0


# ---------------------------------------------------------------------------
# multi-device DP (skipped on single-device CI)
# ---------------------------------------------------------------------------


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 local devices for DP")
def test_dp_smoke_across_local_devices():
    init, apply, in_shape = get_cnn("lenet5")
    params = init(jax.random.PRNGKey(0))
    x = np.zeros((1, *in_shape), np.float32)
    prog = marvel.compile(apply, x, params=params, precompile=False).shard()
    ndev = len(jax.devices())
    assert prog.dp_shards == ndev
    engine = prog.serve(max_batch=2 * ndev)
    assert all(b % ndev == 0 for b in engine.compute.buckets)
    engine.warmup(in_shape)

    async def main():
        async with engine:
            return await engine.submit_wave(
                [np.zeros(in_shape, np.float32)] * (2 * ndev + 1))

    results = asyncio.run(main())
    assert len(results) == 2 * ndev + 1 and all(r.done for r in results)
