"""The fault-tolerant serving control plane, driven through every failure
path by deterministic fault injection.

Layer 1 (request-plane self-healing, ``runtime/cnn_server.py``): transient
compute failures retry with backoff; poison-pill batches bisect so innocent
co-batched requests still succeed; expired deadlines fast-fail before
dispatch; admission sheds load with a retry-after hint; all of it lands in
the ``errors``/``retries``/``shed``/``deadline_failures`` counters and the
``loop_handoffs == batches`` invariant stays exact across error paths.

Layer 2 (supervisor, ``runtime/supervisor.py``): heartbeat health checks,
auto-recovery of dead/hung workers with warmup replay, draining restarts
with zero dropped accepted requests, Prometheus export.

Layer 3 (``runtime/faults.py``): the injection plans themselves are
deterministic, so every counter below is asserted against the plan.
"""
import asyncio
import math

import jax
import numpy as np
import pytest

from repro import marvel
from repro.models.cnn import get_cnn
from repro.runtime.batching import (
    AdmissionError, DeadlineExceeded, RetryPolicy, WorkerUnavailable,
)
from repro.runtime.faults import FaultInjector, FaultPlan, InjectedFault, \
    WorkerDeath
from repro.runtime.supervisor import Supervisor


@pytest.fixture(scope="module")
def lenet_prog():
    init, apply, in_shape = get_cnn("lenet5")
    params = init(jax.random.PRNGKey(0))
    x = np.zeros((1, *in_shape), np.float32)
    prog = marvel.compile(apply, x, params=params, precompile=False)
    prog.shard(jax.make_mesh((1,), ("data",)))  # 1x1 mesh: DP plumbing
    return prog, apply, params, in_shape


def _images(in_shape, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(in_shape).astype(np.float32)
            for _ in range(n)]


FAST_RETRY = dict(backoff_base_ms=0.1, jitter=0.0)


# ---------------------------------------------------------------------------
# layer 3: the injection plans are deterministic
# ---------------------------------------------------------------------------


def test_fault_injector_flaky_is_seeded_deterministic():
    a = FaultInjector(flaky_rate=0.5, seed=7)
    b = FaultInjector(flaky_rate=0.5, seed=7)

    def fire_pattern(inj):
        fired = []
        for _ in range(50):
            try:
                inj.before_compute((0,))
                fired.append(False)
            except InjectedFault:
                fired.append(True)
        return fired

    pa, pb = fire_pattern(a), fire_pattern(b)
    assert pa == pb and any(pa) and not all(pa)
    assert a.injected["flaky"] == sum(pa)


def test_fault_injector_budgets_and_order():
    inj = FaultInjector(FaultPlan(fail_next=2, poison_uids=(3,),
                                  die_after_attempts=5))
    for _ in range(2):  # one-shot budget drains first
        with pytest.raises(InjectedFault, match="one-shot"):
            inj.before_compute((3,))
    with pytest.raises(InjectedFault, match="poison"):
        inj.before_compute((1, 3))
    inj.before_compute((1, 2))  # clean batch passes
    inj.before_compute((4,))
    with pytest.raises(WorkerDeath):  # attempt 6 > die_after_attempts=5
        inj.before_compute((4,))
    assert inj.attempts == 6
    assert inj.injected == {"one_shot": 2, "poison": 1, "flaky": 0,
                            "straggle": 0, "death": 1}


def test_retry_policy_backoff_grows_and_is_seeded():
    p = RetryPolicy(max_retries=3, backoff_base_ms=1.0,
                    backoff_multiplier=2.0, jitter=0.5, seed=3)
    q = RetryPolicy(max_retries=3, backoff_base_ms=1.0,
                    backoff_multiplier=2.0, jitter=0.5, seed=3)
    ba = [p.backoff_ms(a) for a in range(3)]
    assert ba == [q.backoff_ms(a) for a in range(3)]  # seeded jitter
    for a, ms in enumerate(ba):
        base = 2.0 ** a
        assert base <= ms <= base * 1.5  # jitter bounded to +50%


# ---------------------------------------------------------------------------
# layer 1: request-plane self-healing (async engine)
# ---------------------------------------------------------------------------


def test_transient_fault_retries_and_recovers(lenet_prog):
    prog, _, _, in_shape = lenet_prog
    inj = FaultInjector(fail_next=1)

    async def main():
        engine = prog.serve(mode="async", max_batch=4, faults=inj,
                            retry=RetryPolicy(max_retries=2, **FAST_RETRY))
        async with engine:
            results = await engine.submit_wave(_images(in_shape, 4))
        return results, engine.metrics()

    results, m = asyncio.run(main())
    assert all(r.done for r in results)
    assert m["errors"] == 0 and m["completed"] == 4
    assert m["retries"] == 1  # exactly the injected one-shot
    assert inj.injected["one_shot"] == 1


def test_poison_pill_bisection_isolates_one_request(lenet_prog):
    """The acceptance scenario: a 64-request wave with one per-uid poison
    pill completes with exactly one failed request; the counters match the
    plan exactly."""
    prog, apply, params, in_shape = lenet_prog
    poison_uid, max_batch, retries_per_level = 13, 8, 1
    inj = FaultInjector(poison_uids=(poison_uid,))
    imgs = _images(in_shape, 64)

    async def main():
        engine = prog.serve(
            mode="async", max_batch=max_batch, max_delay_ms=5_000.0,
            faults=inj,
            retry=RetryPolicy(max_retries=retries_per_level, **FAST_RETRY),
        )
        async with engine:
            # all 64 queued before the batcher runs -> 8 full batches of 8
            futs = [engine.submit_nowait(im) for im in imgs]
            results = await asyncio.gather(*futs, return_exceptions=True)
        return results, engine.metrics()

    results, m = asyncio.run(main())
    failed = [i for i, r in enumerate(results) if isinstance(r, Exception)]
    assert failed == [poison_uid]
    assert isinstance(results[poison_uid], InjectedFault)
    # the 63 innocents resolved CORRECTLY, not just at all
    import jax.numpy as jnp

    want = np.argmax(np.asarray(apply(params, jnp.stack(imgs))), axis=-1)
    for i, r in enumerate(results):
        if i != poison_uid:
            assert r.done and r.label == want[i]
    # counters match the plan: one error; the poison path retries once per
    # bisection level (8 -> 4 -> 2 -> 1)
    levels = int(math.log2(max_batch)) + 1
    assert m["errors"] == 1
    assert m["retries"] == retries_per_level * levels == 4
    assert m["completed"] == 63 and m["submitted"] == 64
    assert m["batches"] == m["loop_handoffs"] == 8
    assert inj.injected["poison"] == levels * (retries_per_level + 1)


def test_split_budget_exhausted_fails_per_request(lenet_prog):
    """max_splits=0: the failing batch never bisects — every co-batched
    request fails with the same error, but each one *resolves* (bounded
    splits, then per-request failure) and the handoff invariant holds on
    the pure error path."""
    prog, _, _, in_shape = lenet_prog
    inj = FaultInjector(poison_uids=(2,))

    async def main():
        engine = prog.serve(
            mode="async", max_batch=4, max_delay_ms=5_000.0, faults=inj,
            retry=RetryPolicy(max_retries=1, max_splits=0, **FAST_RETRY),
        )
        async with engine:
            futs = [engine.submit_nowait(im)
                    for im in _images(in_shape, 4)]
            results = await asyncio.gather(*futs, return_exceptions=True)
        return results, engine.metrics()

    results, m = asyncio.run(main())
    assert all(isinstance(r, InjectedFault) for r in results)
    assert m["errors"] == 4 and m["completed"] == 0
    assert m["retries"] == 1
    # failed batches are accounted exactly like successful ones
    assert m["batches"] == m["loop_handoffs"] == 1
    assert m["batch_occupancy"] == pytest.approx(1.0)


def test_expired_deadline_fast_fails_before_dispatch(lenet_prog):
    prog, _, _, in_shape = lenet_prog

    async def main():
        async with prog.serve(mode="async", max_batch=4) as engine:
            fut = engine.submit_nowait(_images(in_shape, 1)[0],
                                       deadline_ms=-10.0)  # already expired
            with pytest.raises(DeadlineExceeded, match="deadline"):
                await fut
            mid = engine.metrics()
            # the engine is still serviceable for live-deadline requests
            ok = await engine.submit(_images(in_shape, 1)[0],
                                     deadline_ms=10_000.0)
        return mid, ok, engine.metrics()

    mid, ok, m = asyncio.run(main())
    assert mid["deadline_failures"] == 1
    assert mid["batches"] == 0  # no compute burned on the dead request
    assert ok.done
    assert m["completed"] == 1 and m["deadline_failures"] == 1


def test_admission_shed_carries_retry_after_hint(lenet_prog):
    prog, _, _, in_shape = lenet_prog
    imgs = _images(in_shape, 3)

    async def main():
        engine = prog.serve(mode="async", max_batch=8, max_pending=2)
        async with engine:
            f1 = engine.submit_nowait(imgs[0])
            f2 = engine.submit_nowait(imgs[1])
            with pytest.raises(AdmissionError) as ei:
                engine.submit_nowait(imgs[2])
            await asyncio.gather(f1, f2)
        return ei.value, engine.metrics()

    err, m = asyncio.run(main())
    assert err.retry_after_ms is not None and err.retry_after_ms > 0
    assert m["shed"] == 1 and m["rejected"] == 1
    assert m["completed"] == 2


@pytest.mark.parametrize("served_before", [1, 0],
                         ids=["after_a_served_batch", "on_the_first_batch"])
def test_worker_death_fails_unresolved_with_worker_unavailable(
        lenet_prog, served_before):
    """The worker dies on the attempt after ``served_before`` served ones:
    every unresolved request fails with WorkerUnavailable and the engine
    stops; batches served before the death stay served."""
    prog, _, _, in_shape = lenet_prog
    inj = FaultInjector(die_after_attempts=served_before)

    async def main():
        engine = prog.serve(mode="async", max_batch=4, max_delay_ms=1.0,
                            faults=inj,
                            retry=RetryPolicy(max_retries=0, **FAST_RETRY))
        await engine.start()
        first = []
        if served_before:  # attempt 1 ok
            first = await engine.submit_wave(_images(in_shape, 4))
        futs = [engine.submit_nowait(im) for im in _images(in_shape, 4)]
        second = await asyncio.gather(*futs, return_exceptions=True)
        return first, second, engine

    first, second, engine = asyncio.run(main())
    assert len(first) == 4 * served_before and all(r.done for r in first)
    assert all(isinstance(r, WorkerUnavailable) for r in second)
    assert not engine.is_alive
    assert inj.injected["death"] == 1


def test_engine_contains_compute_errors(lenet_prog):
    """A poisoned uid fails alone, its batch-mates resolve, and the engine
    serves the next wave."""
    prog, _, _, in_shape = lenet_prog

    async def main():
        engine = prog.serve(max_batch=4,
                            faults=FaultInjector(poison_uids=(1,)),
                            retry=RetryPolicy(max_retries=1, **FAST_RETRY))
        async with engine:
            # queued before the batcher runs: the three share one batch
            futs = [engine.submit_nowait(im, uid=uid)
                    for uid, im in enumerate(_images(in_shape, 3))]
            wave = await asyncio.gather(*futs, return_exceptions=True)
            mid = engine.metrics()
            nxt = await engine.submit(_images(in_shape, 1)[0], uid=10)
        return wave, mid, nxt, engine.metrics()

    wave, mid, nxt, m = asyncio.run(main())
    assert isinstance(wave[1], InjectedFault)
    assert wave[0].done and wave[2].done
    assert mid["errors"] == 1 and mid["completed"] == 2
    assert mid["batches"] == 1  # the three shared one batch
    # ...and the engine stays serviceable
    assert nxt.done and nxt.uid == 10
    assert m["completed"] == 3


# ---------------------------------------------------------------------------
# layer 2: the supervisor
# ---------------------------------------------------------------------------


def _mk_supervisor(**kw):
    kw.setdefault("heartbeat_interval_ms", 10.0)
    kw.setdefault("pick_timeout_ms", 20_000.0)
    return Supervisor(**kw)


def test_supervisor_registry_validation(lenet_prog):
    prog, _, _, in_shape = lenet_prog
    sup = _mk_supervisor()
    sup.register("m", prog, warmup=in_shape)
    with pytest.raises(ValueError, match="already registered"):
        sup.register("m", prog)
    with pytest.raises(ValueError, match="workers"):
        sup.register("m2", prog, workers=0)

    async def main():
        async with sup:
            with pytest.raises(KeyError, match="unknown model"):
                await sup.submit(_images(in_shape, 1)[0], model="nope")
            r = await sup.submit(_images(in_shape, 1)[0])  # sole model
        return r

    assert asyncio.run(main()).done


def test_supervisor_recovers_killed_worker_zero_lost_requests(lenet_prog):
    """The acceptance scenario's second half: a worker dies mid-wave (fault
    layer death hook); every accepted request still resolves (failover
    re-routing), and the supervisor restores full healthy capacity."""
    prog, _, _, in_shape = lenet_prog
    spawned = []

    def factory(index):
        # kill worker 0's FIRST incarnation only; replacements are clean
        if index == 0 and 0 not in spawned:
            spawned.append(0)
            return FaultInjector(die_after_attempts=2)
        return None

    sup = _mk_supervisor()
    sup.register("lenet5", prog, workers=2, warmup=in_shape, faults=factory,
                 max_batch=8, max_delay_ms=1.0)

    async def main():
        async with sup:
            results = await sup.submit_wave(_images(in_shape, 64))
            for _ in range(500):  # wait for auto-recovery to converge
                if len(sup.healthy_workers()) == 2:
                    break
                await asyncio.sleep(0.01)
            return results, sup.metrics()

    results, m = asyncio.run(main())
    assert len(results) == 64 and all(r.done for r in results)
    assert len({r.uid for r in results}) == 64  # no lost, no duplicated
    agg = m["aggregate"]
    assert agg["healthy_workers"] == 2
    assert agg["restarts"] >= 1 and agg["failovers"] >= 1
    assert sup.workers["lenet5/0"].restarts >= 1


def test_supervisor_draining_restart_drops_nothing(lenet_prog):
    prog, _, _, in_shape = lenet_prog
    sup = _mk_supervisor()
    sup.register("lenet5", prog, workers=2, warmup=in_shape,
                 max_batch=4, max_delay_ms=5.0)

    async def main():
        async with sup:
            wave = asyncio.ensure_future(
                sup.submit_wave(_images(in_shape, 32))
            )
            await asyncio.sleep(0)  # wave admitted/partially in flight
            await sup.restart_worker("lenet5/0", drain=True)
            results = await wave
            return results, sup.metrics(), sup.workers["lenet5/0"].state

    results, m, state = asyncio.run(main())
    assert len(results) == 32 and all(r.done for r in results)
    assert m["aggregate"]["restarts"] == 1
    assert state == "healthy"


def test_supervisor_detects_dead_worker_via_health_loop(lenet_prog):
    """Direct kill (not through a request): the heartbeat loop notices the
    dead batcher, restarts the worker, and replays the warmup from the
    recorded specs — against the shared AOT cache, so zero recompiles."""
    prog, _, _, in_shape = lenet_prog
    sup = _mk_supervisor()
    sup.register("lenet5", prog, workers=1, warmup=in_shape, max_batch=4)

    async def main():
        async with sup:
            warmed_misses = prog.cache_misses
            sup.workers["lenet5/0"].engine.kill("test chaos")
            for _ in range(500):
                if len(sup.healthy_workers()) == 1:
                    break
                await asyncio.sleep(0.01)
            # the replacement serves traffic
            r = await sup.submit(_images(in_shape, 1)[0])
            return warmed_misses, r, sup.metrics()

    warmed_misses, r, m = asyncio.run(main())
    assert r.done
    assert m["aggregate"]["restarts"] == 1
    assert m["aggregate"]["healthy_workers"] == 1
    # warmup replay hit the program's shared AOT cache: no recompiles
    assert prog.cache_misses == warmed_misses
    specs = sup.workers["lenet5/0"].engine.compute.warmed
    assert (tuple(in_shape), "float32") in specs


def test_supervisor_hung_worker_heartbeat_timeout_recovery(lenet_prog):
    """A straggling compute thread (injected sleep > hang timeout) makes the
    heartbeat time out; the supervisor evicts + replaces the worker and the
    stuck requests fail over to the sibling."""
    prog, _, _, in_shape = lenet_prog

    def factory(index):
        if index == 0:
            return FaultInjector(straggle_next=1, straggle_ms=400.0)
        return None

    sup = _mk_supervisor(hang_timeout_ms=60.0)
    sup.register("lenet5", prog, workers=2, warmup=in_shape, faults=factory,
                 max_batch=4, max_delay_ms=1.0)

    async def main():
        async with sup:
            results = await sup.submit_wave(_images(in_shape, 16))
            for _ in range(500):
                if len(sup.healthy_workers()) == 2:
                    break
                await asyncio.sleep(0.01)
            return results, sup.metrics()

    results, m = asyncio.run(main())
    assert len(results) == 16 and all(r.done for r in results)
    assert m["aggregate"]["restarts"] >= 1
    assert m["aggregate"]["healthy_workers"] == 2


def test_supervisor_watchdog_should_evict_triggers_recovery(lenet_prog):
    """The StragglerWatchdog's ``should_evict`` is wired to an actual
    action: when consecutive heartbeats straggle, the worker is replaced."""
    prog, _, _, in_shape = lenet_prog
    sup = _mk_supervisor()
    sup.register("lenet5", prog, workers=1, warmup=in_shape, max_batch=4)

    class AlwaysStraggling:
        consecutive = 99

        def observe(self, step, dt):
            return True

        @property
        def should_evict(self):
            return True

    async def main():
        async with sup:
            sup.workers["lenet5/0"].watchdog = AlwaysStraggling()
            for _ in range(500):
                if sup.metrics()["aggregate"]["restarts"] >= 1:
                    break
                await asyncio.sleep(0.01)
            r = await sup.submit(_images(in_shape, 1)[0])
            return r, sup.metrics(), sup.workers["lenet5/0"].state

    r, m, state = asyncio.run(main())
    assert r.done
    assert m["aggregate"]["restarts"] >= 1
    # the replacement got a REAL watchdog again, so it is not re-evicted
    assert state == "healthy"


def test_supervisor_prometheus_export(lenet_prog):
    prog, _, _, in_shape = lenet_prog
    sup = _mk_supervisor()
    sup.register("lenet5", prog, workers=2, warmup=in_shape, max_batch=4)

    async def main():
        async with sup:
            await sup.submit_wave(_images(in_shape, 8))
            return sup.prometheus()

    text = asyncio.run(main())
    lines = text.splitlines()
    assert "# TYPE marvel_serving_completed gauge" in lines
    assert "marvel_serving_completed 8" in lines  # aggregate sample
    for key in ("stack_s", "dispatch_s", "result_wait_s", "post_s",
                "queue_wait_s", "executor_wait_s", "build_s", "prefetched",
                "staged_inputs", "ref_fallbacks"):
        assert f"# TYPE marvel_serving_{key} gauge" in lines
    labelled = [ln for ln in lines if 'worker="lenet5/0"' in ln]
    assert any(ln.startswith("marvel_serving_completed{") for ln in labelled)
    assert ('marvel_serving_worker_healthy{model="lenet5",'
            'worker="lenet5/0"} 1') in lines
    # every sample line parses as "name[{labels}] value"
    for ln in lines:
        if ln.startswith("#"):
            continue
        name, value = ln.rsplit(" ", 1)
        assert name.startswith("marvel_serving_")
        float(value)


# ---------------------------------------------------------------------------
# chaos soak (slow lane): converge back to healthy, lose nothing
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_chaos_soak_converges_healthy_no_lost_or_duplicated(lenet_prog):
    """Flaky compute on every worker + one injected worker death, under 200
    requests of ragged concurrent waves: every accepted request resolves
    exactly once (success or a genuine compute failure — never a hang,
    never a WorkerUnavailable leaking to the client), and the fleet ends
    fully healthy."""
    prog, _, _, in_shape = lenet_prog
    total = 200
    spawned = []

    def factory(index):
        if index == 0 and 0 not in spawned:
            spawned.append(0)
            return FaultInjector(flaky_rate=0.05, die_after_attempts=10,
                                 seed=index)
        # fail_next guarantees the retry path fires even if the seeded
        # flaky draws happen to stay quiet for this worker
        return FaultInjector(fail_next=2, flaky_rate=0.05, seed=100 + index)

    sup = _mk_supervisor()
    sup.register("lenet5", prog, workers=2, warmup=in_shape, faults=factory,
                 max_batch=8, max_delay_ms=1.0,
                 retry=RetryPolicy(max_retries=2, **FAST_RETRY))

    async def main():
        async with sup:
            rng = np.random.default_rng(11)
            results, sent = [], 0
            while sent < total:
                n = min(int(rng.integers(1, 25)), total - sent)
                wave = await sup.submit_wave(
                    _images(in_shape, n, seed=sent),
                    return_exceptions=True,
                )
                results.extend(wave)
                sent += n
            for _ in range(500):
                if len(sup.healthy_workers()) == 2:
                    break
                await asyncio.sleep(0.01)
            return results, sup.metrics()

    results, m = asyncio.run(main())
    assert len(results) == total
    done = [r for r in results if not isinstance(r, Exception)]
    failed = [r for r in results if isinstance(r, Exception)]
    # nothing hangs; no worker-plumbing error reaches the client
    assert all(isinstance(r, InjectedFault) for r in failed)
    assert all(r.done for r in done)
    assert len({r.uid for r in done}) == len(done)  # exactly-once
    agg = m["aggregate"]
    assert agg["healthy_workers"] == 2  # converged back
    assert agg["restarts"] >= 1
    # the injected failures were actually absorbed by the retry path, and
    # restarts did not erase the failure history from the aggregate
    assert agg["retries"] >= 2
