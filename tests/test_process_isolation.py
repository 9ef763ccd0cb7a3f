"""Process-isolated worker actors (runtime/actor.py) under the
supervisor: device-allocation planning, the declarative process-level
fault plans, and crash-only recovery across a real OS process boundary.

Layer map:

* pure units — ``allocation_plan`` partitioning, ``ActorSpec`` /
  ``ProcessFaultPlan`` picklability (spawn ships the spec through a
  pickle hop), ``ProcessFaultInjector`` thresholds with ``os.kill`` /
  ``os._exit`` stubbed out;
* fast integration — a real CNN actor fleet: submit round-trips over the
  unix-socket RPC, then the acceptance scenario: SIGKILL a worker
  mid-wave and require zero lost requests plus a warm replacement
  (``recompiles_after_warmup == 0``);
* slow lane — the same zero-loss guarantee for the LM plane (full-prompt
  replay on the replacement), SIGSTOP hang recovery, nonzero-exit
  crashes, corrupt/truncated RPC replies (fail deterministically, never
  hang), slow-start bring-up, and a deterministic multi-fault chaos
  soak.  Every process test carries a hard ``timeout`` marker: a hung
  RPC fails the test instead of wedging the CI job.
"""
import asyncio
import os
import pickle
import signal

import numpy as np
import pytest

from repro.runtime import faults as faults_mod
from repro.runtime.actor import (
    ActorSpec, DeviceAllocation, allocation_plan, cnn_program_factory,
    lm_program_factory,
)
from repro.runtime.faults import (
    FaultInjector, FaultPlan, ProcessFaultInjector, ProcessFaultPlan,
    make_injector,
)
from repro.runtime.supervisor import Supervisor

IN_SHAPE = (28, 28, 1)  # lenet5


def _images(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(IN_SHAPE).astype(np.float32)
            for _ in range(n)]


def _mk_supervisor(**kw):
    kw.setdefault("heartbeat_interval_ms", 50.0)
    kw.setdefault("pick_timeout_ms", 60_000.0)
    return Supervisor(**kw)


def _register_cnn(sup, *, workers=2, **kw):
    sup.register("lenet5", None, workers=workers, isolation="process",
                 program_factory=cnn_program_factory,
                 factory_kwargs=dict(model="lenet5"),
                 warmup=IN_SHAPE, max_batch=8, **kw)


async def _converged(sup, n, *, tries=1200):
    for _ in range(tries):
        if len(sup.healthy_workers()) == n:
            return True
        await asyncio.sleep(0.05)
    return False


def _first_incarnation_only(plan, index=0):
    """Fault-plan factory that arms ``plan`` for worker ``index``'s FIRST
    incarnation only — the replacement spawns clean, so recovery
    converges instead of crash-looping."""
    armed = []

    def factory(i):
        if i == index and not armed:
            armed.append(True)
            return plan
        return None

    return factory


# ---------------------------------------------------------------------------
# device allocation plan
# ---------------------------------------------------------------------------


class TestAllocationPlan:
    def test_contiguous_split_remainder_to_low_indices(self):
        plan = allocation_plan(3, n_devices=8, platform="cpu")
        assert [a.indices for a in plan] == [(0, 1, 2), (3, 4, 5), (6, 7)]
        assert all(a.platform == "cpu" for a in plan)

    def test_even_split(self):
        plan = allocation_plan(2, n_devices=2, platform="cpu")
        assert [a.indices for a in plan] == [(0,), (1,)]

    def test_oversubscription_round_robins(self):
        plan = allocation_plan(5, n_devices=2, platform="cpu")
        assert [a.indices for a in plan] == [(0,), (1,), (0,), (1,), (0,)]

    def test_deterministic_so_replacements_inherit_their_slice(self):
        a = allocation_plan(4, n_devices=8, platform="cpu")
        b = allocation_plan(4, n_devices=8, platform="cpu")
        assert a == b  # a respawned worker i always gets slice i

    def test_defaults_come_from_the_local_backend(self):
        import jax
        plan = allocation_plan(1)
        assert plan[0].platform == jax.default_backend()
        assert max(plan[0].indices) < len(jax.devices())

    def test_rejects_degenerate_inputs(self):
        with pytest.raises(ValueError, match="workers"):
            allocation_plan(0, n_devices=2, platform="cpu")
        with pytest.raises(ValueError, match="n_devices"):
            allocation_plan(2, n_devices=0, platform="cpu")

    def test_tpu_gives_each_worker_its_own_confined_chip(self):
        plan = allocation_plan(4, n_devices=4, platform="tpu")
        assert [a.indices for a in plan] == [(0,), (1,), (2,), (3,)]
        assert all(a.confine for a in plan)
        assert plan[2].env() == {"TPU_VISIBLE_CHIPS": "2",
                                 "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                                 "TPU_PROCESS_BOUNDS": "1,1,1"}
        # one worker takes the whole host and sees every chip
        (whole,) = allocation_plan(1, n_devices=4, platform="tpu")
        assert whole.indices == (0, 1, 2, 3) and whole.env() == {}

    @pytest.mark.parametrize("workers,chips", [(2, 1), (5, 4), (2, 4)])
    def test_tpu_never_shares_or_splits_chips_unevenly(self, workers, chips):
        with pytest.raises(ValueError, match="TPU chip|TPU workers"):
            allocation_plan(workers, n_devices=chips, platform="tpu")

    def test_local_devices_reads_the_host_without_jax(self, monkeypatch):
        from repro.runtime.actor import local_devices

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.setenv("XLA_FLAGS",
                           "--xla_force_host_platform_device_count=3")
        assert local_devices() == ("cpu", 3)
        monkeypatch.delenv("XLA_FLAGS")
        assert local_devices() == ("cpu", 1)

    def test_parent_side_planning_starts_no_jax_backend(self):
        """The supervisor plans worker devices in the parent; starting a
        backend there would take the chips its workers need."""
        import subprocess
        import sys

        code = (
            "from repro.runtime import supervisor\n"
            "from repro.runtime.actor import allocation_plan\n"
            "plan = allocation_plan(2)\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized(), plan\n"
            "print(len(plan))\n"
        )
        out = subprocess.run([sys.executable, "-c", code], check=True,
                             capture_output=True, text=True, timeout=120)
        assert out.stdout.strip() == "2"


# ---------------------------------------------------------------------------
# spec + plan picklability (the spawn boundary is a pickle hop)
# ---------------------------------------------------------------------------


def test_actor_spec_pickles_with_factory_by_reference():
    spec = ActorSpec(
        name="lm/0",
        program_factory=lm_program_factory,
        factory_kwargs=dict(arch="qwen3-8b", smoke=True),
        mode="lm",
        engine_kwargs=dict(slots=4, max_len=64),
        allocation=DeviceAllocation((1, 2), "cpu"),
        fault_plan=ProcessFaultPlan(sigkill_after_attempts=3,
                                    corrupt_reply_after=5,
                                    corrupt_mode="garbage"),
        warmup_specs=[((28, 28, 1), "float32")],
    )
    out = pickle.loads(pickle.dumps(spec))
    assert out.program_factory is lm_program_factory  # by reference
    assert out.allocation == DeviceAllocation((1, 2), "cpu")
    assert out.fault_plan.sigkill_after_attempts == 3
    assert out.fault_plan.corrupt_mode == "garbage"
    assert out.engine_kwargs == dict(slots=4, max_len=64)


# ---------------------------------------------------------------------------
# ProcessFaultInjector units (process-killing syscalls stubbed out)
# ---------------------------------------------------------------------------


class TestProcessFaultInjector:
    def test_sigkill_fires_past_attempt_threshold(self, monkeypatch):
        calls = []
        monkeypatch.setattr(faults_mod.os, "kill",
                            lambda pid, sig: calls.append((pid, sig)))
        inj = ProcessFaultInjector(sigkill_after_attempts=2)
        inj.before_compute((1,))
        inj.before_compute((2,))
        assert calls == []  # attempts 1..2 run clean
        inj.before_compute((3,))
        assert calls == [(os.getpid(), signal.SIGKILL)]
        assert inj.injected["sigkill"] == 1

    def test_sigstop_fires_past_attempt_threshold(self, monkeypatch):
        calls = []
        monkeypatch.setattr(faults_mod.os, "kill",
                            lambda pid, sig: calls.append((pid, sig)))
        inj = ProcessFaultInjector(sigstop_after_attempts=1)
        inj.before_compute((1,))
        inj.before_compute((2,))
        assert calls == [(os.getpid(), signal.SIGSTOP)]

    def test_exit_fires_with_configured_code(self, monkeypatch):
        codes = []
        monkeypatch.setattr(faults_mod.os, "_exit",
                            lambda code: codes.append(code))
        inj = ProcessFaultInjector(exit_after_attempts=1, exit_code=5)
        inj.before_compute((1,))
        inj.before_compute((2,))
        assert codes == [5]
        assert inj.injected["exit"] == 1

    def test_reply_corruption_fires_exactly_once(self):
        inj = ProcessFaultInjector(corrupt_reply_after=2,
                                   corrupt_mode="garbage")
        assert [inj.reply_corruption() for _ in range(4)] == [
            None, "garbage", None, None]
        assert inj.injected["corrupt_reply"] == 1

    def test_make_injector_dispatch(self):
        assert make_injector(None) is None
        live = FaultInjector(FaultPlan(fail_next=1))
        assert make_injector(live) is live
        assert isinstance(make_injector(ProcessFaultPlan(exit_after_attempts=1)),
                          ProcessFaultInjector)
        plain = make_injector(FaultPlan(fail_next=1))
        assert isinstance(plain, FaultInjector)
        assert not isinstance(plain, ProcessFaultInjector)
        with pytest.raises(TypeError):
            make_injector("not a plan")


# ---------------------------------------------------------------------------
# fast integration: a real CNN actor fleet
# ---------------------------------------------------------------------------


@pytest.mark.timeout(300)
def test_process_worker_roundtrip_and_rpc_metrics():
    async def main():
        sup = _mk_supervisor()
        _register_cnn(sup, workers=1)
        async with sup:
            wh = sup.workers["lenet5/0"]
            assert wh.engine.pid is not None and wh.engine.pid != os.getpid()
            results = await sup.submit_wave(_images(8))
            assert len(results) == 8 and all(r.done for r in results)
            assert all(r.error is None for r in results)
            assert sorted({r.uid for r in results}) == sorted(
                r.uid for r in results)  # unique uids
            # once a heartbeat pings, the parent-measured RPC RTT exists
            for _ in range(200):
                if sup.metrics()["aggregate"]["rpc_roundtrip_p50_ms"] > 0:
                    break
                await asyncio.sleep(0.02)
            agg = sup.metrics()["aggregate"]
            assert agg["rpc_roundtrip_p50_ms"] > 0.0
            assert agg["worker_process_restarts"] == 0
            # the child's engine counters flow back through PING
            assert sup.workers["lenet5/0"].engine.metrics()["pid"] \
                == wh.engine.pid
    asyncio.run(main())


@pytest.mark.timeout(300)
def test_cnn_sigkill_mid_wave_loses_nothing():
    """The acceptance scenario: ``kill -9`` one worker while a wave is in
    flight.  Every accepted request must still resolve (failover re-routes
    the dead worker's share), the fleet heals to full strength, and the
    replacement is warm — zero recompiles after its warmup replay."""
    async def main():
        sup = _mk_supervisor()
        _register_cnn(sup, workers=2)
        async with sup:
            w0 = sup.workers["lenet5/0"]
            pid0 = w0.engine.pid

            async def killer():
                # wait until worker 0 actually owns in-flight requests so
                # the kill lands mid-wave, then SIGKILL the OS process
                for _ in range(2000):
                    if w0.engine.outstanding > 0:
                        break
                    await asyncio.sleep(0.001)
                os.kill(pid0, signal.SIGKILL)

            kt = asyncio.ensure_future(killer())
            results = await sup.submit_wave(_images(48))
            await kt

            # zero loss: every request resolved exactly once
            assert len(results) == 48
            assert all(r.done and r.error is None for r in results)
            assert len({r.uid for r in results}) == 48

            assert await _converged(sup, 2), "fleet never healed"
            replacement = sup.workers["lenet5/0"].engine
            assert replacement.pid != pid0

            agg = sup.metrics()["aggregate"]
            assert agg["worker_process_restarts"] >= 1
            assert agg["restarts"] >= 1  # monotone aggregate kept the retire
            assert agg["failovers"] >= 1

            # warm handoff: the replacement replayed the recorded warmup
            # specs before reopening, so serving another wave compiles
            # nothing new
            results2 = await sup.submit_wave(_images(16, seed=1))
            assert all(r.done for r in results2)
            await replacement.ping()
            assert replacement.metrics()["recompiles_after_warmup"] == 0
    asyncio.run(main())


# ---------------------------------------------------------------------------
# slow lane: the full process-fault taxonomy + chaos soak
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_lm_sigkill_mid_wave_replays_full_prompts():
    """LM zero-loss: worker 0 SIGKILLs itself mid-decode; its sequences
    fail over and replay their FULL prompts on a healthy sibling, so every
    stream completes at full length."""
    async def main():
        sup = _mk_supervisor()
        sup.register(
            "tiny-lm", None, workers=2, mode="lm", isolation="process",
            program_factory=lm_program_factory,
            factory_kwargs=dict(arch="qwen3-8b", smoke=True),
            warmup=(), slots=4, max_len=64,
            faults=_first_incarnation_only(
                ProcessFaultPlan(sigkill_after_attempts=3)),
        )
        async with sup:
            prompts = [[(u * 7 + i) % 97 + 1 for i in range(5)]
                       for u in range(8)]
            results = await sup.submit_wave(prompts, max_new_tokens=6)
            assert len(results) == 8
            assert all(r.error is None for r in results)
            assert all(len(r.generated) == 6 for r in results)
            # converge BEFORE reading restart counters: the wave can finish
            # (via failover) before the health loop records the recovery
            assert await _converged(sup, 2)
            agg = sup.metrics()["aggregate"]
            assert agg["failovers"] >= 1
            assert agg["worker_process_restarts"] >= 1
    asyncio.run(main())


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_sigstop_hang_is_detected_and_recovered():
    """A SIGSTOPped child answers nothing: heartbeats time out, the
    supervisor SIGKILLs the frozen process (SIGKILL fells a stopped
    process) and brings up a replacement; in-flight requests re-route."""
    async def main():
        sup = _mk_supervisor(hang_timeout_ms=1_500.0)
        _register_cnn(
            sup, workers=2,
            faults=_first_incarnation_only(
                ProcessFaultPlan(sigstop_after_attempts=1)))
        async with sup:
            old = sup.workers["lenet5/0"].engine
            results = await sup.submit_wave(_images(24))
            assert all(r.done and r.error is None for r in results)
            assert len({r.uid for r in results}) == 24
            assert await _converged(sup, 2)
            assert sup.workers["lenet5/0"].engine.pid != old.pid
            assert old.exitcode == -signal.SIGKILL  # parent felled it
            assert sup.metrics()["aggregate"]["worker_process_restarts"] >= 1
    asyncio.run(main())


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_nonzero_exit_crash_is_recovered():
    async def main():
        sup = _mk_supervisor()
        _register_cnn(
            sup, workers=2,
            faults=_first_incarnation_only(
                ProcessFaultPlan(exit_after_attempts=1, exit_code=5)))
        async with sup:
            old = sup.workers["lenet5/0"].engine
            results = await sup.submit_wave(_images(24))
            assert all(r.done and r.error is None for r in results)
            assert await _converged(sup, 2)
            assert old.exitcode == 5  # the sentinel saw the real exit code
            assert sup.metrics()["aggregate"]["worker_process_restarts"] >= 1
    asyncio.run(main())


@pytest.mark.slow
@pytest.mark.timeout(600)
@pytest.mark.parametrize("mode", ["truncate", "garbage"])
def test_corrupt_rpc_reply_fails_fast_never_hangs(mode):
    """A corrupted/truncated reply frame must surface as a deterministic
    ProtocolError parent-side — the actor is killed and replaced, pending
    calls fail over, and nothing blocks (the timeout marker is the
    no-hang proof)."""
    async def main():
        sup = _mk_supervisor()
        _register_cnn(
            sup, workers=2,
            faults=_first_incarnation_only(
                ProcessFaultPlan(corrupt_reply_after=2, corrupt_mode=mode)))
        async with sup:
            results = await sup.submit_wave(_images(24))
            assert all(r.done and r.error is None for r in results)
            assert len({r.uid for r in results}) == 24
            assert await _converged(sup, 2)
            assert sup.workers["lenet5/0"].restarts >= 1
    asyncio.run(main())


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_slow_start_still_brings_the_fleet_up():
    async def main():
        loop = asyncio.get_running_loop()
        sup = _mk_supervisor()
        _register_cnn(
            sup, workers=1,
            faults=_first_incarnation_only(
                ProcessFaultPlan(slow_start_ms=1_500.0)))
        t0 = loop.time()
        async with sup:
            assert loop.time() - t0 >= 1.5  # the delay really happened
            results = await sup.submit_wave(_images(4))
            assert all(r.done for r in results)
    asyncio.run(main())


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_process_chaos_soak_every_request_resolves_exactly_once():
    """Deterministic chaos soak: three workers, three different process
    faults (self-SIGKILL, nonzero exit, SIGSTOP freeze) armed on their
    first incarnations, three waves of traffic.  Invariants: every
    request resolves exactly once, the fleet converges back to full
    strength, and the aggregate counters stay monotone across all the
    process restarts."""
    async def main():
        plans = {0: ProcessFaultPlan(sigkill_after_attempts=2),
                 1: ProcessFaultPlan(exit_after_attempts=3, exit_code=7),
                 2: ProcessFaultPlan(sigstop_after_attempts=4)}
        armed: set[int] = set()

        def chaos(index):
            if index in plans and index not in armed:
                armed.add(index)
                return plans[index]
            return None

        sup = _mk_supervisor(hang_timeout_ms=1_500.0)
        _register_cnn(sup, workers=3, faults=chaos)
        async with sup:
            all_results = []
            completed_seen = 0
            for wave in range(3):
                results = await sup.submit_wave(_images(24, seed=wave))
                assert len(results) == 24
                assert all(r.done and r.error is None for r in results)
                all_results.extend(results)
                agg = sup.metrics()["aggregate"]
                assert agg["completed"] >= completed_seen  # monotone
                completed_seen = agg["completed"]

            # exactly-once: 72 requests, 72 distinct uids, each resolved
            assert len({r.uid for r in all_results}) == len(all_results) == 72

            assert await _converged(sup, 3), "fleet never healed"
            agg = sup.metrics()["aggregate"]
            assert agg["worker_process_restarts"] >= 3  # one per chaos plan
            assert agg["healthy_workers"] == 3
            assert agg["failovers"] >= 1
    asyncio.run(main())
