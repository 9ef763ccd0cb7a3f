"""Serving-tier benchmark: throughput + tail latency off the async engine.

Drives the full deployment path — marvel.compile -> shard() over the local
devices -> AsyncCnnEngine — with a wave of concurrent single-image requests,
and emits the rows the CI bench-gate consumes: requests/s, p50/p99 latency,
batch occupancy, and the recompiles-after-warmup counter (must be 0: the
whole point of the bucketed AOT cache), and the event-loop handoffs per
batch (must be 1: the compute thread hands back whole batches).

The fault rows (informational, lenet5 only) measure the control plane from
this PR's robustness tier: throughput under injected flaky compute (degraded
vs healthy req/s), the shed rate of an undersized admission queue, the
supervisor's recovery latency after an abrupt worker kill (warmup replay is
an AOT cache hit, so recovery must not recompile), and the overhead of the
process-isolated worker tier (one actor process behind the unix-socket RPC
vs the same wave in-process).

The LM rows drive the continuous-batching decode tier (lm_server +
kvcache): a seeded Poisson arrival trace with varied generation lengths is
served twice on *identical executables* — once with per-step join/leave
(continuous) and once behind a wave barrier (the static-batching
comparison) — and the tokens/s ratio is emitted as
``continuous_static_speedup``, a gated metric (the ``speedup`` pattern):
the slot-refill win is model-derived, not runner wall-clock, so it must
not regress.  TTFT / inter-token tails, mean KV-slot occupancy, and the
recompiles-after-warmup counter (0: one executable per length bucket)
ride along.
"""
from __future__ import annotations

import asyncio
import time

import numpy as np

from benchmarks.common import cnn_setup, emit

MODELS = ("lenet5", "mobilenetv1")
REQUESTS = 64
MAX_BATCH = 8


async def _drive(engine, imgs) -> float:
    t0 = time.perf_counter()
    await engine.submit_wave(imgs)
    return time.perf_counter() - t0


def run() -> None:
    from repro import marvel
    from repro.launch.serve import random_images

    for name in MODELS:
        params, apply, x = cnn_setup(name)
        in_shape = tuple(np.asarray(x).shape[1:])
        prog = marvel.compile(apply, x, params=params, level="v4",
                              precompile=False).shard()
        imgs = random_images(in_shape, REQUESTS)

        # async tier: bounded admission -> coalesce -> DP dispatch
        engine = prog.serve(mode="async", max_batch=MAX_BATCH,
                            max_delay_ms=2.0)

        async def session(engine=engine, in_shape=in_shape, imgs=imgs):
            async with engine:
                engine.warmup(in_shape)
                warm_misses = engine.compute.program.cache_misses
                dt = await _drive(engine, imgs)
                return dt, warm_misses

        dt, warm_misses = asyncio.run(session())
        m = engine.metrics()
        recompiles = m["cache_misses"] - warm_misses
        handoffs = m["loop_handoffs"] / max(m["batches"], 1)
        emit(
            f"serving/{name}_async_throughput", dt / REQUESTS * 1e6,
            f"req_s={REQUESTS / dt:.1f};batches={m['batches']};"
            f"occupancy={m['batch_occupancy']:.2f};"
            f"dp_shards={m['dp_shards']};"
            f"recompiles_after_warmup={recompiles};"
            f"handoffs_per_batch={handoffs:.2f}",
        )
        emit(
            f"serving/{name}_async_latency", 0.0,
            f"p50_ms={m['p50_latency_ms']:.2f};"
            f"p99_ms={m['p99_latency_ms']:.2f};"
            f"deadline_flushes={m['deadline_flushes']};"
            f"full_flushes={m['full_flushes']}",
        )

        if name == "lenet5":
            fault_rows(prog, in_shape, imgs, dt)

    lm_rows()


def fault_rows(prog, in_shape, imgs, healthy_dt: float) -> None:
    """Informational rows for the fault-tolerant control plane."""
    from repro.runtime.batching import AdmissionError, RetryPolicy
    from repro.runtime.faults import FaultInjector
    from repro.runtime.supervisor import Supervisor

    # throughput under injected flaky compute: every 10th-ish attempt fails
    # and is retried with (fast) backoff; degradation vs the healthy run
    inj = FaultInjector(flaky_rate=0.1, seed=7)
    engine = prog.serve(mode="async", max_batch=MAX_BATCH, max_delay_ms=2.0,
                        faults=inj,
                        retry=RetryPolicy(max_retries=3,
                                          backoff_base_ms=0.1, jitter=0.0))

    async def flaky_session():
        async with engine:
            engine.warmup(in_shape)
            return await _drive(engine, imgs)

    fdt = asyncio.run(flaky_session())
    m = engine.metrics()
    emit(
        "serving/lenet5_faulty_throughput", fdt / REQUESTS * 1e6,
        f"req_s={REQUESTS / fdt:.1f};healthy_req_s={REQUESTS / healthy_dt:.1f};"
        f"degradation={fdt / healthy_dt:.2f}x;"
        f"injected={inj.injected['flaky']};retries={m['retries']};"
        f"errors={m['errors']}",
    )

    # shed rate of an undersized admission queue: the overflow is rejected
    # with a retry-after hint instead of queueing without bound
    small = prog.serve(mode="async", max_batch=MAX_BATCH, max_delay_ms=2.0,
                       max_pending=8)

    async def shed_session():
        async with small:
            small.warmup(in_shape)
            futs = []
            for im in imgs:
                try:
                    futs.append(small.submit_nowait(im))
                except AdmissionError:
                    pass
            if futs:
                await asyncio.gather(*futs)

    asyncio.run(shed_session())
    sm = small.metrics()
    emit(
        "serving/lenet5_shed_rate", 0.0,
        f"shed={sm['shed']};submitted={sm['submitted']};"
        f"shed_rate={sm['shed'] / max(sm['shed'] + sm['submitted'], 1):.2f};"
        f"completed={sm['completed']}",
    )

    # supervisor recovery latency: kill a worker, time until the health
    # loop swaps in a warmed replacement (no recompiles: AOT cache hit)
    sup = Supervisor(heartbeat_interval_ms=5.0)

    async def recovery_session():
        sup.register("lenet5", prog, workers=2, warmup=in_shape,
                     max_batch=MAX_BATCH, max_delay_ms=2.0)
        async with sup:
            misses0 = prog.cache_misses
            t0 = time.perf_counter()
            sup.workers["lenet5/0"].engine.kill("bench: injected kill")
            while len(sup.healthy_workers()) < 2:
                await asyncio.sleep(0.001)
            dt = time.perf_counter() - t0
            return dt, prog.cache_misses - misses0

    rdt, recompiles = asyncio.run(recovery_session())
    agg = sup.metrics()["aggregate"]
    emit(
        "serving/lenet5_recovery_latency", rdt * 1e3,
        f"recovery_ms={rdt * 1e3:.1f};restarts={agg['restarts']};"
        f"recompiles_during_recovery={recompiles}",
    )

    # process isolation overhead (informational): the same supervised wave
    # through one in-process worker vs one actor process behind the
    # unix-socket RPC tier; the delta is the pickle + frame round-trip.
    # This process has served in-process above and so holds its devices;
    # an accelerator chip belongs to one process, so the actor row runs
    # only where devices can be shared (CPU)
    import jax

    if jax.default_backend() != "cpu":
        print(f"# serving/lenet5_process_isolation skipped: this process "
              f"holds the {jax.default_backend()} device its worker would "
              f"need (run launch/serve.py --isolation process instead)")
        return
    import inspect

    from repro.runtime.actor import cnn_program_factory

    n = min(32, len(imgs))

    async def supervised_wave(**reg_kwargs):
        program = reg_kwargs.pop("program", prog)
        s = Supervisor()
        s.register("lenet5", program, workers=1, warmup=in_shape,
                   max_batch=MAX_BATCH, max_delay_ms=2.0, **reg_kwargs)
        async with s:
            t0 = time.perf_counter()
            await s.submit_wave(imgs[:n])
            dt = time.perf_counter() - t0
            p = s.workers["lenet5/0"].engine.ping()  # records RPC RTT
            if inspect.isawaitable(p):
                await p
            return dt, s.metrics()["aggregate"]

    idt, _ = asyncio.run(supervised_wave())
    pdt, pagg = asyncio.run(supervised_wave(
        program=None, isolation="process",
        program_factory=cnn_program_factory,
        factory_kwargs=dict(model="lenet5")))
    emit(
        "serving/lenet5_process_isolation", pdt / n * 1e6,
        f"process_req_s={n / pdt:.1f};inproc_req_s={n / idt:.1f};"
        f"process_overhead={pdt / idt:.2f}x;"
        f"rpc_p50_ms={pagg['rpc_roundtrip_p50_ms']:.2f}",
    )


LM_ARCH = "qwen3-8b"
LM_REQUESTS = 24
LM_SLOTS = 4
LM_MAX_LEN = 64


def lm_trace(vocab: int, seed: int = 42):
    """The seeded Poisson arrival trace both engines serve: arrival decode
    step (exponential inter-arrivals, so step-domain Poisson), prompt, and
    a varied generation budget (short and long sequences co-batched — the
    regime where wave barriers hurt and slot refill wins)."""
    rng = np.random.default_rng(seed)
    steps = np.cumsum(rng.exponential(scale=2.0, size=LM_REQUESTS))
    trace = []
    for i in range(LM_REQUESTS):
        prompt = rng.integers(1, vocab, size=int(rng.integers(3, 9))).tolist()
        max_new = int(rng.integers(4, 25))
        trace.append((int(steps[i]), prompt, max_new))
    return trace


def _drive_lm(engine, trace):
    """Feed the arrival trace in decode-step time and run to drain;
    returns (wall seconds, tokens generated, mean slot occupancy)."""
    import time as _t

    i, step, occ = 0, 0, []
    t0 = _t.perf_counter()
    while i < len(trace) or engine.active:
        while i < len(trace) and trace[i][0] <= step:
            arrival, prompt, max_new = trace[i]
            engine.submit(prompt, uid=i, max_new_tokens=max_new)
            i += 1
        engine.step()
        occ.append(engine.manager.occupancy())
        step += 1
    dt = _t.perf_counter() - t0
    toks = engine.metrics()["tokens_total"]
    return dt, toks, float(np.mean(occ)) if occ else 0.0


def lm_rows() -> None:
    """Continuous-batching LM tier vs the wave-barrier static baseline, on
    identical executables (both engines share the program's LM exec cache)."""
    import jax

    from repro import marvel
    from repro.configs.base import RunConfig
    from repro.configs.registry import get_arch, smoke_variant
    from repro.models import transformer as T

    cfg = smoke_variant(get_arch(LM_ARCH)).replace(param_dtype="float32")
    run = RunConfig(seq_len=32, global_batch=LM_SLOTS, mode="decode",
                    attn_chunk=16)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    x = np.ones((1, 8), np.int32)
    prog = marvel.compile(lambda p, t: T.forward_lm(p, t, cfg, run)[0], x,
                          params=params, precompile=False)
    trace = lm_trace(cfg.vocab)
    lm_kwargs = dict(cfg=cfg, run=run, slots=LM_SLOTS, max_len=LM_MAX_LEN)

    results = {}
    for admission in ("continuous", "wave"):
        engine = prog.serve(mode="lm_sync", admission=admission, **lm_kwargs)
        engine.warmup()
        warm_misses = engine.compile_misses
        dt, toks, occ = _drive_lm(engine, trace)
        m = engine.metrics()
        recompiles = m["compile_misses"] - warm_misses
        results[admission] = (toks / dt, m)
        emit(
            f"serving/lm_{admission}_throughput", dt / LM_REQUESTS * 1e6,
            f"tok_s={toks / dt:.1f};tokens={toks};"
            f"decode_steps={m['decode_steps']};"
            f"kv_slot_occupancy={occ:.2f};"
            f"ttft_p50_ms={m['ttft_p50_ms']:.2f};"
            f"ttft_p99_ms={m['ttft_p99_ms']:.2f};"
            f"intertoken_p99_ms={m['intertoken_p99_ms']:.2f};"
            f"slot_reuses={m['kv_slot_reuses']};"
            f"recompiles_after_warmup={recompiles}",
        )
        assert recompiles == 0, (
            f"{admission}: {recompiles} recompiles after warmup"
        )

    cont_tok_s, cm = results["continuous"]
    wave_tok_s, _ = results["wave"]
    ratio = cont_tok_s / wave_tok_s
    emit(
        "serving/lm_continuous_vs_static", 0.0,
        f"continuous_static_speedup={ratio:.3f};"
        f"continuous_tok_s={cont_tok_s:.1f};static_tok_s={wave_tok_s:.1f};"
        f"requests={LM_REQUESTS};slots={LM_SLOTS}",
    )

    # int8 KV cache: same trace, 4x smaller attention pools; the memory
    # ratio is model-derived, the throughput is informational
    engine8 = prog.serve(mode="lm_sync", kv_quant="int8", **lm_kwargs)
    engine8.warmup()
    dt8, toks8, _ = _drive_lm(engine8, trace)
    m8 = engine8.metrics()
    emit(
        "serving/lm_int8_kv", dt8 / LM_REQUESTS * 1e6,
        f"tok_s={toks8 / dt8:.1f};kv_cache_bytes={m8['kv_cache_bytes']};"
        f"fp32_kv_cache_bytes={cm['kv_cache_bytes']};"
        f"cache_ratio={cm['kv_cache_bytes'] / m8['kv_cache_bytes']:.2f}",
    )


if __name__ == "__main__":
    run()
