"""Serving launcher: LM continuous batching, or the CNN async serving tier.

LM continuous-batching tier (marvel.compile -> slot-based KV manager ->
per-step join/leave engine; ``--kv-quant int8`` for the quantized cache)::

    python -m repro.launch.serve --arch qwen3-8b --smoke --lm --requests 8

Supervised LM tier (fault-tolerant control plane, N workers, Prometheus
snapshot on exit; see docs/serving_ops.md)::

    python -m repro.launch.serve --arch qwen3-8b --smoke --lm \
        --supervised --workers 2

Legacy LM wave loop (caller-driven ServeEngine, any arch family)::

    python -m repro.launch.serve --arch qwen3-8b --smoke --requests 8

CNN async tier (marvel.compile -> shard over local devices -> async engine)::

    python -m repro.launch.serve --cnn lenet5 --requests 64 --max-batch 8

Supervised CNN tier::

    python -m repro.launch.serve --cnn lenet5 --supervised --workers 2

Process-isolated workers (each worker is its own OS process owning a
device slice; a ``kill -9`` costs one worker, never the fleet)::

    python -m repro.launch.serve --cnn lenet5 --supervised --workers 2 \
        --isolation process
"""
from __future__ import annotations

import argparse
import asyncio
import json
import time

import jax
import numpy as np

from repro.configs import get_arch, list_archs, smoke_variant
from repro.configs.base import RunConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.runtime.server import Request, ServeEngine


def serve_lm(args) -> None:
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    run = RunConfig(seq_len=128, global_batch=args.slots, mode="decode",
                    attn_chunk=32, ssm_chunk=32, wkv_chunk=16)
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    frames = None
    if cfg.family == "enc_dec":
        frames = jax.random.normal(
            key, (args.slots, cfg.n_frames, cfg.d_model)
        ).astype("bfloat16")
    engine = ServeEngine(params, cfg, run, batch_slots=args.slots,
                         max_len=128, frames=frames)
    t0 = time.time()
    for uid in range(args.requests):
        prompt = [(uid * 7 + i) % (cfg.vocab - 1) + 1 for i in range(5)]
        engine.submit(
            Request(uid=uid, prompt=prompt, max_new_tokens=args.max_new)
        )
    engine.run_until_drained()
    dt = time.time() - t0
    print(f"served {args.requests} requests ({args.max_new} tokens each) "
          f"in {dt:.1f}s with {args.slots} slots")
    print(json.dumps(engine.metrics(), indent=1))


def lm_prompts(vocab: int, n: int) -> list[list[int]]:
    """The launcher's deterministic prompt wave."""
    return [[(uid * 7 + i) % (vocab - 1) + 1 for i in range(5)]
            for uid in range(n)]


def serve_lm_continuous(args) -> None:
    """The LM serving tier: continuous batching over a bucketed KV-slot
    pool, optionally supervised (``--supervised --workers N``), each
    worker optionally its own OS process (``--isolation process``)."""
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    prompts = lm_prompts(cfg.vocab, args.requests)
    process = args.supervised and args.isolation == "process"

    def build_prog():
        from repro import marvel

        run = RunConfig(seq_len=32, global_batch=args.slots, mode="decode",
                        attn_chunk=16)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        x = np.ones((1, 8), np.int32)
        prog = marvel.compile(
            lambda p, t: T.forward_lm(p, t, cfg, run)[0], x,
            params=params, precompile=False)
        return prog, dict(cfg=cfg, run=run)

    engine_kwargs = dict(slots=args.slots, max_len=args.max_len,
                         kv_quant=args.kv_quant)
    if not process:
        prog, ctx_kwargs = build_prog()
        lm_kwargs = {**ctx_kwargs, **engine_kwargs}

    if args.supervised:
        from repro.runtime.supervisor import Supervisor

        async def main() -> str:
            sup = Supervisor()
            if process:
                # each actor rebuilds cfg/run child-side via the factory;
                # only the engine knobs cross the pipe
                from repro.runtime.actor import lm_program_factory

                sup.register(args.arch, None, workers=args.workers,
                             mode="lm", warmup=(), isolation="process",
                             program_factory=lm_program_factory,
                             factory_kwargs=dict(arch=args.arch,
                                                 smoke=args.smoke,
                                                 global_batch=args.slots),
                             **engine_kwargs)
            else:
                sup.register(args.arch, prog, workers=args.workers,
                             mode="lm", warmup=(), **lm_kwargs)
            async with sup:
                t0 = time.perf_counter()
                results = await sup.submit_wave(
                    prompts, max_new_tokens=args.max_new)
                dt = time.perf_counter() - t0
                toks = sum(len(r.generated) for r in results)
                agg = sup.metrics()["aggregate"]
                print(f"served {len(results)} sequences ({toks} tokens) "
                      f"across {agg['healthy_workers']} supervised LM "
                      f"worker(s) in {dt:.2f}s")
                return sup.prometheus()

        print(asyncio.run(main()), end="")
        return

    engine = prog.serve(mode="lm", **lm_kwargs)

    async def main() -> dict:
        async with engine:
            engine.warmup()
            t0 = time.perf_counter()
            results = await engine.submit_wave(
                prompts, max_new_tokens=args.max_new)
            dt = time.perf_counter() - t0
            toks = sum(len(r.generated) for r in results)
            m = engine.metrics()
            print(f"served {len(results)} sequences ({toks} tokens) in "
                  f"{dt:.2f}s — {m['tokens_per_s']:.1f} tok/s busy, "
                  f"{m['compile_misses']} compiles "
                  f"(0 after warmup), kv_quant={m['kv_quant']}")
            print("sample generation:", results[0].generated)
            return m

    print(json.dumps(asyncio.run(main()), indent=1, default=str))


def random_images(in_shape, n: int, seed: int = 0) -> list[np.ndarray]:
    """A deterministic request wave (shared by the example + benchmarks)."""
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(in_shape).astype(np.float32)
            for _ in range(n)]


def serve_cnn_supervised(args, prog, in_shape) -> None:
    """The fault-tolerant path: a Supervisor routing over N workers, with
    the aggregated Prometheus snapshot printed on exit
    (see docs/serving_ops.md for the ops runbook)."""
    from repro.runtime.supervisor import Supervisor

    async def main() -> str:
        sup = Supervisor()
        reg_kwargs = dict(workers=args.workers, warmup=in_shape,
                          max_batch=args.max_batch,
                          max_delay_ms=args.max_delay_ms)
        if args.isolation == "process":
            # no parent-side program: each actor compiles its own copy
            # on its granted device slice
            from repro.runtime.actor import cnn_program_factory

            reg_kwargs.update(isolation="process",
                              program_factory=cnn_program_factory,
                              factory_kwargs=dict(model=args.cnn))
        sup.register(args.cnn, prog, **reg_kwargs)
        async with sup:
            t0 = time.perf_counter()
            results = await sup.submit_wave(
                random_images(in_shape, args.requests)
            )
            dt = time.perf_counter() - t0
            agg = sup.metrics()["aggregate"]
            print(f"served {len(results)} requests across "
                  f"{agg['healthy_workers']} supervised worker(s) in "
                  f"{dt * 1e3:.1f} ms "
                  f"({dt / args.requests * 1e6:.0f} us/request)")
            return sup.prometheus()

    print(asyncio.run(main()), end="")


async def serve_cnn_wave(prog, in_shape, images, *, max_batch: int,
                         max_delay_ms: float) -> tuple[list, dict]:
    """Warm every batch bucket, then serve ``images`` as one wave through
    the async engine.  Returns ``(results, metrics)``; the metrics add
    ``warmup_s``, ``wave_s`` and ``recompiles_after_warmup`` to the
    engine's own."""
    engine = prog.serve(mode="async", max_batch=max_batch,
                        max_delay_ms=max_delay_ms)
    async with engine:
        t0 = time.perf_counter()
        engine.warmup(in_shape)
        warmup_s = time.perf_counter() - t0
        misses = prog.cache_misses
        t0 = time.perf_counter()
        results = await engine.submit_wave(images)
        wave_s = time.perf_counter() - t0
        m = engine.metrics()
    m.update(warmup_s=warmup_s, wave_s=wave_s,
             recompiles_after_warmup=prog.cache_misses - misses)
    return results, m


def serve_cnn(args) -> None:
    from repro.models.cnn import get_cnn
    from repro.runtime.actor import cnn_program_factory

    in_shape = get_cnn(args.cnn)[2]
    if args.supervised and args.isolation == "process":
        serve_cnn_supervised(args, None, in_shape)
        return
    prog = cnn_program_factory(args.cnn)
    if args.supervised:
        serve_cnn_supervised(args, prog, in_shape)
        return
    results, m = asyncio.run(serve_cnn_wave(
        prog, in_shape, random_images(in_shape, args.requests),
        max_batch=args.max_batch, max_delay_ms=args.max_delay_ms))
    print(f"served {len(results)} requests in {m['batches']} batches over "
          f"{prog.dp_shards} DP shard(s) in {m['wave_s'] * 1e3:.1f} ms "
          f"({m['wave_s'] / args.requests * 1e6:.0f} us/request)")
    print(json.dumps(m, indent=1))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--cnn", help="serve a CNN via the async tier instead")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--lm", action="store_true",
                    help="serve --arch through the continuous-batching LM "
                         "tier (slot-based KV manager) instead of the "
                         "legacy wave loop")
    ap.add_argument("--max-len", type=int, default=64,
                    help="largest KV length bucket (with --lm)")
    ap.add_argument("--kv-quant", choices=["int8"], default=None,
                    help="quantize the KV cache (with --lm)")
    ap.add_argument("--supervised", action="store_true",
                    help="run the tier under the fault-tolerant supervisor "
                         "(prints Prometheus metrics on exit)")
    ap.add_argument("--workers", type=int, default=2,
                    help="supervised engine workers (with --supervised)")
    ap.add_argument("--isolation", choices=["inproc", "process"],
                    default="inproc",
                    help="supervised worker isolation: in-process engines "
                         "(default) or one OS process per worker with its "
                         "own device slice (crash-only recovery)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.supervised and not (args.cnn or args.lm):
        ap.error("--supervised requires --cnn or --lm")
    if args.isolation == "process" and not args.supervised:
        ap.error("--isolation process requires --supervised")
    if args.lm and not args.arch:
        ap.error("--lm requires --arch")
    if (args.cnn is None) == (args.arch is None):
        ap.error("pass exactly one of --arch (LM) or --cnn (CNN tier)")
    if args.cnn:
        serve_cnn(args)
    elif args.lm:
        serve_lm_continuous(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
