"""The CNN system: ``marvel.compile`` at the configuration's level, served
by ``MarvelProgram.serve(mode="async")`` (the ``AsyncCnnEngine``), loaded
by the traffic mix, and checked against the plain reference.

Set-up builds the weights from the seed on the device in one jitted call,
compiles the program, warms exactly the traffic's buckets through the
engine, and sends one full bucket through the engine's whole path.  The
window then runs the traffic.  After it closes, every answer still due is
awaited, the device's memory peak is read, the program is freed, and the
reference is computed over the input pool.
"""
from __future__ import annotations

import asyncio
import functools
import gc
import time

import numpy as np

from chipbench import check, loadgen
from chipbench.spec import load_module

DRAIN_S = 60.0  # answers due at the close may take this long to arrive
REF_BLOCK = 16  # reference rows per call


def _make_weights(ref, cfg, key):
    import jax

    return jax.jit(functools.partial(ref.init, cfg=cfg))(key)


def _images(cfg, traffic, key):
    """The input pool: per image a mix of random patterns at four spatial
    scales with weights of its own, per-channel offsets and pixel noise,
    so that different images get clearly different logits.  Made on the
    device, kept on the host as the requests' payload."""
    import jax
    import jax.numpy as jnp

    h, w, c = cfg["in_shape"]
    n = traffic["pool"]

    def make(key):
        keys = jax.random.split(key, 7)
        mix = jax.random.dirichlet(keys[0], jnp.full((4,), 0.5), (n,))
        img = 0.2 * jax.random.normal(keys[1], (n, h, w, c), jnp.float32)
        for i, cells in enumerate((2, 7, 28, 112)):
            coarse = jax.random.normal(keys[2 + i], (n, cells, cells, c))
            img += (2.0 * mix[:, i, None, None, None]
                    * jax.image.resize(coarse, (n, h, w, c), "linear"))
        return img + 0.5 * jax.random.normal(keys[6], (n, 1, 1, c))

    return np.asarray(jax.jit(make)(key))


def _check_layout(program_init, params):
    """The benchmark's weights must have the program model's layout (the
    sizes are the configuration's)."""
    import jax

    want = jax.eval_shape(program_init, jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(params):
        raise RuntimeError("the reference's parameter layout differs from "
                           "the program's model")


def reference_logits(ref, cfg, params, images, bits=None, block=REF_BLOCK):
    """The plain reference over ``images``, float32 at ``highest``
    precision, ``block`` rows per call (one compiled shape)."""
    import jax

    fwd = jax.jit(lambda p, x: ref.forward(p, x, cfg, bits=bits))
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(images), block):
            out.append(np.asarray(fwd(params, images[i:i + block]),
                                  np.float64))
    return np.concatenate(out)


class Built:
    """Everything set-up makes: reference, weights, input pool, program
    and engine."""

    def __init__(self, cell, seed: int, env, level: str | None = None):
        import jax

        from repro import marvel
        from repro.models.cnn import get_cnn

        cfg, serving = cell.config, cell.traffic["serving"]
        self.env = env
        # set-up seconds from process start at the end of each step
        self.phases = {"imports_and_devices": env.since_start()}
        self.cfg = cfg
        self.ref = load_module(cell.root, "refs", cfg["reference"])
        key = env.seed_key(seed)
        self.params = _make_weights(self.ref, cfg, jax.random.fold_in(key, 0))
        program_init, apply, _ = get_cnn(cfg["model"])
        _check_layout(program_init, self.params)
        self.images = _images(cfg, cell.traffic, jax.random.fold_in(key, 1))
        self.in_shape = tuple(cfg["in_shape"])
        self.phases["weights_and_pool"] = env.since_start()
        self.prog = marvel.compile(
            apply, np.zeros((1, *self.in_shape), np.float32),
            params=self.params, level=level or cfg["level"],
            precompile=False)
        self.phases["marvel_flow"] = env.since_start()
        self.engine = self.prog.serve(
            mode="async", max_batch=serving["max_batch"],
            buckets=tuple(serving["buckets"]),
            max_delay_ms=serving["max_delay_ms"],
            max_pending=serving.get("max_pending", 1024))

    async def warm(self) -> None:
        """Compile and run every bucket, then send one full bucket
        through the engine's whole path."""
        self.engine.warmup(self.in_shape)
        self.phases["bucket_warmup"] = self.env.since_start()
        await asyncio.gather(*(
            self.engine.submit(self.images[i % len(self.images)])
            for i in range(self.engine.compute.max_batch)))
        self.phases["full_bucket"] = self.env.since_start()

    async def window(self, traffic: dict, seed: int, seconds: float, env,
                     on_start=None) -> dict:
        """Offer ``traffic`` for ``seconds``, then await what is due."""
        gen = loadgen.Generator(traffic, self.images,
                                self.engine.submit_nowait, seed,
                                span=env.span)
        misses, compiles = self.prog.cache_misses, env.compiles()
        before = self.engine.metrics()
        if on_start is not None:
            on_start()
        with env.window(seconds):
            start, end = await gen.run(seconds)
        after = self.engine.metrics()
        await gen.drain(DRAIN_S)
        return dict(start=start, end=end, requests=gen.requests,
                    engine_before=before, engine_after=after,
                    recompiles=self.prog.cache_misses - misses,
                    window_compiles=env.compiles() - compiles)


def run(cell, args, env) -> dict:
    b = Built(cell, args.seed, env)
    out: dict = {}

    def mark_setup():
        out["setup_s"] = env.since_start()

    async def serve():
        async with b.engine:
            await b.warm()
            out.update(await b.window(cell.traffic, args.seed, args.seconds,
                                      env, on_start=mark_setup))

    asyncio.run(serve())
    out["setup_phases"] = b.phases
    out["memory_peak_bytes"] = env.memory_peak()
    out["site_work"] = {n: env.site_work(b.ref, b.cfg, b.params, n)
                        for n in b.engine.compute.buckets}
    out["flops_per_image"] = sum(
        s["flops"] for s in env.site_work(b.ref, b.cfg, b.params, 1))
    ref, cfg, params, images = b.ref, b.cfg, b.params, b.images
    del b
    gc.collect()
    t0 = time.perf_counter()
    want = reference_logits(ref, cfg, params, images)
    out["reference_s"] = time.perf_counter() - t0
    out["checks"] = check.compare(out["requests"], want, cell.limits)
    return out
