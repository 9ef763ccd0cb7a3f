#!/usr/bin/env python3
"""On-chip smoke test of the paper's main path: a CNN compiled by
``marvel.compile`` and served by the async CNN engine, on a TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the data-parallel path on four chips

One chip: ResNet-50 (full channel widths 64->2048, random weights from
``--seed``) at 224x224x3 is compiled at v4 (the CNN ladder's top rung, every
conv / GEMM / pool site on its Pallas kernel) and at v0 (plain XLA),
through the serving path's own pieces (``cnn_program_factory``,
``repro.launch.serve.serve_cnn_wave``): each program is warmed on every
batch bucket and then answers a seeded wave of requests.  The served logits are compared with
a float32 ``jax.numpy`` forward under ``default_matmul_precision("highest")``
(relative L2 inside the 0.25 band ``tests/test_cross_version.py`` holds
ResNet-50 to, plus argmax agreement).

``--chips N``: only the multi-chip path: the same v4 program run on one
device, then ``.shard()``ed data-parallel over N devices and served; the
served logits are compared with the one-device logits of the same batches.

The script exits non-zero, without its final line, when JAX finds no TPU,
when kernels would run in interpret mode, when a CNN-path kernel is missing
from the v4 executable, when any kernel wrapper fell back to its jnp
reference, when agreement is outside the band, when anything recompiles
after warmup, or when any phase raises.  On success its last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import pathlib
import re
import sys
import time

MODEL = "resnet50"
IN_SHAPE = (224, 224, 3)
REQUESTS = 32
MAX_BATCH = 8
MAX_DELAY_MS = 5.0
BAND = 0.25  # tests/test_cross_version.py: ResNet-50 relative-L2 band
# the kernels ResNet-50's sites resolve to at v4 (pallas_call names)
CNN_KERNELS = ("fused_conv", "matmul_epilogue", "maxpool", "global_avgpool")

REPO = pathlib.Path(__file__).resolve().parent


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def kernels_in(hlo_text: str) -> dict[str, int]:
    """Pallas kernels in a compiled TPU executable: tpu_custom_call count
    per kernel name (the name the pallas_call carries in op_name)."""
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="[^"]*?([\w.-]+)/pallas_call"', line)
        name = m.group(1) if m else "?"
        counts[name] = counts.get(name, 0) + 1
    return counts


def agreement(got, want) -> tuple[float, float]:
    """(relative L2 of got vs want, fraction of rows with equal argmax)."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    return rel, agree


def served_logits(results):
    import numpy as np

    failed = [r.uid for r in results if not r.done]
    check(not failed, f"requests failed: {failed}")
    return np.stack([r.logits for r in results])


def serve_level(level: str, images, seed: int, *, cache_events: dict):
    """Compile ``level``, warm its buckets, serve the wave; returns
    (program, served logits, metrics)."""
    from repro.kernels.ops import REF_FALLBACKS
    from repro.launch.serve import serve_cnn_wave
    from repro.runtime.actor import cnn_program_factory

    REF_FALLBACKS.clear()
    hits0 = cache_events["hits"]
    t0 = time.perf_counter()
    prog = cnn_program_factory(MODEL, level=level, seed=seed,
                               in_shape=IN_SHAPE)
    flow_s = time.perf_counter() - t0
    results, m = asyncio.run(serve_cnn_wave(
        prog, IN_SHAPE, images, max_batch=MAX_BATCH,
        max_delay_ms=MAX_DELAY_MS))
    m["flow_s"] = flow_s
    m["compile_s"] = flow_s + m["warmup_s"]
    m["ref_fallbacks"] = dict(REF_FALLBACKS)
    m["persistent_cache_hits"] = cache_events["hits"] - hits0
    log(f"[{level}] resolved table: {prog.resolved_extensions or 'baseline'}")
    log(f"[{level}] compile: marvel flow {flow_s:.2f}s + bucket warmup "
        f"{m['warmup_s']:.2f}s = {m['compile_s']:.2f}s "
        f"(buckets {prog.cache_size}, persistent-cache hits "
        f"{m['persistent_cache_hits']})")
    log(f"[{level}] served {len(results)} requests in {m['batches']} "
        f"batches, {m['wave_s']:.3f}s; recompiles_after_warmup="
        f"{m['recompiles_after_warmup']}; ref fallbacks "
        f"{sum(REF_FALLBACKS.values())} {dict(REF_FALLBACKS)}")
    check(m["recompiles_after_warmup"] == 0,
          f"{level}: {m['recompiles_after_warmup']} recompiles after warmup")
    check(not REF_FALLBACKS,
          f"{level}: kernel wrappers fell back to jnp: {dict(REF_FALLBACKS)}")
    return prog, served_logits(results), m


def bucket_hlo(prog, batch: int) -> str:
    import jax
    import numpy as np

    spec = jax.ShapeDtypeStruct((batch, *IN_SHAPE), np.float32)
    return prog.executable_for(spec).as_text()


def one_chip(images, seed: int, cache_events: dict) -> None:
    import jax
    import numpy as np

    from repro.models.cnn import get_cnn

    init, apply, _ = get_cnn(MODEL)
    x = np.stack(images)
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(apply)(init(jax.random.PRNGKey(seed)), x))
    log(f"[ref] float32 highest-precision forward {x.shape} -> {ref.shape} "
        f"in {time.perf_counter() - t0:.2f}s")
    check(np.isfinite(ref).all(), "reference logits are not finite")

    for level in ("v4", "v0"):
        prog, got, _ = serve_level(level, images, seed,
                                   cache_events=cache_events)
        if level == "v4":
            table = prog.resolved_extensions
            check(all(table.get(p) == "pallas"
                      for p in ("fused_conv", "matmul_epilogue", "pool")),
                  f"v4 table does not resolve the CNN patterns: {table}")
            found = kernels_in(bucket_hlo(prog, MAX_BATCH))
            log(f"[v4] tpu_custom_calls in the batch-{MAX_BATCH} executable:"
                f" {sum(found.values())} {found}")
            missing = [k for k in CNN_KERNELS if not found.get(k)]
            check(not missing, f"v4 executable lacks kernels {missing}")
        check(np.isfinite(got).all(), f"{level}: served logits not finite")
        rel, agree = agreement(got, ref)
        log(f"[{level}] vs float32 reference: rel_l2={rel:.6f} "
            f"(band {BAND}), argmax agreement {agree:.4f}")
        check(rel < BAND, f"{level}: rel_l2 {rel} outside the {BAND} band")


def sharded(images, seed: int, chips: int, cache_events: dict) -> None:
    import jax
    import numpy as np

    from repro.launch.serve import serve_cnn_wave
    from repro.runtime.actor import cnn_program_factory

    check(len(jax.devices()) == chips,
          f"--chips {chips} but JAX sees {len(jax.devices())} device(s)")
    prog = cnn_program_factory(MODEL, level="v4", seed=seed, shard=False,
                               in_shape=IN_SHAPE)
    # a sharded program runs once per device on its batch shard, and the
    # int8 kernels scale activations per call; so the one-device reference
    # runs the same shards: consecutive runs of MAX_BATCH // chips requests
    per_dev = MAX_BATCH // chips
    t0 = time.perf_counter()
    single = np.concatenate([np.asarray(prog(np.stack(images[i:i + per_dev])))
                             for i in range(0, len(images), per_dev)])
    log(f"[1 device] {len(images) // per_dev} batches of {per_dev} in "
        f"{time.perf_counter() - t0:.2f}s (compile included)")
    prog.shard()
    check(prog.dp_shards == chips, f"dp_shards={prog.dp_shards}")
    # the engine coalesces the wave into full buckets of MAX_BATCH in
    # submission order, so each device's shard is one of the runs above
    results, m = asyncio.run(serve_cnn_wave(
        prog, IN_SHAPE, images, max_batch=MAX_BATCH,
        max_delay_ms=MAX_DELAY_MS))
    got = served_logits(results)
    found = kernels_in(bucket_hlo(prog, MAX_BATCH))
    log(f"[{chips} devices] served {len(results)} requests in "
        f"{m['batches']} batches, occupancy {m.get('batch_occupancy')}, "
        f"dp_shards={m['dp_shards']}, recompiles_after_warmup="
        f"{m['recompiles_after_warmup']}, tpu_custom_calls "
        f"{sum(found.values())} {found}")
    check(m["recompiles_after_warmup"] == 0, "recompiles after warmup")
    full = len(images) // MAX_BATCH
    check(m["batches"] == full,
          f"served in {m['batches']} batches, not {full} full ones")
    check(not [k for k in CNN_KERNELS if not found.get(k)],
          f"sharded executable lacks CNN kernels: {found}")
    rel, agree = agreement(got, single)
    log(f"[{chips} devices] sharded vs one device: rel_l2={rel:.3e}, "
        f"argmax agreement {agree:.4f}")
    check(rel < 1e-3, f"sharded logits disagree with one device: "
                      f"rel_l2={rel}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="1: the one-chip smoke; N>1: only the "
                         "data-parallel path over N chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = REPO / "src"
    if not (src / "repro" / "marvel.py").is_file():
        raise SmokeFailure(f"no repro sources under {src}")
    sys.path.insert(0, str(src))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax
    from jax import monitoring

    cache_events = {"hits": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1

    monitoring.register_event_listener(on_event)

    devices = jax.devices()
    dev = devices[0]
    log(f"devices: {len(devices)} x {dev.platform} ({dev.device_kind}); "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    check(dev.platform == "tpu", f"JAX found no TPU (platform {dev.platform})")
    from repro.kernels import common

    check(not common.interpret_mode(), "Pallas kernels in interpret mode")

    from repro.launch.serve import random_images

    images = random_images(IN_SHAPE, REQUESTS, seed=args.seed)
    if args.chips == 1:
        one_chip(images, args.seed, cache_events)
    else:
        sharded(images, args.seed, args.chips, cache_events)
    log(f"persistent compile cache: {cache_events['hits']} hit(s) in "
        f"{cache_dir}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
