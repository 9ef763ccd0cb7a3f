"""What a run learns about and does to its process: the device, the
compile cache, the clock, profiler spans and the traced window."""
from __future__ import annotations

import contextlib
import json
import os
import pathlib
import threading
import time

from chipbench import work
from chipbench.devtrace import WINDOW_SPAN
from chipbench.spec import HERE, REPO

TRACE_LEAD_S = 1.0  # the trace starts this long into the window
TRACE_S = 2.0  # and lasts this long (or a quarter of a shorter window)


class NoChip(Exception):
    """No accelerator, too few chips, or an unknown one: no result."""


def peaks_for(kind: str, path: pathlib.Path = HERE / "peaks.json") -> dict:
    table = json.loads(path.read_text())
    if kind not in table:
        raise NoChip(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def device_check(devices, chips: int) -> dict:
    """The run's device record; raises :class:`NoChip` where it must not
    run.  ``devices`` is what ``jax.devices()`` returned."""
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees "
                     f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "peaks": peaks_for(dev.device_kind)}


class Env:
    def __init__(self, t_start: float, chips: int, trace_dir: pathlib.Path
                 | None):
        self.t_start = t_start
        self.trace_dir = trace_dir
        self.traced: tuple[float, float] | None = None
        self._compiles = 0
        self._work: dict = {}
        import jax

        self.device = device_check(jax.devices(), chips)
        self.devices = jax.devices()[:chips]
        from repro.kernels import common

        if common.interpret_mode():
            raise NoChip("Pallas kernels would run in interpret mode")
        # a fixed directory in the checkout, unless the environment names one
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not cache:
            cache = str(REPO / ".jax_cache")
            jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.cache_dir = cache
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    # -- clock and compile counting ----------------------------------------

    def since_start(self) -> float:
        return time.perf_counter() - self.t_start

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._compiles += 1

    def compiles(self) -> int:
        """Programs compiled or loaded from the cache so far."""
        return self._compiles

    # -- seeds, work, memory -------------------------------------------------

    @staticmethod
    def seed_key(seed: int):
        """A PRNG key from any whole number (64 bits are kept)."""
        import jax

        key = jax.random.PRNGKey(0)
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)

    def site_work(self, ref, cfg, params, batch: int) -> list[dict]:
        if batch not in self._work:
            import jax
            import numpy as np

            shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
            x = jax.ShapeDtypeStruct((batch, *cfg["in_shape"]), np.float32)
            self._work[batch] = work.sites(
                lambda p, x: ref.forward(p, x, cfg), shapes, x)
        return self._work[batch]

    def memory_peak(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)

    # -- tracing -------------------------------------------------------------

    def span(self, name: str):
        if self.trace_dir is None:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self, seconds: float):
        """The measured window; in a traced run a helper thread traces
        ``TRACE_S`` seconds of it, starting ``TRACE_LEAD_S`` in."""
        if self.trace_dir is None:
            yield
            return
        import jax

        lead = min(TRACE_LEAD_S, seconds / 4)
        length = min(TRACE_S, seconds / 4)

        def tracer():
            time.sleep(lead)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=options)
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                time.sleep(length)
            jax.profiler.stop_trace()

        thread = threading.Thread(target=tracer, name="chipbench-tracer")
        thread.start()
        try:
            yield
        finally:
            thread.join()
