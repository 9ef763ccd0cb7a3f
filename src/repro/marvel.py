"""marvel.compile — one front door that turns a model into a deployable
MarvelProgram artifact.

The paper's output is not a report: it is an ISA-extended core plus an
optimized bare-metal binary with no runtime dependencies.  This module is the
repo's analogue of that end state — one call runs the whole flow

    profile -> classify -> class-aware extension selection -> chess_rewrite
    -> (optional int8 PTQ) -> pattern->impl resolution BAKED at trace time
    -> AOT-lowered executable (shape/dtype-bucketed compile cache)

and returns a :class:`MarvelProgram` whose ``__call__`` is the baked binary:
the resolved extension table is closure-captured into the traced program, so
nothing about its behaviour depends on ambient context managers, thread-local
state, or jit-cache invisibility at call time.

    from repro import marvel
    prog = marvel.compile(lambda x: apply(params, x), x, level="v4")
    y = prog(x)                  # AOT executable; same shape -> cache hit
    prog.report.summary()        # v0..v4 cycle/energy tables (Figs 11/12)
    prog.resolved_extensions     # the baked pattern -> impl table
    prog.cost("v2")              # per-level modeled cost accessors

Serving
-------
A compiled program is a traffic-bearing artifact, not just a callable.
``prog.shard(mesh)`` places it onto a jax mesh (default: a 1-D data-parallel
mesh over every local device) with batch inputs sharded over the mesh's
batch axes via :func:`repro.launch.shardings.dp_input_sharding`; every
bucket executable is then AOT-compiled against those ``NamedSharding``
inputs, so one program serves N chips and the compile cache still holds one
executable per shape bucket.  ``prog.serve()`` returns the
:class:`repro.runtime.cnn_server.AsyncCnnEngine` serving tier (bounded
admission -> deadline-aware micro-batch coalescing -> DP dispatch ->
per-request futures)::

    prog = marvel.compile(apply, x, params=params).shard()   # all devices
    async with prog.serve(max_batch=32) as engine:
        engine.warmup(in_shape)           # zero recompiles after this
        result = await engine.submit(image)
        engine.metrics()  # queue_depth, p50/p99 latency, batch_occupancy,
                          # cache hits/misses, dp_shards — the dict the
                          # serving benchmark and CI bench-gate consume
"""
from __future__ import annotations

import functools
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable

import jax

from repro.core import classes as classes_mod
from repro.core import costmodel, dispatch, profiler
from repro.core import rewrite as rewrite_mod
from repro.core.extensions import resolve_table
from repro.core.pipeline import MarvelReport, build_report
from repro.kernels import tuning as tuning_mod
from repro.quant.ptq import fake_quantize_tree


def _ref_fallback_total() -> int:
    """Dispatch sites traced so far, in this process, whose kernel wrapper
    took its jnp-reference branch (``repro.kernels.ops.REF_FALLBACKS``)."""
    from repro.kernels import ops

    return sum(ops.REF_FALLBACKS.values())


def _bucket_key(args: tuple) -> tuple:
    """Shape/dtype bucket for the AOT compile cache (treedef + leaf avals)."""
    flat, treedef = jax.tree_util.tree_flatten(args)
    leaves = tuple(
        (tuple(getattr(a, "shape", ())),
         str(getattr(a, "dtype", type(a).__name__)))
        for a in flat
    )
    return (treedef, leaves)


@dataclass
class MarvelProgram:
    """The deployable artifact: a table-baked, AOT-compiled executable plus
    the analysis that produced it.

    ``__call__`` looks up (or builds) the AOT executable for the argument
    shapes/dtypes and runs it — compile once, call many.  ``cache_hits`` /
    ``cache_misses`` count bucket reuse, the serving-facing signal that the
    binary really is baked.
    """

    fn: Callable  # table-bound (and optionally fake-quantized) callable
    level: str
    backend: str  # as requested (possibly "auto")
    table: dispatch.ResolvedTable
    report: MarvelReport
    # autotuned tile configs baked alongside the extension table (empty
    # table = kernel defaults); constant for the program's life, so the
    # recompiles_after_warmup=0 contract is untouched
    tuned: tuning_mod.TuneTable = field(default_factory=tuning_mod.TuneTable)
    chips: int = 1
    donate: tuple[int, ...] = ()
    quantized: bool = False
    quant_stats: dict = field(default_factory=dict)
    # apply the chess_rewrite pass to the program that is actually lowered
    # (set by compile() when the pass succeeded on the example args)
    rewrite_baked: bool = False
    cache_hits: int = 0
    cache_misses: int = 0
    build_s: float = 0.0  # seconds lowering + compiling on cache misses
    # dispatch sites that fell back to their jnp oracle (a kernel lost to a
    # guard) while the bucket executables were traced
    ref_fallbacks: int = 0
    mesh: Any = None  # set by shard(); executables compile against it
    # the bound (possibly fake-quantized) parameter pytree, kept so
    # serve(mode="lm") can build decode engines without re-threading params
    bound_params: Any = field(default=None, repr=False)
    _input_rule: Callable | None = field(default=None, repr=False)
    _cache: dict = field(default_factory=dict, repr=False)
    # (bucket_len, slots, kv_quant) -> jitted decode step, shared by every
    # LM engine of this program so replacement workers warm from cache hits
    _lm_exec_cache: dict = field(default_factory=dict, repr=False)

    @property
    def model_class(self) -> str:
        return self.report.model_class

    @property
    def resolved_extensions(self) -> dict[str, str]:
        """The baked pattern -> impl mapping (empty means pure baseline)."""
        return dict(self.table)

    @property
    def tuned_configs(self) -> dict[str, dict[str, dict[str, int]]]:
        """The baked tile configs ({kernel: {"HxW..": {knob: int}}};
        empty means kernel defaults everywhere)."""
        return self.tuned.summary_configs()

    def cost(self, level: str | None = None) -> dict[str, float]:
        """Modeled per-inference cost at ``level`` (default: the compiled
        level): rv32/tpu cycles + energy and HBM bytes (Fig 11/12 rows)."""
        level = level or self.level
        if level not in costmodel.LEVELS:
            raise ValueError(
                f"unknown processor version {level!r}; "
                f"known levels: {costmodel.LEVELS}"
            )
        r = self.report
        return {
            "rv32_cycles": r.rv32_cycles[level],
            "rv32_energy_j": r.rv32_energy_j[level],
            "tpu_cycles": r.tpu_cycles[level],
            "tpu_energy_j": r.tpu_energy_j[level],
            "hbm_bytes": r.hbm_bytes[level],
        }

    def _executable_fn(self, *args) -> Callable:
        """What actually lowers: the table-bound fn, chess_rewritten for this
        shape bucket (the rewritten jaxpr is shape-specialized, so the pass
        re-runs per bucket; it already succeeded on the example args).

        ``self.fn`` is traced behind a new function object: JAX caches a
        trace by function and shapes, and the flow already traced
        ``self.fn`` at the example's shapes, so a bucket of those shapes
        would reuse that trace without running the model code, and its
        dispatch sites would go uncounted in ``ref_fallbacks``."""
        fresh = functools.wraps(self.fn)(lambda *a: self.fn(*a))
        if self.rewrite_baked:
            try:
                fn, _ = rewrite_mod.rewrite(fresh, *args)
                return fn
            except Exception:  # never lose the artifact to the optimizer
                return fresh
        return fresh

    def baked_jaxpr(self, *args):
        """The jaxpr of the program this bucket deploys — custom marvel_*
        instructions visible (Fig 5's v0-vs-v4 assembly analogue)."""
        return jax.make_jaxpr(self._executable_fn(*args))(*args)

    def shard(self, mesh=None, rules: Callable | None = None
              ) -> "MarvelProgram":
        """Place this program onto ``mesh`` with data-parallel batch sharding.

        Every bucket executable is subsequently AOT-compiled against
        ``NamedSharding`` inputs — batch axis split over the mesh's batch
        axes (``pod``/``data``), everything else replicated — and runs once
        per device on its batch shard (see :meth:`lower`), so one program
        serves all the mesh's chips and the engines above it need no
        per-shard logic.  Anything the program computes across the batch
        (the per-tensor activation scale of the int8 kernels) is then
        computed per shard.  Pass a ``make_production_mesh()``, any caller
        mesh, or nothing (a 1-D DP mesh over every local device).

        ``rules`` overrides the input-placement rule: a callable
        ``(mesh, aval) -> Sharding`` (default
        :func:`repro.launch.shardings.dp_input_sharding`).

        Returns ``self`` so ``compile(...).shard(mesh).serve()`` chains; the
        AOT cache is cleared because unsharded executables are placed wrong.
        """
        from repro.launch.mesh import make_serving_mesh
        from repro.launch.shardings import dp_input_sharding

        self.mesh = mesh if mesh is not None else make_serving_mesh()
        self._input_rule = rules or dp_input_sharding
        self._cache.clear()
        return self

    @property
    def dp_shards(self) -> int:
        """Ways the batch axis is split (1 when unsharded)."""
        if self.mesh is None:
            return 1
        from repro.launch.mesh import batch_axes

        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        n = 1
        for a in batch_axes(self.mesh):
            n *= sizes[a]
        return n

    def _in_shardings(self, args):
        """Per-leaf input shardings for the current mesh (None = unsharded)."""
        if self.mesh is None:
            return None
        return jax.tree_util.tree_map(
            lambda a: self._input_rule(self.mesh, a), args
        )

    def lower(self, *args):
        """AOT-lower for these args (ShapeDtypeStructs fine); no caching.

        When sharded, lowering pins the batch-DP ``NamedSharding`` on every
        input and runs the program once per device on its batch shard
        (:func:`jax.shard_map`), so the compiled executable runs SPMD across
        the mesh.  Per-shard is what makes it compile: Mosaic kernels cannot
        be partitioned automatically."""
        shardings = self._in_shardings(args)
        if shardings is None:
            return jax.jit(self._executable_fn(*args),
                           donate_argnums=self.donate).lower(*args)
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import batch_axes

        def is_split(s):
            return len(s.spec) > 0 and s.spec[0] is not None

        def shard_aval(a, s):
            shape = tuple(a.shape)
            if is_split(s):
                shape = (shape[0] // self.dp_shards, *shape[1:])
            return jax.ShapeDtypeStruct(shape, a.dtype)

        # the per-device program is traced (and rewritten) at shard shapes
        fn = self._executable_fn(
            *jax.tree_util.tree_map(shard_aval, args, shardings))
        specs = jax.tree_util.tree_map(lambda s: s.spec, shardings)
        split = any(is_split(s) for s in jax.tree_util.tree_leaves(shardings))
        # outputs keep the batch split when any input carried it (an
        # undivided batch runs replicated on every device)
        per_shard = jax.shard_map(
            fn, mesh=self.mesh, in_specs=specs,
            out_specs=P(batch_axes(self.mesh)) if split else P(),
            check_vma=False)
        return jax.jit(per_shard, donate_argnums=self.donate,
                       in_shardings=shardings).lower(*args)

    def executable_for(self, *args):
        """The compiled executable for this shape/dtype bucket (build on
        miss).  Accepts ShapeDtypeStructs, so buckets can be warmed ahead of
        serving without touching real data."""
        key = _bucket_key(args)
        exe = self._cache.get(key)
        if exe is None:
            self.cache_misses += 1
            t0 = time.perf_counter()
            fallbacks = _ref_fallback_total()
            exe = self.lower(*args).compile()
            self.ref_fallbacks += _ref_fallback_total() - fallbacks
            self.build_s += time.perf_counter() - t0
            self._cache[key] = exe
        else:
            self.cache_hits += 1
        return exe

    def __call__(self, *args):
        return self.executable_for(*args)(*args)

    @property
    def cache_size(self) -> int:
        return len(self._cache)

    def serve(self, mode: str = "async", **engine_kwargs):
        """A serving engine over this artifact.

        CNN classifiers: ``mode="async"`` (the default) returns the
        :class:`~repro.runtime.cnn_server.AsyncCnnEngine` serving tier
        (``await engine.submit(x)``).  It runs bucketed batches through the
        program's bucket executables, so serving reuses the AOT cache — one
        executable per batch bucket — and respects :meth:`shard`: buckets
        round up to ``dp_shards`` and batches dispatch SPMD across the
        mesh.

        LM classes (``*_lm``): ``mode="lm"`` returns the continuous-batching
        :class:`~repro.runtime.lm_server.AsyncLmEngine` (``await
        engine.submit(prompt)``); ``mode="lm_sync"`` the caller-driven
        :class:`~repro.runtime.lm_server.ContinuousBatchEngine`.  Both need
        ``cfg=``/``run=`` (the model's Arch/RunConfig) and take the bucketed
        KV-cache knobs (``slots``, ``max_len`` or ``bucket_lens``,
        ``kv_quant="int8"``); the program's resolved extension table is
        baked into the decode executables, and engines share the program's
        LM exec cache so replacement workers never recompile.

        All engines accept ``retry=`` (a
        :class:`~repro.runtime.batching.RetryPolicy`: backoff + poison-pill
        bisection / eviction-replay) and ``faults=`` (a
        :class:`~repro.runtime.faults.FaultInjector` for drills).  For
        fault-tolerant deployments, wrap programs in a
        :class:`~repro.runtime.supervisor.Supervisor` — supervised workers,
        health checks, auto-recovery, draining restarts — rather than
        serving a bare engine; semantics in ``docs/serving_ops.md``.
        """
        if mode in ("lm", "lm_sync"):
            if not (self.model_class.endswith("_lm")
                    or self.model_class == "unknown"):
                raise NotImplementedError(
                    f"serve(mode={mode!r}) is the LM tier; this program is "
                    f"{self.model_class!r}"
                )
            from repro.runtime.lm_server import (
                AsyncLmEngine, ContinuousBatchEngine,
            )

            params = engine_kwargs.pop("params", None)
            if params is None:
                params = self.bound_params
            if params is None:
                raise ValueError(
                    "serve(mode='lm') needs the parameter pytree: pass "
                    "params= to marvel.compile() or to serve()"
                )
            cls = AsyncLmEngine if mode == "lm" else ContinuousBatchEngine
            return cls(params, engine_kwargs.pop("cfg"),
                       engine_kwargs.pop("run"), table=self.table,
                       exec_cache=self._lm_exec_cache, program=self,
                       **engine_kwargs)
        if self.model_class != "cnn":
            raise NotImplementedError(
                f"serve() covers the cnn class (mode='async') and LM "
                f"classes (mode='lm'/'lm_sync'); this program is "
                f"{self.model_class!r}"
            )
        if mode != "async":
            raise ValueError(
                f"unknown serve mode {mode!r}; choose 'async' for the cnn "
                f"class, 'lm' or 'lm_sync' for LM classes"
            )
        from repro.runtime.cnn_server import AsyncCnnEngine

        return AsyncCnnEngine(self, **engine_kwargs)

    def metrics(self) -> dict:
        """Cache, build, reference-fallback and shard counters, the
        program's slice of the serving metrics surface (the engines merge
        this into theirs)."""
        return {
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "cache_size": self.cache_size,
            "build_s": self.build_s,
            "ref_fallbacks": self.ref_fallbacks,
            "dp_shards": self.dp_shards,
        }

    def summary(self) -> str:
        head = (
            f"MarvelProgram(level={self.level}, backend={self.backend}, "
            f"quantized={self.quantized}, "
            f"impls={self.resolved_extensions or 'baseline'})"
        )
        if self.tuned.n_configs:
            head += f"\n  {self.tuned!r}"
        return head + "\n" + self.report.summary()


def compile(fn: Callable, *example_args, level: str = "v4",
            backend: str = "auto", quantize: bool = False, params=None,
            donate: tuple[int, ...] = (), chips: int = 1,
            do_rewrite: bool = True, precompile: bool = True,
            platform: str | None = None,
            tuned: Any = "auto") -> MarvelProgram:
    """Run the full MARVEL flow on ``fn`` and return the deployable artifact.

    Args:
      fn: the model callable.  Either closes over its params
        (``fn(*example_args)``) or, when ``params`` is given, takes them
        first (``fn(params, *example_args)``).
      example_args: example inputs (concrete arrays or ShapeDtypeStructs).
      level: processor version to bake (``v0``..``v4``).
      backend: ``"auto"`` (pallas per-pattern where production-ready on the
        current platform, baseline otherwise), ``"ref"``/``"baseline"``, or a
        registered backend name (``"pallas"`` forces kernels everywhere,
        interpret mode off-TPU).  Unknown names raise ``ValueError``.
      quantize: apply int8 PTQ to ``params`` (requires ``params``); the
        artifact then carries the deployed model's int8 rounding error.
      params: optional pytree of model parameters to bind (and quantize).
      donate: argnums of ``example_args`` to donate to the executable.
      chips: cost-model chip count.
      do_rewrite: run the chess_rewrite jaxpr pass for the report.
      precompile: eagerly build the AOT executable for the example-arg
        bucket (compile-at-deploy; disable for report-only flows).
      platform: override the platform ``backend="auto"`` resolves against.
      tuned: tile-autotuning configs to bake.  ``"auto"`` (default) loads
        ``benchmarks/tuned/<backend>.json`` for the current platform (empty
        table — kernel defaults — when no file exists); ``None``/``"off"``
        disables tuning; a :class:`repro.kernels.tuning.TuneTable` is used
        as-is.  The table is closure-captured at trace time exactly like the
        extension table, so the artifact keeps its tile sizes and
        ``recompiles_after_warmup`` stays 0.
    """
    quant_stats: dict = {}
    if params is not None:
        bound_params = params
        if quantize:
            bound_params, quant_stats = fake_quantize_tree(params)
        model_fn = lambda *a: fn(bound_params, *a)  # noqa: E731
    else:
        if quantize:
            raise ValueError(
                "quantize=True needs the parameter pytree: pass params=..."
            )
        model_fn = fn

    # 1-2) profile on the baseline + model-class detection ("simulator" step)
    prof = profiler.profile_fn(model_fn, *example_args)
    model_class, exts = classes_mod.recommend(prof)

    # 3) class-aware extension selection -> explicit resolved table, baked
    # by closure capture: jit/AOT tracing of bound_fn resolves every
    # dispatch site against it at trace time; the classified class picks
    # its OWN ladder (CLASS_LADDERS), so an LM program never carries
    # CNN-only patterns and vice versa
    table = resolve_table(level, backend, extensions=exts, platform=platform,
                          model_class=model_class)
    # tile autotuning rides the same trace-time-baking mechanism: the tuned
    # table wraps the extension-bound fn, so the kernel wrappers see it at
    # trace time and the jaxpr carries the tile choice
    if tuned == "auto":
        tuned_table = tuning_mod.load_tuned(platform)
    elif tuned is None or tuned == "off":
        tuned_table = tuning_mod.TuneTable()
    elif isinstance(tuned, tuning_mod.TuneTable):
        tuned_table = tuned
    else:
        raise ValueError(
            f"tuned must be 'auto', 'off'/None, or a TuneTable; got {tuned!r}"
        )
    bound_fn = tuned_table.bind(table.bind(model_fn))

    # 4) chess_rewrite of the bound program — the fusions land in the
    # deployed binary, and the report counts what was actually baked;
    # failures degrade with a warning, never silently
    rewrite_stats: dict = {}
    rewrite_ok = True
    if do_rewrite:
        try:
            _, rewrite_stats = rewrite_mod.rewrite(bound_fn, *example_args)
        except Exception as e:  # rewriting is an optimization, never fatal
            rewrite_stats = {"error": str(e)}
            rewrite_ok = False
            warnings.warn(
                f"chess_rewrite failed ({e!r}); continuing without jaxpr "
                f"fusion — see MarvelReport.rewrite_ok",
                RuntimeWarning,
                stacklevel=2,
            )

    report = build_report(prof, model_class, exts, rewrite_stats,
                          rewrite_ok=rewrite_ok, chips=chips,
                          tuned_configs=tuned_table.summary_configs())

    # 5) the artifact: rewritten (per shape bucket) + AOT-lowered
    program = MarvelProgram(
        fn=bound_fn,
        level=level,
        backend=backend,
        table=table,
        report=report,
        tuned=tuned_table,
        chips=chips,
        donate=tuple(donate),
        quantized=bool(quantize),
        quant_stats=quant_stats,
        rewrite_baked=do_rewrite and rewrite_ok,
        bound_params=bound_params if params is not None else None,
    )

    # 6) AOT-lower the example bucket now (deploy-time compile counts as the
    # first cache miss; every same-shape call after it is a hit)
    if precompile:
        program.executable_for(*example_args)
    return program


def compile_timed(fn: Callable, *example_args, **kwargs
                  ) -> tuple[MarvelProgram, float]:
    """compile() plus wall-clock seconds spent — benchmark convenience."""
    t0 = time.perf_counter()
    prog = compile(fn, *example_args, **kwargs)
    return prog, time.perf_counter() - t0
