"""Batch-inference serving for CNN classifiers over a MarvelProgram.

:class:`AsyncCnnEngine` is the serving tier: an ``asyncio`` request plane
(bounded admission queue -> deadline-aware micro-batch coalescing -> one
compute thread -> per-request futures) decoupled from the blocking jax
dispatch, so thousands of in-flight requests cost one event loop, not one
thread each.  Its compute core is :class:`_BucketedCompute`::

    prog = marvel.compile(apply, x, params=params).shard(mesh)
    async with prog.serve(max_batch=32) as engine:
        result = await engine.submit(image)

Batches are padded to power-of-two buckets (rounded up to the program's DP
shard count when sharded), so a drained queue of thousands of requests
compiles at most ``len(buckets)`` times and :meth:`warmup` can pre-build
every bucket from ShapeDtypeStructs before the first request arrives.

Self-healing request plane
--------------------------
A compute exception does not fail every co-batched request.  Batches run
through the resilient path (:func:`_classify_resilient`): transient
failures retry with exponential backoff + seeded jitter
(:class:`~repro.runtime.batching.RetryPolicy`); a batch that keeps failing
is *bisected* to isolate the poison-pill request, so innocent requests still
resolve and exactly the bad one fails.  Requests whose ``deadline_ms``
expired before dispatch fast-fail
(:class:`~repro.runtime.batching.DeadlineExceeded` — no compute burned), and
admission sheds load with a ``retry_after_ms`` hint on
:class:`AdmissionError`.  Every failure mode is a counter on ``metrics()``:
``errors`` / ``retries`` / ``shed`` / ``deadline_failures``.  A
:class:`~repro.runtime.faults.FaultInjector` passed as ``faults=`` drives
all of these paths deterministically (see ``docs/serving_ops.md``); the
supervisor tier above this module is :mod:`repro.runtime.supervisor`.

Tracing
-------
Each phase of a batch is a ``jax.profiler.TraceAnnotation`` span, inert
unless a profiler is running, and adds its seconds to a counter on
``metrics()``.  Leaf spans, never nested: ``marvel.serve.stack``
(``stack_s``: on the event loop for a staged batch, on the compute thread
otherwise), and on the compute thread ``marvel.serve.dispatch``
(``dispatch_s``), ``marvel.serve.result_wait`` (``result_wait_s``: the
device run and the device-to-host copy), ``marvel.serve.post``
(``post_s``) and ``marvel.serve.handoff``; on the event loop
``marvel.serve.resolve``.  Every span carries ``batch``, the id the engine
gave the batch; the stack, dispatch, result wait and post spans also carry
``size``, ``bucket`` and ``attempt`` (retries and bisection call the
program again).  The engine also counts ``queue_wait_s`` (admission to
dispatch, summed over requests) and ``executor_wait_s`` (dispatch to the
compute thread taking the batch, summed over batches); ``build_s`` is the
program's seconds compiling bucket executables, and ``ref_fallbacks`` its
dispatch sites that fell back to the jnp oracle while those were traced.

Staging
-------
When the event loop coalesces a batch, it stacks and pads the images and
starts their transfer to the device (``jax.device_put`` with the input
sharding of the bucket's executable, not waited on) before handing the
batch to the compute thread.  The batch's launch then only calls the
program on the device array.  A batch is staged once its bucket's
executable is known (warmed, or launched once); a launch that finds no
staged input (the first batch of a bucket not warmed, retries, bisection
sub-batches) stacks on the compute thread.  ``staged_inputs`` on
``metrics()`` counts the launches that found one.

Look-ahead
----------
When the compute thread takes up batch N and batch N+1 is already waiting
behind it, it launches N (unless already launched), then N+1, and only
then waits on N: a launch is the program call (and, for a batch not
staged, the stack before it) plus the start of the result's copy to the
host.  N+1's executable is so queued on the device behind N.  The depth
is one batch; with no batch waiting, the thread launches, waits on and
posts each batch in turn.  The compute thread's spans then read, per
batch taken up: ``dispatch`` of N+1, then ``result_wait``, ``post`` and
``handoff`` of N (``dispatch`` of N too, where no look-ahead launched
it), each batch leaving each span once.  ``prefetched`` on ``metrics()``
counts the batches launched while the batch before them was still in
flight.
"""
from __future__ import annotations

import asyncio
import collections
import concurrent.futures
import operator
import time
from dataclasses import dataclass

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.runtime import batching, faults
from repro.runtime.batching import (  # re-exports  # noqa: F401
    AdmissionError, DeadlineExceeded, RetryPolicy, WorkerUnavailable,
)


# a queued item is (request, future, admission time on the loop's clock,
# deadline or None)
_ADMITTED = operator.itemgetter(2)


@dataclass
class CnnRequest:
    uid: int
    image: np.ndarray  # (H, W, C), model input layout
    label: int | None = None
    probs: np.ndarray | None = None
    logits: np.ndarray | None = None  # the program's output row
    done: bool = False  # resolved successfully (failed requests set .error)
    latency_ms: float = 0.0
    error: Exception | None = None


class _BucketedCompute:
    """program + buckets + the batched classify step of
    :class:`AsyncCnnEngine`.  Buckets are rounded up to the program's DP
    shard count so a sharded program always sees batch dims its mesh
    divides."""

    def __init__(self, program, max_batch: int = 8,
                 buckets: tuple[int, ...] = (),
                 faults_injector: faults.FaultInjector | None = None,
                 metrics: batching.EngineMetrics | None = None):
        self.program = program
        if not buckets:
            buckets = batching.pow2_buckets(max_batch)
        dp = int(getattr(program, "dp_shards", 1) or 1)
        self.buckets = batching.round_up_buckets(buckets, dp)
        self.max_batch = self.buckets[-1]
        self.faults = faults_injector
        # the engine's counters; classify adds each phase's seconds
        self.metrics = metrics if metrics is not None else \
            batching.EngineMetrics()
        # tags of the classify call in progress, set by the compute path:
        # the engine's batch id and the call's number within the batch
        self.tags = {"batch": 0, "attempt": 0}
        # every warmed (shape, dtype) spec, recorded so a supervisor can
        # replay the warmup on a replacement worker before routing traffic
        self.warmed: list[tuple[tuple[int, ...], str]] = []
        # outputs of batches launched ahead of their classify call, by the
        # batch's uids; each is popped by the first classify for its uids
        self.launched: dict[tuple[int, ...], object] = {}
        # batches' inputs put on the device by stage(), with the seconds
        # stacking them took, by the batch's uids; each is popped by the
        # first launch for its uids
        self.device_inputs: dict[tuple[int, ...], tuple] = {}
        # the input sharding of each known bucket executable, by the
        # batch's (shape, dtype): where stage() places a batch
        self.placements: dict[tuple, jax.sharding.Sharding] = {}

    def warmup(self, in_shape: tuple[int, ...], dtype="float32") -> None:
        """Pre-compile AND prime every batch bucket: build the AOT
        executable from shapes alone, then run it once on zeros so the
        first-execution costs (device placement, runtime spin-up) are paid
        here, not by the first live request."""
        for b in self.buckets:
            spec = jax.ShapeDtypeStruct((b, *in_shape), np.dtype(dtype))
            exe = self._executable(spec)
            jax.block_until_ready(exe(np.zeros((b, *in_shape),
                                               np.dtype(dtype))))
        spec = (tuple(in_shape), str(np.dtype(dtype)))
        if spec not in self.warmed:
            self.warmed.append(spec)

    def _executable(self, x):
        """The program's executable for ``x``'s bucket (the program itself
        where it builds none), recording its input sharding so that
        :meth:`stage` can place later batches of the bucket."""
        build = getattr(self.program, "executable_for", None)
        if build is None:
            return self.program
        exe = build(x)
        key = (tuple(x.shape), np.dtype(x.dtype))
        if key not in self.placements:
            self.placements[key] = exe.input_shardings[0][0]
        return exe

    def stage(self, images: list[np.ndarray], uids: tuple[int, ...],
              batch: int) -> None:
        """The event loop's half of a batch, at its dispatch: stack + pad,
        then start the input's transfer to the device, placed as the
        bucket's executable takes it, without waiting on it.  The launch
        for ``uids`` picks the device array up.  Stages nothing where the
        bucket's executable is not known yet or stacking raises: that
        launch then stacks the batch itself."""
        n = len(images)
        bucket = batching.bucket_for(self.buckets, n)
        first = images[0]
        placement = self.placements.get(((bucket, *first.shape), first.dtype))
        if placement is None:
            return
        t0 = time.perf_counter()
        with TraceAnnotation("marvel.serve.stack", batch=batch, attempt=0,
                             size=n, bucket=bucket):
            try:
                x = jax.device_put(
                    batching.pad_batch(np.stack(images), bucket), placement)
            except Exception:  # the launch stacks again, and the
                return  # resilient path reports what raises there
        self.device_inputs[uids] = (x, time.perf_counter() - t0)

    def _launch(self, images: list[np.ndarray], uids: tuple[int, ...]):
        """The first half of a batch: the input :meth:`stage` put on the
        device for ``uids``, else stack + pad here; then the program call
        (enqueue, and the argument transfer of a host array, no wait) and
        the start of the result's copy to the host.  Returns the program's
        output, a ``jax.Array``."""
        n = len(images)
        bucket = batching.bucket_for(self.buckets, n)
        ids = dict(self.tags, size=n, bucket=bucket)
        m = self.metrics
        staged = self.device_inputs.pop(uids, None)
        if staged is None:
            t0 = time.perf_counter()
            with TraceAnnotation("marvel.serve.stack", **ids):
                x = batching.pad_batch(np.stack(images), bucket)
            stack_s = time.perf_counter() - t0
        else:
            x, stack_s = staged
            m.staged_inputs += 1
        m.stack_s += stack_s
        t1 = time.perf_counter()
        with TraceAnnotation("marvel.serve.dispatch", **ids):
            out = self._executable(x)(x)
            out.copy_to_host_async()
        m.dispatch_s += time.perf_counter() - t1
        return out

    def launch(self, images: list[np.ndarray], uids: tuple[int, ...]
               ) -> bool:
        """Launch a batch ahead of its :meth:`classify` call, which picks
        the output up by ``uids``.  Launches nothing and returns False
        where a launched batch already holds these ``uids``."""
        if uids in self.launched:
            return False
        self.launched[uids] = self._launch(images, uids)
        return True

    def classify(self, images: list[np.ndarray], uids: tuple[int, ...] = ()
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One padded bucket through the program -> (labels, probs, logits)
        for the real lanes (padding lanes are computed and discarded).
        Picks up the batch :meth:`launch` launched for ``uids``, else
        launches it here.

        Each phase is a span tagged with :attr:`tags`, ``size`` and
        ``bucket``, and adds its seconds to :attr:`metrics`."""
        out = self.launched.pop(uids, None)
        if self.faults is not None:
            self.faults.before_compute(uids)
        if out is None:
            out = self._launch(images, uids)
        n = len(images)
        ids = dict(self.tags, size=n,
                   bucket=batching.bucket_for(self.buckets, n))
        m = self.metrics
        t2 = time.perf_counter()
        with TraceAnnotation("marvel.serve.result_wait", **ids):
            logits = np.asarray(out)[:n]
        t3 = time.perf_counter()
        m.result_wait_s += t3 - t2
        with TraceAnnotation("marvel.serve.post", **ids):
            z = logits - logits.max(axis=-1, keepdims=True)
            probs = np.exp(z) / np.exp(z).sum(axis=-1, keepdims=True)
            labels = np.argmax(logits, axis=-1)
        m.post_s += time.perf_counter() - t3
        return labels, probs, logits


def _classify_resilient(compute: _BucketedCompute, reqs: list[CnnRequest],
                        retry: batching.RetryPolicy, batch: int = 0
                        ) -> tuple[list[tuple], int]:
    """The resilient compute path (runs on the compute thread).

    Returns ``(outcomes, retries)`` where ``outcomes[i]`` is
    ``("ok", label, probs, logits)`` or ``("err", exception)`` for
    ``reqs[i]``.
    Failed attempts retry with backoff; a still-failing multi-request batch
    bisects (within ``retry.max_splits``) to isolate the poison pill; a
    singleton — or a sub-batch whose split budget ran out — fails
    per-request.  :class:`~repro.runtime.faults.WorkerDeath` is NOT handled
    here: the worker is dying, not the batch, so it propagates to the
    engine's fatal path.  ``batch``, the engine's id for the batch, and
    the call's number within it tag every call's spans.
    """
    retries, calls = 0, 0

    def solve(sub: list[CnnRequest], splits_left: int | None) -> list[tuple]:
        nonlocal retries, calls
        err: Exception | None = None
        for attempt in range(retry.max_retries + 1):
            compute.tags = {"batch": batch, "attempt": calls}
            calls += 1
            try:
                labels, probs, logits = compute.classify(
                    [r.image for r in sub], uids=tuple(r.uid for r in sub)
                )
                return [("ok", int(labels[i]), probs[i], logits[i])
                        for i in range(len(sub))]
            except faults.WorkerDeath:
                raise
            except Exception as e:
                err = e
                if attempt < retry.max_retries:
                    retries += 1
                    time.sleep(retry.backoff_ms(attempt) / 1e3)
        if len(sub) > 1 and (splits_left is None or splits_left > 0):
            nxt = None if splits_left is None else splits_left - 1
            mid = len(sub) // 2
            return solve(sub[:mid], nxt) + solve(sub[mid:], nxt)
        return [("err", err)] * len(sub)

    return solve(reqs, retry.max_splits), retries


class AsyncCnnEngine:
    """The async serving tier: request plane decoupled from compute plane.

    ``submit()`` applies admission control (bounded over queued + in-flight
    requests -> fast :class:`AdmissionError` carrying a ``retry_after_ms``
    load-shedding hint, never unbounded memory), a background batcher
    coalesces requests into pow-2 buckets — flushing on a full bucket or on
    the coalesce deadline, whichever first — and one compute thread runs the
    blocking jax dispatch so the event loop never stalls.  The batcher never
    awaits compute: it stacks each batch, starts its input's transfer to
    the device (the module's "Staging"), hands the batch to the compute
    thread and keeps coalescing, so coalescing, input transfer and jax
    dispatch pipeline.  With a batch waiting behind the current one, the
    compute thread launches it before waiting on the current result (the
    module's "Look-ahead").  The compute thread hands a *finished batch*
    back to the event loop with ONE ``call_soon_threadsafe`` per flush,
    where every future in the batch resolves, in submission order, to its
    :class:`CnnRequest` — batch-granular resolution, not per-request loop
    round-trips.

    Failure semantics: requests whose ``deadline_ms`` expired before
    dispatch fast-fail with :class:`DeadlineExceeded`; compute failures go
    through retry/backoff + poison-pill bisection so only genuinely bad
    requests fail; :class:`~repro.runtime.faults.WorkerDeath` (or
    :meth:`kill`) fails every unresolved future with
    :class:`WorkerUnavailable` so a supervisor can re-route with zero lost
    requests.
    """

    def __init__(self, program, max_batch: int = 8,
                 buckets: tuple[int, ...] = (),
                 max_pending: int = 1024,
                 max_delay_ms: float = 2.0,
                 faults: faults.FaultInjector | None = None,
                 retry: batching.RetryPolicy | None = None):
        self._metrics = batching.EngineMetrics()
        self.compute = _BucketedCompute(program, max_batch, buckets,
                                        faults_injector=faults,
                                        metrics=self._metrics)
        self.retry = retry or batching.RetryPolicy()
        self.max_pending = max_pending
        self.max_delay_ms = max_delay_ms
        self._dispatched = 0  # batches handed to the compute thread: the id
        self._queue: asyncio.Queue | None = None
        self._batcher: asyncio.Task | None = None
        self._pool: concurrent.futures.ThreadPoolExecutor | None = None
        self._inflight: set = set()  # executor futures of dispatched batches
        # (batch id, requests) of batches handed to the compute thread that
        # it has not taken up yet, oldest first: appended by the event loop,
        # popped by the compute thread, read there for the look-ahead
        self._staged: collections.deque = collections.deque()
        # admitted requests whose future has not resolved yet — queued,
        # held in the batcher's coalescing batch, or in the compute thread
        self._live_reqs = 0
        self._unresolved: set = set()  # their asyncio futures (for kill())
        self._killed: str | None = None
        self._uid = 0

    # -- lifecycle ----------------------------------------------------------

    async def start(self) -> "AsyncCnnEngine":
        if self._batcher is None and self._killed is None:
            self._queue = asyncio.Queue()
            # one compute thread = the compute plane; jax dispatch serializes
            # there while the event loop keeps admitting requests
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="cnn-compute"
            )
            # bind the queue at creation: stop() nulls self._queue before
            # the task's first step ever runs, so the task must not read it
            self._batcher = asyncio.get_running_loop().create_task(
                self._run_batcher(self._queue)
            )
        return self

    async def stop(self) -> None:
        if self._batcher is not None:
            # close the request plane FIRST: a submit racing stop() raises
            # instead of landing behind the sentinel, where its future would
            # never resolve (the batcher exits at the sentinel)
            queue, self._queue = self._queue, None
            await queue.put(None)  # sentinel: flush + exit
            await self._batcher
            self._batcher = None
            self._pool.shutdown(wait=True)
            self._pool = None

    def kill(self, reason: str = "killed") -> None:
        """Abrupt worker death (the supervisor's eviction path and the fault
        layer's death hook): cancel the batcher, drop the compute pool, and
        fail every unresolved future with :class:`WorkerUnavailable` — a
        supervisor re-routes them, so nothing accepted is silently lost."""
        if self._killed is not None:
            return
        self._killed = reason
        self._queue = None  # close the request plane
        if self._batcher is not None:
            self._batcher.cancel()
            self._batcher = None
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)
            self._pool = None
        self.compute.launched.clear()
        self.compute.device_inputs.clear()
        err = WorkerUnavailable(f"worker killed: {reason}")
        for fut in list(self._unresolved):
            if not fut.done():
                fut.set_exception(err)
        self._unresolved.clear()
        self._live_reqs = 0

    @property
    def is_alive(self) -> bool:
        """True while the batcher task is running (not stopped or killed)."""
        return self._batcher is not None and not self._batcher.done()

    async def __aenter__(self) -> "AsyncCnnEngine":
        return await self.start()

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    # -- request plane ------------------------------------------------------

    @property
    def pending(self) -> int:
        return self._queue.qsize() if self._queue is not None else 0

    @property
    def outstanding(self) -> int:
        """Admitted-but-unresolved requests — the supervisor's
        least-outstanding routing signal."""
        return self._live_reqs

    def _retry_after_hint_ms(self) -> float:
        """Load-shedding hint: estimated drain time of the current backlog
        (batches ahead x the compute thread's mean seconds per batch, or
        ``max_delay_ms`` before the first batch)."""
        m = self._metrics
        busy_s = m.stack_s + m.dispatch_s + m.result_wait_s + m.post_s
        per_batch = 1e3 * busy_s / m.batches if m.batches else self.max_delay_ms
        backlog = -(-max(self._live_reqs, 1) // self.compute.max_batch)
        return per_batch * backlog

    def submit_nowait(self, image, *, uid: int | None = None,
                      deadline_ms: float | None = None) -> asyncio.Future:
        """Admit one request (or raise :class:`AdmissionError`); returns the
        future that resolves to its finished :class:`CnnRequest`."""
        if self._queue is None:
            raise RuntimeError(
                "engine not started: use `async with engine:` or "
                "`await engine.start()`"
            )
        try:
            # every admitted-but-unresolved request counts — queued,
            # coalescing, or in the compute thread — so the bound holds end
            # to end even though the batcher pipelines batches instead of
            # awaiting each one
            batching.admit_or_raise(self._live_reqs, self.max_pending,
                                    retry_after_ms=self._retry_after_hint_ms())
        except AdmissionError:
            self._metrics.rejected += 1
            self._metrics.shed += 1
            raise
        loop = asyncio.get_running_loop()
        if uid is None:
            uid = self._uid
        self._uid = max(self._uid, uid) + 1
        req = CnnRequest(uid=uid, image=np.asarray(image))
        fut = loop.create_future()
        t0 = loop.time()
        deadline = None if deadline_ms is None else t0 + deadline_ms / 1e3
        self._queue.put_nowait((req, fut, t0, deadline))
        self._live_reqs += 1
        self._unresolved.add(fut)
        fut.add_done_callback(self._unresolved.discard)
        self._metrics.submitted += 1
        return fut

    async def submit(self, image, *, uid: int | None = None,
                     deadline_ms: float | None = None) -> CnnRequest:
        """Admit one request and await its result."""
        return await self.submit_nowait(
            image, uid=uid, deadline_ms=deadline_ms
        )

    async def submit_wave(self, images) -> list[CnnRequest]:
        """Admit a wave of requests concurrently and await every result —
        the whole-client loop (the launcher, example, and serving benchmark
        all drive the engine through this one call)."""
        return await asyncio.gather(*(self.submit(im) for im in images))

    # -- batcher (coalescing) + compute plane -------------------------------

    def _expired(self, item, loop) -> bool:
        return item[3] is not None and item[3] <= loop.time()

    def _fail_deadline(self, item) -> None:
        """Fast-fail a request whose deadline expired before dispatch: no
        compute is burned on an answer nobody is waiting for."""
        req, fut, _, _ = item
        self._live_reqs -= 1
        self._metrics.deadline_failures += 1
        err = DeadlineExceeded(
            f"request uid={req.uid} missed its deadline before dispatch"
        )
        req.error = err
        if not fut.done():
            fut.set_exception(err)

    async def _run_batcher(self, queue: asyncio.Queue) -> None:
        loop = asyncio.get_running_loop()
        closing = False
        while not closing:
            item = await queue.get()
            if item is None:
                break
            if self._expired(item, loop):
                self._fail_deadline(item)
                continue
            batch = [item]
            flush_at = loop.time() + self.max_delay_ms / 1e3
            if item[3] is not None:  # per-request deadline caps the window
                flush_at = min(flush_at, item[3])
            deadline_flush = True
            while len(batch) < self.compute.max_batch:
                try:
                    # fast drain: everything already enqueued coalesces
                    # without timer churn (no wait_for per request)
                    nxt = queue.get_nowait()
                except asyncio.QueueEmpty:
                    timeout = flush_at - loop.time()
                    if timeout <= 0:
                        break
                    try:
                        nxt = await asyncio.wait_for(queue.get(), timeout)
                    except asyncio.TimeoutError:
                        break
                if nxt is None:
                    closing = True
                    deadline_flush = False  # shutdown, not a window expiry
                    break
                if self._expired(nxt, loop):
                    self._fail_deadline(nxt)
                    continue
                batch.append(nxt)
                if nxt[3] is not None:
                    flush_at = min(flush_at, nxt[3])
            else:
                deadline_flush = False  # bucket filled before the deadline
            self._dispatch(loop, batch, deadline_flush)
        # the sentinel only stops coalescing; every dispatched batch must
        # still resolve before stop() returns.  Drop what was awaited here:
        # a future already done is awaited without yielding to the loop, so
        # its queued discard callback would never run and this would spin
        while self._inflight:
            inflight = list(self._inflight)
            await asyncio.gather(*inflight)
            self._inflight.difference_update(inflight)

    def _dispatch(self, loop, batch, deadline_flush: bool) -> None:
        """Stage one coalesced batch's input on the device, hand the batch
        to the compute thread and return without waiting (the batcher
        keeps coalescing while compute runs)."""
        reqs = [b[0] for b in batch]
        uids = tuple(r.uid for r in reqs)
        batch_id = self._dispatched
        self._dispatched += 1
        # summed over the batch: now less each request's admission time
        # (both on the loop's clock)
        self._metrics.queue_wait_s += (len(batch) * loop.time()
                                       - sum(map(_ADMITTED, batch)))
        # before the compute thread can see the batch: its launch, or the
        # look-ahead's, then finds the staged input
        self.compute.stage([r.image for r in reqs], uids, batch_id)
        t_dispatch = time.perf_counter()
        self._staged.append((batch_id, reqs))

        def compute_then_resolve():
            # compute thread: launch the next batch if one waits, then the
            # resilient blocking jax dispatch (retry/backoff + bisection),
            # then ONE call_soon_threadsafe hands the finished batch to the
            # loop
            self._metrics.executor_wait_s += time.perf_counter() - t_dispatch
            self._staged.popleft()  # this batch: the pool runs them in order
            self._look_ahead(batch_id, reqs)
            retries = 0
            try:
                outcomes, retries = _classify_resilient(
                    self.compute, reqs, self.retry, batch=batch_id
                )
                err = None
            except Exception as e:  # WorkerDeath or a catastrophic failure
                outcomes, err = None, e
            # a classify that never reached the launched output or the
            # staged input leaves them
            self.compute.launched.pop(uids, None)
            self.compute.device_inputs.pop(uids, None)
            with TraceAnnotation("marvel.serve.handoff", batch=batch_id):
                try:
                    loop.call_soon_threadsafe(
                        self._resolve_batch, loop, batch_id, batch, outcomes,
                        retries, err, deadline_flush,
                    )
                except RuntimeError:
                    pass  # loop closed during worker death; futures already dead

        fut = loop.run_in_executor(self._pool, compute_then_resolve)
        self._inflight.add(fut)
        fut.add_done_callback(self._inflight.discard)

    def _look_ahead(self, batch_id: int, reqs: list[CnnRequest]) -> None:
        """Compute thread, before this batch's classify: where the next
        batch is already waiting, launch this batch (unless the look-ahead
        before it did) and then the next one, so that the next batch's
        executable is queued on the device behind this one.  Where none is
        waiting, classify runs the batch in turn.  A launch that raises is
        dropped: each batch's own resilient path launches it again."""
        try:
            next_id, next_reqs = self._staged[0]
        except IndexError:
            return
        compute = self.compute
        try:
            compute.tags = {"batch": batch_id, "attempt": 0}
            compute.launch([r.image for r in reqs], tuple(r.uid for r in reqs))
            compute.tags = {"batch": next_id, "attempt": 0}
            if compute.launch([r.image for r in next_reqs],
                              tuple(r.uid for r in next_reqs)):
                self._metrics.prefetched += 1
        except Exception:
            pass

    def _resolve_batch(self, loop, batch_id, batch, outcomes, retries, err,
                       deadline_flush: bool) -> None:
        """Event-loop callback: resolve a whole batch's futures (submission
        order within the batch) and record its metrics, inside the
        ``marvel.serve.resolve`` span."""
        with TraceAnnotation("marvel.serve.resolve", batch=batch_id):
            self._resolve(loop, batch, outcomes, retries, err, deadline_flush)

    def _resolve(self, loop, batch, outcomes, retries, err,
                 deadline_flush: bool) -> None:
        if self._killed is not None:
            return  # kill() already failed the futures; don't double-count
        self._live_reqs -= len(batch)
        # EVERY dispatched batch is accounted here — success or failure —
        # so the structural invariant loop_handoffs == batches stays exact
        # across the error path and latency/occupancy never silently
        # exclude failed batches
        self._metrics.loop_handoffs += 1
        bucket = batching.bucket_for(self.compute.buckets, len(batch))
        self._metrics.observe_batch(len(batch), bucket,
                                    deadline=deadline_flush)
        self._metrics.retries += retries
        if err is not None:
            if isinstance(err, faults.WorkerDeath):
                # the worker is gone, not the batch: kill() fails this
                # batch's futures (and all other unresolved ones) with
                # WorkerUnavailable so a supervisor re-routes them
                self.kill(str(err))
                return
            for req, fut, _, _ in batch:
                req.error = err
                self._metrics.errors += 1
                if not fut.done():
                    fut.set_exception(err)
            return
        now = loop.time()
        for (req, fut, t0, _), out in zip(batch, outcomes):
            req.latency_ms = (now - t0) * 1e3
            if out[0] == "err":
                req.error = out[1]
                self._metrics.errors += 1
                if not fut.done():
                    fut.set_exception(out[1])
                continue
            req.label = out[1]
            req.probs = out[2]
            req.logits = out[3]
            req.done = True
            self._metrics.completed += 1
            self._metrics.observe_latency(req.latency_ms)
            if not fut.done():
                fut.set_result(req)

    # -- observability ------------------------------------------------------

    def warmup(self, in_shape: tuple[int, ...], dtype="float32") -> None:
        self.compute.warmup(in_shape, dtype)

    def ping(self) -> concurrent.futures.Future:
        """A no-op through the compute thread, returned as a concurrent
        future.  The supervisor times this round-trip as the worker
        heartbeat: it queues behind whatever the compute thread is doing,
        so a hung or straggling worker shows up as a slow (or timed-out)
        heartbeat."""
        if self._pool is None:
            raise WorkerUnavailable(
                f"no compute pool (engine "
                f"{'killed: ' + self._killed if self._killed else 'not started'})"
            )
        return self._pool.submit(lambda: None)

    @property
    def batches_run(self) -> int:
        return self._metrics.batches

    def metrics(self) -> dict:
        """The serving metrics surface (program cache counters included)."""
        return self._metrics.snapshot(
            queue_depth=self.pending,
            **_program_metrics(self.compute.program),
        )


def _program_metrics(program) -> dict:
    """Cache hit/miss, build seconds, reference fallbacks and shard
    counters re-exported from the MarvelProgram."""
    return {
        "cache_hits": getattr(program, "cache_hits", 0),
        "cache_misses": getattr(program, "cache_misses", 0),
        "cache_size": getattr(program, "cache_size", 0),
        "build_s": getattr(program, "build_s", 0.0),
        "ref_fallbacks": getattr(program, "ref_fallbacks", 0),
        "dp_shards": int(getattr(program, "dp_shards", 1) or 1),
    }
