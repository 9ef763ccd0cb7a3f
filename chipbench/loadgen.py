"""The one load generator: a closed loop of clients or an open schedule
of arrivals, from a traffic file's parameters and the seed.

Traffic file keys:

* ``kind``: ``"closed"`` (``clients`` callers, each sending its next
  request when the last one is answered) or ``"open"`` (requests sent on a
  schedule whatever the answers: ``rate_per_s`` arrivals per second).
* ``pool``: how many distinct inputs the requests cycle through.

An open schedule sends exactly ``round(rate_per_s * seconds)`` requests, a
Poisson process given its count.  The gaps between arrivals are one fixed
draw of exponential spacings scaled to the window; the seed only orders
them and picks the images, so every seed offers the same work with the
same gaps in another order.
Each request carries its pool index, and every time is on the event
loop's clock: ``due`` is when it was meant to go, ``sent`` when it went,
``done`` when the client had its answer.
"""
from __future__ import annotations

import asyncio
import contextlib
import itertools
from dataclasses import dataclass

import numpy as np


@dataclass
class Request:
    pool: int
    due: float
    sent: float = 0.0
    done: float | None = None
    logits: np.ndarray | None = None
    error: str | None = None


def open_schedule(traffic: dict, seconds: float, seed: int):
    """(offsets in seconds from the window's start, pool indices)."""
    n = int(round(traffic["rate_per_s"] * seconds))
    gaps = np.random.default_rng(0).exponential(1.0, n + 1)
    gaps *= seconds / gaps.sum()  # the last of the n + 1 gaps ends the window
    rng = np.random.default_rng([seed, 1])
    offsets = np.cumsum(rng.permutation(gaps))[:n]
    return offsets, rng.integers(0, traffic["pool"], n)


def pool_order(traffic: dict, seed: int) -> np.ndarray:
    """The order a closed loop's clients take the pool in (cycled)."""
    return np.random.default_rng([seed, 2]).permutation(traffic["pool"])


class Generator:
    """Drives ``submit(image) -> future`` with one traffic mix.

    ``span(name)`` wraps the generator's own host work (a profiler span
    while tracing, nothing otherwise)."""

    def __init__(self, traffic: dict, images, submit, seed: int,
                 span=None):
        self.traffic = traffic
        self.images = images
        self.submit = submit
        self.seed = seed
        self.span = span or (lambda name: contextlib.nullcontext())
        self.requests: list[Request] = []
        self._clients: list[asyncio.Task] = []  # closed loop
        self._pending: set = set()  # open loop: answers still due

    def _send(self, req: Request, loop) -> asyncio.Future | None:
        req.sent = loop.time()
        self.requests.append(req)
        with self.span("chipbench.submit"):
            try:
                fut = self.submit(self.images[req.pool])
            except Exception as e:  # shed at admission
                req.error = repr(e)
                return None
        fut.add_done_callback(lambda f: self._receive(req, f, loop))
        return fut

    def _receive(self, req: Request, fut, loop) -> None:
        with self.span("chipbench.receive"):
            req.done = loop.time()
            if fut.cancelled():
                req.error = "cancelled"
            elif fut.exception() is not None:
                req.error = repr(fut.exception())
            else:
                req.logits = np.asarray(fut.result().logits)

    async def run(self, seconds: float) -> tuple[float, float]:
        """Offer the load for ``seconds``; returns the window's (start,
        end) on the loop clock.  Answers still due at the end are awaited
        by :meth:`drain`."""
        loop = asyncio.get_running_loop()
        if self.traffic["kind"] == "closed":
            return await self._closed(loop, seconds)
        if self.traffic["kind"] == "open":
            return await self._open(loop, seconds)
        raise ValueError(f"unknown traffic kind {self.traffic['kind']!r}")

    async def _closed(self, loop, seconds):
        order = pool_order(self.traffic, self.seed)
        counter = itertools.count()
        start = loop.time()
        end = start + seconds

        async def client():
            while loop.time() < end:
                req = Request(pool=int(order[next(counter) % len(order)]),
                              due=loop.time())
                fut = self._send(req, loop)
                if fut is None:
                    await asyncio.sleep(1e-3)
                    continue
                with contextlib.suppress(Exception):
                    await fut

        self._clients = [loop.create_task(client())
                         for _ in range(self.traffic["clients"])]
        await asyncio.sleep(max(0.0, end - loop.time()))
        return start, end

    async def _open(self, loop, seconds):
        offsets, pools = open_schedule(self.traffic, seconds, self.seed)
        start = loop.time()
        for off, p in zip(offsets, pools):
            due = start + float(off)
            delay = due - loop.time()
            if delay > 0:
                await asyncio.sleep(delay)
            fut = self._send(Request(pool=int(p), due=due), loop)
            if fut is not None:
                self._pending.add(fut)
        end = start + seconds
        await asyncio.sleep(max(0.0, end - loop.time()))
        return start, end

    async def drain(self, timeout: float) -> None:
        """Wait, at most ``timeout`` seconds, for every answer still due.
        A request still unanswered then stays unanswered (``done`` None)."""
        clients = self._clients
        waits = clients + list(self._pending)
        if not waits:
            return
        _, late = await asyncio.wait(waits, timeout=timeout)
        for task in late:
            task.cancel()
        for task in clients:
            if task not in late:
                task.result()  # a client's own failure surfaces here
