"""Load generation: one general generator, driven by a workload file.

A workload file (``workloads/<cell>.json``) names a loop and its
parameters; nothing here knows a cell by name.

* ``loop: closed`` — ``clients`` logical clients, each with exactly one
  request in flight: a client submits its next request when its previous
  one appears on the committed stream.  A slow system receives less load,
  so there is no rate to find.
* ``loop: open`` — Poisson arrivals at ``rate_per_s`` over ``clients``
  Zipf(``client_skew``) clients, whether or not the system keeps up.  Each
  request is timed from when it was DUE, which counts the wait a stall
  imposes on later arrivals, and the generator reports how late it ran.

:class:`ZipfClients` and :class:`OpenLoopPump` are copies of the program's
``smartbft_tpu/testing/load.py`` (listed in PERF.md for a later PR to
delete there); the pump here also hands out each arrival's due time.

Every stamp is a raw ``time.perf_counter()`` reading kept per request;
quantiles come from :mod:`chipbench.stats`, never from a histogram.  The
generator runs on the event loop it loads (one process, one thread), so
its lateness is part of every report.
"""

from __future__ import annotations

import asyncio
import bisect
import contextlib
import random
import time
import types
from typing import Callable, Optional

__all__ = ["LoadLoop", "OpenLoopPump", "ZipfClients", "find_knee"]

#: submit errors that are the system SHEDDING load (counted apart from
#: other failures); matched by class name so this file imports nothing
#: of the program
SHED_ERRORS = ("AdmissionRejected", "SubmitTimeoutError")


class ZipfClients:
    """Client ids under a Zipf(s) popularity law: rank r weighs 1/r^s.
    ``skew`` 0 is uniform."""

    def __init__(self, n_clients: int = 512, skew: float = 1.1,
                 prefix: str = "zipf"):
        if n_clients < 1:
            raise ValueError(f"need at least one client, got {n_clients}")
        self.n_clients = n_clients
        self.skew = skew
        self.prefix = prefix
        self._cdf: list[float] = []
        acc = 0.0
        for rank in range(1, n_clients + 1):
            acc += 1.0 / (rank ** skew)
            self._cdf.append(acc)
        self._total = acc

    def sample(self, rng: random.Random) -> str:
        x = rng.random() * self._total
        idx = bisect.bisect_left(self._cdf, x)
        return f"{self.prefix}{min(idx, self.n_clients - 1)}"


class OpenLoopPump:
    """Poisson arrival schedule against an external clock.  It never skips
    backlog: if the caller stalls, every missed arrival comes out of the
    next call, each with the time at which it was due."""

    def __init__(self, rate: float, rng: random.Random, start: float = 0.0):
        if rate <= 0:
            raise ValueError(f"arrival rate must be positive, got {rate}")
        self.rate = float(rate)
        self._rng = rng
        self._next = start + rng.expovariate(self.rate)

    def due_times(self, now: float) -> list[float]:
        out = []
        while self._next <= now:
            out.append(self._next)
            self._next += self._rng.expovariate(self.rate)
        return out


def find_knee(rows: list) -> dict:
    """The saturation knee from sweep rows (a copy of
    ``benchmarks/openloop.py:find_knee`` on this harness's row keys): the
    last offered rate meeting goodput >= 0.9 x offered and shed < 1 %, and
    the first that misses it.  With no overloaded point the knee lies
    beyond the sweep."""
    ok, overloaded = [], []
    for r in rows:
        meets = (r["goodput_per_s"] >= 0.9 * r["offered_per_s"]
                 and r["shed_share"] < 0.01)
        (ok if meets else overloaded).append(r)
    return {
        "slo": "goodput >= 0.9*offered and shed < 1%",
        "last_ok": max(ok, key=lambda r: r["offered_per_s"]) if ok else None,
        "first_overloaded": min(overloaded, key=lambda r: r["offered_per_s"])
        if overloaded else None,
        "beyond_sweep": not overloaded,
    }


@types.coroutine
def _repark(step):
    yield step


async def _drive(coro, step) -> None:
    """Finish a submit coroutine that parked on its first step."""
    try:
        while True:
            await _repark(step)
            try:
                step = coro.send(None)
            except StopIteration:
                return
    finally:
        coro.close()


class LoadLoop:
    """Drive ``cluster`` with one workload and keep every stamp.

    ``cluster`` needs ``async submit(client_id, request_id)`` and
    ``poll() -> entries`` whose ``request_ids`` are ``"client:request"``
    strings in committed order (``ShardedCluster`` is that).

    :meth:`run` warms up for ``warmup_s``, opens the measured window for
    ``seconds``, closes it (no further submit), and drains.  ``on_open`` /
    ``on_close`` run at the two instants, ``on_tick(now)`` at every turn
    of the generator while the window is open (the traced run's hook).
    """

    def __init__(self, cluster, spec: dict, seed: int, *,
                 clock: Callable[[], float] = time.perf_counter,
                 annotate: Optional[Callable[[str], object]] = None):
        self.cluster = cluster
        self.kind = spec["loop"]
        if self.kind not in ("closed", "open"):
            raise ValueError(f"loop must be closed or open, got {self.kind!r}")
        self.poll_s = float(spec.get("poll_ms", 5)) / 1e3
        self.drain_s = float(spec.get("drain_s", 30.0))
        self.clock = clock
        self.annotate = annotate or (lambda _name: contextlib.nullcontext())
        self.rng = random.Random(seed)
        tag = f"{seed:x}"
        n_clients = int(spec["clients"])
        if self.kind == "closed":
            ids = [f"c{tag}-{i}" for i in range(n_clients)]
            self.rng.shuffle(ids)  # same set of clients, seeded order
            self._ready: list[str] = ids
            self.pump = None
        else:
            self._ready = []
            self.zipf = ZipfClients(n_clients, float(spec["client_skew"]),
                                    prefix=f"z{tag}-")
            self.rate = float(spec["rate_per_s"])
            self.pump = None  # made when run() knows the start time
        self._seq: dict[str, int] = {}
        #: key -> (stamp the latency counts from, client, measured)
        self.inflight: dict[str, tuple[float, str, bool]] = {}
        #: (commit stamp, latency seconds, measured) per committed request
        self.commits: list[tuple[float, float, bool]] = []
        self.committed_keys: list[str] = []
        #: (stamp, requests) per committed decision
        self.decisions: list[tuple[float, int]] = []
        self.lateness: list[float] = []
        self.peak_inflight = 0
        self.attempted = 0
        self.shed = 0
        self.errored = 0
        #: measured submits that were shed or raised
        self.failed_submits = 0
        self.error_samples: list[str] = []
        self.unknown_commits = 0
        self.polls = 0
        self.window: list[Optional[float]] = [None, None]
        self._tasks: set = set()

    # -- submitting ----------------------------------------------------------

    async def _submit(self, client: str, rid: str, key: str,
                      measured: bool) -> None:
        try:
            await self.cluster.submit(client, rid)
        except Exception as e:  # noqa: BLE001 — accounting must not die
            if type(e).__name__ in SHED_ERRORS:
                self.shed += 1
            else:
                self.errored += 1
                if len(self.error_samples) < 4:
                    self.error_samples.append(repr(e))
            if self.inflight.pop(key, None) is not None:
                if measured:
                    self.failed_submits += 1
                if self.kind == "closed":
                    self._ready.append(client)

    def _start_submit(self, client: str, stamp: float) -> None:
        """Stamp, then submit: the stamp precedes any admission or pool
        wait.  A submit that does not park completes inline; one that
        parks becomes a task, so the generator never waits for it."""
        k = self._seq.get(client, 0)
        self._seq[client] = k + 1
        rid = f"r{k}"
        key = f"{client}:{rid}"
        measured = self.window[0] is not None and self.window[1] is None
        if measured:
            self.attempted += 1
        self.inflight[key] = (stamp, client, measured)
        coro = self._submit(client, rid, key, measured)
        try:
            parked_on = coro.send(None)
        except StopIteration:
            return
        task = asyncio.ensure_future(_drive(coro, parked_on))
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)

    def _offer(self, now: float) -> None:
        if self.kind == "closed":
            ready, self._ready = self._ready, []
            for client in ready:
                self._start_submit(client, self.clock())
        else:
            for due in self.pump.due_times(now):
                self._start_submit(self.zipf.sample(self.rng), due)
                self.lateness.append(self.clock() - due)
        if len(self.inflight) > self.peak_inflight:
            self.peak_inflight = len(self.inflight)

    # -- the committed stream ------------------------------------------------

    def _collect(self) -> None:
        entries = self.cluster.poll()
        self.polls += 1
        if not entries:
            return
        now = self.clock()
        for e in entries:
            self.decisions.append((now, len(e.request_ids)))
            for key in e.request_ids:
                rec = self.inflight.pop(key, None)
                if rec is None:
                    self.unknown_commits += 1
                    continue
                stamp, client, measured = rec
                self.commits.append((now, now - stamp, measured))
                self.committed_keys.append(key)
                if self.kind == "closed":
                    self._ready.append(client)

    # -- the run -------------------------------------------------------------

    async def run(self, warmup_s: float, seconds: float, *,
                  on_open: Optional[Callable[[], None]] = None,
                  on_close: Optional[Callable[[], None]] = None,
                  on_tick: Optional[Callable[[float], None]] = None) -> None:
        start = self.clock()
        if self.kind == "open":
            self.pump = OpenLoopPump(self.rate, self.rng, start=start)
        open_at = start + warmup_s
        drain_until = None
        while True:
            with self.annotate("chipbench.poll"):
                self._collect()
            now = self.clock()
            if self.window[0] is None and now >= open_at:
                if on_open is not None:
                    on_open()
                now = self.window[0] = self.clock()
            if self.window[0] is not None and self.window[1] is None:
                if on_tick is not None:
                    on_tick(now)
                    now = self.clock()
                if now >= self.window[0] + seconds:
                    self.window[1] = now
                    if on_close is not None:
                        on_close()
                    drain_until = self.clock() + self.drain_s
            if self.window[1] is None:
                with self.annotate("chipbench.submit"):
                    self._offer(now)
            elif not self.inflight and not self._tasks:
                break
            elif self.clock() >= drain_until:
                break
            with self.annotate("chipbench.yield"):
                await asyncio.sleep(self.poll_s)
        for task in list(self._tasks):
            task.cancel()
        if self._tasks:
            await asyncio.gather(*self._tasks, return_exceptions=True)

    # -- what the window held --------------------------------------------------

    def window_commits(self) -> list[tuple[float, float, bool]]:
        t0, t1 = self.window
        return [c for c in self.commits if t0 <= c[0] < t1]

    def window_decisions(self) -> list[tuple[float, int]]:
        t0, t1 = self.window
        return [d for d in self.decisions if t0 <= d[0] < t1]

    def never_committed(self) -> int:
        """Measured submits still in flight when the drain ended."""
        return sum(1 for _, _, measured in self.inflight.values() if measured)
