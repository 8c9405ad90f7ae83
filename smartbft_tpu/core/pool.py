"""Bounded FIFO request pool with a three-stage timeout chain.

Re-design of /root/reference/internal/bft/requestpool.go:52-567.  The
reference uses a linked list + existence map + weighted semaphore + one
``time.AfterFunc`` goroutine per request; here the FIFO and existence map
collapse into one ordered dict, the semaphore into a waiter queue of
futures, and the per-request timers into a lazy timer wheel (per-stage
FIFO deques + ONE armed timer on the shared tick-driven
:class:`~smartbft_tpu.utils.clock.Scheduler`) so tests are deterministic
and the commit path pays no schedule/cancel pair for timers that never
fire — which at open-loop rates is nearly all of them.

Timeout chain per request (requestpool.go:493-567):
  forward timeout  -> on_request_timeout  (forward request to leader;
                      a view flip and a rotation's hand-over put one
                      bonus forward at FORWARD_TIMEOUT_FLOOR before it,
                      see restart_timers)
  complain timeout -> on_leader_fwd_request_timeout (complain -> view change)
  auto-remove      -> on_auto_remove_timeout (drop the request)
"""

from __future__ import annotations

import abc
import asyncio
from collections import OrderedDict, deque
from dataclasses import dataclass
from typing import Callable, Optional

from ..api import Logger, RequestInspector
from ..metrics import RequestPoolMetrics
from ..obs.recorder import close_for_await
from ..types import RequestInfo
from ..utils.clock import Scheduler, TaskHandle

# dedup memory of recently deleted requests (requestpool.go:26)
DEFAULT_SIZE_OF_DEL_ELEMENTS = 1000


class PoolError(Exception):
    pass


class ReqAlreadyExistsError(PoolError):
    pass


class ReqAlreadyProcessedError(PoolError):
    pass


class RequestTooBigError(PoolError):
    pass


class SubmitTimeoutError(PoolError):
    pass


class AdmissionRejected(PoolError):
    """Fast-fail shed at the admission gate: pool occupancy (pooled
    requests + already-parked submitters) is past the configured
    high-water mark, so this submit is REFUSED immediately instead of
    parking behind a queue that is already past its knee.

    ``retry_after`` is the hint (seconds, same clock as the pool's
    scheduler) derived from the measured drain rate: roughly how long the
    pool needs to drain back below the high-water mark.  A client that
    retries sooner will very likely be shed again; one that waits it out
    arrives when capacity plausibly exists.  ``occupancy`` snapshots the
    gate's inputs at rejection time."""

    def __init__(self, message: str, *, retry_after: float = 0.0,
                 occupancy: Optional[dict] = None):
        super().__init__(message)
        self.retry_after = retry_after
        self.occupancy = occupancy or {}


class PoolClosedError(PoolError):
    pass


class RequestTimeoutHandler(abc.ABC):
    """Implemented by the Controller (requestpool.go:38-47)."""

    @abc.abstractmethod
    def on_request_timeout(self, request: bytes, info: RequestInfo) -> None: ...

    @abc.abstractmethod
    def on_leader_fwd_request_timeout(self, request: bytes, info: RequestInfo) -> None: ...

    @abc.abstractmethod
    def on_auto_remove_timeout(self, info: RequestInfo) -> None: ...


@dataclass
class PoolOptions:
    queue_size: int = 200
    forward_timeout: float = 10.0
    complain_timeout: float = 10.0
    auto_remove_timeout: float = 10.0
    request_max_bytes: int = 100 * 1024
    #: TOTAL wall/logical seconds one submit may spend parked on space —
    #: a single bound across every re-park, not per-wait (a pre-overload
    #: bug let each wakeup re-arm a fresh timeout, so a submitter could
    #: park forever under sustained contention)
    submit_timeout: float = 10.0
    #: admission gate: fraction of queue_size at which submit stops
    #: queueing and fails fast with AdmissionRejected.  The gate input is
    #: pooled requests PLUS already-parked submitters (queueing theory's
    #: "system size", not just the buffer).  >= 1.0 disables shedding —
    #: the pre-overload parking semantics.
    admission_high_water: float = 1.0
    #: optional live forward-timeout provider (RTT derivation, ISSUE 14
    #: satellite): when set, every forward timer arms with
    #: ``clamp(fn(), FORWARD_TIMEOUT_FLOOR, forward_timeout)`` — the
    #: configured constant stays the ceiling AND the fallback (fn
    #: returning None / raising).  Round 16 measured follower-submitted
    #: requests spending 97.6% of their latency waiting out the fixed
    #: constant; on a measured-µs-RTT link the timer collapses to the
    #: floor instead.
    forward_timeout_fn: Optional[Callable[[], Optional[float]]] = None
    #: flip-time backlog drain (ISSUE 15): how many of the OLDEST pooled
    #: requests a view-flip timer restart fast-forwards (their forward
    #: timers arm at FORWARD_TIMEOUT_FLOOR so followers push the stalled
    #: backlog to the new leader within a tick instead of waiting out a
    #: full forward timeout each).  Derived by the consensus facade as
    #: flip_drain_windows * pipeline_depth * request_batch_max_count —
    #: enough to fill the new view's deep windows immediately.  0
    #: disables (every restart uses the ordinary timeout).
    flip_drain_limit: int = 0
    #: the same leg for a ROTATION's hand-over (ISSUE 31): how many of
    #: the oldest requests the replica whose turn just ended forwards to
    #: the new leader, instead of keeping them until the lead comes round
    #: again.  Derived as ONE window (pipeline_depth *
    #: request_batch_max_count), the new leader's first proposals: below
    #: capacity what a leader is left with is what arrived while its last
    #: batch committed, less than a batch; a deeper backlog means the
    #: cluster is at capacity, where carrying it along with the lead
    #: every rotation costs the loop more than it saves (at 400 a
    #: hand-over the knee fell; PERF.md, PR 31).  0 disables.
    handover_limit: int = 0


#: hard lower bound of a derived forward timeout: forwarding is benign
#: (leader pool dedup absorbs duplicates) but a near-zero timer would
#: fire before the submit path even returns
FORWARD_TIMEOUT_FLOOR = 0.01


# timer-wheel stages: which leg of the timeout chain an item's armed
# queue entry belongs to (see Pool._wheel_fire)
_STAGE_IDLE = -1
_STAGE_FWD = 0
_STAGE_COMPLAIN = 1
_STAGE_AUTOREMOVE = 2
_STAGE_FLIP = 3


class _Item:
    __slots__ = ("request", "addition_time", "deadline", "stage", "gen")

    def __init__(self, request: bytes, addition_time: float):
        self.request = request
        self.addition_time = addition_time
        self.deadline = 0.0
        self.stage = _STAGE_IDLE
        self.gen = 0


def remove_delivered_requests(pool, infos, logger) -> None:
    """Bulk-remove a delivered batch from ``pool``, loudly on failure.

    The shared post-delivery idiom (Controller._decide and both ViewChanger
    delivery paths): a not-pooled request is routine on followers and only
    counted, but an unexpected exception means corrupted pool state and
    must warn — the reference logs removal failures too
    (controller.go:258-263, viewchanger.go:1178-1182)."""
    infos = list(infos)
    try:
        not_pooled = pool.remove_requests(infos)
    except Exception as e:
        logger.warnf(
            "Removing delivered requests from the pool failed unexpectedly: %r", e
        )
        return
    if not_pooled:
        logger.debugf(
            "%d of %d delivered requests were not in the pool", not_pooled, len(infos)
        )


class Pool:
    """The request pool.  Owned by the consensus event loop; ``submit`` is
    async (it may wait for space), everything else is synchronous."""

    def __init__(
        self,
        logger: Logger,
        inspector: RequestInspector,
        timeout_handler: RequestTimeoutHandler,
        options: PoolOptions,
        scheduler: Scheduler,
        metrics: Optional[RequestPoolMetrics] = None,
        on_submitted: Optional[Callable[[], None]] = None,
        recorder=None,
    ):
        self._log = logger
        self._inspector = inspector
        self._th = timeout_handler
        self._opts = options
        self._scheduler = scheduler
        self._metrics = metrics
        self._on_submitted = on_submitted or (lambda: None)
        # flight recorder (obs.TraceRecorder, disabled unless tracing —
        # submit's sites guard on .enabled, one attr read each)
        from ..obs.recorder import standby

        self._recorder = standby(recorder)

        self._items: "OrderedDict[RequestInfo, _Item]" = OrderedDict()
        # lazy timer wheel state: one FIFO deque of (deadline, info, gen)
        # per chain stage, and a single armed scheduler timer at the
        # earliest deadline.  See the "timers" section below.
        self._timer_qs: tuple = (deque(), deque(), deque(), deque())
        self._wheel_handle: Optional[TaskHandle] = None
        self._wheel_deadline = float("inf")
        self._gen = 0  # pool-wide monotonic arm counter (stale detection)
        self._size_bytes = 0
        self._closed = False
        self._stopped = False
        # proposed-but-undelivered reservations (pipelined leader only; no
        # reference counterpart).  The single-slot leader re-batches only
        # after delivery REMOVED the previous batch, so the FIFO front is
        # always fresh; a windowed leader batches again while k proposals
        # are still in flight, and without this set it would re-slice the
        # SAME front into every window slot — duplicate delivery of every
        # request up to the window depth.  next_requests skips reserved
        # items; delivery removal clears them; a view change releases them
        # (an uncommitted in-flight batch must become proposable again).
        self._in_flight: set[RequestInfo] = set()
        # recently-deleted dedup: one insertion-ordered dict doubles as
        # membership set and eviction queue (requestpool.go:418-437 keeps a
        # map + slice pair; popping oldest entries from one dict halves the
        # per-removal hash traffic on the n=64 bulk-removal hot path)
        self._del_map: "OrderedDict[RequestInfo, None]" = OrderedDict()
        self._space_waiters: "deque[asyncio.Future]" = deque()
        # slots promised to woken-but-not-yet-resumed waiters: counted as
        # occupied by every capacity check so a fresh submitter cannot
        # barge into a slot during the one-loop-hop wake window
        self._reserved_slots = 0
        # overload accounting: sheds by cause + a drain-rate estimate for
        # the AdmissionRejected retry-after hint (see _note_drained)
        self.shed_admission = 0
        self.shed_timeout = 0
        #: requests fast-forwarded by flip-time timer restarts (ISSUE 15)
        self.flip_drains = 0
        #: requests this replica fast-forwarded when it handed the lead
        #: over at a rotation (ISSUE 31), kept apart from the view changes'
        self.handovers = 0
        self._drain_anchor = scheduler.now()
        self._drain_accum = 0
        self._drain_rate = 0.0  # requests/sec, EWMA over DRAIN_WINDOW spans
        # admission-side twin of the drain estimate: how fast requests are
        # ARRIVING (admitted submits/sec).  The arrival-driven BatchBuilder
        # reads this to predict whether the in-formation wave can fill
        # before its deadline (README "Arrival-driven proposing").
        self._arrival_anchor = scheduler.now()
        self._arrival_accum = 0
        self._arrival_rate = 0.0  # requests/sec, EWMA over ARRIVAL_WINDOW spans

    # ------------------------------------------------------------------ submit

    def _admission_slots(self) -> Optional[int]:
        """The high-water mark in SLOTS, or None when the gate is off."""
        hw = self._opts.admission_high_water
        if hw >= 1.0:
            return None
        return max(1, int(hw * self._opts.queue_size))

    async def submit(self, request: bytes, *, forwarded: bool = False) -> None:
        """Add a request; dedups against in-pool and recently-deleted.

        Overload contract (requestpool.go:191-284, hardened):

        * **admission gate** — with ``admission_high_water`` < 1, a pool
          whose system size (pooled + parked submitters) is at/past the
          mark sheds THIS submit immediately with :class:`AdmissionRejected`
          (retry-after hint from the drain rate) instead of queueing past
          the knee.  ``forwarded=True`` (a follower's forward landing at
          the leader) BYPASSES the gate: the request already holds a pool
          slot cluster-side and shedding it here would only re-arm the
          follower's complain timer — internal forwards ride the existing
          timeout chain, the gate guards the client-facing door (README
          "Overload behavior");
        * **bounded wait** — below the mark but full, the submitter parks
          for at most ``submit_timeout`` TOTAL (one deadline across every
          re-park), then sheds with :class:`SubmitTimeoutError`; a shed
          submitter's request is in no pool;
        * **FIFO fairness** — parked submitters are woken oldest-first,
          and a fresh submitter never barges past them even when a removal
          just freed a slot (it parks at the tail; a woken waiter that
          loses a race re-parks at the HEAD, keeping its place).
        """
        info = self._inspector.request_id(request)
        rec = self._recorder
        if rec.enabled:
            rec.record("req.submit", key=str(info),
                       extra={"forwarded": forwarded} if forwarded else None)
        if self._closed:
            raise PoolClosedError(f"pool closed, request rejected: {info}")
        if len(request) > self._opts.request_max_bytes:
            if self._metrics:
                self._metrics.count_of_failed_add_requests.with_labels("max_bytes").add(1)
            raise RequestTooBigError(
                f"submitted request ({len(request)}) is bigger than "
                f"request max bytes ({self._opts.request_max_bytes})"
            )
        self._check_dup(info)

        hw = self._admission_slots() if not forwarded else None
        if hw is not None \
                and len(self._items) + self._reserved_slots \
                + len(self._space_waiters) >= hw:
            self.shed_admission += 1
            if self._metrics:
                self._metrics.count_of_failed_add_requests.with_labels("admission").add(1)
            if rec.enabled:
                rec.record("req.shed", key=str(info),
                           extra={"kind": "admission"})
            raise AdmissionRejected(
                f"admission control: pool at "
                f"{len(self._items)}+{len(self._space_waiters)} of "
                f"high-water {hw}/{self._opts.queue_size}, request shed: "
                f"{info}",
                retry_after=self.retry_after_hint(),
                occupancy=self.occupancy(),
            )

        deadline = self._scheduler.now() + self._opts.submit_timeout
        at_head = False
        parked_at: Optional[float] = None
        while len(self._items) + self._reserved_slots >= self._opts.queue_size \
                or (self._space_waiters and not at_head):
            if parked_at is None:
                parked_at = self._scheduler.now()
            remaining = deadline - self._scheduler.now()
            if remaining <= 0:
                self.shed_timeout += 1
                if self._metrics:
                    self._metrics.count_of_failed_add_requests.with_labels("semaphore").add(1)
                if rec.enabled:
                    rec.record("req.shed", key=str(info),
                               dur=self._scheduler.now() - parked_at,
                               extra={"kind": "timeout"})
                raise SubmitTimeoutError(
                    f"timeout submitting to request pool: {info}"
                )
            fut: asyncio.Future = asyncio.get_running_loop().create_future()
            if at_head:
                self._space_waiters.appendleft(fut)
            else:
                self._space_waiters.append(fut)
            timer = self._scheduler.schedule(
                remaining,
                lambda: fut.done() or fut.set_exception(
                    SubmitTimeoutError(f"timeout submitting to request pool: {info}")
                ),
            )
            woken_clean = False
            if rec.enabled:
                close_for_await()  # the front door's busy span ends here
            try:
                await fut
                woken_clean = True
            except SubmitTimeoutError:
                self.shed_timeout += 1
                if self._metrics:
                    self._metrics.count_of_failed_add_requests.with_labels("semaphore").add(1)
                if rec.enabled:
                    rec.record("req.shed", key=str(info),
                               dur=self._scheduler.now() - parked_at,
                               extra={"kind": "timeout"})
                raise
            finally:
                timer.cancel()
                if fut in self._space_waiters:
                    self._space_waiters.remove(fut)
                    # this waiter left the queue without consuming a slot;
                    # the new head must not strand on capacity it now owns
                    self._release_space()
                elif fut.done() and not fut.cancelled() \
                        and fut.exception() is None:
                    # woken by _release_space: stop reserving its slot —
                    # filled synchronously below on the clean path
                    self._reserved_slots -= 1
                    if not woken_clean:
                        # woken but exiting abnormally (task cancelled in
                        # the wake window): hand the freed slot to the next
                        # waiter instead of stranding it until a removal
                        self._release_space()
            if self._closed:
                raise PoolClosedError(f"pool closed, request rejected: {info}")
            # space may have been taken by another woken waiter; dedup
            # again and loop — re-parking at the HEAD keeps FIFO order
            at_head = True
            try:
                self._check_dup(info)
            except PoolError:
                # this waiter exits without filling the slot it was woken
                # for — hand it to the next in line, don't strand them
                self._release_space()
                raise

        item = _Item(request, self._scheduler.now())
        self._items[info] = item
        if not self._stopped:
            self._arm(info, item, _STAGE_FWD, self._forward_timeout())
        self._size_bytes += len(request)
        if parked_at is not None and rec.enabled:
            # only a submit that parked on space: dur = the time it was
            # parked.  (An immediate add has its req.submit mark; critpath
            # folds an absent pool mark into the next segment.)
            rec.record("req.pool", key=str(info),
                       dur=self._scheduler.now() - parked_at,
                       extra={"size": len(self._items)})
        if self._metrics:
            self._metrics.count_of_requests.set(len(self._items))
        # the fairness rule parks fresh submitters behind existing waiters
        # even when a slot is free; hand any remaining capacity to them now
        self._release_space()
        self._note_arrival()
        self._on_submitted()

    def _check_dup(self, info: RequestInfo) -> None:
        if info in self._items:
            raise ReqAlreadyExistsError(f"request already exists: {info}")
        if info in self._del_map:
            raise ReqAlreadyProcessedError(f"request already processed: {info}")

    # ------------------------------------------------------------------ batch

    def size(self) -> int:
        return len(self._items)

    def size_bytes(self) -> int:
        return self._size_bytes

    def has_room(self) -> bool:
        """Would a submit land now, without parking on space?"""
        return (len(self._items) + self._reserved_slots
                < self._opts.queue_size and not self._space_waiters)

    def occupancy(self) -> dict:
        """One JSON-able backpressure snapshot — the per-shard building
        block of the sharded front door's combined occupancy surface
        (shard.ShardSet.occupancy sums these across shards).  ``free`` is
        how many submits can land before :meth:`submit` starts waiting;
        ``waiters`` is how many submitters are ALREADY parked on space;
        the ``shed_*`` counters and ``drain_rate`` are the admission
        gate's outputs (README "Overload behavior")."""
        hw = self._admission_slots()
        return {
            "size": len(self._items),
            "bytes": self._size_bytes,
            "capacity": self._opts.queue_size,
            # reserved slots are promised to woken waiters — not free, or
            # this would overstate headroom submit() will refuse to honor
            "free": max(0, self._opts.queue_size - len(self._items)
                        - self._reserved_slots),
            "in_flight": len(self._in_flight),
            # reserved slots belong to woken-but-not-yet-resumed waiters:
            # still counted so the reshard drain (which must wait out every
            # space-waiter) cannot observe a spuriously clean pool
            "waiters": len(self._space_waiters) + self._reserved_slots,
            "high_water": hw if hw is not None else self._opts.queue_size,
            "shed_admission": self.shed_admission,
            "shed_timeout": self.shed_timeout,
            "flip_drains": self.flip_drains,
            "handovers": self.handovers,
            "drain_rate": round(self._drain_rate, 3),
            "arrival_rate": round(self.arrival_rate(), 3),
        }

    # -- drain-rate estimate (the retry-after hint's input) ----------------

    #: seconds of scheduler time one drain-rate sample spans; short enough
    #: to track a breaker trip's capacity collapse within a few waves,
    #: long enough that one bulk removal does not read as a steady rate
    DRAIN_WINDOW = 0.5

    def _note_drained(self, n: int) -> None:
        """Fold ``n`` removals into the drain-rate EWMA.  Called on every
        removal path; O(1), two float ops per call outside window edges."""
        if n <= 0:
            return
        self._drain_accum += n
        now = self._scheduler.now()
        dt = now - self._drain_anchor
        if dt >= self.DRAIN_WINDOW:
            inst = self._drain_accum / dt
            self._drain_rate = inst if self._drain_rate <= 0.0 \
                else 0.5 * self._drain_rate + 0.5 * inst
            self._drain_anchor = now
            self._drain_accum = 0

    #: shorter span than DRAIN_WINDOW: the proposer's fill prediction must
    #: track offered-rate swings within a couple of batch intervals, while
    #: the drain estimate only feeds a coarse retry hint
    ARRIVAL_WINDOW = 0.25

    def _note_arrival(self) -> None:
        """Fold one admitted submit into the arrival-rate EWMA (the
        _note_drained idiom pointed at the front door)."""
        self._arrival_accum += 1
        now = self._scheduler.now()
        dt = now - self._arrival_anchor
        if dt >= self.ARRIVAL_WINDOW:
            inst = self._arrival_accum / dt
            self._arrival_rate = inst if self._arrival_rate <= 0.0 \
                else 0.5 * self._arrival_rate + 0.5 * inst
            self._arrival_anchor = now
            self._arrival_accum = 0

    def arrival_rate(self) -> float:
        """Admitted submits/sec.  While submits keep folding window edges
        this is the EWMA; once the live window overruns ARRIVAL_WINDOW
        without a fold (arrivals too sparse to trigger one) the partial
        window IS the freshest truth, so return it directly — otherwise a
        stale busy-era EWMA would keep predicting "the wave will fill,
        keep waiting" long after traffic stopped."""
        now = self._scheduler.now()
        dt = now - self._arrival_anchor
        if dt >= self.ARRIVAL_WINDOW:
            return self._arrival_accum / dt
        return self._arrival_rate

    def available_count(self) -> int:
        """Pooled requests not reserved in-flight — exactly the population
        next_requests' check-mode fast path counts."""
        return len(self._items) - len(self._in_flight)

    def retry_after_hint(self) -> float:
        """Seconds until the pool plausibly drains back below the
        admission high-water mark at the measured drain rate.  With no
        rate measured yet (cold pool, stalled consensus) the hint is the
        submit timeout — the bound a parked caller would have waited."""
        hw = self._admission_slots()
        if hw is None:
            return 0.0
        # the same system-size expression the gate rejects on — a hint
        # computed from a smaller occupancy would invite an early retry
        # that gets shed again
        excess = (len(self._items) + self._reserved_slots
                  + len(self._space_waiters) - hw + 1)
        if excess <= 0:
            return 0.0
        now = self._scheduler.now()
        rate = self._drain_rate
        # fold the (possibly newer) partial window in so the hint reacts
        # to a drain that started after the last window edge
        dt = now - self._drain_anchor
        if dt >= self.DRAIN_WINDOW and self._drain_accum:
            rate = max(rate, self._drain_accum / dt)
        if rate <= 0.0:
            return self._opts.submit_timeout
        return min(max(excess / rate, 0.001), self._opts.auto_remove_timeout)

    def pending_infos(self) -> list[RequestInfo]:
        """Every request still pooled (including in-flight reservations),
        FIFO order.  The live-reshard drain barrier reads this: a moved
        key-range has drained exactly when no pool in the old shard still
        holds one of its clients' requests — committing past the epoch
        flip on the wrong side would double-deliver."""
        return list(self._items.keys())

    def next_requests(
        self, max_count: int, max_size_bytes: int, check: bool
    ) -> tuple[list[bytes], bool]:
        """Slice up to (max_count, max_size_bytes) from the FIFO front,
        skipping in-flight reservations; ``full`` means calling again cannot
        grow the batch (requestpool.go:297-332).  The check-mode fast path
        counts only UNRESERVED items (the bytes bound stays the pool total:
        a reservation-heavy pool may then return a sub-max batch early,
        which the batcher treats like a timeout batch — harmless)."""
        available = len(self._items) - len(self._in_flight)
        if check and available < max_count and self._size_bytes < max_size_bytes:
            return [], False
        batch: list[bytes] = []
        total = 0
        # the scan walks past reserved items at the FIFO front (O(k*batch)
        # set probes per call at full window depth); a skip cursor would
        # save that but must survive out-of-order removals and releases —
        # not worth it while the probe is a dict hit per item
        for info, item in self._items.items():
            if len(batch) >= max_count:
                break
            if info in self._in_flight:
                continue
            req_len = len(item.request)
            if total + req_len > max_size_bytes:
                return batch, True
            batch.append(item.request)
            total += req_len
        full = total >= max_size_bytes or len(batch) == max_count
        return batch, full

    def mark_in_flight(self, infos) -> None:
        """Reserve proposed-but-undelivered requests: the pipelined leader
        calls this after every propose so the next window slot batches
        FRESH requests instead of re-proposing the in-flight front."""
        self._in_flight.update(infos)

    def release_in_flight(self) -> None:
        """Drop every reservation (view change / view abort): proposals
        that did not survive into a commit are proposable again; those that
        did get removed by delivery anyway."""
        self._in_flight.clear()

    def prune(self, predicate: Callable[[bytes], Optional[Exception]]) -> None:
        """Remove requests failing re-verification (requestpool.go:335-354)."""
        snapshot = [(info, item.request) for info, item in self._items.items()]
        pruned = 0
        for info, request in snapshot:
            err = predicate(request)
            if err is None:
                continue
            try:
                self.remove_request(info)
                pruned += 1
                self._log.debugf("Pruned request: %s; predicate error: %s", info, err)
            except PoolError:
                pass
        if pruned:
            self._log.debugf("Pruned %d requests", pruned)

    # ------------------------------------------------------------------ remove

    def remove_requests(self, infos) -> int:
        """Bulk removal of a delivered batch; returns the not-pooled count.

        The hot post-delivery path: every replica removes every request of
        every decision (RequestBatch x n calls per decision cluster-wide),
        and on followers most are misses — per-request PoolError raising
        alone costs real wall time at n=64 x batch=500.  Misses still pass
        through the recently-deleted dedup map, exactly like
        :meth:`remove_request`."""
        missing = 0
        removed = 0
        for info in infos:
            self._in_flight.discard(info)
            item = self._items.pop(info, None)
            if item is None:
                self._move_to_del(info)
                missing += 1
                continue
            removed += 1
            # no timer to cancel: the wheel entry goes stale with the item
            self._size_bytes -= len(item.request)
            self._move_to_del(info)
            if self._metrics:
                try:
                    # a faulty embedder-supplied metrics provider must not
                    # abort the batch mid-way: the remainder would stay
                    # pooled with live forward timers and no waiter wakeup
                    self._metrics.latency_of_requests.observe(
                        self._scheduler.now() - item.addition_time
                    )
                except Exception:
                    pass
        if removed and self._metrics:
            try:
                # same guard as the per-item observe above: removal fully
                # succeeded by now, so a faulty metrics provider must not
                # escape to the controller's catch-all and log a spurious
                # "pool removal failed" warning
                self._metrics.count_of_requests.set(len(self._items))
            except Exception:
                pass
        self._note_drained(removed)
        self._release_space()
        return missing

    def remove_request(self, info: RequestInfo) -> None:
        self._in_flight.discard(info)
        item = self._items.pop(info, None)
        if item is None:
            self._move_to_del(info)
            raise PoolError(f"request {info} is not in the pool at remove time")
        self._size_bytes -= len(item.request)
        self._move_to_del(info)
        if self._metrics:
            try:
                # same guard as remove_requests: removal already succeeded,
                # so a faulty metrics provider must not escape (prune()
                # catches only PoolError around this call)
                self._metrics.count_of_requests.set(len(self._items))
                self._metrics.latency_of_requests.observe(
                    self._scheduler.now() - item.addition_time
                )
            except Exception:
                pass
        self._note_drained(1)
        self._release_space()

    def seed_processed(self, infos) -> None:
        """Pre-arm the dedup memory with ALREADY-COMMITTED request ids
        (snapshot install / reshard handoff, ISSUE 17): a node seeded
        from a donor snapshot never saw those requests delivered, but a
        client resubmitting one must get ReqAlreadyProcessedError, not a
        second delivery.  Bounded by the same eviction as the delivery
        path."""
        for info in infos:
            self._move_to_del(info)

    def _move_to_del(self, info: RequestInfo) -> None:
        if info in self._del_map:
            return
        self._del_map[info] = None
        # bounded dedup memory (requestpool.go:418-437)
        if len(self._del_map) > 2 * DEFAULT_SIZE_OF_DEL_ELEMENTS:
            for _ in range(len(self._del_map) - DEFAULT_SIZE_OF_DEL_ELEMENTS):
                self._del_map.popitem(last=False)

    def _release_space(self) -> None:
        # wake as many parked submitters as there is capacity (the bulk
        # removal path frees hundreds of slots in one call; waking just one
        # would strand the rest until their submit_timeout).  Overwaking is
        # harmless: submit() re-checks capacity in a while loop.
        capacity = (self._opts.queue_size - len(self._items)
                    - self._reserved_slots)
        while self._space_waiters and capacity > 0:
            fut = self._space_waiters.popleft()
            if not fut.done():
                fut.set_result(None)
                self._reserved_slots += 1
                capacity -= 1

    # ------------------------------------------------------------------ timers
    #
    # Lazy timer wheel (round 18).  The reference arms one timer per
    # request per chain stage; at open-loop rates the schedule/cancel
    # pairs for timers that never fire (requests commit long before
    # their forward timeout) were a top profile line of the whole
    # cluster.  Here an armed item carries (deadline, stage, gen) and is
    # appended to a per-stage FIFO deque; ONE scheduler timer is armed
    # at the earliest outstanding deadline.  Removal just drops the item
    # — its queue entry goes stale (item gone, or gen mismatch after a
    # re-arm) and is skipped when the wheel next fires, so the commit
    # path pays a deque append on submit and nothing on removal.
    # Per-stage queues are near-monotone (uniform timeouts mean FIFO
    # order == deadline order); an adaptive forward_timeout_fn can
    # invert entries, which only DELAYS an interior entry until the
    # queue head's deadline — bounded by the derivation swing, harmless
    # for what is a liveness nudge backed by leader-side dedup.

    def _arm(self, info: RequestInfo, item: _Item, stage: int,
             delay: float) -> None:
        self._gen += 1
        item.gen = self._gen
        item.stage = stage
        item.deadline = self._scheduler.now() + delay
        self._timer_qs[stage].append((item.deadline, info, item.gen))
        if item.deadline < self._wheel_deadline:
            self._arm_wheel(item.deadline)

    def _arm_wheel(self, deadline: float) -> None:
        if self._wheel_handle is not None:
            self._wheel_handle.cancel()
        self._wheel_deadline = deadline
        self._wheel_handle = self._scheduler.schedule(
            max(deadline - self._scheduler.now(), 0.0), self._wheel_fire
        )

    def _cancel_wheel(self) -> None:
        if self._wheel_handle is not None:
            self._wheel_handle.cancel()
            self._wheel_handle = None
        self._wheel_deadline = float("inf")

    def _wheel_fire(self) -> None:
        self._wheel_handle = None
        self._wheel_deadline = float("inf")
        now = self._scheduler.now()
        for stage, q in enumerate(self._timer_qs):
            while q:
                deadline, info, gen = q[0]
                item = self._items.get(info)
                if item is None or item.gen != gen:
                    q.popleft()  # stale: removed, or re-armed elsewhere
                    continue
                if deadline > now:
                    break
                q.popleft()
                # a dispatch handler may stop/close the pool mid-fire
                # (complain -> view change); due entries behind it are
                # dropped exactly as stop_timers would have cancelled them
                if self._closed or self._stopped:
                    continue
                self._dispatch(stage, info, item)
        if self._closed or self._stopped:
            return
        # re-arm at the earliest still-armed entry (stale prefixes were
        # drained above; a dispatch may have appended fresh entries)
        nxt = float("inf")
        for q in self._timer_qs:
            while q:
                deadline, info, gen = q[0]
                item = self._items.get(info)
                if item is None or item.gen != gen:
                    q.popleft()
                    continue
                if deadline < nxt:
                    nxt = deadline
                break
        if nxt < float("inf"):
            self._arm_wheel(nxt)

    def _dispatch(self, stage: int, info: RequestInfo, item: _Item) -> None:
        """Fire one chain leg for one item — the re-arm happens BEFORE the
        handler runs, matching the reference's AfterFunc ordering."""
        request = item.request
        if stage == _STAGE_FWD:
            self._arm(info, item, _STAGE_COMPLAIN, self._opts.complain_timeout)
            if self._metrics:
                self._metrics.count_of_leader_forward_requests.add(1)
            self._th.on_request_timeout(request, info)
        elif stage == _STAGE_COMPLAIN:
            self._arm(info, item, _STAGE_AUTOREMOVE,
                      self._opts.auto_remove_timeout)
            if self._metrics:
                self._metrics.count_of_complain_timeout.add(1)
            self._th.on_leader_fwd_request_timeout(request, info)
        elif stage == _STAGE_AUTOREMOVE:
            self._on_auto_remove_to(info)
        else:  # _STAGE_FLIP: the BONUS forward of a view flip (round 15)
            # or of a rotation's hand-over.
            # Push the stalled request to the new leader immediately, then
            # re-arm the ORDINARY forward->complain chain behind it on its
            # original schedule.  The early forward is purely additive —
            # if it lands, leader-side dedup absorbs the ordinary forward
            # that follows; if it is lost on the wire or refused by a peer
            # that has not flipped to the new view yet, the unchanged
            # chain retries it instead of stranding it until the complain
            # stage.  An accelerated chain was the first design and
            # livelocked the lossy-network gate both ways: early complains
            # re-triggered view changes, and a dropped one-shot forward
            # stalled the drain.
            remaining = max(
                self._forward_timeout() - FORWARD_TIMEOUT_FLOOR, 0.0
            )
            self._arm(info, item, _STAGE_FWD, remaining)
            self._th.on_request_timeout(request, info)

    def _on_auto_remove_to(self, info: RequestInfo) -> None:
        try:
            self.remove_request(info)
        except PoolError as e:
            self._log.errorf("Removal of request %s failed; error: %s", info, e)
            return
        if self._metrics:
            self._metrics.count_of_deleted_requests.add(1)
        self._th.on_auto_remove_timeout(info)

    # ------------------------------------------------------------------ epochs

    def change_options(self, timeout_handler: RequestTimeoutHandler, options: PoolOptions) -> None:
        """Swap the timeout handler and timeouts across a reconfig
        (requestpool.go:146-180); queue size is kept."""
        options.queue_size = self._opts.queue_size
        self._opts = options
        self._th = timeout_handler
        self._log.debugf("Changed pool timeouts")

    def stop_timers(self) -> None:
        """Freeze all request timers during a view change
        (requestpool.go:456-470)."""
        self._stopped = True
        for q in self._timer_qs:
            q.clear()
        self._cancel_wheel()
        self._log.debugf("Stopped all timers: size=%d", len(self._items))

    def restart_timers(self, *, flip: bool = False,
                       handover: bool = False) -> None:
        """Restart all request timers as forward timeouts
        (requestpool.go:472-490).

        ``flip=True`` (a completed view change restarting the timers):
        the oldest ``flip_drain_limit`` requests arm at
        FORWARD_TIMEOUT_FLOOR instead — the stalled backlog reaches the
        NEW leader within a tick and its first proposals batch it into
        deep windows, instead of every pooled request waiting out a full
        forward timeout while the new view idles (round 16: propose_wait
        was 98% of forced-VC request time).  Leader-side dedup absorbs
        any duplicate this forwards; requests past the limit keep the
        ordinary chain.

        ``handover=True`` (a rotation took the lead from THIS replica):
        the same leg for the oldest ``handover_limit`` it still holds —
        what reached it after the last batch of its turn was cut would
        otherwise wait until the lead comes round again, every rotation
        re-arming the full forward timeout before it can fire.  Counted
        in ``handovers`` (and marked ``req.handover``), apart from
        ``flip_drains``.

        Either way a request reserved in flight is no leftover and keeps
        the ordinary chain."""
        self._stopped = False
        for q in self._timer_qs:
            q.clear()  # every item is re-armed fresh below
        self._cancel_wheel()
        fwd = self._forward_timeout()
        limit = (self._opts.handover_limit if handover
                 else self._opts.flip_drain_limit if flip else 0)
        rec = self._recorder if handover and self._recorder.enabled else None
        now = self._scheduler.now()
        fast = 0
        for info, item in self._items.items():
            if fast < limit and info not in self._in_flight:
                fast += 1
                self._arm(info, item, _STAGE_FLIP, FORWARD_TIMEOUT_FLOOR)
                if rec is not None:
                    # dur: how long it had been pooled when the lead went
                    rec.record("req.handover", key=str(info),
                               dur=now - item.addition_time)
            else:
                self._arm(info, item, _STAGE_FWD, fwd)
        if handover:
            self.handovers += fast
        else:
            self.flip_drains += fast
        self._log.debugf("Restarted all timers: size=%d", len(self._items))

    def _forward_timeout(self) -> float:
        """The effective forward timeout for the next timer arm: the
        RTT-derived value from ``forward_timeout_fn`` clamped into
        [FORWARD_TIMEOUT_FLOOR, configured constant]; the constant alone
        when no provider is wired, it has no measurement yet, or it
        fails (telemetry must never wedge request timers)."""
        fn = self._opts.forward_timeout_fn
        ceiling = self._opts.forward_timeout
        if fn is None:
            return ceiling
        try:
            derived = fn()
        except Exception:  # noqa: BLE001 — derivation is advisory
            return ceiling
        if derived is None or derived <= 0:
            return ceiling
        return min(max(derived, FORWARD_TIMEOUT_FLOOR), ceiling)

    def close(self) -> None:
        self._closed = True
        self._cancel_wheel()
        for q in self._timer_qs:
            q.clear()
        for info in list(self._items.keys()):
            item = self._items.pop(info)
            self._size_bytes -= len(item.request)
            self._move_to_del(info)
        for fut in self._space_waiters:
            if not fut.done():
                fut.set_exception(PoolClosedError("pool closed"))
        self._space_waiters.clear()
