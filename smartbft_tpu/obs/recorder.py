"""Bounded-memory flight recorder for request-scoped protocol tracing.

A :class:`TraceRecorder` is a bounded ring of :class:`SpanEvent` records
correlated by request key (``"client:rid"``), (view, seq), reshard epoch
and verify-launch id.  Three shapes of record:

* a **mark** (:meth:`TraceRecorder.record`) — a point in time, optionally
  carrying a duration the site measured itself;
* a **busy span** (:meth:`~TraceRecorder.begin` / :meth:`~TraceRecorder.end`)
  — synchronous work on one thread, NO ``await`` inside.  Busy spans of a
  thread nest and never interleave; the recorder keeps a per-thread stack
  and accumulates, per kind, calls and **self time** (duration minus
  children) as plain float sums.  ``end`` holds the stack discipline: a
  busy span that suspended, so that it is no longer the innermost one
  open on its thread when it ends, is refused — dropped and counted
  under ``refused`` in the account, never raised, because the recorder
  must not break what it observes.  Work that crosses an ``await`` or a
  thread is a wait;
* a **wait span** (:meth:`~TraceRecorder.wait`) — one event at the END of
  something that crossed ``await``s or threads, carrying its duration;
  never on a busy stack, never counted as busy.

**One switch: the profiler.**  Every component always holds a real,
disabled recorder; each site guards with ``if rec.enabled:`` so tracing
off costs one attribute read and a branch — no clock read, no allocation.
:func:`poll_profiler`, called once per scheduler tick by
``WallClockDriver``, reads ``jax.profiler.TraceAnnotation.is_enabled()``
and on a change flips every live recorder (weak registry).  While on by
the profiler a recorder stamps ``t`` and every duration with
``time.perf_counter()`` and writes each record into the profiler's trace
too, as a ``TraceAnnotation`` named ``tpubft.<kind>`` from the thread that
did the work — so program spans sit on the device trace's clock.  A
recorder built with ``enabled=True`` (``ShardedCluster(trace=True)``, the
socket replica's ``trace`` spec, the chaos runner) is forced on by hand,
keeps its injected clock, and is left alone by the switch.

**The account.**  At the on and off edges the switch reads
``perf_counter`` and the loop thread's CPU time (what ``thread_time``
reads, taken with its kernel share from ``getrusage``); at the off edge it
folds every switched recorder into one plain dict, :func:`last_summary`:
the interval, the loop thread's CPU, busy self time and calls by thread
and kind, counts taken at the same sites, per-decision segment lists and
wait-span lists.  Nothing here imports JAX unless the process already has.

**The loop hook.**  Between the on and the off edge, and only then,
:mod:`~smartbft_tpu.obs.loophook` wraps ``asyncio.events.Handle._run``:
every handle the loop thread runs is the outermost busy span of its
thread, of kind ``loop.program`` / ``loop.embedder`` / ``loop.callback``
by its owner, so the loop thread's account has no unnamed remainder but
what the loop does outside its handles.
"""

from __future__ import annotations

import collections
import collections.abc
import contextlib
import gc
import json
import resource
import sys
import threading
import time
import weakref
from typing import Optional, Sequence

__all__ = [
    "SpanEvent",
    "TraceRecorder",
    "PROCESS",
    "assemble_trace_block",
    "busy_steps",
    "last_summary",
    "launch_span",
    "name_this_thread",
    "note_lanes",
    "poll_profiler",
    "close_for_await",
    "set_thread_launch",
    "standby",
]

#: ring size while switched on by the profiler: no cell's traced span
#: comes near it (the busiest recorder holds ~8 k events a second), and a
#: deque only takes the room of what it holds
PROFILER_CAPACITY = 1 << 18

ANNOTATION_PREFIX = "tpubft."


class SpanEvent:
    """One structured trace event.  ``t`` is the instant it was recorded
    (a span's END); ``dur`` >= 0 marks a completed span (seconds, so it
    began at ``t - dur``), -1 a point event.  ``self_s`` >= 0 marks a BUSY
    span (its duration minus its children's) taken on thread ``thread``.
    Unset correlators stay at their sentinel (-1 / "") and are omitted
    from the dict form.  ``seqno`` is the recorder-assigned all-time event
    sequence (1-based) — the incremental-pull cursor compares against it
    EXACTLY, so a snapshot racing a concurrent record (the WAL executor
    thread) can never skip or double-ship an event."""

    __slots__ = ("t", "kind", "node", "key", "view", "seq", "epoch",
                 "launch", "dur", "extra", "seqno", "self_s", "thread")

    def __init__(self, t: float, kind: str, node: str = "", key: str = "",
                 view: int = -1, seq: int = -1, epoch: int = -1,
                 launch: int = -1, dur: float = -1.0,
                 extra: Optional[dict] = None, self_s: float = -1.0,
                 thread: str = ""):
        self.t = t
        self.kind = kind
        self.node = node
        self.key = key
        self.view = view
        self.seq = seq
        self.epoch = epoch
        self.launch = launch
        self.dur = dur
        self.extra = extra
        self.seqno = 0
        self.self_s = self_s
        self.thread = thread

    def as_dict(self) -> dict:
        out = {"t": round(self.t, 6), "kind": self.kind}
        if self.node:
            out["node"] = self.node
        if self.key:
            out["key"] = self.key
        if self.view >= 0:
            out["view"] = self.view
        if self.seq >= 0:
            out["seq"] = self.seq
        if self.epoch >= 0:
            out["epoch"] = self.epoch
        if self.launch >= 0:
            out["launch"] = self.launch
        if self.dur >= 0:
            out["dur_ms"] = round(self.dur * 1e3, 3)
        if self.self_s >= 0:
            out["self_ms"] = round(self.self_s * 1e3, 3)
            out["thread"] = self.thread
        if self.extra:
            out["extra"] = self.extra
        return out


# -- per-thread busy stacks ----------------------------------------------------


class _Open:
    """One open busy span (the token ``begin`` returns)."""

    __slots__ = ("rec", "kind", "t0", "child", "cpu0", "ann", "view",
                 "seq", "launch", "key", "extra", "closed")

    def __init__(self, rec, kind, view, seq, launch, key, extra):
        self.rec = rec
        self.kind = kind
        self.view, self.seq, self.launch = view, seq, launch
        self.key, self.extra = key, extra
        self.child = 0.0
        self.closed = False


class _ThreadState:
    """A thread's open busy spans and the verify launch it is serving
    (the identifier the spans of one launch share, and the tags of the
    submitters whose items the launch carries)."""

    __slots__ = ("ident", "name", "stack", "launch", "tags", "named", "gc",
                 "outer")

    def __init__(self):
        self.ident = threading.get_ident()
        #: the thread's Python name, taken at its first span: a state may
        #: be born inside a garbage collection that interrupts the
        #: thread's own bootstrap, where asking for the current thread
        #: would register a dummy
        self.name = None
        self.stack: list = []
        self.launch = -1
        self.tags: Sequence = ()
        self.named = False
        self.gc = None  # (start, annotation) while a collection runs
        #: the loop hook's open handle (``loophook``): what a span with no
        #: parent on the stack adds its time to, None outside a handle
        self.outer = None


_tls = threading.local()
#: thread name -> kind -> [calls, self seconds, seconds, thread-CPU
#: seconds]: the running sums of every busy span ended since the last on
#: edge.  Keyed by name, not by thread: a verify launch may run on a
#: thread of its own that is gone before the account is read.
_accounts: dict = {}
#: kind -> busy spans refused because they did not end innermost-first
_refused: dict = {}
_accounts_lock = threading.Lock()


#: libc's ``prctl``, looked up once (False: not to be had here).  A
#: launch may run on a thread of its own, so naming one must cost
#: microseconds: loading the library anew each time cost d4 1.6 ms a
#: decision on the chip's host
_prctl = None


def _name_os_thread(name: str) -> None:
    """Give the calling (non-main) thread its Python name at the OS, so
    the profiler's trace shows executor threads on lines of their own
    (a thread's line is named when its first annotation is seen)."""
    global _prctl
    if _prctl is False \
            or threading.get_ident() == threading.main_thread().ident:
        return
    try:
        if _prctl is None:
            import ctypes

            _prctl = ctypes.CDLL(None).prctl
        _prctl(15, name.encode()[:15], 0, 0, 0)  # PR_SET_NAME
    except Exception:  # noqa: BLE001 — a label in a trace, nothing more
        _prctl = False


def _state() -> _ThreadState:
    st = getattr(_tls, "st", None)
    if st is None:
        st = _tls.st = _ThreadState()
    return st


def _name_of(st: _ThreadState) -> str:
    """The state's thread's name; from the thread itself, or (for a
    thread that only ever ran a collection) looked up by its ident."""
    if st.name is None:
        if st.ident == threading.get_ident():
            st.name = threading.current_thread().name
        else:
            st.name = next((t.name for t in threading.enumerate()
                            if t.ident == st.ident), f"thread-{st.ident}")
    return st.name


def close_for_await() -> None:
    """The calling coroutine is about to suspend: end every busy span open
    on this thread here, so none of them counts the wait.  Their owners'
    later ``end`` is then a no-op."""
    stack = _state().stack
    while stack:
        stack[-1].rec.end(stack[-1])


def set_thread_launch(launch: int, tags: Sequence = ()) -> None:
    """Name the verify launch the calling thread is about to serve; the
    engine's ``verify.pack`` / ``verify.device`` spans carry it.
    ``tags``: the submitters (channels) whose items ride it, carried by
    its ``verify.lanes`` marks."""
    st = _state()
    st.launch = launch
    st.tags = tags


def name_this_thread() -> None:
    """Give the calling thread its Python name at the OS, once.  The
    launch's thread does so before every engine call, recorder on or off:
    JAX writes its own events from that thread as soon as a profiler
    session starts, a tick before the recorders come on, and a line keeps
    the name it was first seen under."""
    st = _state()
    if not st.named:
        st.named = True
        _name_os_thread(_name_of(st))


class TraceRecorder:
    """Ring of :class:`SpanEvent`; see the module docstring."""

    def __init__(self, *, clock=None, node: str = "", capacity: int = 4096,
                 kinds_cap: int = 64, enabled: bool = True):
        #: the one attribute every site reads
        self.enabled = bool(enabled)
        #: forced on by hand: the profiler switch leaves it alone
        self.forced = self.enabled
        self._clock = clock if clock is not None else time.monotonic
        self._annotate = None
        self.node = node
        self.capacity = max(int(capacity), 1)
        self.kinds_cap = max(int(kinds_cap), 1)
        self._buf: collections.deque = collections.deque(maxlen=self.capacity)
        self.recorded = 0
        # recorders are fed from the event loop AND executor threads (the
        # WAL group-commit fsync spans, the verify launches): the seqno
        # update is a read-modify-write, so it takes a lock — uncontended
        # acquire is ~100 ns next to the event construction it guards
        self._write_lock = threading.Lock()
        #: all-time per-kind event counts (bounded by ``kinds_cap``;
        #: overflow folds into ``"_other"``)
        self.kind_counts: dict[str, int] = {}
        with _registry_lock:
            _registry.add(self)
        if _switch.on and not self.forced:
            self._arm(_switch.annotate)

    # -- the profiler switch ---------------------------------------------------

    def _arm(self, annotate) -> None:
        """On by the profiler: perf_counter stamps, annotations, a ring
        that drops nothing over a traced span."""
        with self._write_lock:
            self._clock = time.perf_counter
            self._annotate = annotate
            self.capacity = PROFILER_CAPACITY
            self._buf = collections.deque(maxlen=self.capacity)
            self.recorded = 0
            self.kind_counts = {}
        self.enabled = True

    def _disarm(self) -> None:
        self.enabled = False
        self._annotate = None

    def now(self) -> float:
        """The recorder's clock — what a site stamps a wait's start with."""
        return self._clock()

    @property
    def dropped(self) -> int:
        """Events overwritten by the ring bound (recorded beyond cap)."""
        return max(0, self.recorded - self.capacity)

    # -- recording ---------------------------------------------------------------

    def _store(self, ev: SpanEvent) -> SpanEvent:
        with self._write_lock:
            self.recorded += 1
            ev.seqno = self.recorded
            self._buf.append(ev)
            counts = self.kind_counts
            kind = ev.kind
            if kind not in counts and len(counts) >= self.kinds_cap:
                kind = "_other"
            counts[kind] = counts.get(kind, 0) + 1
        return ev

    def record(self, kind: str, *, node: str = "", key: str = "",
               view: int = -1, seq: int = -1, epoch: int = -1,
               launch: int = -1, dur: float = -1.0,
               extra: Optional[dict] = None,
               t: Optional[float] = None) -> SpanEvent:
        """A mark.  ``t`` overrides the event timestamp (SAME clock domain
        as the recorder's): for marks whose true instant precedes the
        record call — the transport stamps ``net.recv`` with the socket
        READ time so per-hop network time excludes the consensus
        processing awaited between read and record."""
        ann = self._annotate
        if ann is not None:
            with ann(ANNOTATION_PREFIX + kind,
                     **_metadata(view, seq, launch)):
                pass
        return self._store(SpanEvent(
            t if t is not None else self._clock(), kind, node or self.node,
            key, view, seq, epoch, launch, dur, extra))

    def wait(self, kind: str, started: Optional[float],
             **kw) -> Optional[SpanEvent]:
        """A wait span, recorded at its end: ``started`` is what
        :meth:`now` read when it began (None: the recorder was off then,
        and nothing is recorded)."""
        if started is None:
            return None
        return self.record(kind, dur=max(self._clock() - started, 0.0), **kw)

    def begin(self, kind: str, *, view: int = -1, seq: int = -1,
              launch: int = -1, key: str = "", extra: Optional[dict] = None,
              cpu: bool = False) -> _Open:
        """Open a busy span on the calling thread.  ``cpu``: also read the
        thread's CPU clock at both ends (for the handful of spans whose
        wall time may be a wait for the interpreter lock)."""
        sp = _Open(self, kind, view, seq, launch, key, extra)
        st = _state()
        if st.name is None:
            _name_of(st)
        ann = self._annotate
        if ann is not None:
            if not st.named:
                name_this_thread()
            ann = ann(ANNOTATION_PREFIX + kind,
                      **_metadata(view, seq, launch))
            ann.__enter__()
        sp.ann = ann
        st.stack.append(sp)
        sp.cpu0 = time.thread_time() if cpu else -1.0
        sp.t0 = self._clock()
        return sp

    def end(self, sp: _Open) -> Optional[SpanEvent]:
        """Close a busy span: it must be the innermost one open on this
        thread."""
        if sp.closed:  # close_for_await() got here first
            return None
        t1 = self._clock()
        cpu = time.thread_time() - sp.cpu0 if sp.cpu0 >= 0.0 else 0.0
        st = _state()
        stack = st.stack
        sp.closed = True
        if not stack or stack[-1] is not sp:
            # not the innermost span open on this thread: it contained an
            # await (or changed threads) and others ran beneath it, so
            # its time is no busy time.  Refused and counted, never
            # raised: the recorder must not break what it observes.
            if sp in stack:
                del stack[stack.index(sp):]
            if sp.ann is not None:
                sp.ann.__exit__(None, None, None)
            with _accounts_lock:
                _refused[sp.kind] = _refused.get(sp.kind, 0) + 1
            return None
        stack.pop()
        dur = t1 - sp.t0
        self_s = dur - sp.child
        parent = stack[-1] if stack else st.outer
        if parent is not None:
            parent.child += dur
        with _accounts_lock:
            acc = _accounts.setdefault(st.name, {}).get(sp.kind)
            if acc is None:
                _accounts[st.name][sp.kind] = [1, self_s, dur, cpu]
            else:
                acc[0] += 1
                acc[1] += self_s
                acc[2] += dur
                acc[3] += cpu
        if sp.ann is not None:
            sp.ann.__exit__(None, None, None)
        extra = sp.extra
        if sp.cpu0 >= 0.0:
            extra = dict(extra or (), cpu_ms=round(cpu * 1e3, 3))
        return self._store(SpanEvent(
            t1, sp.kind, self.node, sp.key, sp.view, sp.seq, -1, sp.launch,
            dur, extra, self_s, st.name))

    # -- reading -----------------------------------------------------------

    def events(self, last: Optional[int] = None) -> list:
        """The buffered events in chronological (record) order, optionally
        only the newest ``last``.  Takes the write lock, so a concurrent
        record() can neither tear the copy nor reorder it; reads are
        control-channel-rate, so the lock never contends the hot path."""
        with self._write_lock:
            out = list(self._buf)
        if last is not None and last >= 0:
            out = out[-last:] if last else []
        return out

    def snapshot(self, last: Optional[int] = None) -> list[dict]:
        return [e.as_dict() for e in self.events(last)]

    def events_since(self, since: int) -> tuple[list, int]:
        """Incremental read for repeated pulls: the buffered events
        recorded AFTER cursor ``since``, plus the next cursor.

        The cursor is an event's all-time ``seqno`` (0 means "from the
        beginning"); the filter compares EXACTLY against each buffered
        event's own sequence number, so a snapshot racing a concurrent
        ``record`` can never skip or double-ship: an event that missed
        this snapshot keeps a seqno above the returned cursor and ships
        next pull.  Events the ring already overwrote are gone — a
        puller more than ``capacity`` events behind gets only the
        surviving tail (the gap is visible as ``dropped`` growth) — and
        a cursor from the future (stale after a recorder restart) stays
        at "nothing new".  This is what keeps ``cmd=trace`` pulls O(new
        events) instead of re-shipping the whole ring every poll."""
        since = max(0, int(since))
        out = [e for e in self.events() if e.seqno > since]
        return out, (out[-1].seqno if out else since)

    def snapshot_since(self, since: int) -> tuple[list[dict], int]:
        events, cursor = self.events_since(since)
        return [e.as_dict() for e in events], cursor

    def trace_block(self) -> dict:
        """The JSON-able ``trace`` summary block (bench rows, cmd=trace)."""
        if not self.enabled and not self.recorded:
            return {"enabled": False}
        return {
            "enabled": self.enabled,
            "node": self.node,
            "capacity": self.capacity,
            "recorded": self.recorded,
            "dropped": self.dropped,
            "kinds": dict(sorted(self.kind_counts.items())),
            "spans": span_stats(self.events()),
        }

    def dump(self) -> dict:
        """The full JSON-able dump (events + summary) the chaos runner
        writes per replica and ``python -m smartbft_tpu.obs.report``
        renders."""
        return {
            "node": self.node,
            "capacity": self.capacity,
            "recorded": self.recorded,
            "dropped": self.dropped,
            "events": self.snapshot(),
        }

    def dump_to(self, path: str) -> str:
        with open(path, "w") as fh:
            json.dump(self.dump(), fh)
        return path


def standby(recorder: Optional[TraceRecorder] = None,
            node: str = "") -> TraceRecorder:
    """``recorder``, or a disabled one of the component's own: what a
    component holds when its embedder wired none — real, so the profiler
    switch can turn it on."""
    if recorder is not None:
        return recorder
    return TraceRecorder(node=node, enabled=False)


def _metadata(view: int, seq: int, launch: int) -> dict:
    md = {}
    if view >= 0:
        md["view"] = view
    if seq >= 0:
        md["seq"] = seq
    if launch >= 0:
        md["launch"] = launch
    return md


def pct(sorted_vals: Sequence[float], q: float) -> float:
    """The q-quantile (0..1) of an ALREADY-SORTED value list by index —
    the one exact-percentile helper the obs modules share (vcphases'
    pooled VC records, report's span summaries, the trace block)."""
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(len(sorted_vals) - 1, int(q * len(sorted_vals)))]


def span_stats(events: Sequence[SpanEvent]) -> dict:
    """kind -> count, sum and exact quantiles (ms) of the durations the
    given events carry."""
    by_kind: dict[str, list] = {}
    for e in events:
        if e.dur >= 0.0:
            by_kind.setdefault(e.kind, []).append(e.dur * 1e3)
    out = {}
    for kind in sorted(by_kind):
        vals = sorted(by_kind[kind])
        out[kind] = {
            "count": len(vals),
            "sum_ms": round(sum(vals), 3),
            "p50_ms": round(pct(vals, 0.50), 3),
            "p95_ms": round(pct(vals, 0.95), 3),
            "p99_ms": round(pct(vals, 0.99), 3),
            "max_ms": round(vals[-1], 3),
        }
    return out


def assemble_trace_block(recorders: Sequence) -> dict:
    """Fold N recorders (one per replica + shared-plane recorders) into
    the ONE ``trace`` block a bench row carries.  Pure function — the
    PR 8 ``assemble_*`` idiom, schema-pinned by tests/test_obs.py.  The
    per-kind duration quantiles are exact, over what the rings hold."""
    live = [r for r in recorders if r.enabled or r.recorded]
    kinds: dict[str, int] = {}
    for r in live:
        for k, n in r.kind_counts.items():
            kinds[k] = kinds.get(k, 0) + n
    return {
        "enabled": bool(live),
        "recorders": len(live),
        "recorded": sum(r.recorded for r in live),
        "dropped": sum(r.dropped for r in live),
        "kinds": dict(sorted(kinds.items())),
        "spans": span_stats([e for r in live for e in r.events()]),
    }


# -- the process-wide switch -----------------------------------------------------

_registry: "weakref.WeakSet[TraceRecorder]" = weakref.WeakSet()
_registry_lock = threading.Lock()


class _Switch:
    on = False
    annotate = None
    is_enabled = None
    #: perf_counter, and the loop thread's (CPU, kernel CPU), at the on
    #: edge and at the last tick that still saw the profiler on
    t_on = t_last = 0.0
    cpu_on = cpu_last = (0.0, 0.0)
    ticks = 0
    loop_thread = ""
    #: the loop hook while on (``loophook.LoopHook``); None where the
    #: running loop could not be hooked
    hook = None
    summary: Optional[dict] = None


_switch = _Switch()

#: the recorder of sites that belong to no replica: the codec, the
#: in-process network's fan-out, the scheduler's timer wheel, the verify
#: engine's pack and device spans
PROCESS = TraceRecorder(node="proc", enabled=False)


class busy_steps(collections.abc.Coroutine):
    """``coro`` as a task would drive it, each step — from a resume to
    the next suspension, synchronous by construction — a busy span of
    ``kind`` in ``rec``.  What a long-lived task (a view's run loop, a
    node's inbox drain) spends between its awaits is named this way
    without a site at every await; spans opened inside a step nest under
    it.  Only while the profiler has the recorder on: the steps exist to
    make the loop thread's account whole, and a recorder forced on by hand
    keeps its ring for the protocol's own marks.  Off: one attribute read
    per step."""

    __slots__ = ("_coro", "_rec", "_kind")

    def __init__(self, coro, rec: TraceRecorder, kind: str):
        self._coro = coro
        self._rec = rec
        self._kind = kind

    def send(self, value):
        rec = self._rec
        if rec._annotate is None:
            return self._coro.send(value)
        span = rec.begin(self._kind)
        try:
            return self._coro.send(value)
        finally:
            rec.end(span)

    def throw(self, *exc):
        rec = self._rec
        if rec._annotate is None:
            return self._coro.throw(*exc)
        span = rec.begin(self._kind)
        try:
            return self._coro.throw(*exc)
        finally:
            rec.end(span)

    def close(self):
        return self._coro.close()

    def __await__(self):
        return self

    def __iter__(self):
        return self

    def __next__(self):
        return self.send(None)


class _LaunchSpan:
    __slots__ = ("kind", "extra", "span")

    def __init__(self, kind: str, extra: Optional[dict]):
        self.kind = kind
        self.extra = extra

    def __enter__(self):
        self.span = PROCESS.begin(self.kind, launch=_state().launch,
                                  extra=self.extra, cpu=True)

    def __exit__(self, *exc):
        PROCESS.end(self.span)


_NO_SPAN = contextlib.nullcontext()


def launch_span(kind: str, **extra):
    """``with launch_span("verify.pack"):`` — a busy span of the verify
    engine on the thread that runs the launch, carrying that thread's
    launch id (:func:`set_thread_launch`) and its CPU time, so wall minus
    CPU says how long the launch stood blocked; ``extra``: the span's own
    fields (``verify.prep``'s ``lanes``).  Off: a shared no-op, nothing
    allocated."""
    return _LaunchSpan(kind, extra or None) if PROCESS.enabled \
        else _NO_SPAN


def note_lanes(kernel: str, lanes: int, used: int,
               per_device: Optional[Sequence[int]] = None,
               refused: Optional[dict] = None) -> None:
    """A verify launch's lanes (padding included) and the lanes used, by
    the kernel that served it: a mark on the launch's thread, folded into
    the account's ``lanes`` block.  ``per_device``: a mesh launch's used
    lanes on each device (its lanes are split evenly), folded into the
    account's ``mesh`` block too.  ``refused``: of the used lanes, those
    the host refused before the device, by cause (``VerifyStats.
    host_refused``), folded into the kernel's ``lanes`` entry.  The mark
    carries the tags of the launch's submitters (:func:`set_thread_launch`),
    folded into the account's ``channels`` block.  Off: one attribute
    read."""
    if PROCESS.enabled:
        st = _state()
        extra = {"kernel": kernel, "lanes": lanes, "used": used}
        if per_device is not None:
            extra["per_device"] = list(per_device)
        if refused is not None:
            extra["refused"] = dict(refused)
        if st.tags:
            extra["tags"] = list(st.tags)
        PROCESS.record("verify.lanes", launch=st.launch, extra=extra)


def _live_recorders() -> list:
    with _registry_lock:
        return list(_registry)


def poll_profiler() -> None:
    """Follow the profiler session: once per scheduler tick, on the loop
    thread.  Off and staying off costs a dict lookup until the process
    has imported JAX, one ~70 ns call after."""
    sw = _switch
    probe = sw.is_enabled
    if probe is None:
        if "jax" not in sys.modules:
            return
        from jax.profiler import TraceAnnotation

        sw.annotate = TraceAnnotation
        probe = sw.is_enabled = TraceAnnotation.is_enabled
    on = probe()
    if on:
        if not sw.on:
            _switch_on()
        sw.t_last, sw.cpu_last = time.perf_counter(), _thread_cpu()
        sw.ticks += 1
        if sw.hook is not None:
            sw.hook.tick()
    elif sw.on:
        _switch_off()


#: (thread state, end instant, seconds, generation) per collection while on
_gc_log: list = []


def _gc_span(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook, installed only while the profiler is on: a
    collection is busy time of kind ``gc`` on whatever thread triggered
    it, taken out of the self time of the span it interrupted.  A full
    collection of a 64-replica heap takes hundreds of milliseconds.  It
    runs between any two bytecodes, so it takes no lock and writes no
    ring: a list append, folded into the account at the off edge."""
    st = _state()
    if phase == "start":
        ann = _switch.annotate(ANNOTATION_PREFIX + "gc",
                               gen=info["generation"])
        ann.__enter__()
        st.gc = (time.perf_counter(), ann)
    elif st.gc is not None:
        (t0, ann), st.gc = st.gc, None
        t1 = time.perf_counter()
        ann.__exit__(None, None, None)
        parent = st.stack[-1] if st.stack else st.outer
        if parent is not None:
            parent.child += t1 - t0
        _gc_log.append((st, t1, t1 - t0, info["generation"]))


def _thread_cpu() -> tuple:
    """(CPU seconds, the part of them in the kernel) of the calling
    thread — what ``time.thread_time()`` reads, with its split, in one
    system call (microseconds each where system calls are emulated)."""
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    return ru.ru_utime + ru.ru_stime, ru.ru_stime


def _switch_on() -> None:
    sw = _switch
    with _accounts_lock:
        _accounts.clear()
        _refused.clear()
    del _gc_log[:]
    sw.on = True
    sw.ticks = 0
    sw.loop_thread = threading.current_thread().name
    for rec in _live_recorders():
        if not rec.forced:
            rec._arm(sw.annotate)
    gc.callbacks.append(_gc_span)
    from .loophook import install

    sw.hook = install(sw.annotate)
    sw.cpu_on = _thread_cpu()
    sw.t_on = time.perf_counter()


def _switch_off() -> None:
    sw = _switch
    sw.on = False
    # first, whatever the fold below does: the loop is left as found
    hook, sw.hook = sw.hook, None
    if hook is not None:
        hook.remove()
    if _gc_span in gc.callbacks:
        gc.callbacks.remove(_gc_span)
    recorders = [r for r in _live_recorders()
                 if not r.forced and r._annotate is not None]
    for rec in recorders:
        rec._disarm()
    with _accounts_lock:
        busy = {name: {k: list(v) for k, v in kinds.items()}
                for name, kinds in _accounts.items()}
        refused = dict(_refused)
    from .account import assemble_account

    sw.summary = assemble_account(
        recorders, busy, t0=sw.t_on, t1=sw.t_last,
        loop_cpu_s=sw.cpu_last[0] - sw.cpu_on[0],
        loop_sys_s=sw.cpu_last[1] - sw.cpu_on[1],
        loop_thread=sw.loop_thread, ticks=sw.ticks,
        refused=refused,
        loop_steps=hook.block() if hook is not None else None,
        collections=[(_name_of(st), t, dur, gen)
                     for st, t, dur, gen in list(_gc_log)],
        frozen=gc.get_freeze_count(), thresholds=gc.get_threshold())


def last_summary() -> Optional[dict]:
    """The account of the last interval the profiler was on (a plain
    dict, see :mod:`smartbft_tpu.obs.account`), or None before one
    ended."""
    return _switch.summary
