"""The loop hook: every handle the event loop runs, accounted by owner.

The program's busy spans name what the PROGRAM does on the loop thread.
What runs between them — a step of the embedder's own task, a future's
done-callback, a hand-back from the launch's thread, a timer — had no
name, and in the cells with a signed-envelope front door it was half of
the loop's CPU.  While the profiler has the recorders on, and only then,
``asyncio.events.Handle._run`` is wrapped (``TimerHandle`` inherits it):
each handle run on the loop thread is one **sums-only busy span**.  It is
the outermost span of its thread (``_ThreadState.outer``), so the
program's spans opened inside it subtract as children do, and at its end
it adds calls, self time and wall to its owner's running sums; it writes
no ring event.  Its kind is by OWNER,
read off the handle:

``loop.program``   a step of a task whose coroutine is defined under
                   ``smartbft_tpu`` and that has no ``busy=`` kind (a task
                   that has one keeps it: the step's self time outside its
                   ``busy_steps`` span is added to that kind);
``loop.embedder``  a step of any other task (the benchmark's generator,
                   an application's own tasks);
``loop.callback``  no task step: done-callbacks, ``call_soon_threadsafe``
                   hand-backs, timer handles.

Beside the sums it keeps a table owner -> ``[calls, self_s]`` (a task's
coroutine ``__qualname__``, a callback's) and the loop's busy intervals
(flat ``t0, t1, ...`` on ``perf_counter``; handles less than
:data:`MERGE_GAP_S` apart are one interval).

The loop runs its handles in **runs**: back to back, from the first handle
after a ``select`` to the handle after which the ready queue holds nothing
to run at once.  A run is what is written into the profiler's trace, as ONE
``tpubft.loop.busy`` annotation, so the loop thread's line there has no
holes; and the thread's CPU clock is read at a run's two ends, not at a
handle's (``time.thread_time()`` is a system call, 6 us where system calls
are emulated, and a saturated loop runs tens of handles a run).  Wall minus
CPU over the runs is how long the loop stood inside a handle and off the
CPU: behind the interpreter lock, or without its core.

Only what ended before the switch's LAST tick that saw the profiler on
counts: the account's interval ends there, and ``stop_trace`` holds the
loop for most of a second inside a handle that must not.  So a handle
adds to its owner's sums "since the last tick", which every tick moves
into the sums proper and the off edge drops.

Where the running loop does not run its handles through
``asyncio.events.Handle`` (another implementation), :func:`install`
declines and the account says ``loop_steps.covered: false``.
"""

from __future__ import annotations

import asyncio
import os
import threading
import time
from asyncio import events
from typing import Optional

from . import recorder as _r

__all__ = ["LOOP_KINDS", "MERGE_GAP_S", "LoopHook", "install"]

LOOP_KINDS = ("loop.program", "loop.embedder", "loop.callback")
#: consecutive handles closer than this are one busy interval
MERGE_GAP_S = 50e-6
_BUSY_NAME = _r.ANNOTATION_PREFIX + "loop.busy"
#: a coroutine whose code lives under this directory is the program's
_PROGRAM_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__))) \
    + os.sep


class _Outer:
    """What the spans inside the running handle add their time to."""

    __slots__ = ("child",)

    def __init__(self):
        self.child = 0.0


_CTask = asyncio.Task
_busy_steps = _r.busy_steps

#: an owner's sums since the last tick, and "not in ``fresh``"
_FRESH = (0, 0.0, 0.0, False)


def _sums(kind: str, name: str) -> list:
    return [kind, name, 0, 0.0, 0.0, *_FRESH]


class LoopHook:
    """The wrapper's state between :func:`install` and :meth:`remove`."""

    def __init__(self, state, clock, cpu_clock):
        self.clock, self.cpu_clock = clock, cpu_clock
        self.state = state  # the loop thread's ``_ThreadState``
        self.original = events.Handle.__dict__["_run"]
        self.installed = False
        #: an owner's sums, by its code object's id or callback name: ``[kind,
        #: name, calls, self_s, wall_s]`` up to the last tick, then the same
        #: three since it, then whether it is in ``fresh``
        self.owners: dict = {}
        #: the owners whose handles ended since the last tick
        self.fresh: list = []
        #: flat ``t0, t1, ...``; the first pair is a sentinel, never read
        self.intervals: list = [-1.0, -1.0]
        #: the run of back-to-back handles under way, in a list the wrapper
        #: shares: its ``tpubft.loop.busy`` annotation (None: no run), the
        #: wall and the CPU clock at its start
        self.run: list = [None, 0.0, 0.0]
        #: wall seconds, thread-CPU seconds and count of the runs: up to
        #: the last tick, and of those ended since
        self.runs: list = [0.0, 0.0, 0]
        self.since: list = [0.0, 0.0, 0]

    def owner(self, handle) -> list:
        """The sums of the handle's owner."""
        cb = handle._callback
        task = getattr(cb, "__self__", None)
        if type(task) is _CTask:
            coro = task.get_coro()
        else:  # a task of another class, or no task at all
            get_coro = getattr(task, "get_coro", None)
            if get_coro is None:
                name = getattr(cb, "__qualname__", None) or type(cb).__name__
                acc = self.owners.get(name)
                if acc is None:
                    acc = self.owners[name] = _sums("loop.callback", name)
                return acc
            coro = get_coro()
        kind = None
        if type(coro) is _busy_steps:
            kind, coro = coro._kind, coro._coro
        code = getattr(coro, "cr_code", None) or getattr(coro, "gi_code", None)
        # by identity: a code object's hash reads all of it, every time
        acc = self.owners.get(id(code))
        if acc is None:
            if code is None:
                kind, name = kind or "loop.embedder", type(coro).__name__
            else:
                name = code.co_qualname
                if kind is None:
                    kind = ("loop.program"
                            if code.co_filename.startswith(_PROGRAM_DIR)
                            else "loop.embedder")
            acc = self.owners[id(code)] = _sums(kind, name)
            acc.append(code)  # kept alive, so that its id stays its own
        return acc

    def tick(self) -> None:
        """A tick saw the profiler on: what ended so far is inside the
        account's interval, and so is the run under way up to here."""
        for acc in self.fresh:
            acc[2] += acc[5]
            acc[3] += acc[6]
            acc[4] += acc[7]
            acc[5:9] = _FRESH
        self.fresh.clear()
        run, since = self.run, self.since
        if run[0] is not None:  # called from a handle: cut its run here
            cpu, now = self.cpu_clock(), self.clock()
            since[0] += now - run[1]
            since[1] += cpu - run[2]
            run[1], run[2] = now, cpu
        for i, v in enumerate(since):
            self.runs[i] += v
        since[:] = 0.0, 0.0, 0

    def remove(self) -> None:
        """Leave ``Handle._run`` exactly as found."""
        if self.installed:
            self.installed = False
            events.Handle._run = self.original
        self.state.outer = None
        ann, self.run[0] = self.run[0], None
        if ann is not None:  # the run the off edge's handle is in
            ann.__exit__(None, None, None)

    def block(self) -> dict:
        """What :func:`~smartbft_tpu.obs.account.assemble_account` takes
        as ``loop_steps``: the sums up to the last tick.  ``kinds``: kind
        -> ``[calls, self_s, dur_s, cpu_s]`` (no CPU: it is read a run, not
        a handle), what the hook adds to the loop thread's busy sums (a
        ``busy=`` task's kind gets the step's self time only: its span
        counted the call and the duration); ``wall_s``: inside handles;
        ``runs_wall_s`` / ``cpu_s`` / ``runs``: of the runs."""
        owners: dict = {}
        kinds: dict = {}
        turns, wall_s = 0, 0.0
        for kind, name, calls, self_s, dur, *_ in self.owners.values():
            if not calls:
                continue
            seen = owners.setdefault((kind, name), [0, 0.0])
            seen[0] += calls
            seen[1] += self_s
            turns += calls
            wall_s += dur
            add = ([calls, self_s, dur] if kind in LOOP_KINDS
                   else [0, self_s, 0.0])
            acc = kinds.setdefault(kind, [0, 0.0, 0.0, 0.0])
            for i, v in enumerate(add):
                acc[i] += v
        return {"turns": turns, "wall_s": wall_s,
                "runs_wall_s": self.runs[0], "cpu_s": self.runs[1],
                "runs": self.runs[2], "owners": owners, "kinds": kinds,
                "intervals": self.intervals[2:]}


def install(annotate, *, clock=time.perf_counter,
            cpu_clock=time.thread_time) -> Optional[LoopHook]:
    """Wrap ``Handle._run`` for the calling thread's running loop; None
    where there is no such loop or it runs its handles another way.
    ``clock`` / ``cpu_clock``: the wall and the thread-CPU clock (tests
    inject a pair)."""
    try:
        loop = asyncio.get_running_loop()
    except RuntimeError:
        return None
    ready = getattr(loop, "_ready", None)
    if not isinstance(loop, asyncio.BaseEventLoop) or ready is None:
        return None
    state = _r._state()
    _r._name_of(state)
    hook = LoopHook(state, clock, cpu_clock)
    original, owner = hook.original, hook.owner
    fresh, iv, run, since = hook.fresh, hook.intervals, hook.run, hook.since
    ident = state.ident
    get_ident = threading.get_ident
    outer = _Outer()

    def _run(handle):
        if get_ident() != ident:  # another thread's loop
            return original(handle)
        acc = owner(handle)
        outer.child = 0.0
        state.outer = outer
        if run[0] is None:  # a run starts: the CPU clock outside the wall's
            run[0] = ann = annotate(_BUSY_NAME)
            ann.__enter__()
            run[2] = cpu_clock()
            t0 = run[1] = clock()
        else:
            t0 = clock()
        try:
            return original(handle)
        finally:
            t1 = clock()
            # the run ends here unless the loop's next handle is waiting to
            # run at once
            if (not ready or ready[0]._cancelled) and run[0] is not None:
                since[0] += t1 - run[1]
                since[1] += cpu_clock() - run[2]
                since[2] += 1
                run[0].__exit__(None, None, None)
                run[0] = None
            state.outer = None
            if hook.installed:  # not the handle that switched it off
                dur = t1 - t0
                acc[5] += 1
                acc[6] += dur - outer.child
                acc[7] += dur
                if not acc[8]:
                    acc[8] = True
                    fresh.append(acc)
                if t0 - iv[-1] < MERGE_GAP_S:
                    iv[-1] = t1
                else:
                    iv.append(t0)
                    iv.append(t1)

    events.Handle._run = _run
    hook.installed = True
    return hook
