"""Group-commit fsync scheduling for write-ahead logs.

The reference fsyncs inline on every append (writeaheadlog.go:469-472): two
fsyncs per decision per replica, each blocking the caller.  In this
framework every replica's WAL appends are issued from asyncio tasks that
share one event loop (and, in the in-process cluster shape, one host), so
an inline fsync stalls *every* component — n replicas x 2 fsyncs of dead
time per decision.

Group commit splits the append in two:

* the frame WRITE happens synchronously inside ``append_async`` (record
  order = call order, CRC chain intact), and
* the FSYNC is batched: dirty WALs register with the per-event-loop
  :class:`GroupCommitScheduler`, whose drain task fsyncs all of them in
  parallel on the executor and resolves the callers' durability futures.

While one wave's fsyncs run, new appends accumulate into the next wave —
classic group commit, here across all WALs in the process.  Protocol
safety is unchanged: the View awaits durability *before* broadcasting the
dependent message (the WAL-first rule of view.go:404-414,500-509); only
the event loop is no longer held hostage while the disk catches up.

No artificial delay is ever added: a wave flushes as soon as the drain
task gets the loop, so deterministic logical-clock tests see no timing
side effects.
"""

from __future__ import annotations

import asyncio
import logging
import weakref
from typing import Protocol

from ..obs.recorder import PROCESS as _REC
from ..utils.tasks import create_logged_task


class _GroupSyncable(Protocol):
    def _group_sync(self) -> None: ...


def _log_unobserved_fsync_failure(exc: BaseException) -> None:
    logging.getLogger("smartbft.wal").warning(
        "WAL group-commit fsync wave failed with no live awaiter "
        "(all callers cancelled); durability is NOT guaranteed for the "
        "wave's appends: %r", exc,
    )


class GroupCommitScheduler:
    """Batches pending WAL fsyncs into parallel executor waves.

    One scheduler per event loop (see :func:`default_scheduler`); WALs from
    every replica in the process share it, so concurrent appends — e.g. all
    followers persisting the same pre-prepare — cost one parallel fsync
    wave instead of n serial fsyncs.
    """

    def __init__(self) -> None:
        self._pending: dict[_GroupSyncable, list[asyncio.Future]] = {}
        self._task: asyncio.Task | None = None
        #: waves flushed / syncs requested — group-commit effectiveness
        self.waves = 0
        self.syncs_requested = 0

    def schedule(self, wal: _GroupSyncable) -> asyncio.Future:
        """Register ``wal`` as dirty; the future resolves once a subsequent
        ``wal._group_sync()`` ran (i.e. the append is durable)."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._pending.setdefault(wal, []).append(fut)
        self.syncs_requested += 1
        if self._task is None or self._task.done():
            self._task = create_logged_task(
                self._drain(), name="wal-group-commit",
                # the loop's side of a wave: hand-off and futures resolved
                busy=(_REC, "wal.flush"),
            )
        return fut

    async def _drain(self) -> None:
        loop = asyncio.get_running_loop()
        while self._pending:
            pending, self._pending = self._pending, {}
            self.waves += 1
            results = await asyncio.gather(
                *(loop.run_in_executor(None, w._group_sync) for w in pending),
                return_exceptions=True,
            )
            for (_, futs), res in zip(pending.items(), results):
                observed = False
                for fut in futs:
                    if fut.done():
                        continue  # caller went away (e.g. cancelled)
                    if isinstance(res, BaseException):
                        fut.set_exception(res)
                        observed = True
                    else:
                        fut.set_result(None)
                if isinstance(res, BaseException) and not observed:
                    # every awaiting caller was already cancelled: a real
                    # durability failure (disk error) must still be heard
                    _log_unobserved_fsync_failure(res)
        # task exits when idle; schedule() restarts it on the next append


_schedulers: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def default_scheduler() -> GroupCommitScheduler:
    """The calling event loop's shared scheduler (created on first use)."""
    loop = asyncio.get_running_loop()
    sched = _schedulers.get(loop)
    if sched is None:
        sched = GroupCommitScheduler()
        _schedulers[loop] = sched
    return sched
