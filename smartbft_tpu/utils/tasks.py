"""Background-task spawning with mandatory failure observation.

Every ``asyncio.create_task`` call site in :mod:`smartbft_tpu` goes through
:func:`create_logged_task` (pinned by ``tests/test_task_audit.py``): a
task whose exception is never retrieved dies SILENTLY — asyncio only
reports it at garbage-collection time, if ever — and a consensus component
whose run loop evaporated mid-protocol is exactly the failure mode a BFT
system cannot afford to miss.  The attached done-callback retrieves and
logs any terminal exception; tasks that are later awaited still re-raise
to their awaiter (``Task.exception`` does not consume the error for
``await``), so structured teardown paths keep their semantics.
"""

from __future__ import annotations

import asyncio
from typing import Optional


def create_logged_task(coro, *, name: str, logger=None,
                       busy: Optional[tuple] = None) -> asyncio.Task:
    """``loop.create_task`` + an exception-logging done-callback.

    ``busy``: ``(recorder, kind)`` — account each step of the task as a
    busy span of that kind while the recorder is on
    (:class:`~smartbft_tpu.obs.recorder.busy_steps`).

    ``logger`` is any object with ``errorf`` (the project Logger SPI);
    None falls back to a module StdLogger so even logger-less contexts
    (clock drivers, test transports) never spawn an unobserved task.

    Deliberate tradeoff: tasks whose failure is ALSO handled by an awaiter
    (run loops awaited in stop/abort, the decide rendezvous) report twice
    on crash paths — once here, once by the handler.  Detecting "someone
    will await this" reliably is not possible, and the duplicate line only
    appears when something already went wrong; the uniform guarantee
    (every task death is logged, auditable by tests/test_task_audit.py)
    is worth more than deduplicated error output.
    """
    if busy is not None:
        from ..obs.recorder import busy_steps

        coro = busy_steps(coro, *busy)
    task = asyncio.get_running_loop().create_task(coro, name=name)

    def _observe(t: asyncio.Task) -> None:
        if t.cancelled():
            return
        exc = t.exception()  # marks the failure retrieved (no GC warning)
        if exc is not None:
            log = logger
            if log is None:
                from .logging import StdLogger

                log = StdLogger("smartbft.tasks")
            log.errorf("Background task %r died: %r", name, exc)

    task.add_done_callback(_observe)
    return task
