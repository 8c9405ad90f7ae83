"""The process host's policy for the interpreter's garbage collector.

An orderer host (:class:`~smartbft_tpu.shard.set.ShardSet`) builds a large
heap once: the interpreter, JAX, the lowered kernels, every replica's
components, keyrings, registries and WAL objects.  None of it is garbage
while the host runs, yet CPython walks all of it in every full
(generation-2) collection, and its 25 % rule (a full pass once the objects
promoted since the last one exceed a quarter of those that survived it)
schedules one every few seconds under protocol traffic, whose votes, pool
items and decoded messages outlive the young generations and are freed by
reference counting, not by the collector.  The share of time spent in full
passes is about 5 x (cost per object) x (objects promoted per second),
whatever the heap's size, so freezing alone only makes the passes cheaper
and as much more frequent.  While a host runs it therefore does both:

* :func:`hold` collects once, then ``gc.freeze()``: the set-up heap leaves
  the collector's sight;
* the third threshold becomes :data:`T2`, so the 25 % rule is consulted
  once in :data:`T2` generation-1 passes instead of once in ten.

Generations 0 and 1 keep the thresholds they had: a cycle that dies young
is found within milliseconds as before, and one that reaches generation 2
is found at the next full pass, over the unfrozen heap only.
:func:`release` puts the thresholds back as :func:`hold` found them and
unfreezes.  ``gc`` can only unfreeze everything, so what was frozen before
the host started is unfrozen with it.  (``gc.get_freeze_count()`` is no
exact test of that: CPython 3.12.12 counts 375 objects of its own, at
start-up and again after any automatic full pass.)  Hosts may nest or
overlap in one process: the first applies, the last restores.
"""

from __future__ import annotations

import gc
import threading

__all__ = ["T2", "hold", "release"]

#: Generation-1 passes between two looks at the 25 % rule.  At n=64 under
#: saturating load the chip's host runs 10 generation-1 passes a second
#: (``gc.get_stats()`` over ten 30 s windows: 9.3-10.5; PERF.md section
#: 6, PR 26), so 5000 of them take about eight minutes, and the pass that
#: then runs walks the unfrozen heap only (0.75 M objects there, a quarter
#: of a second).  A host with less traffic waits longer.  The interpreter's
#: own value is 10: one look a second.
T2 = 5000

_lock = threading.Lock()
_holds = 0
_found: tuple = ()  # the thresholds at the first hold


def hold() -> None:
    """Freeze the heap as it stands and hold back full collections."""
    global _holds, _found
    with _lock:
        _holds += 1
        if _holds > 1:
            return
        _found = gc.get_threshold()
        gc.collect()  # so that no garbage is frozen
        gc.freeze()
        gc.set_threshold(_found[0], _found[1], T2)


def release() -> None:
    """Undo one :func:`hold`; the last one restores the collector."""
    global _holds
    with _lock:
        _holds -= 1
        if _holds > 0:
            return
        gc.set_threshold(*_found)
        gc.unfreeze()
