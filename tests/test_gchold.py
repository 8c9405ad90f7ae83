"""The process host's policy for the garbage collector (ISSUE 26).

* ``ShardSet.start()`` collects, freezes the heap and raises the third
  threshold to ``gchold.T2``; ``stop()`` puts back what was found;
* hosts that overlap in one process: the first applies, the last restores;
* a ``start()`` that raises leaves the collector as found, and the
  ``stop()`` after it releases nothing;
* the young generations are untouched: a cycle made while a host runs is
  reclaimed by an automatic collection;
* the account keeps collections apart by generation and carries the
  freeze count and the thresholds in force.
"""

import asyncio
import gc
import threading
import weakref

import pytest

from smartbft_tpu import obs
from smartbft_tpu.obs import assemble_account
from smartbft_tpu.shard import ShardHandle, ShardSet
from smartbft_tpu.testing.app import wait_for
from smartbft_tpu.testing.sharded import ShardedCluster
from smartbft_tpu.utils import gchold


@pytest.fixture(autouse=True)
def collector_as_found():
    """Every test here starts from, and has to end at, a collector that no
    host holds: its thresholds as found, the heap in its sight.  (The
    freeze count itself is no test of that: this interpreter counts 375
    objects of its own as frozen again after any automatic full pass.)"""
    assert gchold._holds == 0
    found = gc.get_threshold()
    assert found[2] != gchold.T2
    yield found
    after = gc.get_threshold()
    gc.set_threshold(*found)
    assert gchold._holds == 0
    assert after == found
    assert _in_sight(_MARKER)
    assert gc.get_freeze_count() <= _OWN


#: a tracked object as old as this module: part of any host's set-up heap
_MARKER = ["set-up heap"]
#: far above what ``gc.get_freeze_count()`` reads with nothing of ours
#: frozen (0 or 375 here), far below any process's heap
_OWN = 1000


def _in_sight(obj) -> bool:
    """Is ``obj`` in a generation the collector walks (not frozen)?"""
    return any(o is obj for o in gc.get_objects())


class _Stub(ShardHandle):
    def __init__(self, sid, fail=False):
        self.shard_id = sid
        self.fail = fail

    async def start(self):
        if self.fail:
            raise RuntimeError("shard would not start")

    async def stop(self): ...

    async def submit(self, raw): ...

    def poll_committed(self, since):
        return []

    def pool_occupancy(self):
        return {}


def _host(*, fail=False):
    return ShardSet([_Stub(0), _Stub(1, fail=fail)])


def test_start_freezes_the_heap_and_stop_restores(collector_as_found):
    t0, t1, t2 = collector_as_found

    async def run():
        host = _host()
        await host.start()
        try:
            assert gc.get_freeze_count() > _OWN
            assert not _in_sight(_MARKER)
            assert gc.get_threshold() == (t0, t1, gchold.T2)
            await host.start()  # a second start of one host holds once
        finally:
            await host.stop()
        assert _in_sight(_MARKER)
        assert gc.get_threshold() == (t0, t1, t2)
        await host.stop()  # and a second stop releases nothing more
        assert gchold._holds == 0

    asyncio.run(run())


def test_overlapping_hosts_restore_at_the_last_stop(collector_as_found):
    found = collector_as_found

    async def run():
        a, b = _host(), _host()
        await a.start()
        later = ["made after the first host froze the heap"]
        await b.start()
        # the second host found the first's policy in force and left it
        assert _in_sight(later) and not _in_sight(_MARKER)
        await a.stop()
        assert not _in_sight(_MARKER)
        assert gc.get_threshold()[2] == gchold.T2
        await b.stop()
        assert _in_sight(_MARKER)
        assert gc.get_threshold() == found

    asyncio.run(run())


def test_a_start_that_raises_leaves_the_collector_as_found(
        collector_as_found):
    found = collector_as_found

    async def run():
        outer = _host()
        await outer.start()
        held = gc.get_threshold()
        broken = _host(fail=True)
        with pytest.raises(RuntimeError):
            await broken.start()
        await broken.stop()
        # the host that runs keeps its policy: nothing was released twice
        assert gc.get_threshold() == held and not _in_sight(_MARKER)
        await outer.stop()
        alone = _host(fail=True)
        with pytest.raises(RuntimeError):
            await alone.start()
        assert gc.get_threshold() == found and _in_sight(_MARKER)
        await alone.stop()

    asyncio.run(run())


def test_a_stop_that_raises_still_restores(collector_as_found):
    found = collector_as_found

    class _Stuck(_Stub):
        async def stop(self):
            raise RuntimeError("shard would not stop")

    async def run():
        host = ShardSet([_Stuck(0)])
        await host.start()
        assert not _in_sight(_MARKER)
        with pytest.raises(RuntimeError):
            await host.stop()
        assert gc.get_threshold() == found and _in_sight(_MARKER)

    asyncio.run(run())


class _Node:
    pass


def test_a_cycle_made_while_a_host_runs_dies_young():
    """Generations 0 and 1 keep their thresholds, so an unreachable cycle
    is reclaimed by an automatic collection, never asked for here."""
    assert gc.isenabled()

    async def run():
        host = _host()
        await host.start()
        try:
            a, b = _Node(), _Node()
            a.other, b.other = b, a
            # a callback, not a poll: the strong reference a poll returns
            # is on the stack at the very point where a collection runs
            gone = []
            ref = weakref.ref(a, gone.append)
            passes = gc.get_stats()[2]["collections"]
            del a, b
            assert ref() is not None  # reference counting cannot free it
            keep = []
            for _ in range(20 * gc.get_threshold()[0]):
                keep.append([])
                if gone:
                    break
            assert gone and ref() is None
            assert gc.get_stats()[2]["collections"] == passes
        finally:
            await host.stop()

    asyncio.run(run())


def test_a_cluster_holds_the_collector_from_start_to_stop(
        tmp_path, collector_as_found):
    """Through the embedder's entry point, with real replicas behind the
    front door: held while they commit, restored after."""
    found = collector_as_found

    async def run():
        c = ShardedCluster(tmp_path, shards=1, n=4, depth=2)
        assert _in_sight(c.set)
        await c.start()
        try:
            assert not _in_sight(c.set)
            assert gc.get_threshold() == (found[0], found[1], gchold.T2)
            for j in range(4):
                await c.submit(c.client_for_shard(0, j % 2), f"r{j}")
            await wait_for(lambda: c.shard_list[0].committed() >= 4,
                           c.scheduler, 60.0)
            c.check_invariants()
        finally:
            await c.stop()
        assert gc.get_threshold() == found and _in_sight(c.set)

    asyncio.run(run())


# -- the account ---------------------------------------------------------------


class _Ring:
    recorded = dropped = 0

    def events(self):
        return []


@pytest.mark.parametrize("generation", [0, 1, 2])
def test_account_keeps_collections_apart_by_generation(generation):
    collections = [("MainThread", 1.0, 0.25, generation),
                   ("MainThread", 2.0, 0.50, generation),
                   ("MainThread", 9.0, 4.00, generation)]  # after the end
    acc = assemble_account([_Ring()], {}, t0=0.0, t1=5.0, loop_cpu_s=1.0,
                           loop_thread="MainThread", collections=collections,
                           frozen=1234, thresholds=(700, 10, gchold.T2))
    passes = acc["collector"]["passes"]
    for gen in range(3):
        want = (2, 0.75) if gen == generation else (0, 0.0)
        assert (passes[gen]["count"], passes[gen]["seconds"]) == want
    # one ``gc`` kind still, so loop_gc_pct reads what it read
    assert acc["busy"]["MainThread"]["gc"]["self_s"] == pytest.approx(0.75)
    assert acc["collector"]["frozen"] == 1234
    assert acc["collector"]["thresholds"] == [700, 10, gchold.T2]


def test_a_forced_full_pass_shows_under_generation_two(tmp_path):
    """With the recorder on, the account of a host that runs says how many
    objects are frozen, the thresholds in force, and files a forced
    ``gc.collect(2)`` under generation 2."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2

    async def run():
        host = _host()
        await host.start()
        try:
            frozen = gc.get_freeze_count()
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                obs.poll_profiler()
                gc.collect(2)
                gc.collect(0)
                obs.poll_profiler()
            finally:
                jax.profiler.stop_trace()
            obs.poll_profiler()  # the off edge, the host still running
            return frozen
        finally:
            await host.stop()

    frozen = asyncio.run(run())
    acc = obs.last_summary()
    passes = acc["collector"]["passes"]
    assert passes[2]["count"] == 1 and passes[2]["seconds"] > 0
    assert passes[0]["count"] >= 1
    busy = acc["busy"][threading.current_thread().name]["gc"]
    assert busy["calls"] == sum(p["count"] for p in passes)
    assert busy["self_s"] == pytest.approx(sum(p["seconds"] for p in passes))
    # frozen objects still die by reference counting, so a few fewer
    assert _OWN < acc["collector"]["frozen"] <= frozen
    assert acc["collector"]["thresholds"][2] == gchold.T2
