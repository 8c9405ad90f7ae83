"""Overload-safe front door: admission control, bounded FIFO space waits,
commit-latency accounting and the open-loop harness.

Coverage map (ISSUE 8):

- pool admission gate: fast-fail past the high-water mark with a
  drain-rate-derived retry-after hint, shed accounting, and the legacy
  (gate-off) parking semantics untouched;
- pool space waits: ONE total submit deadline across re-parks, FIFO
  wakeup, no barging past parked waiters (including through the
  wake→resume window), and a timed-out waiter's request in NO pool;
- log-scale histograms + CommitLatencyTracker: bounded memory, quantile
  accuracy within bucket resolution, phase windows, shed counters;
- ShardSet: sheds counted per cause, parked-at-barrier submits visible
  to the occupancy surface the autoscaler/admission gate read;
- tier-1 acceptance (logical clock): open-loop load past the knee —
  admission bounds pool occupancy while goodput stays positive; p99
  stays finite and shedding engages THROUGH a verify-breaker trip
  (host-fallback phase) at fixed offered load;
- chaos vocabulary: load_spike/load_stop timeline actions (spike past
  the knee -> sheds -> occupancy bounded -> stop -> p99 recovers);
- the pump on the wall clock: one tiny paced point under a
  WallClockDriver.
"""

import asyncio
import dataclasses

import pytest

from smartbft_tpu.config import ConfigError, Configuration
from smartbft_tpu.core.pool import (
    AdmissionRejected,
    Pool,
    PoolOptions,
    ReqAlreadyExistsError,
    ReqAlreadyProcessedError,
    SubmitTimeoutError,
)
from smartbft_tpu.metrics import CommitLatencyTracker, LogScaleHistogram
from smartbft_tpu.shard import ShardSet
from smartbft_tpu.testing.chaos import (
    ChaosCluster,
    ChaosEvent,
    Invariants,
    chaos_config,
)
from smartbft_tpu.testing.load import OpenLoopPump, ZipfClients, run_open_loop
from smartbft_tpu.testing.sharded import ShardedCluster, sharded_config
from smartbft_tpu.types import RequestInfo
from smartbft_tpu.utils.clock import Scheduler
from smartbft_tpu.utils.logging import RecordingLogger


class _Handler:
    def on_request_timeout(self, request, info):
        pass

    def on_leader_fwd_request_timeout(self, request, info):
        pass

    def on_auto_remove_timeout(self, info):
        pass


class _Inspector:
    def request_id(self, raw):
        return RequestInfo(client_id="c", request_id=raw.decode())


def make_pool(scheduler, **kw):
    opts = PoolOptions(
        queue_size=kw.pop("queue_size", 4),
        forward_timeout=60.0,
        complain_timeout=120.0,
        auto_remove_timeout=240.0,
        request_max_bytes=100,
        submit_timeout=kw.pop("submit_timeout", 1.0),
        admission_high_water=kw.pop("admission_high_water", 1.0),
    )
    return Pool(RecordingLogger("pool"), _Inspector(), _Handler(), opts,
                scheduler)


# -- admission gate -----------------------------------------------------------

def test_admission_gate_sheds_past_high_water():
    """Past the high-water mark submit fails FAST (no parking) with a
    retry-after hint; the shed request is in no pool; below the mark
    submits land normally."""

    async def run():
        s = Scheduler()
        pool = make_pool(s, queue_size=8, admission_high_water=0.5)
        for i in range(4):  # high water = 4 slots
            await pool.submit(b"r%d" % i)
        with pytest.raises(AdmissionRejected) as exc:
            await pool.submit(b"r4")
        assert exc.value.retry_after > 0  # no drain measured yet -> bound
        assert exc.value.occupancy["size"] == 4
        assert pool.occupancy()["shed_admission"] == 1
        assert pool.size() == 4
        # the shed request was never pooled: freeing space lets the SAME
        # id land (a pooled copy would raise ReqAlreadyExists)
        pool.remove_request(RequestInfo("c", "r0"))
        await pool.submit(b"r4")
        assert pool.size() == 4
        pool.close()

    asyncio.run(run())


def test_admission_gate_off_keeps_parking_semantics():
    """admission_high_water=1.0 (the default) never sheds at the gate —
    a full pool parks the submitter exactly as before."""

    async def run():
        s = Scheduler()
        pool = make_pool(s, queue_size=2)  # gate off
        await pool.submit(b"a")
        await pool.submit(b"b")
        waiter = asyncio.ensure_future(pool.submit(b"cc"))
        await asyncio.sleep(0)
        assert not waiter.done()
        assert pool.occupancy()["shed_admission"] == 0
        pool.remove_request(RequestInfo("c", "a"))
        for _ in range(5):
            await asyncio.sleep(0)
        assert waiter.done() and waiter.exception() is None
        pool.close()

    asyncio.run(run())


def test_retry_after_hint_tracks_drain_rate():
    """The hint is excess/drain-rate once a rate is measured, and the
    submit-timeout bound while the pool is cold."""

    async def run():
        s = Scheduler()
        pool = make_pool(s, queue_size=8, admission_high_water=0.5,
                         submit_timeout=3.0)
        for i in range(4):
            await pool.submit(b"r%d" % i)
        # cold pool: no drain rate yet, hint = the submit-timeout bound
        with pytest.raises(AdmissionRejected) as exc:
            await pool.submit(b"x0")
        assert exc.value.retry_after == 3.0
        # drain 4 requests across 2 logical seconds => ~2 req/s
        for i in range(4):
            s.advance_by(0.5)
            pool.remove_request(RequestInfo("c", "r%d" % i))
        for i in range(4):
            await pool.submit(b"q%d" % i)
        with pytest.raises(AdmissionRejected) as exc:
            await pool.submit(b"x1")
        # excess = 1 over the mark; rate ~2/s -> hint ~0.5s
        assert 0.1 <= exc.value.retry_after <= 2.0
        pool.close()

    asyncio.run(run())


def test_forwarded_requests_bypass_admission_gate():
    """REVIEW FIX: a follower's forward landing at the leader already
    holds a pool slot cluster-side — shedding it at the gate would only
    re-arm the follower's complain timer (README: the gate guards the
    client-facing door).  Forwards still ride the queue-size bound."""

    async def run():
        s = Scheduler()
        pool = make_pool(s, queue_size=8, admission_high_water=0.5)
        for i in range(4):  # at the high-water mark
            await pool.submit(b"r%d" % i)
        with pytest.raises(AdmissionRejected):
            await pool.submit(b"client")
        await pool.submit(b"fwd", forwarded=True)  # bypasses the gate
        assert pool.size() == 5
        # but never the hard capacity bound: a forward into a FULL pool
        # parks and sheds on the submit deadline like before
        for i in range(3):
            await pool.submit(b"f%d" % i, forwarded=True)
        assert pool.size() == 8
        waiter = asyncio.ensure_future(pool.submit(b"f9", forwarded=True))
        await asyncio.sleep(0)
        s.advance_by(2.0)  # submit_timeout 1.0
        with pytest.raises(SubmitTimeoutError):
            await waiter
        pool.close()

    asyncio.run(run())


def test_cancelled_woken_waiter_hands_slot_to_next():
    """REVIEW FIX: a waiter woken into the wake window and then cancelled
    must hand its reserved slot to the next waiter — not strand it until
    some future removal."""

    async def run():
        s = Scheduler()
        pool = make_pool(s, queue_size=2, submit_timeout=5.0)
        await pool.submit(b"a")
        await pool.submit(b"b")
        w_a = asyncio.ensure_future(pool.submit(b"wa"))
        await asyncio.sleep(0)
        w_b = asyncio.ensure_future(pool.submit(b"wb"))
        await asyncio.sleep(0)
        pool.remove_request(RequestInfo("c", "a"))  # wakes A (reserved)
        w_a.cancel()  # cancelled inside the wake window
        for _ in range(10):
            await asyncio.sleep(0)
        assert w_a.cancelled()
        assert w_b.done() and w_b.exception() is None, (
            "B stranded on the slot A's cancellation freed"
        )
        assert pool.size() == 2
        assert pool.occupancy()["waiters"] == 0
        pool.close()

    asyncio.run(run())


# -- bounded, fair space waits ------------------------------------------------

def test_space_wait_sheds_at_total_deadline_and_request_in_no_pool():
    """REGRESSION (ISSUE 8 satellite): the submit deadline is ONE bound
    across every re-park — a spurious wakeup into a still-full pool must
    NOT re-arm a fresh timeout — and the timed-out waiter's request is in
    no pool afterwards."""

    async def run():
        s = Scheduler()
        pool = make_pool(s, queue_size=2, submit_timeout=1.0)
        await pool.submit(b"a")
        await pool.submit(b"b")
        waiter = asyncio.ensure_future(pool.submit(b"w"))
        await asyncio.sleep(0)
        s.advance_by(0.6)
        # spurious wake into a still-full pool (popped + reserved exactly
        # as _release_space wakes): the waiter must re-park with the
        # REMAINING 0.4s, not a fresh 1.0s
        pool._space_waiters.popleft().set_result(None)
        pool._reserved_slots += 1
        for _ in range(5):
            await asyncio.sleep(0)
        assert not waiter.done()
        s.advance_by(0.5)  # total 1.1 > 1.0
        with pytest.raises(SubmitTimeoutError):
            await waiter
        assert pool.occupancy()["shed_timeout"] == 1
        assert pool.occupancy()["waiters"] == 0  # no reservation leaked
        # in NO pool: the same id lands cleanly once space exists
        pool.remove_request(RequestInfo("c", "a"))
        await pool.submit(b"w")
        pool.close()

    asyncio.run(run())


def test_space_waiters_wake_fifo_and_fresh_submitters_cannot_barge():
    """REGRESSION (ISSUE 8 satellite): waiters are served oldest-first,
    and a fresh submitter queues BEHIND parked waiters even when a
    removal just freed the slot."""

    async def run():
        s = Scheduler()
        pool = make_pool(s, queue_size=2, submit_timeout=5.0)
        await pool.submit(b"a")
        await pool.submit(b"b")
        order = []

        async def tracked(name, raw):
            await pool.submit(raw)
            order.append(name)

        w1 = asyncio.ensure_future(tracked("w1", b"w1"))
        await asyncio.sleep(0)
        w2 = asyncio.ensure_future(tracked("w2", b"w2"))
        await asyncio.sleep(0)
        # free one slot, then immediately race a fresh submitter: the slot
        # belongs to w1 (head), and the newcomer parks at the tail
        pool.remove_request(RequestInfo("c", "a"))
        w3 = asyncio.ensure_future(tracked("w3", b"w3"))
        for _ in range(10):
            await asyncio.sleep(0)
        assert order == ["w1"]
        pool.remove_request(RequestInfo("c", "b"))
        for _ in range(10):
            await asyncio.sleep(0)
        assert order == ["w1", "w2"]
        pool.remove_request(RequestInfo("c", "w1"))
        for _ in range(10):
            await asyncio.sleep(0)
        assert order == ["w1", "w2", "w3"]
        await asyncio.gather(w1, w2, w3)
        pool.close()

    asyncio.run(run())


def test_woken_waiter_repark_keeps_head_position():
    """A woken waiter that loses its slot re-parks at the HEAD, not the
    tail — its place in line survives the race."""

    async def run():
        s = Scheduler()
        pool = make_pool(s, queue_size=2, submit_timeout=5.0)
        await pool.submit(b"a")
        await pool.submit(b"b")
        order = []

        async def tracked(name, raw):
            await pool.submit(raw)
            order.append(name)

        w1 = asyncio.ensure_future(tracked("w1", b"w1"))
        await asyncio.sleep(0)
        w2 = asyncio.ensure_future(tracked("w2", b"w2"))
        await asyncio.sleep(0)
        # spuriously wake w1 into a still-full pool (popped + reserved as
        # _release_space wakes): it must re-park AHEAD of w2, so the next
        # real slot is still w1's
        pool._space_waiters.popleft().set_result(None)
        pool._reserved_slots += 1
        for _ in range(5):
            await asyncio.sleep(0)
        assert not w1.done() and not w2.done()
        pool.remove_request(RequestInfo("c", "a"))
        for _ in range(10):
            await asyncio.sleep(0)
        assert order == ["w1"]
        pool.remove_request(RequestInfo("c", "b"))
        await asyncio.gather(w1, w2)
        assert order == ["w1", "w2"]
        pool.close()

    asyncio.run(run())


# -- histograms + tracker -----------------------------------------------------

def test_log_scale_histogram_quantiles_and_bounded_memory():
    h = LogScaleHistogram()
    for _ in range(900):
        h.observe(0.010)   # 10 ms
    for _ in range(90):
        h.observe(0.100)   # 100 ms
    for _ in range(10):
        h.observe(1.0)     # 1 s
    assert h.count == 1000
    assert len(h.buckets) == 64  # fixed — a billion observations stay 64 ints
    # √2 buckets: quantile error bounded by one bucket (~±41% worst case)
    assert 0.007 <= h.quantile(0.50) <= 0.015
    assert 0.07 <= h.quantile(0.95) <= 0.15
    assert 0.7 <= h.quantile(0.999) <= 1.0  # clamped into observed max
    snap = h.snapshot()
    assert set(snap) == {"count", "p50_ms", "p95_ms", "p99_ms", "mean_ms",
                         "max_ms"}
    assert snap["max_ms"] == 1000.0
    # out-of-range observations clamp into the edge buckets, never throw
    h.observe(1e-9)
    h.observe(1e6)
    assert h.count == 1002


def test_commit_latency_tracker_phases_sheds_and_bounded_pending():
    t = {"now": 0.0}
    tr = CommitLatencyTracker(clock=lambda: t["now"], max_pending=4)
    tr.begin_phase("healthy")
    tr.on_submitted("c:1")
    t["now"] = 0.05
    tr.on_committed("c:1", shard_id=0)
    tr.begin_phase("degraded")
    tr.on_submitted("c:2")
    tr.on_shed("c:2", "admission")
    tr.on_submitted("c:3")
    t["now"] = 0.45
    tr.on_committed("c:3", shard_id=1)
    tr.on_committed("c:unknown", shard_id=0)  # unstamped: ignored
    snap = tr.snapshot()
    assert snap["count"] == 2
    assert snap["shed"] == {"admission": 1, "timeout": 0, "other": 0}
    assert snap["histogram"], "sparse bucket dump missing from snapshot"
    assert sum(snap["histogram"].values()) == 2
    assert set(snap["phases"]) == {"healthy", "degraded"}
    assert snap["phases"]["healthy"]["count"] == 1
    assert snap["phases"]["degraded"]["shed"]["admission"] == 1
    assert 40 <= snap["phases"]["degraded"]["p99_ms"] <= 600
    assert set(snap["per_shard"]) == {0, 1}
    # bounded pending map: oldest stamps are dropped and counted
    for i in range(10):
        tr.on_submitted(f"c:p{i}")
    assert tr.pending() == 4
    assert tr.dropped_stamps == 6


# -- ShardSet front door ------------------------------------------------------

class _ShedShard:
    """Stub handle whose submit always sheds at the admission gate."""

    def __init__(self, sid, exc):
        self.shard_id = sid
        self.exc = exc

    async def start(self):
        pass

    async def stop(self):
        pass

    async def submit(self, raw):
        raise self.exc

    def poll_committed(self, since):
        return []

    def pool_occupancy(self):
        return {"size": 3, "capacity": 4, "free": 1, "waiters": 0,
                "shed_admission": 7, "shed_timeout": 2}

    def pending_client_ids(self):
        return set()

    def ready(self):
        return True

    def space_waiters(self):
        return 0


def test_shardset_counts_sheds_per_cause_and_reraises():
    async def run():
        s = ShardSet([_ShedShard(0, AdmissionRejected("full", retry_after=1.0)),
                      _ShedShard(1, SubmitTimeoutError("slow"))])
        c0 = next(f"k{i}" for i in range(1000) if s.route(f"k{i}") == 0)
        c1 = next(f"k{i}" for i in range(1000) if s.route(f"k{i}") == 1)
        with pytest.raises(AdmissionRejected):
            await s.submit(c0, b"r", request_key=f"{c0}:r")
        with pytest.raises(SubmitTimeoutError):
            await s.submit(c1, b"r", request_key=f"{c1}:r")
        assert s.latency.shed == {"admission": 1, "timeout": 1, "other": 0}
        assert s.latency.pending() == 0  # shed stamps dropped
        occ = s.occupancy()
        assert occ["shed_admission"] == 14 and occ["shed_timeout"] == 4
        assert s.submitted == 0

    asyncio.run(run())


def test_parked_at_barrier_submits_count_toward_occupancy():
    """ISSUE 8 satellite: a moved client parked at a reshard barrier is
    invisible to every pool, but the occupancy surface the autoscaler and
    admission gate read must still see the pressure."""
    from smartbft_tpu.shard.set import _Transition

    class _Quiet(_ShedShard):
        async def submit(self, raw):
            pass

    async def run():
        s = ShardSet([_Quiet(0, None), _Quiet(1, None)])
        moved = next(f"m{k}" for k in range(10_000)
                     if s.router.moved(f"m{k}", 2, 3))
        tr = _Transition(epoch=1, old_s=2, new_s=3,
                         deadline=asyncio.get_event_loop().time() + 30)
        s._transition = tr
        task = asyncio.ensure_future(s.submit(moved, b"x"))
        await asyncio.sleep(0.02)
        occ = s.occupancy()
        assert occ["parked_moved"] == 1
        assert occ["total_waiters"] >= 1  # same signal the autoscaler reads
        s._transition = None
        tr.flip_event.set()
        await task
        assert s.occupancy()["parked_moved"] == 0

    asyncio.run(run())


def test_barrier_submission_bypasses_admission_gate():
    """REVIEW FIX: the reshard barrier is control plane — internal=True
    rides through Consensus.submit_request so the admission gate cannot
    shed the very command that scales an over-the-knee cluster out."""
    from smartbft_tpu.testing.app import submit_barrier_request

    class _StubConsensus:
        def __init__(self):
            self.calls = []

        async def submit_request(self, req, *, internal=False):
            self.calls.append(internal)

    stub = _StubConsensus()
    asyncio.run(submit_barrier_request(stub, 1, 2, 3))
    assert stub.calls == [True]


def test_autoscaler_reads_shed_pressure_as_saturation():
    """REVIEW FIX: with the gate armed below autoscale_high_occupancy,
    fill can never reach the threshold and waiters never form — shedding
    since the last evaluation must itself read as saturation, or the
    autoscaler watches a shedding cluster forever."""
    from smartbft_tpu.shard import OccupancyAutoscaler

    t = {"now": 0.0}
    a = OccupancyAutoscaler(high=0.85, low=0.15, cooldown=1.0,
                            min_shards=1, max_shards=8,
                            clock=lambda: t["now"])
    base = {"fill": 0.78, "total_waiters": 0, "total_capacity": 100,
            "shed_admission": 0, "shed_timeout": 0}
    assert a.evaluate(base, 2) is None          # below high, no sheds
    grown = dict(base, shed_admission=50)
    assert a.evaluate(grown, 2) == 3            # shed delta => scale out
    a.note_action()
    t["now"] = 10.0                              # past cooldown
    assert a.evaluate(grown, 3) is None          # no NEW sheds => hold
    # shedding also vetoes the idle scale-in
    idle_but_shedding = dict(base, fill=0.05, shed_timeout=75)
    assert a.evaluate(idle_but_shedding, 3) == 4


def test_duplicate_submit_keeps_original_latency_stamp():
    """REVIEW FIX: a retry of a still-pending request must neither reset
    its arrival stamp nor count a shed when the pool dedups it — the
    slow (hence retried) requests are exactly the ones the percentiles
    must not lose."""

    class _DupShard(_ShedShard):
        def __init__(self, sid):
            super().__init__(sid, None)
            self.seen = set()

        async def submit(self, raw):
            if raw in self.seen:
                from smartbft_tpu.core.pool import ReqAlreadyExistsError

                raise ReqAlreadyExistsError("dup")
            self.seen.add(raw)

    async def run():
        t = {"now": 0.0}
        s = ShardSet([_DupShard(0), _DupShard(1)], clock=lambda: t["now"])
        cid = next(f"k{i}" for i in range(1000) if s.route(f"k{i}") == 0)
        key = f"{cid}:r1"
        await s.submit(cid, b"payload", request_key=key)
        t["now"] = 5.0
        with pytest.raises(ReqAlreadyExistsError):
            await s.submit(cid, b"payload", request_key=key)
        assert s.latency.shed == {"admission": 0, "timeout": 0, "other": 0}
        t["now"] = 10.0
        s.latency.on_committed(key, 0)
        # measured from the FIRST submit (t=0), not the retry (t=5)
        assert s.latency.aggregate.count == 1
        assert s.latency.aggregate.max_seen == 10.0
        # an already-processed dup discards its fresh stamp silently
        s.shards[0] = _ShedShard(0, ReqAlreadyProcessedError("done"))
        with pytest.raises(ReqAlreadyProcessedError):
            await s.submit(cid, b"payload", request_key=f"{cid}:r2")
        assert s.latency.pending() == 0
        assert s.latency.shed == {"admission": 0, "timeout": 0, "other": 0}

    asyncio.run(run())


def test_two_spikes_do_not_collide_on_request_ids(tmp_path):
    """REVIEW FIX: a second load_spike continues the run-wide request-id
    sequence — re-issuing the first burst's ids would make the pool
    reject the whole second burst as duplicates (all spike_failed)."""

    async def run():
        cluster = ChaosCluster(
            str(tmp_path), n=4, depth=1,
            config_fn=lambda i: chaos_config(i, depth=1),
        )
        await cluster.start()
        try:
            report = await cluster.run_schedule(
                [ChaosEvent(at=1.0, action="load_spike", fraction=15.0),
                 ChaosEvent(at=3.0, action="load_stop"),
                 ChaosEvent(at=4.0, action="load_spike", fraction=15.0),
                 ChaosEvent(at=6.0, action="load_stop")],
                requests=25, settle_timeout=120.0,
            )
            assert report.spike_offered > 0
            assert report.spike_failed == 0, (
                f"second spike collided with the first: {report}"
            )
            assert report.spike_acked == report.spike_offered \
                - report.spike_shed
            Invariants.exactly_once(cluster)
        finally:
            await cluster.stop()

    asyncio.run(run())


def test_chaos_spike_without_load_stop_gets_implicit_stop(tmp_path):
    """REVIEW FIX: a schedule whose last event fires with the pump still
    running must drain (implicit load_stop), not pump to the 1h cap."""

    async def run():
        cluster = ChaosCluster(
            str(tmp_path), n=4, depth=1,
            config_fn=lambda i: chaos_config(i, depth=1),
        )
        await cluster.start()
        try:
            # baseline pump runs to ~6s logical; the stop-less spike pumps
            # alongside it and is implicitly stopped at the heal point
            report = await cluster.run_schedule(
                [ChaosEvent(at=1.0, action="load_spike", fraction=20.0)],
                requests=20, settle_timeout=120.0,
            )
            assert cluster.spike is None
            assert report.spike_offered > 0
        finally:
            await cluster.stop()

    asyncio.run(run())


# -- tier-1 acceptance gates (logical clock) ----------------------------------

def _overload_cfg(pool_size=24, admission=0.75, **overrides):
    def cfg(s, i):
        base = dict(
            request_pool_size=pool_size,
            admission_high_water=admission,
            request_pool_submit_timeout=1.0,
            request_batch_max_count=8,
        )
        base.update(overrides)
        return dataclasses.replace(sharded_config(i, depth=2), **base)

    return cfg


def test_open_loop_past_knee_bounds_occupancy_and_keeps_goodput(tmp_path):
    """ACCEPTANCE: offered load far past the knee of a small-pool cluster
    — admission control bounds pool occupancy (pooled + parked never
    exceeds combined capacity: no unbounded growth) while committed
    goodput stays positive, sheds carry retry-after hints, and the
    latency block reports finite percentiles.  Logical clock: seconds of
    offered load cost milliseconds."""

    async def run():
        cluster = ShardedCluster(
            str(tmp_path), shards=2, n=4, depth=2,
            config_fn=_overload_cfg(), seed=5,
        )
        await cluster.start()
        try:
            capacity = 2 * 24
            stats = await run_open_loop(
                cluster, rate=600.0, duration=4.0, drain=4.0, seed=9,
            )
            lat = cluster.set.latency.snapshot()
            assert stats.shed_admission > 0, stats.block()
            assert stats.peak_occupancy <= capacity, (
                f"occupancy {stats.peak_occupancy} exceeded capacity "
                f"{capacity}: admission failed to bound the queue"
            )
            assert stats.acked > 0 and lat["count"] > 0, (stats.block(), lat)
            assert cluster.set.committed_requests() > 0
            assert lat["p99_ms"] > 0 and lat["p99_ms"] < 1e6
            assert stats.retry_after_hints, "sheds must carry hints"
            cluster.check_invariants()
        finally:
            await cluster.stop()

    asyncio.run(run())


def test_p99_finite_and_shedding_through_breaker_trip(tmp_path):
    """ACCEPTANCE: fixed offered load past the knee THROUGH a verify-
    engine outage — the breaker trips to host fallback mid-load, p99
    stays finite, shedding engages, goodput stays positive, and the
    phase windows separate healthy from breaker-open percentiles."""

    async def run():
        # engine-fault configs keep heartbeat/VC machinery out of the way
        # (the wall-clock breaker cycle spans many logical seconds)
        cfg = _overload_cfg(
            request_forward_timeout=120.0,
            request_complain_timeout=240.0,
            request_auto_remove_timeout=480.0,
            leader_heartbeat_timeout=30.0,
            view_change_resend_interval=15.0,
            view_change_timeout=60.0,
        )
        cluster = ShardedCluster(
            str(tmp_path), shards=2, n=4, depth=2, engine_faults=True,
            config_fn=cfg, seed=6,
        )
        await cluster.start()
        try:
            tracker = cluster.set.latency
            tracker.begin_phase("healthy")
            warm = await run_open_loop(
                cluster, rate=120.0, duration=2.0, drain=3.0, seed=11,
            )
            assert warm.acked > 0
            # outage: the engine hangs; the deadline->retry->breaker cycle
            # degrades every wave to the host fallback UNDER the pump
            cluster.engine.hang()
            tracker.begin_phase("breaker_open")
            stats = await run_open_loop(
                cluster, rate=600.0, duration=4.0, drain=6.0, seed=12,
                request_prefix="bo",
            )
            tracker.end_phase()
            snap = cluster.coalescer.fault_snapshot()
            assert snap["opens"] >= 1, snap
            assert snap["host_fallback_batches"] >= 1, snap
            lat = tracker.snapshot()
            phase = lat["phases"]["breaker_open"]
            assert stats.shed > 0, stats.block()
            assert phase["count"] > 0, "goodput collapsed during the trip"
            assert 0 < phase["p99_ms"] < 1e6, phase
            assert stats.peak_occupancy <= 2 * 24
            cluster.engine.heal()
            cluster.check_invariants()
        finally:
            await cluster.stop()

    asyncio.run(run())


def test_chaos_load_spike_timeline_sheds_and_recovers(tmp_path):
    """ISSUE 8 satellite: the open-loop pump as a schedulable chaos fault
    — spike past the knee, admission sheds, occupancy stays bounded,
    load stops, the drain completes and p99 recovers (every ACKED spike
    request commits exactly once)."""

    async def run():
        pool_size = 16
        cluster = ChaosCluster(
            str(tmp_path), n=4, depth=2,
            config_fn=lambda i: chaos_config(
                i, depth=2,
                request_pool_size=pool_size,
                admission_high_water=0.75,
                request_pool_submit_timeout=1.0,
            ),
        )
        await cluster.start()
        try:
            cluster.latency.begin_phase("spike")
            schedule = [
                ChaosEvent(at=1.0, action="load_spike", fraction=300.0,
                           count=64),
                ChaosEvent(at=4.0, action="load_stop"),
            ]
            report = await cluster.run_schedule(
                schedule, requests=6, settle_timeout=300.0,
            )
            cluster.latency.begin_phase("after")
            # a few post-spike requests measure the recovered latency
            for k in range(4):
                cluster.latency.on_submitted(f"post:post-{k}")
                await cluster.apps[0].submit("post", f"post-{k}")
            from smartbft_tpu.testing.app import wait_for

            await wait_for(
                lambda: cluster.committed(cluster.apps[0])
                >= 6 + report.spike_acked + 4,
                cluster.scheduler, 60.0,
            )
            cluster.scan_latency_commits()
            cluster.latency.end_phase()
            assert report.spike_offered > 0
            assert report.spike_shed_admission > 0, (
                f"spike never shed: {report}"
            )
            assert report.spike_acked > 0
            # bound = capacity + n: forwarded requests (follower -> leader
            # after forward_timeout) legitimately bypass the gate and may
            # park briefly as waiters on a full leader pool — bounded,
            # just not by the client-facing high-water mark alone
            assert report.spike_peak_occupancy <= pool_size + cluster.n, (
                f"pool occupancy {report.spike_peak_occupancy} grew past "
                f"capacity {pool_size} + forwarding transients {cluster.n}"
            )
            Invariants.fork_free(cluster)
            Invariants.exactly_once(cluster)
            # p99 recovers once the spike stops (scan_commits resolves the
            # post-spike stamps through the run loop's ledger scan)
            snap = cluster.latency.snapshot()
            spike_p99 = snap["phases"]["spike"]["p99_ms"]
            after_p99 = snap["phases"]["after"]["p99_ms"]
            assert snap["phases"]["after"]["count"] > 0
            # one √2 histogram bucket of quantization slack: admission
            # keeps admitted-request latency near baseline even mid-spike,
            # so the phases can be legitimately equal
            assert after_p99 <= max(spike_p99 * 1.5, 1.0), snap["phases"]
        finally:
            await cluster.stop()

    asyncio.run(run())


# -- the pump on the wall clock ------------------------------------------------

def test_open_loop_pump_paces_on_the_wall_clock(tmp_path):
    """One tiny WALL-clock point through ``run_open_loop(wall=True)``
    under a ``WallClockDriver`` (n=4, 150 /s for 1 s): the branch
    ``load_spike`` and every gate above never take (they advance the
    logical scheduler).  Arrivals follow real time, every one is
    accounted for, and the committed stream stamps their latency."""
    import time

    from smartbft_tpu.utils.clock import WallClockDriver

    def cfg(_s, i):
        return dataclasses.replace(
            sharded_config(i, depth=2),
            request_pool_size=64, admission_high_water=0.8,
            request_pool_submit_timeout=1.0,
            request_batch_max_count=8, request_batch_max_interval=0.02,
        )

    async def run():
        cluster = ShardedCluster(str(tmp_path), shards=1, n=4, depth=2,
                                 window=0.005, config_fn=cfg, seed=17)
        driver = WallClockDriver(cluster.scheduler, tick_interval=0.005)
        driver.start()
        await cluster.start()
        try:
            t0 = time.monotonic()
            stats = await run_open_loop(
                cluster, rate=150.0, duration=1.0, drain=1.5, seed=31,
                clients=ZipfClients(64, skew=1.1), wall=True, step=0.005,
            )
            took = time.monotonic() - t0
            lat = cluster.set.latency.snapshot()
            cluster.check_invariants()
        finally:
            await cluster.stop()
            await driver.stop()
        # a Poisson draw of mean 150, five sigma either side
        assert 89 <= stats.offered <= 211, stats.block()
        assert took >= 1.0, f"a 1 s span of arrivals took {took:.2f} s"
        assert stats.acked + stats.shed + stats.failed == stats.offered
        assert stats.failed == 0 and stats.acked > 0, stats.block()
        assert lat["count"] > 0 and 0 < lat["p99_ms"] < 1e6, lat

    asyncio.run(run())


# -- config plumbing ----------------------------------------------------------

def test_admission_config_validation_and_pool_wiring():
    with pytest.raises(ConfigError, match="admission_high_water"):
        Configuration(self_id=1, admission_high_water=0.0).validate()
    with pytest.raises(ConfigError, match="admission_high_water"):
        Configuration(self_id=1, admission_high_water=1.5).validate()
    Configuration(self_id=1, admission_high_water=0.8).validate()
    Configuration(self_id=1).validate()  # default 1.0 (gate off) is valid


def test_zipf_and_pump_shapes():
    import random

    z = ZipfClients(64, skew=1.1)
    rng = random.Random(3)
    counts: dict = {}
    for _ in range(4000):
        cid = z.sample(rng)
        counts[cid] = counts.get(cid, 0) + 1
    # rank-1 dominance: the hottest client draws a large multiple of the
    # uniform share (1/64 ~ 62 of 4000)
    assert counts.get("zipf0", 0) > 300
    assert abs(z.hot_fraction(64) - 1.0) < 1e-9
    pump = OpenLoopPump(100.0, random.Random(1), start=0.0)
    total = sum(pump.due(t / 10.0) for t in range(1, 101))  # 10 seconds
    assert 800 <= total <= 1200  # Poisson(1000) within 6 sigma
    # open-loop: a stalled loop gets the whole backlog, nothing skipped
    pump2 = OpenLoopPump(100.0, random.Random(2), start=0.0)
    assert 800 <= pump2.due(10.0) <= 1200
