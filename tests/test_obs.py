"""Flight recorder (ISSUE 12): ring bound, VC decomposition, overhead.

Tier-1 gates for the observability plane:

* :class:`~smartbft_tpu.obs.TraceRecorder` — bounded ring semantics,
  injectable clock, disabled-recorder contract, dump/report round-trip;
* :class:`~smartbft_tpu.obs.ViewChangePhaseTracker` — sub-phase sums
  equal the end-to-end total by construction (unit + live cluster);
* the tracing-DISABLED overhead gate: the disabled guard is off the hot path
  (microbench pin) and an identical workload with tracing enabled stays
  within a small factor of disabled (paired end-to-end run);
* the task-audit-style memory pin: under a chaos soak segment the ring
  buffer never exceeds its cap even though many times more events were
  recorded;
* the chaos-runner regression: a forced invariant failure produces a
  parseable per-replica dump the report tool renders.
"""

import asyncio
import dataclasses
import json
import time

import pytest

from smartbft_tpu.metrics import InMemoryProvider, MetricsBundle
from smartbft_tpu.obs import (
    TraceRecorder,
    ViewChangePhaseTracker,
    assemble_trace_block,
    assemble_viewchange_block,
)
from smartbft_tpu.obs.report import load_dump, render
from smartbft_tpu.testing.app import fast_config, wait_for

from tests.test_basic import make_nodes, start_all, stop_all


# ---------------------------------------------------------------------------
# recorder units
# ---------------------------------------------------------------------------


def test_ring_buffer_bounds_memory_and_counts_drops():
    rec = TraceRecorder(capacity=8, node="n1")
    for i in range(30):
        rec.record("req.pool", key=f"c:{i}", seq=i)
    events = rec.events()
    assert len(events) == 8  # never exceeds the cap
    assert rec.recorded == 30
    assert rec.dropped == 22
    # chronological order, newest survive
    assert [e.seq for e in events] == list(range(22, 30))
    assert [e["seq"] for e in rec.snapshot(last=3)] == [27, 28, 29]
    # last=0 means "the newest zero events", never the whole buffer
    assert rec.snapshot(last=0) == []


def test_injectable_clock_and_exact_span_stats():
    t = {"now": 10.0}
    rec = TraceRecorder(clock=lambda: t["now"], capacity=16)
    rec.record("verify.launch", launch=1, dur=0.010)
    t["now"] = 11.0
    rec.record("verify.launch", launch=2, dur=0.030)
    assert [e.t for e in rec.events()] == [10.0, 11.0]
    block = rec.trace_block()
    assert block["enabled"] and block["kinds"]["verify.launch"] == 2
    span = block["spans"]["verify.launch"]
    # sum, count and EXACT quantiles of what the ring holds — no buckets
    assert span["count"] == 2 and span["sum_ms"] == 40.0
    assert span["p50_ms"] == 30.0 and span["max_ms"] == 30.0


def test_kind_cap_folds_overflow():
    rec = TraceRecorder(capacity=16, kinds_cap=2)
    for i in range(4):
        rec.record(f"kind-{i}", dur=0.001)
    assert rec.kind_counts == {"kind-0": 1, "kind-1": 1, "_other": 2}


def test_disabled_recorder_is_real_and_inert():
    """Tracing off means a REAL recorder with ``enabled`` False (the
    profiler switch can turn it on), not a shared nop: sites never reach
    it, and it reads as empty."""
    rec = TraceRecorder(enabled=False)
    assert rec.enabled is False and rec.forced is False
    assert rec.events() == [] and rec.recorded == 0
    assert rec.trace_block() == {"enabled": False}


def test_assemble_trace_block_merges_exactly():
    a = TraceRecorder(capacity=8, node="a")
    b = TraceRecorder(capacity=8, node="b")
    for _ in range(3):
        a.record("req.pool", dur=0.001)
    for _ in range(5):
        b.record("req.pool", dur=0.004)
    off = TraceRecorder(enabled=False)
    block = assemble_trace_block([a, b, off])
    assert block["enabled"] and block["recorders"] == 2
    assert block["recorded"] == 8
    assert block["kinds"] == {"req.pool": 8}
    assert block["spans"]["req.pool"]["count"] == 8
    # disabled-only input degrades honestly
    empty = assemble_trace_block([off])
    assert empty["enabled"] is False and empty["recorded"] == 0


# ---------------------------------------------------------------------------
# VC phase tracker units
# ---------------------------------------------------------------------------


def test_vc_phase_sums_equal_end_to_end_total():
    t = {"now": 0.0}

    def clock():
        return t["now"]

    tr = ViewChangePhaseTracker(clock=clock, node="n1")
    tr.armed(1)
    t["now"] = 0.5
    tr.joined(1)
    t["now"] = 0.7
    tr.viewdata_sent(1)
    t["now"] = 1.9
    tr.viewdata_quorum(1)
    t["now"] = 2.0
    tr.newview_done(1)
    t["now"] = 2.25
    tr.decision(1)
    assert not tr.open and tr.completed_total == 1
    (rec,) = tr.records()
    assert rec["view"] == 1
    assert rec["phases"] == {
        "complain": 500.0, "depose": 200.0, "viewdata_collect": 1200.0,
        "newview": 100.0, "first_commit": 250.0,
    }
    assert abs(sum(rec["phases"].values()) - rec["total_ms"]) < 1e-6
    # follower shape: no viewdata_quorum mark, sums still consistent
    tr.armed(2)
    t["now"] = 3.0
    tr.joined(2)
    tr.viewdata_sent(2)
    t["now"] = 3.5
    tr.newview_done(2)
    t["now"] = 4.0
    tr.decision(2)
    rec2 = tr.records()[-1]
    assert "viewdata_collect" not in rec2["phases"]
    assert abs(sum(rec2["phases"].values()) - rec2["total_ms"]) < 1e-6

    block = assemble_viewchange_block([tr])
    assert block["count"] == 2 and block["sums_consistent"]
    assert block["dominant_phase"] in block["phases"]
    shares = sum(p["share"] for p in block["phases"].values())
    assert 0.99 <= shares <= 1.01


def test_vc_tracker_rearm_and_sync_abandon():
    t = {"now": 0.0}
    tr = ViewChangePhaseTracker(clock=lambda: t["now"])
    tr.armed(1)
    t["now"] = 1.0
    tr.armed(2)  # timeout escalation: new round, old one abandoned
    assert tr.rounds == 2 and tr.abandoned == 1 and tr.open
    tr.abandoned_by_sync(2)  # sync installed the view around the pipeline
    assert tr.abandoned == 2 and not tr.open
    # a decision with no open round is a no-op (the controller hot path)
    tr.decision(5)
    assert tr.completed_total == 0


def test_vc_tracker_ignores_out_of_pipeline_decision():
    tr = ViewChangePhaseTracker(clock=time.monotonic)
    tr.armed(3)
    tr.joined(3)
    # no newview mark yet: a delivery cannot close the round
    tr.decision(3)
    assert tr.open and tr.completed_total == 0


# ---------------------------------------------------------------------------
# report tool
# ---------------------------------------------------------------------------


def test_report_renders_dump_round_trip(tmp_path):
    rec = TraceRecorder(capacity=64, node="n1")
    rec.record("req.submit", key="c:r0")
    rec.record("req.pool", key="c:r0", dur=0.002)
    rec.record("req.deliver", key="c:r0", view=0, seq=1)
    rec.record("verify.launch", launch=1, dur=0.015)
    path = rec.dump_to(str(tmp_path / "flight-n1.json"))
    dump = load_dump(path)
    assert dump["node"] == "n1" and len(dump["events"]) == 4
    text = render([dump])
    assert "req.deliver" in text and "span summary" in text
    # derived submit→deliver span joined by request key
    assert "req.submit->deliver" in text
    # CLI entry point renders the same dump
    from smartbft_tpu.obs.report import main

    assert main([path, "--summary-only"]) == 0


def test_report_counts_handed_over_and_dropped_forwards(tmp_path):
    """The forwards line: hand-overs (also a row of the span summary,
    their duration being the time pooled) and the forwards a replica
    dropped because it did not lead; absent where neither happened."""
    old = TraceRecorder(capacity=64, node="n1")
    for k, pooled in enumerate((0.004, 0.020, 0.041)):
        old.record("req.handover", key=f"c:r{k}", dur=pooled)
    new = TraceRecorder(capacity=64, node="n2")
    new.record("req.not_leader", key="c:r0", extra={"sender": 1})
    dumps = [load_dump(r.dump_to(str(tmp_path / f"flight-{r.node}.json")))
             for r in (old, new)]
    text = render(dumps, summary_only=True)
    assert "forwards: 3 handed over with the lead, 1 dropped at a " \
           "replica that did not lead" in text
    row = [ln for ln in text.splitlines() if ln.strip().startswith("req.handover")]
    assert row and row[0].split()[1] == "3"
    quiet = TraceRecorder(capacity=64, node="n3")
    quiet.record("req.submit", key="c:r9")
    assert "forwards:" not in render(
        [load_dump(quiet.dump_to(str(tmp_path / "flight-n3.json")))])


# ---------------------------------------------------------------------------
# live cluster: a real view change decomposes
# ---------------------------------------------------------------------------


def _vc_config(i):
    return dataclasses.replace(
        fast_config(i),
        leader_heartbeat_timeout=2.0,
        leader_heartbeat_count=10,
        view_change_timeout=8.0,
        view_change_resend_interval=2.0,
    )


def test_live_view_change_is_decomposed_and_traced(tmp_path):
    """Disconnect the leader of a traced n=4 cluster: the survivors'
    phase trackers must record a completed VC whose sub-phase sums equal
    its end-to-end total, the flight recorder must carry the vc.* and
    request-lifecycle events, and the wired ViewChangeMetrics must show
    complaint traffic without the trace enabled."""

    async def run():
        apps, scheduler, network, shared = make_nodes(
            4, tmp_path, config_fn=_vc_config
        )
        recorders = {}
        for a in apps:
            recorders[a.id] = a.recorder = TraceRecorder(
                clock=scheduler.now, node=f"n{a.id}", capacity=2048
            )
            a.metrics = MetricsBundle(InMemoryProvider())
        await start_all(apps)
        await apps[0].submit("c", "r0")
        await wait_for(lambda: all(a.height() >= 1 for a in apps), scheduler)
        apps[0].disconnect()
        await wait_for(
            lambda: all(a.consensus.get_leader_id() == 2 for a in apps[1:]),
            scheduler, timeout=120.0,
        )
        await apps[1].submit("c", "r1")
        await wait_for(
            lambda: all(a.height() >= 2 for a in apps[1:]),
            scheduler, timeout=120.0,
        )
        trackers = [a.consensus.vc_phases for a in apps[1:]]
        await stop_all(apps[1:])
        await apps[0].stop()

        completed = [t for t in trackers if t.completed_total >= 1]
        assert completed, "no survivor completed a tracked view change"
        for t in completed:
            for rec in t.records():
                assert abs(sum(rec["phases"].values())
                           - rec["total_ms"]) < 1e-6
        block = assemble_viewchange_block(trackers)
        assert block["count"] >= 1 and block["sums_consistent"]
        assert block["dominant_phase"] is not None
        assert block["end_to_end"]["p99_ms"] > 0
        # recorder timeline: lifecycle + VC events landed
        kinds = set()
        for r in recorders.values():
            kinds.update(e.kind for e in r.events())
        assert "req.submit" in kinds and "req.deliver" in kinds
        assert "vc.armed" in kinds and "vc.newview" in kinds
        assert "vc.complete" in kinds
        # satellite: the wired ViewChangeMetrics saw VC health without
        # needing the trace
        counters = apps[1].metrics.provider.counters
        assert counters["consensus.viewchange.count_complaints_sent"] >= 1
        assert counters["consensus.viewchange.count_complaints_received"] >= 1
        assert counters["consensus.viewchange.count_rounds"] >= 1
        gauges = apps[1].metrics.provider.gauges
        assert gauges["consensus.viewchange.time_in_view_change_seconds"] > 0

    asyncio.run(run())


# ---------------------------------------------------------------------------
# overhead gates (tracing must be off the hot path when disabled)
# ---------------------------------------------------------------------------


def test_disabled_guard_microbench():
    """The instrumentation guard (`if rec.enabled:`) with a disabled
    recorder must cost well under a microsecond per site."""
    rec = TraceRecorder(enabled=False)
    n = 200_000
    t0 = time.perf_counter()
    hits = 0
    for _ in range(n):
        if rec.enabled:
            hits += 1
    per_op = (time.perf_counter() - t0) / n
    assert hits == 0
    assert per_op < 1.5e-6, f"disabled guard costs {per_op * 1e9:.0f} ns/op"


async def _paired_commit_run(tmp_path, tag: str, trace: bool) -> float:
    """One fixed toy workload through the sharded front door (shared
    coalescer = the instrumented verify plane); returns wall seconds."""
    from smartbft_tpu.testing.sharded import ShardedCluster

    cluster = ShardedCluster(
        str(tmp_path / tag), shards=1, n=4, depth=2, crypto="trivial",
        window=0.002, trace=trace,
    )
    await cluster.start()
    try:
        t0 = time.perf_counter()
        for j in range(24):
            await cluster.submit(cluster.client_for_shard(0, j % 3), f"r{j}")
        await wait_for(
            lambda: cluster.committed_requests() >= 24,
            cluster.scheduler, 120.0,
        )
        return time.perf_counter() - t0
    finally:
        await cluster.stop()


def test_tracing_overhead_within_bound(tmp_path):
    """Identical workload, tracing off vs on: enabled must stay within a
    small factor of disabled (min-of-2 against scheduler jitter).  The
    recorder is bounded-memory appends — if this gate trips, an
    instrumentation site grew real work."""

    async def run():
        offs, ons = [], []
        for rep in range(2):
            offs.append(await _paired_commit_run(tmp_path, f"off{rep}", False))
            ons.append(await _paired_commit_run(tmp_path, f"on{rep}", True))
        t_off, t_on = min(offs), min(ons)
        assert t_on <= t_off * 2.0 + 0.5, (
            f"tracing-enabled run {t_on:.3f}s vs disabled {t_off:.3f}s "
            f"— recorder is on the hot path"
        )

    asyncio.run(run())


# ---------------------------------------------------------------------------
# chaos: bounded memory pin + dump regression
# ---------------------------------------------------------------------------


def test_recorder_bounded_and_dump_renders_under_chaos(tmp_path):
    """A traced chaos segment (leader mute → depose → heal) with a tiny
    ring cap (48; 32 until a rotation's hand-over got its marks, which
    pushed the one surviving ``vc.`` mark out of the newest 32): every
    replica's buffer stays at/below the cap while far
    more events were recorded (the wrap really happened), a FORCED
    invariant failure dumps per-replica artifacts, and the report tool
    renders them."""
    from smartbft_tpu.testing.chaos import (
        ChaosCluster,
        Invariants,
        check_with_flight_dump,
        mute_leader_schedule,
    )

    async def run():
        cluster = ChaosCluster(
            str(tmp_path), n=4, depth=1, rotation=True, trace=True,
            trace_capacity=48,
        )
        await cluster.start()
        try:
            report = await cluster.run_schedule(
                mute_leader_schedule(), requests=12, settle_timeout=300.0
            )
            Invariants.fork_free(cluster)
            Invariants.exactly_once(cluster, expected=12)
        finally:
            await cluster.stop()
        assert report.final_committed >= 12

        # task-audit-style memory pin: the ring never exceeds its cap,
        # and it genuinely wrapped under the soak segment's traffic
        assert any(r.recorded > 48 for r in cluster.recorders.values()), \
            "chaos segment recorded too few events to exercise the bound"
        for rec in cluster.recorders.values():
            assert len(rec.events()) <= 48
            assert rec.dropped == max(0, rec.recorded - 48)

        # forced invariant failure -> parseable dump -> report renders
        out_dir = tmp_path / "flight"
        with pytest.raises(AssertionError):
            check_with_flight_dump(
                cluster,
                lambda: Invariants.exactly_once(cluster, expected=10 ** 6),
                out_dir=str(out_dir),
            )
        paths = sorted(out_dir.glob("flight-*.json"))
        assert len(paths) >= 4
        dumps = [load_dump(str(p)) for p in paths]
        text = render(dumps, last=200)
        assert "span summary" in text and "vc." in text

    asyncio.run(run())
