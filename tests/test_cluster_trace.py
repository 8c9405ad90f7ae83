"""Cluster tracing (ISSUE 13): FT_TRACE sidecar, clock-aligned merge,
incremental trace pulls, detection gauges, WAL spans, and the tier-1
wire-tracing overhead gate.

The sidecar contract under test: arming a replica's flight recorder arms
the wire sidecar; the canonical consensus encoding and the data-frame
counts are IDENTICAL traced vs untraced (at most ONE extra FT_TRACE
frame per write-coalesced flush); a socket run with tracing on stays
within 2x the untraced wall clock (min-of-2, the PR 12 idiom).
"""

import asyncio
import time

from smartbft_tpu.codec import decode, encode
from smartbft_tpu.metrics import MetricsBundle, PrometheusProvider
from smartbft_tpu.net.framing import (
    _KNOWN_TYPES,
    FT_TRACE,
    FrameDecoder,
    TraceCtx,
    TraceFrame,
    encode_frame,
)
from smartbft_tpu.obs import (
    TraceRecorder,
    ViewChangePhaseTracker,
    assemble_viewchange_block,
)
from smartbft_tpu.obs.report import link_summary, merged_events
from smartbft_tpu.testing.app import wait_for

from tests.test_net_transport import _committed, make_socket_apps


# ---------------------------------------------------------------------------
# framing: the sidecar frame is a first-class untagged frame type
# ---------------------------------------------------------------------------


def test_trace_frame_round_trip_and_decoder():
    tf = TraceFrame(
        origin=3,
        sent_us=1234567,
        entries=[
            TraceCtx(kind="PrePrepare", view=2, seq=9, origin=3, hop=1),
            TraceCtx(kind="request", key="c:r1", origin=1, hop=2),
        ],
    )
    assert FT_TRACE in _KNOWN_TYPES
    frames = FrameDecoder().feed(encode_frame(FT_TRACE, encode(tf)))
    assert len(frames) == 1
    ftype, payload = frames[0]
    assert ftype == FT_TRACE
    assert decode(TraceFrame, payload) == tf


# ---------------------------------------------------------------------------
# recorder: the incremental event-sequence cursor (cmd=trace since)
# ---------------------------------------------------------------------------


def test_events_since_cursor_semantics():
    rec = TraceRecorder(capacity=4, node="n1")
    for i in range(3):
        rec.record("k", seq=i)
    events, cur = rec.events_since(0)
    assert [e.seq for e in events] == [0, 1, 2] and cur == 3
    # nothing new at the cursor; new events after it ship exactly once
    events, cur2 = rec.events_since(cur)
    assert events == [] and cur2 == 3
    rec.record("k", seq=3)
    events, cur3 = rec.events_since(cur)
    assert [e.seq for e in events] == [3] and cur3 == 4


def test_events_since_survives_ring_wrap_and_future_cursor():
    rec = TraceRecorder(capacity=4, node="n1")
    for i in range(10):
        rec.record("k", seq=i)
    # a puller that fell behind gets only the surviving tail
    events, cur = rec.events_since(2)
    assert [e.seq for e in events] == [6, 7, 8, 9] and cur == 10
    # a stale/future cursor stays put at "nothing new", never negatives
    assert rec.events_since(99) == ([], 99)
    assert TraceRecorder(enabled=False).events_since(0) == ([], 0)
    # the exact-seqno contract: events carry their own all-time sequence,
    # so a snapshot racing a concurrent record can never skip or
    # double-ship (the WAL-executor-thread hazard)
    assert [e.seqno for e in rec.events()] == [7, 8, 9, 10]


# ---------------------------------------------------------------------------
# clock-aligned merge + per-link network time (pure, synthetic)
# ---------------------------------------------------------------------------


def test_merged_events_applies_clock_offsets():
    dumps = [
        {"node": "n1", "clock_offset_s": 0.5,
         "events": [{"t": 10.5, "kind": "a"}]},
        {"node": "n2", "clock_offset_s": -0.25,
         "events": [{"t": 9.76, "kind": "b"}]},
        {"node": "n3", "events": [{"t": 10.005, "kind": "c"}]},
    ]
    events = merged_events(dumps)
    # n1's 10.5 - 0.5 = 10.0 first; n3 unshifted; n2's 9.76 + 0.25 last
    assert [e["kind"] for e in events] == ["a", "c", "b"]
    assert abs(events[0]["t"] - 10.0) < 1e-9
    assert events[0]["node"] == "n1"


def test_link_summary_recovers_hop_time_through_skew():
    """Sender n1 runs 0.5s ahead; its flush stamp maps through ITS
    offset, the receiver event is already aligned — the recovered hop
    time is the true 3ms despite 500ms of skew."""
    offsets = {"n1": 0.5, "n2": -0.25}
    sent_parent = 100.0              # true send instant (parent clock)
    sent_us = int((sent_parent + offsets["n1"]) * 1e6)  # sender's clock
    recv_aligned = sent_parent + 0.003
    events = [{
        "t": recv_aligned, "kind": "net.recv", "node": "n2",
        "extra": {"from": 1, "sent_us": sent_us, "hop": 1, "origin": 1},
    }]
    rows = link_summary(events, offsets)
    assert len(rows) == 1
    assert rows[0]["link"] == "n1->n2"
    assert abs(rows[0]["p50_ms"] - 3.0) < 0.01
    assert rows[0]["clamped"] == 0


# ---------------------------------------------------------------------------
# clock-offset merge edge cases (ISSUE 14 satellite): negative offsets,
# err_bound exceeding the hop time, a node missing offset data
# ---------------------------------------------------------------------------


def test_merged_events_with_negative_offsets_keep_causal_order():
    """A replica whose clock runs BEHIND the parent's has a negative
    offset; the merge must shift its events FORWARD (t - offset adds)
    and keep the cross-node order causal."""
    dumps = [
        {"node": "n1", "clock_offset_s": -0.4,
         "events": [{"t": 9.7, "kind": "send"}]},     # true t = 10.1
        {"node": "n2", "clock_offset_s": -0.1,
         "events": [{"t": 9.95, "kind": "recv"}]},    # true t = 10.05
    ]
    events = merged_events(dumps)
    assert [e["kind"] for e in events] == ["recv", "send"]
    assert abs(events[1]["t"] - 10.1) < 1e-9


def test_link_summary_clamps_negative_network_time():
    """On loopback the offset error bound (RTT/2) exceeds the real hop
    time, so the recovered per-link value can come out NEGATIVE — it
    must be clamped to 0 and COUNTED, never published as a physically
    impossible measurement."""
    offsets = {"n1": 0.0, "n2": 0.0}
    sent_parent = 50.0
    # the skew error makes the receive stamp land 2ms BEFORE the send
    events = [
        {"t": sent_parent - 0.002, "kind": "net.recv", "node": "n2",
         "extra": {"from": 1, "sent_us": int(sent_parent * 1e6),
                   "hop": 1, "origin": 1}},
        {"t": sent_parent + 0.001, "kind": "net.recv", "node": "n2",
         "extra": {"from": 1, "sent_us": int(sent_parent * 1e6),
                   "hop": 1, "origin": 1}},
    ]
    (row,) = link_summary(events, offsets)
    assert row["count"] == 2
    assert row["clamped"] == 1
    # every published value is non-negative after the clamp
    assert min(row["p50_ms"], row["p95_ms"], row["p99_ms"],
               row["max_ms"]) >= 0.0


def test_missing_offset_node_degrades_loudly():
    """A node absent from the offsets file merges UNALIGNED (no silent
    assumed-zero skew): its events still appear on the timeline, its
    per-link rows are excluded in BOTH directions, and the render says
    so out loud."""
    from smartbft_tpu.obs.report import render

    sent_us = int(20.0 * 1e6)
    dumps = [
        {"node": "n1", "clock_offset_s": 0.1, "offset_known": True,
         "events": [
             {"t": 20.002, "kind": "net.recv", "node": "n1",
              "extra": {"from": 3, "sent_us": sent_us, "hop": 1,
                        "origin": 3}},
         ]},
        # n3 has NO offset estimate (its ping failed mid-sweep)
        {"node": "n3", "clock_offset_s": 0.0, "offset_known": False,
         "events": [
             {"t": 20.001, "kind": "net.recv", "node": "n3",
              "extra": {"from": 1, "sent_us": sent_us, "hop": 1,
                        "origin": 1}},
             {"t": 20.5, "kind": "req.deliver", "key": "c:1"},
         ]},
    ]
    events = merged_events(dumps)
    assert len(events) == 3              # n3's events still merge
    offsets = {"n1": 0.1}                # n3 deliberately absent
    rows = link_summary(events, offsets)
    # both directions touch n3's unestimated clock: no rows published
    assert rows == []
    out = render(dumps)
    assert "WARNING" in out and "n3" in out
    assert "UNALIGNED" in out


def test_report_offsets_file_marks_missing_nodes(tmp_path):
    """The --offsets CLI path: a node absent from the offsets file gets
    offset_known=False and the render warns."""
    import json

    from smartbft_tpu.obs import report as report_mod

    d1 = tmp_path / "flight-n1.json"
    d2 = tmp_path / "flight-n9.json"
    d1.write_text(json.dumps({
        "node": "n1", "events": [{"t": 1.0, "kind": "a"}]
    }))
    d2.write_text(json.dumps({
        "node": "n9", "events": [{"t": 1.5, "kind": "b"}]
    }))
    offs = tmp_path / "offsets.json"
    offs.write_text(json.dumps({"n1": {"offset_s": 0.25}}))
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = report_mod.main([str(d1), str(d2), "--offsets", str(offs)])
    assert rc == 0
    out = buf.getvalue()
    assert "clock-aligned" in out
    assert "WARNING" in out and "n9" in out


# ---------------------------------------------------------------------------
# the wire sidecar on a live socket cluster (one process, real UDS)
# ---------------------------------------------------------------------------


def _arm_tracing(apps):
    recorders = []
    for app in apps:
        rec = TraceRecorder(clock=time.monotonic, node=f"n{app.id}",
                            capacity=8192)
        app.recorder = rec
        app.comm.recorder = rec
        app.comm.request_key_fn = \
            lambda raw, a=app: str(a.request_id(raw))
        recorders.append(rec)
    return recorders


async def _socket_run(tmp_path, tag: str, traced: bool):
    apps, scheduler = make_socket_apps(4, tmp_path / tag)
    recorders = _arm_tracing(apps) if traced else []
    for a in apps:
        await a.start()
    try:
        t0 = time.perf_counter()
        total = 16
        for k in range(total):
            await apps[k % 4].submit("trace-cli", f"req-{k}")
        await wait_for(
            lambda: all(_committed(a) >= total for a in apps),
            scheduler, 60.0,
        )
        elapsed = time.perf_counter() - t0
    finally:
        for a in apps:
            await a.stop()
    snaps = [a.comm.transport_snapshot() for a in apps]
    return elapsed, snaps, recorders


def test_wire_tracing_sidecar_and_overhead_gate(tmp_path):
    """The tier-1 gate: an n=4 socket run with trace context on vs off
    stays within 2x wall-clock (min-of-2), the sidecar adds at most ONE
    frame per coalesced flush (frames-on-wire delta bound), and the
    receive side records net.recv hop events carrying the sender's
    flush stamp."""

    async def run():
        offs, ons, on_state = [], [], None
        for rep in range(2):
            t, _, _ = await _socket_run(tmp_path, f"off{rep}", False)
            offs.append(t)
            t, snaps, recorders = await _socket_run(tmp_path, f"on{rep}",
                                                    True)
            ons.append(t)
            on_state = (snaps, recorders)
        t_off, t_on = min(offs), min(ons)
        assert t_on <= t_off * 2.0 + 0.5, (
            f"wire tracing {t_on:.3f}s vs untraced {t_off:.3f}s — the "
            f"sidecar grew real hot-path work"
        )
        snaps, recorders = on_state
        for snap in snaps:
            # frames-on-wire delta bound: ≤ 1 sidecar per flush, and the
            # data-frame count is untouched by construction
            assert snap["trace_frames_sent"] <= snap["flush_batches"]
            assert snap["trace_frames_received"] > 0
            assert snap["malformed_frames"] == 0
        events = [e for r in recorders for e in r.snapshot()]
        recvs = [e for e in events if e["kind"] == "net.recv"]
        assert recvs, "no sidecar hop events recorded"
        kinds = {e["extra"]["wire"] for e in recvs}
        assert "Prepare" in kinds or "Commit" in kinds
        for e in recvs:
            assert e["extra"]["sent_us"] > 0
            assert e["extra"]["hop"] >= 1
        # one process = one clock: link times come out sane (< 5s, >= 0
        # after the µs truncation) with NO offsets needed
        rows = link_summary(sorted(events, key=lambda e: e["t"]), {})
        assert rows and all(-1.0 <= r["p50_ms"] < 5000.0 for r in rows)
        # the critical path decomposes over the socket timeline too
        from smartbft_tpu.obs import assemble_critical_path_block

        block = assemble_critical_path_block(
            sorted(events, key=lambda e: e["t"]))
        assert block["requests_decomposed"] > 0
        assert block["sums_consistent"] is True

    asyncio.run(run())


def test_request_forward_continues_hop_chain():
    """A request context received over the wire and re-forwarded keeps
    its ORIGIN and increments the hop counter (the causal chain of
    client entry -> forwarder -> leader)."""
    from tests.test_net_transport import _Sink, _addrs
    from smartbft_tpu.net.transport import SocketComm

    addrs = _addrs(2, "uds")

    async def run():
        a = SocketComm(1, addrs[1], {2: addrs[2]}, cluster_key=b"k",
                       backoff_base=0.01, backoff_max=0.1)
        b = SocketComm(2, addrs[2], {1: addrs[1]}, cluster_key=b"k",
                       backoff_base=0.01, backoff_max=0.1)
        rec_a = TraceRecorder(node="n1")
        rec_b = TraceRecorder(node="n2")
        a.recorder, b.recorder = rec_a, rec_b
        a.request_key_fn = lambda raw: "cli:r0"
        b.request_key_fn = lambda raw: "cli:r0"
        a.attach(_Sink())
        b.attach(_Sink())
        await a.start()
        await b.start()
        try:
            a.send_transaction(2, b"payload")
            await asyncio.sleep(0.3)
            recvs = [e for e in rec_b.snapshot()
                     if e["kind"] == "net.recv"]
            assert recvs and recvs[0]["key"] == "cli:r0"
            assert recvs[0]["extra"] == dict(
                recvs[0]["extra"], origin=1, hop=1)
            # b re-forwards the SAME request: origin stays 1, hop -> 2
            b.send_transaction(1, b"payload")
            await asyncio.sleep(0.3)
            recvs = [e for e in rec_a.snapshot()
                     if e["kind"] == "net.recv"]
            assert recvs
            assert recvs[0]["extra"]["origin"] == 1
            assert recvs[0]["extra"]["hop"] == 2
        finally:
            await a.close()
            await b.close()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# detection gauges (ROADMAP item 1): arm-to-fire + backlog at flip
# ---------------------------------------------------------------------------


def test_heartbeat_detection_feeds_tracker_and_metrics():
    from smartbft_tpu.core.heartbeat import FOLLOWER, HeartbeatMonitor
    from smartbft_tpu.core.view import ViewSequence, ViewSequencesHolder
    from smartbft_tpu.utils.logging import StdLogger

    fired = []

    class Handler:
        def on_heartbeat_timeout(self, view, leader):
            fired.append((view, leader))

        def sync(self):
            pass

    provider = PrometheusProvider()
    bundle = MetricsBundle(provider)
    clock = {"now": 0.0}
    tracker = ViewChangePhaseTracker(
        clock=lambda: clock["now"], node="n2",
        metrics=bundle.view_change,
    )
    vs = ViewSequencesHolder()
    vs.store(ViewSequence(view_active=True, proposal_seq=1))
    mon = HeartbeatMonitor(
        StdLogger("t"), 1.0, 10, None, 4, Handler(), vs, 10,
        vc_phases=tracker,
    )
    mon.change_role(FOLLOWER, 0, 1)
    mon.tick(0.0)
    mon.tick(0.4)   # silence accrues
    mon.tick(1.7)   # timeout fires: armed at t=0, fired at 1.7
    assert fired == [(0, 1)]
    assert tracker.detections_total == 1
    assert abs(tracker._detections[0] - 1700.0) < 1.0
    key = "consensus_viewchange_heartbeat_detection_seconds"
    assert abs(provider.gauges[key] - 1.7) < 0.01
    assert provider.counters[
        "consensus_viewchange_count_heartbeat_timeouts"] == 1

    # backlog at flip rides the completed-round record + the bench block
    tracker.armed(1)
    tracker.joined(1)
    tracker.viewdata_sent(1)
    tracker.newview_done(1)
    clock["now"] = 2.0
    tracker.decision(1, backlog=37)
    block = assemble_viewchange_block([tracker])
    assert block["detection"]["count"] == 1
    assert abs(block["detection"]["max_ms"] - 1700.0) < 1.0
    assert block["backlog_at_flip"] == {"count": 1, "p50": 37, "max": 37}
    assert provider.gauges[
        "consensus_viewchange_backlog_at_view_flip"] == 37


# ---------------------------------------------------------------------------
# WAL persistence spans (the one hot-path plane PR 12 left dark)
# ---------------------------------------------------------------------------


def test_wal_append_and_group_fsync_spans(tmp_path):
    import smartbft_tpu.wal as walmod

    async def run():
        wal, entries = walmod.initialize_and_read_all(
            str(tmp_path / "wal"), None
        )
        assert entries == []
        rec = TraceRecorder(node="n1")
        wal.attach_recorder(rec)
        # synchronous append: one wal.append span incl. its inline fsync
        wal.append(b"entry-0", False)
        # async append: write now, fsync in the group-commit wave
        await wal.append_async(b"entry-1", False)
        wal.close()
        kinds = [e["kind"] for e in rec.snapshot()]
        assert kinds.count("wal.append") == 2
        assert "wal.fsync" in kinds
        for e in rec.snapshot():
            assert e["dur_ms"] >= 0.0
        # the always-on histograms measured the same ops (recorder or not)
        block = wal.span_block()
        assert block["append"]["count"] == 2
        assert block["fsync"]["count"] >= 1

    asyncio.run(run())


# ---------------------------------------------------------------------------
# reshard generations: fresh recorder labels, no cross-generation merge
# ---------------------------------------------------------------------------


def test_reshard_generations_get_fresh_recorder_labels(tmp_path):
    """A retired-then-reborn shard id is a NEW consensus group: its
    recorders must carry a fresh generation label (s<S>g<G>n<i>), the
    merged timeline never files two generations under one label, and
    the critical-path join treats the generations as distinct (view,
    seq) scopes."""
    from smartbft_tpu.obs.critpath import _shard_of
    from smartbft_tpu.testing.chaos import (
        ChaosEvent,
        run_reshard_schedule,
    )
    from smartbft_tpu.testing.sharded import ShardedCluster

    async def run():
        cluster = ShardedCluster(
            str(tmp_path), shards=3, n=4, depth=2, crypto="trivial",
            window=0.002, seed=7, trace=True, collect_entries=True,
            reshard_drain_deadline=120.0,
        )
        await cluster.start()
        try:
            # retire shard id 2, then rebirth it (generation 1) — under
            # continuous front-door load so the barrier commits
            await run_reshard_schedule(
                cluster,
                [ChaosEvent(at=1.0, action="reshard", count=2),
                 ChaosEvent(at=6.0, action="reshard", count=3)],
                requests=18,
            )
            # land traffic on the REBORN shard id 2 so its generation-1
            # recorders carry pipeline events
            done = cluster.committed_requests()
            for j in range(3):
                await cluster.submit(cluster.client_for_shard(2, j),
                                     f"gen1-{j}")
            await wait_for(
                lambda: cluster.committed_requests() >= done + 3,
                cluster.scheduler, 120.0,
            )
        finally:
            await cluster.stop()
        labels = {r.node for r in cluster.trace_recorders()}
        assert "s2n1" in labels, labels
        assert "s2g1n1" in labels, "reborn shard kept the old label"
        # distinct critical-path scopes: the generations can never
        # interleave their (view, seq) spaces under one label
        assert _shard_of("s2n1") == "s2"
        assert _shard_of("s2g1n1") == "s2g1"
        # and the merged timeline keeps the two generations' events
        # under their own labels
        gen0 = [e for e in cluster.trace_events()
                if e.get("node") == "s2n1"]
        gen1 = [e for e in cluster.trace_events()
                if e.get("node") == "s2g1n1"]
        assert gen0 and gen1
        block = cluster.critical_path_block()
        assert block["requests_decomposed"] >= 3
        assert block["sums_consistent"] is True

    asyncio.run(run())
