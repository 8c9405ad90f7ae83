"""Observability surfaces (ISSUE 12 satellites): exposition + pulls.

* :class:`~smartbft_tpu.metrics.PrometheusProvider` text exposition —
  the renderer multi-process replicas now serve over ``cmd=metrics``;
* :class:`~smartbft_tpu.metrics.LogScaleHistogram` edge cases (empty,
  single observation, overflow past the top bucket, sparse-bucket JSON
  round-trip);
* the multi-process pull: ``ControlServer cmd=trace`` / ``cmd=metrics``
  against live socket replicas, and the dump the report tool renders.
"""

import json

import pytest

from smartbft_tpu.metrics import (
    LogScaleHistogram,
    MetricOpts,
    MetricsBundle,
    PrometheusProvider,
)


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------


def test_expose_renders_counters_gauges_histograms():
    p = PrometheusProvider()
    c = p.new_counter(MetricOpts(namespace="consensus", subsystem="pool",
                                 name="count_of_deleted_requests",
                                 help="requests deleted"))
    g = p.new_gauge(MetricOpts(namespace="consensus", subsystem="view",
                               name="number"))
    h = p.new_histogram(MetricOpts(namespace="consensus",
                                   subsystem="consensus",
                                   name="latency_sync"))
    c.add(3)
    g.set(7)
    h.observe(0.5)
    h.observe(1.5)
    text = p.expose()
    lines = text.splitlines()
    assert "# HELP consensus_pool_count_of_deleted_requests requests deleted" \
        in lines
    assert "# TYPE consensus_pool_count_of_deleted_requests counter" in lines
    assert "consensus_pool_count_of_deleted_requests 3" in lines
    assert "# TYPE consensus_view_number gauge" in lines
    assert "consensus_view_number 7" in lines
    assert "# TYPE consensus_consensus_latency_sync histogram" in lines
    assert 'consensus_consensus_latency_sync_bucket{le="+Inf"} 2' in lines
    assert "consensus_consensus_latency_sync_count 2" in lines
    assert "consensus_consensus_latency_sync_sum 2" in lines
    assert text.endswith("\n")


def test_expose_renders_labels():
    p = PrometheusProvider()
    c = p.new_counter(MetricOpts(namespace="consensus", subsystem="pool",
                                 name="count_of_failed_add_requests",
                                 label_names=("reason",)))
    c.with_labels("admission").add(2)
    c.with_labels("semaphore").add(1)
    text = p.expose()
    assert ('consensus_pool_count_of_failed_add_requests'
            '{reason="admission"} 2') in text
    assert ('consensus_pool_count_of_failed_add_requests'
            '{reason="semaphore"} 1') in text


def test_full_bundle_exposes_viewchange_health():
    """The wired ViewChangeMetrics (satellite 1) must be visible in the
    exposition a ControlServer serves: bundle + feed + render."""
    p = PrometheusProvider()
    bundle = MetricsBundle(p)
    bundle.view_change.count_complaints_sent.add(2)
    bundle.view_change.count_sync_escalations.add(1)
    bundle.view_change.time_in_view_change.set(1.25)
    text = p.expose()
    assert "consensus_viewchange_count_complaints_sent 2" in text
    assert "consensus_viewchange_count_sync_escalations 1" in text
    assert "consensus_viewchange_time_in_view_change_seconds 1.25" in text


# ---------------------------------------------------------------------------
# LogScaleHistogram edge cases (satellite 3)
# ---------------------------------------------------------------------------


def test_empty_histogram_quantiles_and_snapshot():
    h = LogScaleHistogram()
    assert h.quantile(0.5) == 0.0
    assert h.quantile(0.99) == 0.0
    snap = h.snapshot()
    assert snap["count"] == 0 and snap["p99_ms"] == 0.0 \
        and snap["mean_ms"] == 0.0 and snap["max_ms"] == 0.0
    assert h.nonzero_buckets() == {}


def test_single_observation_pins_every_quantile():
    h = LogScaleHistogram()
    h.observe(0.010)
    for q in (0.0, 0.5, 0.95, 0.99, 1.0):
        # midpoint clamped into the observed [min, max] envelope = exact
        assert h.quantile(q) == pytest.approx(0.010)
    snap = h.snapshot()
    assert snap["count"] == 1 and snap["p50_ms"] == pytest.approx(10.0)
    assert snap["max_ms"] == pytest.approx(10.0)


def test_overflow_past_top_bucket_clamps():
    h = LogScaleHistogram(low=1e-6, growth=2.0 ** 0.5, nbuckets=8)
    top_edge = 1e-6 * (2.0 ** 0.5) ** 8  # ~16 µs span: tiny on purpose
    h.observe(top_edge * 1e6)  # far past the top bucket
    h.observe(top_edge * 1e6)
    assert h.buckets[-1] == 2  # clamped into the last bucket, counted
    assert h.count == 2
    # quantile clamps to the observed max, never reports a bucket edge
    # below it or infinity
    assert h.quantile(0.99) == pytest.approx(top_edge * 1e6)
    # sub-low underflow lands in bucket 0 and clamps to observed min
    h2 = LogScaleHistogram()
    h2.observe(1e-9)
    assert h2.buckets[0] == 1
    assert h2.quantile(0.5) == pytest.approx(1e-9)


def test_nonzero_buckets_round_trip_through_json():
    h = LogScaleHistogram()
    for v in (0.001, 0.001, 0.004, 0.1, 5.0):
        h.observe(v)
    row = {"latency": {"histogram": h.nonzero_buckets()}}
    back = json.loads(json.dumps(row))["latency"]["histogram"]
    assert back == h.nonzero_buckets()
    assert sum(back.values()) == h.count
    # keys are the bucket upper edges in ms, parseable as floats
    edges = [float(k) for k in back]
    assert edges == sorted(edges)


def test_merge_from_is_exact_and_rejects_mismatched_geometry():
    a, b = LogScaleHistogram(), LogScaleHistogram()
    for v in (0.001, 0.010):
        a.observe(v)
    for v in (0.100, 1.0, 10.0):
        b.observe(v)
    merged = LogScaleHistogram()
    merged.merge_from(a)
    merged.merge_from(b)
    assert merged.count == 5
    assert merged.max_seen == pytest.approx(10.0)
    assert merged.min_seen == pytest.approx(0.001)
    one_by_one = LogScaleHistogram()
    for v in (0.001, 0.010, 0.100, 1.0, 10.0):
        one_by_one.observe(v)
    assert merged.buckets == one_by_one.buckets
    with pytest.raises(ValueError):
        merged.merge_from(LogScaleHistogram(nbuckets=8))


# ---------------------------------------------------------------------------
# multi-process pull: cmd=trace / cmd=metrics over the control channel
# ---------------------------------------------------------------------------


def test_socket_cluster_trace_and_metrics_pull(tmp_path):
    """A traced UDS cluster serves per-replica timelines (cmd=trace) and
    Prometheus exposition (cmd=metrics) over the control channel, and
    the pulled dump renders through the report tool."""
    from smartbft_tpu.net.cluster import SocketCluster
    from smartbft_tpu.obs.report import render

    with SocketCluster(tmp_path, n=4, transport="uds",
                       trace=True, trace_capacity=512) as cluster:
        leader = cluster.wait_leader()
        for k in range(3):
            cluster.submit(leader, "obs", f"req-{k}")
        cluster.wait_committed(3, timeout=60.0)

        # cmd=trace: the per-replica flight-recorder timeline
        resp = cluster.trace_pull(leader)
        assert resp["trace"]["enabled"] is True
        kinds = {e["kind"] for e in resp["events"]}
        assert "req.submit" in kinds and "req.deliver" in kinds
        tail = cluster.trace_pull(leader, last=2)["events"]
        assert len(tail) == 2

        # incremental pull (ISSUE 13): the since cursor ships only NEW
        # events on the next poll instead of re-sending the whole ring
        cursor = resp["next_since"]
        assert cursor >= len(resp["events"])
        again = cluster.trace_pull(leader, since=cursor)
        assert again["events"] == []
        cluster.submit(leader, "obs", "req-cursor")
        cluster.wait_committed(4, timeout=60.0)
        fresh = cluster.trace_pull(leader, since=cursor)
        assert 0 < len(fresh["events"]) < len(resp["events"]) + 16
        assert fresh["next_since"] > cursor

        # clock-offset estimation + ONE merged cluster timeline with
        # per-link network times (the FT_TRACE sidecar's receive side)
        offsets = cluster.estimate_clock_offsets()
        assert set(offsets) == {f"n{i}" for i in cluster.live_ids()}
        for o in offsets.values():
            assert o["rtt_s"] > 0
            assert abs(o["err_bound_s"] - o["rtt_s"] / 2.0) <= 1e-6
        timeline = cluster.cluster_timeline(str(tmp_path / "timeline"))
        assert timeline["events"] > 0
        assert timeline["hops"], "no per-link network times measured"
        for hop in timeline["hops"]:
            assert hop["count"] > 0
        assert (tmp_path / "timeline" / "offsets.json").exists()
        merged = render(timeline["dumps"], summary_only=True)
        assert "clock-aligned" in merged
        assert "per-link network time" in merged

        # cmd=metrics: Prometheus text exposition with live counters
        text = cluster.metrics_text(leader)
        assert "# TYPE consensus_view_number gauge" in text
        assert "consensus_viewchange_current_view" in text

        # an untraced follower still answers (trace block disabled shape
        # never happens here since every replica got trace=True; instead
        # verify every replica serves a parseable timeline)
        dumps = []
        for i in cluster.live_ids():
            r = cluster.trace_pull(i, last=256)
            dumps.append({"node": r["node"], "dropped": r.get("dropped", 0),
                          "events": r["events"]})
        text = render(dumps, summary_only=True)
        assert "span summary" in text

        # dump artifacts land on disk in the report tool's shape
        paths = cluster.dump_flight_recorders(str(tmp_path / "flight"))
        assert len(paths) == 4
        with open(paths[0]) as fh:
            dump = json.load(fh)
        assert dump["events"], "dump carries no events"


def test_untraced_replica_serves_disabled_trace_block(tmp_path):
    """trace off (the default): cmd=trace answers with the disabled
    block instead of erroring, and dump_flight_recorders is a no-op."""
    from smartbft_tpu.net.cluster import SocketCluster

    with SocketCluster(tmp_path, n=4, transport="uds") as cluster:
        leader = cluster.wait_leader()
        cluster.submit(leader, "obs", "req-0")
        cluster.wait_committed(1, timeout=60.0)
        resp = cluster.trace_pull(leader)
        assert resp["trace"] == {"enabled": False}
        assert resp["events"] == []
        assert cluster.dump_flight_recorders(str(tmp_path / "f")) == []
