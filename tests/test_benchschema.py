"""Bench-row schema pin (ISSUE 14 satellite): every assemble_*_row output
validates against the versioned schema, and drift (missing required keys,
type changes) is caught — the prerequisite for the baseline guard's
cross-round comparability."""

import bench
from smartbft_tpu.obs.benchschema import (
    SCHEMA_VERSION,
    identify_row,
    validate_row,
    validate_rows,
)

# ---------------------------------------------------------------------------
# synthetic child rows shaped like each bench subprocess's real output
# ---------------------------------------------------------------------------


def _latency(p99=80.0):
    return {"count": 10, "p50_ms": 10.0, "p95_ms": 40.0, "p99_ms": p99,
            "mean_ms": 15.0, "max_ms": p99, "shed": {}, "histogram": {},
            "pending_stamps": 0, "dropped_stamps": 0, "per_shard": {}}


def _plane():
    return {"ingest_us": 10.0, "route_us": 5.0, "vote_reg_us": 2.0,
            "codec_us": 3.0, "broadcasts": 4, "sends": 2, "encodes": 4,
            "decodes": 8, "batch_ingests": 2, "msgs_ingested": 8}


def openloop_child_rows():
    sweep = {
        "bench": "openloop", "offered_per_sec": 200.0,
        "goodput_per_sec": 195.0, "shards": 2, "zipf_skew": 1.1,
        "admission_high_water": 0.8,
        "open_loop": {"shed_rate": 0.0, "shed_admission": 0,
                      "shed_timeout": 0, "peak_occupancy": 12},
        "latency": _latency(),
    }
    knee = {"metric": "open_loop_knee", "slo": "x",
            "last_ok": {"offered_per_sec": 200.0}, "first_overloaded": None,
            "beyond_sweep": True}
    degraded = {
        "metric": "open_loop_degraded", "phases": {}, "notes": {},
        "viewchange": {}, "trace": {}, "critical_path": {},
        "health": {"final": {"status": "healthy", "reasons": []},
                   "transitions": []},
    }
    return [sweep, knee, degraded]


def transport_child_rows():
    def row(flavor, tx):
        return {"bench": "transport", "flavor": flavor, "nodes": 4,
                "requests": 120, "payload_bytes": 256, "decisions": 14,
                "elapsed_s": 1.0, "tx_per_sec": tx,
                "transport": {"bytes_sent": 1000, "frames_per_flush": 1.1},
                "protocol_plane": _plane(), "critical_path": {}}

    return [
        row("inproc", 700.0), row("uds", 650.0),
        {"metric": "transport_paired",
         "pairs": [{"flavor": "uds", "vs_inproc": 0.93}]},
        {"metric": "cluster_timeline", "nodes": 4, "transport": "uds",
         "requests": 24, "merged_events": 900, "offsets": {}, "hops": [],
         "critical_path": {}},
    ]


def sharded_child_rows():
    def point(s, tx):
        return {"shards": s, "tx_per_sec": tx, "launches": 4,
                "batch_fill_pct": 10.0, "items_per_launch": 8.0,
                "mixed_waves": 1, "elapsed_s": 2.0, "launch_probe_ms": 220.0,
                "shard": {"per_shard": {}, "aggregate": {}}}

    return [
        point(1, 400.0), point(4, 1200.0),
        {"metric": "sharded_scaling", "value": 3.0},
        {"metric": "live_resize", "path": [2, 4, 3], "phases": [],
         "tracking_vs_first": 1.5, "reshard": {"transitions": 2}},
    ]


def mesh_child_rows():
    def point(d, tx):
        return {"bench": "mesh", "devices": d, "shards": 2, "crypto": "toy",
                "tx_per_sec": tx, "launches": 3, "items_per_launch": 30.0,
                "capacity_items_per_launch": 64, "batch_fill_pct": 50.0,
                "pad_waste_pct": 5.0, "mixed_waves": 1, "elapsed_s": 2.0,
                "launch_probe_ms": 200.0, "hold_s": 0.0,
                "launches_ungated": 6, "batch_fill_ungated_pct": 25.0,
                "tx_per_sec_ungated": tx * 0.9,
                "mesh": {"devices": d, "topology": "1d",
                         "downgrades": 0,
                         "hold": {}}}

    return [
        point(1, 300.0), point(8, 900.0),
        {"metric": "mesh_parity", "match": True, "devices_checked": [1, 8],
         "items": 96},
        {"metric": "mesh_parity_2d", "match": True, "counts_match": True,
         "devices_checked": [2, 8], "items": 96},
        {"metric": "mesh_scaling", "value": 8.0,
         "items_per_launch_ratio": 6.0, "tx_ratio": 3.0},
    ]


def throughput_row(tx=800.0):
    return {"bench": "throughput", "engine": "jax", "nodes": 16,
            "requests": 1200, "pipeline": 16, "burst_decisions": 32,
            "tx_per_sec": tx, "decisions": 32, "batch_fill_pct": 80.0,
            "verify_us_per_sig": 6.0, "launches": 2,
            "launches_per_decision": 0.06, "window_launches": [],
            "launch_probe_ms": 220.0, "sigs_verified": 4000,
            "elapsed_s": 5.0, "breaker": {"open": False}, "mesh": {},
            "protocol_plane": _plane()}


# ---------------------------------------------------------------------------
# every assemble fn's output validates
# ---------------------------------------------------------------------------


def test_assembled_rows_pass_schema():
    rows = [
        bench.assemble_open_loop_row(openloop_child_rows()),
        bench.assemble_transport_row(transport_child_rows(), "uds"),
        bench.assemble_sharded_row(sharded_child_rows()),
        bench.assemble_mesh_row(mesh_child_rows()),
        bench.assemble_e2e_row(throughput_row(800.0), throughput_row(120.0),
                               nodes=16, pipeline=16, decisions=32),
    ]
    families = [identify_row(r) for r in rows]
    assert families == [
        "open_loop_p99_ms", "transport_committed_tx_per_sec",
        "sharded_committed_tx_per_sec", "mesh_committed_tx_per_sec",
        "committed_tx_per_sec_n*",
    ]
    errors = validate_rows(rows)
    assert errors == [], errors
    assert SCHEMA_VERSION == 1


def test_health_block_rides_open_loop_row():
    row = bench.assemble_open_loop_row(openloop_child_rows())
    assert row["health"]["final"]["status"] == "healthy"
    assert validate_row(row) == []


def test_drift_missing_required_key_is_caught():
    row = bench.assemble_transport_row(transport_child_rows(), "uds")
    del row["transport"]
    errors = validate_row(row)
    assert errors and "transport: required key missing" in errors[0]


def test_drift_type_change_is_caught():
    row = bench.assemble_open_loop_row(openloop_child_rows())
    row["value"] = "80ms"  # a stringified value would break every differ
    errors = validate_row(row)
    assert any("value" in e and "expected int/float" in e for e in errors)
    # a numeric field silently turning bool is drift too
    row2 = bench.assemble_open_loop_row(openloop_child_rows())
    row2["offered_per_sec"] = True
    assert any("got bool" in e for e in validate_row(row2))


def test_nested_block_drift_is_caught():
    row = bench.assemble_open_loop_row(openloop_child_rows())
    del row["latency"]["shed"]
    errors = validate_row(row)
    assert any("latency.shed" in e for e in errors)


def test_unpinned_families_are_not_drift():
    assert identify_row({"metric": "some_new_family", "value": 1}) is None
    assert validate_row({"metric": "some_new_family", "value": 1}) == []
    assert validate_row({"bench": "openloop"}) == []  # child rows unpinned


def test_kernel_and_tiny_rows_validate():
    kernel = {"metric": "p256_sig_verify_p50_us", "value": 5.8,
              "unit": "us/sig", "vs_baseline": 10.0, "vs_all_cores": 2.0,
              "cores": 8, "protocol_plane": _plane()}
    assert validate_row(kernel) == []
    from smartbft_tpu.obs.baseline import tiny_logical_row

    assert validate_row(tiny_logical_row(requests=4)) == []


def test_viewchange_guard_rows_validate_and_degrade_gracefully():
    """The ISSUE 15 failover pins: synthetic degraded rows through the
    SAME pure assemble fn bench.py calls must validate against the
    pinned schema, and an absent/empty degraded run yields no rows
    instead of drifting ones."""
    rows = openloop_child_rows()
    degraded = rows[-1]
    degraded["offered_per_sec"] = 300.0
    degraded["shards"] = 2
    degraded["phases"] = {
        "healthy": {"count": 100, "p50_ms": 20.0, "p95_ms": 60.0,
                    "p99_ms": 80.0},
        "view_change": {"count": 90, "p50_ms": 40.0, "p95_ms": 150.0,
                        "p99_ms": 220.0},
    }
    degraded["viewchange"] = {
        "detection": {"count": 3, "p50_ms": 300.0, "p95_ms": 600.0,
                      "p99_ms": 700.0, "max_ms": 710.0},
        "timer": {"derived": True, "timeout_s_max": 0.5},
    }
    guard = bench.viewchange_guard_rows(rows)
    assert [r["metric"] for r in guard] == [
        "viewchange_phase_p99_ms", "viewchange_detection_p99_ms"
    ]
    assert validate_rows(guard) == []
    phase = guard[0]
    assert phase["value"] == 220.0
    assert phase["vs_healthy"] == 2.75
    det = guard[1]
    assert det["value"] == 700.0
    assert det["timer"]["derived"] is True
    # no degraded run -> no guard rows (a missing producer is reported by
    # the baseline checker as 'missing', never as drift)
    assert bench.viewchange_guard_rows(rows[:-1]) == []
    # a degraded run that never completed its phases -> no rows either
    degraded["phases"] = {}
    degraded["viewchange"] = {}
    assert bench.viewchange_guard_rows(rows) == []


def test_byzantine_row_validates_and_guards_missing_p99():
    """The ISSUE 18 degraded-mode pin: synthetic paired probes through
    the SAME pure assemble fn ``bench.py --byzantine`` calls must
    validate against the pinned schema; a probe that never committed a
    spike request (no p99) fails loudly instead of emitting a drifting
    row."""
    import pytest

    def probe(p99, forged=0, shun=0, shed=0):
        return {"latency": _latency(p99), "spike_offered": 48,
                "spike_acked": 40, "decisions": 44, "forged": forged,
                "shun_events": shun, "shed_votes": shed}

    row = bench.assemble_byzantine_row(
        probe(90.0), probe(120.0, forged=60, shun=3, shed=200)
    )
    assert identify_row(row) == "byzantine_forge_p99_ms"
    assert validate_row(row) == [], validate_row(row)
    assert row["value"] == 120.0 and row["healthy_p99_ms"] == 90.0
    assert row["vs_healthy"] == 1.33
    assert row["shun_events"] == 3 and row["shed_votes"] == 200
    with pytest.raises(RuntimeError, match="no spike request"):
        bench.assemble_byzantine_row(probe(90.0), {"latency": {}})


def test_read_rows_validate_and_guard_bad_inputs():
    """The ISSUE 19 read-plane pins: synthetic rows through the SAME
    pure assemble fns ``bench.py --mixed-read`` (benchmarks/readplane.py)
    calls must validate, and nonsense inputs fail loudly instead of
    emitting drifting rows."""
    import pytest

    from smartbft_tpu.obs.benchschema import (
        assemble_read_row,
        assemble_read_scaling_row,
    )

    row = assemble_read_row(
        read_p99_ms=6.3, write_p99_ms=42.8, nodes=4, reads=190, writes=10,
        mode="quorum", local_p99_ms=2.6, follower_p99_ms=1.4, read_sheds=0,
        storm={"offered": 600, "sheds": 437, "writes_committed": 5},
        read_stats={"served": 377, "sheds": 437},
    )
    assert identify_row(row) == "read_p99_ms"
    assert validate_row(row) == [], validate_row(row)
    assert row["vs_write"] == round(6.3 / 42.8, 4)
    assert row["storm"]["sheds"] == 437
    with pytest.raises(ValueError, match="mode"):
        assemble_read_row(read_p99_ms=1.0, write_p99_ms=2.0, nodes=4,
                          reads=10, mode="psychic")

    scaling = assemble_read_scaling_row(
        per_replica_rate_small=2500.0, per_replica_rate_large=2700.0,
        nodes_small=4, nodes_large=8,
    )
    assert identify_row(scaling) == "read_scaling_vs_n"
    assert validate_row(scaling) == [], validate_row(scaling)
    assert scaling["value"] == round((2700.0 * 8) / (2500.0 * 4), 4)
    assert scaling["rate_flatness"] == round(2700.0 / 2500.0, 4)
    assert scaling["ideal"] == 2.0
    with pytest.raises(ValueError, match="nodes"):
        assemble_read_scaling_row(per_replica_rate_small=1.0,
                                  per_replica_rate_large=1.0,
                                  nodes_small=4, nodes_large=4)
    with pytest.raises(ValueError, match="positive"):
        assemble_read_scaling_row(per_replica_rate_small=0.0,
                                  per_replica_rate_large=1.0,
                                  nodes_small=4, nodes_large=8)
