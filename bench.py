"""Benchmark: the BASELINE north star — end-to-end committed tx/s at n=64.

Prints ONE JSON line:
  {"metric": "committed_tx_per_sec_n64", "value": <device tx/s>,
   "unit": "tx/s", "vs_baseline": <device / best-CPU-configuration>}

The device row runs the full consensus cluster (64 replicas, RequestBatch
500, real P-256 signatures on every commit vote, group-commit WALs) with
the pipelined in-flight window (pipeline_depth=16, launch-shadow overlap)
in SUSTAINED-BURST mode (32 back-to-back decisions, so the first launch's
fixed cost is amortized over the burst) and the shared device verify
engine + dedupe coalescer; the baseline row is the SAME cluster at its
best CPU configuration: OpenSSL verify (the reference's Go crypto/ecdsa
class, /root/reference/internal/bft/view.go:537-541) at pipeline_depth=1
(pipelining measurably hurts the GIL-serialized CPU verify path, so k=1
is the baseline's best foot forward).  Every row records its warm-launch
probe (launch_probe_ms) and the output carries BOTH the raw ratio and the
probe-normalized ratio (projected to an earlier rig's 110 ms launch floor).

Platform: the accelerator, or nothing.  A subprocess probe (this parent
stays off JAX so its children can own the chip) asks which platform JAX
initializes; anything but an accelerator exits non-zero.  ``--cpu`` (or
SMARTBFT_BENCH_CPU=1) asks for the CPU instead: the e2e bench then
shrinks to n=16 to bound runtime, and its numbers are not device numbers.
A bench that fails makes the exit code non-zero; nothing is swallowed and
no other bench runs in its place.

Env knobs: SMARTBFT_BENCH_E2E=0 forces the kernel micro bench;
SMARTBFT_BENCH_NODES / SMARTBFT_BENCH_REQUESTS / SMARTBFT_BENCH_PIPELINE
/ SMARTBFT_BENCH_DECISIONS (sustained-burst length, 0 = legacy
request-count mode) resize the cluster; SMARTBFT_BENCH_BATCH /
SMARTBFT_BENCH_REPS / SMARTBFT_BN_UNROLL tune the kernel micro bench as
before.

Sharded mode: ``--shards 1,2,4`` (or SMARTBFT_BENCH_SHARDS) additionally
runs the benchmarks/sharded.py sweep — S consensus groups over ONE shared
verify plane — and prints a second JSON line whose ``shard`` block
carries the per-shard + aggregate numbers (tx/s, launch fill, cross-shard
wave mix) plus the S=top-vs-S=1 scaling ratio, and whose ``reshard``
block carries the LIVE-resize walk (epoch transitions under load:
per-phase tx/s tracking S, moved-key fraction, drain ms, paused-submit
window — PERF.md round 11).

Transport mode: ``--transport {inproc,tcp,uds}`` (or
SMARTBFT_BENCH_TRANSPORT) additionally runs benchmarks/transport.py —
the SAME workload through the in-process Network and through real
sockets on localhost (the ``smartbft_tpu.net`` subsystem) — and prints a
JSON line whose ``transport`` block carries bytes on the wire, frames
per flush (write coalescing), reconnects, and drops, paired against the
in-process tx/s.

Open-loop mode: ``--open-loop`` (or SMARTBFT_BENCH_OPENLOOP=1) runs
benchmarks/openloop.py — Poisson arrivals at swept offered loads over
Zipf-skewed clients against the admission-controlled sharded front door
— and prints a JSON line whose ``latency`` block carries the
submit→commit percentiles (p50/p95/p99, log-scale histogram), shed
counts, the saturation knee, and the per-degraded-phase percentiles
(breaker-open / view-change / reshard) of the fixed-rate degraded run.
The subprocess timeout is DERIVED from the sweep size and phase plan so
a stuck point degrades inside the child (which salvages the other rows)
instead of this parent killing the whole block.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

REPS = int(os.environ.get("SMARTBFT_BENCH_REPS", "9"))  # a 9-rep median
# costs ~1.5s and stabilizes the metric

#: every headline row emitted this run, in order — the input to the
#: longitudinal baseline guard (--check-baseline)
EMITTED_ROWS: list = []


def _emit(row: dict) -> None:
    """Print one headline JSON row AND retain it for --check-baseline."""
    EMITTED_ROWS.append(row)
    print(json.dumps(row), flush=True)


def _resolve_batch(cpu: bool) -> int:
    """TPU: batch 131072 on the comb kernel (on an earlier rig the fixed
    per-launch overhead dominated per-sig cost below that: 4096 -> 26
    us/sig from overhead alone; 131072 -> 5.75 us/sig end to end).  CPU:
    small batch, no unroll — anything bigger compiles for tens of
    minutes."""
    if cpu:
        os.environ.setdefault("SMARTBFT_BN_UNROLL", "1")
        return int(os.environ.get("SMARTBFT_BENCH_BATCH", "128"))
    os.environ.setdefault("SMARTBFT_BN_UNROLL", "33")
    return int(os.environ.get("SMARTBFT_BENCH_BATCH", "131072"))


PROBE_TIMEOUT = float(os.environ.get("SMARTBFT_BENCH_PROBE_TIMEOUT", "120"))


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def ncores_hint() -> int:
    return os.cpu_count() or 1


def _probe_platform() -> str:
    """Probe default-platform JAX init in a subprocess, which exits (and
    lets go of the chip) before any bench child starts.

    Returns the default backend's platform name ('tpu', 'cpu', ...) or ''
    when initialization fails/hangs.
    """
    code = ("import jax; jax.devices(); import jax.numpy as jnp; "
            "(jnp.ones(4)+1).block_until_ready(); "
            "print(jax.default_backend())")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code], timeout=PROBE_TIMEOUT,
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        )
    except subprocess.TimeoutExpired:
        return ""
    if proc.returncode != 0:
        return ""
    return proc.stdout.decode().strip().splitlines()[-1] if proc.stdout else ""


def _openssl_prepare(items):
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        encode_dss_signature,
    )

    pubs = {}
    prepared = []
    for msg, r, s, pub in items:
        if pub not in pubs:
            pubs[pub] = ec.EllipticCurvePublicNumbers(
                pub[0], pub[1], ec.SECP256R1()
            ).public_key()
        prepared.append((msg, encode_dss_signature(r, s), pubs[pub]))
    return prepared


def _openssl_baseline(items) -> float:
    """Single-threaded OpenSSL verify; returns us/sig."""
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec

    prepared = _openssl_prepare(items)
    for msg, der, key in prepared[:32]:  # warm up EVP/allocator state
        key.verify(der, msg, ec.ECDSA(hashes.SHA256()))
    best = float("inf")
    for _ in range(3):  # best-of-3: give the baseline its least-noise run
        t0 = time.perf_counter()
        for msg, der, key in prepared:
            key.verify(der, msg, ec.ECDSA(hashes.SHA256()))
        best = min(best, time.perf_counter() - t0)
    return 1e6 * best / len(prepared)


def _openssl_all_cores_baseline(items) -> tuple[float, int]:
    """OpenSSL verify across all host cores (thread pool; the cryptography
    wheel releases the GIL around EVP verify) — the honest CPU baseline:
    the reference verifies one goroutine per signature across every core
    (/root/reference/internal/bft/view.go:537-541).  Returns (us/sig
    effective, ncores)."""
    from concurrent.futures import ThreadPoolExecutor

    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec

    ncores = os.cpu_count() or 1
    prepared = _openssl_prepare(items)

    def verify_one(job):
        msg, der, key = job
        key.verify(der, msg, ec.ECDSA(hashes.SHA256()))

    chunk = max(1, len(prepared) // (4 * ncores))
    best = float("inf")
    with ThreadPoolExecutor(max_workers=ncores) as pool:
        list(pool.map(verify_one, prepared[:64], chunksize=chunk))  # ramp up
        for _ in range(3):  # best-of-3, like the single-core baseline
            t0 = time.perf_counter()
            list(pool.map(verify_one, prepared, chunksize=chunk))
            best = min(best, time.perf_counter() - t0)
    return 1e6 * best / len(prepared), ncores


def _run_throughput_row(extra_args: list[str], cpu_mode: bool,
                        timeout: float) -> dict:
    """One benchmarks/throughput.py row in a subprocess; returns its JSON."""
    here = os.path.dirname(os.path.abspath(__file__))
    cmd = [sys.executable, os.path.join(here, "benchmarks", "throughput.py")]
    cmd += extra_args
    if cpu_mode:
        cmd.append("--cpu")
    proc = subprocess.run(
        cmd, timeout=timeout, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"throughput row {extra_args} failed: "
            f"{proc.stderr.decode(errors='replace')[-400:]}"
        )
    rows = [json.loads(l) for l in proc.stdout.decode().splitlines() if l.strip()]
    rows = [r for r in rows if "tx_per_sec" in r]
    if not rows:
        raise RuntimeError(f"throughput row {extra_args} produced no result")
    return rows[-1]


#: historical best warm-launch probe on this rig (ms) — the normalization
#: anchor for weather-independent cross-round ratio comparisons
LAUNCH_PROBE_FLOOR_MS = 110.0


def _probe_normalized_tx(row: dict) -> float:
    """Project a row's tx/s to the rig's historical launch floor: subtract
    the excess (probe - floor) paid on each launch from the elapsed time.
    Returns 0.0 when the row lacks the inputs (old rows, no launches)."""
    probe = row.get("launch_probe_ms") or 0.0
    launches = row.get("launches") or 0
    elapsed = row.get("elapsed_s") or 0.0
    tx = row.get("tx_per_sec") or 0.0
    if not (probe and launches and elapsed and tx):
        return 0.0
    excess_s = launches * max(probe - LAUNCH_PROBE_FLOOR_MS, 0.0) / 1e3
    adj = elapsed - excess_s
    if adj <= 0:
        return 0.0
    return round(tx * elapsed / adj, 1)


def e2e_bench(cpu_mode: bool) -> None:
    """The north-star metric: device cluster vs best-CPU cluster.

    Sustained-burst protocol (round 6): both rows commit
    SMARTBFT_BENCH_DECISIONS (default 32) back-to-back decisions so the
    first launch's fixed cost is actually amortized; every row carries the
    warm-launch probe (launch_probe_ms) and the output reports the raw AND
    the probe-normalized ratio."""
    nodes = int(os.environ.get(
        "SMARTBFT_BENCH_NODES", "16" if cpu_mode else "64"))
    requests = int(os.environ.get(
        "SMARTBFT_BENCH_REQUESTS", "1200" if cpu_mode else "4000"))
    decisions = int(os.environ.get("SMARTBFT_BENCH_DECISIONS", "32"))
    pipeline = int(os.environ.get("SMARTBFT_BENCH_PIPELINE", "16"))
    timeout = float(os.environ.get("SMARTBFT_BENCH_E2E_TIMEOUT", "580"))
    # rigs without the `cryptography` wheel can still run the e2e with the
    # pure-Python CPU engine (SMARTBFT_BENCH_CPU_ENGINE=host) — the ratio
    # is then NOT comparable to the OpenSSL baseline, only the row shape
    cpu_engine = os.environ.get("SMARTBFT_BENCH_CPU_ENGINE", "openssl")
    common = ["--nodes", str(nodes), "--requests", str(requests),
              "--batch", "500"]
    if decisions > 0:
        common += ["--burst-decisions", str(decisions)]
    _log(f"bench: e2e n={nodes} requests={requests} decisions={decisions} "
         f"pipeline={pipeline} (cpu_mode={cpu_mode})")
    cpu_row = _run_throughput_row(
        common + ["--engines", cpu_engine, "--pipeline", "1"],
        cpu_mode=True, timeout=timeout,  # needs no device: stays off the chip
    )
    _log(f"bench: cpu-best row {cpu_row}")
    dev_row = _run_throughput_row(
        common + ["--engines", "jax", "--pipeline", str(pipeline)],
        cpu_mode=cpu_mode, timeout=timeout,
    )
    _log(f"bench: device row {dev_row}")
    _emit(assemble_e2e_row(dev_row, cpu_row, nodes=nodes,
                           pipeline=pipeline, decisions=decisions))


def assemble_e2e_row(dev_row: dict, cpu_row: dict, *, nodes: int,
                     pipeline: int, decisions: int) -> dict:
    """Fold the device + best-CPU throughput rows into the ONE north-star
    bench line.  Pure function, importable — the schema drift gate
    (obs.benchschema, tests) pins the ``committed_tx_per_sec_n*`` family
    through it exactly as tests pin the open-loop and mesh rows."""
    norm_tx = _probe_normalized_tx(dev_row)
    return {
        "metric": f"committed_tx_per_sec_n{nodes}",
        "value": dev_row["tx_per_sec"],
        "unit": "tx/s",
        "vs_baseline": round(dev_row["tx_per_sec"] / cpu_row["tx_per_sec"], 3)
        if cpu_row["tx_per_sec"] else 0.0,
        "baseline_tx_per_sec": cpu_row["tx_per_sec"],
        "pipeline": pipeline,
        "burst_decisions": decisions,
        "launches": dev_row.get("launches"),
        "decisions": dev_row.get("decisions"),
        "launches_per_decision": dev_row.get("launches_per_decision"),
        "window_launches": dev_row.get("window_launches"),
        "batch_fill_pct": dev_row.get("batch_fill_pct"),
        "launch_probe_ms": dev_row.get("launch_probe_ms"),
        "baseline_launch_probe_ms": cpu_row.get("launch_probe_ms"),
        # breaker accounting rides along so a degraded (host-fallback)
        # device row is never mistaken for a healthy device run
        "breaker": dev_row.get("breaker"),
        # which verify plane ran: single device or an N-device mesh
        # (devices, fill per device, pad waste, loud downgrades)
        "mesh": dev_row.get("mesh"),
        # per-phase message-plane timers (ingest/route/vote-reg/codec) from
        # the device row's timed window — the PERF.md decomposition inputs
        "protocol_plane": dev_row.get("protocol_plane"),
        "baseline_protocol_plane": cpu_row.get("protocol_plane"),
        "tx_per_sec_probe_normalized": norm_tx,
        "vs_baseline_probe_normalized": round(
            norm_tx / cpu_row["tx_per_sec"], 3)
        if norm_tx and cpu_row["tx_per_sec"] else 0.0,
    }


def sharded_bench(shards: str, cpu_mode: bool) -> None:
    """Run the benchmarks/sharded.py sweep in a subprocess and print ONE
    JSON line with the scaling headline + the full ``shard`` block (per-
    shard and aggregate numbers) — the sharded-mode contract of ISSUE 5."""
    here = os.path.dirname(os.path.abspath(__file__))
    cmd = [sys.executable, os.path.join(here, "benchmarks", "sharded.py"),
           "--shards", shards]
    if cpu_mode:
        cmd.append("--cpu")
    # cover the sweep's own worst case (3 reps x points x the per-point
    # salvage deadline, see benchmarks/sharded.py POINT_TIMEOUT) so a
    # stuck point degrades to fewer reps instead of this parent killing
    # the whole shard block before the sweep's internal deadline can fire
    points = max(1, len([s for s in shards.split(",") if s.strip()]))
    point_timeout = float(os.environ.get(
        "SMARTBFT_BENCH_SHARD_POINT_TIMEOUT", "120"))
    # + the live-resize walk (3 phases x worst case of a full drain
    # deadline PLUS a full settle wait each) so a stuck transition
    # degrades inside the child (which salvages the sweep rows) instead
    # of this parent SIGKILLing the whole shard block
    timeout = float(os.environ.get(
        "SMARTBFT_BENCH_SHARD_TIMEOUT",
        str((3 * points + 6) * point_timeout + 120)))
    proc = subprocess.run(
        cmd, timeout=timeout, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded sweep failed: {proc.stderr.decode(errors='replace')[-400:]}"
        )
    rows = [json.loads(l) for l in proc.stdout.decode().splitlines() if l.strip()]
    _emit(assemble_sharded_row(rows))


def assemble_sharded_row(rows: list) -> dict:
    """Fold benchmarks/sharded.py's JSON lines into the ONE bench.py
    sharded row.  Pure function, importable — the schema drift gate pins
    the ``sharded_committed_tx_per_sec`` family through it (PR 8
    idiom)."""
    points = [r for r in rows if "shards" in r and "tx_per_sec" in r]
    scaling = next((r for r in rows if r.get("metric") == "sharded_scaling"), {})
    resize = next((r for r in rows if r.get("metric") == "live_resize"), {})
    if not points:
        raise RuntimeError("sharded sweep produced no rows")
    peak = max(points, key=lambda r: r["shards"])
    return {
        "metric": "sharded_committed_tx_per_sec",
        "value": peak["tx_per_sec"],
        "unit": "tx/s",
        "vs_baseline": scaling.get("value", 0.0),  # S=top vs S=1 aggregate
        "shard": {
            "sweep": [
                {k: r.get(k) for k in (
                    "shards", "tx_per_sec", "launches", "batch_fill_pct",
                    "items_per_launch", "mixed_waves", "elapsed_s",
                    "launch_probe_ms",
                )}
                for r in points
            ],
            "scaling": scaling,
            # full attribution for the top point: per-shard blocks (plane
            # deltas, pool, decisions) + the shared-plane aggregate
            "top": peak.get("shard"),
        },
        # the elastic-shards contract (ISSUE 7): aggregate tx/s tracking S
        # across a LIVE resize, plus the epoch-transition costs (moved
        # keys, drain ms, paused-submit window) per reshard
        "reshard": {
            "path": resize.get("path"),
            "phases": resize.get("phases"),
            "tracking_vs_first": resize.get("tracking_vs_first"),
            **(resize.get("reshard") or {}),
        } if resize else None,
    }


def assemble_mesh_row(rows: list) -> dict:
    """Fold benchmarks/mesh.py's JSON lines into the ONE bench.py mesh
    row.  Pure function, importable — tests/test_mesh_plane.py pins the
    ``mesh`` block schema against it exactly as tests/test_overload.py
    pins the open-loop ``latency`` block.

    The row contract: ``mesh.sweep`` carries the devices ∈ {1,2,4,8}
    points at the fixed shard count (tx/s, launches, items/launch,
    per-launch capacity, fill, pad waste — gated values, with the
    ungated control's launches/fill riding along), ``mesh.gating`` the
    top point's gated-vs-ungated deltas plus the coalescer's hold
    decisions (waves_held, held_ms, depth_gain_items),
    ``mesh.verdict_parity`` / ``mesh.verdict_parity_2d`` the
    bit-for-bit checks against the single-device engine (1D batch mesh
    and 2D seq×vote quorum mesh), ``mesh.capacity_scaling`` the
    top-vs-1 capacity ratio, and ``downgrades`` records which path ran."""
    sweep = [r for r in rows if r.get("bench") == "mesh"]
    parity = next((r for r in rows if r.get("metric") == "mesh_parity"), {})
    parity_2d = next(
        (r for r in rows if r.get("metric") == "mesh_parity_2d"), {}
    )
    scaling = next((r for r in rows if r.get("metric") == "mesh_scaling"), {})
    if not sweep:
        raise RuntimeError("mesh sweep produced no rows")
    top = max(sweep, key=lambda r: r["devices"])
    base = min(sweep, key=lambda r: r["devices"])
    top_mesh = top.get("mesh") or {}
    return {
        "metric": "mesh_committed_tx_per_sec",
        "value": top["tx_per_sec"],
        "unit": "tx/s",
        "vs_baseline": round(top["tx_per_sec"] / base["tx_per_sec"], 3)
        if base["tx_per_sec"] else 0.0,
        "devices": top["devices"],
        "mesh": {
            "fixed_shards": top.get("shards"),
            "crypto": top.get("crypto"),
            "sweep": [
                {k: r.get(k) for k in (
                    "devices", "tx_per_sec", "launches", "items_per_launch",
                    "capacity_items_per_launch", "batch_fill_pct",
                    "pad_waste_pct", "mixed_waves", "elapsed_s",
                    "launch_probe_ms", "hold_s", "launches_ungated",
                    "batch_fill_ungated_pct", "tx_per_sec_ungated",
                )}
                for r in sweep
            ],
            "capacity_scaling": scaling.get("value"),
            "items_per_launch_ratio": scaling.get("items_per_launch_ratio"),
            "tx_ratio": scaling.get("tx_ratio"),
            # the ISSUE 11 wave-deepening claim at the top point: gated
            # fill up, launches strictly below the ungated control
            "gating": {
                "hold_s": top.get("hold_s"),
                "launches": top.get("launches"),
                "launches_ungated": top.get("launches_ungated"),
                "fill_pct": top.get("batch_fill_pct"),
                "fill_ungated_pct": top.get("batch_fill_ungated_pct"),
                "hold": top_mesh.get("hold"),
            },
            "verdict_parity": {
                "match": parity.get("match"),
                "devices_checked": parity.get("devices_checked"),
                "items": parity.get("items"),
            },
            "verdict_parity_2d": {
                "match": parity_2d.get("match"),
                "counts_match": parity_2d.get("counts_match"),
                "devices_checked": parity_2d.get("devices_checked"),
                "items": parity_2d.get("items"),
            },
            "topology": top_mesh.get("topology", "1d"),
            "downgrades": top_mesh.get("downgrades", 0),
            "top": top_mesh,
        },
    }


def mesh_bench(devices: str, cpu_mode: bool) -> None:
    """Run the benchmarks/mesh.py sweep in a subprocess and print ONE
    JSON line whose ``mesh`` block carries the devices sweep at fixed S
    (the ISSUE 10 contract)."""
    here = os.path.dirname(os.path.abspath(__file__))
    cmd = [sys.executable, os.path.join(here, "benchmarks", "mesh.py"),
           "--devices", devices]
    if cpu_mode:
        cmd.append("--cpu")
    points = max(1, len([d for d in devices.split(",") if d.strip()]))
    point_timeout = float(os.environ.get(
        "SMARTBFT_BENCH_MESH_POINT_TIMEOUT", "120"))
    # derived, not guessed: every point runs TWICE (ungated control +
    # gated run) and may burn its commit deadline plus a stuck-cluster
    # teardown each time, and the two parity stages pay one compile per
    # width — the child's per-point salvage fires before this parent
    # kills it
    timeout = float(os.environ.get(
        "SMARTBFT_BENCH_MESH_TIMEOUT",
        str((2 * points + 3) * point_timeout + 120)
    ))
    proc = subprocess.run(
        cmd, timeout=timeout, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"mesh sweep failed: {proc.stderr.decode(errors='replace')[-400:]}"
        )
    rows = [json.loads(l) for l in proc.stdout.decode().splitlines()
            if l.strip()]
    _emit(assemble_mesh_row(rows))


def assemble_open_loop_row(rows: list) -> dict:
    """Fold benchmarks/openloop.py's JSON lines into the ONE bench.py
    open-loop row.  Pure function, importable — tests/test_overload.py
    pins the ``latency`` block schema against it exactly as
    tests/test_verify_plane.py pins the breaker block.

    The row contract: ``latency`` carries the sweep-wide percentiles and
    histogram of the HIGHEST offered load that still met the SLO (or the
    top point when everything overloaded — worst honest number, never an
    empty block), the shed counts, the knee, and ``phases`` with the
    degraded run's per-phase (breaker_open / view_change / reshard)
    percentiles."""
    sweep = [r for r in rows if r.get("bench") == "openloop"]
    knee = next((r for r in rows if r.get("metric") == "open_loop_knee"), {})
    degraded = next(
        (r for r in rows if r.get("metric") == "open_loop_degraded"), {}
    )
    if not sweep:
        raise RuntimeError("open-loop sweep produced no rows")
    last_ok = (knee.get("last_ok") or {}).get("offered_per_sec")
    anchor = next(
        (r for r in sweep if r["offered_per_sec"] == last_ok),
        max(sweep, key=lambda r: r["offered_per_sec"]),
    )
    latency = dict(anchor["latency"])
    latency["shed"] = dict(
        latency.get("shed") or {},
        **{k: anchor["open_loop"][k]
           for k in ("shed_admission", "shed_timeout")},
    )
    latency["knee"] = {
        k: knee.get(k) for k in ("slo", "last_ok", "first_overloaded",
                                 "beyond_sweep")
    }
    latency["phases"] = degraded.get("phases", {})
    return {
        "metric": "open_loop_p99_ms",
        "value": latency.get("p99_ms", 0.0),
        "unit": "ms",
        "offered_per_sec": anchor["offered_per_sec"],
        "goodput_per_sec": anchor["goodput_per_sec"],
        "shards": anchor.get("shards"),
        "zipf_skew": anchor.get("zipf_skew"),
        "admission_high_water": anchor.get("admission_high_water"),
        # ISSUE 12: the degraded run's measured VC sub-phase decomposition
        # + merged flight-recorder summary ride every open-loop row
        "viewchange": degraded.get("viewchange"),
        "trace": degraded.get("trace"),
        # ISSUE 13: the per-request critical-path decomposition (segment
        # sums == end-to-end within the stated residual; per-phase
        # sub-blocks name each degraded phase's dominant segment)
        "critical_path": degraded.get("critical_path"),
        # ISSUE 14: the continuous SLO verdict over the degraded walk
        # (final state + every healthy/degraded/critical transition with
        # the breaching SLO names)
        "health": degraded.get("health"),
        "sweep": [
            {k: r.get(k) for k in ("offered_per_sec", "goodput_per_sec")}
            | {"p99_ms": r["latency"]["p99_ms"],
               "shed_rate": r["open_loop"]["shed_rate"],
               "peak_occupancy": r["open_loop"]["peak_occupancy"]}
            for r in sweep
        ],
        "degraded_notes": degraded.get("notes"),
        "latency": latency,
    }


def viewchange_guard_rows(rows: list) -> list:
    """The ISSUE 15 longitudinal failover pins: scalar rows derived from
    the degraded run so ``--check-baseline`` catches a failover
    regression — the forced-VC phase's request p99 (the round-12
    degraded-table cell that crowned view change the worst failure mode)
    and the detection arm-to-fire p99 under the muted leader.  Pure
    function, importable; returns [] when the degraded run is absent."""
    degraded = next(
        (r for r in rows if r.get("metric") == "open_loop_degraded"), None
    )
    if not degraded:
        return []
    out = []
    phases = degraded.get("phases") or {}
    vc_phase = phases.get("view_change") or {}
    p99 = vc_phase.get("p99_ms")
    if isinstance(p99, (int, float)):
        healthy = (phases.get("healthy") or {}).get("p99_ms")
        row = {
            "metric": "viewchange_phase_p99_ms",
            "value": p99,
            "unit": "ms",
            "offered_per_sec": degraded.get("offered_per_sec"),
            "shards": degraded.get("shards"),
        }
        if isinstance(healthy, (int, float)):
            row["healthy_p99_ms"] = healthy
            if healthy:
                row["vs_healthy"] = round(p99 / healthy, 2)
        out.append(row)
    det = (degraded.get("viewchange") or {}).get("detection") or {}
    if det.get("count") and isinstance(det.get("p99_ms"), (int, float)):
        out.append({
            "metric": "viewchange_detection_p99_ms",
            "value": det["p99_ms"],
            "unit": "ms",
            "count": det.get("count"),
            "offered_per_sec": degraded.get("offered_per_sec"),
            "shards": degraded.get("shards"),
            # the effective-timer derivation that produced it, verbatim
            "timer": (degraded.get("viewchange") or {}).get("timer"),
        })
    return out


def commitpath_guard_rows(rows: list) -> list:
    """The ISSUE 16 commit-path pins: scalar rows derived from the
    open-loop child's output so ``--check-baseline`` catches a raw-speed
    regression — the saturation knee (tx/s, higher is better) and the
    healthy-phase ``propose_wait`` / ``deliver`` critpath shares (unit
    ``share``, lower is better: the two segments the arrival-driven
    proposer and the batched deliver fan-out cut).  Pure function,
    importable; rows degrade to [] when their source block is absent."""
    out = []
    knee = next((r for r in rows if r.get("metric") == "open_loop_knee"), {})
    last_ok = knee.get("last_ok") or {}
    if isinstance(last_ok.get("offered_per_sec"), (int, float)):
        out.append({
            "metric": "open_loop_knee_tx_per_sec",
            "value": last_ok["offered_per_sec"],
            "unit": "tx/s",
            "goodput_per_sec": last_ok.get("goodput_per_sec"),
            "p99_ms": last_ok.get("p99_ms"),
            "beyond_sweep": knee.get("beyond_sweep"),
        })
    degraded = next(
        (r for r in rows if r.get("metric") == "open_loop_degraded"), None
    )
    healthy = (((degraded or {}).get("critical_path") or {})
               .get("phases") or {}).get("healthy") or {}
    segments = healthy.get("segments") or {}
    for seg in ("propose_wait", "deliver"):
        share = (segments.get(seg) or {}).get("share")
        if isinstance(share, (int, float)):
            out.append({
                "metric": f"critpath_{seg}_share",
                "value": share,
                "unit": "share",
                "phase": "healthy",
                "requests": healthy.get("requests"),
                "dominant_segment": healthy.get("dominant_segment"),
                "sums_consistent": healthy.get("sums_consistent"),
                "offered_per_sec": (degraded or {}).get("offered_per_sec"),
            })
    for kr in rows:
        if kr.get("metric") != "open_loop_affinity_knee":
            continue
        s, ok = kr.get("shards"), kr.get("last_ok") or {}
        if isinstance(ok.get("offered_per_sec"), (int, float)):
            out.append({
                "metric": f"open_loop_affinity_s{s}_knee_tx_per_sec",
                "value": ok["offered_per_sec"],
                "unit": "tx/s",
                "shards": s,
                "loop_affinity": kr.get("loop_affinity"),
                "goodput_per_sec": ok.get("goodput_per_sec"),
                "beyond_sweep": kr.get("beyond_sweep"),
            })
    return out


def open_loop_bench(cpu_mode: bool) -> None:
    """Run benchmarks/openloop.py in a subprocess and print ONE JSON line
    whose ``latency`` block carries percentiles + histogram + shed counts
    + knee + degraded-phase percentiles (the round-12 contract)."""
    here = os.path.dirname(os.path.abspath(__file__))
    # default grid raised in round 18 (commit-path raw speed): the knee
    # moved from 800/s to the 8-9k/s band, so the old 200-1600 sweep
    # would read "beyond sweep" and pin nothing
    rates = os.environ.get("SMARTBFT_BENCH_OPENLOOP_RATES",
                           "1000,2000,4000,8000,9000")
    duration = float(os.environ.get("SMARTBFT_BENCH_OPENLOOP_DURATION", "8"))
    phase = float(os.environ.get("SMARTBFT_BENCH_OPENLOOP_PHASE", "6"))
    drain = 3.0
    sweep_shards = os.environ.get("SMARTBFT_BENCH_OPENLOOP_SWEEP_SHARDS", "")
    cmd = [sys.executable, os.path.join(here, "benchmarks", "openloop.py"),
           "--rates", rates, "--duration", str(duration),
           "--phase-duration", str(phase)]
    if sweep_shards:
        cmd += ["--sweep-shards", sweep_shards]
    if cpu_mode:
        cmd.append("--cpu")
    points = len([r for r in rates.split(",") if r.strip()])
    # each affinity-sweep point runs its S workers CONCURRENTLY, so a
    # point costs one duration+drain+salvage budget regardless of S
    affinity_points = (points * len([s for s in sweep_shards.split(",")
                                     if s.strip()])
                       if sweep_shards else 0)
    phase_timeout = float(os.environ.get(
        "SMARTBFT_BENCH_OPENLOOP_PHASE_TIMEOUT", "60"))
    # derived, not guessed (the PR-5/7 salvage lesson): every sweep point
    # may burn its duration + drain + a stuck-cluster teardown, and the
    # degraded run is 5 pumped phases plus 4 bounded waits (breaker
    # open/close, depose, quiesce x2 share one budget each) plus a drain
    # deadline — the child's own salvage fires before this parent kills it
    timeout = float(os.environ.get(
        "SMARTBFT_BENCH_OPENLOOP_TIMEOUT",
        str((points + affinity_points) * (duration + drain + phase_timeout)
            + 5 * (phase + drain) + 5 * phase_timeout + 120)))
    proc = subprocess.run(
        cmd, timeout=timeout, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"open-loop bench failed: "
            f"{proc.stderr.decode(errors='replace')[-400:]}"
        )
    rows = [json.loads(l) for l in proc.stdout.decode().splitlines()
            if l.strip()]
    _emit(assemble_open_loop_row(rows))
    for guard_row in viewchange_guard_rows(rows):
        _emit(guard_row)
    for guard_row in commitpath_guard_rows(rows):
        _emit(guard_row)


def transport_bench(flavor: str) -> None:
    """Run benchmarks/transport.py paired (inproc + the chosen socket
    flavor, SAME workload/protocol stack, only the Comm seam differs) and
    print ONE JSON line whose ``transport`` block carries both rows —
    bytes on the wire, frames per flush (write coalescing), reconnects —
    next to the usual ``protocol_plane`` block."""
    here = os.path.dirname(os.path.abspath(__file__))
    flavors = "inproc" if flavor == "inproc" else f"inproc,{flavor}"
    nodes = os.environ.get("SMARTBFT_BENCH_TRANSPORT_NODES", "4")
    requests = os.environ.get("SMARTBFT_BENCH_TRANSPORT_REQUESTS", "120")
    cmd = [sys.executable, os.path.join(here, "benchmarks", "transport.py"),
           "--flavors", flavors, "--nodes", nodes, "--requests", requests,
           "--cluster-trace"]
    timeout = float(os.environ.get("SMARTBFT_BENCH_TRANSPORT_TIMEOUT", "560"))
    proc = subprocess.run(
        cmd, timeout=timeout, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),  # no device in this bench
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"transport bench failed: "
            f"{proc.stderr.decode(errors='replace')[-400:]}"
        )
    rows = [json.loads(l) for l in proc.stdout.decode().splitlines() if l.strip()]
    _emit(assemble_transport_row(rows, flavor))


def assemble_transport_row(rows: list, flavor: str) -> dict:
    """Fold benchmarks/transport.py's JSON lines into the ONE bench.py
    transport row.  Pure function, importable — the schema drift gate
    pins the ``transport_committed_tx_per_sec`` family through it."""
    by_flavor = {r["flavor"]: r for r in rows if r.get("bench") == "transport"}
    if not by_flavor:
        raise RuntimeError("transport bench produced no rows")
    paired = next((r for r in rows if r.get("metric") == "transport_paired"), {})
    cluster_trace = next(
        (r for r in rows if r.get("metric") == "cluster_timeline"), None
    )
    main_row = by_flavor.get(flavor) or next(iter(by_flavor.values()))
    inproc = by_flavor.get("inproc", {})
    return {
        "metric": "transport_committed_tx_per_sec",
        "value": main_row["tx_per_sec"],
        "unit": "tx/s",
        "vs_baseline": (paired.get("pairs") or [{}])[0].get("vs_inproc", 1.0),
        "flavor": flavor,
        "nodes": main_row["nodes"],
        "requests": main_row["requests"],
        "transport": main_row["transport"],
        "inproc_tx_per_sec": inproc.get("tx_per_sec"),
        "protocol_plane": main_row.get("protocol_plane"),
        "inproc_protocol_plane": inproc.get("protocol_plane"),
        # ISSUE 13: the per-request critical-path decomposition of the
        # measured flavor, and the multi-process merged cluster timeline
        # (clock offsets + per-link network time + merged critical path)
        "critical_path": main_row.get("critical_path"),
        "cluster_trace": cluster_trace,
    }


def rejoin_guard_rows(rows: list) -> list:
    """The ISSUE 17 flat-rejoin pin: ONE scalar row derived from the
    rejoin sweep so ``--check-baseline`` catches an O(1)-rejoin
    regression — the deep-history snapshot rejoin's wall clock over the
    shallow one (unit ``x``, lower is better; the committed baseline
    pins the ideal 1.0 with a 100% allowance, i.e. deep must stay
    within 2x shallow).  The replay control's same ratio rides along
    as context (it is O(depth) by design — hundreds of x).  Pure
    function, importable; returns [] without both snapshot points."""
    snaps, replays = {}, {}
    for r in rows:
        h = r.get("history_decisions")
        if not isinstance(h, (int, float)) \
                or not isinstance(r.get("value"), (int, float)):
            continue
        {"snapshot": snaps, "replay": replays}.get(r.get("mode"), {})[h] = r
    if len(snaps) < 2:
        return []
    small, deep = min(snaps), max(snaps)
    if not snaps[small]["value"]:
        return []
    row = {
        "metric": "rejoin_flatness_vs_depth",
        "value": round(snaps[deep]["value"] / snaps[small]["value"], 4),
        "unit": "x",
        "history_small": int(small),
        "history_deep": int(deep),
        "snapshot_small_s": snaps[small]["value"],
        "snapshot_deep_s": snaps[deep]["value"],
        "interval": snaps[deep].get("interval"),
    }
    if small in replays and deep in replays and replays[small]["value"]:
        row["replay_ratio"] = round(
            replays[deep]["value"] / replays[small]["value"], 4)
    return [row]


def rejoin_bench() -> None:
    """Run benchmarks/rejoin.py (snapshot-install vs full-chain-replay
    rejoin at shallow vs deep history, real LedgerFile/SnapshotStore/
    verification end to end) and emit its ``rejoin_*`` rows plus the
    flat-vs-depth guard row."""
    here = os.path.dirname(os.path.abspath(__file__))
    histories = os.environ.get("SMARTBFT_BENCH_REJOIN_HISTORIES",
                               "100,100000")
    cmd = [sys.executable, os.path.join(here, "benchmarks", "rejoin.py"),
           "--histories", histories]
    timeout = float(os.environ.get("SMARTBFT_BENCH_REJOIN_TIMEOUT", "560"))
    proc = subprocess.run(
        cmd, timeout=timeout, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),  # no device in this bench
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"rejoin bench failed: "
            f"{proc.stderr.decode(errors='replace')[-400:]}"
        )
    rows = [json.loads(l) for l in proc.stdout.decode().splitlines()
            if l.strip()]
    if not rows:
        raise RuntimeError("rejoin bench produced no rows")
    for row in rows:
        _emit(row)
    for guard_row in rejoin_guard_rows(rows):
        _emit(guard_row)


def assemble_byzantine_row(healthy: dict, degraded: dict) -> dict:
    """Fold the paired Byzantine latency probes (no actor vs an active
    vote-forgery flood, SAME cluster + open-loop load) into the ONE
    ``--byzantine`` degraded-mode row.  Pure function, importable — the
    schema drift gate pins the ``byzantine_forge_p99_ms`` family through
    it.  The row's value is the honest-path request p99 WITH the forger
    flooding; ``healthy_p99_ms``/``vs_healthy`` carry the no-actor
    control so the baseline can bound the forger's latency tax."""
    h_lat = healthy.get("latency") or {}
    d_lat = degraded.get("latency") or {}
    h99, d99 = h_lat.get("p99_ms"), d_lat.get("p99_ms")
    if not isinstance(d99, (int, float)) or not isinstance(h99, (int, float)):
        raise RuntimeError(
            f"byzantine probes resolved no p99 (healthy={h99!r}, "
            f"degraded={d99!r}) — no spike request ever committed"
        )
    row = {
        "metric": "byzantine_forge_p99_ms",
        "value": round(float(d99), 3),
        "unit": "ms",
        "healthy_p99_ms": round(float(h99), 3),
        "forged": degraded.get("forged"),
        "shun_events": degraded.get("shun_events"),
        "shed_votes": degraded.get("shed_votes"),
        "spike_acked": degraded.get("spike_acked"),
        "healthy_spike_acked": healthy.get("spike_acked"),
        "latency": d_lat,
        "healthy_latency": h_lat,
    }
    if h99:
        row["vs_healthy"] = round(float(d99) / float(h99), 2)
    return row


def byzantine_bench() -> None:
    """Run the paired Byzantine degraded-mode probes (ISSUE 18): open-
    loop arrivals against the n=4 forgery-rejecting toy-crypto cluster,
    once clean and once with an f=1 actor flooding forged votes at the
    shared verify plane.  The emitted row bounds what the flood costs
    HONEST clients once the per-sender accounting shuns and sheds the
    forger — the longitudinal pin that the defense keeps working."""
    import asyncio

    from smartbft_tpu.testing.chaos import byzantine_latency_probe

    rate = float(os.environ.get("SMARTBFT_BENCH_BYZ_RATE", "30"))

    async def paired():
        healthy = await byzantine_latency_probe(forge=False, rate=rate)
        degraded = await byzantine_latency_probe(forge=True, rate=rate)
        return healthy, degraded

    healthy, degraded = asyncio.run(paired())
    _emit(assemble_byzantine_row(healthy, degraded))


def selfdrive_bench() -> None:
    """Run the self-driving control-plane storm round (ISSUE 20): one
    ``remediation_storm_round`` — load spike, verify-engine hang, muted
    leader — with the verdict→action controller live, emitting the
    ``selfdrive_actions_per_fault`` and ``selfdrive_oscillation_reversals``
    guard rows.  The baseline pins actions-per-fault at the measured 1.0
    (trips past 2, the anti-thrash bound) and reversals at zero (any
    A→B→A flip inside one hysteresis window regresses)."""
    import asyncio

    from smartbft_tpu.obs.benchschema import assemble_selfdrive_rows
    from smartbft_tpu.testing.chaos import remediation_storm_round

    seed = int(os.environ.get("SMARTBFT_BENCH_SELFDRIVE_SEED", "1"))
    stats = asyncio.run(remediation_storm_round(seed=seed, verbose=False))
    for row in assemble_selfdrive_rows(stats):
        _emit(row)


def mixed_read_bench() -> None:
    """Run benchmarks/readplane.py (ISSUE 19): the mixed 95/5 read/write
    sweep against the live socket cluster (quorum-read p99 next to the
    same run's full-path write p99, the read-storm isolation check) plus
    the n=4 vs n=8 read-capacity scaling point, emitting the
    ``read_p99_ms`` and ``read_scaling_vs_n`` rows."""
    here = os.path.dirname(os.path.abspath(__file__))
    scale = os.environ.get("SMARTBFT_BENCH_READ_SCALE", "4,8")
    cmd = [sys.executable, os.path.join(here, "benchmarks", "readplane.py"),
           "--scale-nodes", scale]
    timeout = float(os.environ.get("SMARTBFT_BENCH_READ_TIMEOUT", "560"))
    proc = subprocess.run(
        cmd, timeout=timeout, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),  # no device in this bench
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"read-plane bench failed: "
            f"{proc.stderr.decode(errors='replace')[-400:]}"
        )
    rows = [json.loads(l) for l in proc.stdout.decode().splitlines()
            if l.strip()]
    if not rows:
        raise RuntimeError("read-plane bench produced no rows")
    for row in rows:
        _emit(row)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--shards", default=os.environ.get("SMARTBFT_BENCH_SHARDS", ""),
        help="comma-separated shard counts: additionally run the sharded "
             "sweep (benchmarks/sharded.py) and emit its JSON row with the "
             "per-shard + aggregate `shard` block",
    )
    ap.add_argument(
        "--mesh", nargs="?", const="1,2,4,8",
        default=os.environ.get("SMARTBFT_BENCH_MESH", ""),
        help="additionally run the mesh verify-plane sweep (benchmarks/"
             "mesh.py): fixed S, devices swept (default 1,2,4,8) on the "
             "virtual CPU mesh (real devices when present), emitting a "
             "`mesh` block (per-launch capacity/fill/pad-waste per device "
             "count + bit-for-bit verdict parity) in the JSON row",
    )
    ap.add_argument(
        "--open-loop", action="store_true",
        default=os.environ.get("SMARTBFT_BENCH_OPENLOOP", "") == "1",
        help="additionally run the open-loop service-level bench "
             "(benchmarks/openloop.py): Poisson/Zipf arrivals against the "
             "admission-controlled sharded front door, emitting a "
             "`latency` block (p50/p95/p99, shed counts, saturation knee, "
             "per-degraded-phase percentiles) in the JSON row",
    )
    ap.add_argument(
        "--transport", default=os.environ.get("SMARTBFT_BENCH_TRANSPORT", ""),
        choices=("", "inproc", "tcp", "uds"),
        help="additionally run the paired transport bench (benchmarks/"
             "transport.py): the SAME workload through the in-process "
             "Network and through real sockets on localhost, emitting a "
             "`transport` block (bytes on the wire, frames/flush, "
             "reconnects) in the JSON row",
    )
    ap.add_argument(
        "--rejoin", action="store_true",
        default=os.environ.get("SMARTBFT_BENCH_REJOIN", "") == "1",
        help="additionally run the rejoin bench (benchmarks/rejoin.py): "
             "snapshot-install vs full-chain-replay rejoin wall clock and "
             "bytes at shallow vs deep decision history "
             "(SMARTBFT_BENCH_REJOIN_HISTORIES, default 100,100000), "
             "emitting `rejoin_*` rows plus the flat-vs-depth guard row",
    )
    ap.add_argument(
        "--byzantine", action="store_true",
        default=os.environ.get("SMARTBFT_BENCH_BYZANTINE", "") == "1",
        help="additionally run the Byzantine degraded-mode probe "
             "(testing.chaos.byzantine_latency_probe): honest-path "
             "request p99 under an active vote-forgery flood vs the same "
             "cluster's no-actor control, emitting the "
             "byzantine_forge_p99_ms row the baseline bounds",
    )
    ap.add_argument(
        "--selfdrive", action="store_true",
        default=os.environ.get("SMARTBFT_BENCH_SELFDRIVE", "") == "1",
        help="additionally run the self-driving control-plane storm "
             "round (testing.chaos.remediation_storm_round): spike + "
             "engine hang + muted leader with the verdict→action "
             "controller live, emitting the selfdrive_actions_per_fault "
             "and selfdrive_oscillation_reversals guard rows",
    )
    ap.add_argument(
        "--mixed-read", action="store_true",
        default=os.environ.get("SMARTBFT_BENCH_MIXED_READ", "") == "1",
        help="additionally run the read-plane bench (benchmarks/"
             "readplane.py): mixed 95/5 quorum-read/write wall p99s "
             "against the live socket cluster, the read-storm shed "
             "isolation check, and the n=4 vs n=8 read-capacity scaling "
             "point (SMARTBFT_BENCH_READ_SCALE), emitting the "
             "read_p99_ms and read_scaling_vs_n rows",
    )
    ap.add_argument(
        "--cpu", action="store_true",
        default=os.environ.get("SMARTBFT_BENCH_CPU", "") == "1",
        help="run on the CPU (JAX_PLATFORMS=cpu in every child) at the "
             "CPU-sized configuration; without it the bench needs an "
             "accelerator and exits non-zero when JAX finds none",
    )
    ap.add_argument(
        "--check-baseline", nargs="?", const="BASELINE_OBS.json",
        default=os.environ.get("SMARTBFT_BENCH_CHECK_BASELINE", ""),
        help="after every selected bench ran, diff the emitted rows (plus "
             "the deterministic tiny logical-clock row) against the pinned "
             "baseline file (default BASELINE_OBS.json) and exit non-zero "
             "on regression or schema drift — the longitudinal guard "
             "(smartbft_tpu.obs.baseline)",
    )
    args, _unknown = ap.parse_known_args()

    cpu_mode = args.cpu
    if not cpu_mode:
        plat = _probe_platform()
        if plat in ("", "cpu"):
            raise SystemExit(
                f"bench: no accelerator (JAX platform probe gave {plat!r}); "
                "pass --cpu to run the CPU-sized bench instead"
            )

    if args.shards:
        sharded_bench(args.shards, cpu_mode)
    if args.mesh:
        mesh_bench(args.mesh, cpu_mode)
    if args.open_loop:
        open_loop_bench(cpu_mode)
    if args.transport:
        transport_bench(args.transport)
    if args.rejoin:
        rejoin_bench()
    if args.byzantine:
        byzantine_bench()
    if args.selfdrive:
        selfdrive_bench()
    if args.mixed_read:
        mixed_read_bench()

    if os.environ.get("SMARTBFT_BENCH_E2E", "1") == "1":
        e2e_bench(cpu_mode)
    else:
        kernel_bench(cpu_mode)

    if args.check_baseline:
        raise SystemExit(check_baseline(args.check_baseline))


def check_baseline(path: str) -> int:
    """The longitudinal regression gate: diff this run's emitted rows —
    plus the deterministic tiny logical-clock row, so the gate always
    has at least one comparable metric — against the pinned baseline.
    Returns the process exit code (non-zero on regression/drift)."""
    from smartbft_tpu.obs.baseline import (
        check_rows, load_baseline, render_check, tiny_logical_row,
    )

    rows = list(EMITTED_ROWS)
    tiny_failed = False
    try:
        rows.append(tiny_logical_row())
    except Exception as exc:  # noqa: BLE001 — the gate still checks the
        _log(f"bench: tiny logical row failed ({exc!r})")  # emitted rows
        tiny_failed = True
    result = check_rows(rows, load_baseline(path))
    _log(render_check(result))
    # a gate that compared NOTHING verified nothing: an empty comparison
    # (every bench failed AND the tiny row failed) must read as failure,
    # not as green — that is exactly the most-broken state
    vacuous = not result["checked"]
    ok = result["ok"] and not vacuous and not tiny_failed
    if vacuous:
        _log("bench: baseline check compared ZERO metrics — failing the "
             "gate (a vacuous check is not a passing one)")
    print(json.dumps({
        "metric": "baseline_check",
        "baseline": path,
        "ok": ok,
        "vacuous": vacuous,
        "tiny_row_failed": tiny_failed,
        "checked": result["checked"],
        "regressions": result["regressions"],
        "schema_errors": result["schema_errors"],
    }), flush=True)
    return 0 if ok else 1


def kernel_bench(cpu_mode: bool) -> None:
    BATCH = _resolve_batch(cpu_mode)  # must precede the first p256 import
    from smartbft_tpu.utils.jaxenv import enable_compile_cache, force_cpu

    if cpu_mode:
        force_cpu()
    else:
        enable_compile_cache()
    import jax
    import jax.numpy as jnp

    from smartbft_tpu.crypto import p256

    platform = jax.devices()[0].platform
    _log(f"bench: platform={platform} batch={BATCH} reps={REPS}")

    # workload: BATCH commit votes, 64 distinct replica keys, distinct msgs.
    # Signing goes through sign_raw (native OpenSSL when available, ~60 us;
    # the pure-Python RFC 6979 signer would take minutes at this scale).
    keys = [p256.keygen(b"bench-%d" % i) for i in range(64)]
    t0 = time.perf_counter()
    items = []
    for i in range(BATCH):
        d, pub = keys[i % 64]
        msg = b"proposal-%d" % i
        sig = p256.sign_raw(d, msg)
        r, s = int.from_bytes(sig[:32], "big"), int.from_bytes(sig[32:], "big")
        items.append((msg, r, s, pub))
    _log(f"bench: signed {BATCH} items in {time.perf_counter() - t0:.1f}s")

    import numpy as np

    # One kernel, chosen by the platform and never by a failure: the
    # static-key comb kernel on the accelerator, the XLA kernel on the CPU.
    # A kernel that does not compile fails the bench.  Every timed call
    # includes the RESULT READBACK (np.asarray), which is what the engine
    # does in production.
    if not cpu_mode:
        from smartbft_tpu.crypto import pallas_comb

        tile = int(os.environ.get("SMARTBFT_BENCH_TILE", "512"))
        reg = pallas_comb.CombKeyRegistry()
        t0 = time.perf_counter()
        e8, r8, s8, kidx = pallas_comb.pack_items(items, reg)
        _log(f"bench: host prep (tables for 64 keys + packing) "
             f"{time.perf_counter() - t0:.1f}s")
        gtab = jnp.asarray(pallas_comb.g_table(), jnp.bfloat16)
        qtab = jnp.asarray(reg.stacked(), jnp.bfloat16)
        cargs = tuple(jnp.asarray(a) for a in (e8, r8, s8, kidx))

        def kern():
            return pallas_comb.ecdsa_verify_comb(*cargs, gtab, qtab, tile=tile)

        kern_name = f"comb (tile={tile})"
    else:
        xargs = tuple(jnp.asarray(a) for a in p256.verify_inputs(items))
        xla = jax.jit(p256.ecdsa_verify_kernel)

        def kern():
            return xla(*xargs)

        kern_name = "xla"
    t0 = time.perf_counter()
    mask = np.asarray(kern())
    _log(f"bench: {kern_name} kernel first call (compile+run) "
         f"{time.perf_counter() - t0:.1f}s")

    if not mask.all():
        _log("bench: ERROR device kernel rejected valid signatures")
        raise SystemExit(1)

    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        np.asarray(kern())
        times.append(time.perf_counter() - t0)
    device_us = 1e6 * statistics.median(times) / BATCH
    _log(f"bench: kernel={kern_name}")
    _log(f"bench: device {device_us:.1f} us/sig "
         f"({BATCH / statistics.median(times):.0f} sigs/s)")

    base_n = min(BATCH, 256)
    base_us = _openssl_baseline(items[:base_n])
    _log(f"bench: openssl single-core {base_us:.1f} us/sig")
    mc_us, ncores = _openssl_all_cores_baseline(items[: max(base_n, 64 * ncores_hint())])
    _log(f"bench: openssl all-cores ({ncores}) {mc_us:.1f} us/sig effective")

    from smartbft_tpu.metrics import protocol_plane_snapshot

    _emit({
        "metric": "p256_sig_verify_p50_us",
        "value": round(device_us, 2),
        "unit": "us/sig",
        "vs_baseline": round(base_us / device_us, 3),
        "vs_all_cores": round(mc_us / device_us, 3),
        "cores": ncores,
        # kernel micro bench drives no cluster, so the plane block is the
        # (all-zero) process snapshot — present in EVERY bench row by
        # contract so downstream tooling can rely on the key
        "protocol_plane": protocol_plane_snapshot(),
    })


if __name__ == "__main__":
    main()
