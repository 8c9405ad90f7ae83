"""Mesh verify-plane sweep: one coalesced wave, N devices (ISSUE 10/11).

Fixed shard count S, devices swept over ``--devices`` (default 1,2,4,8):
each point runs a full S-shard cluster — routed front door, pipelined
windows, ONE shared coalescer — with the verify plane graduated onto a
D-device mesh through the REAL ``Configuration.verify_mesh_devices``
knob (``Consensus._wire_verify_plane`` → ``CryptoProvider.
configure_verify_mesh``), not a bench-only bypass.  Each engine carries
a fixed per-device lane budget, so aggregate per-launch CAPACITY scales
linearly with the mesh width — the economics that amortize the rig's
fixed ~220 ms launch overhead across all devices (PAPERS.md [7]).

ISSUE 11: every sweep point now runs TWICE at the same fixed workload —
an UNGATED control (``verify_flush_hold = 0``, the round-13 eager
contract) and a GATED run (occupancy-aware flush gating through the
real Configuration knob) — and the row carries both, so the
wave-deepening claim (gated fill > 90 % at D=8, strictly fewer
launches than the control) is measured, not asserted.  Client
submission is PACED (``--pace`` between decision rounds) so waves
arrive the way live traffic does — staggered — instead of as one
pre-loaded burst the eager window would accidentally coalesce anyway.

Stages, each printing JSON lines:

* **parity** — the same randomized mixed wave (several signers, forged
  items, counts that force pad slots) is verified through the
  single-device engine and through a MeshVerifyEngine at every swept
  device count; the row records whether every verdict vector matched
  bit-for-bit.  The tier-1 property test pins the same claim for P-256;
  the bench re-checks it for the crypto it actually runs.  A second
  ``mesh_parity_2d`` row makes the same bit-for-bit check through the
  seq×vote ``QuorumMeshVerifyEngine`` (the ``verify_mesh_topology =
  "2d"`` path, whose quorum counts psum across the 'vote' mesh axis).
* **sweep** — one ``{"bench": "mesh", "devices": D, ...}`` row per
  point (gated tx/s, launches, items/launch, capacity, fill, pad
  waste, mixed waves, the coalescer ``mesh`` block with its ``hold``
  decisions, plus the ungated control's launches/fill/tx) and a final
  ``mesh_scaling`` line comparing the top point against D=1.

Crypto: ``--crypto toy`` (default) is the real CryptoProvider stack over
``testing.toy_scheme`` — an array-math kernel that compiles in
milliseconds at EVERY mesh width, so the sweep runs anywhere (each
device count is a distinct mesh, hence a distinct XLA computation; the
P-256 bignum kernel costs minutes per mesh shape on a cold cache).
``--crypto p256`` runs the production curve for device rigs.

On CPU-only hosts the sweep self-provisions a virtual device mesh
exactly like the MULTICHIP harness (``force_cpu(virtual_devices=N)``);
with real accelerators present it uses them, dropping (and logging)
sweep points wider than the host.

Run:  python benchmarks/mesh.py [--devices 1,2,4,8] [--shards 2] [--cpu]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from smartbft_tpu.utils.jaxenv import force_cpu  # noqa: E402


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


#: per-sweep-point commit deadline (seconds); bench.py derives its
#: subprocess timeout from this so a stuck point degrades inside this
#: child (which salvages the other rows) instead of the parent killing
#: the whole mesh block (the PR 5/7/8 salvage lesson)
POINT_TIMEOUT = float(os.environ.get("SMARTBFT_BENCH_MESH_POINT_TIMEOUT",
                                     "120"))


def _scheme(crypto: str):
    if crypto == "toy":
        from smartbft_tpu.testing import toy_scheme

        return toy_scheme
    from smartbft_tpu.crypto import p256

    return p256


def _mixed_wave(scheme, n_signers: int = 3, count: int = 23,
                forge_every: int = 5, seed: bytes = b"mesh-parity"):
    """One mixed-tag wave: ``count`` items round-robined over
    ``n_signers`` distinct keys (the shard analog), every
    ``forge_every``-th signature corrupted.  ``count`` deliberately not a
    device multiple, so every mesh width exercises pad slots."""
    keys = [scheme.keygen(seed + b"-%d" % i) for i in range(n_signers)]
    items, expect = [], []
    for i in range(count):
        sk, pub = keys[i % n_signers]
        msg = b"mesh-msg-%d" % i
        sig = scheme.sign_raw(sk, msg)
        ok = i % forge_every != forge_every - 1
        if not ok:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        items.append(scheme.make_item(msg, sig, pub))
        expect.append(ok)
    return items, expect


def run_parity(device_counts: list[int], crypto: str) -> dict:
    """Bit-for-bit verdict parity: mesh engines at every device count
    against the single-device engine over the same mixed wave."""
    from smartbft_tpu.crypto.provider import JaxVerifyEngine
    from smartbft_tpu.parallel import MeshVerifyEngine

    scheme = _scheme(crypto)
    items, expect = _mixed_wave(scheme)
    single = JaxVerifyEngine(pad_sizes=(16, 64), scheme=scheme)
    base = single.verify(items)
    match = base == expect
    checked = []
    for d in device_counts:
        mesh = MeshVerifyEngine(devices=d, pad_sizes=(16, 64), scheme=scheme)
        got = mesh.verify(items)
        checked.append(d)
        if got != base:
            match = False
            _log(f"mesh parity: MISMATCH at devices={d}")
    return {
        "metric": "mesh_parity",
        "crypto": crypto,
        "devices_checked": checked,
        "items": len(items),
        "match": bool(match),
    }


def run_parity_2d(device_counts: list[int], crypto: str) -> dict:
    """The 2D (seq×vote) quorum-mesh parity row (ISSUE 11 tentpole b):
    the same mixed wave through ``QuorumMeshVerifyEngine`` at every
    even swept width must match the single-device engine bit for bit,
    and the psum'd per-message vote counts must equal the host tally of
    valid verdicts."""
    from smartbft_tpu.crypto.provider import JaxVerifyEngine
    from smartbft_tpu.parallel import QuorumMeshVerifyEngine

    scheme = _scheme(crypto)
    items, expect = _mixed_wave(scheme)
    base = JaxVerifyEngine(pad_sizes=(16, 64), scheme=scheme).verify(items)
    match = base == expect
    counts_match = True
    checked = []
    for d in device_counts:
        eng = QuorumMeshVerifyEngine(devices=d, scheme=scheme, quorum=3)
        got = eng.verify(items)
        checked.append(d)
        if got != base:
            match = False
            _log(f"mesh 2d parity: verdict MISMATCH at devices={d}")
        tally: dict = {}
        for it, ok in zip(items, got):
            tally[it[0]] = tally.get(it[0], 0) + (1 if ok else 0)
        if eng.last_counts != tally:
            counts_match = False
            _log(f"mesh 2d parity: psum count MISMATCH at devices={d}")
    return {
        "metric": "mesh_parity_2d",
        "crypto": crypto,
        "devices_checked": checked,
        "items": len(items),
        "match": bool(match),
        "counts_match": bool(counts_match),
    }


def build_cluster(tmp, devices: int, args, scheme, hold: float):
    """S-shard cluster whose verify plane graduates onto a
    ``devices``-wide mesh through the Configuration knob; ``hold``
    arms occupancy-aware flush gating through the REAL
    ``verify_flush_hold`` knob (0 = the ungated control)."""
    import dataclasses

    from smartbft_tpu.crypto.provider import JaxVerifyEngine
    from smartbft_tpu.testing.sharded import ShardedCluster, sharded_config

    per_dev = tuple(int(x) for x in args.per_device_lanes.split(",")
                    if x.strip())
    pad_sizes = tuple(l * devices for l in per_dev)

    def cfg(s, i):
        return dataclasses.replace(
            sharded_config(i, depth=args.pipeline),
            verify_mesh_devices=devices,
            verify_mesh_topology=args.topology,
            verify_flush_hold=hold,
            wal_group_commit=True,
            request_batch_max_count=args.batch,
            request_batch_max_interval=0.02,
            request_pool_size=max(4 * args.decisions * args.batch, 800),
            incoming_message_buffer_size=max(2000, 40 * args.nodes),
            request_forward_timeout=300.0,
            request_complain_timeout=600.0,
            request_auto_remove_timeout=1200.0,
            view_change_resend_interval=300.0,
            view_change_timeout=1200.0,
            leader_heartbeat_timeout=900.0,
        )

    # the initial engine only donates its pad ladder: configure_verify_mesh
    # (wired from the knob at Consensus.start) swaps the coalescer onto the
    # MeshVerifyEngine with the SAME ladder — fixed lanes per device, so
    # capacity scales with the mesh width
    seed_engine = JaxVerifyEngine(pad_sizes=pad_sizes, scheme=scheme)
    return ShardedCluster(
        tmp, shards=args.shards, n=args.nodes, depth=args.pipeline,
        crypto=args.crypto, engine=seed_engine, window=args.window,
        config_fn=cfg, seed=17,
    )


async def run_cluster_point(devices: int, args, hold: float,
                            on_engine=None) -> dict:
    """One fixed-workload cluster run at ``devices`` width with the
    given flush-hold knob; returns the raw measurement dict.
    ``on_engine``: called with the graduated, prewarmed engine before any
    request is submitted (chip_smoke.py checks its verdicts there)."""
    from smartbft_tpu.crypto.provider import (
        VerifyStats,
        prewarm_verify_engine,
    )
    from smartbft_tpu.utils.clock import WallClockDriver

    scheme = _scheme(args.crypto)
    requests_per_shard = args.decisions * args.batch
    tmp = tempfile.mkdtemp(prefix=f"bench-mesh-{devices}-")
    cluster = build_cluster(tmp, devices, args, scheme, hold)
    driver = WallClockDriver(cluster.scheduler, tick_interval=0.01)
    try:
        driver.start()
        await cluster.start()
        engine = cluster.coalescer.engine
        got_devices = int(getattr(engine, "devices", 0))
        if got_devices != devices \
                or getattr(engine, "topology", "1d") != args.topology:
            raise RuntimeError(
                f"knob wiring failed: wanted a {devices}-device "
                f"{args.topology} mesh, coalescer runs "
                f"{type(engine).__name__} ({got_devices})"
            )
        if abs(cluster.coalescer.hold - hold) > 1e-9:
            raise RuntimeError(
                f"knob wiring failed: wanted verify_flush_hold={hold}, "
                f"coalescer holds {cluster.coalescer.hold}"
            )
        # pre-warm every mesh lane shape (persists into the compilation
        # cache — see enable_compile_cache) + probe the warm launch cost
        prewarm_verify_engine(engine, scheme)
        sk, pub = scheme.keygen(b"mesh-probe")
        item = scheme.make_item(b"p", scheme.sign_raw(sk, b"p"), pub)
        t0 = time.perf_counter()
        for _ in range(3):
            engine.verify([item])
        launch_probe_ms = 1e3 * (time.perf_counter() - t0) / 3
        if on_engine is not None:
            on_engine(engine)
        engine.stats = type(engine.stats)(
            devices=got_devices, metrics=engine.stats.metrics
        ) if hasattr(engine.stats, "devices") else VerifyStats()

        for s in range(args.shards):
            cluster.client_for_shard(s, 3)
        t0 = time.perf_counter()
        # PACED submission: one decision round per pace interval, so
        # waves arrive staggered like live traffic (the eager window
        # would otherwise coalesce a pre-loaded burst by accident and
        # the gated-vs-ungated comparison would measure nothing)
        for j in range(args.decisions):
            for s in range(args.shards):
                for k in range(args.batch):
                    cid = cluster.client_for_shard(s, (j + k) % 4)
                    await cluster.submit(cid, f"m-{s}-{j}-{k}")
            if args.pace > 0:
                await asyncio.sleep(args.pace)
        deadline = time.perf_counter() + POINT_TIMEOUT
        while time.perf_counter() < deadline:
            if all(sh.committed() >= requests_per_shard
                   for sh in cluster.shard_list):
                break
            await asyncio.sleep(0.02)
        else:
            raise TimeoutError(
                f"devices={devices}: shards committed "
                f"{[sh.committed() for sh in cluster.shard_list]} "
                f"of {requests_per_shard} in time"
            )
        elapsed = time.perf_counter() - t0
        cluster.check_invariants()

        stats = engine.stats
        return {
            "hold_s": hold,
            "launch_probe_ms": round(launch_probe_ms, 2),
            "elapsed_s": round(elapsed, 2),
            "total": sum(sh.committed() for sh in cluster.shard_list),
            "decisions": sum(sh.height() for sh in cluster.shard_list),
            "launches": stats.launches,
            "items": stats.sigs_verified,
            "fill_pct": round(stats.batch_fill_pct, 1),
            "capacity": int(engine.pad_sizes[-1]),
            "mesh": cluster.coalescer.mesh_snapshot(),
            "mixed_waves": cluster.coalescer.shard_snapshot()["mixed_waves"],
        }
    finally:
        try:
            await cluster.stop()
        except Exception:
            pass
        await driver.stop()
        shutil.rmtree(tmp, ignore_errors=True)


async def run_sweep_point(devices: int, args) -> dict:
    """One devices-sweep row: the UNGATED control first (hold 0, the
    round-13 contract), then the GATED run at the same fixed workload.
    Gated numbers are the row's primary values; the control rides along
    as ``*_ungated`` so fill/launch deltas are in every row.  With
    ``--hold 0`` the two runs would be identical, so the control is
    reused instead of paying a second cluster for a no-op comparison."""
    control = await run_cluster_point(devices, args, 0.0)
    gated = control if args.hold <= 0 \
        else await run_cluster_point(devices, args, args.hold)
    mesh_block = gated["mesh"]
    return {
        "bench": "mesh",
        "devices": devices,
        "shards": args.shards,
        "crypto": args.crypto,
        "nodes_per_shard": args.nodes,
        "pipeline": args.pipeline,
        "decisions": gated["decisions"],
        "hold_s": args.hold,
        "pace_s": args.pace,
        "tx_per_sec": round(gated["total"] / gated["elapsed_s"], 1)
        if gated["elapsed_s"] else 0.0,
        "launches": gated["launches"],
        "items_per_launch":
            round(gated["items"] / gated["launches"], 1)
            if gated["launches"] else 0.0,
        "capacity_items_per_launch": gated["capacity"],
        "batch_fill_pct": gated["fill_pct"],
        "pad_waste_pct": mesh_block.get("pad_waste_pct", 0.0),
        "mixed_waves": gated["mixed_waves"],
        "launch_probe_ms": gated["launch_probe_ms"],
        "elapsed_s": gated["elapsed_s"],
        # the ungated control at the SAME fixed workload: the
        # wave-deepening deltas (fill up, launches strictly down)
        "launches_ungated": control["launches"],
        "batch_fill_ungated_pct": control["fill_pct"],
        "tx_per_sec_ungated": round(
            control["total"] / control["elapsed_s"], 1)
        if control["elapsed_s"] else 0.0,
        "mesh": mesh_block,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", default="1,2,4,8",
                    help="comma-separated mesh widths to sweep")
    ap.add_argument("--shards", type=int, default=2,
                    help="FIXED shard count S (the sweep varies devices)")
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--decisions", type=int, default=12,
                    help="decisions committed per shard per point")
    ap.add_argument("--pipeline", type=int, default=8)
    ap.add_argument("--crypto", choices=("toy", "p256"), default="toy")
    ap.add_argument("--topology", choices=("1d", "2d"), default="1d",
                    help="the mesh shape the sweep's clusters graduate "
                         "onto (Configuration.verify_mesh_topology)")
    ap.add_argument("--per-device-lanes", default="4,8,12,16",
                    help="pad-ladder lanes contributed by EACH device — "
                         "per-launch capacity = lanes x devices (a denser "
                         "ladder lets deepened waves land near a rung)")
    ap.add_argument("--window", type=float, default=0.02,
                    help="coalescer fan-in window (seconds)")
    ap.add_argument("--hold", type=float, default=0.25,
                    help="verify_flush_hold for the GATED run (seconds; "
                         "the ungated control always runs at 0)")
    ap.add_argument("--pace", type=float, default=0.03,
                    help="sleep between decision submission rounds — "
                         "staggers wave arrivals like live traffic")
    ap.add_argument("--cpu", action="store_true",
                    help="pin JAX to CPU and self-provision a virtual "
                         "device mesh (the MULTICHIP harness idiom)")
    args = ap.parse_args()

    sweep = [int(x) for x in args.devices.split(",") if x.strip()]
    if args.cpu or os.environ.get("SMARTBFT_BENCH_CPU") == "1":
        force_cpu(virtual_devices=max(sweep))
    else:
        # device rigs: persist compiled mesh shapes across bench
        # subprocesses — the 2-3 min per-process compile tax must not
        # poison every row
        from smartbft_tpu.utils.jaxenv import enable_compile_cache

        enable_compile_cache()
    import jax

    avail = len(jax.devices())
    dropped = [d for d in sweep if d > avail]
    if dropped:
        # no silent caps: the sweep runs what fits and SAYS what it dropped
        _log(f"mesh: host has {avail} device(s); dropping sweep points "
             f"{dropped}")
        sweep = [d for d in sweep if d <= avail]
    if not sweep:
        _log("mesh: no sweep point fits this host")
        return

    try:
        print(json.dumps(run_parity(sweep, args.crypto)), flush=True)
    except Exception as exc:  # noqa: BLE001 — parity row is additive
        _log(f"mesh parity: FAILED — {exc!r}")
    try:
        # the 2D engine needs an even width for a real 'vote' axis
        two_d = [d for d in sweep if d % 2 == 0] or sweep
        print(json.dumps(run_parity_2d(two_d, args.crypto)), flush=True)
    except Exception as exc:  # noqa: BLE001 — parity row is additive
        _log(f"mesh 2d parity: FAILED — {exc!r}")

    rows = []
    for d in sweep:
        try:
            row = asyncio.run(run_sweep_point(d, args))
        except Exception as exc:  # noqa: BLE001 — a failed point costs
            # ITS slot only; the sweep still prints the other rows
            _log(f"mesh[{d}]: FAILED — {exc!r}")
            continue
        _log(f"mesh[{d}]: {row['tx_per_sec']} tx/s, {row['launches']} "
             f"launches (ungated {row['launches_ungated']}), "
             f"{row['items_per_launch']} items/launch "
             f"(capacity {row['capacity_items_per_launch']}), fill "
             f"{row['batch_fill_pct']}% (ungated "
             f"{row['batch_fill_ungated_pct']}%)")
        print(json.dumps(row), flush=True)
        rows.append(row)

    by_d = {r["devices"]: r for r in rows}
    if len(by_d) >= 2:
        base = by_d[min(by_d)]
        top = by_d[max(by_d)]
        print(json.dumps({
            "metric": "mesh_scaling",
            "value": round(
                top["capacity_items_per_launch"]
                / base["capacity_items_per_launch"], 3
            ) if base["capacity_items_per_launch"] else 0.0,
            "unit": f"x per-launch capacity at D={top['devices']} vs "
                    f"D={base['devices']}",
            "devices": sorted(by_d),
            "tx_ratio": round(top["tx_per_sec"] / base["tx_per_sec"], 3)
            if base["tx_per_sec"] else 0.0,
            "items_per_launch_ratio": round(
                top["items_per_launch"] / base["items_per_launch"], 3
            ) if base["items_per_launch"] else 0.0,
            "launch_ratio": round(top["launches"] / base["launches"], 3)
            if base["launches"] else 0.0,
        }), flush=True)


if __name__ == "__main__":
    main()
