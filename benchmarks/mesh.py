"""The S-shard cluster on a D-device mesh behind ``chip_smoke.py --chips 4``.

``run_cluster_point`` runs a full S-shard cluster (routed front door,
pipelined windows, ONE shared coalescer) with the verify plane graduated
onto a D-device mesh through the REAL ``Configuration.verify_mesh_devices``
knob (``Consensus._wire_verify_plane`` -> ``CryptoProvider.
configure_verify_mesh``), commits a fixed number of requests and returns
what the smoke holds the run to.  Each engine carries a fixed per-device
lane budget, so per-launch capacity scales with the mesh width.

Crypto: ``toy`` is the real CryptoProvider stack over
``testing.toy_scheme`` (an array-math kernel that compiles in milliseconds
at every mesh width); ``p256`` is the production curve.
"""

from __future__ import annotations

import asyncio
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


#: commit deadline of one run (seconds)
POINT_TIMEOUT = 120.0


def _scheme(crypto: str):
    if crypto == "toy":
        from smartbft_tpu.testing import toy_scheme

        return toy_scheme
    from smartbft_tpu.crypto import p256

    return p256


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
        # cache — see enable_compile_cache)
        prewarm_verify_engine(engine, scheme)
        if on_engine is not None:
            on_engine(engine)
        engine.stats = type(engine.stats)(
            devices=got_devices, metrics=engine.stats.metrics
        ) if hasattr(engine.stats, "devices") else VerifyStats()

        for s in range(args.shards):
            cluster.client_for_shard(s, 3)
        t0 = time.perf_counter()
        # PACED submission: one decision round per pace interval, so
        # waves arrive staggered like live traffic
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
