"""Cluster throughput benchmark: committed tx/sec with real crypto.

The BASELINE.md north-star metric.  Spins an n-node cluster in one process
(production wall-clock mode), every commit vote a real signature, and
measures committed transactions per second end-to-end — submit, batch,
three protocol phases, quorum signature verification, two fsync'd WAL
appends per decision, deliver.

Engines (--engines, comma-separated, one cluster run each):
  openssl — OpenSSL via the `cryptography` wheel (the fair stand-in for
            the reference's Go crypto/ecdsa native path).  p256 only.
  jax     — the batched device kernel + async coalescer (cross-sequence
            cross-replica batching).
  host    — pure-Python arithmetic (floor reference).

Schemes (--scheme): p256 (default), ed25519 (BASELINE configs[3]),
bls (configs[4]: aggregate quorum, one pairing equation per check).

--share-engine (default on for jax): all replicas share ONE engine and ONE
async coalescer — the single-chip deployment shape, where concurrent
quorum checks from different replicas merge into shared kernel launches
(the cross-replica half of configs[2]'s batching).

Run:  python benchmarks/throughput.py [--nodes 4] [--requests 600]
      [--batch 100] [--engines openssl,jax] [--scheme p256]
Prints one JSON line per engine plus a final comparison line.
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

from smartbft_tpu.utils.jaxenv import force_cpu


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def get_scheme(name: str):
    if name == "p256":
        from smartbft_tpu.crypto import p256

        return p256
    if name == "ed25519":
        from smartbft_tpu.crypto import ed25519

        return ed25519
    if name == "bls":
        from smartbft_tpu.crypto import bls12381

        return bls12381
    raise ValueError(f"unknown scheme {name}")


def get_provider_cls(name: str):
    from smartbft_tpu.crypto.provider import (
        BlsCryptoProvider,
        Ed25519CryptoProvider,
        P256CryptoProvider,
    )

    return {"p256": P256CryptoProvider, "ed25519": Ed25519CryptoProvider,
            "bls": BlsCryptoProvider}[name]


def build_engine(kind: str, pad_sizes, scheme, n_nodes: int = 4):
    from smartbft_tpu.crypto.provider import HostVerifyEngine, JaxVerifyEngine

    if kind == "openssl":
        from smartbft_tpu.crypto import p256
        from smartbft_tpu.crypto.openssl_engine import OpenSSLVerifyEngine

        if scheme is not p256:
            raise ValueError("the openssl engine is p256-only")
        return OpenSSLVerifyEngine(scheme=scheme)
    if kind == "jax":
        return JaxVerifyEngine(pad_sizes=pad_sizes, scheme=scheme)
    if kind == "sharded":
        # quorum waves sharded over ALL visible devices (SURVEY §2.4's
        # multi-chip shape; on CI this is the virtual 8-CPU mesh —
        # run with --cpu or JAX_PLATFORMS=cpu
        # XLA_FLAGS=--xla_force_host_platform_device_count=8)
        from smartbft_tpu.parallel import ShardedVerifyEngine, build_mesh

        return ShardedVerifyEngine(mesh=build_mesh(), pad_sizes=pad_sizes,
                                   scheme=scheme)
    if kind == "sharded2d":
        # the 2D (seq x vote) quorum-block path: waves group by sequence
        # and vote counts psum across the 'vote' mesh axis (quorum_decide
        # under live consensus); multi-chip validation shape
        import jax

        from smartbft_tpu.parallel import QuorumMeshVerifyEngine, build_mesh

        ndev = len(jax.devices())
        vote_par = 2 if ndev % 2 == 0 else 1
        mesh = build_mesh((ndev // vote_par, vote_par), ("seq", "vote"))
        # honor --pad-sizes: the engine's block is seq_tile x vote_tile
        # lanes, sized so one block covers the requested top rung
        vote_tile = max(16, n_nodes)
        seq_tile = max(1, -(-max(pad_sizes) // vote_tile))
        quorum = (n_nodes + (n_nodes - 1) // 3 + 1 + 1) // 2
        return QuorumMeshVerifyEngine(mesh=mesh, quorum=quorum,
                                      seq_tile=seq_tile,
                                      vote_tile=vote_tile, scheme=scheme)
    if kind == "host":
        return HostVerifyEngine(scheme=scheme)
    raise ValueError(f"unknown engine {kind}")


# moved into the library (PR 28); re-exported for the callers that import
# it from here (chip_smoke.py, chipbench/deployments/sharded.py)
from smartbft_tpu.crypto.ladder import auto_pad_sizes  # noqa: E402,F401


def bench_keyrings(n: int, scheme) -> dict:
    """node id -> Keyring for the n bench replicas (ids 1..n)."""
    from smartbft_tpu.crypto.provider import Keyring

    return Keyring.generate(list(range(1, n + 1)), seed=b"bench-tput",
                            scheme=scheme)


async def run_cluster(engine_kind: str, n: int, requests: int, batch: int,
                      pad_sizes, scheme_name: str = "p256",
                      share_engine: bool = False,
                      dedupe: bool = False,
                      pipeline: int = 1,
                      burst_decisions: int = 0,
                      ledgers_out: dict | None = None) -> dict:
    """``burst_decisions`` > 0 enables the sustained-burst mode: the request
    count is sized to commit that many decisions back to back (decisions x
    batch requests submitted up front), so the FIRST launch's fixed cost is
    amortized over a long window train instead of a single window, and the
    JSON row carries per-window launch counts.

    ``ledgers_out``: when given, filled with node id -> the request ids
    that node committed, in ledger order (what a caller needs to hold the
    run to fork-freedom and exactly-once)."""
    import dataclasses

    from smartbft_tpu.crypto.provider import AsyncBatchCoalescer
    from smartbft_tpu.testing.app import App, SharedLedgers, fast_config
    from smartbft_tpu.testing.network import Network
    from smartbft_tpu.utils.clock import Scheduler, WallClockDriver

    scheme = get_scheme(scheme_name)
    provider_cls = get_provider_cls(scheme_name)
    if burst_decisions > 0:
        requests = burst_decisions * batch

    def cfg(i):
        pipe = {}
        if pipeline > 1:
            # pipelined window requires rotation off (config.validate)
            pipe = dict(leader_rotation=False, decisions_per_leader=0,
                        pipeline_depth=pipeline)
        return dataclasses.replace(
            fast_config(i),
            **pipe,
            wal_group_commit=True,  # production durability path
            request_batch_max_count=batch,
            request_batch_max_interval=0.02,
            request_pool_size=max(2 * requests, 800),
            incoming_message_buffer_size=max(2000, 40 * n),
            request_forward_timeout=300.0,
            request_complain_timeout=600.0,
            request_auto_remove_timeout=1200.0,
            view_change_resend_interval=300.0,
            view_change_timeout=1200.0,
            leader_heartbeat_timeout=900.0,
        )

    node_ids = list(range(1, n + 1))
    rings = bench_keyrings(n, scheme)
    if share_engine:
        one = build_engine(engine_kind, pad_sizes, scheme, n_nodes=n)
        engines = {i: one for i in node_ids}
        # wider fan-in window when a whole cluster shares one chip:
        # waiting ~20ms merges every replica's quorum check into ONE
        # launch (sized on an earlier rig whose launches cost ~100 ms)
        window = float(os.environ.get("SMARTBFT_BENCH_WINDOW", "0.02"))
        # pipelined mode: up to 2*`pipeline` decisions' quorum waves (base
        # window + launch shadow) coalesce into one flush — max_batch must
        # not force-flush a single wave
        coalescer = AsyncBatchCoalescer(one, window=window,
                                        max_batch=2 * pipeline * max(pad_sizes),
                                        dedupe=dedupe)
        coalescers = {i: coalescer for i in node_ids}
    else:
        engines = {i: build_engine(engine_kind, pad_sizes, scheme, n_nodes=n)
                   for i in node_ids}
        coalescers = {i: None for i in node_ids}

    # warm with a RING key: a foreign key would grow the comb-table
    # registry past the membership (65 keys -> npad 128) and force a
    # recompile of every padded shape mid-run
    sk, pub = scheme.keygen(b"bench-tput-1")
    item = scheme.make_item(
        b"warm-msg", scheme.sign_raw(sk, b"warm-msg"), pub
    )
    # pre-warm every device engine at every lane size so no XLA compile
    # lands inside the timed window
    if engine_kind in ("jax", "sharded", "sharded2d"):
        for eng in set(engines.values()):
            if hasattr(eng, "prewarm_keys"):
                eng.prewarm_keys(
                    rings[node_ids[0]].public_keys.values()
                )
        t0 = time.perf_counter()
        for eng in set(engines.values()):
            for size in pad_sizes:
                eng.verify([item] * size)
        _log(f"bench[{engine_kind}/{scheme_name}]: pre-warmed pad sizes "
             f"{tuple(pad_sizes)} on {len(set(engines.values()))} engine(s) "
             f"in {time.perf_counter() - t0:.1f}s")
    # measure the steady-state per-launch overhead (device: launch + pad;
    # host engines: one warm single-item verify) for EVERY engine kind —
    # launch_probe_ms in the JSON row is what lets ratios be normalized
    # across measurement days (VERDICT round-5 item 6)
    probe_eng = engines[node_ids[0]]
    probe_eng.verify([item])  # warm the single-item shape itself
    t0 = time.perf_counter()
    for _ in range(3):
        probe_eng.verify([item])
    launch_probe_ms = 1e3 * (time.perf_counter() - t0) / 3
    _log(f"bench[{engine_kind}/{scheme_name}]: warm launch overhead "
         f"{launch_probe_ms:.1f} ms")
    # drop warm-up/probe traffic from the reported stats
    from smartbft_tpu.crypto.provider import VerifyStats

    for eng in set(engines.values()):
        eng.stats = VerifyStats()

    from smartbft_tpu.metrics import PROTOCOL_PLANE, ProtocolPlaneTimers

    scheduler = Scheduler()
    driver = WallClockDriver(scheduler, tick_interval=0.01)
    network = Network(seed=13)
    shared = SharedLedgers()
    tmp = tempfile.mkdtemp(prefix=f"bench-tput-{engine_kind}-")
    providers = {
        i: provider_cls(rings[i], engine=engines[i], coalescer=coalescers[i])
        for i in node_ids
    }
    apps = [
        App(i, network, shared, scheduler,
            wal_dir=os.path.join(tmp, f"wal-{i}"), config=cfg(i),
            crypto=providers[i])
        for i in node_ids
    ]
    try:
        driver.start()
        for a in apps:
            await a.start()

        # snapshot the protocol-plane timers at the start of the timed
        # window so the row's block covers exactly the measured burst
        plane_before = PROTOCOL_PLANE.snapshot()
        t0 = time.perf_counter()
        for k in range(requests):
            await apps[0].submit("bench", f"req-{k}")

        target = requests
        deadline = time.perf_counter() + 600.0

        def committed(app) -> int:
            return sum(
                len(app.requests_from_proposal(d.proposal)) for d in app.ledger()
            )

        # per-window launch sampling: snapshot the launch counter each time
        # the leader's ledger crosses a k-decision window boundary, so the
        # row shows how the coalescer amortizes launches ACROSS the burst
        # (window_launches[i] = launches during the i-th window of k
        # decisions), not just the end-to-end total
        stats_eng = engines[node_ids[1]]  # follower / shared engine
        window_size = max(1, pipeline)
        marks: list[int] = []
        next_mark = window_size
        while time.perf_counter() < deadline:
            d = len(apps[0].ledger())
            while d >= next_mark:
                marks.append(stats_eng.stats.launches)
                next_mark += window_size
            if all(committed(a) >= target for a in apps):
                break
            await asyncio.sleep(0.02)
        else:
            raise TimeoutError(f"cluster did not commit {target} requests in time")
        elapsed = time.perf_counter() - t0
        # per-phase protocol-plane timers for the timed window (encode-once
        # broadcast + wave-batched ingest accounting; PERF.md decomposition)
        plane = ProtocolPlaneTimers.delta(plane_before, PROTOCOL_PLANE.snapshot())

        decisions = len(apps[0].ledger())
        if ledgers_out is not None:
            for a in apps:
                ledgers_out[a.id] = [
                    (info.client_id, info.request_id)
                    for d in a.ledger()
                    for info in a.requests_from_proposal(d.proposal)
                ]
        stats = stats_eng.stats
        if len(marks) * window_size < decisions:
            marks.append(stats.launches)  # tail window (partial)
        window_launches = [
            b - a for a, b in zip([0] + marks[:-1], marks)
        ]
        # verify-plane fault accounting: breaker state + fallback counts in
        # EVERY row, so a degraded (host-fallback) run is never silently
        # reported as a device run.  Shared mode has one coalescer; in
        # per-replica mode ANY node degrading must show, so snapshots are
        # aggregated (counters summed, flags OR-ed) across all nodes.
        coalescers = list({
            id(providers[i].coalescer): providers[i].coalescer
            for i in node_ids
        }.values())
        snaps = [co.fault_snapshot() for co in coalescers]
        breaker_row = {
            k: (any(s[k] for s in snaps) if isinstance(snaps[0][k], bool)
                else sum(s[k] for s in snaps))
            for k in snaps[0]
        }
        # mesh block (ISSUE 10 contract: in EVERY bench row) — shared mode
        # has one coalescer; in per-replica mode the planes are homogeneous
        # in SHAPE (devices/enabled/downgrades) but the launch/fill counts
        # below are ONE plane's, so `planes` makes the scope explicit
        mesh_row = dict(coalescers[0].mesh_snapshot(),
                        planes=len(coalescers))
        return {
            "engine": engine_kind,
            "scheme": scheme_name,
            "nodes": n,
            "shared_engine": share_engine,
            "dedupe": dedupe,
            "pipeline": pipeline,
            "burst_decisions": burst_decisions,
            "tx_per_sec": round(requests / elapsed, 1),
            "decisions": decisions,
            "batch_fill_pct": round(stats.batch_fill_pct, 1),
            "verify_us_per_sig": round(stats.us_per_sig, 1),
            "launches": stats.launches,
            "launches_by_kernel": dict(stats.launches_by_kernel),
            "launches_per_decision": round(stats.launches / decisions, 3)
            if decisions else 0.0,
            "window_launches": window_launches,
            "launch_probe_ms": round(launch_probe_ms, 2),
            "sigs_verified": stats.sigs_verified,
            "elapsed_s": round(elapsed, 2),
            "breaker": breaker_row,
            "mesh": mesh_row,
            "protocol_plane": dict(
                plane,
                # the four timers are disjoint (metrics.ProtocolPlaneTimers),
                # so their sum is the plane's accounted cost per decision
                us_per_decision=round(
                    (plane["ingest_us"] + plane["route_us"]
                     + plane["vote_reg_us"] + plane["codec_us"]) / decisions, 1
                ) if decisions else 0.0,
                encodes_per_broadcast=round(
                    plane["encodes"] / plane["broadcasts"], 3
                ) if plane["broadcasts"] else 0.0,
            ),
        }
    finally:
        for a in apps:
            try:
                await a.stop()
            except Exception:
                pass
        await driver.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--requests", type=int, default=600)
    ap.add_argument("--batch", type=int, default=100)
    ap.add_argument("--engines", default="openssl,jax")
    ap.add_argument("--scheme", default="p256",
                    choices=("p256", "ed25519", "bls"))
    ap.add_argument(
        "--pad-sizes", default="auto",
        help="comma-separated engine pad ladder, or 'auto': derive it from "
             "the cluster size (see auto_pad_sizes)",
    )
    ap.add_argument("--share-engine", choices=("auto", "yes", "no"),
                    default="auto",
                    help="share one engine+coalescer across replicas "
                         "(auto: yes for the jax engine)")
    ap.add_argument("--dedupe", choices=("auto", "yes", "no"), default="auto",
                    help="deduplicate identical verify items within a "
                         "coalesced flush (auto: on when the engine is "
                         "shared — colocated replicas re-check the same "
                         "commit votes, so a quorum wave holds each "
                         "signature up to n times)")
    ap.add_argument("--cpu", action="store_true",
                    help="pin JAX to the CPU backend")
    ap.add_argument("--pipeline", type=int, default=1,
                    help="pipelined in-flight window depth k (k>=2 runs "
                         "rotation-off mode: the leader keeps k sequences "
                         "outstanding — up to 2k under the launch shadow — "
                         "so consecutive quorum waves coalesce into shared "
                         "device launches)")
    ap.add_argument("--burst-decisions", type=int, default=0,
                    help="sustained-burst mode: size the request load to "
                         "commit this many decisions back to back "
                         "(overrides --requests with N*batch); the JSON row "
                         "then carries per-window launch counts so launch "
                         "amortization over the burst is visible")
    args = ap.parse_args()
    if args.pad_sizes == "auto":
        pad_sizes = auto_pad_sizes(args.nodes, args.scheme, args.pipeline)
    else:
        pad_sizes = tuple(int(x) for x in args.pad_sizes.split(","))

    if args.cpu or os.environ.get("SMARTBFT_BENCH_CPU") == "1":
        force_cpu()
    else:
        # persistent XLA compile cache on the device path too (force_cpu
        # enables it for the CPU path): pad-shape prewarms cost full
        # compiles otherwise, every run
        from smartbft_tpu.utils.jaxenv import enable_compile_cache

        enable_compile_cache()

    results = []
    for kind in args.engines.split(","):
        share = (kind in ("jax", "sharded", "sharded2d")) if args.share_engine == "auto" \
            else args.share_engine == "yes"
        # dedupe lives in the shared coalescer: without --share-engine there
        # is no cross-replica batch to deduplicate, so report it as off
        dedupe = share and (args.dedupe != "no")
        if args.dedupe == "yes" and not share:
            _log("bench: --dedupe yes ignored without a shared engine")
        res = asyncio.run(
            run_cluster(kind, args.nodes, args.requests, args.batch,
                        pad_sizes, scheme_name=args.scheme,
                        share_engine=share, dedupe=dedupe,
                        pipeline=args.pipeline,
                        burst_decisions=args.burst_decisions)
        )
        _log(f"bench[{kind}]: {res}")
        print(json.dumps(res), flush=True)
        results.append(res)

    if len(results) >= 2:
        base, dev = results[0], results[-1]
        print(json.dumps({
            "metric": f"committed_tx_per_sec_n{args.nodes}",
            "value": dev["tx_per_sec"],
            "unit": "tx/s",
            "vs_baseline": round(dev["tx_per_sec"] / base["tx_per_sec"], 3)
            if base["tx_per_sec"] else 0.0,
        }), flush=True)


if __name__ == "__main__":
    main()
