"""The n-replica cluster run behind ``chip_smoke.py``'s cluster phase.

``run_cluster`` spins an n-node cluster in one process (production
wall-clock mode), every commit vote a real signature, and commits a fixed
number of requests end to end: submit, batch, three protocol phases,
quorum signature verification, two fsync'd WAL appends per decision,
deliver.  It returns what the smoke holds the run to (launches by kernel,
breaker and mesh state, the ledgers); it measures nothing: speed is
``python3 -m chipbench.run``'s to state.

``auto_pad_sizes`` is re-exported from ``smartbft_tpu.crypto.ladder`` for
``chipbench/deployments/sharded.py``, which imports it from here inside a
cell's measured process: importing this module must stay free of side
effects (it pins no platform and reads no environment).
"""

from __future__ import annotations

import asyncio
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))



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


def build_engine(kind: str, pad_sizes, scheme):
    from smartbft_tpu.crypto.provider import HostVerifyEngine, JaxVerifyEngine

    if kind == "openssl":
        from smartbft_tpu.crypto import p256
        from smartbft_tpu.crypto.openssl_engine import OpenSSLVerifyEngine

        if scheme is not p256:
            raise ValueError("the openssl engine is p256-only")
        return OpenSSLVerifyEngine(scheme=scheme)
    if kind == "jax":
        return JaxVerifyEngine(pad_sizes=pad_sizes, scheme=scheme)
    if kind == "host":  # pure Python: what tier-1 drives run_cluster with
        return HostVerifyEngine(scheme=scheme)
    raise ValueError(f"unknown engine {kind}")


# lives in the library; re-exported for the callers that import it from
# here (chip_smoke.py, chipbench/deployments/sharded.py)
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
                      ledgers_out: dict | None = None) -> dict:
    """``ledgers_out``: when given, filled with node id -> the request ids
    that node committed, in ledger order (what a caller needs to hold the
    run to fork-freedom and exactly-once)."""
    import dataclasses

    from smartbft_tpu.crypto.provider import AsyncBatchCoalescer
    from smartbft_tpu.testing.app import App, SharedLedgers, fast_config
    from smartbft_tpu.testing.network import Network
    from smartbft_tpu.utils.clock import Scheduler, WallClockDriver

    scheme = get_scheme(scheme_name)
    provider_cls = get_provider_cls(scheme_name)

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
        one = build_engine(engine_kind, pad_sizes, scheme)
        engines = {i: one for i in node_ids}
        # wider fan-in window when a whole cluster shares one chip:
        # waiting ~20ms merges every replica's quorum check into ONE
        # launch (what committee-n64-p256 pins as its coalescer window)
        window = 0.02
        # pipelined mode: up to 2*`pipeline` decisions' quorum waves (base
        # window + launch shadow) coalesce into one flush — max_batch must
        # not force-flush a single wave
        coalescer = AsyncBatchCoalescer(one, window=window,
                                        max_batch=2 * pipeline * max(pad_sizes),
                                        dedupe=dedupe)
        coalescers = {i: coalescer for i in node_ids}
    else:
        engines = {i: build_engine(engine_kind, pad_sizes, scheme)
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
    # lands inside the run
    if engine_kind == "jax":
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
    # drop warm-up traffic from the reported stats
    from smartbft_tpu.crypto.provider import VerifyStats

    for eng in set(engines.values()):
        eng.stats = VerifyStats()

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

        t0 = time.perf_counter()
        for k in range(requests):
            await apps[0].submit("bench", f"req-{k}")

        target = requests
        deadline = time.perf_counter() + 600.0

        def committed(app) -> int:
            return sum(
                len(app.requests_from_proposal(d.proposal)) for d in app.ledger()
            )

        stats_eng = engines[node_ids[1]]  # follower / shared engine
        while time.perf_counter() < deadline:
            if all(committed(a) >= target for a in apps):
                break
            await asyncio.sleep(0.02)
        else:
            raise TimeoutError(f"cluster did not commit {target} requests in time")
        elapsed = time.perf_counter() - t0
        decisions = len(apps[0].ledger())
        if ledgers_out is not None:
            for a in apps:
                ledgers_out[a.id] = [
                    (info.client_id, info.request_id)
                    for d in a.ledger()
                    for info in a.requests_from_proposal(d.proposal)
                ]
        stats = stats_eng.stats
        # verify-plane fault accounting: breaker state + fallback counts,
        # so a degraded (host-fallback) run is never taken for a device
        # run.  Shared mode has one coalescer; in per-replica mode ANY
        # node degrading must show, so snapshots are aggregated (counters
        # summed, flags OR-ed) across all nodes.
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
        # mesh block: in per-replica mode the planes are homogeneous in
        # SHAPE (devices/enabled/downgrades) but the launch/fill counts
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
            "tx_per_sec": round(requests / elapsed, 1),
            "decisions": decisions,
            "batch_fill_pct": round(stats.batch_fill_pct, 1),
            "verify_us_per_sig": round(stats.us_per_sig, 1),
            "launches": stats.launches,
            "launches_by_kernel": dict(stats.launches_by_kernel),
            "launches_per_decision": round(stats.launches / decisions, 3)
            if decisions else 0.0,
            "sigs_verified": stats.sigs_verified,
            "elapsed_s": round(elapsed, 2),
            "breaker": breaker_row,
            "mesh": mesh_row,
        }
    finally:
        for a in apps:
            try:
                await a.stop()
            except Exception:
                pass
        await driver.stop()
        shutil.rmtree(tmp, ignore_errors=True)
