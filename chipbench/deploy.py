"""From a configuration file to a running deployment.

A configuration (``configs/<name>.json``) states what a deployment fixes:
replicas, scheme, protocol ``Configuration`` fields, the embedder's
coalescer arguments, the scheduler tick, the engine and the kernel every
launch must be served by.  It does NOT pin the pad ladder: that is the
program's tuning (``benchmarks.throughput.auto_pad_sizes`` today), read
from the program so a later PR that improves it can show it.

The deployment is reached through the program's normal entry points:
``ShardedCluster(shards=1, crypto=<scheme>, engine=..., window=...,
config_fn=...)`` — the routed front door, the delivery mux's gapless /
exactly-once checks, and ``poll()``.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """-> (BENCHMARK.json, the cell's entry, its configuration file, its
    workload file), all found by name."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no cell {name!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    workload = load_json(os.path.join(HERE, "workloads", f"{name}.json"))
    if workload.get("config", cell["config"]) != cell["config"]:
        raise SystemExit(
            f"chipbench: workloads/{name}.json is written for "
            f"{workload['config']!r}, BENCHMARK.json runs it on "
            f"{cell['config']!r}")
    return bench, cell, config, workload


def get_scheme(name: str):
    if name != "p256":
        raise SystemExit(f"chipbench: no plain reference for scheme {name!r}")
    from smartbft_tpu.crypto import p256

    return p256


def pad_ladder(config: dict) -> tuple:
    """The program's pad ladder for this deployment."""
    from benchmarks.throughput import auto_pad_sizes

    return tuple(auto_pad_sizes(config["replicas"], config["scheme"],
                                config["pipeline_depth"]))


def ring_keys(config: dict) -> list:
    """``(private, public)`` of every replica, exactly as ShardedCluster
    derives them for shard 0 — the keys the comb registry has to hold
    BEFORE the ladder is prewarmed (one key outside the ring grows the
    registry from 64 slots to 128 and recompiles every rung)."""
    from smartbft_tpu.crypto.provider import Keyring

    ids = list(range(1, config["replicas"] + 1))
    rings = Keyring.generate(ids, seed=b"shard-0",
                             scheme=get_scheme(config["scheme"]))
    return [(rings[i].private_key, rings[i].public_keys[i]) for i in ids]


def build_engine(config: dict):
    """-> (engine, pad ladder or ())."""
    scheme = get_scheme(config["scheme"])
    kind = config["engine"]
    if kind == "jax":
        from smartbft_tpu.crypto.provider import JaxVerifyEngine

        ladder = pad_ladder(config)
        return JaxVerifyEngine(pad_sizes=ladder, scheme=scheme), ladder
    if kind == "openssl":  # rehearsals on the CPU only: no device involved
        from smartbft_tpu.crypto.openssl_engine import OpenSSLVerifyEngine

        return OpenSSLVerifyEngine(scheme=scheme), ()
    raise SystemExit(f"chipbench: unknown engine {kind!r}")


def build_cluster(config: dict, engine, wal_root: str):
    from smartbft_tpu.config import Configuration
    from smartbft_tpu.testing.sharded import ShardedCluster

    fields = dict(config["configuration"])
    if fields.get("pipeline_depth", 1) != config["pipeline_depth"]:
        raise SystemExit("chipbench: pipeline_depth differs between the "
                         "configuration file's two places")

    def config_fn(_shard: int, node: int):
        return dataclasses.replace(Configuration(self_id=node), **fields)

    cluster = ShardedCluster(
        wal_root, shards=1, n=config["replicas"],
        depth=config["pipeline_depth"], crypto=config["scheme"],
        engine=engine, window=config["coalescer"]["window_s"],
        config_fn=config_fn, journal=False,
    )
    want = config["coalescer"].get("max_batch")
    if want is not None and cluster.coalescer.max_batch != want:
        raise SystemExit(
            f"chipbench: the coalescer's max_batch is "
            f"{cluster.coalescer.max_batch}, the configuration pins {want}")
    return cluster


async def settle(cluster, timeout: float = 60.0) -> bool:
    """Wait until every replica holds as many decisions as the most
    advanced one (followers deliver a little after the leader)."""
    apps = cluster.shard_list[0].apps
    deadline = time.perf_counter() + timeout
    while True:
        heights = [a.height() for a in apps]
        if min(heights) == max(heights):
            return True
        if time.perf_counter() > deadline:
            return False
        await asyncio.sleep(0.02)


def ledgers(cluster) -> dict:
    """Replica id -> its committed request keys (``"client:request"``) in
    ledger order, read from each replica's own ledger."""
    memo: dict = {}
    out = {}
    for app in cluster.shard_list[0].apps:
        keys = []
        for d in app.ledger():
            payload = d.proposal.payload
            got = memo.get(payload)
            if got is None:
                got = memo[payload] = [
                    str(info) for info in app.requests_from_proposal(
                        d.proposal)]
            keys.extend(got)
        out[app.id] = keys
    return out


def filesystem_of(path: str) -> str:
    """The filesystem type a path is on (from /proc/mounts), for the
    report line about where the WALs were."""
    best, kind = "", "?"
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _dev, mount, fstype = line.split()[:3]
                if path.startswith(mount) and len(mount) > len(best):
                    best, kind = mount, fstype
    except OSError:
        pass
    return kind
