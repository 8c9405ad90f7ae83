"""chip_smoke.py — the quickest proof that the commit path still runs on the chip.

One process, which is the only one that touches JAX.  It stamps the device
and exits non-zero at once unless ``jax.devices()[0].platform == "tpu"``;
it never pins the CPU and no kernel on this path runs in interpret mode.
Every phase raises on failure, so the exit code is non-zero and the result
line is never printed.

    python chip_smoke.py            # one chip: kernels + the n=64 cluster
    python chip_smoke.py --bls      # one chip: the BLS pairing kernel only
    python chip_smoke.py --chips 4  # four chips: the mesh path only

* kernels — comb P-256, generic Pallas P-256, XLA P-256 and Ed25519 comb,
  each on real signatures made from ``--seed`` with a known set of
  corrupted lanes; every mask is compared lane by lane with the host
  verifier (OpenSSL for P-256, the host implementation for Ed25519).
* cluster — BASELINE.json configs[2] through the normal entry points, via
  ``benchmarks/throughput.py:run_cluster``: 64 replicas (``Consensus`` started through ``App``), one shared
  ``JaxVerifyEngine`` + dedupe ``AsyncBatchCoalescer``, RequestBatch 500,
  pipeline depth 16, group-commit WALs on the native framing library,
  every commit vote a real P-256 signature.  4000 requests must land
  exactly once, in the same order, on all 64 ledgers, every launch served
  by the comb kernel, breaker closed, no host fallback, no mesh downgrade,
  no compile after the prewarm.  Then the same requests through the same
  cluster on per-replica OpenSSL engines — the plain reference — must
  commit the same set, fork-free.

The last line of stdout is ``{"ok": true, "device": {...}}`` with the
device as JAX reports it; the lines before it say what is worth knowing.
A cold run is 5-10 minutes, nearly all of it compiling.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import statistics
import time

def say(msg: str) -> None:
    print(msg, flush=True)


def stamp_device(min_chips: int) -> dict:
    """The device as JAX reports it — or exit: no TPU, no smoke."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu" or dev["count"] < min_chips:
        raise SystemExit(
            f"chip_smoke: needs {min_chips} TPU chip(s), JAX found {dev}")
    return dev


class CompileLog:
    """Every XLA backend compile of this process, from jax's own
    monitoring events: what compiled, for how long, and whether the
    persistent cache served it."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.events: list[tuple[str, float, bool]] = []
        self._hit = False
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._hit = True  # precedes its compile's duration event

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((kw.get("fun_name", "?"), secs, self._hit))
            self._hit = False

    def since(self, mark: int) -> str:
        """The compiles after ``mark`` (= an earlier ``len(log.events)``),
        worded for a report line; tiny ones (< 1 s) are only counted."""
        evs = self.events[mark:]
        big = [f"{name} {secs:.1f}s cache={'hit' if hit else 'miss'}"
               for name, secs, hit in evs if secs >= 1.0 or hit]
        return (", ".join(big) or "nothing over 1 s") + \
            f" ({len(evs)} compile(s) in all)"


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# signatures with a known set of corrupted lanes
# ---------------------------------------------------------------------------


def make_items(scheme, rng: random.Random, keys, n: int, votes: int = 1):
    """n real signatures round-robined over ``keys``, ``votes`` of them
    over each message, about one lane in eleven corrupted four different
    ways -> (items, expected verdicts)."""
    bad = set(rng.sample(range(n), max(4, n // 11)))
    items, expect = [], []
    for i in range(n):
        sk, pub = keys[i % len(keys)]
        if i % votes == 0:
            shared = rng.randbytes(48)
        msg = shared
        sig = scheme.sign_raw(sk, msg)
        if i in bad:
            how = i % 4
            if how == 0:    # a bit of the first half (r / R)
                sig = bytes([sig[0] ^ 0x20]) + sig[1:]
            elif how == 1:  # a bit of the second half (s / S)
                sig = sig[:40] + bytes([sig[40] ^ 0x01]) + sig[41:]
            elif how == 2:  # another message
                msg = msg[:-1] + bytes([msg[-1] ^ 0xFF])
            else:           # another signer's key
                pub = keys[(i + 1) % len(keys)][1]
        items.append(scheme.make_item(msg, sig, pub))
        expect.append(i not in bad)
    return items, expect


def must_match(name: str, got, ref, expect) -> None:
    """Lane by lane against the host verifier, which itself must flag
    exactly the lanes that were corrupted."""
    got, ref = [bool(v) for v in got], [bool(v) for v in ref]
    if ref != expect:
        raise AssertionError(f"{name}: the HOST verifier disagrees with the "
                             "known corrupted lanes")
    wrong = [i for i, (g, r) in enumerate(zip(got, ref)) if g != r]
    if wrong or len(got) != len(ref):
        raise AssertionError(
            f"{name}: {len(wrong)} lane(s) differ from the host verifier, "
            f"first {wrong[:8]} (got {len(got)} lanes of {len(ref)})")


def must_serve(name: str, by_kernel: dict, kernel: str) -> dict:
    """``by_kernel`` (a ``VerifyStats.launches_by_kernel``) must hold
    launches under ``kernel`` and no other -> its non-zero entries."""
    served = {k: v for k, v in by_kernel.items() if v}
    if set(served) != {kernel}:
        raise AssertionError(
            f"{name}: launches by kernel {served}, want all under {kernel!r}")
    return served


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_kernels(seed: int, log: CompileLog, lanes: int = 512) -> None:
    import jax
    import numpy as np

    from smartbft_tpu.crypto import ed25519, p256, pallas_ecdsa
    from smartbft_tpu.crypto.openssl_engine import OpenSSLVerifyEngine
    from smartbft_tpu.crypto.provider import JaxVerifyEngine

    rng = random.Random(seed)
    for scheme, name in ((p256, "p256"), (ed25519, "ed25519")):
        keys = [scheme.keygen(b"smoke-%d-%d" % (seed, i)) for i in range(64)]
        items, expect = make_items(scheme, rng, keys, lanes)
        if scheme is p256:
            ref = OpenSSLVerifyEngine(scheme=p256).verify(items)
            arrays = p256.verify_inputs(items)
            kernels = {
                "pallas": lambda: pallas_ecdsa.ecdsa_verify(*arrays),
                "xla": lambda: jax.jit(p256.verify_kernel)(*arrays),
            }
        else:
            ref = [ed25519.verify_item(it) for it in items]
            kernels = {}
        eng = JaxVerifyEngine(pad_sizes=(lanes,), scheme=scheme)
        eng.prewarm_keys(pub for _, pub in keys)
        kernels = {"comb": lambda: eng.verify(items), **kernels}
        for kernel, launch in kernels.items():
            mark = len(log.events)
            got, secs = timed(lambda: np.asarray(launch()))
            must_match(f"{name} {kernel}", got, ref, expect)
            say(f"kernels: {name} {kernel} kernel, {lanes} lanes, "
                f"{expect.count(False)} corrupted: mask == host verifier; "
                f"first call {secs:.1f}s; compiled: {log.since(mark)}")
        must_serve(f"{name} engine", eng.stats.launches_by_kernel, "comb")


def phase_bls(seed: int, log: CompileLog, lanes: int = 64,
              quorum: int = 5) -> None:
    """The BLS aggregate lane on the XLA pairing kernel, at the batch size
    where an earlier compiler corrupted the Miller loop's scan carry."""
    from smartbft_tpu.crypto import bls12381 as bls
    from smartbft_tpu.crypto.provider import JaxVerifyEngine

    rng = random.Random(seed)
    keys = [bls.keygen(b"smoke-bls-%d-%d" % (seed, i)) for i in range(quorum)]
    bad = set(rng.sample(range(lanes), max(4, lanes // 8)))
    agg, expect = [], []
    for i in range(lanes):
        msg = rng.randbytes(32)
        votes = [bls.make_item(msg, bls.sign_raw(sk, msg), pub)
                 for sk, pub in keys]
        if i in bad:  # one vote of the quorum signed something else
            sk, pub = keys[i % quorum]
            votes[i % quorum] = bls.make_item(
                msg, bls.sign_raw(sk, msg + b"!"), pub)
        agg.append(bls.aggregate_items(votes))
        expect.append(i not in bad)
    ref = [bls.verify_item(it) for it in agg]
    eng = JaxVerifyEngine(pad_sizes=(lanes,), scheme=bls)
    mark = len(log.events)
    got, secs = timed(lambda: eng.verify(agg))
    must_match("bls aggregate lane", got, ref, expect)
    must_serve("bls engine", eng.stats.launches_by_kernel, "xla")
    _, warm = timed(lambda: eng.verify(agg))
    say(f"bls: {lanes} aggregate lanes (quorum {quorum}), {len(bad)} "
        f"corrupted: mask == host pairing; first call {secs:.1f}s, warm "
        f"{1e3 * warm:.1f} ms; compiled: {log.since(mark)}")


def phase_cluster(log: CompileLog, n: int = 64, requests: int = 4000,
                  batch: int = 500, pipeline: int = 16,
                  waves=(64, 512, 2688)) -> None:
    import jax
    import jax.numpy as jnp

    from benchmarks.throughput import (auto_pad_sizes, bench_keyrings,
                                       run_cluster)
    from smartbft_tpu import native
    from smartbft_tpu.crypto import p256, pallas_comb
    from smartbft_tpu.crypto.provider import JaxVerifyEngine

    if not native.using_native():
        raise AssertionError("the native framing library is not in use")
    pad_sizes = auto_pad_sizes(n, "p256", pipeline)
    rings = bench_keyrings(n, p256)
    pubs = list(rings[1].public_keys.values())

    # prewarm the ladder on an engine of the cluster's shape (same key
    # count, same rungs): run_cluster's own engine then finds every kernel
    # compiled, and ANY compile during its run is one the ladder missed
    eng = JaxVerifyEngine(pad_sizes=pad_sizes, scheme=p256)
    eng.prewarm_keys(pubs)
    sk = rings[1].private_key
    item = p256.make_item(b"warm", p256.sign_raw(sk, b"warm"), pubs[0])
    for size in pad_sizes:
        mark = len(log.events)
        _, secs = timed(lambda: eng.verify([item] * size))
        say(f"cluster: prewarm comb rung {size} lanes x {len(pubs)} keys: "
            f"{secs:.1f}s; compiled: {log.since(mark)}")
    must_serve("prewarm", eng.stats.launches_by_kernel, "comb")

    # warm single-wave launches: through the engine (host pack + transfer
    # + kernel + readback), and the kernel alone on device-resident inputs
    sigs = [p256.make_item(
                b"wave-%d" % i,
                p256.sign_raw(rings[1 + i % n].private_key, b"wave-%d" % i),
                pubs[i % n])
            for i in range(max(waves))]
    reg = pallas_comb.CombKeyRegistry()
    for pub in pubs:
        reg.register(pub)
    gtab = jnp.asarray(pallas_comb.g_table(), jnp.bfloat16)
    qtab = jnp.asarray(reg.stacked(), jnp.bfloat16)
    for wave in waves:
        rung = min(s for s in pad_sizes if s >= wave)
        ms = [1e3 * timed(lambda: eng.verify(sigs[:wave]))[1]
              for _ in range(7)]
        packed = pallas_comb.pack_items(
            (sigs[:wave] + [sigs[0]] * rung)[:rung], reg)
        dev = [jax.device_put(a) for a in packed]
        # tile=128 spelled out like CombVerifier does: jit keys its cache
        # on how a static argument was passed, too
        ks = [1e3 * timed(lambda: pallas_comb.ecdsa_verify_comb(
            *dev, gtab, qtab, tile=128).block_until_ready())[1]
            for _ in range(7)]
        say(f"cluster: warm launch, wave of {wave} signatures -> rung "
            f"{rung}: engine.verify median {statistics.median(ms):.2f} ms; "
            f"kernel alone (device-resident, block_until_ready) median "
            f"{statistics.median(ks):.2f} ms")

    def run(engine_kind: str, **kw):
        ledgers: dict = {}
        row = asyncio.run(run_cluster(
            engine_kind, n, requests, batch, pad_sizes, scheme_name="p256",
            ledgers_out=ledgers, **kw))
        first = ledgers[1]
        if any(led != first for led in ledgers.values()) \
                or len(ledgers) != n:
            raise AssertionError(f"{engine_kind}: the {n} ledgers differ")
        want = {("bench", f"req-{k}") for k in range(requests)}
        if len(first) != requests or set(first) != want:
            raise AssertionError(
                f"{engine_kind}: ledger holds {len(first)} requests "
                f"({len(set(first))} distinct), want the {requests} "
                "submitted once each")
        return row, first

    mark = len(log.events)
    row, committed = run("jax", share_engine=True, dedupe=True,
                         pipeline=pipeline)
    served = must_serve("device run", row["launches_by_kernel"], "comb")
    br, mesh = row["breaker"], row["mesh"]
    say(f"cluster: device run: {requests} requests on {n} replicas, "
        f"{row['decisions']} decisions, {row['launches']} launches "
        f"{served}, fill {row['batch_fill_pct']}%, {row['sigs_verified']} "
        f"signatures, {row['elapsed_s']}s ({row['tx_per_sec']} tx/s, one "
        f"run, not a benchmark); breaker opens {br['opens']}, host "
        f"fallbacks {br['host_fallback_batches']}, launch failures "
        f"{br['launch_failures']}, mesh downgrades {mesh['downgrades']}; "
        f"compiles during the run: {len(log.events) - mark}; native "
        f"framing library in use: {native.using_native()}")
    if br["open"] or br["opens"] or br["degraded"] or br["launch_failures"] \
            or br["host_fallback_batches"] or mesh["downgrades"]:
        raise AssertionError(f"degraded device run: {br} {mesh}")
    if len(log.events) != mark:
        raise AssertionError(
            f"compiled during the cluster run: {log.events[mark:]}")

    # the plain reference: per-replica OpenSSL engines, no shared
    # coalescer, no dedupe, no pipelining
    ref_row, ref_committed = run("openssl", pipeline=1)
    if set(ref_committed) != set(committed):
        raise AssertionError("the OpenSSL reference committed another set")
    say(f"cluster: OpenSSL reference: same {len(ref_committed)} requests "
        f"committed, fork-free, {ref_row['decisions']} decisions, "
        f"{ref_row['elapsed_s']}s")


def phase_mesh(seed: int, log: CompileLog, chips: int = 4,
               n: int = 16) -> None:
    """Both mesh engines, reached through Configuration.verify_mesh_devices
    at Consensus.start: one wave bit for bit against the one-device
    engine, an n-replica cluster committing through the mesh, and inputs
    and outputs that really span ``chips`` distinct devices."""
    from benchmarks.mesh import run_cluster_point
    from smartbft_tpu.crypto import p256
    from smartbft_tpu.crypto.openssl_engine import OpenSSLVerifyEngine
    from smartbft_tpu.crypto.provider import JaxVerifyEngine

    rng = random.Random(seed)
    keys = [p256.keygen(b"smoke-mesh-%d-%d" % (seed, i)) for i in range(n)]
    # 12 sequences' worth of commit votes, one from every replica, so the
    # 2D engine's (sequence x vote) block has lanes on every device
    items, expect = make_items(p256, rng, keys, 12 * n, votes=n)
    one = JaxVerifyEngine(pad_sizes=(256,), scheme=p256)
    base, secs = timed(lambda: one.verify(items))
    must_match("one-device engine", base,
               OpenSSLVerifyEngine(scheme=p256).verify(items), expect)
    say(f"mesh: one-device engine, {len(items)}-signature wave: mask == "
        f"OpenSSL, "
        f"launches {one.stats.launches_by_kernel}, first call {secs:.1f}s")

    for topology, engine_cls in (("1d", "MeshVerifyEngine"),
                                 ("2d", "QuorumMeshVerifyEngine")):
        args = argparse.Namespace(
            shards=1, nodes=n, crypto="p256", topology=topology,
            pipeline=4, batch=50, decisions=4, pace=0.0, window=0.02,
            per_device_lanes="8,64")
        seen = {}

        def one_wave(engine):
            mark = len(log.events)
            got, secs = timed(lambda: engine.verify(items))
            io = engine.stats.last_io_devices
            seen.update(cls=type(engine).__name__, got=got, io=io,
                        fill=list(engine.stats.last_device_fill_pct),
                        secs=secs, compiled=log.since(mark))

        mark = len(log.events)
        row = asyncio.run(run_cluster_point(chips, args, 0.0,
                                            on_engine=one_wave))
        mesh = row["mesh"]
        say(f"mesh[{topology}]: {seen['cls']} via verify_mesh_devices="
            f"{chips}: wave of {len(items)} bit-identical to the one-device "
            f"engine: {seen['got'] == base}, {1e3 * seen['secs']:.1f} ms "
            f"warm; inputs on {seen['io'][0]} "
            f"devices, output on {seen['io'][1]}; per-device fill "
            f"{seen['fill']}%; n={n} cluster committed {row['total']} "
            f"requests in {row['decisions']} decisions, {row['launches']} "
            f"mesh launches, last launch io {mesh['io_devices_last']}, "
            f"downgrades {mesh['downgrades']}; compiled: {log.since(mark)}")
        if seen["cls"] != engine_cls or seen["got"] != base:
            raise AssertionError(f"mesh[{topology}]: verdicts differ from "
                                 f"the one-device engine ({seen['cls']})")
        if tuple(seen["io"]) != (chips, chips) or min(seen["fill"]) <= 0 \
                or list(mesh["io_devices_last"]) != [chips, chips]:
            raise AssertionError(f"mesh[{topology}]: lanes do not span "
                                 f"{chips} devices: {seen} {mesh}")
        if mesh["downgrades"] or mesh["devices"] != chips \
                or not row["launches"] \
                or row["total"] != args.batch * args.decisions:
            raise AssertionError(f"mesh[{topology}]: degraded run: {row}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the keys, messages and corrupted lanes of "
                         "the kernel and mesh waves")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run ONLY the mesh path (and the one-device "
                         "engine it is compared with) on four chips")
    ap.add_argument("--bls", action="store_true",
                    help="run ONLY the BLS pairing kernel (its cold "
                         "compile takes minutes)")
    args = ap.parse_args()

    device = stamp_device(args.chips)
    import jax
    import jaxlib

    from smartbft_tpu.utils.jaxenv import enable_compile_cache

    enable_compile_cache()
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — a version string for a report line
        libtpu = "?"
    say(f"chip_smoke: device {device}; jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu {libtpu}; compile cache at "
        f"{jax.config.jax_compilation_cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')})")
    log = CompileLog()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(args.seed, log, chips=4)
    elif args.bls:
        phase_bls(args.seed, log)
    else:
        phase_kernels(args.seed, log)
        phase_cluster(log)
    hits = sum(1 for _, _, hit in log.events if hit)
    say(f"chip_smoke: all phases passed in {time.perf_counter() - t0:.0f}s; "
        f"{len(log.events)} compiles, {hits} served by the cache")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
