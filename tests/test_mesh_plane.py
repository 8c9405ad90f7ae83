"""Mesh-sharded verify plane (ISSUE 10): one coalesced wave, N devices.

Tier-1 virtual-mesh gates — the conftest provisions 8 virtual CPU
devices (the MULTICHIP harness's ``force_cpu(virtual_devices=8)``), so
the REAL mesh path runs in the CPU-only suite, no TPU required:

- engine: batch-axis partitioning (``NamedSharding(mesh, P('batch'))``),
  pad-to-device-multiple, per-device fill accounting, MeshUnavailable;
- bit-for-bit verdict parity: randomized mixed-tag waves (incl. pad
  slots and forged votes) through the mesh engine equal the
  single-device engine's verdicts exactly (P-256, the production curve);
- wiring: ``Configuration.verify_mesh_devices`` graduates the shared
  coalescer's engine at start (idempotent across colocated replicas and
  fault-injection wrappers), an unbuildable mesh DOWNGRADES loudly with
  a counted metric instead of dying, and the knob rides ConfigMirror;
- PR 3 semantics per MESH launch: deadline abandon, retry, breaker trip
  → host fallback → canary close back ONTO the mesh, metrics-asserted;
- chaos: ONE lost mesh device fails every launch (a mesh is one logical
  launch), so the breaker degrades ALL shards to host together and the
  canary recovers them together — the PR 5 breaker-coherence contract
  extended to the mesh.
"""

import asyncio
import dataclasses
import random
import time

import numpy as np
import pytest

from smartbft_tpu.config import ConfigError, Configuration
from smartbft_tpu.crypto import p256
from smartbft_tpu.crypto.provider import (
    KERNELS,
    AsyncBatchCoalescer,
    HostVerifyEngine,
    JaxVerifyEngine,
    Keyring,
    P256CryptoProvider,
)
from smartbft_tpu.metrics import InMemoryProvider, TPUCryptoMetrics
from smartbft_tpu.parallel import MeshUnavailable, MeshVerifyEngine
from smartbft_tpu.testing import toy_scheme
from smartbft_tpu.testing.app import wait_for
from smartbft_tpu.testing.engine_faults import FaultyEngine, always_valid_engine
from smartbft_tpu.testing.sharded import ShardedCluster, sharded_config


from tests.conftest import tight_verify_policy as tight_policy  # noqa: E402
# (shared with test_flush_gating / test_mesh_2d — one fault-policy
# default for every mesh-plane suite)


async def wait_until(cond, timeout: float = 10.0, step: float = 0.01) -> None:
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "condition not met in time"
        await asyncio.sleep(step)


def toy_items(n: int, *, key_seeds=(b"ta", b"tb"), forge_every: int = 4):
    """n toy-scheme items over several signers; every ``forge_every``-th
    signature corrupted.  Returns (items, expected verdicts)."""
    keys = [toy_scheme.keygen(s) for s in key_seeds]
    items, expect = [], []
    for i in range(n):
        sk, pub = keys[i % len(keys)]
        msg = b"toy-%d" % i
        sig = toy_scheme.sign_raw(sk, msg)
        ok = i % forge_every != forge_every - 1
        if not ok:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        items.append(toy_scheme.make_item(msg, sig, pub))
        expect.append(ok)
    return items, expect


# ------------------------------------------------------------- engine shape

def test_mesh_engine_pads_and_partitions_batch_axis():
    import jax

    eng = MeshVerifyEngine(devices=8, pad_sizes=(16,), scheme=p256)
    assert eng.devices == 8
    assert eng.mesh.axis_names == ("batch",)  # the ISSUE's P('batch') idiom
    assert all(s % 8 == 0 for s in eng.pad_sizes)
    placed = eng._place(np.zeros((64, 16), np.uint32))
    devices = {s.device for s in placed.addressable_shards}
    assert len(devices) == 8
    assert placed.addressable_shards[0].data.shape[0] == 8  # 64 / 8 devices


def test_mesh_engine_default_ladder_scales_capacity_with_devices():
    e2 = MeshVerifyEngine(devices=2, scheme=p256)
    e8 = MeshVerifyEngine(devices=8, scheme=p256)
    assert e8.pad_sizes[-1] == 4 * e2.pad_sizes[-1]  # fixed lanes PER device


def test_mesh_unavailable_raises_cleanly():
    with pytest.raises(MeshUnavailable, match="host has"):
        MeshVerifyEngine(devices=64, scheme=p256)


def test_launches_are_counted_per_kernel():
    """Every launch is counted under the kernel that served it, so a run
    can refuse a result its expected kernel did not produce: the XLA
    kernel on this backend (single-device and mesh), host code for the
    host engine, and nothing under the Pallas names."""
    items, expect = toy_items(5)
    single = JaxVerifyEngine(pad_sizes=(8,), scheme=toy_scheme)
    mesh = MeshVerifyEngine(devices=2, scheme=toy_scheme)
    host = HostVerifyEngine(scheme=toy_scheme)
    for eng in (single, mesh, host):
        assert eng.stats.launches_by_kernel == dict.fromkeys(KERNELS, 0)
        assert eng.verify(items) == expect
        assert eng.verify(items[:3]) == expect[:3]
    for eng, kernel in ((single, "xla"), (mesh, "xla"), (host, "host")):
        assert eng.stats.launches_by_kernel == {
            **dict.fromkeys(KERNELS, 0), kernel: 2}
        assert sum(eng.stats.launches_by_kernel.values()) == eng.stats.launches


# ------------------------------------------------------------ verdict parity

def test_mesh_verdicts_match_single_device_bitwise():
    """THE property gate: randomized mixed-tag waves — items from
    several signers (the shard analog) with forged votes mixed in, wave
    sizes that force pad slots and multi-chunk launches — verify to
    BIT-IDENTICAL verdict vectors on the 8-device mesh and the
    single-device engine, and both match ground truth."""
    rng = random.Random(0xE5)
    single = JaxVerifyEngine(pad_sizes=(16,), scheme=p256)
    mesh = MeshVerifyEngine(devices=8, pad_sizes=(16,), scheme=p256)
    # a small signed pool (pure-Python P-256 signing is slow on CI rigs);
    # waves sample it with replacement and flip bytes for forgeries
    keys = [p256.keygen(b"mesh-prop-%d" % t) for t in range(3)]
    pool = []
    for i in range(6):
        sk, pub = keys[i % 3]
        msg = b"prop-msg-%d" % i
        pool.append((msg, p256.sign_raw(sk, msg), pub))
    for _wave in range(3):
        count = rng.choice((5, 11, 21))  # never device multiples: pad slots
        items, expect = [], []
        for _ in range(count):
            msg, sig, pub = pool[rng.randrange(len(pool))]
            ok = rng.random() > 0.3
            if not ok:
                sig = bytes([sig[0] ^ 1]) + sig[1:]
            items.append(p256.make_item(msg, sig, pub))
            expect.append(ok)
        got_mesh = mesh.verify(items)
        got_single = single.verify(items)
        assert got_mesh == got_single == expect
    # per-launch mesh accounting rode along
    snap = mesh.mesh_snapshot()
    assert snap["devices"] == 8 and snap["launches"] >= 3
    assert snap["pad_slots"] > 0 and len(snap["device_fill_pct_last"]) == 8


def test_strided_placement_spreads_pad_slots_evenly():
    """ISSUE 11 satellite: items round-robin over devices, so per-device
    item counts differ by AT MOST ONE — round 13's pathology (6 devices
    at 100 %, 2 at 0 in one launch) cannot recur for any wave of >= D
    items — while verdict ORDER stays bit-identical."""
    eng = MeshVerifyEngine(devices=8, pad_sizes=(64,), scheme=toy_scheme)
    single = JaxVerifyEngine(pad_sizes=(64,), scheme=toy_scheme)
    for n in (8, 21, 37, 50):  # odd sizes: pad slots at every width
        items, expect = toy_items(n, forge_every=3)
        assert eng.verify(items) == single.verify(items) == expect
        fills = eng.stats.last_device_fill_pct
        assert len(fills) == 8
        per_dev = eng.pad_sizes[0] // 8
        counts = [round(f * per_dev / 100.0) for f in fills]
        assert sum(counts) == n
        # the satellite's pinned variance bound: round-robin placement
        # can never skew per-device counts by more than one item
        assert max(counts) - min(counts) <= 1, (n, counts)
        if n >= 8:
            assert min(counts) >= 1  # no zeroed device while others fill
    # a launch with items on every device counts as spanning
    assert eng.stats.launches_spanning_all_devices >= 3


def test_mesh_coalescer_slices_tagged_submitters_exactly():
    """Concurrent tagged submissions (two shards) share one mesh wave;
    each submitter gets exactly its own verdict slice back."""
    eng = MeshVerifyEngine(devices=8, pad_sizes=(64,), scheme=toy_scheme)
    co = AsyncBatchCoalescer(eng, window=0.01)
    items_a, expect_a = toy_items(7, key_seeds=(b"shard-a",))
    items_b, expect_b = toy_items(12, key_seeds=(b"shard-b",), forge_every=3)

    async def run():
        ra, rb = await asyncio.gather(
            co.submit(items_a, tag=0), co.submit(items_b, tag=1)
        )
        return ra, rb

    ra, rb = asyncio.run(run())
    assert ra == expect_a and rb == expect_b
    snap = co.shard_snapshot()
    assert snap["mixed_waves"] >= 1 and set(snap["per_tag"]) == {"0", "1"}
    assert eng.stats.launches == 1  # ONE logical launch carried both tags


# ---------------------------------------------------------------- wiring

def test_configure_verify_mesh_graduates_idempotently_and_downgrades():
    rings = Keyring.generate([1, 2], seed=b"mesh-wire")
    mem = InMemoryProvider()
    prov = P256CryptoProvider(rings[1], engine=JaxVerifyEngine(pad_sizes=(8,)))
    co = prov.coalescer
    prov.configure_verify_mesh(8, metrics=TPUCryptoMetrics(mem))
    assert isinstance(co.engine, MeshVerifyEngine)
    assert co.engine.devices == 8 and co.engine.pad_sizes == (8,)
    assert co.mesh_configured == 8
    assert isinstance(co.fallback_engine, HostVerifyEngine)
    assert mem.gauges["consensus.tpu.mesh_devices"] == 8.0
    graduated = co.engine
    prov.configure_verify_mesh(8)  # reconfig with the same width: no churn
    assert co.engine is graduated

    # unbuildable width: LOUD counted downgrade, the installed engine stays
    prov.configure_verify_mesh(999)
    assert co.engine is graduated
    assert co.mesh_downgrades == 1 and co.mesh_configured == 999
    assert mem.counters["consensus.tpu.count_mesh_downgrades"] == 1
    snap = co.mesh_snapshot()
    assert snap["configured_devices"] == 999 and snap["devices"] == 8
    assert snap["downgrades"] == 1


def test_configure_verify_mesh_respects_fault_wrapped_mesh():
    """A FaultyEngine-wrapped mesh still reads as graduated (devices is
    delegated), so the knob wiring never strips fault injection."""
    wrapped = FaultyEngine(
        MeshVerifyEngine(devices=8, pad_sizes=(16,), scheme=p256)
    )
    rings = Keyring.generate([1, 2], seed=b"mesh-wrap")
    prov = P256CryptoProvider(
        rings[1], coalescer=AsyncBatchCoalescer(wrapped, window=0.001)
    )
    prov.configure_verify_mesh(8)
    assert prov.coalescer.engine is wrapped

    # a fault wrapper around a SINGLE-device engine graduates INSIDE the
    # wrapper: chaos injection stays connected to the live plane
    single_wrapped = FaultyEngine(JaxVerifyEngine(pad_sizes=(8,)))
    prov2 = P256CryptoProvider(
        rings[2],
        coalescer=AsyncBatchCoalescer(single_wrapped, window=0.001),
    )
    prov2.configure_verify_mesh(8)
    assert prov2.coalescer.engine is single_wrapped
    assert isinstance(single_wrapped.inner, MeshVerifyEngine)
    assert single_wrapped.devices == 8
    assert single_wrapped.pad_sizes == single_wrapped.inner.pad_sizes


def test_mesh_snapshot_on_single_device_plane_reports_disabled():
    co = AsyncBatchCoalescer(always_valid_engine(), window=0.001)
    snap = co.mesh_snapshot()
    assert snap["enabled"] is False and snap["devices"] == 1
    assert snap["downgrades"] == 0 and snap["configured_devices"] == 0


def test_verify_mesh_devices_config_validation_and_mirror():
    Configuration(self_id=1, verify_mesh_devices=8).validate()
    with pytest.raises(ConfigError, match="verify_mesh_devices"):
        Configuration(self_id=1, verify_mesh_devices=-1).validate()
    from smartbft_tpu.testing.reconfig import mirror_config, unmirror_config

    cfg = Configuration(self_id=3, verify_mesh_devices=4)
    assert unmirror_config(mirror_config(cfg)).verify_mesh_devices == 4


# -------------------------------------------- the live sharded mesh plane

def test_sharded_consensus_runs_live_on_the_mesh_via_config_knob(tmp_path):
    """S groups → one coalescer → N devices, LIVE: the Configuration
    knob (not a harness bypass) graduates the shared plane onto the
    8-device virtual mesh, both shards commit through it, and the
    ``mesh`` block lands in the stats roll-up."""

    def cfg(s, i):
        return dataclasses.replace(
            sharded_config(i, depth=4), verify_mesh_devices=8
        )

    async def run():
        c = ShardedCluster(tmp_path, shards=2, n=4, depth=4, crypto="toy",
                           config_fn=cfg)
        await c.start()
        try:
            eng = c.coalescer.engine
            assert isinstance(eng, MeshVerifyEngine) and eng.devices == 8
            for s in range(2):
                for j in range(6):
                    await c.submit(c.client_for_shard(s, j % 2), f"m{s}-{j}")
            await wait_for(
                lambda: all(sh.committed() >= 6 for sh in c.shard_list),
                c.scheduler, 90.0,
            )
            c.check_invariants()
            blk = c.stats_block()
            mesh = blk["aggregate"]["mesh"]
            assert mesh["enabled"] is True and mesh["devices"] == 8
            assert mesh["launches"] >= 1 and mesh["items"] >= 12
            assert mesh["configured_devices"] == 8 and mesh["downgrades"] == 0
            # both shards' quorum waves rode the ONE mesh plane
            tags = c.coalescer.shard_snapshot()["per_tag"]
            assert set(tags) == {"0", "1"}
        finally:
            await c.stop()

    asyncio.run(run())


def test_mesh_launch_fault_contract_deadline_retry_breaker_canary():
    """PR 3 semantics pinned per MESH launch: a hung mesh launch is
    abandoned at the deadline, retried, trips the breaker to the host
    fallback, and the canary closes back ONTO the mesh — all counted."""
    mesh = MeshVerifyEngine(devices=8, pad_sizes=(16,), scheme=toy_scheme)
    engine = FaultyEngine(mesh)
    items, expect = toy_items(5)
    # compile the mesh shape outside the coalescer, and give a HEALTHY
    # launch a deadline a loaded worker does not outrun: at 0.08 s the
    # first (compiling) launch was itself abandoned under six busy
    # workers, which opened the breaker before the fault was injected
    assert mesh.verify(items) == expect
    co = AsyncBatchCoalescer(
        engine, window=0.001, policy=tight_policy(launch_timeout=1.0),
        fallback_engine=HostVerifyEngine(scheme=toy_scheme),
    )

    async def run():
        assert await co.submit(items) == expect  # healthy mesh launch first
        before = mesh.stats.launches
        engine.hang()
        assert await asyncio.wait_for(co.submit(items), 30) == expect
        assert co.fault_stats.launch_timeouts >= 1      # deadline abandon
        assert co.fault_stats.breaker_opens == 1        # breaker trip
        assert co.fault_stats.host_fallback_batches == 1  # host fallback
        assert mesh.stats.launches == before  # the mesh never served it
        engine.heal()
        await wait_until(lambda: not co.breaker_open)
        assert co.fault_stats.breaker_closes == 1       # canary close
        assert co.fault_stats.probe_successes >= 1
        assert await co.submit(items) == expect
        assert mesh.stats.launches > before  # ...back ON the mesh

    try:
        asyncio.run(run())
    finally:
        engine.heal()


def test_one_lost_mesh_device_degrades_all_shards_then_recovers(tmp_path):
    """Extends the PR 5 breaker-coherence gate to the mesh: ONE lost
    device of the 8-device mesh fails every launch (a mesh launch spans
    all devices), so the breaker opens ONCE for ALL shards, both commit
    through the outage on the host fallback, and the canary closes the
    breaker back onto the mesh for everyone — metrics-asserted."""

    def cfg(s, i):
        return dataclasses.replace(
            sharded_config(
                i, depth=4,
                # device outages stall verification for wall-clock spans
                # the logical clock races past — keep deposition machinery
                # quiet (same rationale as the PR 5 coherence test)
                request_forward_timeout=120.0,
                request_complain_timeout=240.0,
                request_auto_remove_timeout=480.0,
                leader_heartbeat_timeout=30.0,
                view_change_resend_interval=15.0,
                view_change_timeout=60.0,
                verify_launch_timeout=0.15, verify_launch_retries=2,
                verify_breaker_threshold=3, verify_probe_interval=0.05,
            ),
            verify_mesh_devices=8,  # idempotent over the wrapped mesh
        )

    async def run():
        engine = FaultyEngine(
            MeshVerifyEngine(devices=8, pad_sizes=(16,), scheme=toy_scheme)
        )
        c = ShardedCluster(tmp_path, shards=2, n=4, depth=4, crypto="toy",
                           engine=engine, config_fn=cfg, seed=37)
        await c.start()
        try:
            assert c.coalescer.engine is engine  # knob did not strip faults
            # healthy warm-up: both shards commit on the mesh
            for s in range(2):
                await c.submit(c.client_for_shard(s), f"warm-{s}a")
                await c.submit(c.client_for_shard(s, 1), f"warm-{s}b")
            await wait_for(
                lambda: all(sh.committed() >= 2 for sh in c.shard_list),
                c.scheduler, 60.0,
            )
            mesh_launches_healthy = engine.inner.stats.launches
            assert mesh_launches_healthy >= 1

            engine.lose_device(3)  # ONE device of the mesh goes away
            for s in range(2):
                for j in range(4):
                    await c.submit(c.client_for_shard(s, j % 2), f"o-{s}{j}")
            # every shard commits THROUGH the outage (breaker → host)
            await wait_for(
                lambda: all(sh.committed() >= 6 for sh in c.shard_list),
                c.scheduler, 120.0,
            )
            snap = c.coalescer.fault_snapshot()
            assert snap["opens"] >= 1, snap
            assert snap["host_fallback_batches"] >= 1, snap
            tags = c.coalescer.shard_snapshot()["per_tag"]
            assert set(tags) == {"0", "1"}  # one plane, one breaker, all shards

            engine.restore_device(3)
            deadline = time.monotonic() + 10.0
            while c.coalescer.breaker_open and time.monotonic() < deadline:
                await asyncio.sleep(0.02)
            assert not c.coalescer.breaker_open
            assert c.coalescer.fault_snapshot()["closes"] >= 1
            # post-recovery traffic lands on the MESH again
            for s in range(2):
                await c.submit(c.client_for_shard(s, 2), f"post-{s}")
            await wait_for(
                lambda: all(sh.committed() >= 7 for sh in c.shard_list),
                c.scheduler, 60.0,
            )
            assert engine.inner.stats.launches > mesh_launches_healthy
            c.check_invariants()
            counters = c.verify_metrics_provider.counters
            assert counters["consensus.tpu.count_breaker_open"] >= 1
            assert counters["consensus.tpu.count_breaker_close"] >= 1
        finally:
            await c.stop()

    asyncio.run(run())


def test_faulty_engine_mesh_device_faults_are_transient_class():
    eng = FaultyEngine(always_valid_engine())
    eng.lose_device(2)
    with pytest.raises(RuntimeError, match="UNAVAILABLE.*device"):
        eng.verify([("a",)])
    eng.restore_device(2)
    assert eng.verify([("a",)]) == [True]
    eng.lose_device(1)
    eng.heal()  # heal clears device faults too
    assert eng.verify([("a",)]) == [True]


# --------------------------------------------- compile-cache persistence

def test_compile_cache_rule(monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set the program sets no cache
    directory in code (jax reads the variable itself); unset, the cache
    is at ONE fixed path inside the checkout — the path is part of the
    cache key, so nothing of it may come from a temp name, pid or time."""
    import pathlib

    import jax

    from smartbft_tpu.utils import jaxenv

    repo = pathlib.Path(__file__).resolve().parent.parent
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    jaxenv.enable_compile_cache()
    assert seen == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    jaxenv.enable_compile_cache()
    jaxenv.enable_compile_cache()
    assert seen == [("jax_compilation_cache_dir", jaxenv.cache_dir())] * 2
    fixed = pathlib.Path(jaxenv.cache_dir())
    assert fixed.parent == repo / ".jax_cache"
    assert ".jax_cache/" in (repo / ".gitignore").read_text().splitlines()


def test_prewarm_verify_engine_compiles_every_rung():
    from smartbft_tpu.crypto.provider import prewarm_verify_engine
    from smartbft_tpu.testing import toy_scheme

    eng = MeshVerifyEngine(devices=8, pad_sizes=(16, 64),
                           scheme=toy_scheme)
    prewarm_verify_engine(eng)
    assert eng.stats.launches == 2            # one launch per rung
    assert eng.stats.slots_used == 16 + 64    # every shape compiled
    prewarm_verify_engine(always_valid_engine())  # no ladder: no-op


# ------------------------------------------- the comb kernel on the mesh
# (the kernel itself, in interpret mode on four devices:
# tests/test_crypto_comb.py; here what the engine does around it)

def _p256_votes(n, keys, forge=()):
    items, expect = [], []
    for i in range(n):
        sk, pub = keys[i % len(keys)]
        msg = b"ring-vote-%d" % i
        sig = p256.sign_raw(sk, msg)
        if i in forge:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        items.append(p256.make_item(msg, sig, pub))
        expect.append(i not in forge)
    return items, expect


@pytest.mark.parametrize("devices, given, comb, xla", [
    # mesh4-n16-p256: auto_pad_sizes(16, "p256", 16) on four chips
    (4, (8, 32, 128, 256, 512), (512,), (8, 32, 128, 256, 512)),
    (4, (8, 600, 2688), (512, 1024, 3072), (8, 600, 2688)),
    (8, (16,), (1024,), (16,)),
    (2, None, (256, 1024, 4096), (16, 128, 1024, 4096)),
])
def test_mesh_ladder_is_rounded_to_what_the_serving_kernel_can_launch(
        monkeypatch, devices, given, comb, xla):
    """Where the comb kernel serves, a device's share is whole 128-lane
    tiles and rungs that would be the same launch are one rung; on the XLA
    kernel a device multiple is enough.  The XLA ladder stays the one of
    keys outside a pinned ring."""
    monkeypatch.setenv("SMARTBFT_PALLAS", "1")  # what a TPU backend decides
    on = MeshVerifyEngine(devices=devices, pad_sizes=given, scheme=p256)
    assert on.pad_sizes == comb and on.request_pad_sizes == xla
    assert on.supports_pallas and on._comb.mesh is on.mesh
    assert on._pallas_kernel is None  # no arbitrary-key kernel on a mesh
    monkeypatch.delenv("SMARTBFT_PALLAS")
    off = MeshVerifyEngine(devices=devices, pad_sizes=given, scheme=p256)
    assert off.pad_sizes == off.request_pad_sizes == xla
    # a scheme whose comb kernel has no mesh wrapper stays on XLA
    monkeypatch.setenv("SMARTBFT_PALLAS", "1")
    from smartbft_tpu.crypto import ed25519

    ed = MeshVerifyEngine(devices=devices, pad_sizes=given, scheme=ed25519)
    assert ed._comb is None and ed.pad_sizes == xla and not ed._pallas_on


def test_pinned_ring_on_a_mesh_engine_splits_comb_and_xla(monkeypatch):
    """A mesh engine told a ring: ring keys ride the comb kernel (stubbed
    here: it answers "valid" from the mesh, with the sharding the real
    one answers with), keys outside it the sharded XLA kernel, recorded
    ``xla``, verdicts in submission order — and no TypeError from the
    generic flag the one-device engine passes down."""
    import jax

    monkeypatch.setenv("SMARTBFT_PALLAS", "1")
    ring_keys = [p256.keygen(b"mesh-ring-%d" % i) for i in range(3)]
    other_keys = [p256.keygen(b"mesh-client-%d" % i) for i in range(2)]
    eng = MeshVerifyEngine(devices=8, pad_sizes=(16,), scheme=p256)
    assert eng.pad_sizes == (1024,) and eng.request_pad_sizes == (16,)
    eng.pin_ring([pub for _, pub in ring_keys])
    seen = {}

    def comb_stub(lanes, gtab, qtab):
        seen["io"] = len(lanes.sharding.device_set)
        seen["tables"] = [len(t.sharding.device_set) for t in (gtab, qtab)]
        seen["shape"] = lanes.shape
        return jax.device_put(np.ones(lanes.shape[0], np.uint32),
                              eng._sharding)

    monkeypatch.setattr(eng._comb, "launch_on_mesh", comb_stub)
    votes, _ = _p256_votes(5, ring_keys)
    envelopes, want = _p256_votes(6, other_keys, forge=(1, 4))
    items = [votes[0], envelopes[0], envelopes[1], votes[1], votes[2],
             envelopes[2], envelopes[3], votes[3], envelopes[4], votes[4],
             envelopes[5]]
    got = eng.verify(items)
    assert [got[i] for i in (1, 2, 5, 6, 8, 10)] == want
    assert all(got[i] for i in (0, 3, 4, 7, 9))
    assert eng.stats.launches_by_kernel == {
        **dict.fromkeys(KERNELS, 0), "comb": 1, "xla": 1}
    assert eng.stats.used_by_kernel["comb"] == 5 \
        and eng.stats.lanes_by_kernel["comb"] == 1024
    assert eng.stats.used_by_kernel["xla"] == 6 \
        and eng.stats.lanes_by_kernel["xla"] == 16
    assert seen == {"io": 8, "tables": [8, 8], "shape": (1024, 100)}
    assert eng.stats.launches_below_width == 0
    # the clients' keys never reached the comb registry
    assert len(eng._comb.registry) == 3
    # all outside, all inside: one launch each, no split
    assert eng.verify(envelopes) == want
    assert eng.verify(votes) == [True] * 5
    assert eng.stats.launches_by_kernel["xla"] == 2 \
        and eng.stats.launches_by_kernel["comb"] == 2


def test_graduating_onto_the_mesh_keeps_the_pinned_ring():
    rings = Keyring.generate([1, 2, 3, 4], seed=b"mesh-keeps-ring")
    ring = list(rings[1].public_keys.values())
    prov = P256CryptoProvider(
        rings[1], engine=JaxVerifyEngine(pad_sizes=(8,), ring=ring))
    prov.configure_verify_mesh(4)
    eng = prov.coalescer.engine
    assert isinstance(eng, MeshVerifyEngine) and eng._ring == frozenset(ring)


def test_a_launch_laid_out_below_the_mesh_width_is_counted():
    import jax

    eng = MeshVerifyEngine(devices=4, pad_sizes=(8,), scheme=toy_scheme)
    items, expect = toy_items(6)
    assert eng.verify(items) == expect
    assert eng.stats.launches_below_width == 0
    eng._place = lambda a: jax.device_put(a, jax.devices()[0])
    assert eng.verify(items) == expect  # right verdicts, wrong layout
    assert eng.stats.launches_below_width == 1
    assert eng.stats.last_io_devices == (1, 1)
    assert eng.mesh_snapshot()["launches_below_width"] == 1


def test_mesh_launch_is_accounted_pack_place_device_and_by_device(tmp_path):
    """While a profiler session is on, a mesh launch leaves, on its
    thread: ``verify.pack``, ``verify.place`` and ``verify.device`` busy
    spans, back to back, and a ``verify.lanes`` mark with its kernel and
    the lanes used on each device; the account folds the mark into
    ``lanes`` AND into its ``mesh`` block."""
    import threading

    import jax

    from smartbft_tpu import obs
    from smartbft_tpu.obs import recorder as recmod

    eng = MeshVerifyEngine(devices=4, pad_sizes=(16,), scheme=toy_scheme)
    items, expect = toy_items(6)
    assert eng.verify(items) == expect  # compiled, and nothing recorded
    out = {}

    def launch():
        recmod.set_thread_launch(7)
        out["wide"] = eng.verify(items)
        out["lone"] = eng.verify(items[:1])

    jax.profiler.start_trace(str(tmp_path))
    try:
        obs.poll_profiler()
        th = threading.Thread(target=launch, name="smartbft-verify-launch")
        th.start()
        th.join()
        obs.poll_profiler()
    finally:
        jax.profiler.stop_trace()
    obs.poll_profiler()
    assert out == {"wide": expect, "lone": expect[:1]}
    acc = obs.last_summary()
    busy = acc["busy"]["smartbft-verify-launch"]
    assert busy["verify.pack"]["calls"] == busy["verify.place"]["calls"] \
        == busy["verify.device"]["calls"] == 2
    assert acc["counters"]["launches"] == 2
    assert acc["lanes"] == {"xla": {"launches": 2, "launched": 32,
                                    "used": 7}}
    assert acc["mesh"] == {
        "launches": 2, "spanning": 1, "used": 7, "launched": 32,
        "used_by_device": [3, 2, 1, 1], "launched_by_device": [8, 8, 8, 8]}
    spans = sorted((e for e in obs.PROCESS.events()
                    if e.kind in ("verify.pack", "verify.place",
                                  "verify.device")), key=lambda e: e.t)
    assert [e.kind for e in spans] == ["verify.pack", "verify.place",
                                       "verify.device"] * 2
    assert {e.launch for e in spans} == {7}


def test_n16_committee_commits_through_a_four_device_mesh(tmp_path):
    """`mesh4-n16-p256`'s shape on the CPU: 16 replicas (f = 5, quorum
    11), one coalescer, ``verify_mesh_devices=4`` through the
    Configuration knob; they commit and all 16 ledgers agree."""

    def cfg(_s, i):
        return dataclasses.replace(
            sharded_config(i, depth=4), verify_mesh_devices=4,
            verify_mesh_topology="1d")

    async def run():
        c = ShardedCluster(tmp_path, shards=1, n=16, depth=4, crypto="toy",
                           config_fn=cfg)
        await c.start()
        try:
            eng = c.coalescer.engine
            assert isinstance(eng, MeshVerifyEngine) and eng.devices == 4
            for j in range(8):
                await c.submit(c.client_for_shard(0, j % 2), f"n16-{j}")
            sh = c.shard_list[0]
            await wait_for(lambda: sh.committed() >= 8, c.scheduler, 120.0)
            await wait_for(
                lambda: len({a.height() for a in sh.apps}) == 1,
                c.scheduler, 60.0)
            c.check_invariants()
            ledgers = [[d.proposal.payload for d in a.ledger()]
                       for a in sh.apps]
            assert len(ledgers) == 16
            assert all(led == ledgers[0] for led in ledgers)
            mesh = c.coalescer.mesh_snapshot()
            assert mesh["devices"] == 4 and mesh["downgrades"] == 0
            assert mesh["launches"] >= 1
            assert mesh["launches_below_width"] == 0
            assert mesh["io_devices_last"] == [4, 4]
        finally:
            await c.stop()

    asyncio.run(run())
