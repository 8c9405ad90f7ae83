"""The 2D seq×vote quorum mesh in the LIVE path (ISSUE 11 tentpole b).

``Configuration.verify_mesh_topology = "2d"`` graduates the shared
coalescer's engine onto :class:`QuorumMeshVerifyEngine` through the SAME
``verify_mesh_devices`` seam as the 1D batch mesh — per-sequence quorum
counts ``psum`` across the 'vote' mesh axis (quorum counting rides the
collective, never the host) while per-item verdicts stay BIT-IDENTICAL
to the 1D engine.  Tier-1 pins:

- engine shape: devices-count construction, (seq, vote) mesh axes,
  MeshUnavailable on narrow hosts (a counted downgrade at the seam),
  MeshVerifyStats accounting, the ``topology`` marker;
- THE parity gate: randomized mixed-tag waves with forged votes, pad
  slots and duplicate votes verify bit-identically through the 2D
  engine, the 1D mesh engine, and the single-device engine — and the
  psum'd per-message counts equal the host tally of DISTINCT valid
  votes;
- wiring: topology knob validation + ConfigMirror round-trip,
  idempotent graduation, topology switching, graduation INSIDE a
  FaultyEngine wrapper, quorum derived from the keyring;
- the live sharded cluster: S=2 groups commit through the 2D mesh via
  Configuration alone, psum steps counted;
- the PR 3 deadline/retry/breaker/canary contract metrics-asserted per
  2D mesh launch.
"""

import asyncio
import dataclasses
import random
import time

import pytest

from smartbft_tpu.config import ConfigError, Configuration
from smartbft_tpu.crypto import p256
from smartbft_tpu.crypto.provider import (
    AsyncBatchCoalescer,
    HostVerifyEngine,
    JaxVerifyEngine,
    Keyring,
    MeshVerifyStats,
    P256CryptoProvider,
)
from smartbft_tpu.parallel import (
    MeshUnavailable,
    MeshVerifyEngine,
    QuorumMeshVerifyEngine,
)
from smartbft_tpu.testing import toy_scheme
from smartbft_tpu.testing.app import wait_for
from smartbft_tpu.testing.engine_faults import FaultyEngine
from smartbft_tpu.testing.sharded import ShardedCluster, sharded_config

from tests.conftest import tight_verify_policy as tight_policy


def toy_wave(rng, count, n_signers=3, forge_p=0.3, dup_p=0.2):
    """A randomized mixed wave: several signers, forged votes, and
    duplicate votes (the colocated-replica shape); returns (items,
    expected verdicts)."""
    keys = [toy_scheme.keygen(b"w2d-%d" % t) for t in range(n_signers)]
    items, expect = [], []
    for i in range(count):
        if items and rng.random() < dup_p:
            j = rng.randrange(len(items))
            items.append(items[j])
            expect.append(expect[j])
            continue
        sk, pub = keys[i % n_signers]
        msg = b"w2d-msg-%d" % rng.randrange(count)
        sig = toy_scheme.sign_raw(sk, msg)
        ok = rng.random() > forge_p
        if not ok:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        items.append(toy_scheme.make_item(msg, sig, pub))
        expect.append(ok)
    return items, expect


# --------------------------------------------------------------- engine shape

def test_quorum_mesh_engine_shape_and_accounting():
    eng = QuorumMeshVerifyEngine(devices=8, scheme=toy_scheme, quorum=3)
    assert eng.devices == 8 and eng.topology == "2d"
    assert tuple(eng.mesh.axis_names) == ("seq", "vote")
    assert eng.mesh.devices.shape == (4, 2)  # vote axis 2-wide on even D
    assert isinstance(eng.stats, MeshVerifyStats)
    assert eng.pad_sizes == (eng.seq_tile * eng.vote_tile,)
    items, expect = toy_wave(random.Random(1), 10)
    assert eng.verify(items) == expect
    snap = eng.mesh_snapshot()
    assert snap["topology"] == "2d" and snap["psum_steps"] >= 1
    assert snap["devices"] == 8 and snap["launches"] == 1
    # per-device fill is the EXACT tile-mapped item distribution, not
    # the contiguous 1D model: the reported real-lane counts sum to the
    # wave size (honest-fill contract of the mesh block)
    per_dev = (eng.seq_tile * eng.vote_tile) // eng.devices
    counts = [round(f * per_dev / 100.0)
              for f in eng.stats.last_device_fill_pct]
    assert len(counts) == 8 and sum(counts) == len(items)


def test_quorum_mesh_unavailable_on_narrow_host():
    with pytest.raises(MeshUnavailable, match="host has"):
        QuorumMeshVerifyEngine(devices=64, scheme=toy_scheme)


def test_quorum_mesh_too_wide_downgrades_at_the_seam():
    """A 2D mesh wider than the host refuses at CONSTRUCTION, so the
    wiring seam downgrades loudly (and counts it) instead of dying at
    first verify — and a run can refuse the downgraded plane."""
    rings = Keyring.generate([1, 2], seed=b"nosm", scheme=toy_scheme)
    prov = toy_scheme.ToyCryptoProvider(rings[1])
    before = prov.coalescer.engine
    prov.configure_verify_mesh(64, topology="2d")
    assert prov.coalescer.engine is before
    assert prov.coalescer.mesh_downgrades == 1
    snap = prov.coalescer.mesh_snapshot()
    assert snap["downgrades"] == 1 and snap["configured_devices"] == 64
    assert snap["enabled"] is False


# ------------------------------------------------------------- THE parity gate

def test_2d_verdicts_bit_identical_to_1d_and_single_device():
    """THE acceptance gate: randomized mixed-tag waves — forged votes,
    pad slots, duplicate votes, counts off every tile boundary — verify
    to BIT-IDENTICAL verdict vectors on the 2D quorum mesh, the 1D
    batch mesh, and the single-device engine; the psum'd per-message
    counts equal the host tally of DISTINCT valid votes."""
    rng = random.Random(0x2D)
    single = JaxVerifyEngine(pad_sizes=(64,), scheme=toy_scheme)
    mesh_1d = MeshVerifyEngine(devices=8, pad_sizes=(64,),
                               scheme=toy_scheme)
    mesh_2d = QuorumMeshVerifyEngine(devices=8, scheme=toy_scheme, quorum=2)
    for _ in range(4):
        count = rng.choice((5, 17, 33, 50))  # off-tile: pad cells everywhere
        items, expect = toy_wave(rng, count)
        got_2d = mesh_2d.verify(items)
        assert got_2d == mesh_1d.verify(items) == single.verify(items) \
            == expect
        # psum counts tally DISTINCT valid votes per message
        tally: dict = {}
        seen: set = set()
        for it, ok in zip(items, got_2d):
            tally.setdefault(it[0], 0)
            if ok and it not in seen:
                tally[it[0]] += 1
            seen.add(it)
        assert mesh_2d.last_counts == tally
        assert mesh_2d.last_decided == {
            m: c >= 2 for m, c in tally.items()
        }


@pytest.mark.slow  # ~4 min cold XLA compile for the bignum kernel under
# shard_map (the PR 2 n=16-mesh-e2e precedent); the toy-scheme parity
# test above pins the identical psum path bit-for-bit in tier-1, and the
# 1D p256 property test (test_mesh_plane) pins the production curve
def test_2d_parity_p256_production_curve():
    """One real P-256 wave through a small-tile 2D mesh — the
    production curve's verdicts match the single-device engine bit for
    bit."""
    rng = random.Random(7)
    keys = [p256.keygen(b"p2d-%d" % t) for t in range(2)]
    pool = []
    for i in range(4):
        sk, pub = keys[i % 2]
        msg = b"p2d-msg-%d" % i
        pool.append((msg, p256.sign_raw(sk, msg), pub))
    items, expect = [], []
    for _ in range(11):
        msg, sig, pub = pool[rng.randrange(len(pool))]
        ok = rng.random() > 0.3
        if not ok:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        items.append(p256.make_item(msg, sig, pub))
        expect.append(ok)
    single = JaxVerifyEngine(pad_sizes=(8,), scheme=p256)
    mesh_2d = QuorumMeshVerifyEngine(devices=8, seq_tile=4, vote_tile=2,
                                     scheme=p256, quorum=3)
    assert mesh_2d.verify(items) == single.verify(items) == expect


def test_2d_coalescer_slices_tagged_submitters_exactly():
    eng = QuorumMeshVerifyEngine(devices=8, scheme=toy_scheme, quorum=2)
    co = AsyncBatchCoalescer(eng, window=0.01)
    rng = random.Random(3)
    items_a, expect_a = toy_wave(rng, 9)
    items_b, expect_b = toy_wave(rng, 14)

    async def run():
        return await asyncio.gather(
            co.submit(items_a, tag=0), co.submit(items_b, tag=1)
        )

    ra, rb = asyncio.run(run())
    assert ra == expect_a and rb == expect_b
    assert eng.stats.launches == 1  # one logical 2D launch carried both
    assert co.shard_snapshot()["mixed_waves"] == 1


# -------------------------------------------------------------------- wiring

def test_topology_knob_validation_and_mirror():
    Configuration(self_id=1, verify_mesh_topology="2d").validate()
    with pytest.raises(ConfigError, match="verify_mesh_topology"):
        Configuration(self_id=1, verify_mesh_topology="3d").validate()
    from smartbft_tpu.testing.reconfig import mirror_config, unmirror_config

    cfg = Configuration(self_id=3, verify_mesh_devices=8,
                        verify_mesh_topology="2d")
    assert unmirror_config(mirror_config(cfg)).verify_mesh_topology == "2d"


def test_configure_verify_mesh_2d_graduates_and_switches_topologies():
    rings = Keyring.generate([1, 2, 3, 4], seed=b"2dwire",
                             scheme=toy_scheme)
    prov = toy_scheme.ToyCryptoProvider(rings[1])
    co = prov.coalescer
    prov.configure_verify_mesh(8, topology="2d")
    eng = co.engine
    assert isinstance(eng, QuorumMeshVerifyEngine) and eng.devices == 8
    # quorum derived from the keyring: n=4, f=1 -> ceil((4+1+1)/2) = 3
    assert eng.quorum == 3
    prov.configure_verify_mesh(8, topology="2d")  # same width+topology
    assert co.engine is eng                       # -> no churn
    prov.configure_verify_mesh(8, topology="1d")  # topology switch swaps
    assert isinstance(co.engine, MeshVerifyEngine)
    assert co.engine.topology == "1d"
    # the 2d->1d rebuild derives the full per-device ladder — the 2D
    # engine's single tile-product rung must NOT be inherited as a cap
    from smartbft_tpu.parallel.engine import MESH_PER_DEVICE_LANES

    assert co.engine.pad_sizes == tuple(8 * l for l in MESH_PER_DEVICE_LANES)
    snap = co.mesh_snapshot()
    assert snap["topology"] == "1d" and snap["downgrades"] == 0


def test_configure_verify_mesh_2d_inside_fault_wrapper():
    """Graduating to the 2D engine inside a FaultyEngine wrapper keeps
    chaos injection connected and delegates the topology marker."""
    wrapped = FaultyEngine(JaxVerifyEngine(pad_sizes=(8,),
                                           scheme=toy_scheme))
    rings = Keyring.generate([1, 2], seed=b"2dwrap", scheme=toy_scheme)
    prov = toy_scheme.ToyCryptoProvider(
        rings[1], coalescer=AsyncBatchCoalescer(wrapped, window=0.001)
    )
    prov.configure_verify_mesh(8, topology="2d")
    assert prov.coalescer.engine is wrapped
    assert isinstance(wrapped.inner, QuorumMeshVerifyEngine)
    assert wrapped.devices == 8 and wrapped.topology == "2d"


# ------------------------------------------- the live sharded 2D mesh plane

def test_sharded_consensus_commits_through_2d_quorum_mesh(tmp_path):
    """S=2 groups -> one coalescer -> the 8-device seq×vote mesh, LIVE,
    selected by Configuration ALONE: both shards commit through the 2D
    engine, psum steps ran, and the ``mesh`` block says which topology
    served."""

    def cfg(s, i):
        return dataclasses.replace(
            sharded_config(i, depth=4),
            verify_mesh_devices=8,
            verify_mesh_topology="2d",
        )

    async def run():
        c = ShardedCluster(tmp_path, shards=2, n=4, depth=4, crypto="toy",
                           config_fn=cfg)
        await c.start()
        try:
            eng = c.coalescer.engine
            assert isinstance(eng, QuorumMeshVerifyEngine)
            assert eng.devices == 8 and eng.quorum == 3
            for s in range(2):
                for j in range(6):
                    await c.submit(c.client_for_shard(s, j % 2), f"q{s}-{j}")
            await wait_for(
                lambda: all(sh.committed() >= 6 for sh in c.shard_list),
                c.scheduler, 90.0,
            )
            c.check_invariants()
            assert eng.psum_steps >= 1  # quorum counting rode the psum
            blk = c.stats_block()
            mesh = blk["aggregate"]["mesh"]
            assert mesh["topology"] == "2d" and mesh["devices"] == 8
            assert mesh["enabled"] is True and mesh["launches"] >= 1
            tags = c.coalescer.shard_snapshot()["per_tag"]
            assert set(tags) == {"0", "1"}
        finally:
            await c.stop()

    asyncio.run(run())


def test_2d_mesh_launch_fault_contract_deadline_retry_breaker_canary():
    """The PR 3 contract metrics-asserted per 2D MESH launch: a hung 2D
    launch is deadline-abandoned, retried, trips the breaker to the
    host fallback, and the canary closes back ONTO the quorum mesh."""
    from smartbft_tpu.metrics import InMemoryProvider, TPUCryptoMetrics

    mem = InMemoryProvider()
    mesh = QuorumMeshVerifyEngine(devices=8, scheme=toy_scheme, quorum=2)
    engine = FaultyEngine(mesh)
    items, expect = toy_wave(random.Random(9), 7)
    # as tests/test_mesh_plane.py's twin: compile outside the coalescer,
    # and a healthy launch's deadline is one a loaded worker keeps
    assert mesh.verify(items) == expect
    co = AsyncBatchCoalescer(
        engine, window=0.001, policy=tight_policy(launch_timeout=1.0),
        fallback_engine=HostVerifyEngine(scheme=toy_scheme),
        metrics=TPUCryptoMetrics(mem),
    )

    async def wait_until(cond, timeout=10.0):
        deadline = time.monotonic() + timeout
        while not cond():
            assert time.monotonic() < deadline, "condition not met in time"
            await asyncio.sleep(0.01)

    async def run():
        assert await co.submit(items) == expect  # healthy 2D launch first
        before = mesh.stats.launches
        engine.hang()
        assert await asyncio.wait_for(co.submit(items), 30) == expect
        assert co.fault_stats.launch_timeouts >= 1      # deadline abandon
        assert co.fault_stats.breaker_opens == 1        # breaker trip
        assert co.fault_stats.host_fallback_batches == 1
        assert mesh.stats.launches == before  # the mesh never served it
        engine.heal()
        await wait_until(lambda: not co.breaker_open)
        assert co.fault_stats.breaker_closes == 1       # canary close
        assert await co.submit(items) == expect
        assert mesh.stats.launches > before   # ...back ON the 2D mesh

    try:
        asyncio.run(run())
    finally:
        engine.heal()
    assert mem.counters["consensus.tpu.count_breaker_open"] >= 1
    assert mem.counters["consensus.tpu.count_breaker_close"] >= 1
    assert mem.counters["consensus.tpu.count_launch_timeouts"] >= 1
