"""Static-key comb-table kernel (crypto/pallas_comb.py): host tables,
digit decomposition, interpret-mode kernel equivalence, key registry, and
the engine integration.

The kernel replaces the same reference hot path as pallas_ecdsa
(/root/reference/internal/bft/view.go:537-541) with per-replica
precomputed Lim-Lee comb tables — keys are static per configuration in a
BFT deployment, so table building moves to registration time.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from smartbft_tpu.crypto import p256
from smartbft_tpu.crypto import pallas_comb as pc


def _items(n, nkeys=2, corrupt=()):
    keys = [p256.keygen(b"ct-%d" % i) for i in range(nkeys)]
    items, expect = [], []
    for i in range(n):
        d, pub = keys[i % nkeys]
        msg = b"m-%d" % i
        r, s = p256.sign(d, msg)
        ok = True
        if i in corrupt:
            r = (r + 1) % p256.N
            ok = False
        items.append((msg, r, s, pub))
        expect.append(ok)
    return items, expect


def test_comb_table_entries_match_scalar_mults():
    _, pub = p256.keygen(b"table-key")
    table = pc.build_table(pub)
    assert table.shape == (pc.ROWS, pc.TSIZE)
    for idx in (0, 1, 3, 0x80, 0xA5, 0xFF):
        lo, hi = table[:48, idx], table[48:, idx]
        limbs = (lo + hi * 256).astype(np.uint64)
        x = sum(int(v) << (16 * i) for i, v in enumerate(limbs[0:16]))
        y = sum(int(v) << (16 * i) for i, v in enumerate(limbs[16:32]))
        z = sum(int(v) << (16 * i) for i, v in enumerate(limbs[32:48]))
        # decode from Montgomery domain
        rinv = pow(pc.FP.R, -1, p256.P)
        x, y, z = (v * rinv % p256.P for v in (x, y, z))
        k = sum(1 << (pc.STRIDE * t) for t in range(pc.TEETH) if idx >> t & 1)
        want = p256.scalar_mult_int(k, pub)
        if want is None:
            assert z == 0
        else:
            assert z == 1 and (x, y) == want


def test_comb_digits_reconstruct_scalar():
    rng = np.random.default_rng(3)
    u_int = int(rng.integers(1, 1 << 62)) | (1 << 255)
    from smartbft_tpu.crypto.bignum import to_limbs

    u = jnp.asarray(to_limbs(u_int, 16)).reshape(16, 1)
    digs = pc._comb_digits(u, 1)
    assert len(digs) == pc.STRIDE
    got = 0
    for k, d in enumerate(digs):  # row k is column STRIDE-1-k
        c = pc.STRIDE - 1 - k
        v = int(np.asarray(d)[0])
        for t in range(pc.TEETH):
            if v >> t & 1:
                got |= 1 << (c + pc.STRIDE * t)
    assert got == u_int


def test_comb_kernel_interpret_all_cases():
    """ONE interpret-mode launch covering the whole rejection matrix —
    interpret execution costs ~1 min/launch, so all kernel-executing
    assertions share a single batch (valid votes, corrupted r, r = 0,
    s >= n, a wrong-key claim, and zero-padded lanes)."""
    items, expect = _items(8, nkeys=2, corrupt=(3, 5))
    items[1] = (items[1][0], 0, items[1][2], items[1][3])          # r = 0
    items[2] = (items[2][0], items[2][1], p256.N, items[2][3])     # s >= n
    expect[1] = expect[2] = False
    reg = pc.CombKeyRegistry()
    e8, r8, s8, kidx = pc.pack_items(items, reg)
    kidx[6] = 1 - kidx[6]  # signature of key A presented as key B's vote
    expect[6] = False
    # zero-padded lanes (what the engine's pad ladder produces) must fail
    z = np.zeros((4, 32), np.uint8)
    e8, r8, s8 = (np.concatenate([a, z]) for a in (e8, r8, s8))
    kidx = np.concatenate([kidx, np.zeros(4, np.int32)])
    expect += [False] * 4
    mask = pc.ecdsa_verify_comb(
        e8, r8, s8, kidx, pc.g_table(), reg.stacked(), tile=16, interpret=True
    )
    assert [bool(v) for v in np.asarray(mask)] == expect
    # cross-check against the integer reference (lane 6's wrong-key claim
    # exists only at the kernel level, so it is excluded)
    assert [p256.verify_item(it) for it in items[:6]] == expect[:6]


def test_pack_items_matches_verify_inputs():
    items, _ = _items(5, nkeys=1)
    reg = pc.CombKeyRegistry()
    e8, r8, s8, kidx = pc.pack_items(items, reg)
    e, r, s, _, _ = p256.verify_inputs(items)
    for a8, al in ((e8, e), (r8, r), (s8, s)):
        a32 = a8.astype(np.uint32)
        limbs = a32[:, 0::2] | (a32[:, 1::2] << 8)
        assert (limbs == al).all()
    assert (kidx == 0).all()


def test_registry_rejects_off_curve_and_enforces_cap():
    reg = pc.CombKeyRegistry(cap=2)
    _, pub1 = p256.keygen(b"a")
    _, pub2 = p256.keygen(b"b")
    _, pub3 = p256.keygen(b"c")
    assert reg.register(pub1) == 0
    assert reg.register(pub1) == 0  # idempotent
    assert reg.register(pub2) == 1
    with pytest.raises(ValueError, match="full"):
        reg.register(pub3)
    with pytest.raises(ValueError, match="curve"):
        pc.CombKeyRegistry().register((pub1[0], (pub1[1] + 1) % p256.P))
    # stack pads key count to a power of two
    assert reg.stacked().shape == (2 * pc.ROWS, pc.TSIZE)
    reg1 = pc.CombKeyRegistry()
    reg1.register(pub1)
    assert reg1.stacked().shape == (pc.ROWS, pc.TSIZE)


def test_engine_comb_path_and_fallback(monkeypatch):
    """The engine routes chunks through CombVerifier when enabled and falls
    back to the generic kernel for unregistrable keys.  Kernels are stubbed
    with the integer reference — the kernel itself is covered by
    test_comb_kernel_interpret_all_cases."""
    from smartbft_tpu.crypto.provider import JaxVerifyEngine

    monkeypatch.setenv("SMARTBFT_PALLAS", "1")
    eng = JaxVerifyEngine(pad_sizes=(8,), scheme=p256)
    assert eng._comb is not None
    calls = {"comb": 0, "generic": 0}

    def comb_stub(items, pad_to):
        calls["comb"] += 1
        for _, _, _, pub in items:
            eng._comb.registry.register(pub)  # raises like the real path
        return np.array([p256.verify_item(it) for it in items], np.uint32)

    monkeypatch.setattr(eng._comb, "verify", comb_stub)
    items, expect = _items(6, nkeys=2, corrupt=(2,))
    out = eng.verify(items)
    assert out == expect
    assert calls["comb"] == 1
    assert eng.stats.launches_by_kernel["comb"] == 1

    # registry full -> CombVerifier.verify returns None -> generic kernel,
    # and the launch is counted under ITS name
    eng2 = JaxVerifyEngine(pad_sizes=(8,), scheme=p256)
    eng2._comb.registry = pc.CombKeyRegistry(cap=0)

    def generic_stub(*arrays):
        calls["generic"] += 1
        e = np.asarray(arrays[0])
        mask = np.zeros(e.shape[0], np.uint32)
        mask[: len(items)] = [p256.verify_item(it) for it in items]
        return mask

    monkeypatch.setattr(eng2, "_pallas_kernel", generic_stub)
    out2 = eng2.verify(items)
    assert out2 == expect
    assert calls["generic"] == 1
    assert eng2.stats.launches_by_kernel["comb"] == 0
    assert eng2.stats.launches_by_kernel["pallas"] == 1


def test_concurrent_registration_binds_keys_consistently(monkeypatch):
    """Concurrent verify() calls racing first-use registration must not
    misbind pub -> table index (two threads both reading idx=len(tables)
    would bind different keys to one index — signatures would then verify
    against the WRONG replica's key, a quorum-safety hazard).  Engines
    overlap flushes via asyncio.to_thread, so this race is reachable in
    production; CombVerifier serializes registry access with a lock."""
    import threading

    v = pc.CombVerifier()
    monkeypatch.setattr(
        v, "_launch",
        lambda arrays, ok, kidx, gtab, qtab: np.ones(
            len(np.asarray(kidx)), np.uint32),
    )
    nkeys = 12
    keys = [p256.keygen(b"race-%d" % i) for i in range(nkeys)]
    items_per_key = []
    for d, pub in keys:
        r, s = p256.sign(d, b"race-msg")
        items_per_key.append([(b"race-msg", r, s, pub)])

    barrier = threading.Barrier(nkeys)
    errs = []

    def worker(items):
        try:
            barrier.wait(timeout=30)
            for _ in range(3):
                v.verify(items, pad_to=8)
        except Exception as e:  # pragma: no cover - failure reporting
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(it,))
               for it in items_per_key]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errs
    # Bijection: every key got a distinct index and exactly nkeys tables.
    reg = v.registry
    assert len(reg) == nkeys
    idxs = [reg.index_of(pub) for _, pub in keys]
    assert sorted(idxs) == list(range(nkeys))
    # Binding: each index's table is the table OF THAT KEY.
    for (_, pub), idx in zip(keys, idxs):
        assert np.array_equal(reg._tables[idx], pc.build_table(pub))


def test_registry_full_mid_drain_warns_and_continues(monkeypatch, caplog):
    """A CombRegistryFull raised while draining pending prewarm keys must
    neither escape verify() (the engine would misread it as a kernel
    failure) nor degrade the current chunk when its signers are all
    registered.  Scenario: shared long-lived engine — this provider's
    prewarm passed the cap check at construction, then OTHER providers'
    first-use registrations filled the registry before our first verify."""
    import logging

    v = pc.CombVerifier(cap=1)
    monkeypatch.setattr(
        v, "_launch",
        lambda arrays, ok, kidx, gtab, qtab: np.ones(
            len(np.asarray(kidx)), np.uint32),
    )
    d1, pub1 = p256.keygen(b"drain-1")
    _, pub2 = p256.keygen(b"drain-2")
    r, s = p256.sign(d1, b"m")
    assert v.verify([(b"m", r, s, pub1)], pad_to=8) is not None  # fills cap
    v._pending_prewarm.append(pub2)  # simulates the raced shared engine
    with caplog.at_level(logging.WARNING, logger="smartbft_tpu.crypto"):
        # all-registered chunk keeps the comb path despite the overflow
        assert v.verify([(b"m", r, s, pub1)], pad_to=8) is not None
    assert v._pending_prewarm == []  # unregistrable pendings are dropped
    assert any("registry full" in rec.message for rec in caplog.records)


def test_prewarm_overflow_queues_fitting_prefix(monkeypatch):
    """prewarm_keys past capacity still queues the keys that fit (their
    tables build up front, avoiding a mid-protocol build/retrace stall)
    and raises CombRegistryFull only for the overflow."""
    v = pc.CombVerifier(cap=2)
    keys = [p256.keygen(b"pw-%d" % i)[1] for i in range(3)]
    with pytest.raises(pc.CombRegistryFull, match="1 key"):
        v.prewarm_keys(keys)
    assert v._pending_prewarm == keys[:2]
    # idempotent for already-queued keys; overflow still reported
    with pytest.raises(pc.CombRegistryFull):
        v.prewarm_keys(keys)
    assert v._pending_prewarm == keys[:2]


def test_unregistrable_key_short_circuits_before_pack(monkeypatch, caplog):
    """When the registry is full, a chunk containing any unregistered key
    degrades to the generic kernel WITHOUT paying the per-item hash/pack,
    while all-registered chunks keep the comb path; the drain-time
    registry-full condition warns (once)."""
    import logging

    v = pc.CombVerifier(cap=1)
    monkeypatch.setattr(
        v, "_launch",
        lambda arrays, ok, kidx, gtab, qtab: np.ones(
            len(np.asarray(kidx)), np.uint32),
    )
    d1, pub1 = p256.keygen(b"sc-1")
    _, pub2 = p256.keygen(b"sc-2")
    r, s = p256.sign(d1, b"m")
    assert v.verify([(b"m", r, s, pub1)], pad_to=8) is not None  # fills cap

    packed = {"n": 0}
    real_pack = v._pack

    def counting_pack(items):
        packed["n"] += 1
        return real_pack(items)

    monkeypatch.setattr(v, "_pack", counting_pack)
    with caplog.at_level(logging.WARNING, logger="smartbft_tpu.crypto"):
        # mixed chunk with an unregistrable key: no pack, generic fallback
        assert v.verify([(b"m", r, s, pub1), (b"m", r, s, pub2)],
                        pad_to=8) is None
        assert packed["n"] == 0
        # all-registered chunk still rides the comb path
        assert v.verify([(b"m", r, s, pub1)], pad_to=8) is not None
        assert packed["n"] == 1
        # repeated overflow hits warn only once
        assert v.verify([(b"m", r, s, pub2)], pad_to=8) is None
    msgs = [rec.message for rec in caplog.records
            if "registry full at verify time" in rec.message]
    assert len(msgs) == 1


# ------------------------------------------------ the kernel across a mesh

MESH_DEVICES, MESH_TILE = 4, 16


def _mesh_wave():
    """12 lanes over 2 registered keys: valid votes and one forgery of each
    kind the benchmark's set-up wave holds (a bit of r, a bit of s, another
    message, another registered key)."""
    keys = [p256.keygen(b"ct-%d" % i) for i in range(2)]
    items, expect = [], []
    for i in range(12):
        sk, pub = keys[i % 2]
        msg = b"mesh-m-%d" % i
        sig = p256.sign_raw(sk, msg)
        how = {2: "r", 5: "s", 7: "msg", 8: "key"}.get(i)
        if how == "r":
            sig = bytes([sig[0] ^ 0x20]) + sig[1:]
        elif how == "s":
            sig = sig[:40] + bytes([sig[40] ^ 0x01]) + sig[41:]
        elif how == "msg":
            msg = msg[:-1] + bytes([msg[-1] ^ 0xFF])
        elif how == "key":
            pub = keys[(i + 1) % 2][1]
        items.append(p256.make_item(msg, sig, pub))
        expect.append(how is None)
    return keys, items, expect


@pytest.fixture(scope="module")
def mesh_launch():
    """ONE interpret-mode launch of the shard-mapped comb kernel on four
    virtual devices (its compile takes ~3 min here), through the engine
    that runs it in production; every test below reads what it left."""
    import functools

    from smartbft_tpu.parallel import MeshVerifyEngine

    keys, items, expect = _mesh_wave()
    mp = pytest.MonkeyPatch()
    try:
        # what a TPU backend decides by itself; and the kernel's own
        # interpret switch with a small tile, as the one-device test above
        mp.setenv("SMARTBFT_PALLAS", "1")
        mp.setattr(pc, "CombVerifier", functools.partial(
            pc.CombVerifier, tile=MESH_TILE, interpret=True))
        eng = MeshVerifyEngine(devices=MESH_DEVICES, pad_sizes=(8, 32, 64),
                               scheme=p256)
    finally:
        mp.undo()
    eng.prewarm_keys([pub for _, pub in keys])
    got = eng.verify(items)
    return eng, keys, items, expect, got


def test_mesh_comb_launch_equals_every_other_verifier(mesh_launch):
    """Lane for lane: the comb kernel on four devices == the comb kernel
    on one == the XLA kernel == OpenSSL == what was corrupted."""
    from smartbft_tpu.crypto.openssl_engine import OpenSSLVerifyEngine
    from smartbft_tpu.crypto.provider import JaxVerifyEngine

    eng, _keys, items, expect, got = mesh_launch
    assert got == expect
    reg = pc.CombKeyRegistry()
    e8, r8, s8, kidx = pc.pack_items(items, reg)
    one = pc.ecdsa_verify_comb(e8, r8, s8, kidx, pc.g_table(), reg.stacked(),
                               tile=MESH_TILE, interpret=True)
    assert [bool(v) for v in np.asarray(one)] == got
    assert JaxVerifyEngine(pad_sizes=(16,), scheme=p256).verify(items) == got
    assert OpenSSLVerifyEngine(scheme=p256).verify(items) == got
    # padded lanes: 12 of the rung's 64 were real, the rest verified False
    # on the device and were never read back
    assert eng.stats.slots_used == 64 and eng.stats.sigs_verified == 12


def test_mesh_comb_launch_is_counted_under_comb_and_spans_the_mesh(
        mesh_launch):
    from smartbft_tpu.crypto.provider import KERNELS

    eng = mesh_launch[0]
    s = eng.stats
    assert s.launches_by_kernel == {**dict.fromkeys(KERNELS, 0), "comb": 1}
    assert s.lanes_by_kernel["comb"] == 64 and s.used_by_kernel["comb"] == 12
    # inputs and output laid out over all four devices, none below width
    assert s.last_io_devices == (MESH_DEVICES, MESH_DEVICES)
    assert s.launches_below_width == 0
    assert eng.mesh_snapshot()["launches_below_width"] == 0
    # strided placement: 12 lanes over 4 devices, 3 each
    per_dev = 64 // MESH_DEVICES
    counts = [round(f * per_dev / 100.0) for f in s.last_device_fill_pct]
    assert counts == [3, 3, 3, 3] and s.launches_spanning_all_devices == 1
    assert ("comb", 64, eng._comb.registry.slots()) in eng._launched


def test_mesh_comb_tables_are_whole_on_every_device_and_follow_the_registry(
        mesh_launch):
    eng, keys = mesh_launch[0], mesh_launch[1]

    def whole_everywhere(tab):
        assert len(tab.sharding.device_set) == MESH_DEVICES
        assert tab.sharding.is_fully_replicated
        assert {s.data.shape for s in tab.addressable_shards} == {tab.shape}

    # what the launch ran on
    for tab in (eng._comb._dev_gtab, eng._comb._dev_qtab):
        whole_everywhere(tab)
    assert eng._comb._dev_qtab.shape == (2 * pc.ROWS, pc.TSIZE)
    # a verifier of its own on the same mesh (growing the engine's
    # registry would cost the tests after this one a second compile)
    comb = pc.CombVerifier(mesh=eng.mesh)
    for _, pub in keys:
        comb.registry.register(pub)
    qtab = comb._device_tables()[1]
    assert comb._device_tables()[1] is qtab  # placed once per version
    # a third key: the registry grows, the stack is placed anew, whole on
    # every device again
    _, pub3 = p256.keygen(b"ct-third")
    comb.registry.register(pub3)
    gtab, grown = comb._device_tables()
    assert grown is not qtab and grown.shape == (4 * pc.ROWS, pc.TSIZE)
    whole_everywhere(gtab)
    whole_everywhere(grown)
    assert np.array_equal(
        np.asarray(grown.addressable_shards[3].data, np.float32),
        comb.registry.stacked())


def test_mesh_launcher_needs_no_second_compile_for_a_second_wave(mesh_launch):
    """The same rung again (fewer lanes, other rows): the compiled launch
    is reused and the verdicts un-permute to submission order."""
    eng, _keys, items, expect, _got = mesh_launch
    keep = [0, 2, 3, 5, 11]
    n0 = eng._comb.launch_on_mesh._cache_size()
    assert eng.verify([items[i] for i in keep]) == [expect[i] for i in keep]
    assert eng._comb.launch_on_mesh._cache_size() == n0
    assert eng.stats.launches_by_kernel["comb"] == 2
    assert eng.stats.launches_spanning_all_devices == 2  # 5 lanes, 4 devices
