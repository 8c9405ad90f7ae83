"""Snapshot state transfer + log compaction (ISSUE 17).

Covers every layer of the tentpole without a live socket cluster where
possible (the full kill-rejoin-via-snapshot runs are slow-marked at the
bottom): the pure verification functions, the crash-safe SnapshotStore,
LedgerFile compaction/recovery, the ReplicaApp crash-point recovery
matrix and install path, the sync-poisoning guard (satellite 2), the
reshard snapshot handoff on the in-process App, and ConfigMirror
round-trip of the snapshot knobs."""

import asyncio
import dataclasses
import os
import shutil
from types import SimpleNamespace

import pytest

from smartbft_tpu.codec import decode, encode
from smartbft_tpu.core.pool import ReqAlreadyProcessedError
from smartbft_tpu.core.util import compute_quorum
from smartbft_tpu.messages import Proposal, Signature, ViewMetadata
from smartbft_tpu.net.framing import WireDecision
from smartbft_tpu.net.launch import LedgerFile, ReplicaApp
from smartbft_tpu.snapshot import (
    CHAIN_SEED,
    RECENT_IDS_CAP,
    AppState,
    SnapshotError,
    SnapshotStore,
    chain_update,
    encode_snapshot_blob,
    fold_ids,
    make_manifest,
    parse_snapshot_blob,
    plan_catchup,
    verify_anchor,
    verify_snapshot,
    verify_tail,
)
from smartbft_tpu.testing.app import (
    App,
    BatchPayload,
    SharedLedgers,
    wait_for,
)
from smartbft_tpu.testing.app import TestRequest as _Request  # noqa: N814 — pytest must not collect it
from smartbft_tpu.testing.network import Network
from smartbft_tpu.testing.reconfig import mirror_config, unmirror_config
from smartbft_tpu.types import Decision, RequestInfo
from smartbft_tpu.utils.clock import Scheduler

NODES = (1, 2, 3, 4)
QUORUM, _F = compute_quorum(len(NODES))
MEMBERS = frozenset(NODES)

# ---------------------------------------------------------------------------
# committed-history builder (real TestRequest/BatchPayload/ViewMetadata
# encoding, so requests_from_proposal and the digest folds see exactly
# what a live cluster's decisions look like)
# ---------------------------------------------------------------------------


def _sigs(signers=NODES):
    return [Signature(signer=i, value=b"sig-%d" % i, msg=b"") for i in signers]


def _decision(seq, n_reqs=1, signers=NODES):
    raws = [
        encode(_Request(client_id="cli", request_id=f"r-{seq}-{k}",
                        payload=b"p"))
        for k in range(n_reqs)
    ]
    md = ViewMetadata(view_id=1, latest_sequence=seq)
    prop = Proposal(header=b"", payload=encode(BatchPayload(requests=raws)),
                    metadata=encode(md), verification_sequence=0)
    ids = [f"cli:r-{seq}-{k}" for k in range(n_reqs)]
    return Decision(proposal=prop, signatures=tuple(_sigs(signers))), ids


class _History:
    """Decisions 1..depth plus the chain/ids digests at EVERY height."""

    def __init__(self, depth):
        self.decisions, self.ids = [], []
        self.chains = [CHAIN_SEED]
        self.ids_digests = [CHAIN_SEED]
        chain = idd = CHAIN_SEED
        for seq in range(1, depth + 1):
            d, ids = _decision(seq)
            self.decisions.append(d)
            self.ids.append(ids)
            chain = chain_update(chain, d.proposal.payload,
                                 d.proposal.metadata)
            idd = fold_ids(idd, ids)
            self.chains.append(chain)
            self.ids_digests.append(idd)

    def app_state(self, h):
        flat = [i for ids in self.ids[:h] for i in ids]
        return AppState(request_count=len(flat),
                        ids_digest=self.ids_digests[h],
                        recent_ids=flat[-RECENT_IDS_CAP:])

    def manifest(self, h):
        blob = encode(self.app_state(h))
        d = self.decisions[h - 1]
        return make_manifest(h, self.chains[h], blob, d.proposal,
                             list(d.signatures)), blob


# ---------------------------------------------------------------------------
# pure functions
# ---------------------------------------------------------------------------


def test_chain_digest_is_prefix_independent():
    """Seeding the chain at a snapshot horizon and folding the suffix
    lands on the SAME digest as replaying everything — the property that
    lets compaction delete the prefix without losing fork detection."""
    hist = _History(12)
    seeded = hist.chains[8]
    for d in hist.decisions[8:]:
        seeded = chain_update(seeded, d.proposal.payload, d.proposal.metadata)
    assert seeded == hist.chains[12]
    idd = hist.ids_digests[8]
    for ids in hist.ids[8:]:
        idd = fold_ids(idd, ids)
    assert idd == hist.ids_digests[12]
    # order sensitivity: any reordering changes the digest
    assert fold_ids(CHAIN_SEED, ["a:1", "b:2"]) != \
        fold_ids(CHAIN_SEED, ["b:2", "a:1"])


def test_verify_snapshot_accepts_clean_and_names_each_failure():
    hist = _History(8)
    manifest, blob = hist.manifest(8)
    assert verify_snapshot(manifest, blob, QUORUM, MEMBERS) is None
    # tampered state blob (bit-FLIP the last byte: the AppState tail is
    # empty-list zero bytes since the kv fields landed, so writing a
    # constant could be a no-op)
    assert "digest mismatch" in verify_snapshot(
        manifest, blob[:-1] + bytes([blob[-1] ^ 0xFF]), QUORUM, MEMBERS)
    # truncated state blob (size check fires first)
    assert "size mismatch" in verify_snapshot(
        manifest, blob[:-1], QUORUM, MEMBERS)
    # thin certificate: 2 signers < quorum 3
    thin_d, _ = _decision(8, signers=(1, 2))
    thin = make_manifest(8, hist.chains[8], blob, thin_d.proposal,
                         list(thin_d.signatures))
    assert "quorum" in verify_snapshot(thin, blob, QUORUM, MEMBERS)
    # signer outside the membership
    alien_d, _ = _decision(8, signers=(1, 2, 9))
    alien = make_manifest(8, hist.chains[8], blob, alien_d.proposal,
                          list(alien_d.signatures))
    assert "unknown" in verify_snapshot(alien, blob, QUORUM, MEMBERS)
    # anchor at the wrong sequence
    off_d, _ = _decision(7)
    off = make_manifest(8, hist.chains[8], blob, off_d.proposal,
                        list(off_d.signatures))
    assert "sequence" in verify_snapshot(off, blob, QUORUM, MEMBERS)
    # anchor with no / undecodable metadata
    bare = make_manifest(8, hist.chains[8], blob, Proposal(), [])
    assert "no metadata" in verify_anchor(bare, QUORUM, MEMBERS)
    junk = make_manifest(8, hist.chains[8], blob,
                         Proposal(metadata=b"\xff\xff\xff"), [])
    assert "undecodable" in verify_anchor(junk, QUORUM, MEMBERS)
    # non-positive height is never installable
    zero = dataclasses.replace(manifest, height=0)
    assert "non-positive" in verify_snapshot(zero, blob, QUORUM, MEMBERS)


def test_verify_tail_continuity_and_certificates():
    hist = _History(6)
    wire = [WireDecision(proposal=d.proposal, signatures=list(d.signatures))
            for d in hist.decisions]
    assert verify_tail(wire, 0) is None
    assert verify_tail(wire, 0, quorum=QUORUM, members=MEMBERS) is None
    assert verify_tail(wire[2:], 2, quorum=QUORUM, members=MEMBERS) is None
    # gap: tail starting past our height
    assert "sequence" in verify_tail(wire[3:], 1)
    # certificate phase: thin and alien signers are named failures
    thin_d, _ = _decision(1, signers=(1, 2))
    thin = [WireDecision(proposal=thin_d.proposal,
                         signatures=list(thin_d.signatures))]
    assert verify_tail(thin, 0) is None  # continuity alone passes
    assert "quorum" in verify_tail(thin, 0, quorum=QUORUM, members=MEMBERS)
    alien_d, _ = _decision(1, signers=(1, 2, 9))
    alien = [WireDecision(proposal=alien_d.proposal,
                          signatures=list(alien_d.signatures))]
    assert "unknown" in verify_tail(alien, 0, quorum=QUORUM, members=MEMBERS)
    # metadata damage
    bare = [WireDecision(proposal=Proposal(), signatures=[])]
    assert "no metadata" in verify_tail(bare, 0)


def test_plan_catchup_branches():
    assert plan_catchup(10, 10, 0) == "none"
    assert plan_catchup(10, 8, 0) == "none"
    assert plan_catchup(5, 20, 0) == "tail"
    assert plan_catchup(5, 20, 5) == "tail"
    assert plan_catchup(5, 20, 16) == "snapshot"


def test_snapshot_blob_roundtrip_and_damage():
    hist = _History(4)
    manifest, blob = hist.manifest(4)
    data = encode_snapshot_blob(manifest, blob)
    parsed = parse_snapshot_blob(data)
    assert parsed is not None
    m2, s2 = parsed
    assert m2.height == 4 and m2.chain_digest == hist.chains[4] and s2 == blob
    assert parse_snapshot_blob(b"") is None
    assert parse_snapshot_blob(b"nonsense!" + data[9:]) is None
    assert parse_snapshot_blob(data[:len(data) // 2]) is None  # torn
    flipped = bytearray(data)
    flipped[-1] ^= 0xFF  # tampered state byte -> digest mismatch
    assert parse_snapshot_blob(bytes(flipped)) is None


# ---------------------------------------------------------------------------
# SnapshotStore crash safety
# ---------------------------------------------------------------------------


def test_snapshot_store_atomic_save_gc_and_torn_file_skip(tmp_path):
    hist = _History(16)
    store = SnapshotStore(str(tmp_path / "snaps"))
    m8, b8 = hist.manifest(8)
    path8 = store.save(m8, b8)
    got = store.latest()
    assert got is not None and got.manifest.height == 8 and got.state == b8
    assert store.disk_bytes() == os.path.getsize(path8)
    # newer snapshot wins; keep=1 prunes the old one AFTER durability
    m16, b16 = hist.manifest(16)
    # a crash mid-save leaves a stray temp file — save must sweep it
    stray = os.path.join(store.dir, "snapshot-cafe.snap.tmp")
    with open(stray, "wb") as fh:
        fh.write(b"half-written")
    path16 = store.save(m16, b16)
    assert store.latest().manifest.height == 16
    assert not os.path.exists(path8) and not os.path.exists(stray)
    # a torn newest file is SKIPPED (counted), never installed
    with open(path16, "r+b") as fh:
        fh.truncate(os.path.getsize(path16) // 2)
    assert store.latest() is None
    assert store.rejected_files >= 1
    # tampered bytes are equally rejected (bit-FLIP the last byte — the
    # AppState tail is empty-list zero bytes since the kv fields landed,
    # so writing a constant could be a no-op)
    store.save(m16, b16)
    with open(path16, "r+b") as fh:
        fh.seek(-1, os.SEEK_END)
        last = fh.read(1)
        fh.seek(-1, os.SEEK_END)
        fh.write(bytes([last[0] ^ 0xFF]))
    assert store.latest() is None
    # refusing to WRITE an inconsistent snapshot in the first place
    with pytest.raises(SnapshotError):
        store.save(m8, b8 + b"extra")


def test_snapshot_store_crash_between_save_and_gc_picks_newer(tmp_path):
    """Both files on disk (killed before gc): latest() picks the newer;
    when the newer is corrupt, it falls back to the older good one."""
    hist = _History(16)
    store = SnapshotStore(str(tmp_path / "snaps"))
    m8, b8 = hist.manifest(8)
    store.save(m8, b8)
    # simulate the crash: a second durable file gc never saw
    m16, b16 = hist.manifest(16)
    newer = os.path.join(store.dir, "snapshot-%016x.snap" % 16)
    with open(newer, "wb") as fh:
        fh.write(encode_snapshot_blob(m16, b16))
    assert store.latest().manifest.height == 16
    with open(newer, "r+b") as fh:
        fh.truncate(10)
    assert store.latest().manifest.height == 8


# ---------------------------------------------------------------------------
# LedgerFile compaction + recovery
# ---------------------------------------------------------------------------


def _write_ledger(path, decisions):
    lf = LedgerFile(path)
    lf.open_append()
    for d in decisions:
        lf.append(d)
    lf.close()
    return lf


def test_ledger_compact_preserves_chain_bit_identically(tmp_path):
    hist = _History(12)
    path = str(tmp_path / "ledger.bin")
    lf = _write_ledger(path, hist.decisions)
    lf.open_append()
    anchor = encode(WireDecision(proposal=hist.decisions[7].proposal,
                                 signatures=list(hist.decisions[7].signatures)))
    state = encode(hist.app_state(8))
    lf.compact(8, hist.chains[8], hist.decisions[8:], app_state=state,
               anchor=anchor)
    before = lf.disk_bytes()
    lf.close()
    # a fresh reader sees base ref + suffix, and the re-folded chain is
    # bit-identical to the full-replay digest
    lf2 = LedgerFile(path)
    suffix = lf2.read_all()
    assert lf2.base_height == 8 and lf2.base_digest == hist.chains[8]
    assert lf2.base_state == state and lf2.base_anchor == anchor
    assert len(suffix) == 4
    chain = lf2.base_digest
    for d in suffix:
        chain = chain_update(chain, d.proposal.payload, d.proposal.metadata)
    assert chain == hist.chains[12]
    # compaction actually shrank the file
    full_size = os.path.getsize(str(tmp_path / "ledger.bin"))
    assert before == full_size
    uncompacted = str(tmp_path / "full.bin")
    _write_ledger(uncompacted, hist.decisions)
    assert full_size < os.path.getsize(uncompacted)


def test_ledger_torn_tail_and_misplaced_base_ref(tmp_path):
    hist = _History(5)
    path = str(tmp_path / "ledger.bin")
    _write_ledger(path, hist.decisions)
    # SIGKILL mid-append: half a frame at the tail is dropped, the
    # complete prefix survives
    from smartbft_tpu.net.framing import encode_frame
    from smartbft_tpu.net.launch import _FT_LEDGER

    frame = encode_frame(_FT_LEDGER, encode(WireDecision(
        proposal=hist.decisions[0].proposal,
        signatures=list(hist.decisions[0].signatures))))
    with open(path, "ab") as fh:
        fh.write(frame[:len(frame) // 2])
    lf = LedgerFile(path)
    assert len(lf.read_all()) == 5
    assert lf.base_height == 0
    # a base ref anywhere but FIRST is corruption: replay stops there
    from smartbft_tpu.net.launch import _FT_LEDGER_BASE, LedgerBaseRef

    bad = str(tmp_path / "bad.bin")
    with open(bad, "wb") as fh:
        fh.write(frame)
        fh.write(encode_frame(_FT_LEDGER_BASE,
                              encode(LedgerBaseRef(height=3))))
        fh.write(frame)
    lf_bad = LedgerFile(bad)
    assert len(lf_bad.read_all()) == 1
    assert lf_bad.base_height == 0


# ---------------------------------------------------------------------------
# ReplicaApp: the crash-point recovery matrix + install (no sockets —
# SocketComm binds nothing until start(), so the replica is constructible
# and its disk recovery drivable entirely in-process)
# ---------------------------------------------------------------------------


def _spec(tmp_path, node_id=1):
    base = str(tmp_path)
    peers = {i: f"uds:{base}/n{i}.sock" for i in NODES if i != node_id}
    return {
        "node_id": node_id,
        "peers": peers,
        "listen": f"uds:{base}/n{node_id}.sock",
        "ledger_path": f"{base}/ledger-{node_id}.bin",
        "wal_dir": f"{base}/wal-{node_id}",
    }


def _recovered(spec):
    r = ReplicaApp(spec)
    r._recover_local_state()
    return r


def test_recovery_reconciles_snapshot_ahead_of_compaction(tmp_path):
    """Killed between the snapshot rename and the ledger compaction:
    snapshot at H=8 next to the FULL 12-decision ledger.  Recovery seeds
    from the snapshot and folds only the suffix past it — bit-identical
    to a control replica that replayed everything."""
    hist = _History(12)
    spec = _spec(tmp_path, node_id=1)
    _write_ledger(spec["ledger_path"], hist.decisions)
    store = SnapshotStore(spec["ledger_path"] + "-snapshots")
    manifest, blob = hist.manifest(8)
    store.save(manifest, blob)
    r = _recovered(spec)
    try:
        assert r.height() == 12
        assert r._base_height == 0  # the file was never compacted
        assert r._chain == hist.chains[12]
        assert r.ids_digest() == hist.ids_digests[12].hex()
        assert r.committed_requests() == 12
        # the snapshot is re-offered to peers after the restart
        assert r._last_snapshot_height == 8
        assert r._snap_offer is not None and r._snap_offer[0] == 8
    finally:
        r.ledger_file.close()
    # control: same ledger, NO snapshot — digests must agree exactly
    ctl_spec = _spec(tmp_path, node_id=2)
    _write_ledger(ctl_spec["ledger_path"], hist.decisions)
    ctl = _recovered(ctl_spec)
    try:
        assert ctl._chain == r._chain
        assert ctl.ids_digest() == r.ids_digest()
        assert ctl.committed_requests() == r.committed_requests()
    finally:
        ctl.ledger_file.close()


def _compacted_spec(tmp_path, hist, h, node_id=1):
    spec = _spec(tmp_path, node_id=node_id)
    lf = _write_ledger(spec["ledger_path"], hist.decisions)
    lf.open_append()
    anchor_d = hist.decisions[h - 1]
    lf.compact(h, hist.chains[h], hist.decisions[h:],
               app_state=encode(hist.app_state(h)),
               anchor=encode(WireDecision(proposal=anchor_d.proposal,
                                          signatures=list(anchor_d.signatures))))
    lf.close()
    return spec


def test_recovery_from_compacted_ledger_with_lost_snapshot_dir(tmp_path):
    """The prefix is GONE from disk and so is the snapshot directory:
    the base ref's embedded app_state/anchor seed recovery instead of
    restarting the counters at zero."""
    hist = _History(12)
    spec = _compacted_spec(tmp_path, hist, 8)
    snap_dir = spec["ledger_path"] + "-snapshots"
    assert not os.path.exists(snap_dir)  # never written in this scenario
    r = _recovered(spec)
    try:
        assert r.height() == 12 and r._base_height == 8
        assert r._chain == hist.chains[12]
        assert r.ids_digest() == hist.ids_digests[12].hex()
        assert r.committed_requests() == 12
        assert r._anchor_decision is not None
        md = decode(ViewMetadata, r._anchor_decision.proposal.metadata)
        assert md.latest_sequence == 8
    finally:
        r.ledger_file.close()


def test_recovery_with_torn_snapshot_falls_back_to_base_ref(tmp_path):
    hist = _History(12)
    spec = _compacted_spec(tmp_path, hist, 8)
    snap_dir = spec["ledger_path"] + "-snapshots"
    store = SnapshotStore(snap_dir)
    manifest, blob = hist.manifest(8)
    path = store.save(manifest, blob)
    with open(path, "r+b") as fh:
        fh.truncate(12)  # torn by the crash
    r = _recovered(spec)
    try:
        assert r.snapshot_store.rejected_files >= 1
        assert r.height() == 12 and r._base_height == 8
        assert r._chain == hist.chains[12]
        assert r.committed_requests() == 12
    finally:
        r.ledger_file.close()


def test_recovery_tolerates_torn_ledger_tail_after_compaction(tmp_path):
    hist = _History(12)
    spec = _compacted_spec(tmp_path, hist, 8)
    from smartbft_tpu.net.framing import encode_frame
    from smartbft_tpu.net.launch import _FT_LEDGER

    frame = encode_frame(_FT_LEDGER, encode(WireDecision(
        proposal=hist.decisions[0].proposal,
        signatures=list(hist.decisions[0].signatures))))
    with open(spec["ledger_path"], "ab") as fh:
        fh.write(frame[: len(frame) // 2])
    r = _recovered(spec)
    try:
        # the torn record is dropped; everything durable survives
        assert r.height() == 12 and r._base_height == 8
        assert r._chain == hist.chains[12]
    finally:
        r.ledger_file.close()


def test_install_snapshot_then_restart_recovers_identically(tmp_path):
    """_install_snapshot persists the snapshot FIRST, then compacts the
    ledger to just the base ref — so a restart straight after lands on
    the exact same state (the crash-between-persist-and-reset case)."""
    hist = _History(10)
    spec = _spec(tmp_path, node_id=1)
    r = _recovered(spec)
    manifest, blob = hist.manifest(10)
    assert verify_snapshot(manifest, blob, QUORUM, MEMBERS) is None
    r._install_snapshot(manifest, blob)
    try:
        assert r.height() == 10 and r._base_height == 10
        assert r._chain == hist.chains[10]
        assert r.ids_digest() == hist.ids_digests[10].hex()
        assert r.committed_requests() == 10
        assert r.snapshot_store.latest().manifest.height == 10
        assert r._snap_offer is not None and r._snap_offer[0] == 10
        disk = r.disk_snapshot()
        assert disk["base_height"] == 10 and disk["snapshot_height"] == 10
        assert disk["snapshot_age_decisions"] == 0
    finally:
        r.ledger_file.close()
    r2 = _recovered(_spec(tmp_path, node_id=1))  # same paths = restart
    try:
        assert r2.height() == 10 and r2._base_height == 10
        assert r2._chain == hist.chains[10]
        assert r2.committed_requests() == 10
        # consensus re-anchors at the snapshot's certificate
        md = decode(ViewMetadata, r2._anchor_decision.proposal.metadata)
        assert md.latest_sequence == 10
    finally:
        r2.ledger_file.close()


def test_install_then_snapshot_dir_loss_recovers_from_embedded_base(tmp_path):
    hist = _History(10)
    spec = _spec(tmp_path, node_id=1)
    r = _recovered(spec)
    manifest, blob = hist.manifest(10)
    r._install_snapshot(manifest, blob)
    r.ledger_file.close()
    shutil.rmtree(spec["ledger_path"] + "-snapshots")
    r2 = _recovered(_spec(tmp_path, node_id=1))
    try:
        assert r2.height() == 10 and r2._chain == hist.chains[10]
        assert r2.committed_requests() == 10
        assert r2._anchor_decision is not None
    finally:
        r2.ledger_file.close()


# ---------------------------------------------------------------------------
# satellite 2: the sync-poisoning guard rejects LOUDLY, never installs
# ---------------------------------------------------------------------------


def test_snapshot_catchup_rejects_every_poisoned_offer(tmp_path):
    hist = _History(8)
    r = _recovered(_spec(tmp_path))
    manifest, blob = hist.manifest(8)
    thin_d, _ = _decision(8, signers=(1, 2))
    alien_d, _ = _decision(8, signers=(1, 2, 9))
    offers = {
        2: b"not a snapshot at all",
        3: encode_snapshot_blob(
            make_manifest(8, hist.chains[8], blob, thin_d.proposal,
                          list(thin_d.signatures)), blob),
        4: encode_snapshot_blob(
            make_manifest(8, hist.chains[8], blob, alien_d.proposal,
                          list(alien_d.signatures)), blob),
    }

    async def fake_fetch(peer, height, chunk_bytes=0):
        return offers[peer]

    r.transport.fetch_snapshot = fake_fetch
    batches = [(p, SimpleNamespace(decisions=[], snapshot_height=8,
                                   snapshot_bytes=len(offers[p])))
               for p in (2, 3, 4)]
    try:
        installed = asyncio.run(
            r._try_snapshot_catchup(batches, 0, QUORUM, MEMBERS))
        assert installed is False
        assert r.height() == 0  # nothing installed, ever
        assert r.snapshot_store.latest() is None
        assert set(r.sync_poisoned) == {2, 3, 4}
        assert r.transport.metrics.sync_poisoned == 3
        assert r.disk_snapshot()["sync_poisoned"] == {2: 1, 3: 1, 4: 1}
        # an honest offer right after still installs (no lockout)
        offers[3] = encode_snapshot_blob(manifest, blob)
        installed = asyncio.run(r._try_snapshot_catchup(
            [(3, SimpleNamespace(decisions=[], snapshot_height=8,
                                 snapshot_bytes=len(offers[3])))],
            0, QUORUM, MEMBERS))
        assert installed is True
        assert r.height() == 8 and r._base_height == 8
        assert r._chain == hist.chains[8]
    finally:
        r.ledger_file.close()


def test_sync_over_wire_poisoned_tail_counts_per_peer(tmp_path):
    """A bogus tail (thin certificates) from every peer: rejected whole,
    counted per peer, zero decisions applied."""
    thin = []
    for seq in range(1, 5):
        d, _ = _decision(seq, signers=(1, 2))
        thin.append(WireDecision(proposal=d.proposal,
                                 signatures=list(d.signatures)))
    r = _recovered(_spec(tmp_path))

    async def fake_sync(peer, from_height, timeout=1.0):
        return SimpleNamespace(decisions=list(thin), snapshot_height=0,
                               snapshot_bytes=0)

    r.transport.request_sync = fake_sync
    try:
        asyncio.run(r._sync_over_wire())
        assert r.height() == 0
        assert set(r.sync_poisoned) == set(r.peers)
        assert all(v == 1 for v in r.sync_poisoned.values())
        assert r.transport.metrics.sync_poisoned == len(r.peers)
    finally:
        r.ledger_file.close()


def test_sync_over_wire_stale_tail_skipped_quietly(tmp_path):
    """Continuity failures are the normal raced-a-commit case, NOT
    poisoning: a tail starting past our height is skipped without
    touching the counters."""
    hist = _History(6)
    wire = [WireDecision(proposal=d.proposal, signatures=list(d.signatures))
            for d in hist.decisions[3:]]  # starts at seq 4, we are at 0
    r = _recovered(_spec(tmp_path))

    async def fake_sync(peer, from_height, timeout=1.0):
        return SimpleNamespace(decisions=list(wire), snapshot_height=0,
                               snapshot_bytes=0)

    r.transport.request_sync = fake_sync
    try:
        asyncio.run(r._sync_over_wire())
        assert r.height() == 0
        assert r.sync_poisoned == {}
        assert r.transport.metrics.sync_poisoned == 0
    finally:
        r.ledger_file.close()


# ---------------------------------------------------------------------------
# reshard snapshot handoff on the in-process App + pool dedup seeding
# ---------------------------------------------------------------------------


def _make_nodes(n, tmp_path):
    scheduler, network, shared = Scheduler(), Network(seed=1), SharedLedgers()
    apps = [
        App(i, network, shared, scheduler,
            wal_dir=str(tmp_path / f"wal-{i}"))
        for i in range(1, n + 1)
    ]
    return apps, scheduler, network, shared


def test_app_capture_install_chains_across_handoffs(tmp_path):
    async def run():
        apps, scheduler, network, shared = _make_nodes(4, tmp_path)
        for a in apps:
            await a.start()
        for k in range(3):
            await apps[0].submit("client-a", f"req-{k}")
        await wait_for(
            lambda: all(a.height() >= 1 for a in apps), scheduler)
        await wait_for(
            lambda: all(
                sum(len(a.requests_from_proposal(d.proposal))
                    for d in a.ledger()) == 3
                for a in apps),
            scheduler)
        snap = apps[0].capture_snapshot()
        # identical committed history -> identical digests on every node
        assert apps[1].capture_snapshot() == snap
        assert snap["request_count"] == 3
        assert len(snap["recent_ids"]) == 3
        # a NOT-YET-STARTED receiver seeded from the donor reports the
        # donor's exact digests from an empty local ledger (chaining)
        rx = App(9, network, shared, scheduler,
                 wal_dir=str(tmp_path / "wal-9"))
        rx.install_base_state(snap)
        assert rx.capture_snapshot() == snap
        # install on a STARTED node is a hard error
        with pytest.raises(RuntimeError):
            apps[0].install_base_state(snap)
        for a in apps:
            await a.stop()

    asyncio.run(run())


def test_installed_recent_ids_arm_pool_dedup(tmp_path):
    """A client resubmitting a request the donor already committed gets
    refused by the seeded receiver — never double-delivered."""

    async def run():
        apps, scheduler, network, shared = _make_nodes(4, tmp_path)
        seeded = {"height": 0, "chain_digest": "", "ids_digest": "",
                  "request_count": 0, "recent_ids": ["cli:r-0"]}
        for a in apps:
            a.install_base_state(seeded)
        for a in apps:
            await a.start()
        for a in apps:
            pool = a.consensus.pool
            assert RequestInfo(client_id="cli", request_id="r-0") \
                in pool._del_map
            with pytest.raises(ReqAlreadyProcessedError):
                pool._check_dup(
                    RequestInfo(client_id="cli", request_id="r-0"))
        # an unrelated request still flows end to end
        await apps[0].submit("cli", "r-1")
        await wait_for(lambda: all(a.height() >= 1 for a in apps),
                       scheduler)
        for a in apps:
            await a.stop()

    asyncio.run(run())


def test_config_mirror_roundtrips_snapshot_knobs():
    from smartbft_tpu.testing.app import fast_config

    cfg = dataclasses.replace(fast_config(1), snapshot_interval_decisions=8,
                              snapshot_chunk_bytes=4096)
    back = unmirror_config(mirror_config(cfg))
    assert back.snapshot_interval_decisions == 8
    assert back.snapshot_chunk_bytes == 4096


# ---------------------------------------------------------------------------
# slow: the full kill-rejoin-via-snapshot runs over real processes
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_socket_snapshot_rejoin_end_to_end(tmp_path):
    """SIGKILL a replica, grow + compact the donors past its crash
    height, respawn it: it MUST come back via chunked snapshot install
    (chain replay is impossible — the prefix is deleted) and converge
    fork-free with bounded disk."""
    from smartbft_tpu.net.cluster import SocketCluster, run_snapshot_rejoin

    with SocketCluster(
        tmp_path, n=4, transport="uds",
        config_overrides={"snapshot_interval_decisions": 8,
                          "snapshot_chunk_bytes": 1024},
    ) as cluster:
        report = run_snapshot_rejoin(cluster, warmup=8, history=48)
        assert report.victim_base_after > report.victim_height_at_kill
        assert report.snap_chunks_received > 1  # chunk size forces paging
        assert report.sync_poisoned_total == 0
        # disk stays bounded: every replica's ledger holds only a suffix
        for i in cluster.live_ids():
            stats = cluster.snapshot_stats(i)
            assert stats["base_height"] > 0
            assert stats["snapshot_age_decisions"] <= \
                2 * 8 + 10  # interval + one in-flight capture of slack


@pytest.mark.slow
def test_socket_snapshot_rejoin_crash_during_capture_and_donor_kill(tmp_path):
    """The adversarial variant: the victim dies RACING its own snapshot
    capture, and a serving donor is killed mid-chunk-transfer during the
    rejoin — the fetch must fail over, never wedge."""
    from smartbft_tpu.net.cluster import SocketCluster, run_snapshot_rejoin

    with SocketCluster(
        tmp_path, n=4, transport="uds",
        config_overrides={"snapshot_interval_decisions": 8,
                          "snapshot_chunk_bytes": 1024},
    ) as cluster:
        report = run_snapshot_rejoin(cluster, warmup=8, history=48,
                                     crash_during_snapshot=True,
                                     mid_fetch_donor_kill=True)
        assert report.victim_base_after > report.victim_height_at_kill
        assert "crash_during_snapshot" in report.events
        assert any(e.startswith("donor_kill:") for e in report.events)
