"""Signed client envelopes, held to OpenSSL exactly.

Seeded random envelopes, a known share corrupted five ways (a bit of
``r``, a bit of ``s``, a byte of the payload, another enrolled client's
key, a key that is not enrolled): whatever the system does with an
envelope equals what the plain reference (``tests/envelope_reference.py``:
OpenSSL on the raw bytes, nothing of the program) says of it.  Verdicts
are booleans and ledgers are bytes: every comparison is exact.
"""

import asyncio
import dataclasses
import hashlib
import random

import numpy as np
import pytest

from smartbft_tpu.codec import decode, encode
from smartbft_tpu.crypto import p256
from smartbft_tpu.crypto.envelope import (
    EnvelopeRejected,
    EnvelopeVerifier,
    creator_bytes,
    sign_envelope,
    split_envelope,
)
from smartbft_tpu.crypto.provider import (
    AsyncBatchCoalescer,
    HostVerifyEngine,
    JaxVerifyEngine,
    Keyring,
    P256CryptoProvider,
)
from smartbft_tpu.messages import PrePrepare
from smartbft_tpu.obs import TraceRecorder
from smartbft_tpu.testing.app import (
    App,
    BatchPayload,
    SharedLedgers,
    TestRequest as UnsignedRequest,
    fast_config,
    wait_for,
)
from smartbft_tpu.testing.network import Network
from smartbft_tpu.utils.clock import Scheduler

from tests.envelope_reference import openssl_accepts, openssl_accepts_vote
from tests.test_basic import stop_all

FORGERIES = ("bit_of_r", "bit_of_s", "byte_of_payload",
             "another_enrolled_key", "key_not_enrolled")
#: what the system says of each, before any comparison with the reference
CAUSE = {"bit_of_r": "bad_signature", "bit_of_s": "bad_signature",
         "byte_of_payload": "bad_signature",
         "another_enrolled_key": "bad_signature",
         "key_not_enrolled": "not_enrolled"}


def flip(raw: bytes, at: int, mask: int) -> bytes:
    return raw[:at] + bytes([raw[at] ^ mask]) + raw[at + 1:]


class Channel:
    """``n`` enrolled identities and one outsider, from a seed."""

    def __init__(self, n: int, seed: int):
        self.rng = random.Random(seed)
        self.ids = [p256.keygen(b"envelope-test-%d-%d" % (seed, i))
                    for i in range(n)]
        self.outsider = p256.keygen(b"envelope-test-%d-outsider" % seed)
        self.enrolled = [pub for _, pub in self.ids]
        self.creators = {creator_bytes(pub) for pub in self.enrolled}

    def honest(self, i: int, rid: str, size: int = 96) -> bytes:
        return sign_envelope(*self.ids[i], f"client-{i}", rid,
                             self.rng.randbytes(size))

    def forged(self, i: int, rid: str, how: str, size: int = 96) -> bytes:
        signer = self.outsider if how == "key_not_enrolled" else self.ids[i]
        raw = sign_envelope(*signer, f"client-{i}", rid,
                            self.rng.randbytes(size))
        end = len(raw)
        if how == "bit_of_r":
            return flip(raw, end - 64 + self.rng.randrange(32),
                        1 << self.rng.randrange(8))
        if how == "bit_of_s":
            return flip(raw, end - 32 + self.rng.randrange(32),
                        1 << self.rng.randrange(8))
        if how == "byte_of_payload":
            return flip(raw, end - 137 - self.rng.randrange(size), 0xFF)
        if how == "another_enrolled_key":
            other = self.enrolled[(i + 1) % len(self.enrolled)]
            return raw[:end - 132] + creator_bytes(other) + raw[end - 68:]
        return raw

    def accepts(self, raw: bytes) -> bool:
        return openssl_accepts(raw, self.creators)


def test_the_envelope_layout_is_the_unsigned_request_plus_a_trailer():
    ch = Channel(2, 1)
    raw = sign_envelope(*ch.ids[0], "alice", "r7", b"pay")
    signed, creator, sig = split_envelope(raw)
    assert signed == encode(UnsignedRequest(
        client_id="alice", request_id="r7", payload=b"pay"))
    assert creator == creator_bytes(ch.enrolled[0]) and len(sig) == 64
    assert raw == signed + (64).to_bytes(4, "big") + creator \
        + (64).to_bytes(4, "big") + sig
    assert ch.accepts(raw)
    for bad in (b"", raw[:-1], signed, raw + b"\x00"):
        with pytest.raises(EnvelopeRejected) as e:
            split_envelope(bad)
        assert e.value.cause == "malformed" and not ch.accepts(bad)


# -- (i) the engine's split ----------------------------------------------------


def one_flush(ev, lanes) -> tuple:
    """``lanes``: ``("vote", item)`` / ``("env", raw)`` in submission order
    -> the flush's items and, by lane, the cause of each envelope refused
    before any device work."""
    items, refused = [], {}
    for n, (kind, x) in enumerate(lanes):
        if kind == "vote":
            items.append(x)
            continue
        try:
            items.append(ev.item(x))
        except EnvelopeRejected as e:
            refused[n] = e.cause
    return items, refused


def judged(eng, items, refused, lanes, ch) -> tuple:
    """-> (the system's verdict, OpenSSL's) lane by lane."""
    verdicts = iter(eng.verify(items))
    got = [False if n in refused else next(verdicts)
           for n in range(len(lanes))]
    return got, [openssl_accepts_vote(x) if kind == "vote"
                 else ch.accepts(x)
                 for kind, x in lanes]


def test_a_mixed_flush_is_one_comb_and_one_generic_launch_in_order(
        monkeypatch):
    """A ring of 4 and 40 other keys in ONE flush: the ring's lanes ride
    the comb kernel (the real kernel, interpret mode), every other key the
    arbitrary-key path (the XLA P-256 kernel under the generic kernel's
    name: its Pallas twin does not run on a CPU), verdicts in submission
    order and equal to OpenSSL's lane by lane; no client key enters the
    comb registry, by first use or by ``prewarm_keys``."""
    from smartbft_tpu.crypto import pallas_comb as pc

    monkeypatch.setenv("SMARTBFT_PALLAS", "1")
    rings = Keyring.generate([1, 2, 3, 4], seed=b"split", scheme=p256)
    ring = [rings[1].public_keys[i] for i in (1, 2, 3, 4)]
    eng = JaxVerifyEngine(pad_sizes=(16,), scheme=p256, ring=ring,
                          request_pad_sizes=(64,))
    monkeypatch.setattr(eng, "_pallas_kernel", eng._kernel)
    monkeypatch.setattr(
        eng._comb, "_launch",
        lambda arrays, ok, kidx, gtab, qtab: pc.ecdsa_verify_comb(
            *arrays, kidx, gtab, qtab, tile=16, interpret=True))

    ch = Channel(40, 2)
    rng = ch.rng
    votes = []
    for k in range(8):
        i = 1 + k % 4
        msg = rng.randbytes(48)
        sig = p256.sign_raw(rings[i].private_key, msg)
        if k in (2, 5):  # a vote with a bit of s flipped
            sig = flip(sig, 40, 0x04)
        votes.append(p256.make_item(msg, sig, ring[i - 1]))
    envelopes = [ch.honest(i, "r0") for i in range(40)]
    for n, i in enumerate(rng.sample(range(40), 15)):
        envelopes[i] = ch.forged(i, "r0", FORGERIES[n % 5])
    ev = EnvelopeVerifier(ch.enrolled, engine=eng)

    # one flush, votes scattered among the envelopes' lanes
    lanes = [("vote", v) for v in votes] + [("env", e) for e in envelopes]
    rng.shuffle(lanes)
    items, refused = one_flush(ev, lanes)
    assert set(refused.values()) == {"not_enrolled"} and len(refused) == 3

    for pub in ring:  # what the ring's first vote wave does
        eng._comb.registry.register(pub)
    slots = eng._comb.registry.slots()
    got, want = judged(eng, items, refused, lanes, ch)
    assert got == want
    assert want.count(False) == 2 + 15 and want.count(True) == 6 + 25

    s = eng.stats
    assert s.launches_by_kernel == {"comb": 1, "pallas": 1, "xla": 0,
                                    "host": 0}
    assert s.lanes_by_kernel["comb"] == 16 and s.used_by_kernel["comb"] == 8
    assert s.lanes_by_kernel["pallas"] == 64 \
        and s.used_by_kernel["pallas"] == 37
    # the ring's four keys, and nothing else, ever
    assert len(eng._comb.registry) == 4
    assert eng._comb.registry.slots() == slots == 4
    others = [p256.keygen(b"another-%d" % i)[1] for i in range(1000)]
    eng.prewarm_keys(ring + ch.enrolled + others)  # what the harness does
    assert eng._comb._pending_prewarm == []
    assert len(eng._comb.registry) == 4
    assert eng._comb.registry.slots() == slots


def test_leading_zero_bytes_in_r_s_and_digest_are_judged_as_openssl_does(
        monkeypatch):
    """The launch packs ``r``, ``s``, the key and the digest from bytes; a
    value whose top bytes are zero is where that goes wrong.  A ring of 4
    and 30 other keys in ONE flush on the XLA P-256 kernel (a CPU has no
    other: both key classes ride it, each on its ladder), with envelopes
    and votes SEARCHED for a zero top byte in ``r``, in ``s`` and in the
    digest of the signed part (two for a digest), and the five forgeries
    among them: verdicts equal OpenSSL's lane by lane, in submission
    order."""
    monkeypatch.delenv("SMARTBFT_PALLAS", raising=False)
    rings = Keyring.generate([1, 2, 3, 4], seed=b"zeros", scheme=p256)
    ring = [rings[1].public_keys[i] for i in (1, 2, 3, 4)]
    eng = JaxVerifyEngine(pad_sizes=(64,), scheme=p256, ring=ring,
                          request_pad_sizes=(64,))
    ch = Channel(30, 3)
    rng = ch.rng
    digest = lambda signed: hashlib.sha256(signed).digest()  # noqa: E731

    def search(make, found, tries=1 << 18):
        for _ in range(tries):
            x = make()
            if found(x):
                return x
        raise AssertionError("no such value in %d tries" % tries)

    # envelopes: signing is randomised, so the same envelope signed again
    # gives another (r, s); another payload gives another digest
    zero_r = [search(lambda: ch.honest(i, "zr"), lambda raw: raw[-64] == 0)
              for i in (0, 1, 2)]
    zero_s = [search(lambda: ch.honest(i, "zs"), lambda raw: raw[-32] == 0)
              for i in (3, 4, 5)]
    zero_e = [search(lambda: ch.honest(6, "ze"),
                     lambda raw: digest(split_envelope(raw)[0])[0] == 0),
              search(lambda: ch.honest(7, "ze2"),
                     lambda raw: digest(split_envelope(raw)[0])[:2] == b"\0\0")]
    # a forgery OF a value with a zero top byte: its zero byte stays, a
    # bit of the other half flips
    forged_zero = [flip(zero_r[0], len(zero_r[0]) - 10, 0x01),
                   flip(zero_s[0], len(zero_s[0]) - 40, 0x80)]
    plain = [ch.honest(i, "p") for i in range(8, 20)]
    forged = [ch.forged(20 + n, "f", how) for n, how in enumerate(FORGERIES)]
    envelopes = zero_r + zero_s + zero_e + forged_zero + plain + forged
    # votes of the ring, two of them with a zero top byte too
    votes = []
    for k, want_zero in enumerate((None, 0, None, 32, None, None)):
        i, msg = 1 + k % 4, rng.randbytes(48)
        sig = search(lambda: p256.sign_raw(rings[i].private_key, msg),
                     lambda sig: want_zero is None or sig[want_zero] == 0)
        if k == 4:
            sig = flip(sig, 5, 0x10)
        votes.append(p256.make_item(msg, sig, ring[i - 1]))
    assert sum(v[1] < 1 << 248 for v in votes) >= 1 \
        and sum(v[2] < 1 << 248 for v in votes) >= 1

    ev = EnvelopeVerifier(ch.enrolled, engine=eng)
    lanes = [("vote", v) for v in votes] + [("env", e) for e in envelopes]
    rng.shuffle(lanes)
    items, refused = one_flush(ev, lanes)
    assert refused and set(refused.values()) == {"not_enrolled"}
    # the lanes the search was for are really in the flush
    outside = [it for it in items if it[-1] not in ring]
    assert sum(it[1] < 1 << 248 for it in outside) >= 4  # r
    assert sum(it[2] < 1 << 248 for it in outside) >= 4  # s
    assert sum(digest(it[0])[0] == 0 for it in outside) >= 2
    assert sum(digest(it[0])[:2] == b"\0\0" for it in outside) >= 1

    got, want = judged(eng, items, refused, lanes, ch)
    assert got == want
    assert want.count(False) == 1 + 2 + 5
    assert want.count(True) == 5 + 8 + 12
    s = eng.stats
    assert s.launches_by_kernel == {"comb": 0, "pallas": 0, "xla": 2,
                                    "host": 0}
    assert s.used_by_kernel["xla"] == len(items) == 6 + 27 - len(refused)


def test_an_engine_told_no_ring_registers_what_it_is_handed(monkeypatch):
    """The contract before rings, kept: keys are registrable at first use
    and ``prewarm_keys`` of more than the registry holds raises
    ``CombRegistryFull`` (what ``CryptoProvider`` degrades on)."""
    from smartbft_tpu.crypto import pallas_comb as pc

    monkeypatch.setenv("SMARTBFT_PALLAS", "1")
    eng = JaxVerifyEngine(pad_sizes=(8,), scheme=p256)
    keys = [p256.keygen(b"no-ring-%d" % i)[1] for i in range(130)]
    with pytest.raises(pc.CombRegistryFull):
        eng.prewarm_keys(keys)
    assert len(eng._comb._pending_prewarm) == 128
    assert eng.request_pad_sizes == eng.pad_sizes


def test_the_prewarm_entry_compiles_both_kernels_rungs(monkeypatch):
    """``prewarm_verify_engine`` on an engine with a ring launches every
    rung of BOTH ladders, each under its own kernel (stubbed kernels: what
    is pinned is which shapes are asked for)."""
    from smartbft_tpu.crypto.ladder import auto_pad_sizes, request_pad_sizes
    from smartbft_tpu.crypto.provider import prewarm_verify_engine

    monkeypatch.setenv("SMARTBFT_PALLAS", "1")
    rings = Keyring.generate([1, 2, 3, 4], seed=b"prewarm", scheme=p256)
    votes, requests = auto_pad_sizes(4, "p256", 1), request_pad_sizes(500)
    assert votes == (8, 32, 128) and requests == (512,)
    eng = JaxVerifyEngine(pad_sizes=votes, scheme=p256,
                          ring=rings[1].public_keys.values(),
                          request_pad_sizes=requests)
    shapes = []

    def comb(items, pad_to):
        shapes.append(("comb", pad_to))
        return np.zeros(len(items), np.uint32)

    def generic(*arrays):
        shapes.append(("pallas", arrays[0].shape[0]))
        return np.zeros(arrays[0].shape[0], np.uint32)

    monkeypatch.setattr(eng._comb, "verify", comb)
    monkeypatch.setattr(eng, "_pallas_kernel", generic)
    prewarm_verify_engine(eng)
    assert sorted(shapes) == [("comb", 8), ("comb", 32), ("comb", 128),
                              ("pallas", 512)]
    assert eng.stats.lanes_by_kernel["pallas"] == 512


# -- clusters --------------------------------------------------------------------


def cluster_config(i):
    return dataclasses.replace(
        fast_config(i),
        # the engine runs on threads in real time while the logical clock
        # races ahead: generous liveness timers (as test_byzantine_crypto)
        request_forward_timeout=60.0, request_complain_timeout=120.0,
        request_auto_remove_timeout=240.0, view_change_resend_interval=60.0,
        view_change_timeout=240.0, leader_heartbeat_timeout=120.0,
    )


def make_cluster(tmp_path, enrolled, engine=None, recorders=False):
    scheduler, network, shared = Scheduler(), Network(seed=7), SharedLedgers()
    if engine is None:
        from smartbft_tpu.crypto.openssl_engine import OpenSSLVerifyEngine

        engine = OpenSSLVerifyEngine(scheme=p256)
    coalescer = AsyncBatchCoalescer(engine, window=0.005, max_batch=4096,
                                    dedupe=True)
    rings = Keyring.generate([1, 2, 3, 4], seed=b"envelopes", scheme=p256)
    apps = [
        App(i, network, shared, scheduler,
            wal_dir=str(tmp_path / f"wal-{i}"), config=cluster_config(i),
            crypto=P256CryptoProvider(rings[i], coalescer=coalescer),
            enrolled=enrolled,
            recorder=TraceRecorder(node=f"n{i}", enabled=True)
            if recorders else None)
        for i in (1, 2, 3, 4)
    ]
    return apps, scheduler, coalescer


def ledger_requests(app) -> list:
    return [raw for d in app.ledger() if d.proposal.payload
            for raw in decode(BatchPayload, d.proposal.payload).requests]


def test_honest_envelopes_commit_once_and_forged_ones_reach_no_ledger(
        tmp_path):
    """(ii) end to end on four replicas, the engine the pure-Python host
    verifier (nothing of OpenSSL in the system's path): what the front
    door does with each envelope is what OpenSSL says of it; the honest
    ones are on all four ledgers exactly once, byte for byte as submitted,
    the forged ones on none."""

    async def run():
        ch = Channel(12, 3)
        apps, scheduler, _co = make_cluster(
            tmp_path, ch.enrolled, engine=HostVerifyEngine(scheme=p256))
        for a in apps:
            await a.start()
        honest = [ch.honest(i, f"r{k}") for i in range(12) for k in (0, 1)]
        forged = [(ch.forged(i, f"f{i}", FORGERIES[i % 5]), FORGERIES[i % 5])
                  for i in range(10)]
        every = [(raw, None) for raw in honest] + forged
        ch.rng.shuffle(every)

        async def submit(raw):
            try:
                await apps[0].consensus.submit_request(raw)
                return None
            except EnvelopeRejected as e:
                return e.cause

        came_back = await asyncio.gather(*(submit(raw) for raw, _ in every))
        for (raw, how), cause in zip(every, came_back):
            assert (cause is None) == ch.accepts(raw)
            assert cause == (CAUSE[how] if how else None)
        await wait_for(
            lambda: all(len(ledger_requests(a)) >= 24 for a in apps),
            scheduler, timeout=600.0)
        for a in apps:
            assert sorted(ledger_requests(a)) == sorted(honest)
            assert ledger_requests(a) == ledger_requests(apps[0])
        door = apps[0].envelopes
        assert door.rejected == {"malformed": 0, "not_enrolled": 2,
                                 "bad_signature": 8, "wrong_channel": 0}
        for a in apps[1:]:  # followers judged every block's envelopes
            assert a.envelopes.accepted == 24
            assert not any(a.envelopes.rejected.values())
        # the synchronous SPI refuses them too
        for raw, how in forged:
            with pytest.raises(EnvelopeRejected):
                apps[1].verify_request(raw)
        await stop_all(apps)

    asyncio.run(run())


def test_a_leader_that_proposes_a_forged_envelope_is_deposed(tmp_path):
    """(iii) the leader's pre-prepare carries one forged envelope (put in
    at its send): every follower refuses the proposal, the view changes,
    the honest envelopes commit under the next leader, the forged one
    never."""

    async def run():
        ch = Channel(8, 4)
        apps, scheduler, _co = make_cluster(tmp_path, ch.enrolled)
        for a in apps:
            await a.start()
        honest = [ch.honest(i, "r0") for i in range(8)]
        forgery = ch.forged(0, "r0", "byte_of_payload")
        assert not ch.accepts(forgery)

        def inject(_target, msg):
            if isinstance(msg, PrePrepare) and msg.proposal.payload:
                reqs = list(decode(BatchPayload,
                                   msg.proposal.payload).requests)
                reqs[0] = forgery
                return dataclasses.replace(msg, proposal=dataclasses.replace(
                    msg.proposal,
                    payload=encode(BatchPayload(requests=reqs))))
            return msg

        apps[0].node.mutate_send = inject
        # a client of a BFT channel sends to every orderer
        for raw in honest:
            for a in apps:
                await a.consensus.submit_request(raw)
        await wait_for(
            lambda: all(a.consensus.get_leader_id() == 2 for a in apps[1:]),
            scheduler, timeout=600.0)
        for a in apps[1:]:
            assert a.envelopes.rejected["bad_signature"] >= 1
        apps[0].node.mutate_send = None
        await wait_for(
            lambda: all(len(ledger_requests(a)) >= 8 for a in apps[1:]),
            scheduler, timeout=600.0)
        for a in apps[1:]:
            assert sorted(ledger_requests(a)) == sorted(honest)
        assert all(forgery not in ledger_requests(a) for a in apps)
        await stop_all(apps)

    asyncio.run(run())


def test_a_forwarded_forged_envelope_is_dropped_at_the_leader(tmp_path):
    """(iv) a follower's forward: verified through the coalescer at the
    leader, a forged one dropped and counted, an honest one pooled and
    committed."""

    async def run():
        ch = Channel(4, 5)
        apps, scheduler, _co = make_cluster(tmp_path, ch.enrolled)
        for a in apps:
            await a.start()
        leader = apps[0].consensus
        good = ch.honest(1, "r0")
        for how in FORGERIES:
            assert await leader.handle_request(2, ch.forged(0, "f", how)) \
                is None
        assert await leader.handle_request(3, good) is None
        await wait_for(lambda: leader.controller.bad_forwards == 5
                       and all(ledger_requests(a) == [good] for a in apps),
                       scheduler, timeout=600.0)
        assert apps[0].envelopes.rejected == {
            "malformed": 0, "not_enrolled": 1, "bad_signature": 4,
            "wrong_channel": 0}
        assert leader.pool_occupancy().get("size", 0) == 0
        await stop_all(apps)

    asyncio.run(run())


@pytest.mark.parametrize("enrolled", [False, True],
                         ids=["no identities", "enrolled"])
def test_without_enrolled_identities_the_path_is_the_old_one(tmp_path,
                                                             enrolled):
    """(v) by the recorder's counts, not by time: an App with no enrolled
    identities exposes no coroutine to the core, nothing is packed, awaited
    or submitted to the coalescer per request, and the coalescer sees the
    votes' waves only; with identities each request is counted once at the
    front door and once per follower's proposal check."""

    async def run():
        ch = Channel(6, 6)
        apps, scheduler, co = make_cluster(
            tmp_path, ch.enrolled if enrolled else None, recorders=True)
        verify_rec = TraceRecorder(node="verify", enabled=True)
        co.attach_recorder(verify_rec)
        for a in apps:
            await a.start()
        for i in range(6):
            if enrolled:
                await apps[0].consensus.submit_request(ch.honest(i, "r0"))
            else:
                await apps[0].submit(f"client-{i}", "r0", b"unsigned")
        await wait_for(lambda: all(len(ledger_requests(a)) >= 6
                                   for a in apps), scheduler, timeout=600.0)
        decisions = apps[0].height()
        kinds = {}
        for a in apps:
            for k, n in a.recorder.kind_counts.items():
                kinds[k] = kinds.get(k, 0) + n
        waits = verify_rec.kind_counts.get("verify.wait", 0)
        await stop_all(apps)
        return decisions, kinds, waits, apps

    decisions, kinds, waits, apps = asyncio.run(run())
    votes = 2 * 4 * decisions  # two quorum checks a replica a decision
    if not enrolled:
        for a in apps:
            assert a.envelopes is None
            assert not hasattr(a, "verify_request_async")
            assert not hasattr(a, "verify_proposal_async")
            assert a.consensus.controller._check_request is None
        for kind in ("request.verify", "proposal.verify", "request.pack",
                     "req.rejected"):
            assert kind not in kinds
        assert waits <= votes
    else:
        assert kinds["request.verify"] == 6
        assert kinds["proposal.verify"] == 3 * decisions
        assert kinds["request.pack"] == 6 + 3 * decisions
        assert "req.rejected" not in kinds
        assert 6 + 3 * decisions <= waits <= 6 + 3 * decisions + votes


def test_the_resumed_submitter_is_one_busy_span_a_request(tmp_path):
    """``req.admit`` (ISSUE 37): the awaited request path after the
    verdict, from the resume until the pool has the request.  Once an
    honest envelope, on the loop thread, after that envelope's
    ``request.verify`` wait (whose record is inside it); a refused
    envelope's ends as the refusal is raised; and it never spans an await:
    nothing is refused by the stack discipline, also where the pool is
    full and parks the submitter."""
    from smartbft_tpu.obs import recorder as recmod

    async def run():
        ch = Channel(8, 9)
        apps, scheduler, _co = make_cluster(tmp_path, ch.enrolled,
                                            recorders=True)
        for a in apps:
            await a.start()
        leader = apps[0].consensus
        leader.controller.request_pool._opts.queue_size = 2  # some park
        sends = [asyncio.ensure_future(
            leader.submit_request(ch.honest(i, "r0"))) for i in range(6)]
        with pytest.raises(EnvelopeRejected):
            await leader.submit_request(ch.forged(6, "f", "bit_of_r"))
        await wait_for(lambda: all(len(ledger_requests(a)) >= 6
                                   for a in apps), scheduler, timeout=600.0)
        await asyncio.gather(*sends)
        events = apps[0].recorder.events()
        await stop_all(apps)
        return events

    before = dict(recmod._refused)
    events = asyncio.run(run())
    assert recmod._refused == before
    admits = [e for e in events if e.kind == "req.admit"]
    verdicts = [e for e in events if e.kind == "request.verify"]
    assert len(admits) == len(verdicts) == 7  # six honest, one forged
    assert any(e.kind == "req.pool" for e in events)  # some did park
    assert all(e.self_s >= 0.0 and e.dur < 0.05 for e in admits)
    assert len({e.thread for e in admits}) == 1
    # each holds its own verdict's record: they end in pairs, no other
    # request's in between
    order = [e.kind for e in events
             if e.kind in ("req.admit", "request.verify")]
    assert order == ["request.verify", "req.admit"] * 7


def test_the_socket_replica_app_refuses_a_forged_envelope(tmp_path):
    """The second embedder, through the same implementation: a socket
    replica given enrolled identities refuses a forged envelope in
    ``verify_request`` and in ``verify_proposal``; given none it takes
    unsigned requests as before."""
    from smartbft_tpu.messages import Proposal
    from smartbft_tpu.net.launch import ReplicaApp

    def spec(node_id, **more):
        base = str(tmp_path)
        return dict({
            "node_id": node_id,
            "peers": {i: f"uds:{base}/n{i}.sock" for i in (1, 2, 3, 4)
                      if i != node_id},
            "listen": f"uds:{base}/n{node_id}.sock",
            "ledger_path": f"{base}/ledger-{node_id}.bin",
            "wal_dir": f"{base}/wal-{node_id}",
        }, **more)

    ch = Channel(5, 7)
    r = ReplicaApp(spec(1, enrolled=[creator_bytes(p).hex()
                                     for p in ch.enrolled]))
    good = [ch.honest(i, "r0") for i in range(5)]
    assert str(r.verify_request(good[0])) == "client-0:r0"
    block = Proposal(payload=encode(BatchPayload(requests=good)))
    assert [str(i) for i in r.verify_proposal(block)] == [
        f"client-{i}:r0" for i in range(5)]
    for how in FORGERIES:
        bad = ch.forged(2, "r0", how)
        assert not ch.accepts(bad)
        with pytest.raises(EnvelopeRejected) as e:
            r.verify_request(bad)
        assert e.value.cause == CAUSE[how]
        with pytest.raises(EnvelopeRejected):
            r.verify_proposal(Proposal(payload=encode(BatchPayload(
                requests=good[:2] + [bad] + good[3:]))))
    with pytest.raises(EnvelopeRejected):  # an unsigned request, on a
        r.verify_request(encode(UnsignedRequest(  # channel with identities
            client_id="client-0", request_id="r1")))
    assert hasattr(r, "verify_request_async")

    plain = ReplicaApp(spec(2))
    assert plain.envelopes is None
    assert not hasattr(plain, "verify_request_async")
    raw = encode(UnsignedRequest(client_id="c", request_id="r"))
    assert str(plain.verify_request(raw)) == "c:r"
