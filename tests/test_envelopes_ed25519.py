"""Ed25519-signed client envelopes, held to OpenSSL exactly, and the pin
that P-256 envelopes keep their bytes.

An Ed25519 envelope is the same signed part with a trailer of the
creator's 32-byte key and RFC 8032's ``R || S``.  The plain reference here
reads that trailer by offset and asks OpenSSL, nothing of the program.
"""

import asyncio
import dataclasses
import hashlib
import random

import pytest
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

from smartbft_tpu.codec import decode
from smartbft_tpu.crypto import ed25519, p256
from smartbft_tpu.crypto.envelope import (
    EnvelopeRejected,
    EnvelopeVerifier,
    creator_bytes,
    sign_envelope,
    split_envelope,
)
from smartbft_tpu.crypto.openssl_engine import OpenSSLVerifyEngine
from smartbft_tpu.crypto.provider import (
    AsyncBatchCoalescer,
    Ed25519CryptoProvider,
    HostVerifyEngine,
    Keyring,
)
from smartbft_tpu.testing.app import App, BatchPayload, SharedLedgers, \
    fast_config, wait_for
from smartbft_tpu.testing.network import Network
from smartbft_tpu.utils.clock import Scheduler

from tests.test_basic import stop_all

#: the trailer of an Ed25519 envelope: u32(32) key u32(64) R || S
TRAILER = 4 + 32 + 4 + 64
L = ed25519.L
FORGERIES = ("bit_of_r", "bit_of_s", "byte_of_payload",
             "another_enrolled_key", "key_not_enrolled", "s_plus_l")
#: what the system says of each
CAUSE = dict(dict.fromkeys(FORGERIES, "bad_signature"),
             key_not_enrolled="not_enrolled")


def flip(raw: bytes, at: int, mask: int) -> bytes:
    return raw[:at] + bytes([raw[at] ^ mask]) + raw[at + 1:]


def openssl_accepts(raw: bytes, enrolled: set) -> bool:
    cut = len(raw) - TRAILER
    if cut < 0 or raw[cut:cut + 4] != b"\0\0\0\x20" \
            or raw[cut + 36:cut + 40] != b"\0\0\0\x40":
        return False
    key, sig = raw[cut + 4:cut + 36], raw[cut + 40:]
    if key not in enrolled:
        return False
    try:
        Ed25519PublicKey.from_public_bytes(key).verify(sig, raw[:cut])
        return True
    except Exception:  # noqa: BLE001 — any refusal is a False verdict
        return False


class Channel:
    """``n`` enrolled Ed25519 identities and one outsider, from a seed."""

    def __init__(self, n: int, seed: int):
        self.rng = random.Random(seed)
        self.ids = [ed25519.keygen(b"ed-envelope-%d-%d" % (seed, i))
                    for i in range(n)]
        self.outsider = ed25519.keygen(b"ed-envelope-%d-outsider" % seed)
        self.enrolled = [pub for _, pub in self.ids]

    def honest(self, i: int, rid: str, size: int = 96) -> bytes:
        return sign_envelope(*self.ids[i], f"client-{i}", rid,
                             self.rng.randbytes(size), scheme=ed25519)

    def forged(self, i: int, rid: str, how: str, size: int = 96) -> bytes:
        signer = self.outsider if how == "key_not_enrolled" else self.ids[i]
        raw = sign_envelope(*signer, f"client-{i}", rid,
                            self.rng.randbytes(size), scheme=ed25519)
        end = len(raw)
        if how == "bit_of_r":
            return flip(raw, end - 64 + self.rng.randrange(32),
                        1 << self.rng.randrange(8))
        if how == "bit_of_s":  # below the top byte: S stays under 2^253
            return flip(raw, end - 32 + self.rng.randrange(31),
                        1 << self.rng.randrange(8))
        if how == "byte_of_payload":
            return flip(raw, end - TRAILER - 1 - self.rng.randrange(size),
                        0xFF)
        if how == "another_enrolled_key":
            other = self.enrolled[(i + 1) % len(self.enrolled)]
            return raw[:end - 100] + other + raw[end - 68:]
        if how == "s_plus_l":
            s = int.from_bytes(raw[end - 32:], "little")
            return raw[:end - 32] + (s + L).to_bytes(32, "little")
        return raw

    def accepts(self, raw: bytes) -> bool:
        return openssl_accepts(raw, set(self.enrolled))


def test_p256_envelopes_keep_their_bytes(monkeypatch):
    """A fixed key and request give a fixed envelope: the scheme-aware
    envelope changed nothing of a P-256 channel's bytes (RFC 6979 nonces,
    so the signature is reproducible)."""
    monkeypatch.setenv("SMARTBFT_DETERMINISTIC_SIGN", "1")
    sk, pk = p256.keygen(b"envelope-pin")
    raw = sign_envelope(sk, pk, "alice", "r7", b"payload-bytes" * 10)
    assert len(raw) == 285 and hashlib.sha256(raw).hexdigest() == \
        "067357829ac85e15082ff7582b665c9ec5062f0b7bb073a89725201809008226"
    named = sign_envelope(sk, pk, "alice", "r8", b"x" * 32, channel="trade")
    assert len(named) == 209 and hashlib.sha256(named).hexdigest() == \
        "2b4eadebe71c564f527e6eba053fd3ddf95d55e8edc7595f96345e8b414b5c6d"
    assert split_envelope(raw)[1] == creator_bytes(pk) \
        == sign_envelope(sk, pk, "a", "b", scheme=p256)[-132:-68]


def test_the_ed25519_trailer_is_the_key_and_r_s():
    ch = Channel(2, 1)
    raw = ch.honest(0, "r1")
    signed, creator, sig = split_envelope(raw, ed25519)
    assert creator == ch.enrolled[0] == creator_bytes(ch.enrolled[0],
                                                      ed25519)
    assert len(sig) == 64 and raw == signed + b"\0\0\0\x20" + creator \
        + b"\0\0\0\x40" + sig
    Ed25519PublicKey.from_public_bytes(creator).verify(sig, signed)
    # the other scheme's reading finds no trailer, either way round
    with pytest.raises(EnvelopeRejected, match="malformed"):
        split_envelope(raw)
    sk, pk = p256.keygen(b"other")
    with pytest.raises(EnvelopeRejected, match="malformed"):
        split_envelope(sign_envelope(sk, pk, "a", "b"), ed25519)
    with pytest.raises(ValueError, match="no client envelopes"):
        EnvelopeVerifier(ch.enrolled, engine=None, scheme=object())


@pytest.mark.parametrize("engine", ["openssl", "host"])
def test_each_forgery_is_refused_for_its_cause(engine):
    """Honest envelopes and the six forgeries through ONE check each:
    what the verifier says equals what OpenSSL says, with the cause; a
    truncated envelope is ``malformed`` and one naming a channel this
    verifier does not serve ``wrong_channel``."""
    ch = Channel(6, 2)
    eng = OpenSSLVerifyEngine(scheme=ed25519) if engine == "openssl" \
        else HostVerifyEngine(scheme=ed25519)
    ev = EnvelopeVerifier(ch.enrolled, engine=eng, scheme=ed25519)
    honest = [ch.honest(i, "r0") for i in range(6)]
    ev.check(honest)
    assert ev.accepted == 6 and all(ch.accepts(raw) for raw in honest)
    for n, how in enumerate(FORGERIES):
        raw = ch.forged(n % 6, f"f{n}", how)
        assert not ch.accepts(raw)
        with pytest.raises(EnvelopeRejected) as e:
            ev.check([raw])
        assert e.value.cause == CAUSE[how], how
    with pytest.raises(EnvelopeRejected, match="malformed"):
        ev.check([honest[0][:-1]])
    named = sign_envelope(*ch.ids[0], "client-0", "n0", b"x",
                          channel="trade", scheme=ed25519)
    assert ch.accepts(named)
    ev.check([named])  # an unnamed channel does not look into payloads
    with pytest.raises(EnvelopeRejected, match="wrong_channel"):
        EnvelopeVerifier(ch.enrolled, engine=eng, channel="settle",
                         scheme=ed25519).check([named])
    assert ev.rejected == {"malformed": 1, "not_enrolled": 1,
                           "bad_signature": 5, "wrong_channel": 0}


def test_enrolled_keys_are_decoded_once_and_carried_to_the_engine():
    """The verifier holds each enrolled key decoded (``ed25519.PublicKey``)
    and its items carry it: the engine's arbitrary-key path reads the
    point from the key it is handed, however many identities there are
    (more than the 1024 a memoised decoding would hold)."""
    pubs = [ed25519.keygen(b"many-%d" % i)[1] for i in range(1100)]
    ev = EnvelopeVerifier(pubs, engine=None, scheme=ed25519)
    sk, pk = ed25519.keygen(b"many-7")
    msg, sig, held = ev.item(sign_envelope(sk, pk, "c", "r", b"p",
                                           scheme=ed25519))
    assert isinstance(held, ed25519.PublicKey) and held == pk
    assert held.point == ed25519.decompress(pk)
    Ed25519PublicKey.from_public_bytes(held).verify(sig, msg)


# -- a cluster ---------------------------------------------------------------------


def cluster_config(i):
    return dataclasses.replace(
        fast_config(i),
        request_forward_timeout=60.0, request_complain_timeout=120.0,
        request_auto_remove_timeout=240.0, view_change_resend_interval=60.0,
        view_change_timeout=240.0, leader_heartbeat_timeout=120.0,
    )


def ledger_requests(app) -> list:
    return [raw for d in app.ledger() if d.proposal.payload
            for raw in decode(BatchPayload, d.proposal.payload).requests]


def test_ed25519_envelopes_commit_once_and_forged_ones_reach_no_ledger(
        tmp_path):
    """Four replicas whose votes and clients are Ed25519 (the engine the
    pure-Python host verifier: nothing of OpenSSL in the system's path):
    what the front door does with each envelope is what OpenSSL says of
    it; the honest ones are on all four ledgers exactly once, byte for
    byte, the forged ones on none; a control-plane request of the channel
    goes out signed with the channel's scheme."""

    async def run():
        ch = Channel(8, 3)
        scheduler, network, shared = Scheduler(), Network(seed=7), \
            SharedLedgers()
        coalescer = AsyncBatchCoalescer(HostVerifyEngine(scheme=ed25519),
                                        window=0.005, max_batch=4096,
                                        dedupe=True)
        rings = Keyring.generate([1, 2, 3, 4], seed=b"ed-envelopes",
                                 scheme=ed25519)
        apps = [App(i, network, shared, scheduler,
                    wal_dir=str(tmp_path / f"wal-{i}"),
                    config=cluster_config(i),
                    crypto=Ed25519CryptoProvider(rings[i],
                                                 coalescer=coalescer),
                    enrolled=ch.enrolled)
                for i in (1, 2, 3, 4)]
        for a in apps:
            await a.start()
        honest = [ch.honest(i, f"r{k}") for i in range(8) for k in (0, 1)]
        forged = [(ch.forged(i, f"f{i}", how), how)
                  for i, how in enumerate(FORGERIES)]
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
        await apps[0].submit("client-0", "ctl", b"c", signer=ch.ids[0])
        await wait_for(
            lambda: all(len(ledger_requests(a)) >= 17 for a in apps),
            scheduler, timeout=600.0)
        for a in apps:
            assert ledger_requests(a) == ledger_requests(apps[0])
            assert len(ledger_requests(a)) == 17
            assert set(honest) < set(ledger_requests(a))
        assert all(ch.accepts(raw) for raw in ledger_requests(apps[0]))
        assert apps[0].envelopes.rejected == {
            "malformed": 0, "not_enrolled": 1, "bad_signature": 5,
            "wrong_channel": 0}
        for a in apps[1:]:  # followers judged every block's envelopes
            assert a.envelopes.accepted == 17
        await stop_all(apps)

    asyncio.run(run())
