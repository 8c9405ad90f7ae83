"""The plain reference of several channels on one orderer host.

A straightforward model of what the host has to do, written from the
envelope's bytes with OpenSSL (the ``cryptography`` wheel) and the
standard library.  It imports nothing of the program: not the envelope
module, not the front door, the providers, the coalescer or the kernels.

An envelope is::

    u32 <client id>  u32 <request id>  u32 <payload>   u32(64) <creator X || Y>  u32(64) <r || s>
    |------------------- signed -------------------|

(all big-endian).  It NAMES a channel where its payload starts with
``\\x00tpubft.channel\\x00``, one length byte and the name.  The model:
for each channel the host serves, the ledger is the same sequence on all
its replicas and holds exactly the submitted envelopes that name it and
that OpenSSL accepts under a creator enrolled on THAT channel, each once;
nothing that names another channel or none.
"""

from collections import Counter

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import (
    encode_dss_signature,
)

TRAILER = 4 + 64 + 4 + 64
U32_64 = (64).to_bytes(4, "big")
MAGIC = b"\x00tpubft.channel\x00"


def parse(raw: bytes):
    """-> ``(key "client:request", channel name or None, signed bytes,
    creator, signature)``, or None unless ``raw`` is an envelope."""
    cut = len(raw) - TRAILER
    if cut < 12 or raw[cut:cut + 4] != U32_64 \
            or raw[cut + 68:cut + 72] != U32_64:
        return None
    fields, at = [], 0
    for _ in range(3):
        if at + 4 > cut:
            return None
        n = int.from_bytes(raw[at:at + 4], "big")
        if at + 4 + n > cut:
            return None
        fields.append(raw[at + 4:at + 4 + n])
        at += 4 + n
    if at != cut:
        return None
    client, request, payload = fields
    channel = None
    if payload.startswith(MAGIC):
        head = len(MAGIC)
        if len(payload) <= head or len(payload) < head + 1 + payload[head]:
            return None
        try:
            channel = payload[head + 1:head + 1 + payload[head]].decode()
        except UnicodeDecodeError:
            return None
    try:
        key = client.decode() + ":" + request.decode()
    except UnicodeDecodeError:
        return None
    return key, channel, raw[:cut], raw[cut + 4:cut + 68], raw[cut + 72:]


def openssl_accepts(signed: bytes, creator: bytes, sig: bytes) -> bool:
    try:
        ec.EllipticCurvePublicNumbers(
            int.from_bytes(creator[:32], "big"),
            int.from_bytes(creator[32:], "big"),
            ec.SECP256R1()).public_key().verify(
            encode_dss_signature(int.from_bytes(sig[:32], "big"),
                                 int.from_bytes(sig[32:], "big")),
            signed, ec.ECDSA(hashes.SHA256()))
        return True
    except (InvalidSignature, ValueError):
        return False


def may_order(raw: bytes, channel: str, enrolled_creators) -> bool:
    """May ``channel`` order this envelope?  It names the channel, its
    creator is enrolled there (``enrolled_creators``: the 64-byte
    ``X || Y`` of each), and OpenSSL accepts the creator's signature."""
    got = parse(raw)
    if got is None:
        return False
    _key, named, signed, creator, sig = got
    return named == channel and creator in enrolled_creators \
        and openssl_accepts(signed, creator, sig)


def expected_ledgers(submitted, enrolled: dict) -> dict:
    """``submitted``: every envelope handed to the host, honest or not;
    ``enrolled``: channel -> its enrolled creators.  -> channel -> the
    multiset (a Counter) of envelopes its ledger has to hold."""
    distinct = set(submitted)
    return {channel: Counter(raw for raw in distinct
                             if may_order(raw, channel, creators))
            for channel, creators in enrolled.items()}


def channel_faults(ledgers: dict, submitted, enrolled: dict) -> list:
    """``ledgers``: channel -> one list of raw envelopes, in ledger order,
    per replica.  -> why the host did not do what the model says, or []."""
    faults = []
    want = expected_ledgers(submitted, enrolled)
    if set(ledgers) != set(want):
        faults.append(f"the host has ledgers of {sorted(ledgers)}, the "
                      f"channels are {sorted(want)}")
    for channel in sorted(set(ledgers) & set(want)):
        replicas = ledgers[channel]
        for i, other in enumerate(replicas[1:], 2):
            if other != replicas[0]:
                faults.append(f"channel {channel}: replica {i}'s ledger is "
                              f"not replica 1's")
        got = Counter(replicas[0])
        twice = [raw for raw, c in got.items() if c > 1]
        if twice:
            faults.append(f"channel {channel}: {len(twice)} envelope(s) "
                          f"ordered more than once")
        foreign = [raw for raw in got
                   if (parse(raw) or (None, None))[1] != channel]
        if foreign:
            faults.append(f"channel {channel}: {len(foreign)} envelope(s) "
                          f"on its ledger name another channel or none")
        refused = [raw for raw in got
                   if raw not in foreign
                   and not may_order(raw, channel, enrolled[channel])]
        if refused:
            faults.append(f"channel {channel}: {len(refused)} envelope(s) "
                          f"on its ledger that OpenSSL refuses or whose "
                          f"creator is not enrolled there")
        missing = set(want[channel]) - set(got)
        if missing:
            faults.append(f"channel {channel}: {len(missing)} envelope(s) "
                          f"it had to order are not on its ledger")
    return faults
