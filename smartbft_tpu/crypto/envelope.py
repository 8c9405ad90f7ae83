"""Signed client envelopes: the request a Fabric-style channel orders.

An envelope is the embedder's request (client id, request id, payload in
the canonical codec) followed by a trailer: the creator's public key, as
the certificate in a Fabric envelope would carry it, and a 64-byte
signature over everything before the trailer, each after its length.
The channel's scheme fixes both: a P-256 creator is its point ``X || Y``
(64 bytes big-endian) and the signature ECDSA's ``r || s``; an Ed25519
creator is its 32-byte key and the signature RFC 8032's ``R || S``
(PureEdDSA over the signed bytes)::

    <client id> <request id> <payload>  u32(64) <creator>  u32(64) <r || s>
    <client id> <request id> <payload>  u32(32) <creator>  u32(64) <R || S>
    |------------ signed ------------|  |------------- trailer ----------|

An envelope may NAME the channel it is for: its payload then starts with
a channel header (:func:`channel_header`: a fixed magic, a length byte,
the channel's name), inside the signed part, so the creator's signature
covers it.  An envelope that names no channel has the bytes it always
had.  A replica of a named channel (``EnvelopeVerifier(channel=...)``)
refuses every envelope that does not name it (``wrong_channel``), where
it refuses a forged one: at the front door, at a forward, in a proposal.

Replicas hold the channel's ENROLLED identities — a set of public keys.
It stands for the MSP's cached certificate validation (a departure from
Fabric, which validates a certificate chain per creator): an envelope
whose creator is not in the set is refused before any device work.

:class:`EnvelopeVerifier` is the one implementation of "envelope ->
verify item, or reject as malformed / not enrolled" and of the verdict
that follows; both embedders (``testing.app.App``, ``net.launch.
ReplicaApp``) hold one when they are given enrolled identities, and
expose its two coroutines to the protocol core as ``verify_request_async``
/ ``verify_proposal_async``.  An App without enrolled identities holds
none and the core takes the path it always took.
"""

from __future__ import annotations

import struct
from typing import Awaitable, Callable, Iterable, Optional, Sequence

from ..codec import encode, wiremsg
from ..obs.recorder import standby
from . import ed25519, p256

__all__ = ["CHANNEL_MAGIC", "EnvelopeRejected", "EnvelopeVerifier", "TRAILER",
           "channel_header", "creator_bytes", "envelope_channel",
           "sign_envelope", "split_envelope"]

#: a creator's length in an envelope, by the channel's scheme
_KEY = {p256: 64, ed25519: 32}
_SIG = 64  # r || s, R || S
_LEN = struct.Struct(">I")
#: bytes after the signed part of a P-256 envelope: two length-prefixed
#: 64-byte fields
TRAILER = 2 * _LEN.size + _KEY[p256] + _SIG
_SIG_PREFIX = _LEN.pack(_SIG)

#: a payload that starts with these bytes names the envelope's channel:
#: the magic, one length byte, the channel's name (UTF-8, 1-255 bytes),
#: then the embedder's own payload
CHANNEL_MAGIC = b"\x00tpubft.channel\x00"

#: why an envelope was refused (the ``cause`` of :class:`EnvelopeRejected`,
#: the keys of :attr:`EnvelopeVerifier.rejected`, the ``req.rejected``
#: mark's ``cause``)
CAUSES = ("malformed", "not_enrolled", "bad_signature", "wrong_channel")


@wiremsg
class _Signed:
    """The signed part: an embedder's unsigned request has these bytes.
    (The verify path reads the trailer by offset and never decodes it.)"""

    client_id: str = ""
    request_id: str = ""
    payload: bytes = b""


class EnvelopeRejected(ValueError):
    """An envelope the channel refuses; ``cause`` is one of
    :data:`CAUSES`."""

    def __init__(self, cause: str, detail: str = ""):
        super().__init__(f"envelope rejected ({cause})"
                         + (f": {detail}" if detail else ""))
        self.cause = cause


def _key_len(scheme) -> int:
    try:
        return _KEY[scheme]
    except KeyError:
        raise ValueError(f"no client envelopes for scheme "
                         f"{getattr(scheme, '__name__', scheme)}") from None


def creator_bytes(pub, scheme=p256) -> bytes:
    """A public key as the bytes an envelope carries: a P-256 point's 64,
    an Ed25519 key's own 32."""
    if scheme is p256:
        return pub[0].to_bytes(32, "big") + pub[1].to_bytes(32, "big")
    _key_len(scheme)
    return bytes(pub)


def channel_header(channel: str) -> bytes:
    """The bytes that name ``channel`` at the start of a payload."""
    name = channel.encode()
    if not 0 < len(name) < 256:
        raise ValueError(f"a channel name is 1-255 bytes, got {channel!r}")
    return CHANNEL_MAGIC + bytes((len(name),)) + name


def envelope_channel(raw: bytes):
    """The channel ``raw`` names (an envelope, its signed part, or an
    unsigned request of the same layout), or None where it names none;
    read by offset, nothing decoded.  ``EnvelopeRejected("malformed")``
    where the bytes end inside a field or the header."""
    try:
        at = 4 + _LEN.unpack_from(raw, 0)[0]
        at += 4 + _LEN.unpack_from(raw, at)[0]
        size = _LEN.unpack_from(raw, at)[0]
        at += 4
        head = at + len(CHANNEL_MAGIC)
        if raw[at:head] != CHANNEL_MAGIC:
            return None
        end = head + 1 + raw[head]
        if end > at + size:
            raise IndexError
        return raw[head + 1:end].decode()
    except (struct.error, IndexError, UnicodeDecodeError):
        raise EnvelopeRejected("malformed", "fields or channel header cut "
                               "short") from None


def sign_envelope(private, public, client_id: str, request_id: str,
                  payload: bytes = b"", *, channel=None,
                  scheme=p256) -> bytes:
    """Build and sign one envelope with the scheme's native signer
    (``sign_raw``).  ``channel``: the channel it names, in a header
    before ``payload`` and under the signature; None names none, and the
    bytes are the ones an envelope always had."""
    if channel is not None:
        payload = channel_header(channel) + payload
    elif payload.startswith(CHANNEL_MAGIC):
        raise ValueError("the payload starts with the channel header's "
                         "magic: name the channel with channel=")
    signed = encode(_Signed(client_id=client_id, request_id=request_id,
                            payload=payload))
    creator = creator_bytes(public, scheme)
    return b"".join((signed, _LEN.pack(len(creator)), creator,
                     _SIG_PREFIX, scheme.sign_raw(private, signed)))


def split_envelope(raw: bytes, scheme=p256) -> tuple[bytes, bytes, bytes]:
    """-> (signed bytes, creator, signature); raises
    ``EnvelopeRejected("malformed")`` unless the scheme's trailer is
    there."""
    key = _key_len(scheme)
    cut = len(raw) - (2 * _LEN.size + key + _SIG)
    if (cut < 0 or raw[cut:cut + 4] != _LEN.pack(key)
            or raw[cut + 4 + key:cut + 8 + key] != _SIG_PREFIX):
        raise EnvelopeRejected("malformed", "no creator / signature trailer")
    return raw[:cut], raw[cut + 4:cut + 4 + key], raw[cut + 8 + key:]


class EnvelopeVerifier:
    """The enrolled identities of one channel and the check against them.

    ``enrolled``: the clients' public keys.  ``engine``: the verify engine
    behind the synchronous SPI methods (``Verifier.verify_request`` /
    ``verify_proposal``); ``submit``: the provider's awaited path into the
    shared coalescer (``CryptoProvider.verify_items_async``), behind the
    two coroutines the protocol core awaits.  Client keys are handed to
    the engine as they come, never registered with it: on a TPU they ride
    the arbitrary-key kernel (``JaxVerifyEngine.pin_ring``).  ``scheme``:
    the channel's, P-256 or Ed25519; an Ed25519 key is decoded here, once
    (``ed25519.PublicKey``), and its items carry the point to the
    engine."""

    def __init__(self, enrolled: Iterable, *, engine,
                 submit: Optional[Callable[[list], Awaitable[list]]] = None,
                 recorder=None, channel: Optional[str] = None,
                 scheme=p256):
        #: the channel these replicas order for: every envelope has to
        #: name it.  None: an unnamed channel, whose envelopes' payloads
        #: are not looked into
        self.channel = channel
        _key_len(scheme)
        self.scheme = scheme
        hold = ed25519.PublicKey if scheme is ed25519 else (lambda pub: pub)
        self._pub_of = {creator_bytes(pub, scheme): hold(pub)
                        for pub in enrolled}
        if not self._pub_of:
            raise ValueError("an EnvelopeVerifier needs enrolled identities")
        self.engine = engine
        self._submit = submit
        self.recorder = standby(recorder)
        #: always on: envelopes refused, by cause
        self.rejected = dict.fromkeys(CAUSES, 0)
        #: envelopes judged valid (front door, forwards and proposals)
        self.accepted = 0

    # -- envelope -> item -------------------------------------------------------

    def body(self, raw: bytes) -> bytes:
        """The signed part (what the embedder decodes as its request)."""
        try:
            return split_envelope(raw, self.scheme)[0]
        except EnvelopeRejected as e:
            self._note(e.cause)
            raise

    def item(self, raw: bytes) -> tuple:
        """The verify item of one envelope, or ``EnvelopeRejected``
        (``malformed`` / ``wrong_channel`` / ``not_enrolled``), counted."""
        try:
            signed, creator, signature = split_envelope(raw, self.scheme)
            if self.channel is not None:
                named = envelope_channel(signed)
                if named != self.channel:
                    raise EnvelopeRejected(
                        "wrong_channel",
                        f"names {named!r}, this is {self.channel!r}")
            pub = self._pub_of.get(creator)
            if pub is None:
                raise EnvelopeRejected("not_enrolled",
                                       f"creator {creator[:8].hex()}..")
        except EnvelopeRejected as e:
            self._note(e.cause)
            raise
        return self.scheme.make_item(signed, signature, pub)

    def items(self, raws: Sequence[bytes]) -> list:
        rec = self.recorder
        span = rec.begin("request.pack") if rec.enabled else None
        try:
            return [self.item(raw) for raw in raws]
        finally:
            if span is not None:
                rec.end(span)

    def _note(self, cause: str) -> None:
        self.rejected[cause] += 1
        rec = self.recorder
        if rec.enabled:
            rec.record("req.rejected", extra={"cause": cause})

    def _judge(self, mask: Sequence) -> None:
        bad = sum(1 for ok in mask if not ok)
        self.accepted += len(mask) - bad
        if bad:
            for _ in range(bad):
                self._note("bad_signature")
            raise EnvelopeRejected(
                "bad_signature", f"{bad} of {len(mask)} envelope(s)")

    # -- the verdict ------------------------------------------------------------

    def check(self, raws: Sequence[bytes]) -> None:
        """Synchronous: every envelope of ``raws`` is well formed, enrolled
        and validly signed, or ``EnvelopeRejected``."""
        if raws:
            self._judge(self.engine.verify(self.items(raws)))

    async def check_async(self, raws: Sequence[bytes]) -> None:
        """The same through the shared coalescer, as ONE submission."""
        if raws:
            self._judge(await self._submit(self.items(raws)))
