"""The plain reference of the envelope check: OpenSSL through the
``cryptography`` wheel, one envelope at a time, from the envelope's raw
bytes.  It imports nothing of the program: not the envelope module, not
the providers, the coalescer or the kernels.

An envelope is ``<signed part> u32(64) <creator X || Y> u32(64) <r || s>``
(all big-endian); a channel may order it only if its creator is enrolled
and the creator's ECDSA P-256 / SHA-256 signature over the signed part is
valid."""

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import (
    encode_dss_signature,
)

TRAILER = 4 + 64 + 4 + 64
U32_64 = (64).to_bytes(4, "big")


def openssl_accepts(raw: bytes, enrolled_creators: set) -> bool:
    """``enrolled_creators``: the 64-byte ``X || Y`` of every enrolled
    identity."""
    cut = len(raw) - TRAILER
    if cut < 0 or raw[cut:cut + 4] != U32_64 \
            or raw[cut + 68:cut + 72] != U32_64:
        return False
    creator, sig = raw[cut + 4:cut + 68], raw[cut + 72:]
    if creator not in enrolled_creators:
        return False
    try:
        key = ec.EllipticCurvePublicNumbers(
            int.from_bytes(creator[:32], "big"),
            int.from_bytes(creator[32:], "big"),
            ec.SECP256R1()).public_key()
        key.verify(
            encode_dss_signature(int.from_bytes(sig[:32], "big"),
                                 int.from_bytes(sig[32:], "big")),
            raw[:cut], ec.ECDSA(hashes.SHA256()))
        return True
    except (InvalidSignature, ValueError):
        return False


def openssl_accepts_vote(item) -> bool:
    """A vote's lane ``(message, r, s, (x, y))``, the same way."""
    msg, r, s, pub = item
    try:
        ec.EllipticCurvePublicNumbers(
            pub[0], pub[1], ec.SECP256R1()).public_key().verify(
            encode_dss_signature(r, s), msg, ec.ECDSA(hashes.SHA256()))
        return True
    except (InvalidSignature, ValueError):
        return False
