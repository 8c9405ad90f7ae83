"""Ed25519 (RFC 8032): batched TPU verification, host-side signing.

Same design as :mod:`p256` — the alt-curve Signer/Verifier variant of
BASELINE.md configs[3].  The reference delegates signatures to the embedding
application (/root/reference/pkg/api/dependencies.go:47-71) and verifies one
commit vote per goroutine (/root/reference/internal/bft/view.go:537-541);
here a whole quorum of EdDSA votes is ONE jitted kernel launch:

* Field/scalar arithmetic: :mod:`bignum` Montgomery contexts for
  p = 2^255-19 and the group order L.
* Curve arithmetic: extended twisted-Edwards coordinates (X:Y:Z:T) with the
  unified a=-1 addition formula (Hisil-Wong-Carter-Dawson 2008,
  "add-2008-hwcd-3").  Because -1 is a square mod p and d is non-square,
  the formula is complete: one branch-free straight-line block covers
  addition, doubling, and the identity — ideal for XLA.
* Verification equation (cofactorless, as in Go's crypto/ed25519):
  [S]B == R + [h]A, evaluated as [S]B + [h](-A) == R with 2-bit-windowed
  Strauss-Shamir interleaving: a single ``lax.scan`` over 127 digit pairs —
  two doublings, one gather from the 16-entry joint table {iB + j(-A)}
  (the B multiples are host-precomputed constants), one unified addition
  per digit.

Hashing (SHA-512) and point decompression are host-side marshalling —
exactly like SHA-256 digesting in the P-256 path; the kernel re-checks both
points against the curve equation so a bad decompression can never validate.
"""

from __future__ import annotations

import functools
import hashlib
import secrets

import numpy as np

import jax.numpy as jnp


from . import bignum as bn
from .bignum import MontCtx

# --- curve constants (RFC 8032 §5.1) ---------------------------------------

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = (-121665 * pow(121666, -1, P)) % P
BY = (4 * pow(5, -1, P)) % P
BX = 15112221349535400772501151409588531511454012693041857206046113283949847762202
SQRT_M1 = pow(2, (P - 1) // 4, P)  # sqrt(-1) mod p

NLIMBS = 16
FP = MontCtx(P, NLIMBS)
FL = MontCtx(L, NLIMBS)

_D_MONT = FP.encode(D)
_D2_MONT = FP.encode((2 * D) % P)


def _aff_add(p1, p2):
    """Host affine Edwards addition (for the fixed-base table constants)."""
    x1, y1 = p1
    x2, y2 = p2
    den = D * x1 * x2 * y1 * y2 % P
    return ((x1 * y2 + x2 * y1) * pow(1 + den, -1, P) % P,
            (y1 * y2 + x1 * x2) * pow(1 - den, -1, P) % P)


def _ext_mont(x: int, y: int) -> np.ndarray:
    """Host affine ints -> extended (X:Y:1:XY) Montgomery limb stack."""
    return np.stack([FP.encode(x), FP.encode(y), FP.one_mont,
                     FP.encode(x * y % P)])


_B2_AFF = _aff_add((BX, BY), (BX, BY))
_B3_AFF = _aff_add(_B2_AFF, (BX, BY))
_B_MONT = _ext_mont(BX, BY)
_B2_MONT = _ext_mont(*_B2_AFF)
_B3_MONT = _ext_mont(*_B3_AFF)
# identity in extended coordinates: (0 : 1 : 1 : 0)
_ID_MONT = np.stack([FP.zero, FP.one_mont, FP.one_mont, FP.zero])


# ---------------------------------------------------------------------------
# extended twisted-Edwards ops (points are (..., 4, NLIMBS) Mont arrays)
# ---------------------------------------------------------------------------

def point_add(p, q):
    """Unified addition, add-2008-hwcd-3 (a = -1).  Complete on this curve.

    8 field mults + 1 mult by the 2d constant — level-scheduled: the
    independent ops of each dataflow level stack into single grouped
    Montgomery calls (3 mul groups + 4 add/sub groups of sequential
    depth; see :func:`bignum.grouped`).
    """
    f = FP
    x1, y1, z1, t1 = (p[..., i, :] for i in range(4))
    x2, y2, z2, t2 = (q[..., i, :] for i in range(4))

    s1, s2 = bn.grouped(f.sub, [(y1, x1), (y2, x2)])
    a1, a2, z1d = bn.grouped(f.add, [(y1, x1), (y2, x2), (z1, z1)])
    a, b, c1, d = bn.grouped(
        f.mul,
        [(s1, s2), (a1, a2), (t1, jnp.asarray(_D2_MONT)), (z1d, z2)],
    )
    c = f.mul(c1, t2)
    e, ff = bn.grouped(f.sub, [(b, a), (d, c)])
    g, h = bn.grouped(f.add, [(d, c), (b, a)])
    x3, y3, t3, z3 = bn.grouped(
        f.mul, [(e, ff), (g, h), (e, h), (ff, g)]
    )
    return jnp.stack([x3, y3, z3, t3], axis=-2)


def point_double(p):
    """Dedicated doubling, dbl-2008-hwcd with both output halves negated
    (a = -1).  Complete on this curve — the identity doubles to itself.

    4M + 4S versus the unified addition's 8M + 1mb, with the squarings in
    ONE grouped :func:`bignum.square_columns` call: with E = (X+Y)^2-A-B,
    G = B-A, F = 2Z^2-G, H = A+B it returns (EF : GH : FG : EH), which is
    the EFD formula's output scaled by -1 — the same projective point.
    The T1 input is unused (doubling never needs the extended coordinate).
    """
    f = FP
    x, y, z = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    xy = f.add(x, y)
    a, b, zz, s = bn.grouped1(f.square, [x, y, z, xy])
    c, h = bn.grouped(f.add, [(zz, zz), (a, b)])
    g, e1 = bn.grouped(f.sub, [(b, a), (s, a)])
    e = f.sub(e1, b)
    ff = f.sub(c, g)
    x3, y3, z3, t3 = bn.grouped(
        f.mul, [(e, ff), (g, h), (ff, g), (e, h)]
    )
    return jnp.stack([x3, y3, z3, t3], axis=-2)


def point_neg(p):
    """-(X:Y:Z:T) = (-X:Y:Z:-T)."""
    return jnp.stack([
        FP.neg(p[..., 0, :]), p[..., 1, :], p[..., 2, :], FP.neg(p[..., 3, :])
    ], axis=-2)


def is_on_curve(xm, ym):
    """-x^2 + y^2 == 1 + d*x^2*y^2 in Mont domain; (...,) uint32 mask."""
    f = FP
    xx = f.mul(xm, xm)
    yy = f.mul(ym, ym)
    lhs = f.sub(yy, xx)
    one = jnp.broadcast_to(jnp.asarray(FP.one_mont), xx.shape)
    rhs = f.add(one, f.mul(jnp.asarray(_D_MONT), f.mul(xx, yy)))
    return bn.eq(lhs, rhs)


def _extended(xm, ym):
    """Affine Mont coords -> extended (X:Y:1:XY)."""
    one = jnp.broadcast_to(jnp.asarray(FP.one_mont), xm.shape)
    return jnp.stack([xm, ym, one, FP.mul(xm, ym)], axis=-2)


def shamir_double_scalar(s, h, nega):
    """[s]B + [h]*nega, 2-bit-windowed Shamir: 127 digits x (2 dbl + 1 add).

    s/h: (..., NLIMBS) standard-domain scalars (< 2^253 < 2^254); nega:
    (..., 4, NLIMBS) Mont domain.  B is fixed, so its window multiples are
    host-precomputed constants; the -A multiples build in two point_add
    depths and the 16 combination adds share ONE grouped call.
    """
    ident = jnp.broadcast_to(jnp.asarray(_ID_MONT), nega.shape)
    bs = [ident] + [
        jnp.broadcast_to(jnp.asarray(c), nega.shape)
        for c in (_B_MONT, _B2_MONT, _B3_MONT)
    ]
    na2 = point_add(nega, nega)
    na3 = point_add(na2, nega)
    table = bn.joint_table(
        point_add, bs, [ident, nega, na2, na3]
    )  # (..., 16, 4, n); entry 4i+j = iB + j*nega
    return bn.shamir_scan_w(
        point_add, table, ident,
        bn.digits_msb(s, 127, 2), bn.digits_msb(h, 127, 2), width=2,
        point_double=point_double,
    )


def eddsa_verify_kernel(s, h, rx, ry, ax, ay, ok_in):
    """Batched Ed25519 verification.  Pure, jittable.

    Inputs are (..., NLIMBS) uint32 limb vectors in the *standard* domain:
    ``s`` the signature scalar, ``h`` = SHA-512(R || A || M) mod L (host
    hashing, like the P-256 path's SHA-256), (rx, ry) and (ax, ay) the
    decompressed signature/public points, plus ``ok_in`` — a (...,) uint32
    host flag, 0 where decoding/decompression already failed (those lanes
    carry identity coordinates).  Returns a (...,) uint32 validity mask;
    invalid signatures yield 0, never an exception.
    """
    l_arr = jnp.asarray(FL.N)
    s_ok = jnp.uint32(1) - bn.geq(s, l_arr)  # RFC 8032: 0 <= s < L

    rxm, rym = FP.to_mont(rx), FP.to_mont(ry)
    axm, aym = FP.to_mont(ax), FP.to_mont(ay)
    oncurve = is_on_curve(rxm, rym) * is_on_curve(axm, aym)

    nega = point_neg(_extended(axm, aym))
    acc = shamir_double_scalar(s, h, nega)
    # [s]B - [h]A, extended coords; Z != 0 by completeness

    xz = acc[..., 0, :]
    yz = acc[..., 1, :]
    z = acc[..., 2, :]
    match = bn.eq(FP.mul(rxm, z), xz) * bn.eq(FP.mul(rym, z), yz)
    return match * s_ok * oncurve * ok_in


# ---------------------------------------------------------------------------
# host-side reference arithmetic (Python ints) — keygen, sign, CPU verify
# ---------------------------------------------------------------------------

# affine Edwards addition over GF(P); (0, 1) is the identity
_edwards_add_int = _aff_add


def scalar_mult_int(k: int, point):
    """Double-and-add with Python ints (host-side; keygen/sign only)."""
    acc = (0, 1)
    addend = point
    while k:
        if k & 1:
            acc = _edwards_add_int(acc, addend)
        addend = _edwards_add_int(addend, addend)
        k >>= 1
    return acc


def compress(point) -> bytes:
    x, y = point
    return (y | ((x & 1) << 255)).to_bytes(32, "little")


def decompress(data: bytes):
    """32-byte encoding -> affine point, or None if invalid (RFC 8032 §5.1.3).

    The sqrt mod p runs in the native C++ helper when available (~30 us vs
    ~150 us as a Python pow) — every Ed25519 verification decompresses R,
    making this the host-prep hot spot of the batch path."""
    if len(data) != 32:
        return None
    from .. import native

    if native.ed_available():
        return native.ed_decompress(data)
    val = int.from_bytes(data, "little")
    sign = val >> 255
    y = val & ((1 << 255) - 1)
    if y >= P:
        return None
    yy = y * y % P
    u = (yy - 1) % P
    v = (D * yy + 1) % P
    # candidate root of u/v: (u*v^3) * (u*v^7)^((p-5)/8)
    x = u * pow(v, 3, P) * pow(u * pow(v, 7, P) % P, (P - 5) // 8, P) % P
    if v * x * x % P != u:
        x = x * SQRT_M1 % P
        if v * x * x % P != u:
            return None
    if x == 0 and sign:
        return None
    if x & 1 != sign:
        x = P - x
    return (x, y)


def _clamp(raw: bytes) -> int:
    a = bytearray(raw)
    a[0] &= 248
    a[31] &= 127
    a[31] |= 64
    return int.from_bytes(bytes(a), "little")


def keygen(seed: bytes | None = None):
    """Returns (private_key_bytes, public_key_bytes).  Deterministic w/ seed."""
    if seed is None:
        priv = secrets.token_bytes(32)
    else:
        priv = hashlib.sha256(b"ed25519-keygen" + seed).digest()
    if _sign_native is not None:  # the same key, ~20 ms sooner
        return priv, _public_native(priv)
    h = hashlib.sha512(priv).digest()
    a = _clamp(h[:32])
    return priv, compress(scalar_mult_int(a, (BX, BY)))


@functools.lru_cache(maxsize=256)
def _expand_key(priv: bytes) -> tuple[int, bytes, bytes]:
    """(clamped scalar, prefix, public key) — fixed per private key, so cache
    it instead of re-deriving A with a full scalar mult on every sign()."""
    h = hashlib.sha512(priv).digest()
    a = _clamp(h[:32])
    return a, h[32:], compress(scalar_mult_int(a, (BX, BY)))


def sign(priv: bytes, msg: bytes) -> bytes:
    """RFC 8032 §5.1.6 deterministic signature; returns 64 bytes R || S."""
    a, prefix, pub = _expand_key(priv)
    r = int.from_bytes(hashlib.sha512(prefix + msg).digest(), "little") % L
    r_enc = compress(scalar_mult_int(r, (BX, BY)))
    k = int.from_bytes(
        hashlib.sha512(r_enc + pub + msg).digest(), "little"
    ) % L
    s = (r + k * a) % L
    return r_enc + s.to_bytes(32, "little")


def verify_int(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """Pure-Python Ed25519 verify — CPU reference / baseline engine path."""
    if len(sig) != 64:
        return False
    a_pt = decompress(pub)
    r_pt = decompress(sig[:32])
    if a_pt is None or r_pt is None:
        return False
    s = int.from_bytes(sig[32:], "little")
    if s >= L:
        return False
    k = int.from_bytes(hashlib.sha512(sig[:32] + pub + msg).digest(), "little") % L
    lhs = scalar_mult_int(s, (BX, BY))
    rhs = _edwards_add_int(r_pt, scalar_mult_int(k, a_pt))
    return lhs == rhs


# ---------------------------------------------------------------------------
# host <-> kernel marshalling (scheme API used by the verify engines)
# ---------------------------------------------------------------------------

try:  # native signing fast path (RFC 8032 is deterministic, so OpenSSL
    # produces byte-identical signatures to the pure-Python sign(); the
    # pure path costs ~180 ms per signature on this host, OpenSSL ~50 us)
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey as _CgEd25519,
    )

    import functools as _ft

    @_ft.lru_cache(maxsize=4096)  # bounded; holds a channel's signing clients
    def _cg_key(priv: bytes):
        return _CgEd25519.from_private_bytes(priv)

    def _sign_native(priv: bytes, msg: bytes) -> bytes:
        return _cg_key(priv).sign(msg)

    def _public_native(priv: bytes) -> bytes:
        return _cg_key(priv).public_key().public_bytes_raw()
except Exception as _exc:  # pragma: no cover — wheel absent/broken
    import logging as _logging

    _logging.getLogger("smartbft_tpu.crypto").warning(
        "native Ed25519 signer unavailable (%s); falling back to the "
        "pure-Python signer", _exc,
    )
    _sign_native = None


def sign_raw(priv: bytes, msg: bytes) -> bytes:
    if _sign_native is not None:
        return _sign_native(priv, msg)
    return sign(priv, msg)


def make_item(msg: bytes, sig: bytes, pub: bytes):
    return (msg, sig, pub)


def verify_item(item) -> bool:
    msg, sig, pub = item
    return verify_int(pub, msg, sig)


@functools.lru_cache(maxsize=1024)
def _decompress_pub(pub: bytes):
    """Signer pubkeys come from the small static membership set; memoize the
    sqrt-heavy decompression so the batched hot path pays it once per key.
    R decompression stays uncached — unique per signature."""
    if isinstance(pub, PublicKey):
        return pub.point
    return decompress(pub)


class PublicKey(bytes):
    """A public key's 32 bytes with its point decoded once, where it is
    enrolled.  Equal to, and hashed as, its bytes, so it stands wherever a
    key does (an item's last field, a ring, the SHA-512 input); the verify
    paths read ``point``, and ``neg_limbs`` (-A as (32,) uint32 16-bit
    limbs, x then y), instead of decoding the key for every lane, however
    many identities there are.  Both are None for bytes that are no key."""

    def __new__(cls, pub: bytes):
        self = super().__new__(cls, pub)
        pt = self.point = decompress(bytes(pub)) if len(pub) == 32 else None
        self.neg_limbs = None if pt is None else np.concatenate(
            [bn.to_limbs((P - pt[0]) % P, NLIMBS), bn.to_limbs(pt[1], NLIMBS)])
        return self


def verify_inputs(items) -> tuple[np.ndarray, ...]:
    """[(msg, sig64, pub32), ...] -> stacked (B, 16)x6 + (B,) kernel inputs."""
    n = len(items)
    s = np.zeros((n, NLIMBS), np.uint32)
    h = np.zeros((n, NLIMBS), np.uint32)
    rx = np.zeros((n, NLIMBS), np.uint32)
    ry = np.zeros((n, NLIMBS), np.uint32)
    ry[:, 0] = 1  # identity placeholder for invalid lanes
    ax = np.zeros((n, NLIMBS), np.uint32)
    ay = np.zeros((n, NLIMBS), np.uint32)
    ay[:, 0] = 1
    ok = np.zeros((n,), np.uint32)
    for i, (msg, sig, pub) in enumerate(items):
        if len(sig) != 64:
            continue
        r_pt = decompress(sig[:32])
        a_pt = _decompress_pub(pub)
        if r_pt is None or a_pt is None:
            continue
        s[i] = bn.to_limbs(int.from_bytes(sig[32:], "little") % (1 << 256), NLIMBS)
        k = int.from_bytes(
            hashlib.sha512(sig[:32] + pub + msg).digest(), "little"
        ) % L
        h[i] = bn.to_limbs(k, NLIMBS)
        rx[i], ry[i] = bn.to_limbs(r_pt[0], NLIMBS), bn.to_limbs(r_pt[1], NLIMBS)
        ax[i], ay[i] = bn.to_limbs(a_pt[0], NLIMBS), bn.to_limbs(a_pt[1], NLIMBS)
        ok[i] = 1
    return s, h, rx, ry, ax, ay, ok


verify_kernel = eddsa_verify_kernel
