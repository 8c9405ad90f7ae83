"""NIST P-256 ECDSA: batched TPU verification, host-side signing.

The verify kernel replaces the reference's per-signature goroutine fan-out
(/root/reference/internal/bft/view.go:519-551 spawns one goroutine per
commit vote, each doing one ``crypto/ecdsa`` verify).  Here a whole quorum
— across commits, replicas, and in-flight sequences — is verified as ONE
jitted call:

* Field/scalar arithmetic: :mod:`bignum` Montgomery contexts for p and n.
* Curve arithmetic: Renes–Costello–Batina 2015 complete addition formulas
  (Algorithm 4, a = -3) in homogeneous projective coordinates — branch-free
  and identity-safe, exactly what XLA wants: one straight-line formula for
  add, double, and infinity alike.
* Double-scalar multiplication u1*G + u2*Q: 2-bit-windowed Strauss–Shamir
  as a single ``lax.scan`` over 128 digit pairs — two doublings, one gather
  from the 16-entry joint table {i*G + j*Q}, one complete addition per
  digit.  No data-dependent control flow anywhere.

Signing stays on the host (one signature per decision — never a hot path)
with RFC 6979 deterministic nonces.
"""

from __future__ import annotations

import hashlib
import hmac
import os as _os
import secrets

import numpy as np

import jax.numpy as jnp


from . import bignum as bn
from .bignum import MontCtx

# --- curve constants (FIPS 186-4, D.1.2.3) ---------------------------------

P = 0xFFFFFFFF00000001000000000000000000000000FFFFFFFFFFFFFFFFFFFFFFFF
N = 0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551
B = 0x5AC635D8AA3A93E7B3EBBD55769886BC651D06B0CC53B0F63BCE3C3E27D2604B
GX = 0x6B17D1F2E12C4247F8BCE6E563A440F277037D812DEB33A0F4A13945D898C296
GY = 0x4FE342E2FE1A7F9B8EE7EB4A7C0F9E162BCE33576B315ECECBB6406837BF51F5

NLIMBS = 16
FP = MontCtx(P, NLIMBS)
FN = MontCtx(N, NLIMBS)

_B_MONT = FP.encode(B)
_G_MONT = np.stack([FP.encode(GX), FP.encode(GY), FP.one_mont])
_INF_MONT = np.stack([FP.zero, FP.one_mont, FP.zero])


# ---------------------------------------------------------------------------
# projective curve ops (points are (..., 3, NLIMBS) Montgomery-domain arrays)
# ---------------------------------------------------------------------------

def point_add(p, q):
    """Complete addition, RCB15 Algorithm 4 (a = -3).

    Valid for every input pair: distinct points, doubling, and the identity
    (0 : 1 : 0).  12 field mults + 2 mults by b + 29 add/subs — but
    level-scheduled: independent ops stack into single grouped Montgomery
    calls (4 mul groups + 11 add/sub groups of sequential depth), ~3x
    fewer carry chains than executing the algorithm's 43 ops in sequence.
    The math is the original sequence SSA-renamed; nothing is reordered
    across a data dependency.
    """
    f = FP
    b_m = jnp.asarray(_B_MONT)
    x1, y1, z1 = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    x2, y2, z2 = q[..., 0, :], q[..., 1, :], q[..., 2, :]

    # L1: cross-term preadds
    a1, a2, a4, a5, a7, a8 = bn.grouped(
        f.add, [(x1, y1), (x2, y2), (y1, z1), (y2, z2), (x1, z1), (x2, z2)]
    )
    # L2: all six products of the inputs
    t0, t1, t2, m1, m2, m3 = bn.grouped(
        f.mul, [(x1, x2), (y1, y2), (z1, z2), (a1, a2), (a4, a5), (a7, a8)]
    )
    # L3: pair sums + first doublings
    a3, a6, a9, u1, w1 = bn.grouped(
        f.add, [(t0, t1), (t1, t2), (t0, t2), (t2, t2), (t0, t0)]
    )
    # L4: Karatsuba recoveries
    t3, t4, y3a = bn.grouped(f.sub, [(m1, a3), (m2, a6), (m3, a9)])
    u2, w2 = bn.grouped(f.add, [(u1, t2), (w1, t0)])  # 3*t2, 3*t0
    # L5: the two b-multiples
    zb, yb = bn.grouped(f.mul, [(b_m, t2), (b_m, y3a)])
    # L6
    x3a, t0b, y3b = bn.grouped(f.sub, [(y3a, zb), (w2, u2), (yb, u2)])
    # L7
    z3a = f.add(x3a, x3a)
    y3c = f.sub(y3b, t0)
    # L8
    x3b, v1 = bn.grouped(f.add, [(x3a, z3a), (y3c, y3c)])
    # L9
    x3c, y3d = bn.grouped(f.add, [(t1, x3b), (v1, y3c)])
    z3b = f.sub(t1, x3b)
    # L10: all six closing products
    p1, p2, p3, p4, p5, p6 = bn.grouped(
        f.mul,
        [(t4, y3d), (t0b, y3d), (x3c, z3b), (t3, x3c), (t4, z3b), (t3, t0b)],
    )
    # L11
    y3, z3 = bn.grouped(f.add, [(p3, p2), (p5, p6)])
    x3 = f.sub(p4, p1)
    return jnp.stack([x3, y3, z3], axis=-2)


def point_double(p):
    """Complete doubling, RCB15 Algorithm 6 (a = -3).

    Valid for every input, including the identity.  8M + 3S + 2 mults by b
    versus the general addition's 12M + 2mb — and the squarings go through
    :func:`bignum.square_columns` at ~half the lane-mult cost.  Level-
    scheduled like :func:`point_add`: 4 mul groups + 8 add/sub groups.
    """
    f = FP
    b_m = jnp.asarray(_B_MONT)
    x, y, z = p[..., 0, :], p[..., 1, :], p[..., 2, :]

    t0, t1, t2 = bn.grouped1(f.square, [x, y, z])
    xy, xz, yz = bn.grouped(f.mul, [(x, y), (x, z), (y, z)])
    # doublings + first steps of the 3x chains
    t3, z3a, yz2, t2a, t0a = bn.grouped(
        f.add, [(xy, xy), (xz, xz), (yz, yz), (t2, t2), (t0, t0)]
    )
    t2_3, t0_3 = bn.grouped(f.add, [(t2a, t2), (t0a, t0)])
    bt2, bz3 = bn.grouped(f.mul, [(b_m, t2), (b_m, z3a)])
    y3a, z3b, t0d = bn.grouped(
        f.sub, [(bt2, z3a), (bz3, t2_3), (t0_3, t2_3)]
    )
    y3a2 = f.add(y3a, y3a)
    z3c = f.sub(z3b, t0)
    y3b, z3c2 = bn.grouped(f.add, [(y3a2, y3a), (z3c, z3c)])
    z3d, y3c = bn.grouped(f.add, [(z3c2, z3c), (t1, y3b)])
    x3a = f.sub(t1, y3b)
    y3d, x3b, t0b, zz, zt = bn.grouped(
        f.mul,
        [(x3a, y3c), (x3a, t3), (t0d, z3d), (yz2, z3d), (yz2, t1)],
    )
    y3, zt2 = bn.grouped(f.add, [(y3d, t0b), (zt, zt)])
    x3 = f.sub(x3b, zz)
    z3 = f.add(zt2, zt2)
    return jnp.stack([x3, y3, z3], axis=-2)


def is_on_curve(xm, ym):
    """y^2 == x^3 - 3x + b in Montgomery domain; (...,) uint32 mask."""
    f = FP
    lhs = f.mul(ym, ym)
    x3 = f.mul(f.mul(xm, xm), xm)
    threex = f.add(f.add(xm, xm), xm)
    rhs = f.add(f.sub(x3, threex), jnp.asarray(_B_MONT))
    return bn.eq(lhs, rhs)


def shamir_double_scalar(u1, u2, q):
    """u1*G + u2*Q, 2-bit-windowed Shamir: 128 digits x (2 dbl + 1 add).

    u1/u2: (..., NLIMBS) standard-domain scalars; q: (..., 3, NLIMBS) Mont
    domain.  The 16-entry joint table {i*G + j*Q} builds in three stacked
    point_add depths (the 16 combination adds share ONE grouped call).
    """
    g = jnp.broadcast_to(jnp.asarray(_G_MONT), q.shape)
    inf = jnp.broadcast_to(jnp.asarray(_INF_MONT), q.shape)
    two = point_add(jnp.stack([g, q]), jnp.stack([g, q]))
    three = point_add(two, jnp.stack([g, q]))
    table = bn.joint_table(
        point_add, [inf, g, two[0], three[0]], [inf, q, two[1], three[1]]
    )  # (..., 16, 3, n); entry 4i+j = i*G + j*Q
    return bn.shamir_scan_w(
        point_add, table, inf,
        bn.digits_msb(u1, 128, 2), bn.digits_msb(u2, 128, 2), width=2,
        point_double=point_double,
    )


def ecdsa_verify_kernel(e, r, s, qx, qy):
    """Batched ECDSA-P256 verification.  Pure, jittable.

    All inputs are (..., NLIMBS) uint32 limb vectors in the *standard*
    domain: e = 256-bit truncated message hash, (r, s) the signature,
    (qx, qy) the signer's affine public key.  Returns a (...,) uint32
    validity mask.  Invalid signatures yield 0 — never an exception — so a
    whole quorum batch survives one bad vote (the protocol layer maps the
    mask back to per-replica verdicts).
    """
    n_arr = jnp.asarray(FN.N)

    # 1 <= r, s < n
    r_ok = (jnp.uint32(1) - bn.is_zero(r)) * (jnp.uint32(1) - bn.geq(r, n_arr))
    s_ok = (jnp.uint32(1) - bn.is_zero(s)) * (jnp.uint32(1) - bn.geq(s, n_arr))

    # scalars: u1 = e/s, u2 = r/s (mod n)
    e_red = FN.reduce_once(e)  # e < 2^256 < 2n
    w = FN.inv(FN.to_mont(s))
    u1 = FN.from_mont(FN.mul(FN.to_mont(e_red), w))
    u2 = FN.from_mont(FN.mul(FN.to_mont(r), w))

    # curve: R = u1*G + u2*Q
    xm, ym = FP.to_mont(qx), FP.to_mont(qy)
    oncurve = is_on_curve(xm, ym)
    qpt = jnp.stack([xm, ym, jnp.broadcast_to(jnp.asarray(FP.one_mont), xm.shape)],
                    axis=-2)
    acc = shamir_double_scalar(u1, u2, qpt)

    xr, zr = acc[..., 0, :], acc[..., 2, :]
    not_inf = jnp.uint32(1) - bn.is_zero(zr)
    # Projective comparison — no field inversion of zr.  With x_aff =
    # xr/zr < p and p < 2n, "x_aff mod n == r" is exactly
    # x_aff ∈ {r, r+n} ∩ [0, p), and each candidate c tests as
    # c~ * zr == xr in the Montgomery domain (zr != 0 is masked above).
    # This replaces a 256-bit Fermat inversion with four multiplies.
    c = bn.add_raw(r, n_arr, NLIMBS + 1)
    c_in_range = (c[..., NLIMBS] == 0).astype(jnp.uint32)
    c16 = c[..., :NLIMBS]
    _, c_borrow = bn.sub_borrow(c16, jnp.asarray(FP.N))
    c_ok = c_in_range * c_borrow  # r + n < p
    r2 = jnp.asarray(FP.R2)
    r_m, c_m = bn.grouped(FP.mul, [(r, r2), (c16, r2)])
    m_r, m_c = bn.grouped(FP.mul, [(r_m, zr), (c_m, zr)])
    match = jnp.maximum(bn.eq(m_r, xr), c_ok * bn.eq(m_c, xr))
    return match * not_inf * r_ok * s_ok * oncurve


# ---------------------------------------------------------------------------
# host-side reference arithmetic (Python ints) — keygen, sign, CPU verify
# ---------------------------------------------------------------------------

def _inv_mod(a: int, m: int) -> int:
    return pow(a, -1, m)


def _point_add_int(p1, p2):
    """Affine addition over GF(P); None is the identity."""
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    x1, y1 = p1
    x2, y2 = p2
    if x1 == x2 and (y1 + y2) % P == 0:
        return None
    if p1 == p2:
        lam = (3 * x1 * x1 - 3) * _inv_mod(2 * y1, P) % P
    else:
        lam = (y2 - y1) * _inv_mod(x2 - x1, P) % P
    x3 = (lam * lam - x1 - x2) % P
    y3 = (lam * (x1 - x3) - y1) % P
    return (x3, y3)


def scalar_mult_int(k: int, point):
    """Double-and-add with Python ints (host-side; keygen/sign only)."""
    acc = None
    addend = point
    while k:
        if k & 1:
            acc = _point_add_int(acc, addend)
        addend = _point_add_int(addend, addend)
        k >>= 1
    return acc


def keygen(seed: bytes | None = None):
    """Returns (private_scalar, (qx, qy)).  Deterministic given a seed."""
    if seed is None:
        d = secrets.randbelow(N - 1) + 1
    else:
        d = (int.from_bytes(hashlib.sha256(b"p256-keygen" + seed).digest(), "big")
             % (N - 1)) + 1
    if _sign_native is not None:
        # d*G by OpenSSL: ~50 us against ~9 ms of Python integers, which
        # a channel of a thousand enrolled clients pays a thousand times
        nums = _cg_key(d).public_key().public_numbers()
        return d, (nums.x, nums.y)
    return d, scalar_mult_int(d, (GX, GY))


def _rfc6979_nonce(priv: int, h1: bytes) -> int:
    """Deterministic nonce, RFC 6979 §3.2 with HMAC-SHA256."""
    holen = 32
    bx = priv.to_bytes(32, "big") + (
        int.from_bytes(h1, "big") % N
    ).to_bytes(32, "big")
    v = b"\x01" * holen
    k = b"\x00" * holen
    k = hmac.new(k, v + b"\x00" + bx, hashlib.sha256).digest()
    v = hmac.new(k, v, hashlib.sha256).digest()
    k = hmac.new(k, v + b"\x01" + bx, hashlib.sha256).digest()
    v = hmac.new(k, v, hashlib.sha256).digest()
    while True:
        v = hmac.new(k, v, hashlib.sha256).digest()
        cand = int.from_bytes(v, "big")
        if 1 <= cand < N:
            return cand
        k = hmac.new(k, v + b"\x00", hashlib.sha256).digest()
        v = hmac.new(k, v, hashlib.sha256).digest()


def sign(priv: int, msg: bytes):
    """ECDSA-SHA256 sign; returns (r, s) Python ints.  Host-side."""
    h1 = hashlib.sha256(msg).digest()
    e = int.from_bytes(h1, "big")
    while True:
        k = _rfc6979_nonce(priv, h1)
        pt = scalar_mult_int(k, (GX, GY))
        r = pt[0] % N
        if r == 0:
            h1 = hashlib.sha256(h1).digest()
            continue
        s = _inv_mod(k, N) * (e + r * priv) % N
        if s == 0:
            h1 = hashlib.sha256(h1).digest()
            continue
        return r, s


def verify_int(pub, msg: bytes, r: int, s: int) -> bool:
    """Pure-Python ECDSA verify — the CPU reference the kernel is tested
    against and the single-threaded baseline for the benchmark harness."""
    if not (1 <= r < N and 1 <= s < N):
        return False
    e = int.from_bytes(hashlib.sha256(msg).digest(), "big")
    w = _inv_mod(s, N)
    u1, u2 = e * w % N, r * w % N
    pt = _point_add_int(
        scalar_mult_int(u1, (GX, GY)), scalar_mult_int(u2, pub)
    )
    if pt is None:
        return False
    return pt[0] % N == r


# ---------------------------------------------------------------------------
# host <-> kernel marshalling
# ---------------------------------------------------------------------------

def hashes_to_limbs(msgs) -> np.ndarray:
    """SHA-256 of each message as a row of 16 limbs (the kernel's ``e``
    input), in bulk: the big-endian digests reversed, joined and read
    once.  ``hashlib`` lets go of the interpreter lock for a message
    over 2047 bytes."""
    raw = b"".join([hashlib.sha256(m).digest()[::-1] for m in msgs])
    return bn.bytes_to_limbs(raw, NLIMBS)


def hash_to_limbs(msg: bytes) -> np.ndarray:
    """SHA-256(msg) as a 16-limb vector."""
    return hashes_to_limbs([msg])[0]


def verify_inputs(items) -> tuple[np.ndarray, ...]:
    """[(msg, r, s, (qx,qy)), ...] -> stacked (B,16) kernel inputs, each
    column built from bytes in one pass (:func:`bn.batch_to_limbs`)."""
    e = hashes_to_limbs([m for m, _, _, _ in items])
    r = bn.batch_to_limbs([r for _, r, _, _ in items], NLIMBS)
    s = bn.batch_to_limbs([s for _, _, s, _ in items], NLIMBS)
    qx = bn.batch_to_limbs([q[0] for _, _, _, q in items], NLIMBS)
    qy = bn.batch_to_limbs([q[1] for _, _, _, q in items], NLIMBS)
    return e, r, s, qx, qy


# ---------------------------------------------------------------------------
# scheme API (uniform surface the verify engines/providers program against)
# ---------------------------------------------------------------------------

try:  # native signing fast path: the reference signs with Go's native
    # crypto/ecdsa; pure-Python signing costs ~9.5 ms and dominated the
    # cluster protocol loop, OpenSSL via the cryptography wheel does it in
    # ~60 us.  Verification paths are unaffected (that is the TPU's job).
    from cryptography.hazmat.primitives import hashes as _cg_hashes
    from cryptography.hazmat.primitives.asymmetric import ec as _cg_ec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        decode_dss_signature as _cg_decode_dss,
    )

    import functools as _ft

    @_ft.lru_cache(maxsize=4096)  # bounded; holds a channel's signing clients
    def _cg_key(priv: int):
        return _cg_ec.derive_private_key(priv, _cg_ec.SECP256R1())

    def _sign_native(priv: int, msg: bytes):
        der = _cg_key(priv).sign(msg, _cg_ec.ECDSA(_cg_hashes.SHA256()))
        return _cg_decode_dss(der)
except Exception as _exc:  # pragma: no cover — wheel absent/broken
    import logging as _logging

    _logging.getLogger("smartbft_tpu.crypto").warning(
        "native P-256 signer unavailable (%s); falling back to the "
        "~150x slower pure-Python signer", _exc,
    )
    _sign_native = None


def sign_raw(priv: int, msg: bytes) -> bytes:
    """Sign and encode as fixed 64-byte big-endian r || s.

    Uses the native OpenSSL signer when available (non-deterministic k,
    like the reference's crypto/ecdsa); :func:`sign` remains the
    deterministic RFC 6979 pure-Python reference.  Set
    ``SMARTBFT_DETERMINISTIC_SIGN=1`` to force the RFC 6979 path so
    signature bytes for identical (priv, msg) are reproducible across
    environments regardless of whether the cryptography wheel imports."""
    if _sign_native is not None and _os.environ.get(
        "SMARTBFT_DETERMINISTIC_SIGN"
    ) != "1":
        r, s = _sign_native(priv, msg)
    else:
        r, s = sign(priv, msg)
    return r.to_bytes(32, "big") + s.to_bytes(32, "big")


def make_item(msg: bytes, sig: bytes, pub):
    if len(sig) != 64:
        raise ValueError("bad signature length")
    return (msg, int.from_bytes(sig[:32], "big"),
            int.from_bytes(sig[32:], "big"), pub)


def verify_item(item) -> bool:
    msg, r, s, pub = item
    return verify_int(pub, msg, r, s)


verify_kernel = ecdsa_verify_kernel
