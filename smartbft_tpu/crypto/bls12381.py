"""BLS12-381 aggregate signatures with a batched TPU pairing kernel.

The reference library verifies each consenter signature independently on the
CPU (/root/reference/internal/bft/view.go:537-541 — one goroutine per commit
vote).  BLS aggregation collapses an entire Prepare/Commit quorum into ONE
pairing equation — the BASELINE.md "BLS12-381 aggregate (1 pairing/quorum)"
configuration:

    e(agg_sig, -g2) * e(H(m), agg_pk) == 1
    agg_sig = sum sig_i  (G1),  agg_pk = sum pk_i  (G2)

Scheme: "min-sig" — signatures in G1 (96B uncompressed), public keys in G2
(192B uncompressed).  Same-message aggregation only, which is exactly the
quorum shape (every vote signs the same proposal digest).

Design (TPU-first):

* The Fp2/Fp6/Fp12 tower, the Miller loop steps, and the final
  exponentiation are written ONCE, generically over a field "backend".
  The host backend computes with Python ints (reference + signing path);
  the device backend computes with the 16-bit-limb Montgomery engine
  (:mod:`smartbft_tpu.crypto.bignum`), fully batched — so the device kernel
  is the same audited formulas, retraced onto arrays.
* Tower: Fp2 = Fp[u]/(u^2+1), Fp6 = Fp2[v]/(v^3 - (u+1)),
  Fp12 = Fp6[w]/(w^2 - v).  The curve twist E'/Fp2: y^2 = x^3 + 4(u+1) is
  an M-twist; untwisting scales lines by powers of w, and every line is
  normalized by w^3 — a factor in the Fp4 subfield Fp2(w^3), killed by the
  easy part of the final exponentiation.
* Miller loop: projective (Jacobian) G2 arithmetic over Fp2, no inversions;
  line(P) = l00 + (lx * xP) v + (ly * yP) vw.  The -g2 loop's line
  coefficients are all precomputed on the host (g2 is fixed), so per batch
  lane the device runs one variable-Q loop and one table-driven loop fused
  into a single shared Miller accumulator.
* Final exponentiation: easy part (p^6-1)(p^2+1) via conjugation, one
  inversion, and Frobenius; hard part via the BLS12 identity
  (p^4-p^2+1)/r = (x-1)^2 (x+p) (x^2+p^2-1) + 3 — five 64-bit
  exponentiations by |x| instead of one 4600-bit exponentiation.

Host-side checks (on-curve + r-torsion subgroup) run at marshalling time;
the device evaluates the pairing equation itself.
"""

from __future__ import annotations

import functools
import hashlib
import secrets

import numpy as np

# ---------------------------------------------------------------------------
# curve constants
# ---------------------------------------------------------------------------

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R_ORDER = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
X_ABS = 0xD201000000010000  # |x|; the BLS parameter x is -X_ABS
B1 = 4

G1X = 0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB
G1Y = 0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1

G2X = (
    0x024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8,
    0x13E02B6052719F607DACD3A088274F65596BD0D09920B61AB5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E,
)
G2Y = (
    0x0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A76D429A695160D12C923AC9CC3BACA289E193548608B82801,
    0x0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE,
)

H1_COFACTOR = 0x396C8C005555E1568C00AAAB0000AAAB

FP_BYTES = 48
SIG_BYTES = 2 * FP_BYTES       # G1 affine uncompressed: x || y
PUB_BYTES = 4 * FP_BYTES       # G2 affine uncompressed: x0 || x1 || y0 || y1

NLIMBS = 24  # 384 bits of 16-bit limbs holds the 381-bit field


# ---------------------------------------------------------------------------
# field backends
#
# A backend provides Fp arithmetic; the tower above it is backend-generic.
# Elements of the host backend are Python ints; elements of the device
# backend are (..., NLIMBS) uint32 arrays in the Montgomery domain.
# ---------------------------------------------------------------------------

class HostFp:
    """Python-int Fp arithmetic (reference, signing, and precompute path)."""

    def add(self, a, b):
        return (a + b) % P

    def sub(self, a, b):
        return (a - b) % P

    def mul(self, a, b):
        return (a * b) % P

    def sqr(self, a):
        return (a * a) % P

    def neg(self, a):
        return (-a) % P

    def inv(self, a):
        return pow(a, P - 2, P)

    def small(self, k: int, a):
        return (k * a) % P

    def zero(self, like=None):
        return 0

    def one(self, like=None):
        return 1

    def const(self, x: int, like=None):
        return x % P


HOST = HostFp()


# -- Fp2 --------------------------------------------------------------------

def fp2_add(F, a, b):
    return (F.add(a[0], b[0]), F.add(a[1], b[1]))


def fp2_sub(F, a, b):
    return (F.sub(a[0], b[0]), F.sub(a[1], b[1]))


def fp2_neg(F, a):
    return (F.neg(a[0]), F.neg(a[1]))


def fp2_conj(F, a):
    return (a[0], F.neg(a[1]))


def fp2_mul(F, a, b):
    """Karatsuba: 3 Fp mults.  (a0+a1 u)(b0+b1 u), u^2 = -1."""
    t0 = F.mul(a[0], b[0])
    t1 = F.mul(a[1], b[1])
    t2 = F.mul(F.add(a[0], a[1]), F.add(b[0], b[1]))
    return (F.sub(t0, t1), F.sub(t2, F.add(t0, t1)))


def fp2_sqr(F, a):
    """(a0+a1 u)^2 = (a0+a1)(a0-a1) + 2 a0 a1 u — 2 Fp mults."""
    t0 = F.mul(F.add(a[0], a[1]), F.sub(a[0], a[1]))
    t1 = F.mul(a[0], a[1])
    return (t0, F.add(t1, t1))


def fp2_small(F, k, a):
    return (F.small(k, a[0]), F.small(k, a[1]))


def fp2_mul_fp(F, a, s):
    """Multiply an Fp2 element by an Fp scalar."""
    return (F.mul(a[0], s), F.mul(a[1], s))


def fp2_mul_xi(F, a):
    """Multiply by xi = 1 + u: (a0 - a1) + (a0 + a1) u."""
    return (F.sub(a[0], a[1]), F.add(a[0], a[1]))


def fp2_inv(F, a):
    d = F.inv(F.add(F.sqr(a[0]), F.sqr(a[1])))
    return (F.mul(a[0], d), F.neg(F.mul(a[1], d)))


def fp2_zero(F, like=None):
    return (F.zero(like), F.zero(like))


def fp2_one(F, like=None):
    return (F.one(like), F.zero(like))


def fp2_const(F, c, like=None):
    return (F.const(c[0], like), F.const(c[1], like))


# -- Fp6 = Fp2[v]/(v^3 - xi) ------------------------------------------------

def fp6_add(F, a, b):
    return tuple(fp2_add(F, x, y) for x, y in zip(a, b))


def fp6_sub(F, a, b):
    return tuple(fp2_sub(F, x, y) for x, y in zip(a, b))


def fp6_neg(F, a):
    return tuple(fp2_neg(F, x) for x in a)


def fp6_mul(F, a, b):
    """Schoolbook with xi-reduction: 6 Fp2 mults via Karatsuba-lite."""
    a0, a1, a2 = a
    b0, b1, b2 = b
    t0 = fp2_mul(F, a0, b0)
    t1 = fp2_mul(F, a1, b1)
    t2 = fp2_mul(F, a2, b2)
    # c0 = t0 + xi*((a1+a2)(b1+b2) - t1 - t2)
    s = fp2_mul(F, fp2_add(F, a1, a2), fp2_add(F, b1, b2))
    c0 = fp2_add(F, t0, fp2_mul_xi(F, fp2_sub(F, fp2_sub(F, s, t1), t2)))
    # c1 = (a0+a1)(b0+b1) - t0 - t1 + xi*t2
    s = fp2_mul(F, fp2_add(F, a0, a1), fp2_add(F, b0, b1))
    c1 = fp2_add(F, fp2_sub(F, fp2_sub(F, s, t0), t1), fp2_mul_xi(F, t2))
    # c2 = (a0+a2)(b0+b2) - t0 - t2 + t1
    s = fp2_mul(F, fp2_add(F, a0, a2), fp2_add(F, b0, b2))
    c2 = fp2_add(F, fp2_sub(F, fp2_sub(F, s, t0), t2), t1)
    return (c0, c1, c2)


def fp6_sqr(F, a):
    return fp6_mul(F, a, a)


def fp6_mul_v(F, a):
    """Multiply by v: (a0, a1, a2) -> (xi*a2, a0, a1)."""
    return (fp2_mul_xi(F, a[2]), a[0], a[1])


def fp6_inv(F, a):
    a0, a1, a2 = a
    c0 = fp2_sub(F, fp2_sqr(F, a0), fp2_mul_xi(F, fp2_mul(F, a1, a2)))
    c1 = fp2_sub(F, fp2_mul_xi(F, fp2_sqr(F, a2)), fp2_mul(F, a0, a1))
    c2 = fp2_sub(F, fp2_sqr(F, a1), fp2_mul(F, a0, a2))
    t = fp2_add(
        F,
        fp2_mul_xi(F, fp2_add(F, fp2_mul(F, a2, c1), fp2_mul(F, a1, c2))),
        fp2_mul(F, a0, c0),
    )
    ti = fp2_inv(F, t)
    return (fp2_mul(F, c0, ti), fp2_mul(F, c1, ti), fp2_mul(F, c2, ti))


def fp6_zero(F, like=None):
    return (fp2_zero(F, like),) * 3


def fp6_one(F, like=None):
    return (fp2_one(F, like), fp2_zero(F, like), fp2_zero(F, like))


# -- Fp12 = Fp6[w]/(w^2 - v) -------------------------------------------------

def fp12_mul(F, a, b):
    """(a0 + a1 w)(b0 + b1 w) = (a0 b0 + v a1 b1) + ((a0+a1)(b0+b1)-a0b0-a1b1) w."""
    t0 = fp6_mul(F, a[0], b[0])
    t1 = fp6_mul(F, a[1], b[1])
    t2 = fp6_mul(F, fp6_add(F, a[0], a[1]), fp6_add(F, b[0], b[1]))
    return (
        fp6_add(F, t0, fp6_mul_v(F, t1)),
        fp6_sub(F, fp6_sub(F, t2, t0), t1),
    )


def fp12_sqr(F, a):
    return fp12_mul(F, a, a)


def fp12_conj(F, a):
    """Conjugation = the p^6 Frobenius: a0 - a1 w.  For elements of the
    cyclotomic subgroup this is also the inverse."""
    return (a[0], fp6_neg(F, a[1]))


def fp12_inv(F, a):
    t = fp6_inv(F, fp6_sub(F, fp6_sqr(F, a[0]), fp6_mul_v(F, fp6_sqr(F, a[1]))))
    return (fp6_mul(F, a[0], t), fp6_neg(F, fp6_mul(F, a[1], t)))


def fp12_one(F, like=None):
    return (fp6_one(F, like), fp6_zero(F, like))


def fp12_eq_one_host(a) -> bool:
    return a == fp12_one(HOST)


# -- Frobenius ---------------------------------------------------------------

def _host_fp2_pow(a, e: int):
    """Fp2 exponentiation with Python ints (constant precompute only)."""
    result = (1, 0)
    base = a
    while e:
        if e & 1:
            result = fp2_mul(HOST, result, base)
        base = fp2_sqr(HOST, base)
        e >>= 1
    return result


#: gamma1 = xi^((p-1)/6), gamma2 = gamma1^2, used by the p-power Frobenius.
_G1F = _host_fp2_pow((1, 1), (P - 1) // 6)
_G2F = fp2_mul(HOST, _G1F, _G1F)
_G4F = fp2_mul(HOST, _G2F, _G2F)  # gamma2^2 = xi^(2(p-1)/3)


def fp12_frob(F, a, g1c, g2c, g4c):
    """The p-power Frobenius.  g1c/g2c/g4c are the backend-encoded gamma
    constants (host ints or device limb constants)."""
    (a0, a1, a2), (b0, b1, b2) = a
    a0 = fp2_conj(F, a0)
    a1 = fp2_mul(F, fp2_conj(F, a1), g2c)
    a2 = fp2_mul(F, fp2_conj(F, a2), g4c)
    b0 = fp2_mul(F, fp2_conj(F, b0), g1c)
    b1 = fp2_mul(F, fp2_conj(F, b1), fp2_mul(F, g1c, g2c))
    b2 = fp2_mul(F, fp2_conj(F, b2), fp2_mul(F, g1c, g4c))
    return ((a0, a1, a2), (b0, b1, b2))


# ---------------------------------------------------------------------------
# G1 / G2 host arithmetic (Python ints, Jacobian coordinates)
# ---------------------------------------------------------------------------

def _jac_dbl(F, pt, fp_sqr, fp_mul, fp_add, fp_sub, fp_small):
    X, Y, Z = pt
    A = fp_sqr(F, X)
    Bv = fp_sqr(F, Y)
    C = fp_sqr(F, Bv)
    D = fp_sub(F, fp_sqr(F, fp_add(F, X, Bv)), fp_add(F, A, C))
    D = fp_add(F, D, D)
    E = fp_add(F, fp_add(F, A, A), A)
    Fv = fp_sqr(F, E)
    X3 = fp_sub(F, Fv, fp_add(F, D, D))
    C8 = fp_small(F, 8, C)
    Y3 = fp_sub(F, fp_mul(F, E, fp_sub(F, D, X3)), C8)
    Z3 = fp_mul(F, fp_add(F, Y, Y), Z)
    return (X3, Y3, Z3)


def _g1_dbl(pt):
    return _jac_dbl(
        HOST, pt,
        lambda F, a: F.sqr(a), lambda F, a, b: F.mul(a, b),
        lambda F, a, b: F.add(a, b), lambda F, a, b: F.sub(a, b),
        lambda F, k, a: F.small(k, a),
    )


def _g2_dbl(pt):
    return _jac_dbl(HOST, pt, fp2_sqr, fp2_mul, fp2_add, fp2_sub, fp2_small)


def _jac_add_generic(F, p1, p2, sqr, mul, add, sub, small, zero_pred):
    """Full Jacobian addition (host only; branches allowed)."""
    X1, Y1, Z1 = p1
    X2, Y2, Z2 = p2
    if zero_pred(Z1):
        return p2
    if zero_pred(Z2):
        return p1
    Z1Z1 = sqr(F, Z1)
    Z2Z2 = sqr(F, Z2)
    U1 = mul(F, X1, Z2Z2)
    U2 = mul(F, X2, Z1Z1)
    S1 = mul(F, Y1, mul(F, Z2, Z2Z2))
    S2 = mul(F, Y2, mul(F, Z1, Z1Z1))
    if U1 == U2:
        if S1 == S2:
            return _jac_dbl(F, p1, sqr, mul, add, sub, small)
        return None  # point at infinity
    H = sub(F, U2, U1)
    Rr = sub(F, S2, S1)
    H2 = sqr(F, H)
    H3 = mul(F, H, H2)
    U1H2 = mul(F, U1, H2)
    X3 = sub(F, sub(F, sqr(F, Rr), H3), add(F, U1H2, U1H2))
    Y3 = sub(F, mul(F, Rr, sub(F, U1H2, X3)), mul(F, S1, H3))
    Z3 = mul(F, mul(F, Z1, Z2), H)
    return (X3, Y3, Z3)


def _g1_add(p1, p2):
    r = _jac_add_generic(
        HOST, p1, p2,
        lambda F, a: F.sqr(a), lambda F, a, b: F.mul(a, b),
        lambda F, a, b: F.add(a, b), lambda F, a, b: F.sub(a, b),
        lambda F, k, a: F.small(k, a), lambda z: z == 0,
    )
    return (1, 1, 0) if r is None else r


def _g2_add(p1, p2):
    r = _jac_add_generic(
        HOST, p1, p2, fp2_sqr, fp2_mul, fp2_add, fp2_sub, fp2_small,
        lambda z: z == (0, 0),
    )
    return ((1, 0), (1, 0), (0, 0)) if r is None else r


def _scalar_mult(k: int, pt, dbl, add, inf):
    acc = inf
    q = pt
    while k:
        if k & 1:
            acc = add(acc, q)
        q = dbl(q)
        k >>= 1
    return acc


def _native_bls():
    """The C++ group-arithmetic backend (native/bls381.cc), or None.

    Signing was ~20 ms and aggregation ~63 Python point-adds per quorum
    check in pure ints — the measured reason round 2's BLS cluster row
    could not be deployed.  The native path is ~10x; the Python path
    remains both the fallback and the cross-check oracle."""
    from .. import native

    return native if native.bls_available() else None


def g1_scalar_mult(k: int, affine):
    """k*P, k taken AS GIVEN — no mod-r reduction, because subgroup checks
    multiply by r itself and points may lie outside the r-torsion."""
    nat = _native_bls()
    if nat is not None:
        return nat.bls_g1_mul(k, affine)
    pt = (affine[0], affine[1], 1)
    X, Y, Z = _scalar_mult(k, pt, _g1_dbl, _g1_add, (1, 1, 0))
    return _g1_to_affine((X, Y, Z))


def g2_scalar_mult(k: int, affine):
    nat = _native_bls()
    if nat is not None:
        return nat.bls_g2_mul(k, affine)
    pt = (affine[0], affine[1], (1, 0))
    res = _scalar_mult(k, pt, _g2_dbl, _g2_add, ((1, 0), (1, 0), (0, 0)))
    return _g2_to_affine(res)


def _g1_to_affine(pt):
    X, Y, Z = pt
    if Z == 0:
        return None  # infinity
    zi = pow(Z, P - 2, P)
    zi2 = zi * zi % P
    return (X * zi2 % P, Y * zi2 % P * zi % P)


def _g2_to_affine(pt):
    X, Y, Z = pt
    if Z == (0, 0):
        return None
    zi = fp2_inv(HOST, Z)
    zi2 = fp2_sqr(HOST, zi)
    return (fp2_mul(HOST, X, zi2), fp2_mul(HOST, Y, fp2_mul(HOST, zi2, zi)))


def g1_add_affine(a1, a2):
    """Affine G1 addition (None = infinity)."""
    if a1 is None:
        return a2
    if a2 is None:
        return a1
    return _g1_to_affine(_g1_add((a1[0], a1[1], 1), (a2[0], a2[1], 1)))


def g2_add_affine(a1, a2):
    if a1 is None:
        return a2
    if a2 is None:
        return a1
    return _g2_to_affine(
        _g2_add((a1[0], a1[1], (1, 0)), (a2[0], a2[1], (1, 0)))
    )


def g1_on_curve(pt) -> bool:
    x, y = pt
    return y * y % P == (x * x % P * x + B1) % P


def g2_on_curve(pt) -> bool:
    x, y = pt
    rhs = fp2_add(HOST, fp2_mul(HOST, fp2_sqr(HOST, x), x), fp2_mul_xi(HOST, (B1, 0)))
    return fp2_sqr(HOST, y) == rhs


def g1_in_subgroup(pt) -> bool:
    return g1_scalar_mult(R_ORDER, pt) is None


def g2_in_subgroup(pt) -> bool:
    return g2_scalar_mult(R_ORDER, pt) is None


# ---------------------------------------------------------------------------
# hash to G1 (deterministic try-and-increment + cofactor clearing)
#
# Not RFC 9380 (whose SSWU map would also work); this framework defines its
# own wire format, and try-and-increment is deterministic, uniform enough,
# and runs once per proposal digest on the host — the pairing is the
# device-side cost.
# ---------------------------------------------------------------------------

_SQRT_EXP = (P + 1) // 4  # p = 3 mod 4


@functools.lru_cache(maxsize=4096)
def hash_to_g1(msg: bytes):
    ctr = 0
    while True:
        t = hashlib.sha256(b"smartbft-bls12381-g1" + ctr.to_bytes(4, "big") + msg).digest()
        t2 = hashlib.sha256(b"smartbft-bls12381-g1b" + ctr.to_bytes(4, "big") + msg).digest()
        x = int.from_bytes(t + t2[:16], "big") % P
        rhs = (x * x % P * x + B1) % P
        y = pow(rhs, _SQRT_EXP, P)
        if y * y % P == rhs:
            if (t2[16] & 1) != (y & 1):
                y = P - y
            pt = g1_scalar_mult(H1_COFACTOR, (x, y))
            if pt is not None:
                return pt
        ctr += 1


# ---------------------------------------------------------------------------
# Miller loop (generic over backend) and final exponentiation
# ---------------------------------------------------------------------------

_X_BITS = [(X_ABS >> i) & 1 for i in range(X_ABS.bit_length() - 2, -1, -1)]
_XP1_BITS = [((X_ABS + 1) >> i) & 1 for i in range((X_ABS + 1).bit_length() - 1, -1, -1)]


def _line_to_fp12(F, l00, lx, ly, like=None):
    """Assemble the (scaled) line l00 + lx*v + ly*vw as a full Fp12 element.

    Derivation (module docstring): untwisting scales x by w^-2 and y by
    w^-3; multiplying the affine line by w^3 leaves components at w^0 (Fp2),
    w^2 = v, and w^3 = vw.  The w^3 normalization lies in Fp2(w^3) = Fp4 and
    is erased by the easy final exponentiation.
    """
    z = fp2_zero(F, like)
    return ((l00, lx, z), (z, ly, z))


def _dbl_step(F, T):
    """One Miller doubling: T <- 2T on the twist.

    Returns (T', raw line coeffs (l00, lxc, lyc)); the caller scales
    lxc by xP and lyc by yP.  Line (scaled by the Fp2 factor 2YZ^3,
    erased by the final exp):
      l00 = 3X^3 - 2Y^2,  lxc = -3 X^2 Z^2,  lyc = 2 Y Z^3
    """
    X, Y, Z = T
    X2 = fp2_sqr(F, X)
    Y2 = fp2_sqr(F, Y)
    Z2 = fp2_sqr(F, Z)
    X2_3 = fp2_add(F, fp2_add(F, X2, X2), X2)
    l00 = fp2_sub(F, fp2_mul(F, X2_3, X), fp2_add(F, Y2, Y2))
    lxc = fp2_neg(F, fp2_mul(F, X2_3, Z2))
    YZ = fp2_mul(F, Y, Z)
    lyc = fp2_mul(F, fp2_add(F, YZ, YZ), Z2)
    # dbl-2007-b/l
    C = fp2_sqr(F, Y2)
    D = fp2_sub(F, fp2_sqr(F, fp2_add(F, X, Y2)), fp2_add(F, X2, C))
    D = fp2_add(F, D, D)
    Fv = fp2_sqr(F, X2_3)
    X3 = fp2_sub(F, Fv, fp2_add(F, D, D))
    Y3 = fp2_sub(F, fp2_mul(F, X2_3, fp2_sub(F, D, X3)), fp2_small(F, 8, C))
    Z3 = fp2_add(F, YZ, YZ)
    return (X3, Y3, Z3), (l00, lxc, lyc)


def _add_step(F, T, Q):
    """One Miller mixed addition: T <- T + Q (Q affine).

    With H = xq Z^2 - X, r = yq Z^3 - Y (line scaled by the Fp2 factor HZ):
      l00 = r*xq - HZ*yq,  lxc = -r,  lyc = HZ
    """
    X, Y, Z = T
    xq, yq = Q
    Z2 = fp2_sqr(F, Z)
    Z3c = fp2_mul(F, Z2, Z)
    H = fp2_sub(F, fp2_mul(F, xq, Z2), X)
    Rr = fp2_sub(F, fp2_mul(F, yq, Z3c), Y)
    HZ = fp2_mul(F, H, Z)
    l00 = fp2_sub(F, fp2_mul(F, Rr, xq), fp2_mul(F, HZ, yq))
    lxc = fp2_neg(F, Rr)
    lyc = HZ
    H2 = fp2_sqr(F, H)
    H3 = fp2_mul(F, H2, H)
    UH2 = fp2_mul(F, X, H2)
    X3 = fp2_sub(F, fp2_sub(F, fp2_sqr(F, Rr), H3), fp2_add(F, UH2, UH2))
    Y3 = fp2_sub(F, fp2_mul(F, Rr, fp2_sub(F, UH2, X3)), fp2_mul(F, Y, H3))
    Z3 = HZ
    return (X3, Y3, Z3), (l00, lxc, lyc)


def _scale_line(F, coeffs, xp, yp):
    l00, lxc, lyc = coeffs
    return (l00, fp2_mul_fp(F, lxc, xp), fp2_mul_fp(F, lyc, yp))


def host_miller_loop(p_affine, q_affine):
    """f_{|x|,Q}(P) conjugated (x < 0) — host ints.  P in G1, Q on the twist."""
    F = HOST
    xp, yp = p_affine
    T = (q_affine[0], q_affine[1], (1, 0))
    f = fp12_one(F)
    for bit in _X_BITS:
        f = fp12_sqr(F, f)
        T, coeffs = _dbl_step(F, T)
        f = fp12_mul(F, f, _line_to_fp12(F, *_scale_line(F, coeffs, xp, yp)))
        if bit:
            T, coeffs = _add_step(F, T, q_affine)
            f = fp12_mul(F, f, _line_to_fp12(F, *_scale_line(F, coeffs, xp, yp)))
    return fp12_conj(F, f)  # x < 0


def _cyclo_exp_abs(F, m, bits, g1c, g2c, g4c):
    """m^e for e = |x| or |x|+1 given MSB-first bits; m cyclotomic (host)."""
    acc = m
    for bit in bits[1:]:
        acc = fp12_sqr(F, acc)
        if bit:
            acc = fp12_mul(F, acc, m)
    return acc


def host_final_exp(f):
    """f^(3 (p^12-1)/r): easy part + the BLS12 hard-part identity
    3 (p^4-p^2+1)/r = (x-1)^2 (x+p) (x^2+p^2-1) + 3.

    The extra factor of 3 (coprime to r) yields the CUBE of the optimal ate
    pairing — itself a bilinear, non-degenerate pairing of order r, which is
    all the verification equation needs; skipping the cube root saves work
    (the common trick in production pairing code)."""
    F = HOST
    g1c, g2c, g4c = _G1F, _G2F, _G4F
    # easy: f <- f^(p^6-1), then f <- f^(p^2) * f  => f^((p^6-1)(p^2+1))
    f = fp12_mul(F, fp12_conj(F, f), fp12_inv(F, f))
    f = fp12_mul(F, fp12_frob(F, fp12_frob(F, f, g1c, g2c, g4c), g1c, g2c, g4c), f)
    m = f
    conj = lambda z: fp12_conj(F, z)
    expx = lambda z: conj(_cyclo_exp_abs(F, z, _X_BITS_FULL, g1c, g2c, g4c))
    expxm1 = lambda z: conj(_cyclo_exp_abs(F, z, _XP1_BITS, g1c, g2c, g4c))
    a = expxm1(m)                       # m^(x-1)
    a = expxm1(a)                       # m^((x-1)^2)
    b = expx(a)                         # a^x
    a = fp12_mul(F, b, fp12_frob(F, a, g1c, g2c, g4c))   # a^(x+p)
    c = expx(expx(a))                   # a^(x^2)
    a2 = fp12_frob(F, fp12_frob(F, a, g1c, g2c, g4c), g1c, g2c, g4c)
    a = fp12_mul(F, fp12_mul(F, c, a2), conj(a))         # a^(x^2+p^2-1)
    m3 = fp12_mul(F, fp12_mul(F, m, m), m)
    return fp12_mul(F, a, m3)


_X_BITS_FULL = [(X_ABS >> i) & 1 for i in range(X_ABS.bit_length() - 1, -1, -1)]

NEG_G2 = (G2X, fp2_neg(HOST, G2Y))


def host_pairing_check(pairs) -> bool:
    """prod e(P_i, Q_i) == 1, host ints.  pairs: [(G1 affine, twist affine)]."""
    f = fp12_one(HOST)
    for p_aff, q_aff in pairs:
        f = fp12_mul(HOST, f, host_miller_loop(p_aff, q_aff))
    return fp12_eq_one_host(host_final_exp(f))


# ---------------------------------------------------------------------------
# scheme API (host): keygen / sign / verify / aggregate
# ---------------------------------------------------------------------------

def _fp_to_bytes(x: int) -> bytes:
    return x.to_bytes(FP_BYTES, "big")


def _fp_from_bytes(b: bytes) -> int:
    x = int.from_bytes(b, "big")
    if x >= P:
        raise ValueError("field element out of range")
    return x


def serialize_g1(pt) -> bytes:
    return _fp_to_bytes(pt[0]) + _fp_to_bytes(pt[1])


def deserialize_g1(b: bytes):
    if len(b) != SIG_BYTES:
        raise ValueError("bad G1 encoding length")
    return (_fp_from_bytes(b[:FP_BYTES]), _fp_from_bytes(b[FP_BYTES:]))


def serialize_g2(pt) -> bytes:
    (x0, x1), (y0, y1) = pt
    return b"".join(_fp_to_bytes(v) for v in (x0, x1, y0, y1))


def deserialize_g2(b: bytes):
    if len(b) != PUB_BYTES:
        raise ValueError("bad G2 encoding length")
    v = [_fp_from_bytes(b[i * FP_BYTES:(i + 1) * FP_BYTES]) for i in range(4)]
    return ((v[0], v[1]), (v[2], v[3]))


def keygen(seed: bytes | None = None):
    """Returns (sk_int, pk_bytes).  pk = sk * g2, 192B uncompressed."""
    if seed is None:
        seed = secrets.token_bytes(32)
    sk = (
        int.from_bytes(hashlib.sha512(b"smartbft-bls-keygen" + seed).digest(), "big")
        % (R_ORDER - 1)
    ) + 1
    pk = g2_scalar_mult(sk, (G2X, G2Y))
    return sk, serialize_g2(pk)


def sign(sk: int, msg: bytes) -> bytes:
    """sig = sk * H(msg) in G1; 96B uncompressed.

    H(msg) is cofactor-cleared (r-torsion by construction), so the native
    GLV ladder is sound here — ~halves the doublings of the generic path
    (native/bls381.cc jac_mul_glv)."""
    if sk % R_ORDER == 0:
        # sk*h would be the point at infinity (rc==0 from the native ABI,
        # None from the software ladder) — unserializable and useless as a
        # signature; fail with a diagnosis instead of a TypeError downstream
        raise ValueError("BLS secret key is 0 mod r; refusing to sign")
    h = hash_to_g1(msg)
    nat = _native_bls()
    if nat is not None:
        pt = nat.bls_g1_mul_torsion(sk, h)
    else:
        pt = g1_scalar_mult(sk, h)
    if pt is None:  # h at infinity (negligible-probability hash output)
        raise ValueError("BLS signing produced the point at infinity")
    return serialize_g1(pt)


# Proof of possession: same-message ("fast") aggregate verification is only
# sound against rogue-key attacks (pk_B = b*g2 - pk_A lets B forge an
# aggregate containing a vote A never cast) when every registered public key
# has proven knowledge of its secret key — the PoP scheme of the IETF BLS
# draft.  The domain tag separates PoP messages from every consensus payload.
_POP_TAG = b"smartbft-bls12381-pop:"


def pop_prove(sk: int, pub: bytes) -> bytes:
    """Proof of possession for ``pub``: a signature over its own wire bytes."""
    return sign(sk, _POP_TAG + pub)


def pop_verify(pub: bytes, pop: bytes) -> bool:
    """Check a proof of possession produced by :func:`pop_prove`."""
    return verify_int(pub, _POP_TAG + pub, pop)


def keygen_with_pop(seed: bytes | None = None):
    """(sk, pk, pop) — keygen plus the proof of possession for pk."""
    sk, pk = keygen(seed)
    return sk, pk, pop_prove(sk, pk)


@functools.lru_cache(maxsize=1024)
def _checked_pub(pub: bytes):
    pk = deserialize_g2(pub)
    if not g2_on_curve(pk) or not g2_in_subgroup(pk):
        raise ValueError("public key not in G2")
    return pk


@functools.lru_cache(maxsize=4096)
def _checked_sig(sig: bytes):
    """Decode + on-curve + r-torsion check, memoized by wire bytes.

    The subgroup check is a full scalar-mult by r on the host; the cache
    means a signature relayed across paths (commit vote, ViewData last
    decision, aggregate-failure fallback lanes) pays it once.
    """
    pt = deserialize_g1(sig)
    if not g1_on_curve(pt) or not g1_in_subgroup(pt):
        raise ValueError("signature not in G1")
    return pt


def verify_int(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """Single-signature verify, host ints: e(sig,-g2) e(H(m),pk) == 1."""
    try:
        pk = _checked_pub(pub)
        s = _checked_sig(sig)
    except ValueError:
        return False
    return host_pairing_check([(s, NEG_G2), (hash_to_g1(msg), pk)])


def aggregate_sigs(sigs) -> bytes:
    """Sum of G1 signatures (same-message aggregation)."""
    nat = _native_bls()
    if nat is not None:
        acc = nat.bls_g1_sum(deserialize_g1(sig) for sig in sigs)
    else:
        acc = None
        for sig in sigs:
            acc = g1_add_affine(acc, deserialize_g1(sig))
    if acc is None:
        raise ValueError("empty or cancelling aggregate")
    return serialize_g1(acc)


def aggregate_pubs(pubs) -> bytes:
    nat = _native_bls()
    if nat is not None:
        acc = nat.bls_g2_sum(deserialize_g2(pub) for pub in pubs)
    else:
        acc = None
        for pub in pubs:
            acc = g2_add_affine(acc, deserialize_g2(pub))
    if acc is None:
        raise ValueError("empty or cancelling aggregate")
    return serialize_g2(acc)


def aggregate_verify_int(pubs, msg: bytes, sigs) -> bool:
    """Whole-quorum verify with ONE pairing equation (host path)."""
    try:
        pks = [_checked_pub(p) for p in pubs]
        pts = [_checked_sig(s) for s in sigs]
    except ValueError:
        return False
    nat = _native_bls()
    if nat is not None:
        agg_sig = nat.bls_g1_sum(pts)
        agg_pk = nat.bls_g2_sum(pks)
    else:
        agg_sig = None
        for pt in pts:
            agg_sig = g1_add_affine(agg_sig, pt)
        agg_pk = None
        for pk in pks:
            agg_pk = g2_add_affine(agg_pk, pk)
    if agg_sig is None or agg_pk is None:
        return False
    return host_pairing_check([(agg_sig, NEG_G2), (hash_to_g1(msg), agg_pk)])


# ---------------------------------------------------------------------------
# provider-scheme glue (same surface as p256/ed25519 modules)
# ---------------------------------------------------------------------------

def sign_raw(sk, msg: bytes) -> bytes:
    return sign(sk, msg)


def make_item(msg: bytes, sig: bytes, pub: bytes):
    return (msg, sig, pub)


def verify_item(item) -> bool:
    msg, sig, pub = item
    return verify_int(pub, msg, sig)


# ---------------------------------------------------------------------------
# device backend: the same tower formulas over 16-bit-limb Montgomery arrays
# ---------------------------------------------------------------------------

import jax.numpy as jnp  # noqa: E402  (device section)
from jax import lax  # noqa: E402

from . import bignum as bn  # noqa: E402
from .bignum import MontCtx  # noqa: E402

CTX = MontCtx(P, NLIMBS)


class DeviceFp:
    """Backend over (..., NLIMBS) uint32 Montgomery-domain arrays; every op
    is natively batched over leading axes."""

    def __init__(self, ctx: MontCtx):
        self.ctx = ctx

    def add(self, a, b):
        return self.ctx.add(a, b)

    def sub(self, a, b):
        return self.ctx.sub(a, b)

    def mul(self, a, b):
        return self.ctx.mul(a, b)

    def sqr(self, a):
        return self.ctx.square(a)  # square_columns: ~47% fewer lane mults

    def neg(self, a):
        return self.ctx.neg(a)

    def inv(self, a):
        return self.ctx.inv(a)

    def small(self, k: int, a):
        acc = a
        for bit in bin(k)[3:]:  # skip leading 1
            acc = self.ctx.add(acc, acc)
            if bit == "1":
                acc = self.ctx.add(acc, a)
        return acc

    def zero(self, like=None):
        z = jnp.asarray(self.ctx.zero)
        return z if like is None else jnp.broadcast_to(z, like.shape)

    def one(self, like=None):
        o = jnp.asarray(self.ctx.one_mont)
        return o if like is None else jnp.broadcast_to(o, like.shape)

    def const(self, x: int, like=None):
        c = jnp.asarray(self.ctx.encode(x))
        return c if like is None else jnp.broadcast_to(c, like.shape)


DEV = DeviceFp(CTX)


def _tree_select(mask, a, b):
    """Elementwise select over matching nested tuples of limb arrays."""
    if isinstance(a, tuple):
        return tuple(_tree_select(mask, x, y) for x, y in zip(a, b))
    return bn.select(mask, a, b)


# -- stacked Fp12: (..., 12, NLIMBS) arrays ---------------------------------
#
# XLA compiles nested while-loops (the carry chains inside every Montgomery
# mult) far more slowly than data-parallel ops.  A naive port of the tower
# would emit ~330 sequential Fp mults per Miller step — thousands of nested
# loops.  Instead every INDEPENDENT Fp mult inside one Fp12 operation is
# gathered into a single batched Montgomery call over a stacked axis: one
# Fp12 mult = one (18-way) stacked Karatsuba Fp2 product + a handful of
# stacked add/sub chains, regardless of batch size.
#
# Row layout of a stacked element f = (a0 + a1 v + a2 v^2) + (b0 + ...) w:
#   rows 0..5  = a0re, a0im, a1re, a1im, a2re, a2im
#   rows 6..11 = b0re, b0im, b1re, b1im, b2re, b2im


def _stk_from_tuple(f):
    (a0, a1, a2), (b0, b1, b2) = f
    return jnp.stack(
        [a0[0], a0[1], a1[0], a1[1], a2[0], a2[1],
         b0[0], b0[1], b1[0], b1[1], b2[0], b2[1]], axis=-2
    )


def _stk_to_tuple(x):
    r = lambda i: x[..., i, :]
    return (
        ((r(0), r(1)), (r(2), r(3)), (r(4), r(5))),
        ((r(6), r(7)), (r(8), r(9)), (r(10), r(11))),
    )


def _stk_one(like):
    """1 in stacked form, broadcast to like's batch shape (like: (..., L))."""
    one = jnp.broadcast_to(jnp.asarray(CTX.one_mont), like.shape)
    zero = jnp.zeros_like(one)
    return jnp.stack([one] + [zero] * 11, axis=-2)


def _rows_mul(A, B):
    """Stacked Karatsuba Fp2 products: (..., K, 2, L) x (..., K, 2, L).

    3K Fp mults in ONE Montgomery call; 5 further stacked chains total.
    """
    ctx = CTX
    a0, a1 = A[..., 0, :], A[..., 1, :]
    b0, b1 = B[..., 0, :], B[..., 1, :]
    lhs = jnp.stack([a0, a1, ctx.add(a0, a1)], axis=-2)
    rhs = jnp.stack([b0, b1, ctx.add(b0, b1)], axis=-2)
    t = ctx.mul(lhs, rhs)
    t0, t1, t2 = t[..., 0, :], t[..., 1, :], t[..., 2, :]
    re = ctx.sub(t0, t1)
    im = ctx.sub(t2, ctx.add(t0, t1))
    return jnp.stack([re, im], axis=-2)


def _rows_xi(a):
    """xi * a for stacked fp2 rows (..., 2, L): (re - im, re + im)."""
    ctx = CTX
    re, im = a[..., 0, :], a[..., 1, :]
    return jnp.stack([ctx.sub(re, im), ctx.add(re, im)], axis=-2)


# -- tensor-driven Fp12 multiplication (lazy reduction) ----------------------
#
# The Fp12 multiplication tensor over the 12 Fp coordinates is generated once
# from the HOST tower (so it is correct by construction) as a static list of
# product slots (i, j, negate, output).  At runtime: gather operand rows, one
# batched mul_columns for ALL slots, vector-add columns per output into <= 9
# product buckets (the redc_cols bound), and ONE stacked Montgomery reduction
# for every output coordinate.  An Fp12 mult is ~8 sequential chains total —
# this is what makes the pairing kernel compile AND run fast.

# products per reduction; redc_cols requires k < R/N, and R/P = 2^384/P
# is ~9.84 for BLS12-381, so 9 buckets are safe (9 * P^2 < 2^384 * P)
_BUCKET_CAP = 9


def _coord_basis(i: int):
    """Host fp12 with a 1 in flat coordinate i (layout of _stk_from_tuple)."""
    flat = [0] * 12
    flat[i] = 1
    it = iter(flat)
    return tuple(
        tuple((next(it), next(it)) for _ in range(3)) for _ in range(2)
    )


def _flatten_host_fp12(f):
    return [c for half in f for pair in half for c in pair]


@functools.lru_cache(maxsize=4)
def _build_mul_tensor(y_support: tuple):
    """Static product-slot table for z = x * y with y zero outside
    ``y_support`` rows.  Returns (lhs_idx, rhs_idx, neg, out_slot, n_buckets)
    as numpy arrays / int."""
    slots_per_out: list[list[tuple[int, int, bool]]] = [[] for _ in range(12)]
    for i in range(12):
        for j in y_support:
            prod = _flatten_host_fp12(
                fp12_mul(HOST, _coord_basis(i), _coord_basis(j))
            )
            for k, c in enumerate(prod):
                if c == 0:
                    continue
                if c <= 4:
                    repeat, neg = c, False
                elif P - c <= 4:
                    repeat, neg = P - c, True
                else:  # pragma: no cover — tower structure guarantees small c
                    raise AssertionError(f"unexpected tensor coeff {c}")
                slots_per_out[k].extend([(i, j, neg)] * repeat)
    n_buckets = max(
        (len(s) + _BUCKET_CAP - 1) // _BUCKET_CAP for s in slots_per_out
    )
    lhs, rhs, neg, out = [], [], [], []
    for k, slots in enumerate(slots_per_out):
        for pos, (i, j, n) in enumerate(slots):
            lhs.append(i)
            rhs.append(j)
            neg.append(n)
            out.append((pos // _BUCKET_CAP) * 12 + k)
    return (
        np.asarray(lhs, np.int32),
        np.asarray(rhs, np.int32),
        np.asarray(neg, bool),
        np.asarray(out, np.int32),
        n_buckets,
    )


_FULL_SUPPORT = tuple(range(12))
#: line rows: l00 at fp2 coord 0 (rows 0-1), lx at coord 1 (rows 2-3),
#: ly at coord 4 (rows 8-9) — see _line_to_fp12
_LINE_SUPPORT = (0, 1, 2, 3, 8, 9)


def _mul12_tensor(x, y, y_support):
    """z = x * y over stacked (..., 12, L) coordinates; ~8 chains total."""
    ctx = CTX
    lhs_idx, rhs_idx, negmask, out_slot, n_buckets = _build_mul_tensor(y_support)
    yneg, _ = bn.sub_borrow(
        jnp.broadcast_to(jnp.asarray(ctx.N), y.shape), y
    )
    lhs = jnp.take(x, jnp.asarray(lhs_idx), axis=-2)
    rhs = jnp.where(
        jnp.asarray(negmask)[:, None],
        jnp.take(yneg, jnp.asarray(rhs_idx), axis=-2),
        jnp.take(y, jnp.asarray(rhs_idx), axis=-2),
    )
    cols = bn.mul_columns(lhs, rhs)  # (..., K, 2L)
    # vector-accumulate column arrays per output slot (static grouping)
    groups: dict[int, list[int]] = {}
    for pos, slot in enumerate(out_slot):
        groups.setdefault(int(slot), []).append(pos)
    slot_cols = []
    for slot in range(12 * n_buckets):
        members = groups.get(slot)
        if not members:
            slot_cols.append(jnp.zeros(cols.shape[:-2] + (cols.shape[-1],), bn.DTYPE))
            continue
        acc = cols[..., members[0], :]
        for pos in members[1:]:
            acc = acc + cols[..., pos, :]
        slot_cols.append(acc)
    stacked = jnp.stack(slot_cols, axis=-2)  # (..., 12*n_buckets, 2L)
    red = ctx.redc_cols(stacked)  # (..., 12*n_buckets, L)
    result = red[..., 0:12, :]
    for b in range(1, n_buckets):
        result = ctx.add(result, red[..., b * 12 : (b + 1) * 12, :])
    return result


def mul12(x, y):
    """Fp12 mult via the lazy-reduction tensor path."""
    return _mul12_tensor(x, y, _FULL_SUPPORT)


def mul12_line(f, line_rows):
    """f times a sparse line element (rows 0-3 and 8-9 only)."""
    return _mul12_tensor(f, line_rows, _LINE_SUPPORT)


def sqr12(x):
    return _mul12_tensor(x, x, _FULL_SUPPORT)


def conj12(x):
    """a - b w: negate rows 6..11 (one stacked chain)."""
    a = x[..., 0:6, :]
    b = CTX.neg(x[..., 6:12, :])
    return jnp.concatenate([a, b], axis=-2)


_FROB_COEFFS = None


def _frob_coeffs():
    """Stacked gamma constants for the p-power Frobenius: (5, 2, L)."""
    global _FROB_COEFFS
    if _FROB_COEFFS is None:
        g1g2 = fp2_mul(HOST, _G1F, _G2F)
        g1g4 = fp2_mul(HOST, _G1F, _G4F)
        _FROB_COEFFS = np.stack([
            _fp2_const_mont(_G2F),   # a1
            _fp2_const_mont(_G4F),   # a2
            _fp2_const_mont(_G1F),   # b0
            _fp2_const_mont(g1g2),   # b1
            _fp2_const_mont(g1g4),   # b2
        ])
    return _FROB_COEFFS


def frob12(x):
    """p-power Frobenius, stacked: conjugate all Fp2 rows then scale five of
    the six components by the gamma constants (one 5-way mult call)."""
    ctx = CTX
    re = x[..., 0::2, :]
    im = ctx.neg(x[..., 1::2, :])
    conj = jnp.stack([re, im], axis=-2)  # (..., 6, 2, L)
    a0 = conj[..., 0:1, :, :]
    rest = conj[..., 1:6, :, :]
    coeffs = jnp.broadcast_to(jnp.asarray(_frob_coeffs()), rest.shape)
    scaled = _rows_mul(rest, coeffs)
    out = jnp.concatenate([a0, scaled], axis=-3)  # (..., 6, 2, L)
    return out.reshape(out.shape[:-3] + (12, NLIMBS))


_HALF_SUPPORT = (0, 1, 2, 3, 4, 5)  # fp6 embedded in rows 0..5, w-half zero


def _stk_pad6(a):
    """(..., 6, L) fp6 rows -> (..., 12, L) fp12 with zero w-half."""
    return jnp.concatenate([a, jnp.zeros_like(a)], axis=-2)


def _stk_mul_v(a):
    """v * (c0 + c1 v + c2 v^2) = xi*c2 + c0 v + c1 v^2 on (..., 6, L) rows."""
    return jnp.concatenate([_rows_xi(a[..., 4:6, :]), a[..., 0:4, :]], axis=-2)


def inv12(x):
    """Fp12 inversion, stacked: the same norm-tower chain as the host
    :func:`fp12_inv` (fp12 -> fp6 -> fp2 -> one Fp Fermat inversion), but
    with every level's independent fp2 products gathered into stacked
    Montgomery calls — ~12 sequential chains + one exp scan, versus the
    ~100 chains the generic tuple tower emitted (which alone cost ~2 min
    of XLA compile)."""
    ctx = CTX
    a, b = x[..., 0:6, :], x[..., 6:12, :]
    # a^2, b^2 as fp6 products via the fp12 tensor on zero-w-half operands
    pa, pb = _stk_pad6(a), _stk_pad6(b)
    a2 = _mul12_tensor(pa, pa, _HALF_SUPPORT)[..., 0:6, :]
    b2 = _mul12_tensor(pb, pb, _HALF_SUPPORT)[..., 0:6, :]
    den = ctx.sub(a2, _stk_mul_v(b2))  # a^2 - v b^2 in fp6 rows
    d0, d1, d2 = (den[..., 0:2, :], den[..., 2:4, :], den[..., 4:6, :])
    # fp6 inversion (host fp6_inv formulas), fp2 ops stacked 3-wide
    s0, s1, s2 = _fp2_stk_sqr3(d0, d1, d2)  # d0^2, d1^2, d2^2
    p12, p01, p02 = _fp2_stk_mul([(d1, d2), (d0, d1), (d0, d2)])
    c0 = ctx.sub(s0, _rows_xi(p12))
    c1 = ctx.sub(_rows_xi(s2), p01)
    c2 = ctx.sub(s1, p02)
    q21, q12, q00 = _fp2_stk_mul([(d2, c1), (d1, c2), (d0, c0)])
    t = ctx.add(_rows_xi(ctx.add(q21, q12)), q00)  # (..., 2, L) fp2
    # fp2 inversion: 1/(tr + ti u) = (tr - ti u) / (tr^2 + ti^2)
    tr, ti = t[..., 0, :], t[..., 1, :]
    sq = ctx.square(jnp.stack([tr, ti], axis=-2))
    norm = ctx.add(sq[..., 0, :], sq[..., 1, :])
    ninv = ctx.inv(norm)  # the single Fp Fermat inversion (exp scan)
    ri = ctx.mul(jnp.stack([tr, ti], axis=-2),
                 jnp.stack([ninv, ninv], axis=-2))
    tinv = jnp.stack([ri[..., 0, :], ctx.neg(ri[..., 1, :])], axis=-2)
    e0, e1, e2 = _fp2_stk_mul([(c0, tinv), (c1, tinv), (c2, tinv)])
    e = jnp.concatenate([e0, e1, e2], axis=-2)  # fp6 = 1/(a^2 - v b^2)
    # (a - b w) * e  =  a e  -  (b e) w  =  x^-1
    return _mul12_tensor(conj12(x), _stk_pad6(e), _HALF_SUPPORT)


def _fp2_const_mont(c) -> np.ndarray:
    return np.stack([CTX.encode(c[0]), CTX.encode(c[1])])


# -- fixed -g2 Miller line tables (precomputed with host ints) ---------------

def _precompute_fixed_lines(q_affine):
    """Per-step raw line coefficients for the fixed-Q Miller loop, encoded
    into the Montgomery domain: two (steps, 3, 2, NLIMBS) arrays."""
    T = (q_affine[0], q_affine[1], (1, 0))
    dbl_rows, add_rows = [], []

    def enc(coeffs):
        return np.stack([_fp2_const_mont(c) for c in coeffs])

    for bit in _X_BITS:
        T, coeffs = _dbl_step(HOST, T)
        dbl_rows.append(enc(coeffs))
        if bit:
            T, coeffs = _add_step(HOST, T, q_affine)
            add_rows.append(enc(coeffs))
        else:
            add_rows.append(enc(((0, 0), (0, 0), (0, 0))))
    return np.stack(dbl_rows), np.stack(add_rows)


_FIXED_DBL, _FIXED_ADD = _precompute_fixed_lines(NEG_G2)
_X_BITS_ARR = np.asarray(_X_BITS, dtype=np.uint32)


def _fp2_stk_sqr3(a, b, c):
    """Square three independent stacked fp2 values in one Montgomery call."""
    s = jnp.stack([a, b, c], axis=-3)
    t = _rows_mul(s, s)
    return t[..., 0, :, :], t[..., 1, :, :], t[..., 2, :, :]


def _fp2_stk_mul(pairs):
    """[(a, b), ...] independent stacked-fp2 products in one call."""
    lhs = jnp.stack([jnp.broadcast_to(a, jnp.broadcast_shapes(a.shape, b.shape))
                     for a, b in pairs], axis=-3)
    rhs = jnp.stack([jnp.broadcast_to(b, jnp.broadcast_shapes(a.shape, b.shape))
                     for a, b in pairs], axis=-3)
    t = _rows_mul(lhs, rhs)
    return tuple(t[..., i, :, :] for i in range(len(pairs)))


def _stk_dbl_step(T):
    """Stacked Miller doubling (same formulas as :func:`_dbl_step`): four
    Montgomery calls total instead of one per field mult."""
    ctx = CTX
    X, Y, Z = T  # each (..., 2, L)
    X2, Y2, Z2 = _fp2_stk_sqr3(X, Y, Z)
    X2_3 = ctx.add(ctx.add(X2, X2), X2)
    P1, P2, YZ = _fp2_stk_mul([(X2_3, X), (X2_3, Z2), (Y, Z)])
    l00 = ctx.sub(P1, ctx.add(Y2, Y2))
    lxc = ctx.neg(P2)
    XpY2 = ctx.add(X, Y2)
    C, D2s, Fv = _fp2_stk_sqr3(Y2, XpY2, X2_3)
    D = ctx.sub(D2s, ctx.add(X2, C))
    D = ctx.add(D, D)
    X3 = ctx.sub(Fv, ctx.add(D, D))
    YZ2 = ctx.add(YZ, YZ)
    M1, lyc = _fp2_stk_mul([(X2_3, ctx.sub(D, X3)), (YZ2, Z2)])
    C2 = ctx.add(C, C)
    C4 = ctx.add(C2, C2)
    Y3 = ctx.sub(M1, ctx.add(C4, C4))
    return (X3, Y3, YZ2), (l00, lxc, lyc)


def _stk_add_step(T, Q):
    """Stacked Miller mixed addition (same formulas as :func:`_add_step`)."""
    ctx = CTX
    X, Y, Z = T
    xq, yq = Q  # stacked fp2 (..., 2, L)
    (Z2,) = _fp2_stk_mul([(Z, Z)])
    Z3c, U2 = _fp2_stk_mul([(Z2, Z), (xq, Z2)])
    (S2,) = _fp2_stk_mul([(yq, Z3c)])
    H = ctx.sub(U2, X)
    Rr = ctx.sub(S2, Y)
    HZ, H2, R2 = _fp2_stk_mul([(H, Z), (H, H), (Rr, Rr)])
    Rxq, HZyq, H3, UH2 = _fp2_stk_mul([(Rr, xq), (HZ, yq), (H2, H), (X, H2)])
    X3 = ctx.sub(ctx.sub(R2, H3), ctx.add(UH2, UH2))
    M1, M2 = _fp2_stk_mul([(Rr, ctx.sub(UH2, X3)), (Y, H3)])
    Y3 = ctx.sub(M1, M2)
    l00 = ctx.sub(Rxq, HZyq)
    lxc = ctx.neg(Rr)
    lyc = HZ
    return (X3, Y3, HZ), (l00, lxc, lyc)


def _line_rows(coeffs_fp2, xp, yp):
    """Stacked line: scale lxc by xp, lyc by yp (one 2-way mult call) and
    assemble the sparse rows [l00, lx, 0, | 0, ly, 0] as (..., 12, L)."""
    ctx = CTX
    l00, lxc, lyc = coeffs_fp2  # each (..., 2, L)
    ab = jnp.stack([lxc, lyc], axis=-3)  # (..., 2, 2, L)
    sc = jnp.stack(
        [jnp.stack([xp, xp], axis=-2), jnp.stack([yp, yp], axis=-2)], axis=-3
    )
    scaled = ctx.mul(ab, sc)
    lx, ly = scaled[..., 0, :, :], scaled[..., 1, :, :]
    z = jnp.zeros_like(lx)
    l00b = jnp.broadcast_to(l00, lx.shape)  # fixed-table coeffs are unbatched
    rows = jnp.concatenate([
        l00b[..., None, :, :], lx[..., None, :, :], z[..., None, :, :],
        z[..., None, :, :], ly[..., None, :, :], z[..., None, :, :],
    ], axis=-3)  # (..., 6, 2, L)
    return rows.reshape(rows.shape[:-3] + (12, NLIMBS))


def _dev_miller_fused(sig_x, sig_y, hm_x, hm_y, pk):
    """Fused dual Miller loop: e(sig, -g2) (table-driven) and e(hm, pk)
    (variable Q) share one accumulator — a single squaring chain.

    All coordinates are Montgomery-domain (..., NLIMBS) arrays; internally
    fp2 values are stacked as (..., 2, NLIMBS).
    """
    qx = jnp.stack([pk[0][0], pk[0][1]], axis=-2)  # (..., 2, L)
    qy = jnp.stack([pk[1][0], pk[1][1]], axis=-2)
    one = jnp.broadcast_to(jnp.asarray(CTX.one_mont), qx.shape[:-2] + (NLIMBS,))
    one2 = jnp.stack([one, jnp.zeros_like(one)], axis=-2)
    f0 = _stk_one(sig_x)
    T0 = (qx, qy, one2)
    xs = (
        jnp.asarray(_X_BITS_ARR),
        jnp.asarray(_FIXED_DBL),
        jnp.asarray(_FIXED_ADD),
    )

    def body(carry, x):
        f, T = carry
        bit, dbl_row, add_row = x
        mask = jnp.broadcast_to(bit, f.shape[:-2]).astype(bn.DTYPE)
        f = sqr12(f)
        # variable side: doubling + line at (hm_x, hm_y)
        T2, coeffs = _stk_dbl_step(T)
        f = mul12_line(f, _line_rows(coeffs, hm_x, hm_y))
        # fixed side: precomputed coefficients at (sig_x, sig_y)
        frow = (dbl_row[0], dbl_row[1], dbl_row[2])
        f = mul12_line(f, _line_rows(frow, sig_x, sig_y))
        # conditional addition step: select the LINES to identity when the
        # bit is 0 (select on 12 rows is far cheaper than a second mult path)
        Ta, acoeffs = _stk_add_step(T2, (qx, qy))
        ident = _stk_one(sig_x)
        la = _line_rows(acoeffs, hm_x, hm_y)
        lf = _line_rows((add_row[0], add_row[1], add_row[2]), sig_x, sig_y)
        mask_r = mask[..., None]
        f = mul12_line(f, _tree_select(mask_r, la, ident))
        f = mul12_line(f, _tree_select(mask_r, lf, ident))
        T = _tree_select(mask_r, Ta, T2)
        return (f, T), None

    # unroll=2: an earlier TPU compiler miscompiled the single-iteration
    # loop-back of this scan at batch >= ~64 (the (B, 12, L) carry came
    # back corrupted; batch 5 was fine, components all verified in
    # isolation).  Two steps per trip sidestepped the bad relayout.  Kept:
    # with it the kernel gives the host's verdicts on the v5e under libtpu
    # 0.0.34 at batch 64 (`chip_smoke.py --bls`, PERF.md "Bring-up");
    # unroll=1 has not been re-tried on today's compiler.
    (f, _), _ = lax.scan(body, (f0, T0), xs, unroll=2)
    return conj12(f)  # x < 0


def _dev_cyclo_exp_abs(m, bits_arr):
    """m^e (stacked) with e given MSB-first static bits; m cyclotomic."""

    def body(acc, bit):
        acc = sqr12(acc)
        mask = jnp.broadcast_to(bit, acc.shape[:-2]).astype(bn.DTYPE)[..., None]
        acc = _tree_select(mask, mul12(acc, m), acc)
        return acc, None

    # unroll=2: the same scan-carry workaround as the Miller loop (see
    # _dev_miller_fused)
    acc, _ = lax.scan(body, m, jnp.asarray(bits_arr[1:]), unroll=2)
    return acc


_XP1_BITS_ARR = np.asarray(_XP1_BITS, dtype=np.uint32)
_X_BITS_FULL_ARR = np.asarray(_X_BITS_FULL, dtype=np.uint32)


def _dev_final_exp(f):
    """Device final exponentiation — same chain as :func:`host_final_exp`."""
    f = mul12(conj12(f), inv12(f))
    f = mul12(frob12(frob12(f)), f)
    m = f
    expx = lambda z: conj12(_dev_cyclo_exp_abs(z, _X_BITS_FULL_ARR))
    expxm1 = lambda z: conj12(_dev_cyclo_exp_abs(z, _XP1_BITS_ARR))
    a = expxm1(m)
    a = expxm1(a)
    b = expx(a)
    a = mul12(b, frob12(a))
    c = _dev_cyclo_exp_abs(_dev_cyclo_exp_abs(a, _X_BITS_FULL_ARR), _X_BITS_FULL_ARR)
    a = mul12(mul12(c, frob12(frob12(a))), conj12(a))
    m3 = mul12(sqr12(m), m)
    return mul12(a, m3)


def _dev_is_one(f):
    """Stacked equality with 1: row 0 == 1_mont, rows 1..11 == 0."""
    one = jnp.broadcast_to(jnp.asarray(CTX.one_mont), f[..., 0, :].shape)
    mask = bn.eq(f[..., 0, :], one)
    rest = f[..., 1:, :]
    zero = (jnp.max(rest, axis=(-1, -2)) == 0).astype(bn.DTYPE)
    return mask * zero


def bls_verify_kernel(sig_x, sig_y, hm_x, hm_y, pk_x0, pk_x1, pk_y0, pk_y1, ok):
    """Batched BLS12-381 verification.  Pure, jittable.

    Each lane checks e(sig, -g2) * e(H(m), pk) == 1 with one fused dual
    Miller loop + one final exponentiation.  A lane may hold a single
    signature or a whole aggregated quorum — same cost either way; that is
    the point.  All inputs are (..., NLIMBS) uint32 Montgomery-domain limb
    arrays (see :func:`verify_inputs`); ok is the host-side validity mask
    (decode/on-curve/subgroup failures).  Returns a (...,) uint32 mask.
    """
    pk = ((pk_x0, pk_x1), (pk_y0, pk_y1))
    f = _dev_miller_fused(sig_x, sig_y, hm_x, hm_y, pk)
    f = _dev_final_exp(f)
    return _dev_is_one(f) * ok


def _encode_g1(pt) -> tuple[np.ndarray, np.ndarray]:
    return CTX.encode(pt[0]), CTX.encode(pt[1])


def verify_inputs(items) -> tuple[np.ndarray, ...]:
    """[(msg, sig96, pub192), ...] -> batched kernel inputs.

    Host-side work per item: deserialize, on-curve + r-torsion subgroup
    checks (memoized for the small static pubkey set), hash-to-G1
    (memoized per digest), Montgomery encoding.  Invalid items become
    generator-dummy lanes with ok=0.
    """
    n = len(items)
    shape = (n, NLIMBS)
    sig_x = np.zeros(shape, np.uint32)
    sig_y = np.zeros(shape, np.uint32)
    hm_x = np.zeros(shape, np.uint32)
    hm_y = np.zeros(shape, np.uint32)
    pk_x0 = np.zeros(shape, np.uint32)
    pk_x1 = np.zeros(shape, np.uint32)
    pk_y0 = np.zeros(shape, np.uint32)
    pk_y1 = np.zeros(shape, np.uint32)
    ok = np.zeros((n,), np.uint32)
    g1m = _encode_g1((G1X, G1Y))
    g2xm = _fp2_const_mont(G2X)
    g2ym = _fp2_const_mont(G2Y)
    for i, (msg, sig, pub) in enumerate(items):
        try:
            pk = _checked_pub(pub)
            s = _checked_sig(sig)
        except ValueError:
            sig_x[i], sig_y[i] = g1m
            hm_x[i], hm_y[i] = g1m
            pk_x0[i], pk_x1[i] = g2xm
            pk_y0[i], pk_y1[i] = g2ym
            continue
        hm = hash_to_g1(msg)
        sig_x[i], sig_y[i] = _encode_g1(s)
        hm_x[i], hm_y[i] = _encode_g1(hm)
        pk_x0[i], pk_x1[i] = _fp2_const_mont(pk[0])
        pk_y0[i], pk_y1[i] = _fp2_const_mont(pk[1])
        ok[i] = 1
    return sig_x, sig_y, hm_x, hm_y, pk_x0, pk_x1, pk_y0, pk_y1, ok


def aggregate_items(items):
    """Collapse same-message items into ONE kernel lane
    [(msg, sig, pub), ...] -> (msg, agg_sig, agg_pub).

    This is the quorum path: Q-1 commit votes over one proposal digest
    become a single pairing-equation lane (BASELINE "1 pairing/quorum").
    """
    if not items:
        raise ValueError("no items")
    msg = items[0][0]
    if any(m != msg for m, _, _ in items):
        raise ValueError("aggregate_items requires a common message")
    agg_sig = aggregate_sigs([s for _, s, _ in items])
    agg_pub = aggregate_pubs([p for _, _, p in items])
    return (msg, agg_sig, agg_pub)


verify_kernel = bls_verify_kernel
