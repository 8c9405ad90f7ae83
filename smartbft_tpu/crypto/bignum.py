"""Fixed-width big-integer arithmetic for TPU, on 16-bit limbs.

Design notes (TPU-first):

* A k-bit integer is stored little-endian as ``ceil(k/16)`` limbs of 16 bits
  each, in a ``uint32`` array whose last axis is the limb axis.  All ops are
  natively batched: any leading axes are batch axes, so a (B, n) array is a
  batch of B bignums and every primitive vectorizes on the VPU without
  ``vmap``.
* 16-bit limbs inside 32-bit lanes mean every partial product
  ``a_i * b_j <= (2^16-1)^2`` fits a uint32 lane, and a full schoolbook
  column (<= 2n terms of 16 bits) stays below 2^21 — so multiplication needs
  **no 64-bit arithmetic at all**.  TPUs have no native int64; this layout is
  why the kernels run at full VPU rate instead of through XLA's i64
  emulation.
* The only sequential parts are the carry/borrow chains, expressed as
  ``lax.scan`` along the limb axis (16-32 steps) while the batch dimension
  stays fully vectorized.
* Modular arithmetic is Montgomery-form (separated operand scanning: one
  full product, one low product by N', one full product by N).  The modulus
  is a Python int baked in at trace time via :class:`MontCtx`, so P-256's
  p and n, Ed25519's p and L, and BLS12-381's q all share this engine.

Replaces the host-language bigint the reference leans on implicitly via Go's
``crypto/ecdsa`` (/root/reference/internal/bft/view.go:537-541 is the
per-signature verify fan-out this engine batches).
"""

from __future__ import annotations

import numpy as np


import jax.numpy as jnp
from jax import lax

LIMB_BITS = 16
LIMB_MASK = (1 << LIMB_BITS) - 1
DTYPE = jnp.uint32

# Carry-chain scan unrolling (lax.scan unroll=N).  The chains are short
# (~25-50 steps) but appear inside every Montgomery op; for kernels whose
# scan bodies contain many of them (the pairing), unrolling trades while-loop
# count for straightline ops, which XLA often compiles much faster.
import os as _os

UNROLL = int(_os.environ.get("SMARTBFT_BN_UNROLL", "1") or "1")


# ---------------------------------------------------------------------------
# host <-> device conversion
# ---------------------------------------------------------------------------

def to_limbs(x: int, nlimbs: int) -> np.ndarray:
    """Python int -> little-endian 16-bit limb vector (numpy uint32)."""
    if x < 0:
        raise ValueError("negative")
    out = np.zeros(nlimbs, dtype=np.uint32)
    for i in range(nlimbs):
        out[i] = x & LIMB_MASK
        x >>= LIMB_BITS
    if x:
        raise ValueError("overflow: value does not fit in %d limbs" % nlimbs)
    return out


def from_limbs(arr) -> int:
    """Limb vector (1-D) -> Python int.  Host-side only."""
    a = np.asarray(arr, dtype=np.uint64)
    x = 0
    for i in range(a.shape[-1] - 1, -1, -1):
        x = (x << LIMB_BITS) | int(a[i])
    return x


def bytes_to_limbs(raw: bytes, nlimbs: int) -> np.ndarray:
    """Little-endian values of ``2 * nlimbs`` bytes each, joined ->
    (B, nlimbs) uint32 limbs: one read and one widening cast."""
    return np.frombuffer(raw, "<u2").astype(np.uint32).reshape(-1, nlimbs)


def batch_to_limbs(xs, nlimbs: int) -> np.ndarray:
    """List of Python ints -> (B, nlimbs) uint32, in bulk from bytes:
    what stacking :func:`to_limbs` of each value gives, with no
    interpreted work per limb."""
    width = nlimbs * LIMB_BITS // 8
    try:
        raw = b"".join([x.to_bytes(width, "little") for x in xs])
    except OverflowError as exc:  # int.to_bytes: negative, or too wide
        raise ValueError(
            "negative, or overflow: value does not fit in %d limbs" % nlimbs
        ) from exc
    return bytes_to_limbs(raw, nlimbs)


# ---------------------------------------------------------------------------
# carry / borrow chains
#
# Two interchangeable implementations, selected by SMARTBFT_BN_CHAIN:
#   'prefix' (default) — Kogge–Stone carry-lookahead: two local
#     redistribution passes reduce every residual carry to 0/1, then a
#     log2(m)-step (generate, propagate) parallel prefix resolves them.
#     ~12 data-dependent levels instead of m sequential scan steps, and —
#     critically — NO while-loop in the HLO: graphs with hundreds of
#     Montgomery ops compile minutes faster on XLA:CPU (copy-insertion is
#     superlinear in while-op count) and the TPU VPU pipeline stays full.
#   'scan' — the original lax.scan along the limb axis (kept for A/B and
#     as a hedge against Mosaic/XLA regressions).
# ---------------------------------------------------------------------------

CHAIN = _os.environ.get("SMARTBFT_BN_CHAIN", "prefix")


def _shift_up(x, s: int):
    """Limb shift toward higher index along the last axis (zero fill)."""
    pad = [(0, 0)] * (x.ndim - 1) + [(s, 0)]
    return jnp.pad(x, pad)[..., : x.shape[-1]]


def _resolve_prefix(x, m: int):
    """Resolve 0/1 residual carries of ``x`` (values <= 2^16) via
    Kogge–Stone prefix over (generate, propagate); returns (limbs, carry)
    with carry the (...,) carry out of limb m-1."""
    g = x >> LIMB_BITS  # 0/1 by precondition
    b = x & LIMB_MASK
    p = (b == LIMB_MASK).astype(DTYPE)
    G, P = g, p
    s = 1
    while s < m:
        G = G | (P & _shift_up(G, s))
        P = P & _shift_up(P, s)
        s <<= 1
    return (b + _shift_up(G, 1)) & LIMB_MASK, G[..., m - 1]


def carry_propagate(cols, out_len: int):
    """Normalize column sums (< 2^31 each) into 16-bit limbs.

    ``cols``: (..., m) uint32.  Returns (..., out_len) with out_len >= m.
    Any final carry out of limb out_len-1 is DISCARDED: callers either
    bound their inputs so it is zero, or rely on the mod-2^(16*out_len)
    truncation (redc_cols' m-computation does this deliberately).
    """
    m = cols.shape[-1]
    if out_len > m:
        pad = [(0, 0)] * (cols.ndim - 1) + [(0, out_len - m)]
        cols = jnp.pad(cols, pad)
    if CHAIN == "prefix":
        x = cols
        # two local passes: 2^31 -> carries < 2^15 -> values <= 2^16,
        # residual carries in {0, 1}
        for _ in range(2):
            x = (x & LIMB_MASK) + _shift_up(x >> LIMB_BITS, 1)
        limbs, _ = _resolve_prefix(x, out_len)
        return limbs
    x = jnp.moveaxis(cols, -1, 0)  # (out_len, ...)

    def step(c, col):
        t = col + c
        return t >> LIMB_BITS, t & LIMB_MASK

    _, limbs = lax.scan(step, jnp.zeros(x.shape[1:], DTYPE), x, unroll=UNROLL)
    return jnp.moveaxis(limbs, 0, -1)


def sub_borrow(a, b):
    """(a - b) mod 2^(16n) limb-wise; returns (diff, borrow_out).

    borrow_out is (...,) uint32: 1 when a < b.
    """
    if CHAIN == "prefix":
        b = jnp.broadcast_to(b, a.shape)
        n = a.shape[-1]
        # a - b = a + ~b + 1 (two's complement); carry-out <=> a >= b
        x = a + (jnp.uint32(LIMB_MASK) - b)
        x = jnp.concatenate(
            [x[..., :1] + jnp.uint32(1), x[..., 1:]], axis=-1
        )
        # one local pass: values < 2^17 -> <= 2^16, residual carries 0/1.
        # The top limb's local carry leaves the array here — it IS a carry
        # out of limb n-1, so it joins the prefix stage's (at most one of
        # the two can be set: the true carry-out is a single bit).
        hi = x >> LIMB_BITS
        x = (x & LIMB_MASK) + _shift_up(hi, 1)
        diff, carry = _resolve_prefix(x, n)
        return diff, jnp.uint32(1) - (carry | hi[..., n - 1])
    xa = jnp.moveaxis(a, -1, 0)
    xb = jnp.moveaxis(jnp.broadcast_to(b, a.shape), -1, 0)

    def step(borrow, ab):
        ai, bi = ab
        t = ai + jnp.uint32(1 << LIMB_BITS) - bi - borrow
        return jnp.uint32(1) - (t >> LIMB_BITS), t & LIMB_MASK

    borrow, limbs = lax.scan(
        step, jnp.zeros(xa.shape[1:], DTYPE), (xa, xb), unroll=UNROLL
    )
    return jnp.moveaxis(limbs, 0, -1), borrow


def geq(a, b):
    """a >= b as (...,) uint32 0/1."""
    _, borrow = sub_borrow(a, b)
    return jnp.uint32(1) - borrow


def select(mask, a, b):
    """mask ? a : b, broadcasting a (...,) mask over the limb axis."""
    return jnp.where(mask[..., None].astype(bool), a, b)


def is_zero(a):
    """(...,) uint32 1 if the bignum is zero."""
    return (jnp.max(a, axis=-1) == 0).astype(DTYPE)


def eq(a, b):
    """(...,) uint32 1 if equal limb-wise."""
    return jnp.all(a == b, axis=-1).astype(DTYPE)


def bits_msb(a, nbits: int):
    """Bit decomposition, most-significant first: (..., n) -> (..., nbits)."""
    idx = np.arange(nbits - 1, -1, -1)
    limb = idx // LIMB_BITS
    off = idx % LIMB_BITS
    return (a[..., limb] >> jnp.asarray(off, DTYPE)) & jnp.uint32(1)


def grouped(op, pairs):
    """Run independent binary field ops as ONE stacked call.

    The Montgomery ops' sequential carry chains broadcast over leading
    axes, so stacking k independent (a, b) pairs along a new axis shares
    the chains: k ops for the sequential cost of one.  This is the
    level-scheduling primitive behind the fast curve formulas.
    """
    shape = jnp.broadcast_shapes(*(jnp.shape(x) for pr in pairs for x in pr))
    a = jnp.stack([jnp.broadcast_to(x, shape) for x, _ in pairs])
    b = jnp.stack([jnp.broadcast_to(y, shape) for _, y in pairs])
    out = op(a, b)
    return tuple(out[i] for i in range(len(pairs)))


def grouped1(op, items):
    """Unary sibling of :func:`grouped` — k independent one-operand ops
    (squarings, negations) stacked into one call sharing the carry chains."""
    shape = jnp.broadcast_shapes(*(jnp.shape(x) for x in items))
    a = jnp.stack([jnp.broadcast_to(x, shape) for x in items])
    out = op(a)
    return tuple(out[i] for i in range(len(items)))


def digits_msb(a, ndigits: int, width: int = 2):
    """Fixed-width digit decomposition, most-significant digit first.

    (..., n) -> (..., ndigits), each digit in [0, 2**width).
    """
    bits = bits_msb(a, ndigits * width)
    bits = bits.reshape(bits.shape[:-1] + (ndigits, width))
    weights = jnp.asarray([1 << (width - 1 - k) for k in range(width)], DTYPE)
    return jnp.sum(bits * weights, axis=-1, dtype=DTYPE)


def joint_table(point_add, ps, qs):
    """Cross-join table for :func:`shamir_scan_w`: entry len(qs)*i + j is
    ps[i] + qs[j], all combination adds in ONE stacked point_add call."""
    lhs = jnp.stack([p for p in ps for _ in qs], axis=-3)
    rhs = jnp.stack([q for _ in ps for q in qs], axis=-3)
    return point_add(lhs, rhs)


def shamir_scan_w(point_add, table, ident, d1, d2, width: int = 2,
                  point_double=None):
    """Windowed Strauss–Shamir double-scalar mult.

    Per digit: ``width`` doublings + one gather + one addition — for w=2
    that is 3 point ops per 2 bits versus 4 for the bitwise scan, 25%
    fewer sequential point operations.  ``table`` is (..., 4**width, C, n)
    with entry i * 2**width + j holding i*P1 + j*P2; d1/d2 are
    (..., ndigits) MSB-first digits from :func:`digits_msb`.
    ``point_add`` must be complete (identity-safe); ``point_double``, when
    given, must be a complete dedicated doubling (cheaper than the general
    addition — squarings replace cross products).
    """
    dbl = point_double if point_double is not None else (
        lambda p: point_add(p, p))
    xs = (jnp.moveaxis(d1, -1, 0), jnp.moveaxis(d2, -1, 0))
    base = jnp.uint32(1 << width)

    def step(acc, ds):
        i, j = ds
        for _ in range(width):
            acc = dbl(acc)
        idx = (i * base + j).astype(jnp.int32)
        sel = jnp.take_along_axis(
            table, idx[..., None, None, None], axis=-3
        )[..., 0, :, :]
        return point_add(acc, sel), None

    acc, _ = lax.scan(step, ident, xs)
    return acc


def shamir_scan(point_add, table, ident, bits1, bits2):
    """Strauss–Shamir double-scalar-mult scan shared by every curve.

    Per bit: one doubling + one gather from ``table`` (shape (..., 4, C, n),
    entries [ident, P1, P2, P1+P2]) + one addition.  ``bits1``/``bits2`` are
    (..., nbits) MSB-first; points are (..., C, n) for any coordinate count C.
    ``point_add`` must be complete (identity-safe) — no branches are emitted.
    """
    xs = (jnp.moveaxis(bits1, -1, 0), jnp.moveaxis(bits2, -1, 0))

    def step(acc, bits):
        b1, b2 = bits
        acc = point_add(acc, acc)
        idx = (b1 + 2 * b2).astype(DTYPE)
        sel = jnp.take_along_axis(
            table, idx[..., None, None, None].astype(jnp.int32), axis=-3
        )[..., 0, :, :]
        return point_add(acc, sel), None

    acc, _ = lax.scan(step, ident, xs)
    return acc


# ---------------------------------------------------------------------------
# multiplication
# ---------------------------------------------------------------------------

def _put(x, off: int, total: int):
    """Zero-pad ``x`` to ``total`` columns with ``off`` leading zeros.

    The pad+add accumulation primitive (mirrors pallas_ecdsa._pad_rows):
    scatter-free HLO, since XLA:CPU expands ``.at[].add`` scatters into
    slow-to-compile, slow-to-run update loops."""
    pad = [(0, 0)] * (x.ndim - 1) + [(off, total - off - x.shape[-1])]
    return jnp.pad(x, pad)


def mul_columns(a, b):
    """Raw product columns: (..., n) x (..., n) -> (..., 2n) UNNORMALIZED.

    Schoolbook via shift-accumulate, WITHOUT the carry chain — zero
    sequential ops.  Row i of partial products lands in columns [i, i+n);
    each 32-bit product is split into 16-bit halves before accumulation, so
    column sums stay < 2^22; callers may add up to ~2^7 such column arrays
    together before normalizing (uint32 headroom), which is the basis of
    the lazy-reduction tower arithmetic: linear combinations of products
    cost vector adds only, and one carry chain + one Montgomery reduction
    amortizes over the whole combination.
    """
    n = a.shape[-1]
    bshape = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    acc = jnp.zeros(bshape + (2 * n,), DTYPE)
    for i in range(n):
        p = a[..., i : i + 1] * b
        acc = acc + _put(p & LIMB_MASK, i, 2 * n) + _put(
            p >> LIMB_BITS, i + 1, 2 * n
        )
    return acc


def mul_columns_low(a, b):
    """Low-n product columns only: a*b mod 2^(16n), unnormalized.

    The Montgomery m-step (m = T_lo * N' mod R) discards the high half of
    the product; skipping partial products landing at column >= n halves
    this step's lane-mult count."""
    n = a.shape[-1]
    bshape = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    acc = jnp.zeros(bshape + (n,), DTYPE)
    for i in range(n):
        p = a[..., i : i + 1] * b[..., : n - i]  # columns i..n-1
        acc = acc + _put(p & LIMB_MASK, i, n)
        if i + 1 < n:
            acc = acc + _put((p >> LIMB_BITS)[..., : n - i - 1], i + 1, n)
    return acc


def square_columns(a):
    """Raw squaring columns: (..., n) -> (..., 2n) UNNORMALIZED.

    Same contract as :func:`mul_columns` with b = a, but computes only the
    n(n+1)/2 upper-triangle partial products and weights the off-diagonal
    ones by 2 (the halves are doubled *after* the 16-bit split, so nothing
    overflows a uint32 lane) — 136 lane-mults instead of 256 at n = 16.
    Column sums stay < 2^23, well inside :func:`carry_propagate`'s budget,
    and the output is valid input for :meth:`MontCtx.redc_cols`.
    """
    n = a.shape[-1]
    acc = jnp.zeros(a.shape[:-1] + (2 * n,), DTYPE)
    for i in range(n):
        row = a[..., i : i + 1] * a[..., i:]  # j = i..n-1 -> column i+j
        w = np.full(n - i, 2, dtype=np.uint32)
        w[0] = 1  # the diagonal term a_i^2 counts once
        wj = jnp.asarray(w)
        acc = acc + _put((row & LIMB_MASK) * wj, 2 * i, 2 * n) + _put(
            (row >> LIMB_BITS) * wj, 2 * i + 1, 2 * n
        )
    return acc


def mul_full(a, b):
    """Full product: (..., n) x (..., n) -> (..., 2n), normalized limbs."""
    n = a.shape[-1]
    return carry_propagate(mul_columns(a, b), 2 * n + 1)[..., : 2 * n]


def add_raw(a, b, out_len: int):
    """Plain (non-modular) limb addition with carry normalization."""
    m = max(a.shape[-1], b.shape[-1])
    pad_a = [(0, 0)] * (a.ndim - 1) + [(0, m - a.shape[-1])]
    pad_b = [(0, 0)] * (b.ndim - 1) + [(0, m - b.shape[-1])]
    cols = jnp.pad(a, pad_a) + jnp.pad(b, pad_b)
    return carry_propagate(cols, out_len)


# ---------------------------------------------------------------------------
# Montgomery context
# ---------------------------------------------------------------------------

class MontCtx:
    """Montgomery arithmetic mod an odd ``modulus`` over ``nlimbs`` limbs.

    All device methods accept/return (..., nlimbs) uint32 arrays in the
    Montgomery domain unless noted.  Constants are precomputed with Python
    ints at construction and baked into the trace as numpy constants.
    """

    def __init__(self, modulus: int, nlimbs: int):
        if modulus % 2 == 0:
            raise ValueError("modulus must be odd")
        self.modulus = modulus
        self.n = nlimbs
        R = 1 << (LIMB_BITS * nlimbs)
        if modulus >= R:
            raise ValueError("modulus too large for limb count")
        self.R = R
        self.N = to_limbs(modulus, nlimbs)
        self.N_ext = to_limbs(modulus, nlimbs + 1)
        self.R2 = to_limbs((R * R) % modulus, nlimbs)
        self.Nprime = to_limbs((-pow(modulus, -1, R)) % R, nlimbs)
        self.one_mont = to_limbs(R % modulus, nlimbs)  # 1 in Mont domain
        self.zero = to_limbs(0, nlimbs)

    # -- domain conversion --------------------------------------------------

    def to_mont(self, a):
        return self.mul(a, jnp.asarray(self.R2))

    def from_mont(self, a):
        return self.mul(a, jnp.asarray(to_limbs(1, self.n)))

    def encode(self, x: int) -> np.ndarray:
        """Host: Python int -> Montgomery-domain limbs (numpy)."""
        return to_limbs((x * self.R) % self.modulus, self.n)

    def decode(self, arr) -> int:
        """Host: Montgomery-domain limbs -> Python int."""
        return (from_limbs(arr) * pow(self.R, -1, self.modulus)) % self.modulus

    # -- core ops -----------------------------------------------------------

    def mul(self, a, b):
        """Montgomery product a*b*R^-1 mod N — the k=1 case of
        :meth:`redc_cols`: 4 sequential carry chains instead of the naive
        five (three normalized mul_fulls + accumulate + subtract)."""
        return self.redc_cols(mul_columns(a, b))

    def square(self, a):
        """Montgomery square via :func:`square_columns` — ~47% fewer lane
        mults than :meth:`mul`; same 4 sequential carry chains."""
        return self.redc_cols(square_columns(a))

    def redc_cols(self, cols):
        """Montgomery-reduce raw product columns: (..., 2n) -> (..., n) < N.

        ``cols`` is a sum of k column arrays from :func:`mul_columns` over
        operands < N, with k strictly less than R/N — the exact requirement
        is k * N^2 < R * N, i.e. the summed value T < R*N.  (For BLS12-381
        with R = 2^384, R/P is ~9.84, so k <= 9 is safe even though
        floor(R/P) = 9.)
        Output is (T + mN)/R mod N, strictly < N after one conditional
        subtract.  Exactly 4 sequential chains regardless of how many
        outputs are stacked in the leading axes — the whole point.
        """
        n = self.n
        T = carry_propagate(cols, 2 * n + 1)
        m = mul_columns_low(T[..., :n], jnp.asarray(self.Nprime))
        m = carry_propagate(m, n)  # low n limbs: mod R
        s = carry_propagate(
            jnp.pad(T, [(0, 0)] * (T.ndim - 1) + [(0, 1)])
            + jnp.pad(mul_columns(m, jnp.asarray(self.N)),
                      [(0, 0)] * (T.ndim - 1) + [(0, 2)]),
            2 * n + 2,
        )
        r = s[..., n : 2 * n + 1]  # (..., n+1), value < 2N
        d, borrow = sub_borrow(r, jnp.asarray(self.N_ext))
        return select(borrow, r, d)[..., :n]

    def add(self, a, b):
        s = add_raw(a, b, self.n + 1)
        d, borrow = sub_borrow(s, jnp.asarray(self.N_ext))
        return select(borrow, s, d)[..., : self.n]

    def sub(self, a, b):
        d, borrow = sub_borrow(a, b)
        wrapped = add_raw(d, jnp.asarray(self.N), self.n + 1)[..., : self.n]
        return select(borrow, wrapped, d)

    def neg(self, a):
        """-a mod N (a in [0, N))."""
        d, _ = sub_borrow(jnp.broadcast_to(jnp.asarray(self.N), a.shape), a)
        return select(is_zero(a), a, d)

    def dbl(self, a):
        return self.add(a, a)

    def reduce_once(self, a):
        """One conditional subtract: a in [0, 2N) -> a mod N."""
        d, borrow = sub_borrow(a, jnp.asarray(self.N))
        return select(borrow, a, d)

    # -- exponentiation (static exponent) ------------------------------------

    def exp(self, a, e: int, window: int = 4):
        """a^e mod N for a *static* Python-int exponent; a in Mont domain.

        Fixed-window exponentiation as a ``lax.scan`` over the exponent's
        base-2^w digits (MSB first): w cheap squarings + one gather from
        the 2^w-entry power table + one multiply per digit.  Digit 0
        gathers a^0 = 1~ whose Montgomery product is the identity, so the
        body needs no select.  Versus bitwise square-and-multiply this
        trades 256 always-on multiplies for ~64 + a 14-mult table build.
        """
        if e < 0:
            raise ValueError("negative exponent")
        one = jnp.broadcast_to(jnp.asarray(self.one_mont), a.shape)
        if e == 0:
            return one
        if e.bit_length() <= window:  # tiny exponent: straightline
            out = a
            for bit in bin(e)[3:]:
                out = self.square(out)
                if bit == "1":
                    out = self.mul(out, a)
            return out

        # power table a^0 .. a^(2^w - 1), built in log depth with grouped
        # calls: each round squares/multiplies everything derivable so far.
        pows: list = [one, a]
        while len(pows) < (1 << window):
            have = len(pows)
            take = min(have - 1, (1 << window) - have)
            new = grouped(self.mul, [(pows[have - 1], pows[i + 1])
                                     for i in range(take)])
            pows.extend(new)
        table = jnp.stack(pows, axis=-2)  # (..., 2^w, n)

        ndig = (e.bit_length() + window - 1) // window
        digs = np.array(
            [(e >> (window * i)) & ((1 << window) - 1)
             for i in range(ndig - 1, -1, -1)], dtype=np.int32,
        )

        def step(acc, dig):
            for _ in range(window):
                acc = self.square(acc)
            sel = jnp.take(table, dig, axis=-2)  # digit is batch-uniform
            return self.mul(acc, sel), None

        # first digit is nonzero (e > 0): seed with its table entry
        acc0 = jnp.broadcast_to(table[..., int(digs[0]), :], a.shape)
        out, _ = lax.scan(step, acc0, jnp.asarray(digs[1:]))
        return out

    def inv(self, a):
        """a^-1 mod N via Fermat (N must be prime); Mont domain in/out."""
        return self.exp(a, self.modulus - 2)
