"""Ed25519 verification as Pallas kernels: a static-key comb-table kernel
for the orderers' ring (:func:`eddsa_verify_comb`) and an arbitrary-key
kernel for every other key (:func:`ed25519_verify`, section at the end).

The twisted-Edwards analogue of :mod:`pallas_comb` — replacing the same
reference hot path (one goroutine per commit-signature verify,
/root/reference/internal/bft/view.go:537-541) for the alt-curve variant of
BASELINE.md configs[3].  The cofactorless verification equation
``[S]B == R + [h]A`` is evaluated as ``[S]B + [h](-A) == R``:

* both bases are STATIC — B is the RFC 8032 base point and A is one of n
  replica keys fixed at configuration — so each gets a host-precomputed
  Lim-Lee comb table (w=8 teeth, stride 32; the key tables store the
  NEGATED public point so the scan only ever adds);
* there is NO scalar inversion anywhere, so the kernel is just the
  32-iteration comb walk (1 doubling + 2 unified additions each) plus the
  projective comparison against R — even simpler than P-256's;
* table entries are affine Edwards points (identity (0, 1) included — the
  a=-1 unified formulas are complete), stored as split-byte Montgomery
  rows [X, Y, T=x*y] with Z == 1 implicit, selected by one-hot bf16
  matmuls on the MXU exactly like pallas_comb;
* the public key's curve membership is checked once at registration
  (host ints), R's at every verify in-kernel (R arrives per signature).

Host-side marshalling (SHA-512, point decompression, the s < L range
check) mirrors the existing XLA kernel path (:mod:`ed25519`).

The arbitrary-key kernel checks the same cofactorless equation for keys
that have no table (a channel's client identities: thousands).  One
accumulator walks h's 4-bit windows over a per-lane table of j·(-A) built
in the kernel (-A decoded on the host once per key), and during the last
eight windows each doubling also adds B's comb entry for S (the same
one-hot MXU selects).  R is never decoded: the kernel encodes its result
(one inversion by exponentiation, then y and x's sign bit) and compares
that with R's 32 bytes, as OpenSSL does, so a non-canonical or off-curve
R fails the comparison.  The host hashes, and checks lengths and
``S < L`` on whole arrays (:func:`prep_inputs`).
"""

from __future__ import annotations

import functools
import hashlib

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import ed25519 as ed
from .bignum import to_limbs
from .ed25519 import BX, BY, D, L, P
from .pallas_comb import (
    ROWS,
    STRIDE,
    TEETH,
    TSIZE,
    VMEM_LIMIT_BYTES,
    CombKeyRegistry,
    CombVerifier,
    _comb_digits,
    _maybe_unpack,
)
from .pallas_ecdsa import LIMB_BITS, NL, _ccol, _digits_w, _eq, _Fld, _grp, \
    _grp1, _is_zero, _limbs, _select, _sub_borrow

R_MONT = 1 << (LIMB_BITS * NL)

_P_ED = _limbs(P)
_L_ED = _limbs(L)
_P_NPRIME_ED = _limbs((-pow(P, -1, R_MONT)) % R_MONT)
_P_R2_ED = _limbs((R_MONT * R_MONT) % P)
_P_ONE_ED = _limbs(R_MONT % P)
_D_MONT_ED = _limbs((D * R_MONT) % P)
_D2_MONT_ED = _limbs((2 * D * R_MONT) % P)


# ---------------------------------------------------------------------------
# host-side tables
# ---------------------------------------------------------------------------


def is_on_curve_int(pt) -> bool:
    """-x² + y² == 1 + d x² y² (mod p) for an affine Edwards point."""
    x, y = pt
    if not (0 <= x < P and 0 <= y < P):
        return False
    return (y * y - x * x - 1 - D * x * x % P * (y * y % P)) % P == 0


def _comb_entries(point) -> list:
    """All 2^TEETH subset sums of {2^(STRIDE·t)·point : t < TEETH}."""
    bases = [point]
    for _ in range(TEETH - 1):
        b = bases[-1]
        for _ in range(STRIDE):
            b = ed._aff_add(b, b)
        bases.append(b)
    table = [(0, 1)] * TSIZE
    for idx in range(1, TSIZE):
        low = idx & -idx
        table[idx] = ed._aff_add(table[idx ^ low], bases[low.bit_length() - 1])
    return table


def _mont_limbs(v: int) -> np.ndarray:
    return np.asarray(to_limbs((v * R_MONT) % P, NL), np.uint32)


def build_table(point) -> np.ndarray:
    """(ROWS, TSIZE) float32 comb table for one affine Edwards point.

    Rows [0:48] are low bytes of (X, Y, T=x·y) Montgomery limbs, [48:96]
    the high bytes; Z == 1 for every entry (the identity (0, 1) is an
    ordinary affine point on this curve).
    """
    entries = _comb_entries(point)
    out = np.zeros((ROWS, TSIZE), dtype=np.float32)
    for idx, (x, y) in enumerate(entries):
        limbs = np.concatenate(
            [_mont_limbs(x), _mont_limbs(y), _mont_limbs(x * y % P)]
        )
        out[:48, idx] = limbs & 0xFF
        out[48:, idx] = limbs >> 8
    return out


def _neg_pub_table(pub_pt) -> np.ndarray:
    """Comb table of -A for a decompressed public point A."""
    x, y = pub_pt
    return build_table(((P - x) % P, y))


@functools.lru_cache(maxsize=1)
def b_table() -> np.ndarray:
    return build_table((BX, BY))


# ---------------------------------------------------------------------------
# limb-major twisted-Edwards ops (points are (..., 4, NL, B): X, Y, Z, T)
# ---------------------------------------------------------------------------


def _ed_add(fp, d2, p, q):
    """Unified add-2008-hwcd-3 (a = -1); complete, mirrors ed.point_add."""
    x1, y1, z1, t1 = (p[..., i, :, :] for i in range(4))
    x2, y2, z2, t2 = (q[..., i, :, :] for i in range(4))
    s1, s2 = _grp(fp.sub, [(y1, x1), (y2, x2)])
    a1, a2, z1d = _grp(fp.add, [(y1, x1), (y2, x2), (z1, z1)])
    a, b, c1, d = _grp(fp.mul, [(s1, s2), (a1, a2), (t1, d2), (z1d, z2)])
    c = fp.mul(c1, t2)
    e, ff = _grp(fp.sub, [(b, a), (d, c)])
    g, h = _grp(fp.add, [(d, c), (b, a)])
    x3, y3, t3, z3 = _grp(fp.mul, [(e, ff), (g, h), (e, h), (ff, g)])
    return jnp.stack([x3, y3, z3, t3], axis=-3)


def _ed_dbl(fp, p, with_t: bool = True):
    """dbl-2008-hwcd with both halves negated (a = -1); mirrors
    ed.point_double.  T input unused.  ``with_t=False``: T out is left
    zero, a multiplication saved, for a doubling only a doubling follows."""
    x, y, z = p[..., 0, :, :], p[..., 1, :, :], p[..., 2, :, :]
    xy = fp.add(x, y)
    a, b, zz, s = _grp1(fp.sqr, [x, y, z, xy])
    c, h = _grp(fp.add, [(zz, zz), (a, b)])
    g, e1 = _grp(fp.sub, [(b, a), (s, a)])
    e = fp.sub(e1, b)
    ff = fp.sub(c, g)
    if not with_t:
        x3, y3, z3 = _grp(fp.mul, [(e, ff), (g, h), (ff, g)])
        return jnp.stack([x3, y3, z3, jnp.zeros_like(x3)], axis=-3)
    x3, y3, z3, t3 = _grp(fp.mul, [(e, ff), (g, h), (ff, g), (e, h)])
    return jnp.stack([x3, y3, z3, t3], axis=-3)


def _sel_ed(table_f32, one_p):
    """(ROWS, B) selected columns -> (4, NL, B) extended point, Z = 1."""
    lo = table_f32[:48, :]
    hi = table_f32[48:, :]
    limbs = (lo + hi * 256.0).astype(jnp.int32).astype(jnp.uint32)
    x, y, t = limbs[0:NL], limbs[NL:2 * NL], limbs[2 * NL:3 * NL]
    return jnp.stack([x, y, jnp.broadcast_to(one_p, x.shape), t], axis=-3)


def _kernel(nkeys, s_ref, h_ref, rx_ref, ry_ref, ok_ref, kidx_ref, btab_ref,
            qtab_ref, out_ref, idx_scratch):
    s, h = s_ref[:], h_ref[:]
    rx, ry = rx_ref[:], ry_ref[:]
    kidx = kidx_ref[0, :]
    nb = s.shape[-1]
    fp = _Fld(_P_ED, _P_NPRIME_ED, nb)
    one_p = _ccol(_P_ONE_ED, nb)
    p_r2 = _ccol(_P_R2_ED, nb)
    d2 = _ccol(_D2_MONT_ED, nb)
    d_m = _ccol(_D_MONT_ED, nb)
    zero = jnp.zeros((NL, nb), jnp.uint32)
    ident = jnp.stack([zero, one_p, one_p, zero], axis=-3)

    for k, v in enumerate(_comb_digits(s, nb)):
        idx_scratch[k, :] = v
    for k, v in enumerate(_comb_digits(h, nb)):
        idx_scratch[STRIDE + k, :] = v

    # R into the Montgomery domain + on-curve check (A was checked at
    # registration; R arrives with every signature)
    rxm, rym = _grp(fp.mul, [(rx, p_r2), (ry, p_r2)])
    xx, yy = _grp1(fp.sqr, [rxm, rym])
    lhs = fp.sub(yy, xx)
    rhs = fp.add(one_p, fp.mul(d_m, fp.mul(xx, yy)))
    r_oncurve = _eq(lhs, rhs)

    btab = btab_ref[:]
    qtab = qtab_ref[:]
    iota_t = lax.broadcasted_iota(jnp.int32, (TSIZE, nb), 0)

    def scan_body(i, acc):
        acc = _ed_dbl(fp, acc)
        sd = idx_scratch[pl.ds(i, 1), :][0]
        hd = idx_scratch[pl.ds(i + STRIDE, 1), :][0]
        oh_b = (iota_t == sd[None, :]).astype(jnp.bfloat16)
        oh_q = (iota_t == hd[None, :]).astype(jnp.bfloat16)
        sel_b = jnp.dot(btab, oh_b, preferred_element_type=jnp.float32)
        aq = jnp.dot(qtab, oh_q, preferred_element_type=jnp.float32)
        sq = jnp.zeros((ROWS, nb), jnp.float32)
        for k in range(nkeys):
            mask = (kidx == k).astype(jnp.float32)[None, :]
            sq = sq + aq[k * ROWS:(k + 1) * ROWS, :] * mask
        acc = _ed_add(fp, d2, acc, _sel_ed(sel_b, one_p))
        return _ed_add(fp, d2, acc, _sel_ed(sq, one_p))

    acc = lax.fori_loop(0, STRIDE, scan_body, ident)
    xz, yz, z = acc[..., 0, :, :], acc[..., 1, :, :], acc[..., 2, :, :]
    # Z != 0 guard: complete Edwards formulas never produce Z = 0 from
    # valid inputs, but a zero (padding) table entry would drive the
    # accumulator to the all-zero point, which the projective comparison
    # below otherwise matches (0 == 0) for EVERY lane — a false accept
    not_zero = jnp.uint32(1) - _is_zero(z)
    mx, my = _grp(fp.mul, [(rxm, z), (rym, z)])
    match = _eq(mx, xz) * _eq(my, yz)
    out_ref[:] = (match * not_zero * r_oncurve * ok_ref[0, :])[None, :]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def eddsa_verify_comb(s, h, rx, ry, ok, kidx, btab, qtab, tile: int = 128,
                      interpret: bool = False):
    """Batched Ed25519 verify against registered keys.

    ``s, h, rx, ry``: (B, 32) uint8 little-endian (or (B, 16) uint32
    limbs); ``ok``: (B,) host pre-check mask (decompression, s < L);
    ``kidx``: per-lane key index; ``btab``/``qtab``: comb tables.
    Returns the (B,) uint32 validity mask; padded lanes (ok = 0) fail.
    """
    from jax.experimental.pallas import tpu as pltpu

    if tile % 128 and not interpret:
        raise ValueError(f"tile must be a multiple of 128 lanes, got {tile}")
    if qtab.shape[0] % ROWS:
        raise ValueError("qtab row count must be a multiple of 96")
    nkeys = qtab.shape[0] // ROWS

    s, h, rx, ry = (_maybe_unpack(a) for a in (s, h, rx, ry))
    bsz = s.shape[0]
    pad = (-bsz) % tile
    if pad:
        s, h, rx, ry = (jnp.pad(jnp.asarray(a), ((0, pad), (0, 0)))
                        for a in (s, h, rx, ry))
        kidx = jnp.pad(jnp.asarray(kidx), (0, pad))
        ok = jnp.pad(jnp.asarray(ok), (0, pad))
    total = s.shape[0]
    args = [jnp.transpose(jnp.asarray(a)).astype(jnp.uint32)
            for a in (s, h, rx, ry)]
    kidx = jnp.asarray(kidx, jnp.int32).reshape(1, total)
    ok = jnp.asarray(ok, jnp.uint32).reshape(1, total)
    btab = jnp.asarray(btab, jnp.bfloat16)
    qtab = jnp.asarray(qtab, jnp.bfloat16)

    spec = pl.BlockSpec((NL, tile), lambda i: (0, i))
    lane_spec = pl.BlockSpec((1, tile), lambda i: (0, i))
    out = pl.pallas_call(
        functools.partial(_kernel, nkeys),
        out_shape=jax.ShapeDtypeStruct((1, total), jnp.uint32),
        grid=(total // tile,),
        in_specs=[spec] * 4 + [lane_spec, lane_spec,
                               pl.BlockSpec((ROWS, TSIZE), lambda i: (0, 0)),
                               pl.BlockSpec((nkeys * ROWS, TSIZE),
                                            lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        scratch_shapes=[pltpu.VMEM((2 * STRIDE, tile), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(*args, ok, kidx, btab, qtab)
    return out[0, :bsz]


# ---------------------------------------------------------------------------
# arbitrary-key kernel: any key, one per lane
# ---------------------------------------------------------------------------

#: h's window: WIN-bit digits, MSB first, over a per-lane table of the
#: 2^WIN multiples of -A built in the kernel (h < L < 2^253)
WIN = 4
NWIN = 256 // WIN
#: the last NTAIL windows' doublings are the STRIDE doublings of B's comb
NTAIL = STRIDE // WIN
#: lanes of one grid step.  As P-256's arbitrary-key kernel: the batch
#: fills the VPU's 128 lanes, and at 128 the live set (the 16-entry
#: table of -A, 512 KiB, and the 7-wide stacked additions that build it)
#: stays well inside the 16 MiB of scoped VMEM
TILE = 128


def _sqr_n(fp, x, n: int):
    if n <= 2:
        for _ in range(n):
            x = fp.sqr(x)
        return x
    return lax.fori_loop(0, n, lambda _, v: fp.sqr(v), x)


def _inv_p(fp, z):
    """1/z = z^(p-2) in the Montgomery domain: ref10's addition chain,
    254 squarings and 11 multiplications."""
    z2 = fp.sqr(z)
    z9 = fp.mul(_sqr_n(fp, z2, 2), z)
    z11 = fp.mul(z9, z2)
    z5 = fp.mul(fp.sqr(z11), z9)               # z^(2^5 - 1)
    z10 = fp.mul(_sqr_n(fp, z5, 5), z5)
    z20 = fp.mul(_sqr_n(fp, z10, 10), z10)
    z40 = fp.mul(_sqr_n(fp, z20, 20), z20)
    z50 = fp.mul(_sqr_n(fp, z40, 10), z10)
    z100 = fp.mul(_sqr_n(fp, z50, 50), z50)
    z200 = fp.mul(_sqr_n(fp, z100, 100), z100)
    z250 = fp.mul(_sqr_n(fp, z200, 50), z50)
    return fp.mul(_sqr_n(fp, z250, 5), z11)    # z^(2^255 - 21)


def _neg_a_table(fp, d2, ident, na):
    """(2^WIN, 4, NL, B): j·(-A) for j < 16, in four stacked levels."""
    two = _ed_dbl(fp, na)
    l2 = _ed_add(fp, d2, jnp.stack([two, two]), jnp.stack([na, two]))
    three, four = l2[0], l2[1]
    l3 = _ed_add(fp, d2, jnp.stack([four] * 4),
                 jnp.stack([na, two, three, four]))
    low = [na, two, three, four, l3[0], l3[1], l3[2]]
    eight = l3[3]
    l4 = _ed_add(fp, d2, jnp.stack([eight] * 7), jnp.stack(low))
    return jnp.stack([ident, *low, eight, *(l4[i] for i in range(7))])


def _verify_kernel(s_ref, h_ref, r_ref, ax_ref, ay_ref, ok_ref, btab_ref,
                   out_ref, idx_scratch):
    """[S]B + [h](-A), encoded, against R's 32 bytes.  One shared
    accumulator: NWIN windows of h (WIN doublings, one table add each);
    in the last NTAIL, every doubling also adds B's comb entry for s."""
    s, h, r = s_ref[:], h_ref[:], r_ref[:]
    nb = s.shape[-1]
    fp = _Fld(_P_ED, _P_NPRIME_ED, nb)
    one_p = _ccol(_P_ONE_ED, nb)
    p_r2 = _ccol(_P_R2_ED, nb)
    d2 = _ccol(_D2_MONT_ED, nb)
    zero = jnp.zeros((NL, nb), jnp.uint32)
    ident = jnp.stack([zero, one_p, one_p, zero], axis=-3)

    # rows [0, STRIDE): B's comb columns of s; then h's windows
    for k, v in enumerate(_comb_digits(s, nb)):
        idx_scratch[k, :] = v
    for k, v in enumerate(_digits_w(h, NWIN, WIN)):
        idx_scratch[STRIDE + k, :] = v.astype(jnp.int32)

    # -A, decoded and negated on the host once per key
    xm, ym = _grp(fp.mul, [(ax_ref[:], p_r2), (ay_ref[:], p_r2)])
    na = jnp.stack([xm, ym, one_p, fp.mul(xm, ym)], axis=-3)
    table = _neg_a_table(fp, d2, ident, na)

    def window(i, acc):
        d = idx_scratch[pl.ds(STRIDE + i, 1), :][0]
        sel = jnp.zeros((4, NL, nb), jnp.uint32)
        for k in range(1 << WIN):  # masked accumulation: no per-lane gather
            sel = sel + table[k] * (d == k).astype(jnp.uint32)[None, None, :]
        return _ed_add(fp, d2, acc, sel)

    def head(i, acc):
        acc = lax.fori_loop(0, WIN - 1,
                            lambda _, a: _ed_dbl(fp, a, with_t=False), acc)
        return window(i, _ed_dbl(fp, acc))

    btab = btab_ref[:]
    iota_t = lax.broadcasted_iota(jnp.int32, (TSIZE, nb), 0)

    def comb_step(k, acc):
        acc = _ed_dbl(fp, acc)
        sd = idx_scratch[pl.ds(k, 1), :][0]
        oh = (iota_t == sd[None, :]).astype(jnp.bfloat16)
        sel = jnp.dot(btab, oh, preferred_element_type=jnp.float32)
        return _ed_add(fp, d2, acc, _sel_ed(sel, one_p))

    def tail(j, acc):
        acc = lax.fori_loop(WIN * j, WIN * (j + 1), comb_step, acc)
        return window(NWIN - NTAIL + j, acc)

    acc = lax.fori_loop(0, NWIN - NTAIL, head, ident)
    acc = lax.fori_loop(0, NTAIL, tail, acc)

    # encode (RFC 8032 5.1.2): y = Y/Z fully reduced, x's low bit on top;
    # R's bytes must be that encoding (a y >= p in R never is)
    z = acc[2]
    zi = _inv_p(fp, z)
    xa, ya = _grp(fp.mul, [(acc[0], zi), (acc[1], zi)])
    one_raw = _ccol(_limbs(1), nb)
    xs, ys = _grp(fp.mul, [(xa, one_raw), (ya, one_raw)])
    top = r[NL - 1]
    r_y = jnp.concatenate([r[:NL - 1], (top & jnp.uint32(0x7FFF))[None]])
    same_sign = jnp.uint32(1) - ((xs[0] & jnp.uint32(1)) ^ (top >> 15))
    # Z = 0 never comes of valid inputs; a padding lane's (0, 0) "point"
    # could drive it there, and 0/0 encodes as y = 0
    not_zero = jnp.uint32(1) - _is_zero(z)
    out_ref[:] = (_eq(ys, r_y) * same_sign * not_zero
                  * ok_ref[0, :])[None, :]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def ed25519_verify(s, h, r, ax, ay, ok, tile: int = TILE,
                   interpret: bool = False):
    """Batched Ed25519 verify for arbitrary keys, one fused Pallas kernel.

    ``s``, ``h``, ``r``, ``ax``, ``ay``: (B, 16) uint32 little-endian
    16-bit limbs of S, h = SHA-512(R || A || M) mod L, R's 32 bytes as they
    are, and -A's affine coordinates; ``ok``: (B,) uint32 host checks
    (:func:`prep_inputs`).  Returns the (B,) uint32 validity mask of the
    cofactorless check [S]B + [h](-A) == R; padded lanes (ok = 0) fail."""
    from jax.experimental.pallas import tpu as pltpu

    if tile % 128 and not interpret:
        raise ValueError(f"tile must be a multiple of 128 lanes, got {tile}")
    bsz = s.shape[0]
    pad = (-bsz) % tile
    if pad:
        s, h, r, ax, ay = (jnp.pad(jnp.asarray(a), ((0, pad), (0, 0)))
                           for a in (s, h, r, ax, ay))
        ok = jnp.pad(jnp.asarray(ok), (0, pad))
    total = s.shape[0]
    args = [jnp.transpose(jnp.asarray(a)).astype(jnp.uint32)
            for a in (s, h, r, ax, ay)]
    ok = jnp.asarray(ok, jnp.uint32).reshape(1, total)
    btab = jnp.asarray(b_table(), jnp.bfloat16)

    spec = pl.BlockSpec((NL, tile), lambda i: (0, i))
    lane_spec = pl.BlockSpec((1, tile), lambda i: (0, i))
    out = pl.pallas_call(
        _verify_kernel,
        out_shape=jax.ShapeDtypeStruct((1, total), jnp.uint32),
        grid=(total // tile,),
        in_specs=[spec] * 5 + [lane_spec,
                               pl.BlockSpec((ROWS, TSIZE), lambda i: (0, 0))],
        out_specs=lane_spec,
        scratch_shapes=[pltpu.VMEM((STRIDE + NWIN, tile), jnp.int32)],
        interpret=interpret,
    )(*args, ok, btab)
    return out[0, :bsz]


_L_WORDS = tuple((L >> (64 * i)) & ((1 << 64) - 1) for i in range(4))
_NO_SIG = bytes(64)
_NO_KEY = np.zeros(2 * NL, np.uint32)


#: a key handed in as plain bytes (a probe, a direct caller), decoded once
_held = functools.lru_cache(maxsize=1024)(ed.PublicKey)


def key_limbs(pub):
    """-A of a public key as (32,) uint32 limbs, x then y, or None where
    it does not decode: what an :class:`ed25519.PublicKey` carries from
    its enrollment, else decoded here (memoised)."""
    if not isinstance(pub, ed.PublicKey):
        pub = _held(bytes(pub))
    return pub.neg_limbs


def prep_inputs(items) -> tuple:
    """``(msg, sig, pub)`` items -> ((s, h, r, ax, ay), ok, refused): the
    arbitrary-key kernel's inputs and the lanes the host refused, by
    cause.  Per lane only SHA-512(R || A || M) mod L is Python; the
    lengths and s < L (RFC 8032 5.1.7) are checked on whole arrays, R is
    left to the kernel, A's point is the key's own."""
    n = len(items)
    keys = [key_limbs(pub) for _, _, pub in items]
    well = np.fromiter((len(sig) == 64 and key is not None
                        for (_, sig, _), key in zip(items, keys)), bool, n)
    raw = np.frombuffer(b"".join(sig if w else _NO_SIG for (_, sig, _), w
                                 in zip(items, well)), np.uint8).reshape(n, 64)
    words = raw[:, 32:].view("<u8")
    below, level = np.zeros(n, bool), np.ones(n, bool)
    for i in (3, 2, 1, 0):  # s < L, from the top word down
        below |= level & (words[:, i] < _L_WORDS[i])
        level &= words[:, i] == _L_WORDS[i]
    ok = well & below
    digests = b"".join(
        (int.from_bytes(hashlib.sha512(sig[:32] + pub + msg).digest(),
                        "little") % L).to_bytes(32, "little")
        if good else bytes(32)
        for (msg, sig, pub), good in zip(items, ok))
    h = np.frombuffer(digests, "<u2").reshape(n, NL).astype(np.uint32)
    limbs = raw.view("<u2").astype(np.uint32)
    a = np.stack([_NO_KEY if key is None else key for key in keys])
    refused = {"s_not_reduced": int(np.count_nonzero(well & ~below)),
               "malformed": int(n - np.count_nonzero(well))}
    return ((limbs[:, NL:], h, limbs[:, :NL], a[:, :NL], a[:, NL:]),
            ok.astype(np.uint32), refused)


# ---------------------------------------------------------------------------
# registry + engine adapter
# ---------------------------------------------------------------------------


def _validate_pub(pub: bytes):
    """Decompress + validate a compressed public key; returns the point."""
    pt = ed.decompress(pub)
    if pt is None or not is_on_curve_int(pt):
        raise ValueError("public key is not on the Ed25519 curve")
    return pt


def _build_key_table(pub: bytes) -> np.ndarray:
    return _neg_pub_table(_validate_pub(pub))


def pack_items(items, registry) -> tuple:
    """items -> ((B,32) uint8 s/h/rx/ry, ok, kidx) host prep.

    Host work mirrors ed25519.verify_inputs: SHA-512 binding hash mod L,
    R decompression, the RFC 8032 s < L check.  Lanes failing any host
    check get ok = 0 (the kernel returns 0 for them).
    """
    B = len(items)
    s8 = np.zeros((B, 32), np.uint8)
    h8 = np.zeros((B, 32), np.uint8)
    rx8 = np.zeros((B, 32), np.uint8)
    ry8 = np.zeros((B, 32), np.uint8)
    ok = np.zeros(B, np.uint32)
    kidx = np.zeros(B, np.int32)
    for i, (msg, sig, pub) in enumerate(items):
        kidx[i] = registry.register(pub)
        if len(sig) != 64:
            continue
        s_int = int.from_bytes(sig[32:], "little")
        if s_int >= L:
            continue
        rpt = ed.decompress(sig[:32])
        if rpt is None:
            continue
        h_int = int.from_bytes(
            hashlib.sha512(sig[:32] + pub + msg).digest(), "little") % L
        s8[i] = np.frombuffer(s_int.to_bytes(32, "little"), np.uint8)
        h8[i] = np.frombuffer(h_int.to_bytes(32, "little"), np.uint8)
        rx8[i] = np.frombuffer(rpt[0].to_bytes(32, "little"), np.uint8)
        ry8[i] = np.frombuffer(rpt[1].to_bytes(32, "little"), np.uint8)
        ok[i] = 1
    return s8, h8, rx8, ry8, ok, kidx


class Ed25519CombVerifier(CombVerifier):
    """Engine adapter: the Edwards hooks on CombVerifier's scaffolding."""

    def _make_registry(self, cap: int) -> CombKeyRegistry:
        return CombKeyRegistry(
            cap=cap, validate=_validate_pub, build=_build_key_table
        )

    def _validate_key(self, pub) -> None:
        _validate_pub(pub)

    def _base_table(self) -> np.ndarray:
        return b_table()

    def _pack(self, items):
        s8, h8, rx8, ry8, ok, kidx = pack_items(items, self.registry)
        return [s8, h8, rx8, ry8], ok, kidx

    def _launch(self, arrays, ok, kidx, btab, qtab):
        return eddsa_verify_comb(*arrays, ok, kidx, btab, qtab,
                                 tile=self.tile)
