"""Unit tests: limb arithmetic + Montgomery engine vs Python ints.

The reference has no bignum layer (Go's crypto/ecdsa hides it); these tests
anchor the TPU engine the way the reference's WAL tests anchor its framing
(/root/reference/pkg/wal/writeaheadlog_test.go) — byte-exact against an
independent implementation, here CPython's arbitrary-precision ints.
"""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from smartbft_tpu.crypto import bignum as bn

P256_P = 0xFFFFFFFF00000001000000000000000000000000FFFFFFFFFFFFFFFFFFFFFFFF
P256_N = 0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551
ED_P = 2**255 - 19

rng = random.Random(1234)


def rnd_batch(mod, k=16):
    return [rng.randrange(mod) for _ in range(k)]


def test_limb_roundtrip():
    for x in [0, 1, 0xFFFF, 2**255 - 19, 2**256 - 1]:
        assert bn.from_limbs(bn.to_limbs(x, 16)) == x
    with pytest.raises(ValueError):
        bn.to_limbs(2**256, 16)


def assert_same_limbs(got, want):
    """Element for element, and as the kernels are handed it."""
    assert got.shape == want.shape and got.dtype == want.dtype == np.uint32
    assert got.flags.c_contiguous
    assert np.array_equal(got, want)


#: where packing from bytes goes wrong: the ends of the range, and values
#: whose top one, two and sixteen bytes are zero
BULK_CASES = {
    "random": (rnd_batch(2**256, 64), 16),
    "ends": ([0, 1, P256_N - 1, 2**256 - 1, 0xFFFF, 0x10000], 16),
    "top_byte_zero": ([2**248 - 1, 2**247 + 5, rng.randrange(2**248)], 16),
    "top_two_bytes_zero": ([2**240 - 1, rng.randrange(2**240)], 16),
    "top_sixteen_bytes_zero": ([2**128 - 1, rng.randrange(2**128), 2**128], 16),
    "batch_of_one": ([rng.randrange(2**256)], 16),
    "four_limbs": ([0, 5, 2**64 - 1, rng.randrange(2**64)], 4),
    "bls_width": ([0, 2**384 - 1] + rnd_batch(2**381, 6), 24),
    "none": ([], 16),
}


@pytest.mark.parametrize("case", BULK_CASES)
def test_batch_to_limbs_equals_to_limbs_of_each(case):
    """The bulk build from bytes against the per-integer reference."""
    xs, nlimbs = BULK_CASES[case]
    want = np.stack([bn.to_limbs(x, nlimbs) for x in xs]) if xs \
        else np.zeros((0, nlimbs), np.uint32)
    got = bn.batch_to_limbs(xs, nlimbs)
    assert_same_limbs(got, want)
    assert [bn.from_limbs(row) for row in got] == xs


@pytest.mark.parametrize("xs", [[2**256], [1, 2**256 + 7, 2], [-1], [3, -5]],
                         ids=["overflow", "overflow_among", "negative",
                              "negative_among"])
def test_batch_to_limbs_refuses_what_to_limbs_refuses(xs):
    with pytest.raises(ValueError):
        np.stack([bn.to_limbs(x, 16) for x in xs])
    with pytest.raises(ValueError):
        bn.batch_to_limbs(xs, 16)


def test_mul_full_matches_python():
    xs, ys = rnd_batch(2**256, 8), rnd_batch(2**256, 8)
    F = bn.mul_full(jnp.asarray(bn.batch_to_limbs(xs, 16)),
                    jnp.asarray(bn.batch_to_limbs(ys, 16)))
    for i in range(8):
        assert bn.from_limbs(np.asarray(F[i])) == xs[i] * ys[i]


@pytest.mark.parametrize("mod", [P256_P, P256_N, ED_P], ids=["p256p", "p256n", "ed25519p"])
def test_mont_ops(mod):
    ctx = bn.MontCtx(mod, 16)
    xs, ys = rnd_batch(mod), rnd_batch(mod)
    X = jnp.asarray(np.stack([ctx.encode(x) for x in xs]))
    Y = jnp.asarray(np.stack([ctx.encode(y) for y in ys]))
    Z = jax.jit(ctx.mul)(X, Y)
    A = jax.jit(ctx.add)(X, Y)
    S = jax.jit(ctx.sub)(X, Y)
    for i, (x, y) in enumerate(zip(xs, ys)):
        assert ctx.decode(np.asarray(Z[i])) == x * y % mod
        assert ctx.decode(np.asarray(A[i])) == (x + y) % mod
        assert ctx.decode(np.asarray(S[i])) == (x - y) % mod


def test_mont_inv_prime_field():
    ctx = bn.MontCtx(P256_N, 16)
    xs = rnd_batch(P256_N - 1, 4)
    xs = [x + 1 for x in xs]  # nonzero
    X = jnp.asarray(np.stack([ctx.encode(x) for x in xs]))
    I = jax.jit(ctx.inv)(X)
    for i, x in enumerate(xs):
        assert ctx.decode(np.asarray(I[i])) == pow(x, -1, P256_N)


def test_cmp_helpers():
    a = jnp.asarray(bn.batch_to_limbs([5, 7, 7, 0], 4))
    b = jnp.asarray(bn.batch_to_limbs([7, 5, 7, 0], 4))
    assert np.asarray(bn.geq(a, b)).tolist() == [0, 1, 1, 1]
    assert np.asarray(bn.eq(a, b)).tolist() == [0, 0, 1, 1]
    assert np.asarray(bn.is_zero(a)).tolist() == [0, 0, 0, 1]


def test_bits_msb():
    x = 0b1011_0000_0000_0001_0101
    arr = jnp.asarray(bn.to_limbs(x, 4))[None]
    bits = np.asarray(bn.bits_msb(arr, 20))[0]
    assert int("".join(str(b) for b in bits), 2) == x


# ---------------------------------------------------------------------------
# both carry-chain implementations stay verified against the integer
# reference (the non-default mode is otherwise a dead path that can rot)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["prefix", "scan"])
def test_carry_chain_modes_match_ints(mode, monkeypatch):
    import smartbft_tpu.crypto.bignum as bn_mod

    monkeypatch.setattr(bn_mod, "CHAIN", mode)
    rng = np.random.default_rng(7)
    # column sums < 2^31 as carry_propagate's contract requires
    cols = rng.integers(0, 1 << 31, size=(5, 24), dtype=np.uint32)
    out = np.asarray(bn_mod.carry_propagate(jnp.asarray(cols), 24))
    for row_in, row_out in zip(cols, out):
        want = sum(int(v) << (16 * i) for i, v in enumerate(row_in))
        want %= 1 << (16 * 24)
        got = sum(int(v) << (16 * i) for i, v in enumerate(row_out))
        assert got == want

    a = rng.integers(0, 1 << 16, size=(6, 16), dtype=np.uint32)
    b = rng.integers(0, 1 << 16, size=(6, 16), dtype=np.uint32)
    diff, borrow = bn_mod.sub_borrow(jnp.asarray(a), jnp.asarray(b))
    diff, borrow = np.asarray(diff), np.asarray(borrow)
    for ra, rb, rd, bo in zip(a, b, diff, borrow):
        ia = sum(int(v) << (16 * i) for i, v in enumerate(ra))
        ib = sum(int(v) << (16 * i) for i, v in enumerate(rb))
        idiff = sum(int(v) << (16 * i) for i, v in enumerate(rd))
        assert idiff == (ia - ib) % (1 << 256)
        assert int(bo) == (1 if ia < ib else 0)


@pytest.mark.parametrize("mode", ["ripple", "prefix"])
def test_pallas_carry_chain_modes_match_ints(mode, monkeypatch):
    import smartbft_tpu.crypto.pallas_ecdsa as pe_mod

    monkeypatch.setattr(pe_mod, "CHAIN", mode)
    rng = np.random.default_rng(11)
    # limb-major (m, B) columns < 2^31
    cols = rng.integers(0, 1 << 31, size=(24, 4), dtype=np.uint32)
    out = np.asarray(pe_mod._carry(jnp.asarray(cols)))
    for lane in range(4):
        want = sum(int(v) << (16 * i) for i, v in enumerate(cols[:, lane]))
        want %= 1 << (16 * 24)
        got = sum(int(v) << (16 * i) for i, v in enumerate(out[:, lane]))
        assert got == want

    a = rng.integers(0, 1 << 16, size=(16, 5), dtype=np.uint32)
    b = rng.integers(0, 1 << 16, size=(16, 5), dtype=np.uint32)
    diff, borrow = pe_mod._sub_borrow(jnp.asarray(a), jnp.asarray(b))
    diff, borrow = np.asarray(diff), np.asarray(borrow)
    for lane in range(5):
        ia = sum(int(v) << (16 * i) for i, v in enumerate(a[:, lane]))
        ib = sum(int(v) << (16 * i) for i, v in enumerate(b[:, lane]))
        idiff = sum(int(v) << (16 * i) for i, v in enumerate(diff[:, lane]))
        assert idiff == (ia - ib) % (1 << 256)
        assert int(borrow[lane]) == (1 if ia < ib else 0)
