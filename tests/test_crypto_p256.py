"""P-256 ECDSA: host signer/verifier self-consistency + TPU kernel parity.

Mirrors the role of the reference's crypto seam tests — the reference
delegates signatures to the embedder (/root/reference/pkg/api/
dependencies.go:47-71) and its test app uses no-op crypto
(/root/reference/test/test_app.go:237-267); here real ECDSA is a
first-class, tested component because batched verification on the TPU is
the framework's point.
"""

import hashlib
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from smartbft_tpu.crypto import bignum as bn
from smartbft_tpu.crypto import p256

from tests.test_crypto_bignum import assert_same_limbs


def test_host_sign_verify_roundtrip():
    d, pub = p256.keygen(b"seed")
    r, s = p256.sign(d, b"payload")
    assert p256.verify_int(pub, b"payload", r, s)
    assert not p256.verify_int(pub, b"payload2", r, s)
    assert not p256.verify_int(pub, b"payload", r, (s + 1) % p256.N)


def test_sign_deterministic_rfc6979():
    d, _ = p256.keygen(b"seed")
    assert p256.sign(d, b"m") == p256.sign(d, b"m")
    assert p256.sign(d, b"m") != p256.sign(d, b"m2")


def test_point_add_matches_host():
    d, pub = p256.keygen(b"k")
    FP = p256.FP
    G = jnp.asarray(p256._G_MONT)[None]
    qm = jnp.asarray(
        np.stack([FP.encode(pub[0]), FP.encode(pub[1]), FP.one_mont])
    )[None]

    def decode_affine(pt):
        x, y, z = [np.asarray(pt[0, i]) for i in range(3)]
        zi = pow(FP.decode(z), -1, p256.P)
        return FP.decode(x) * zi % p256.P, FP.decode(y) * zi % p256.P

    add = jax.jit(p256.point_add)
    assert decode_affine(add(G, G)) == p256._point_add_int(
        (p256.GX, p256.GY), (p256.GX, p256.GY)
    )
    assert decode_affine(add(G, qm)) == p256._point_add_int((p256.GX, p256.GY), pub)
    # identity handling (completeness)
    inf = jnp.asarray(p256._INF_MONT)[None]
    assert decode_affine(add(G, inf)) == (p256.GX, p256.GY)
    out = add(inf, inf)
    assert p256.FP.decode(np.asarray(out[0, 2])) == 0  # still infinity


@pytest.fixture(scope="module")
def verify_jit():
    return jax.jit(p256.ecdsa_verify_kernel)


def test_verify_kernel_batch(verify_jit):
    items, truth = [], []
    for i in range(4):
        d, pub = p256.keygen(bytes([i]))
        msg = b"msg-%d" % i
        r, s = p256.sign(d, msg)
        if i == 1:
            s = (s + 1) % p256.N
            truth.append(False)
        elif i == 2:
            msg += b"x"
            truth.append(False)
        else:
            truth.append(True)
        items.append((msg, r, s, pub))
    args = [jnp.asarray(a) for a in p256.verify_inputs(items)]
    mask = np.asarray(verify_jit(*args))
    assert mask.astype(bool).tolist() == truth


def test_verify_kernel_rejects_degenerate(verify_jit):
    d, pub = p256.keygen(b"z")
    msg = b"m"
    r, s = p256.sign(d, msg)
    e = np.stack([p256.hash_to_limbs(msg)] * 4)
    rr = bn.batch_to_limbs([0, r, p256.N, r], 16)       # r=0 / ok / r=n / ok
    ss = bn.batch_to_limbs([s, 0, s, s], 16)            # ok / s=0 / ok / ok
    qx = bn.batch_to_limbs([pub[0]] * 4, 16)
    qy = bn.batch_to_limbs([pub[1], pub[1], pub[1], (pub[1] + 1) % p256.P], 16)
    mask = np.asarray(verify_jit(*[jnp.asarray(a) for a in (e, rr, ss, qx, qy)]))
    # lanes: r=0 -> 0, s=0 -> 0, r=n -> 0, off-curve pubkey -> 0
    assert mask.tolist() == [0, 0, 0, 0]


# ---------------------------------------------------------------------------
# the launch's inputs, built in bulk from bytes, against the per-integer
# reference (bn.to_limbs of every value and of every digest)
# ---------------------------------------------------------------------------

def _digest_with_zero_top(nbytes: int, rng) -> bytes:
    """A message whose SHA-256 starts with ``nbytes`` zero bytes (search)."""
    while True:
        msg = rng.randbytes(24)
        if hashlib.sha256(msg).digest()[:nbytes] == bytes(nbytes):
            return msg


def _pack_cases():
    rng = random.Random(2929)
    val = lambda top=256: rng.randrange(1 << top)  # noqa: E731
    item = lambda m=None, **kw: (  # noqa: E731
        rng.randbytes(40) if m is None else m, kw.get("r", val()),
        kw.get("s", val()), (kw.get("qx", val()), kw.get("qy", val())))
    return {
        "random": [item() for _ in range(64)],
        "ends": [item(r=0, s=1, qx=p256.N - 1, qy=2**256 - 1),
                 item(r=2**256 - 1, s=p256.N - 1, qx=0, qy=1),
                 item(r=1, s=0, qx=2**256 - 1, qy=0)],
        "values_top_byte_zero": [item(r=val(248)), item(s=val(248)),
                                 item(qx=val(248)), item(qy=val(248))],
        "values_top_two_bytes_zero": [item(r=val(240), s=val(240)),
                                      item(qx=val(240), qy=val(240))],
        "values_top_sixteen_bytes_zero": [item(r=val(128), s=val(128)),
                                          item(qx=val(128), qy=2**128)],
        "digest_top_byte_zero": [item(_digest_with_zero_top(1, rng)),
                                 item()],
        "digest_top_two_bytes_zero": [item(),
                                      item(_digest_with_zero_top(2, rng))],
        "batch_of_one": [item()],
        "empty_message": [item(b""), item(), item(b"")],
        # an envelope's signed part, and the length from which hashlib
        # lets go of the interpreter lock
        "envelope_sized_message": [item(rng.randbytes(n)) for n in (
            3096, 2047, 2048, 10000)],
    }


PACK_CASES = _pack_cases()


def _reference_inputs(items, digest=lambda m: hashlib.sha256(m).digest()):
    cols = ([int.from_bytes(digest(m), "big") for m, _, _, _ in items],
            [r for _, r, _, _ in items], [s for _, _, s, _ in items],
            [q[0] for _, _, _, q in items], [q[1] for _, _, _, q in items])
    return [np.stack([bn.to_limbs(x, 16) for x in col]) for col in cols]


def _assert_same_inputs(got, want):
    assert len(got) == len(want) == 5
    for a, b in zip(got, want):
        assert_same_limbs(a, b)


@pytest.mark.parametrize("case", PACK_CASES)
def test_verify_inputs_equal_the_per_integer_reference(case):
    items = PACK_CASES[case]
    got = p256.verify_inputs(items)
    _assert_same_inputs(got, _reference_inputs(items))
    for (m, _, _, _), row in zip(items, got[0]):
        assert np.array_equal(p256.hash_to_limbs(m), row)


@pytest.mark.parametrize("zeros", [1, 2, 16, 31, 32])
def test_verify_inputs_digest_column_with_leading_zero_bytes(zeros,
                                                             monkeypatch):
    """Digests no search finds: a stand-in for SHA-256 whose output starts
    with ``zeros`` zero bytes, seen by the bulk build and by the reference
    alike."""
    rng = random.Random(zeros)

    def digest(m):
        return bytes(zeros) + hashlib.sha256(m).digest()[zeros:]

    class Stub:
        @staticmethod
        def sha256(m):
            return type("H", (), {"digest": staticmethod(lambda: digest(m))})

    items = [(rng.randbytes(30), 5, 7, (11, 13)) for _ in range(5)]
    want = _reference_inputs(items, digest)
    monkeypatch.setattr(p256, "hashlib", Stub)
    got = p256.verify_inputs(items)
    _assert_same_inputs(got, want)
    assert bn.from_limbs(got[0][0]) < 1 << (256 - 8 * zeros)


@pytest.mark.parametrize("items", [
    [(b"m", 2**256, 1, (1, 1))], [(b"m", 1, 2**300, (1, 1))],
    [(b"m", 1, 1, (2**256, 1))], [(b"a", 1, 1, (1, 1)), (b"m", 1, 1, (1, -1))],
    [(b"m", -1, 1, (1, 1))],
], ids=["r_overflow", "s_overflow", "qx_overflow", "qy_negative",
        "r_negative"])
def test_verify_inputs_refuses_overflow_and_negative(items):
    with pytest.raises(ValueError):
        p256.verify_inputs(items)


def test_verify_inputs_is_the_bulk_path(monkeypatch):
    """The launch's packing makes no per-integer ``to_limbs`` call: with
    it gone the five arrays are what they were."""
    items = PACK_CASES["random"] + PACK_CASES["envelope_sized_message"]
    want = _reference_inputs(items)

    def gone(*_a, **_k):
        raise AssertionError("to_limbs called from the launch's packing")

    monkeypatch.setattr(bn, "to_limbs", gone)
    _assert_same_inputs(p256.verify_inputs(items), want)
