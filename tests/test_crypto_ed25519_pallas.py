"""Ed25519's arbitrary-key Pallas kernel (``pallas_ed25519.ed25519_verify``)
and the engine's two key classes on that scheme, held to OpenSSL lane by
lane.

Verdicts are bits, so every comparison is exact.  The kernel runs in
interpret mode at ONE shape (16 lanes, one 16-lane grid step): every
launch of this file that reaches it is that shape, so it compiles once.
"""

import functools
import hashlib
import random

import numpy as np
import pytest
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

from smartbft_tpu.crypto import ed25519 as ed
from smartbft_tpu.crypto import pallas_ed25519 as ped
from smartbft_tpu.crypto.provider import (
    JaxVerifyEngine,
    Keyring,
    prewarm_verify_engine,
)

LANES = 16
#: the one compiled shape of the interpret-mode kernel in this file
KERNEL = functools.partial(ped.ed25519_verify, tile=LANES, interpret=True)


def openssl(item) -> bool:
    msg, sig, pub = item
    try:
        Ed25519PublicKey.from_public_bytes(bytes(pub)).verify(bytes(sig), msg)
        return True
    except Exception:  # noqa: BLE001 — any refusal is a False verdict
        return False


def _le(v: int) -> bytes:
    return v.to_bytes(32, "little")


def _no_point(rng) -> bytes:
    """32 bytes that decode to no point (y < p, no x for it)."""
    while True:
        raw = _le(rng.randrange(ed.P))
        if ed.decompress(raw) is None:
            return raw


def edge_lanes():
    """The sixteen lanes of the launch: ``(item, what)``, the edge cases
    the kernel and the host checks must settle as OpenSSL does."""
    rng = random.Random(39)
    keys = [ed.keygen(b"ed-pallas-%d" % i) for i in range(3)]
    identity, order2 = _le(1), _le(ed.P - 1)  # small-order keys

    def signed(k, msg=None, pub=None):
        msg = rng.randbytes(64) if msg is None else msg
        sk, pk = keys[k]
        return [msg, ed.sign_raw(sk, msg), pk if pub is None else pub]

    def base_times(r):
        return ed.compress(ed.scalar_mult_int(r, (ed.BX, ed.BY)))

    lanes = []
    lanes.append((signed(0), "valid"))
    item = signed(1)
    lanes.append(([*item[:2], ed.PublicKey(item[2])], "valid, held key"))
    item = signed(0)
    s = int.from_bytes(item[1][32:], "little")
    lanes.append(([item[0], item[1][:32] + _le(s + ed.L), item[2]], "S + L"))
    item = signed(1)
    lanes.append(([item[0], _no_point(rng) + item[1][32:], item[2]],
                  "R on no point"))
    lanes.append(([b"m", _le(ed.P + 1) + _le(0), identity],
                  "R non-canonical (y = p + 1)"))
    lanes.append(([b"m", _le(1) + _le(0), identity], "R canonical, S = 0"))
    r = rng.randrange(1, ed.L)
    lanes.append(([b"x", base_times(r) + _le(r), identity],
                  "small-order A (identity), [S]B = R"))
    msg = b"order two"
    for r in range(1, 64):  # [h]A vanishes for even h at A of order 2
        h = int.from_bytes(hashlib.sha512(base_times(r) + order2 + msg)
                           .digest(), "little") % ed.L
        if h % 2 == 0:
            break
    lanes.append(([msg, base_times(r) + _le(r), order2],
                  "small-order A (order 2), [S]B = R"))
    lanes.append((signed(0, pub=identity), "small-order A, another's sig"))
    item = signed(2)
    lanes.append(([item[0], bytes([item[1][0] ^ 0x20]) + item[1][1:],
                   item[2]], "a bit of R"))
    item = signed(2)
    sig = item[1][:40] + bytes([item[1][40] ^ 0x04]) + item[1][41:]
    lanes.append(([item[0], sig, item[2]], "a bit of S"))
    item = signed(0)
    lanes.append(([item[0][:-1] + bytes([item[0][-1] ^ 1]), *item[1:]],
                  "a bit of the message"))
    lanes.append((signed(0, pub=keys[1][1]), "another key"))
    item = signed(1)
    lanes.append(([item[0], item[1][:63], item[2]], "63-byte signature"))
    lanes.append((signed(2, pub=_no_point(rng)), "a key on no point"))
    item = signed(2, msg=rng.randbytes(3072))
    lanes.append(([*item[:2], ed.PublicKey(item[2])], "valid, 3 KB"))
    return [tuple(item) for item, _ in lanes], [what for _, what in lanes]


#: OpenSSL's verdicts of :func:`edge_lanes`, written out: a small-order
#: key is accepted where the cofactorless equation holds, as OpenSSL does
WANT = [True, True, False, False, False, True, True, True, False, False,
        False, False, False, False, False, True]


def test_the_kernel_agrees_with_openssl_on_every_edge_lane():
    items, what = edge_lanes()
    assert len(items) == LANES
    assert [openssl(it) for it in items] == WANT, what
    arrays, ok, refused = ped.prep_inputs(items)
    # the host refuses S >= L and what is not of its length or decodes to
    # no key; R is left to the kernel
    assert refused == {"s_not_reduced": 1, "malformed": 2}
    assert [i for i, v in enumerate(ok) if not v] == [2, 13, 14]
    mask = np.asarray(KERNEL(*arrays, ok))
    got = [bool(v) for v in mask]
    assert got == WANT, [w for w, g, x in zip(what, got, WANT) if g != x]


def test_the_host_checks_run_on_whole_arrays():
    """``prep_inputs``: S and R go to the kernel as the signature's bytes,
    h is SHA-512(R || A || M) mod L, -A is the key's own decoded point,
    and a lane is refused for exactly what RFC 8032 refuses before any
    point arithmetic."""
    items, _ = edge_lanes()
    (s, h, r, ax, ay), ok, _ = ped.prep_inputs(items)
    msg, sig, pub = items[0]
    limbs = lambda v: [(v >> (16 * i)) & 0xFFFF for i in range(16)]  # noqa
    assert list(s[0]) == limbs(int.from_bytes(sig[32:], "little"))
    assert list(r[0]) == limbs(int.from_bytes(sig[:32], "little"))
    want_h = int.from_bytes(hashlib.sha512(sig[:32] + pub + msg).digest(),
                            "little") % ed.L
    assert list(h[0]) == limbs(want_h)
    x, y = ed.decompress(pub)
    assert list(ax[0]) == limbs(ed.P - x) and list(ay[0]) == limbs(y)
    # S = L - 1 passes, S = L does not; both the same lane otherwise
    top = [(msg, sig[:32] + _le(ed.L - v), pub) for v in (1, 0)]
    assert list(ped.prep_inputs(top)[1]) == [1, 0]
    # the word-wise compare against Python's: scalars around L and 2^256
    rng = random.Random(8)
    scalars = [ed.L + rng.randrange(-1 << rng.randrange(1, 256), 1 << 250)
               for _ in range(300)] + [0, (1 << 256) - 1, ed.L, ed.L - 1]
    scalars = [v % (1 << 256) for v in scalars]
    ok = ped.prep_inputs([(msg, sig[:32] + _le(v), pub) for v in scalars])[1]
    assert [bool(v) for v in ok] == [v < ed.L for v in scalars]


def test_an_enrolled_key_is_decoded_once_and_stands_as_its_bytes(
        monkeypatch):
    _, pub = ed.keygen(b"held")
    held = ed.PublicKey(pub)
    assert held == pub and hash(held) == hash(pub) and {pub: 1}[held] == 1
    assert held.point == ed.decompress(pub)

    def no_decoding(_raw):
        raise AssertionError("decoded again")

    monkeypatch.setattr(ed, "decompress", no_decoding)
    assert ped.key_limbs(held) is held.neg_limbs
    assert ed._decompress_pub(held) == held.point
    assert ed.PublicKey(b"\x00" * 31).neg_limbs is None  # not a key's length


def _ring_and_clients():
    rings = Keyring.generate([1, 2], seed=b"ed-split", scheme=ed)
    ring = [rings[1].public_keys[i] for i in (1, 2)]
    rng = random.Random(3)
    votes = []
    for k in range(6):
        i, msg = 1 + k % 2, rng.randbytes(48)
        sig = ed.sign_raw(rings[i].private_key, msg)
        if k == 3:
            sig = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
        votes.append((msg, sig, ring[i - 1]))
    return ring, votes


def _mixed_flush(votes, clients):
    """Votes scattered among the clients' lanes, in one flush."""
    lanes = [("vote", v) for v in votes] + [("env", c) for c in clients]
    random.Random(5).shuffle(lanes)
    return [item for _, item in lanes]


def test_ring_keys_ride_the_comb_kernel_and_every_other_key_this_one(
        monkeypatch):
    """A pinned ring of 2 and the sixteen edge lanes of other keys in ONE
    flush: the ring's lanes on the comb kernel, the rest on the
    arbitrary-key kernel (both real, interpret mode), verdicts in
    submission order equal to OpenSSL's; the launches counted under
    ``comb`` and ``pallas``, and the host's refusals by cause."""
    monkeypatch.setenv("SMARTBFT_PALLAS", "1")
    ring, votes = _ring_and_clients()
    eng = JaxVerifyEngine(pad_sizes=(8,), scheme=ed, ring=ring,
                          request_pad_sizes=(LANES,))
    assert eng._pallas_kernel is ped.ed25519_verify
    monkeypatch.setattr(eng, "_pallas_kernel", KERNEL)
    monkeypatch.setattr(
        eng._comb, "_launch",
        lambda arrays, ok, kidx, btab, qtab: ped.eddsa_verify_comb(
            *arrays, ok, kidx, btab, qtab, tile=8, interpret=True))
    clients, _ = edge_lanes()
    items = _mixed_flush(votes, clients)
    assert eng.verify(items) == [openssl(it) for it in items]
    s = eng.stats
    assert s.launches_by_kernel == {"comb": 1, "pallas": 1, "xla": 0,
                                    "host": 0}
    assert s.used_by_kernel == {"comb": 6, "pallas": 16, "xla": 0, "host": 0}
    assert s.host_refused == {"s_not_reduced": 1, "malformed": 2}
    assert len(eng._comb.registry) == 2  # no client key in the registry


def test_without_pallas_both_classes_ride_the_xla_kernel(monkeypatch):
    """Off the TPU (Pallas off) the same flush rides the XLA Ed25519
    kernel on both ladders, verdicts equal to OpenSSL's; its marshalling
    refuses nothing by count (it has no such account)."""
    monkeypatch.setenv("SMARTBFT_PALLAS", "0")
    ring, votes = _ring_and_clients()
    eng = JaxVerifyEngine(pad_sizes=(LANES,), scheme=ed, ring=ring,
                          request_pad_sizes=(LANES,))
    clients, _ = edge_lanes()
    items = _mixed_flush(votes, clients)
    assert eng.verify(items) == [openssl(it) for it in items]
    assert eng.stats.launches_by_kernel == {"comb": 0, "pallas": 0, "xla": 2,
                                            "host": 0}
    assert eng.stats.host_refused == {"s_not_reduced": 0, "malformed": 0}


def test_the_prewarm_entry_compiles_both_ed25519_ladders(monkeypatch):
    """``prewarm_verify_engine`` on an Ed25519 engine with a ring: every
    rung of both ladders, each under its kernel (stubbed kernels)."""
    monkeypatch.setenv("SMARTBFT_PALLAS", "1")
    ring, _ = _ring_and_clients()
    eng = JaxVerifyEngine(pad_sizes=(8, 32), scheme=ed, ring=ring,
                          request_pad_sizes=(512,))
    shapes = []

    def comb(items, pad_to):
        shapes.append(("comb", pad_to))
        return np.zeros(len(items), np.uint32)

    def arbitrary(*arrays):
        shapes.append(("pallas", arrays[0].shape[0], len(arrays)))
        return np.zeros(arrays[0].shape[0], np.uint32)

    monkeypatch.setattr(eng._comb, "verify", comb)
    monkeypatch.setattr(eng, "_pallas_kernel", arbitrary)
    prewarm_verify_engine(eng)
    # the arbitrary-key kernel takes S, h, R, -A's x and y, and the mask
    assert sorted(shapes) == [("comb", 8), ("comb", 32), ("pallas", 512, 6)]


@pytest.mark.parametrize("cause, n", [("s_not_reduced", 3), ("malformed", 2)])
def test_the_account_carries_the_prep_span_and_the_refusals(monkeypatch,
                                                            cause, n):
    """With the recorder on, an arbitrary-key launch leaves a ``verify.prep``
    busy span (its lanes) inside ``verify.pack`` and its refusals on the
    ``verify.lanes`` mark; the account folds both."""
    from smartbft_tpu.obs import TraceRecorder
    from smartbft_tpu.obs import recorder as recmod
    from smartbft_tpu.obs.account import assemble_account

    rec = TraceRecorder(node="proc", enabled=True)
    monkeypatch.setattr(recmod, "PROCESS", rec)
    with recmod.launch_span("verify.pack"):
        with recmod.launch_span("verify.prep", lanes=10):
            sum(range(1000))
    recmod.note_lanes("pallas", 16, 10, refused={cause: n})
    events = rec.events()
    prep = next(e for e in events if e.kind == "verify.prep")
    pack = next(e for e in events if e.kind == "verify.pack")
    assert prep.extra["lanes"] == 10 and 0.0 <= prep.self_s <= pack.dur
    acc = assemble_account([rec], {}, t0=0.0,
                           t1=max(e.t for e in events) + 1.0,
                           loop_cpu_s=0.0, loop_thread="loop")
    assert acc["prep"] == {"calls": 1, "lanes": 10,
                           "self_s": pytest.approx(prep.self_s)}
    assert acc["lanes"]["pallas"] == {"launches": 1, "launched": 16,
                                      "used": 10, "host_refused": {cause: n}}
