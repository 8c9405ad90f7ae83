"""Mesh-sharded verification on the virtual 8-device CPU mesh.

Validates the product parallel plane the driver's multichip dry-run
compiles: the 1D lane-sharded verify engine (drop-in for JaxVerifyEngine)
and the 2D (seq x vote) quorum step with its psum reduction.
"""

import numpy as np


from smartbft_tpu.crypto import p256
from smartbft_tpu.crypto.provider import Keyring, P256CryptoProvider
from smartbft_tpu.messages import Proposal
from smartbft_tpu.parallel import ShardedVerifyEngine, build_mesh, quorum_decide


def _votes(n, msg=b"digest", seed=b"par"):
    keys = [p256.keygen(seed + b"-%d" % i) for i in range(n)]
    items = []
    for d, pub in keys:
        r, s = p256.sign(d, msg)
        items.append((msg, r, s, pub))
    return items


def test_build_mesh_default_uses_all_devices():
    mesh = build_mesh()
    assert int(np.prod(mesh.devices.shape)) == 8
    assert mesh.axis_names == ("lane",)


def test_sharded_engine_flags_bad_lane():
    mesh = build_mesh((8,))
    eng = ShardedVerifyEngine(mesh=mesh, pad_sizes=(16,))
    items = _votes(12)
    bad = items[5]
    items[5] = (bad[0], bad[1] ^ 1, bad[2], bad[3])
    mask = eng.verify(items)
    assert mask == [i != 5 for i in range(12)]
    assert eng.stats.launches == 1
    assert eng.stats.slots_used == 16  # padded to a multiple of the mesh


def test_sharded_engine_pad_sizes_rounded_to_mesh():
    eng = ShardedVerifyEngine(mesh=build_mesh((8,)), pad_sizes=(3, 20))
    assert eng.pad_sizes == (8, 24)


def test_sharded_engine_plugs_into_provider():
    rings = Keyring.generate([1, 2, 3, 4], seed=b"par-prov")
    eng = ShardedVerifyEngine(mesh=build_mesh((8,)), pad_sizes=(16,))
    provs = {n: P256CryptoProvider(rings[n], engine=eng) for n in rings}
    prop = Proposal(header=b"h", payload=b"block", metadata=b"m")
    votes = [provs[n].sign_proposal(prop, b"aux-%d" % n) for n in (1, 2, 3)]
    auxes = provs[4].verify_consenter_sigs_batch(votes, prop)
    assert auxes == [b"aux-1", b"aux-2", b"aux-3"]


def _place_quorum_block(mesh, args):
    """Device-place a quorum block with per-rank (seq, vote[, None]) specs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    def spec(a):
        return P("seq", "vote", None) if np.ndim(a) == 3 else P("seq", "vote")

    return tuple(
        jax.device_put(jnp.asarray(a), NamedSharding(mesh, spec(a)))
        for a in args
    )


def test_quorum_decide_2d_mesh():
    mesh = build_mesh((4, 2), ("seq", "vote"))
    n_seq, n_votes = 4, 4
    quorum = 3

    keys = [p256.keygen(b"q-%d" % v) for v in range(n_votes)]
    items = []
    for s in range(n_seq):
        msg = b"prop-%d" % s
        for v, (d, pub) in enumerate(keys):
            r, sg = p256.sign(d, msg)
            # sequence 2 only gets 2 valid votes: below quorum
            if s == 2 and v >= 2:
                r ^= 1
            items.append((msg, r, sg, pub))
    arrays = p256.verify_inputs(items)
    args = tuple(a.reshape((n_seq, n_votes, 16)) for a in arrays)

    step = quorum_decide(mesh, quorum)
    decided = np.asarray(step(*_place_quorum_block(mesh, args)))
    assert decided.tolist() == [True, True, False, True]


def test_quorum_decide_scheme_generic_ed25519():
    """ed25519's trailing host-validity mask is a rank-2 quorum input; the
    per-rank partition specs must handle it."""
    from smartbft_tpu.crypto import ed25519 as ed

    mesh = build_mesh((2, 2), ("seq", "vote"))
    n_seq, n_votes = 2, 2
    quorum = 2

    keys = [ed.keygen(b"edq-%d" % v) for v in range(n_votes)]
    items = []
    for s in range(n_seq):
        msg = b"prop-%d" % s
        for sk, pub in keys:
            items.append((msg, ed.sign(sk, msg), pub))
    arrays = ed.verify_inputs(items)
    args = tuple(
        a.reshape((n_seq, n_votes) + a.shape[1:]) for a in arrays
    )

    step = quorum_decide(mesh, quorum, scheme=ed)
    decided = np.asarray(step(*_place_quorum_block(mesh, args)))
    assert decided.tolist() == [True, True]
