"""Full consensus on the virtual 8-device mesh (SURVEY §2.4 multi-chip).

The conftest provisions 8 virtual CPU devices; these tests drive the SAME
path the driver's ``dryrun_multichip`` validates: a real cluster whose
quorum verification runs through ``ShardedVerifyEngine`` with batch lanes
partitioned across the mesh — not just the bare ``quorum_decide`` kernel.
"""

import numpy as np
import pytest


import __graft_entry__ as graft
from smartbft_tpu.crypto import p256
from smartbft_tpu.parallel import ShardedVerifyEngine, build_mesh


def test_sharded_engine_partitions_lanes_across_mesh():
    import jax

    assert len(jax.devices()) >= 8, "conftest should provision 8 devices"
    mesh = build_mesh()
    engine = ShardedVerifyEngine(mesh=mesh, pad_sizes=(8, 64))
    assert engine.lanes == len(jax.devices())
    # every pad size is a mesh multiple so tiles are equal and static
    assert all(s % engine.lanes == 0 for s in engine.pad_sizes)

    # the placed operand really is distributed: one shard per device
    placed = engine._place(np.zeros((64, 16), np.uint32))
    devices = {s.device for s in placed.addressable_shards}
    assert len(devices) == len(jax.devices())
    assert placed.addressable_shards[0].data.shape[0] == 64 // engine.lanes


@pytest.mark.slow
def test_consensus_cluster_commits_on_mesh():
    """Real decisions end-to-end on the 2D (seq x vote) mesh: an n=16
    pipelined cluster whose quorum waves verify through
    QuorumMeshVerifyEngine, with vote counts psum'd across the 'vote' axis
    under live consensus — the scenario the round-4 review flagged as
    exercised only by the bare kernel.

    slow-marked: ~4 min of XLA compiles on the CPU rig (the engine-level
    mesh tests below keep the kernel correctness in tier-1).  Run
    explicitly or via -m slow."""
    graft._dryrun_cluster_on_mesh(8)


def test_quorum_mesh_engine_counts_match_verdicts():
    """The psum'd per-sequence counts equal the host-side tally of valid
    votes — forged votes excluded, padding lanes never counted."""
    from smartbft_tpu.parallel import QuorumMeshVerifyEngine

    mesh = build_mesh((4, 2), ("seq", "vote"))
    eng = QuorumMeshVerifyEngine(mesh=mesh, quorum=3, seq_tile=4, vote_tile=4)
    keys = [p256.keygen(b"qm%d" % i) for i in range(4)]
    items, expect = [], []
    for s in range(6):  # 6 sequences -> two (4, 4) blocks
        msg = b"qm-seq-%d" % s
        for i, (d, pub) in enumerate(keys):
            sig = p256.sign_raw(d, msg)
            if i == s % 4:  # forge a rotating vote per sequence
                sig = bytes([sig[0] ^ 1]) + sig[1:]
            items.append(p256.make_item(msg, sig, pub))
            expect.append(i != s % 4)
    got = eng.verify(items)
    assert got == expect
    assert eng.psum_steps == 2
    for s in range(6):
        assert eng.last_counts[b"qm-seq-%d" % s] == 3
        assert eng.last_decided[b"qm-seq-%d" % s] is True  # quorum=3 met
