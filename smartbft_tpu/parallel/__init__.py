"""Device-mesh parallelism for the crypto plane.

The reference scales by adding replicas (one process each); its only
in-process parallelism is goroutine fan-out per signature
(/root/reference/internal/bft/view.go:537-541).  Here the same work is data
parallel over kernel lanes, so it shards over a TPU pod slice with
`jax.sharding` — no NCCL/MPI analog needed: XLA inserts the collectives.

Two products:

* :class:`ShardedVerifyEngine` — a drop-in verify engine (same surface as
  ``JaxVerifyEngine``) that annotates the batch axis with a 1D 'lane' mesh
  sharding; XLA partitions the vmap'd kernel across devices with zero
  communication (verification is embarrassingly parallel until the final
  host-side mask read).
* :func:`quorum_decide` — the 2D (seq x vote) quorum step: each device
  verifies its (sequences, votes) tile, vote counts reduce with a `psum`
  over the 'vote' axis, and the decided mask shards over 'seq'.  This is
  the flagship multi-chip step `__graft_entry__.dryrun_multichip` compiles.
* :class:`QuorumMeshVerifyEngine` — that quorum step as a LIVE verify
  engine (ISSUE 11): selectable through ``Configuration.
  verify_mesh_topology = "2d"`` on the same ``verify_mesh_devices`` knob
  path as :class:`MeshVerifyEngine`, with per-item verdicts bit-identical
  to the 1D engine and per-sequence vote counts psum'd on device.
"""

from .engine import (
    MeshUnavailable,
    MeshVerifyEngine,
    QuorumMeshVerifyEngine,
    ShardedVerifyEngine,
    build_mesh,
    quorum_decide,
)

__all__ = [
    "MeshUnavailable",
    "MeshVerifyEngine",
    "QuorumMeshVerifyEngine",
    "ShardedVerifyEngine",
    "build_mesh",
    "quorum_decide",
]
