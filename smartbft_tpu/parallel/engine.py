"""Mesh-sharded signature verification and the distributed quorum step.

Design notes (TPU-first):

* Verification lanes are independent — the ideal SPMD workload.  The
  engine pads each batch to a lane count divisible by the mesh and places
  inputs with ``NamedSharding(mesh, P('lane'))``; ``jax.jit`` then
  partitions the XLA kernel's body across devices without any
  hand-written collectives.
* The quorum step is the one place a cross-device reduction exists: vote
  counts sum over the 'vote' mesh axis (``lax.psum`` riding ICI), the
  cheapest possible collective (one scalar per in-flight sequence).
* The XLA kernels are the scheme modules' single-chip kernels unchanged:
  for them sharding is an annotation, not a rewrite.  A Pallas kernel is
  not: ``pallas_call`` has no partitioning rules, so the 1D engine runs
  the static-key comb kernel under ``shard_map`` (lanes split over the
  mesh's axis, the key tables whole on every device:
  ``pallas_comb.mesh_comb_launcher``) wherever the one-device engine
  would run it, and the XLA kernel elsewhere.  The 2D engine is XLA only.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

from ..crypto import p256
from ..crypto.provider import JaxVerifyEngine, MeshVerifyStats
from ..obs.recorder import launch_span, note_lanes


def build_mesh(shape: Optional[tuple[int, ...]] = None,
               axis_names: tuple[str, ...] = ("lane",),
               devices=None):
    """A `jax.sharding.Mesh` over the first prod(shape) devices.

    Default: all visible devices on a 1D 'lane' axis.  For the quorum step
    pass ``shape=(seq_par, vote_par)`` and ``axis_names=('seq', 'vote')``.
    """
    import jax

    devices = list(jax.devices() if devices is None else devices)
    if shape is None:
        shape = (len(devices),)
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(f"mesh shape {shape} needs {n} devices, "
                         f"have {len(devices)}")
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices[:n]).reshape(shape), axis_names)


def _device_span(arrays) -> int:
    """Fewest distinct devices any of ``arrays`` is laid out over."""
    return min(len(a.sharding.device_set) for a in arrays)


class ShardedVerifyEngine(JaxVerifyEngine):
    """`JaxVerifyEngine` with batch lanes sharded over a 1D device mesh.

    Same engine surface, so it plugs into ``CryptoProvider`` and the async
    coalescer unchanged.  Pad sizes are rounded up to multiples of the mesh
    size so every device gets equal, static tiles; padded inputs are placed
    with a lane sharding and XLA partitions the kernel.
    """

    # this engine leaves the split to jit, which can partition the XLA
    # kernel and cannot partition a pallas_call: its lanes stay on XLA
    supports_pallas = False

    def __init__(self, mesh=None,
                 pad_sizes: tuple[int, ...] = (64, 256, 1024), scheme=p256):
        from jax.sharding import NamedSharding, PartitionSpec

        self.mesh = mesh if mesh is not None else build_mesh()
        if len(self.mesh.axis_names) != 1:
            raise ValueError("ShardedVerifyEngine wants a 1D mesh; use "
                             "quorum_decide for 2D (seq x vote) meshes")
        self.lanes = int(np.prod(self.mesh.devices.shape))
        rounded = sorted({-(-s // self.lanes) * self.lanes for s in pad_sizes})
        super().__init__(pad_sizes=rounded, scheme=scheme)
        self._sharding = NamedSharding(
            self.mesh, PartitionSpec(self.mesh.axis_names[0])
        )

    def _place(self, a):
        return self._jax.device_put(a, self._sharding)


class MeshUnavailable(RuntimeError):
    """The configured verify mesh cannot be built on this host (fewer
    visible devices than requested).  The wiring seam
    (``CryptoProvider.configure_verify_mesh``) catches this and constructs
    the single-device engine LOUDLY with a counted downgrade — a
    mis-provisioned host degrades to reduced width instead of dying."""


#: default per-device lane ladder for the graduated mesh engine: each
#: device contributes a fixed lane budget, so aggregate per-launch
#: capacity scales linearly with the mesh width (the whole point of
#: amortizing the ~fixed launch overhead across N devices)
MESH_PER_DEVICE_LANES = (8, 64, 512, 2048)


class MeshVerifyEngine(ShardedVerifyEngine):
    """The GRADUATED live-path mesh engine (ISSUE 10, ROADMAP item 1).

    Each coalesced wave is padded to a device-count multiple, partitioned
    along the batch axis with ``NamedSharding(mesh, P('batch'))`` (the
    SNIPPETS.md [1]/[2] idiom), and verified in ONE logical launch that
    spans the whole mesh; per-item verdicts gather back to the host and
    the coalescer slices them per submitter/tag exactly as on the
    single-device engine.  Construction raises :class:`MeshUnavailable`
    when the host has fewer visible devices than requested — the wiring
    seam turns that into a loud counted downgrade, never a crash.

    ``pad_sizes=None`` derives a ladder of ``MESH_PER_DEVICE_LANES`` lanes
    PER DEVICE, so per-launch capacity (``pad_sizes[-1]``) scales with the
    mesh width; an explicit ladder is rounded up to device multiples like
    any :class:`ShardedVerifyEngine`.  ``stats`` is a
    :class:`~smartbft_tpu.crypto.provider.MeshVerifyStats`: per-launch
    per-device fill and pad waste ride every record, exported through
    ``AsyncBatchCoalescer.mesh_snapshot`` into the bench ``mesh`` block.

    **Strided placement** (ISSUE 11 satellite): items round-robin over
    devices (item *j* lands in device ``j % D``'s tile) instead of
    filling devices front to back, so pad slots spread EVENLY — round 13
    measured one contiguous launch running 6 devices at 100 % and 2 at
    0 %; strided, per-device item counts differ by at most one.
    Verification lanes are independent, so the permutation cannot change
    any verdict; results un-permute before slicing, keeping the output
    bit-identical to the single-device engine.

    **Which kernel** (PR 32) is decided as on one device, by what the
    engine can observe: on a TPU, P-256 chunks whose keys are registrable
    ride the static-key comb kernel ON EVERY DEVICE (``shard_map`` over
    the ``batch`` axis, the tables whole on each device), recorded
    ``comb``; on other backends, for other schemes (Ed25519's comb kernel
    has no mesh wrapper: it stays on XLA here) and for keys outside a
    pinned ring (:meth:`JaxVerifyEngine.pin_ring`; the arbitrary-key
    Pallas kernel has none either) the XLA kernel that ``jit`` partitions
    does, recorded ``xla``.  When the comb kernel serves, ``pad_sizes`` is
    rounded to multiples of ``devices x tile`` lanes (a device's share
    must be whole kernel tiles) and rungs that would be the same launch
    are one rung; ``request_pad_sizes`` stays the XLA kernel's ladder.
    """

    #: wraps the comb kernel in shard_map itself (see the class docstring)
    supports_pallas = True
    #: bench/wiring marker: which mesh shape this engine runs (the 2D
    #: seq×vote engine says "2d"); configure_verify_mesh keys idempotence
    #: on (devices, topology)
    topology = "1d"

    def __init__(self, devices: Optional[int] = None, mesh=None,
                 pad_sizes: Optional[tuple[int, ...]] = None, scheme=p256,
                 metrics=None):
        if mesh is None:
            import jax

            avail = list(jax.devices())
            want = len(avail) if not devices else int(devices)
            if want < 1 or want > len(avail):
                raise MeshUnavailable(
                    f"verify mesh wants {want} device(s), host has "
                    f"{len(avail)}"
                )
            mesh = build_mesh((want,), ("batch",), devices=avail[:want])
        n_dev = int(np.prod(mesh.devices.shape))
        if pad_sizes is None:
            pad_sizes = tuple(l * n_dev for l in MESH_PER_DEVICE_LANES)
        super().__init__(mesh=mesh, pad_sizes=tuple(pad_sizes), scheme=scheme)
        #: mesh width — the attribute the wiring seam keys idempotence on
        #: (FaultyEngine delegates it, so a fault-wrapped mesh still reads
        #: as "already graduated")
        self.devices = self.lanes
        self.stats = MeshVerifyStats(devices=self.devices, metrics=metrics)
        # the mesh was built from the backend's devices, so the backend is
        # up and the kernel can be chosen now, with the ladder it needs
        self._pallas_on = self._use_pallas()
        if self._pallas_on and self._comb is not None:
            block = self.devices * self._comb.tile
            self.pad_sizes = tuple(sorted(
                {-(-s // block) * block for s in self.pad_sizes}))

    def _pallas_kernels(self, scheme) -> tuple:
        if scheme is p256:
            from ..crypto.pallas_comb import CombVerifier

            return CombVerifier(mesh=self.mesh), None
        return None, None

    def mesh_snapshot(self) -> dict:
        """JSON-able block: devices, per-launch fill per device, pad
        waste — the engine half of the bench ``mesh`` block."""
        out = self.stats.mesh_block(capacity=self.pad_sizes[-1])
        out["topology"] = self.topology
        return out

    def _verify_chunk(self, items, generic: bool = False) -> list[bool]:
        """Strided chunk verify: scatter item *j* to padded row
        ``(j % D) * per_dev + j // D`` — device *d*'s tile holds items
        ``d, d+D, d+2D, ...`` — run ONE mesh launch, then un-permute the
        mask back to submission order.  Pad rows stay zero (they verify
        False and are never read back).  ``generic``: the chunk's keys are
        outside the pinned ring, so the comb kernel is not asked."""
        n = len(items)
        size = self._rung(self.request_pad_sizes, n) if generic \
            else self._pad_to(n)
        d_count = self.devices
        idx = np.arange(n)
        rows = (idx % d_count) * (size // d_count) + idx // d_count
        t0 = time.perf_counter()
        kernel, (mask, io_devices) = self._launch_strided(
            items, size, rows, generic)
        dt = time.perf_counter() - t0
        counts = [len(range(d, n, d_count)) for d in range(d_count)]
        with self._lock:
            self.stats.record(n, size, dt, kernel, per_device=counts,
                              io_devices=io_devices)
        note_lanes(kernel, size, n, per_device=counts)
        return [bool(v) for v in mask[rows]]

    def _launch_strided(self, items, size: int, rows, generic: bool):
        """One chunk through the kernel the backend and the chunk's keys
        select -> (kernel name, (host mask in padded row order, the
        devices its inputs / its output were laid out over))."""
        if self._pallas_on and self._comb is not None and not generic:
            got = self._guarded_launch(
                "comb", size, lambda: self._comb_strided(items, size, rows))
            if got is not None:
                return "comb", got
        with launch_span("verify.pack"):
            arrays = self.scheme.verify_inputs(items)
        return "xla", self._guarded_launch(
            "xla", size, lambda: self._run(self._kernel, arrays, size, rows))

    def _comb_strided(self, items, size: int, rows):
        """The chunk through the comb kernel on every device, or None for
        a key the registry cannot hold."""
        with launch_span("verify.pack"):
            packed = self._comb.pack_for_mesh(items)
        if packed is None:
            return None
        lanes, gtab, qtab = packed
        return self._run(
            lambda placed: self._comb.launch_on_mesh(placed, gtab, qtab),
            [lanes], size, rows)

    def _run(self, kernel, arrays, size: int, rows) -> tuple:
        """Scatter ``arrays`` to the strided ``rows`` of ``size`` padded
        lanes, hand each device its share, launch, read the mask back."""
        with launch_span("verify.place"):
            placed = []
            for a in arrays:
                out = np.zeros((size,) + a.shape[1:], a.dtype)
                out[rows] = a
                placed.append(self._place(out))
        with launch_span("verify.device"):
            out = kernel(*placed)
            io_devices = (_device_span(placed), _device_span([out]))
            return np.asarray(out), io_devices


class QuorumMeshVerifyEngine(JaxVerifyEngine):
    """2D (seq x vote) mesh engine: live cluster waves through the psum.

    A coalesced cluster flush holds commit votes for one or more in-flight
    sequences (each vote's message bytes identify its sequence).  This
    engine groups the flush into a (seq_tile x vote_tile) quorum block —
    one row per distinct message — and runs ONE sharded step per block:
    each device verifies its tile of the block, then weighted vote counts
    ``psum`` across the 'vote' mesh axis (the quorum-decision collective
    of :func:`quorum_decide`).  Per-item verdicts feed the protocol's
    certificate construction unchanged; the psum'd per-sequence counts are
    exposed via :attr:`last_counts` and checked against the host-side
    quorum decisions in CI.

    Padding cells replicate a real item of the same block with weight 0,
    so they cannot inflate counts and the compiled shape is static.

    GRADUATED into the live path (ISSUE 11 tentpole b): selectable
    through ``Configuration.verify_mesh_topology = "2d"`` via the same
    ``CryptoProvider.configure_verify_mesh`` seam as the 1D engine —
    construction from a ``devices`` count builds the (seq × vote) mesh
    (vote axis 2-wide on even widths), raises :class:`MeshUnavailable`
    on narrower hosts (a loud counted downgrade at the seam), and the PR 3
    deadline/retry/breaker/canary contract wraps ``verify`` per mesh
    launch exactly like the 1D engine's.
    """

    supports_pallas = False  # jit partitions its lanes: the XLA kernel
    topology = "2d"

    def __init__(self, devices: Optional[int] = None, mesh=None,
                 quorum: int = 3, seq_tile: int = 8,
                 vote_tile: int = 16, scheme=p256, metrics=None):
        if mesh is None:
            import jax

            avail = list(jax.devices())
            want = len(avail) if not devices else int(devices)
            if want < 1 or want > len(avail):
                raise MeshUnavailable(
                    f"2d verify mesh wants {want} device(s), host has "
                    f"{len(avail)}"
                )
            vote_par = 2 if want % 2 == 0 else 1
            mesh = build_mesh((want // vote_par, vote_par), ("seq", "vote"),
                              devices=avail[:want])
        if tuple(mesh.axis_names) != ("seq", "vote"):
            raise ValueError("QuorumMeshVerifyEngine wants a ('seq','vote') mesh")
        self.mesh = mesh
        seq_par, vote_par = (int(x) for x in mesh.devices.shape)
        self._seq_par, self._vote_par = seq_par, vote_par
        self.seq_tile = -(-seq_tile // seq_par) * seq_par
        self.vote_tile = -(-vote_tile // vote_par) * vote_par
        self.quorum = quorum
        super().__init__(pad_sizes=(self.seq_tile * self.vote_tile,),
                         scheme=scheme, metrics=metrics)
        #: mesh width — the attribute configure_verify_mesh keys
        #: idempotence on (together with ``topology``)
        self.devices = seq_par * vote_par
        self.stats = MeshVerifyStats(devices=self.devices, metrics=metrics)
        self._steps: dict[tuple[int, ...], object] = {}
        #: sharded quorum steps executed (each = one psum over 'vote')
        self.psum_steps = 0
        #: message bytes -> psum'd valid-vote count, from the last flush
        self.last_counts: dict[bytes, int] = {}
        #: message bytes -> count >= quorum, the mesh-side quorum decision
        self.last_decided: dict[bytes, bool] = {}

    def mesh_snapshot(self) -> dict:
        """The engine half of the bench ``mesh`` block (same schema as
        the 1D engine, plus the psum-step count)."""
        out = self.stats.mesh_block(capacity=self.pad_sizes[-1])
        out["topology"] = self.topology
        out["psum_steps"] = self.psum_steps
        return out

    def _build_step(self, ranks: tuple[int, ...]):
        """One jitted shard_map step per input-rank tuple, with the
        shardings its (weights, *inputs) are placed with: kernel inputs
        may be per-vote vectors (rank 3 as a quorum block) or per-vote
        scalars (rank 2, e.g. the toy scheme's key column) — specs are
        derived from the actual ranks like :func:`quorum_decide`."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        scheme = self.scheme

        def step(w, *arrays):
            local = scheme.verify_kernel(*arrays)  # (S/seq, V/vote)
            counts = jax.lax.psum(jnp.sum(local * w, axis=-1), "vote")
            return local, counts

        in_specs = (P("seq", "vote"),) + tuple(
            P("seq", "vote", None) if r == 3 else P("seq", "vote")
            for r in ranks
        )
        shardings = [NamedSharding(self.mesh, s) for s in in_specs]
        # check_vma off: the bignum carry-chain scans initialize carries
        # from unvarying constants, which the checker rejects
        sharded = jax.shard_map(step, mesh=self.mesh, in_specs=in_specs,
                                out_specs=(P("seq", "vote"), P("seq")),
                                check_vma=False)
        return jax.jit(sharded), shardings

    def _probe_item(self):
        sk, pub = self.scheme.keygen(b"quorum-mesh-probe")
        return self.scheme.make_item(b"p", self.scheme.sign_raw(sk, b"p"), pub)

    def verify(self, items) -> list[bool]:
        if not items:
            return []
        import time as _time

        # group the flush into rows by message; rows with more votes than
        # the tile split across rows (verdicts stay exact; the split rows'
        # counts are partial and merged host-side below)
        rows: list[tuple[bytes, list[int]]] = []
        by_msg: dict[bytes, int] = {}
        counted: set = set()  # distinct items whose lane weights count
        duplicate_lanes: set[int] = set()
        for idx, it in enumerate(items):
            msg = it[0]
            # duplicate votes (colocated replicas re-checking the same
            # signature in an un-deduped flush) get verified lanes but
            # weight 0, so the psum'd quorum count tallies DISTINCT valid
            # votes; unhashable scheme items degrade to counting all
            try:
                if it in counted:
                    duplicate_lanes.add(idx)
                else:
                    counted.add(it)
            except TypeError:
                pass
            at = by_msg.get(msg)
            if at is None or len(rows[at][1]) >= self.vote_tile:
                by_msg[msg] = len(rows)
                rows.append((msg, [idx]))
            else:
                rows[at][1].append(idx)

        out = [False] * len(items)
        self.last_counts = {}
        t0 = _time.perf_counter()
        lanes = 0
        # exact per-device REAL-item counts under the (seq x vote) tile
        # mapping: device (r-tile, v-tile) owns rows_per_dev x
        # votes_per_dev cells of each block — the honest fill vector
        # (the contiguous 1D model would fabricate idle devices here)
        dev_counts = [0] * self.devices
        rows_per_dev = self.seq_tile // self._seq_par
        votes_per_dev = self.vote_tile // self._vote_par
        for off in range(0, len(rows), self.seq_tile):
            block = rows[off : off + self.seq_tile]
            flat: list = []
            weights = np.zeros((self.seq_tile, self.vote_tile), np.uint32)
            for r in range(self.seq_tile):
                idxs = block[r][1] if r < len(block) else []
                fill = items[idxs[0]] if idxs else (
                    items[block[0][1][0]] if block else self._probe_item()
                )
                for v in range(self.vote_tile):
                    if v < len(idxs):
                        flat.append(items[idxs[v]])
                        if idxs[v] not in duplicate_lanes:
                            weights[r, v] = 1
                    else:
                        flat.append(fill)
            arrays = self.scheme.verify_inputs(flat)
            shape = (self.seq_tile, self.vote_tile)
            blocks = tuple(a.reshape(shape + a.shape[1:]) for a in arrays)
            ranks = tuple(b.ndim for b in blocks)
            step = self._steps.get(ranks)
            if step is None:
                step = self._steps[ranks] = self._build_step(ranks)
            fn, shardings = step
            # host -> each device's tile directly, not via device 0
            placed = [self._jax.device_put(a, s)
                      for a, s in zip((weights,) + blocks, shardings)]
            mask2d, counts = fn(*placed)
            io_devices = (_device_span(placed), _device_span([mask2d]))
            mask2d = np.asarray(mask2d)
            counts = np.asarray(counts)
            self.psum_steps += 1
            lanes += self.seq_tile * self.vote_tile
            for r, (msg, idxs) in enumerate(block):
                for v, idx in enumerate(idxs):
                    out[idx] = bool(mask2d[r, v])
                    dev_counts[(r // rows_per_dev) * self._vote_par
                               + (v // votes_per_dev)] += 1
                self.last_counts[msg] = (
                    self.last_counts.get(msg, 0) + int(counts[r])
                )
        self.last_decided = {
            m: c >= self.quorum for m, c in self.last_counts.items()
        }
        self.stats.record(len(items), lanes, _time.perf_counter() - t0,
                          per_device=dev_counts, io_devices=io_devices)
        return out


def quorum_decide(mesh, quorum: int, scheme=p256):
    """The distributed quorum step: (S, V, ...) vote block -> (S,) decided.

    Shards sequences over 'seq' and votes over 'vote'; each device runs the
    scheme's verify kernel on its tile, then vote counts `psum` across the
    'vote' axis.  Returns a function over device arrays placed with
    ``NamedSharding(mesh, P('seq', 'vote', *))``.

    Scheme-generic: kernel inputs may be per-vote vectors (rank 3 as a
    quorum block) or per-vote scalars like the host-validity masks of
    ed25519/bls12381 (rank 2); partition specs are derived from the actual
    ranks at first call and the wrapped shard_map is cached per rank tuple.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if tuple(mesh.axis_names) != ("seq", "vote"):
        raise ValueError("quorum_decide wants a ('seq', 'vote') mesh")

    def step(*arrays):
        local = scheme.verify_kernel(*arrays)  # (S/seq, V/vote)
        counts = jax.lax.psum(jnp.sum(local, axis=-1), "vote")
        return counts >= quorum

    cache: dict[tuple[int, ...], object] = {}

    def wrap(ranks: tuple[int, ...]):
        if any(r not in (2, 3) for r in ranks):
            raise ValueError(f"quorum-block inputs must be rank 2 or 3, got {ranks}")
        specs = tuple(
            P("seq", "vote", None) if r == 3 else P("seq", "vote") for r in ranks
        )
        sharded = jax.shard_map(step, mesh=mesh, in_specs=specs,
                                out_specs=P("seq"), check_vma=False)
        return jax.jit(sharded)

    def decide(*arrays):
        ranks = tuple(np.ndim(a) for a in arrays)
        fn = cache.get(ranks)
        if fn is None:
            fn = cache[ranks] = wrap(ranks)
        return fn(*arrays)

    return decide
