"""Static-key comb-table Pallas kernel: P-256 verify in 32 point-op levels.

The fused scan kernel (:mod:`pallas_ecdsa`) treats every lane's public key
as unknown data: it builds a 16-entry joint table per launch and walks 128
Strauss–Shamir windows — 256 doublings + 128 adds per verify.  But in a BFT
deployment both bases are STATIC: G is the curve generator and Q is one of
n replica keys fixed at configuration time (the reference validates a
quorum of known-consenter signatures, /root/reference/internal/bft/
view.go:537-541, viewchanger.go:696-727).  This kernel exploits that:

* **Lim–Lee combs** (w=8 teeth, stride d=32): the host precomputes, once
  per key, a 256-entry table ``T[idx] = Σ_t bit_t(idx)·2^(32t)·K``.  The
  scan then needs only ``d=32`` iterations of (1 complete doubling + 2
  complete additions) for the full ``u1·G + u2·Q`` — 32 doublings + 64
  adds, a ~4× cut in point-operation count.
* **Table lookups ride the MXU.**  TPU has no per-lane gather; instead the
  per-lane digit becomes a one-hot column and the lookup is a matmul:
  ``dot(table (rows,256), onehot (256,B))``.  Entries are stored as SPLIT
  BYTES (16-bit limbs -> lo/hi rows) in bfloat16, so every product is
  0/1 × (<256) — exact in bf16×bf16->f32 — and the n-key table stack
  stays small: (npad·96, 256) bf16 = npad·49KB of VMEM.
* **Key validation moves to registration.**  The engine checks each
  replica key is on-curve ONCE at registration (host ints), so the
  per-signature on-curve check disappears from the kernel.

Layout/arithmetic building blocks (limb-major (NL, B), Montgomery fields,
complete RCB15 formulas, the Fermat inversion) are shared with
:mod:`pallas_ecdsa`.
"""

from __future__ import annotations

import functools
import hashlib
import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import p256
from ..obs.recorder import launch_span
from .bignum import to_limbs
from .p256 import B as CURVE_B, FP, GX, GY, N, NLIMBS, P
from .pallas_ecdsa import (
    INV_DIGITS,
    LIMB_BITS,
    NL,
    _add_rows,
    _B_MONT,
    _ccol,
    _eq,
    _Fld,
    _grp,
    _inv_n,
    _is_zero,
    _limbs,
    _N,
    _N_NPRIME,
    _N_ONE,
    _N_R2,
    _P,
    _P_NPRIME,
    _P_ONE,
    _P_R2,
    _point_add,
    _point_double,
    _select,
    _sub_borrow,
)

#: comb teeth (bits per table index) and stride (scan iterations)
TEETH = 8
STRIDE = 32  # = 256 / TEETH
TSIZE = 1 << TEETH  # 256 table entries per key
#: table rows: [0:48] = low bytes of (X,Y,Z) Montgomery limbs, [48:96] = high
ROWS = 6 * NL  # 96
#: scoped-VMEM limit of the comb kernels (this one and pallas_ed25519's).
#: The whole key stack is one block: at the registry's cap of 128 keys the
#: table block (6.3 MB), its loaded value and the f32 one-hot product need
#: 18.5 MB in the one-grid-step shapes, which the compiler's 16 MiB default
#: refuses.  32 MiB admits every key count up to the cap at tiles 128 and
#: 512; a v5e core has 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


# ---------------------------------------------------------------------------
# host-side table precomputation (Python ints; once per key per process)
# ---------------------------------------------------------------------------


def is_on_curve_int(pub) -> bool:
    """Host check y² = x³ - 3x + b (mod p) for an affine public key."""
    x, y = pub
    if not (0 <= x < P and 0 <= y < P):
        return False
    return (y * y - (x * x * x - 3 * x + CURVE_B)) % P == 0


def _comb_entries(point) -> list:
    """All 2^TEETH subset sums of {2^(STRIDE·t)·point : t < TEETH}."""
    bases = [point]
    for _ in range(TEETH - 1):
        b = bases[-1]
        for _ in range(STRIDE):
            b = p256._point_add_int(b, b)
        bases.append(b)
    table = [None] * TSIZE
    for idx in range(1, TSIZE):
        low = idx & -idx
        table[idx] = p256._point_add_int(table[idx ^ low], bases[low.bit_length() - 1])
    return table


def build_table(pub) -> np.ndarray:
    """(ROWS, TSIZE) float32 comb table for one affine point.

    Column = table index; rows split each Montgomery limb into lo/hi bytes
    so a one-hot matmul in bf16 selects entries exactly.  The identity
    (entry 0) is stored as the projective identity (0 : 1 : 0) in the
    Montgomery domain, which the complete addition formulas absorb without
    any masking.
    """
    entries = _comb_entries(pub)
    out = np.zeros((ROWS, TSIZE), dtype=np.float32)
    one_m = FP.encode(1)
    for idx, ent in enumerate(entries):
        if ent is None:
            limbs = np.concatenate([np.zeros(NL, np.uint32), one_m,
                                    np.zeros(NL, np.uint32)])
        else:
            limbs = np.concatenate([FP.encode(ent[0]), FP.encode(ent[1]), one_m])
        out[:48, idx] = limbs & 0xFF
        out[48:, idx] = limbs >> 8
    return out


@functools.lru_cache(maxsize=1)
def g_table() -> np.ndarray:
    """The generator's comb table (shared by every verification)."""
    return build_table((GX, GY))


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def pack_items(items, registry) -> tuple:
    """Fast host prep: items -> ((B,32) uint8 e/r/s little-endian, kidx).

    Transfers to the device at 96 B/sig instead of the 192 B/sig of the
    padded uint32 limb arrays that :func:`p256.verify_inputs` hands the
    arbitrary-key kernel (built from bytes as here, since PR 29): C-speed
    ``int.to_bytes`` + ``frombuffer`` (~1 us/sig).  Raises ValueError via
    the registry for unregistrable keys.
    """
    B = len(items)
    e8 = np.empty((B, 32), np.uint8)
    r8 = np.empty((B, 32), np.uint8)
    s8 = np.empty((B, 32), np.uint8)
    kidx = np.empty(B, np.int32)
    for i, (msg, r, s, pub) in enumerate(items):
        e8[i] = np.frombuffer(hashlib.sha256(msg).digest()[::-1], np.uint8)
        r8[i] = np.frombuffer(r.to_bytes(32, "little"), np.uint8)
        s8[i] = np.frombuffer(s.to_bytes(32, "little"), np.uint8)
        kidx[i] = registry.register(pub)
    return e8, r8, s8, kidx


def _maybe_unpack(a):
    """(B,32) uint8 little-endian bytes -> (B,16) uint32 limbs; uint32
    limb arrays pass through."""
    a = jnp.asarray(a)
    if a.dtype == jnp.uint8:
        a32 = a.astype(jnp.uint32)
        return a32[..., 0::2] | (a32[..., 1::2] << 8)
    return a


class _InvOps:
    """dig_at shim for the shared Fermat inversion (static-exponent reads)."""

    def __init__(self, digs_ref):
        self._digs_ref = digs_ref

    def dig_at(self, i):
        return self._digs_ref[0, i]  # SMEM scalar read


def _comb_digits(u, nb: int) -> list:
    """(NL, B) scalar -> STRIDE (B,) int32 comb indices, MSB-first.

    Row k selects column c = STRIDE-1-k: bits {c + STRIDE·t : t < TEETH}.
    """
    rows = []
    for k in range(STRIDE):
        c = STRIDE - 1 - k
        idx = jnp.zeros((nb,), jnp.uint32)
        for t in range(TEETH):
            p = c + STRIDE * t
            limb, off = p // LIMB_BITS, p % LIMB_BITS
            idx = idx | (((u[limb] >> jnp.uint32(off)) & jnp.uint32(1))
                         << jnp.uint32(t))
        rows.append(idx.astype(jnp.int32))
    return rows


def _sel_rows(table_f32):
    """(ROWS, B) f32 selected columns -> (3, NL, B) uint32 point."""
    lo = table_f32[:48, :]
    hi = table_f32[48:, :]
    # exact: values < 2^16; Mosaic has no f32->uint32 cast, go via int32
    limbs = (lo + hi * 256.0).astype(jnp.int32).astype(jnp.uint32)
    return jnp.stack([limbs[0:NL], limbs[NL:2 * NL], limbs[2 * NL:3 * NL]],
                     axis=-3)


def _kernel(nkeys, digs_ref, e_ref, r_ref, s_ref, kidx_ref, gtab_ref,
            qtab_ref, out_ref, idx_scratch):
    e, r, s = e_ref[:], r_ref[:], s_ref[:]
    kidx = kidx_ref[0, :]
    nb = e.shape[-1]
    fp = _Fld(_P, _P_NPRIME, nb)
    fn = _Fld(_N, _N_NPRIME, nb)
    b_m = _ccol(_B_MONT, nb)
    one_p = _ccol(_P_ONE, nb)
    one_n = _ccol(_N_ONE, nb)
    p_r2 = _ccol(_P_R2, nb)
    n_r2 = _ccol(_N_R2, nb)
    one_raw = _ccol(_limbs(1), nb)
    zero = jnp.zeros((NL, nb), jnp.uint32)
    inf = jnp.stack([zero, one_p, zero], axis=-3)

    # 1 <= r, s < n
    _, rb = _sub_borrow(r, fn.N)
    _, sb = _sub_borrow(s, fn.N)
    r_ok = (jnp.uint32(1) - _is_zero(r)) * rb
    s_ok = (jnp.uint32(1) - _is_zero(s)) * sb

    # u1 = e/s, u2 = r/s (mod n); shared Fermat inversion
    d, eb = _sub_borrow(e, fn.N)
    e_red = _select(eb, e, d)
    s_m, r_m_n, e_m_n = _grp(fn.mul, [(s, n_r2), (r, n_r2), (e_red, n_r2)])
    w = _inv_n(fn, one_n, s_m, _InvOps(digs_ref))
    u1m, u2m = _grp(fn.mul, [(e_m_n, w), (r_m_n, w)])
    u1, u2 = _grp(fn.mul, [(u1m, one_raw), (u2m, one_raw)])

    # stash comb digits: rows [0:STRIDE) = u1/G, [STRIDE:2*STRIDE) = u2/Q
    for k, v in enumerate(_comb_digits(u1, nb)):
        idx_scratch[k, :] = v
    for k, v in enumerate(_comb_digits(u2, nb)):
        idx_scratch[STRIDE + k, :] = v

    gtab = gtab_ref[:]
    qtab = qtab_ref[:]
    iota_t = lax.broadcasted_iota(jnp.int32, (TSIZE, nb), 0)

    def scan_body(i, acc):
        acc = _point_double(fp, b_m, acc)
        gd = idx_scratch[pl.ds(i, 1), :][0]
        qd = idx_scratch[pl.ds(i + STRIDE, 1), :][0]
        oh_g = (iota_t == gd[None, :]).astype(jnp.bfloat16)
        oh_q = (iota_t == qd[None, :]).astype(jnp.bfloat16)
        sel_g = jnp.dot(gtab, oh_g, preferred_element_type=jnp.float32)
        aq = jnp.dot(qtab, oh_q, preferred_element_type=jnp.float32)
        # per-key masked reduce over the stacked table rows (no gather,
        # no reshape across sublane tiles: nkeys static slices)
        sq = jnp.zeros((ROWS, nb), jnp.float32)
        for k in range(nkeys):
            mask = (kidx == k).astype(jnp.float32)[None, :]
            sq = sq + aq[k * ROWS:(k + 1) * ROWS, :] * mask
        tg = _sel_rows(sel_g)
        tq = _sel_rows(sq)
        # complete formulas absorb identities and coincidences, so the two
        # table adds need no special cases
        acc = _point_add(fp, b_m, acc, tg)
        return _point_add(fp, b_m, acc, tq)

    acc = lax.fori_loop(0, STRIDE, scan_body, inf)
    xr, zr = acc[..., 0, :, :], acc[..., 2, :, :]

    not_inf = jnp.uint32(1) - _is_zero(zr)
    # projective comparison: x_aff in {r, r+n} ∩ [0, p)
    c17 = _add_rows(r, fn.N)
    c_in_range = (c17[NL] == 0).astype(jnp.uint32)
    c16 = c17[:NL]
    _, c_lt_p = _sub_borrow(c16, fp.N)
    c_ok = c_in_range * c_lt_p
    r_mp, c_mp = _grp(fp.mul, [(r, p_r2), (c16, p_r2)])
    mr, mc = _grp(fp.mul, [(r_mp, zr), (c_mp, zr)])
    match = _eq(mr, xr) | (c_ok * _eq(mc, xr))
    out_ref[:] = (match * not_inf * r_ok * s_ok)[None, :]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def ecdsa_verify_comb(e, r, s, kidx, gtab, qtab, tile: int = 128,
                      interpret: bool = False):
    """Batched P-256 verify against registered keys.

    ``e, r, s``: (B, 16) standard-domain uint32 limbs (as
    :func:`p256.verify_inputs`); ``kidx``: (B,) int32 index of each lane's
    key in the table stack; ``gtab``: (96, 256) generator comb table;
    ``qtab``: (nkeys*96, 256) stacked per-key comb tables (both float32 or
    bfloat16; cast to bf16 for the MXU one-hot select).  Returns (B,)
    uint32 validity mask.  Padded lanes (r = s = 0) always fail.
    """
    from jax.experimental.pallas import tpu as pltpu

    if tile % 128 and not interpret:
        raise ValueError(f"tile must be a multiple of 128 lanes, got {tile}")
    if qtab.shape[0] % ROWS:
        raise ValueError("qtab row count must be a multiple of 96")
    nkeys = qtab.shape[0] // ROWS

    e, r, s = _maybe_unpack(e), _maybe_unpack(r), _maybe_unpack(s)
    bsz = e.shape[0]
    pad = (-bsz) % tile
    if pad:
        e, r, s = (jnp.pad(jnp.asarray(a), ((0, pad), (0, 0)))
                   for a in (e, r, s))
        kidx = jnp.pad(jnp.asarray(kidx), (0, pad))
    total = e.shape[0]
    args = [jnp.transpose(jnp.asarray(a)).astype(jnp.uint32)
            for a in (e, r, s)]
    kidx = jnp.asarray(kidx, jnp.int32).reshape(1, total)
    gtab = jnp.asarray(gtab, jnp.bfloat16)
    qtab = jnp.asarray(qtab, jnp.bfloat16)

    spec = pl.BlockSpec((NL, tile), lambda i: (0, i))
    dig_spec = pl.BlockSpec((1, INV_DIGITS.shape[0]), lambda i: (0, 0),
                            memory_space=pltpu.SMEM)
    kidx_spec = pl.BlockSpec((1, tile), lambda i: (0, i))
    gtab_spec = pl.BlockSpec((ROWS, TSIZE), lambda i: (0, 0))
    qtab_spec = pl.BlockSpec((nkeys * ROWS, TSIZE), lambda i: (0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, nkeys),
        out_shape=jax.ShapeDtypeStruct((1, total), jnp.uint32),
        grid=(total // tile,),
        in_specs=[dig_spec, spec, spec, spec, kidx_spec, gtab_spec,
                  qtab_spec],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        scratch_shapes=[pltpu.VMEM((2 * STRIDE, tile), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(jnp.asarray(INV_DIGITS).reshape(1, -1), *args, kidx, gtab, qtab)
    return out[0, :bsz]


#: one lane of a mesh launch as the host hands it over: e | r | s (32
#: little-endian bytes each) | the key's index (int32, little-endian)
MESH_LANE_BYTES = 100


def pack_mesh_lanes(arrays, kidx) -> np.ndarray:
    """:func:`pack_items`' columns side by side, (B, MESH_LANE_BYTES)
    uint8: ONE host array a launch, so one scatter and one transfer to
    each device where there were four.  Every hand-over to the runtime
    lets the interpreter lock go, and getting it back from a busy loop
    thread has cost the launch's thread up to 5 ms a time (PERF.md)."""
    key_bytes = np.ascontiguousarray(kidx, "<i4").view(np.uint8)
    return np.concatenate([*arrays, key_bytes.reshape(-1, 4)], axis=1)


def mesh_comb_launcher(mesh, tile: int = 128, interpret: bool = False):
    """:func:`ecdsa_verify_comb` on every device of a 1D ``mesh`` at once.

    ``pallas_call`` has no partitioning rules, so ``jit`` alone cannot
    split it; ``shard_map`` can: the lanes (:func:`pack_mesh_lanes`) and
    the mask are sharded along the mesh's one axis, the two tables are
    whole on every device, and each device unpacks its own lanes and runs
    the kernel on them.  Lanes per device must be a multiple of ``tile``
    (each device pads its shard to one otherwise, and the mask would no
    longer line up).  Returns the jitted function ``(lanes, gtab, qtab) ->
    mask``; its XLA module is named ``jit_ecdsa_verify_comb_mesh``.
    """
    from jax.sharding import PartitionSpec

    lane, whole = PartitionSpec(mesh.axis_names[0]), PartitionSpec()

    def ecdsa_verify_comb_mesh(lanes, gtab, qtab):
        e, r, s = (lanes[:, at:at + 32] for at in (0, 32, 64))
        k = lanes[:, 96:].astype(jnp.int32)
        kidx = k[:, 0] | (k[:, 1] << 8) | (k[:, 2] << 16) | (k[:, 3] << 24)
        return ecdsa_verify_comb(e, r, s, kidx, gtab, qtab, tile=tile,
                                 interpret=interpret)

    # check_vma off: the kernel's carry chains start from unvarying
    # constants, which the checker rejects (as in parallel/engine.py)
    return jax.jit(jax.shard_map(
        ecdsa_verify_comb_mesh, mesh=mesh, in_specs=(lane, whole, whole),
        out_specs=lane, check_vma=False))


# ---------------------------------------------------------------------------
# key registry + engine adapter
# ---------------------------------------------------------------------------


class CombRegistryFull(ValueError):
    """The registry's key cap was reached — NOT an invalid key.

    Callers distinguish this from key-validation failures: a full registry
    only means this engine's comb path can't serve the extra keys (the
    generic kernel still verifies them fine), whereas an invalid key is a
    configuration error worth failing loudly over.
    """


def _p256_validate(pub):
    if not is_on_curve_int(pub):
        raise ValueError("public key is not on the P-256 curve")


class CombKeyRegistry:
    """pub -> table index; tables built once per key, stacked and padded.

    Scheme-agnostic: ``validate``/``build`` default to the P-256 curve
    check and comb builder; :mod:`pallas_ed25519` instantiates it with
    Edwards equivalents.  The stack is padded to a power-of-two key count
    so jit re-traces at most log2(cap) times as membership grows.
    Padding tables are zero — their Z rows decode to 0 so any (buggy)
    reference to a padded index yields the point at infinity and a failed
    verify, never a false accept.
    """

    def __init__(self, cap: int = 128, validate=None, build=None):
        self.cap = cap
        self._validate = validate if validate is not None else _p256_validate
        self._build = build if build is not None else build_table
        self._index: dict = {}
        self._tables: list[np.ndarray] = []
        self._stack: np.ndarray | None = None

    def __len__(self) -> int:
        return len(self._tables)

    def register(self, pub) -> int:
        """Index for ``pub`` (validating + building its table on first use).

        Raises ValueError for invalid keys or when the cap is exceeded.
        """
        idx = self._index.get(pub)
        if idx is not None:
            return idx
        if len(self._tables) >= self.cap:
            raise CombRegistryFull(f"comb key registry full ({self.cap})")
        self._validate(pub)
        idx = len(self._tables)
        self._index[pub] = idx
        self._tables.append(self._build(pub))
        self._stack = None
        return idx

    def index_of(self, pub):
        """Registered index or None (no side effects)."""
        return self._index.get(pub)

    def slots(self) -> int:
        """Key slots of the stack: the key count padded to a power of two."""
        npad = 1
        while npad < len(self._tables):
            npad *= 2
        return npad

    def stacked(self) -> np.ndarray:
        """(slots*96, 256) float32 stack."""
        if self._stack is None:
            stack = np.zeros((self.slots() * ROWS, TSIZE), np.float32)
            for i, t in enumerate(self._tables):
                stack[i * ROWS:(i + 1) * ROWS] = t
            self._stack = stack
        return self._stack


class CombVerifier:
    """Engine adapter: items -> comb-kernel launch with cached device tables.

    ``verify(items)`` returns the host mask, or None when any item's key
    is unregistrable (caller falls back to the generic kernel).  The prewarm /
    device-table caching / pad-and-launch scaffolding is scheme-agnostic;
    subclasses (pallas_ed25519.Ed25519CombVerifier) override the four
    ``_...`` hooks.

    ``mesh``: a 1D device mesh (P-256 only).  The tables are then whole on
    EVERY device of it, placed once per registry version, and
    ``MeshVerifyEngine`` launches through ``launch_on_mesh`` with
    :meth:`pack_for_mesh`'s lanes, which it lays out over the mesh itself.  ``interpret`` is the
    kernel's own switch, for tests on the CPU.
    """

    def __init__(self, tile: int = 128, cap: int = 128, mesh=None,
                 interpret: bool = False):
        self.registry = self._make_registry(cap)
        self.tile = tile
        self.mesh = mesh
        #: ``(lanes, gtab, qtab) -> mask``: :meth:`pack_for_mesh`'s lanes,
        #: padded and sharded over the mesh by the caller, through the
        #: kernel on every device; the mask comes back sharded as they are
        self.launch_on_mesh = None if mesh is None else \
            mesh_comb_launcher(mesh, tile, interpret)
        self._pending_prewarm: list = []
        self._dev_version: int = -1
        self._dev_gtab = None
        self._dev_qtab = None
        # Engines overlap flushes via asyncio.to_thread, so concurrent
        # verify() calls can race first-use registration: two threads both
        # computing idx=len(tables) would bind different keys to one index,
        # making signatures verify against the wrong replica's key.  All
        # registry / prewarm / device-table mutation happens under this
        # lock; only the kernel launch itself runs outside it.
        self._reg_lock = threading.RLock()
        self._warned_full = False

    # -- scheme hooks (P-256 defaults) --------------------------------------

    def _make_registry(self, cap: int) -> CombKeyRegistry:
        return CombKeyRegistry(cap=cap)

    def _validate_key(self, pub) -> None:
        _p256_validate(pub)

    def _base_table(self) -> np.ndarray:
        return g_table()

    def _pack(self, items):
        """items -> ([(B,32) uint8 arrays...], ok-mask-or-None, kidx)."""
        e8, r8, s8, kidx = pack_items(items, self.registry)
        return [e8, r8, s8], None, kidx

    def _launch(self, arrays, ok, kidx, gtab, qtab):
        return ecdsa_verify_comb(*arrays, kidx, gtab, qtab, tile=self.tile)

    # -- shared scaffolding --------------------------------------------------

    def prewarm_keys(self, pubs) -> None:
        """Record a known key set (e.g. the whole keyring) to register
        before the first verify, so membership growth never re-traces
        mid-protocol.  Validation is EAGER (an invalid key raises here, at
        provider construction); table building is DEFERRED — it costs
        ~2.4 ms/key of host EC arithmetic, which engines on non-TPU
        backends (where the comb path never runs) must not pay.  If the
        set exceeds remaining registry capacity, the fitting prefix is
        still queued and CombRegistryFull reports the overflow — callers
        degrade those keys to the generic kernel."""
        pubs = list(pubs)
        for pub in pubs:
            self._validate_key(pub)
        with self._reg_lock:
            known = set(self.registry._index) | set(self._pending_prewarm)
            room = self.registry.cap - len(known)
            fitting, overflow = [], 0
            for pub in pubs:
                if pub in known:
                    continue
                if len(fitting) < room:
                    fitting.append(pub)
                    known.add(pub)
                else:
                    overflow += 1
            # Queue what fits BEFORE signalling overflow: those keys still
            # get their tables built up front, avoiding the mid-protocol
            # build/retrace stall prewarm exists to prevent.  Chunks whose
            # signers are all registered keep the comb path; any chunk
            # containing an overflow key degrades wholly to the generic
            # kernel (verify short-circuits it rather than splitting the
            # launch).
            self._pending_prewarm.extend(fitting)
            if overflow:
                raise CombRegistryFull(
                    f"comb key registry full ({self.registry.cap}): "
                    f"{overflow} key(s) beyond capacity "
                    f"({len(fitting)} queued)")

    def _warn_registry_full(self, exc) -> None:
        """Warn ONCE per verifier when registration hits a full registry
        (prewarm drain or first-use) — chunks carrying unregistrable keys
        silently riding the generic kernel would hide the fast path dying."""
        if not self._warned_full:
            self._warned_full = True
            import logging

            logging.getLogger("smartbft_tpu.crypto").warning(
                "comb key registry full at verify time; chunks with "
                "unregistered keys fall back to the generic verify "
                "kernel: %s", exc,
            )

    def _put_table(self, table: np.ndarray):
        """One table onto the device — or, on a mesh, whole onto every
        device of it, straight from the host."""
        if self.mesh is None:
            return jnp.asarray(table, jnp.bfloat16)
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(table.astype(jnp.bfloat16),
                              NamedSharding(self.mesh, PartitionSpec()))

    def _device_tables(self):
        version = len(self.registry)
        if version != self._dev_version:
            self._dev_gtab = self._put_table(self._base_table())
            self._dev_qtab = self._put_table(self.registry.stacked())
            self._dev_version = version
        return self._dev_gtab, self._dev_qtab

    def verify(self, items, pad_to: int):
        # Registry mutation (drain + first-use registration) and the
        # device-table snapshot happen under the lock; the per-item
        # hash/pack and the launch run outside it, so concurrent flushes
        # only serialize on the (once-per-key) table builds, not on every
        # chunk's O(n) hashing.
        with launch_span("verify.pack"):
            packed = self._pack_chunk(items, pad_to)
        if packed is None:
            return None
        arrays, ok, kidx, gtab, qtab = packed
        with launch_span("verify.device"):
            # dispatch to the mask's readback.  Read back BEFORE slicing:
            # an eager slice of the device array would compile a tiny
            # program for every new wave size
            mask = np.asarray(self._launch(arrays, ok, kidx, gtab, qtab))
        return mask[:len(items)]

    def pack_for_mesh(self, items):
        """Register, hash and pack one chunk, unpadded -> ``(lanes, gtab,
        qtab)`` for ``launch_on_mesh``, or None when a key is
        unregistrable."""
        packed = self._pack_chunk(items, len(items))
        if packed is None:
            return None
        arrays, _ok, kidx, gtab, qtab = packed
        return pack_mesh_lanes(arrays, kidx), gtab, qtab

    def _pack_chunk(self, items, pad_to: int):
        """Register, hash, pack and pad one chunk -> the launch's inputs,
        or None when a key is unregistrable."""
        chunk_pubs = {it[-1] for it in items}
        with self._reg_lock:
            if self._pending_prewarm:
                pending, self._pending_prewarm = self._pending_prewarm, []
                try:
                    for pub in pending:
                        self.registry.register(pub)
                except CombRegistryFull as exc:
                    # Other engine users filled the registry after our
                    # prewarm passed its cap check.  Warn like the
                    # construction-time overflow does, but keep going:
                    # chunks whose signers are all registered still ride
                    # the comb path.
                    self._warn_registry_full(exc)
            try:
                # O(distinct signers) lock-held work, not O(items): a
                # quorum wave repeats each replica's key thousands of times
                for pub in chunk_pubs:
                    self.registry.register(pub)
            except CombRegistryFull as exc:
                # An unregistrable key sends the WHOLE chunk to the generic
                # kernel (splitting the launch would double the fixed
                # per-launch cost).  This raises before any hashing, and
                # must not escape — the engine would misread it as a
                # kernel failure.
                self._warn_registry_full(exc)
                return None
            except ValueError:
                return None  # invalid key: generic kernel
            gtab, qtab = self._device_tables()
        try:
            # every key is now registered, so _pack's register calls are
            # pure dict hits — no shared-state mutation outside the lock
            arrays, ok, kidx = self._pack(items)
        except ValueError:
            return None
        n = len(items)
        if pad_to > n:
            z = np.zeros((pad_to - n, 32), np.uint8)
            arrays = [np.concatenate([a, z]) for a in arrays]
            if ok is not None:
                ok = np.concatenate([ok, np.zeros(pad_to - n, np.uint32)])
            kidx = np.concatenate([kidx, np.zeros(pad_to - n, np.int32)])
        return arrays, ok, kidx, gtab, qtab
