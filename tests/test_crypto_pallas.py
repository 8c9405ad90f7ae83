"""Unit tests for the limb-major Pallas ECDSA kernel building blocks.

The full fused kernel compiles for minutes on CPU, so the suite checks the
layer beneath it: the limb-major Montgomery field, the curve formulas, and
the digit decomposition, each against the host big-int reference.  The
end-to-end mask equivalence runs where it is cheap — on the TPU bench
(bench_pallas) and behind SMARTBFT_SLOW_TESTS=1 here.
"""

import functools
import os
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from smartbft_tpu.crypto import p256
from smartbft_tpu.crypto import pallas_ecdsa as pe

rng = random.Random(7)

# jit the building blocks under test: eager dispatch of their unrolled
# chains costs ~40-60s per test on 1 CPU core, while the jitted versions
# hit the persistent compile cache on every run after the first
_jit_point_add = jax.jit(pe._point_add, static_argnums=0)
_jit_point_double = jax.jit(pe._point_double, static_argnums=0)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _jit_inv_n(fn, one_n, sm, ops):
    return pe._inv_n(fn, one_n, sm, ops)


def to_cols(vals, nl=pe.NL):
    """List of ints -> (NL, B) limb-major array."""
    out = np.zeros((nl, len(vals)), np.uint32)
    for j, v in enumerate(vals):
        for i in range(nl):
            out[i, j] = v & pe.LIMB_MASK
            v >>= pe.LIMB_BITS
    return jnp.asarray(out)


def from_cols(arr):
    a = np.asarray(arr, np.uint64)
    out = []
    for j in range(a.shape[1]):
        v = 0
        for i in range(a.shape[0] - 1, -1, -1):
            v = (v << pe.LIMB_BITS) | int(a[i, j])
        out.append(v)
    return out


@pytest.fixture(scope="module")
def fp():
    return pe._Fld(pe._P, pe._P_NPRIME, 4)


def test_field_mul_sqr_add_sub(fp):
    xs = [rng.randrange(p256.P) for _ in range(4)]
    ys = [rng.randrange(p256.P) for _ in range(4)]
    R = pe.R
    xm = to_cols([x * R % p256.P for x in xs])
    ym = to_cols([y * R % p256.P for y in ys])
    got = from_cols(fp.mul(xm, ym))
    exp = [x * y * R % p256.P for x, y in zip(xs, ys)]
    assert got == exp
    got = from_cols(fp.sqr(xm))
    exp = [x * x * R % p256.P for x in xs]
    assert got == exp
    got = from_cols(fp.add(xm, ym))
    exp = [(x * R + y * R) % p256.P for x, y in zip(xs, ys)]
    assert got == exp
    got = from_cols(fp.sub(xm, ym))
    exp = [(x * R - y * R) % p256.P for x, y in zip(xs, ys)]
    assert got == exp


def affine(point):
    """(3, NL, B) Montgomery projective -> list of affine int pairs."""
    R = pe.R
    X = from_cols(point[..., 0, :, :])
    Y = from_cols(point[..., 1, :, :])
    Z = from_cols(point[..., 2, :, :])
    out = []
    rinv = pow(R, -1, p256.P)
    for x, y, z in zip(X, Y, Z):
        x, y, z = (v * rinv % p256.P for v in (x, y, z))
        zi = pow(z, -1, p256.P)
        out.append((x * zi % p256.P, y * zi % p256.P))
    return out


def test_point_double_matches_add(fp):
    nb = 2
    fld = pe._Fld(pe._P, pe._P_NPRIME, nb)
    b_m = pe._ccol(pe._B_MONT, nb)
    one_p = pe._ccol(pe._P_ONE, nb)
    d1, q1 = p256.keygen(b"pal-1")
    d2, q2 = p256.keygen(b"pal-2")
    R = pe.R
    pt = jnp.stack([
        to_cols([q1[0] * R % p256.P, q2[0] * R % p256.P]),
        to_cols([q1[1] * R % p256.P, q2[1] * R % p256.P]),
        one_p,
    ], axis=-3)
    dbl = _jit_point_double(fld, b_m, pt)
    add = _jit_point_add(fld, b_m, pt, pt)
    assert affine(dbl) == affine(add)
    # ...and both agree with the host reference doubling
    for got, q in zip(affine(dbl), (q1, q2)):
        assert got == p256.scalar_mult_int(2, q)


def test_point_identity_cases(fp):
    nb = 1
    fld = pe._Fld(pe._P, pe._P_NPRIME, nb)
    b_m = pe._ccol(pe._B_MONT, nb)
    one_p = pe._ccol(pe._P_ONE, nb)
    zero = jnp.zeros((pe.NL, nb), jnp.uint32)
    inf = jnp.stack([zero, one_p, zero], axis=-3)
    d, q = p256.keygen(b"pal-3")
    R = pe.R
    pt = jnp.stack(
        [to_cols([q[0] * R % p256.P]), to_cols([q[1] * R % p256.P]), one_p],
        axis=-3,
    )
    # inf + P = P;  dbl(inf) = inf
    s = _jit_point_add(fld, b_m, inf, pt)
    assert affine(s) == [q]
    di = _jit_point_double(fld, b_m, inf)
    assert from_cols(di[..., 2, :, :])[0] == 0


def test_inv_n():
    nb = 2
    fn = pe._Fld(pe._N, pe._N_NPRIME, nb)
    one_n = pe._ccol(pe._N_ONE, nb)
    ss = [rng.randrange(1, p256.N) for _ in range(nb)]
    R = pe.R
    sm = to_cols([s * R % p256.N for s in ss])
    inv = _jit_inv_n(fn, one_n, sm, pe._JaxOps(jnp.asarray(pe.INV_DIGITS)))
    got = from_cols(inv)
    exp = [pow(s, -1, p256.N) * R % p256.N for s in ss]
    assert got == exp


def test_digits_msb():
    v = rng.randrange(1 << 256)
    a = to_cols([v])
    rows = pe._digits2(a, 128)
    got = [int(np.asarray(r)[0]) for r in rows]
    exp = [(v >> (2 * (127 - k))) & 3 for k in range(128)]
    assert got == exp


def test_digits_w_crosses_limb_boundaries():
    """3-bit windows straddle 16-bit limbs; every digit must still match
    the Python-int reference."""
    for _ in range(4):
        v = rng.randrange(1 << 256)
        a = to_cols([v])
        ndig = -(-256 // 3)
        rows = pe._digits_w(a, ndig, 3)
        got = [int(np.asarray(r)[0]) for r in rows]
        exp = [(v >> (3 * (ndig - 1 - k))) & 7 for k in range(ndig)]
        assert got == exp
    # width 2 agrees with the dedicated reader
    v = rng.randrange(1 << 256)
    a = to_cols([v])
    assert [int(np.asarray(r)[0]) for r in pe._digits_w(a, 128, 2)] == \
           [int(np.asarray(r)[0]) for r in pe._digits2(a, 128)]


def test_pallas_ops_plumbing_interpret():
    """The Mosaic-path dynamic lookups (_PallasOps: VMEM idx scratch via
    pl.ds, SMEM digit reads) exercised through a real pallas_call in
    interpret mode — a tiny graph, so it runs on every CPU CI pass even
    though the full fused kernel is gated below."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb = 4
    n_rows = 8

    def kernel(digs_ref, a_ref, out_ref, idx_scratch):
        ops = pe._PallasOps(digs_ref, idx_scratch)
        ops.stash_idx([a_ref[0, :] + jnp.uint32(k) for k in range(n_rows)])

        def body(i, acc):
            return acc + ops.idx_at(i)

        acc = jax.lax.fori_loop(
            0, n_rows, body, jnp.zeros((nb,), jnp.uint32)
        )
        # INV_DIGITS is int32 and dig_at is an SMEM scalar read; the
        # uint32 + int32 sum promotes to int32, which interpret mode's
        # strict ref-dtype check rejects on store (the fused kernel only
        # ever COMPARES digits, so production never hits the promotion)
        out_ref[0, :] = acc + ops.dig_at(0).astype(jnp.uint32)

    digs = jnp.asarray(pe.INV_DIGITS).reshape(1, -1)
    a = jnp.arange(nb, dtype=jnp.uint32).reshape(1, nb)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, nb), jnp.uint32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, nb), lambda: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nb), lambda: (0, 0)),
        scratch_shapes=[pltpu.VMEM((n_rows, nb), jnp.uint32)],
        interpret=True,
    )(digs, a)
    base = np.arange(nb, dtype=np.uint32)
    want = sum(base + k for k in range(n_rows)) + int(pe.INV_DIGITS[0])
    assert np.asarray(out)[0].tolist() == want.tolist()


@pytest.mark.skipif(
    os.environ.get("SMARTBFT_SLOW_TESTS") != "1",
    reason="full fused-kernel compile takes minutes on CPU",
)
def test_full_kernel_matches_reference():
    import jax

    msgs = [bytes([i]) * 12 for i in range(8)]
    items = []
    for i, m in enumerate(msgs):
        d, pub = p256.keygen(bytes([i]))
        r, s = p256.sign(d, m)
        if i % 3 == 2:
            r = (r + 1) % p256.N
        items.append((m, r, s, pub))
    e, r, s, qx, qy = p256.verify_inputs(items)

    @jax.jit
    def body(e, r, s, qx, qy):
        ops = pe._JaxOps(jnp.asarray(pe.INV_DIGITS))
        return pe._verify_block(ops, e.T, r.T, s.T, qx.T, qy.T)

    mask = np.asarray(body(e, r, s, qx, qy))
    exp = np.array([p256.verify_item(it) for it in items], np.uint32)
    assert (mask == exp).all()


class _FakeJax:
    def __init__(self, backend):
        self._backend = backend

    def default_backend(self):
        if isinstance(self._backend, Exception):
            raise self._backend
        return self._backend

    def jit(self, fn):
        return fn


def _engine_probe(backend, env, monkeypatch):
    """Evaluate JaxVerifyEngine._use_pallas against a faked backend."""
    from smartbft_tpu.crypto.provider import JaxVerifyEngine

    if env is None:
        monkeypatch.delenv("SMARTBFT_PALLAS", raising=False)
    else:
        monkeypatch.setenv("SMARTBFT_PALLAS", env)
    eng = JaxVerifyEngine(pad_sizes=(8,), scheme=p256)
    eng._jax = _FakeJax(backend)
    return eng._use_pallas()


@pytest.mark.parametrize("backend,env,want", [
    ("tpu", None, True),       # default ON on TPU
    ("plugin-x", None, False),  # a platform name nobody knows: OFF
    ("cpu", None, False),      # default OFF elsewhere
    ("tpu", "0", False),       # explicit opt-out wins
    ("tpu", "false", False),   # any set value other than "1" disables
    ("tpu", "", False),
    ("cpu", "1", True),        # explicit opt-in wins
    # a backend that cannot initialize is an error, not "no Pallas"
    (RuntimeError("no backend"), None, RuntimeError),
])
def test_pallas_default_on_tpu(backend, env, want, monkeypatch):
    if isinstance(want, type):
        with pytest.raises(want, match="no backend"):
            _engine_probe(backend, env, monkeypatch)
    else:
        assert _engine_probe(backend, env, monkeypatch) is want


def _stub_engine(monkeypatch, pallas, xla=None):
    """A P-256 engine forced onto the Pallas path with stubbed kernels
    (no comb verifier: every chunk rides the arbitrary-key kernel)."""
    from smartbft_tpu.crypto.provider import JaxVerifyEngine

    monkeypatch.setenv("SMARTBFT_PALLAS", "1")
    eng = JaxVerifyEngine(pad_sizes=(8, 32), scheme=p256)
    eng._comb = None
    eng._pallas_kernel = pallas
    if xla is not None:
        eng._kernel = xla
    return eng


def _items(n):
    d, pub = p256.keygen(b"guard")
    r, s = p256.sign(d, b"m")
    return [(b"m", r, s, pub)] * n


def test_kernel_compile_failure_raises_naming_kernel_and_shape(monkeypatch):
    """A kernel that fails on its first launch at a shape — the compile —
    raises KernelCompileError naming kernel and shape; it is never a
    fallback to the next kernel.  RESOURCE_EXHAUSTED from a compile (the
    scoped-VMEM refusal) is no exception, and the coalescer opens the
    breaker on it at once instead of retrying."""
    import asyncio

    from smartbft_tpu.crypto.provider import (
        AsyncBatchCoalescer, HostVerifyEngine, KernelCompileError,
        VerifyFaultPolicy,
    )

    calls = {"pallas": 0, "xla": 0}

    def refused(*arrays):
        calls["pallas"] += 1
        raise RuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in memory "
                           "space vmem. Scoped allocation with size 18.57M "
                           "and limit 16.00M")

    def xla(*arrays):  # pragma: no cover — must never run
        calls["xla"] += 1
        return np.ones(arrays[0].shape[0], np.uint32)

    eng = _stub_engine(monkeypatch, refused, xla)
    with pytest.raises(KernelCompileError,
                       match=r"pallas kernel .* first launch at 8 lanes"):
        eng.verify(_items(3))
    with pytest.raises(KernelCompileError, match="32 lanes"):
        eng.prewarm_shapes(_items(1)[0], sizes=(32,))
    assert calls == {"pallas": 2, "xla": 0}
    assert eng.stats.launches == 0

    async def through_coalescer():
        co = AsyncBatchCoalescer(
            eng, window=0.0, policy=VerifyFaultPolicy(launch_retries=2),
            fallback_engine=HostVerifyEngine(scheme=p256))
        out = await co.submit(_items(2))
        return out, co.fault_snapshot()

    out, snap = asyncio.run(through_coalescer())
    assert out == [True, True]  # served by the host fallback, and counted
    assert snap["open"] and snap["opens"] == 1 and snap["retries"] == 0
    assert snap["host_fallback_batches"] == 1 and snap["degraded"]
    assert calls["xla"] == 0


def test_kernel_runtime_blip_falls_back_to_host_and_is_counted(monkeypatch):
    """A kernel that compiled and then fails at run time is a sick device,
    not a compile failure: the engine re-raises it as it is (no switch to
    another kernel), the coalescer retries, and a wave that exhausts its
    retries is served by the host engine — counted, so no degraded run can
    pass for a device run."""
    import asyncio

    from smartbft_tpu.crypto.provider import (
        KERNELS, AsyncBatchCoalescer, HostVerifyEngine, KernelCompileError,
        VerifyFaultPolicy,
    )

    state = {"fail": 0, "calls": 0}

    def flaky(*arrays):
        state["calls"] += 1
        if state["fail"]:
            state["fail"] -= 1
            raise RuntimeError("UNAVAILABLE: device went away")
        return np.ones(arrays[0].shape[0], np.uint32)

    eng = _stub_engine(monkeypatch, flaky)
    assert eng.verify(_items(3)) == [True] * 3  # the shape has compiled
    state["fail"] = 1
    with pytest.raises(RuntimeError, match="UNAVAILABLE") as exc:
        eng.verify(_items(3))
    assert not isinstance(exc.value, KernelCompileError)
    assert eng.stats.launches_by_kernel == {
        **dict.fromkeys(KERNELS, 0), "pallas": 1}

    host = HostVerifyEngine(scheme=p256)

    async def through_coalescer():
        co = AsyncBatchCoalescer(
            eng, window=0.0, fallback_engine=host,
            policy=VerifyFaultPolicy(launch_retries=1, backoff_base=0.001,
                                     breaker_threshold=5))
        state["fail"] = 1   # one blip: the retry serves the wave on-device
        first = await co.submit(_items(2))
        mid = co.fault_snapshot()
        state["fail"] = 2   # outlasts the retries: the host serves it
        second = await co.submit(_items(2))
        return first, mid, second, co.fault_snapshot()

    first, mid, second, snap = asyncio.run(through_coalescer())
    assert first == second == [True, True]
    assert mid["retries"] == 1 and mid["host_fallback_batches"] == 0
    assert not mid["degraded"]
    assert snap["host_fallback_batches"] == 1 and snap["degraded"]
    assert not snap["open"]  # below the breaker threshold
    assert eng.stats.launches_by_kernel["pallas"] == 2
    assert host.stats.launches_by_kernel["host"] == 1
