"""Verify-plane fault tolerance: launch deadlines, retry/backoff, the
host-fallback circuit breaker, the result-length guard, the coalescer
double-flush race, and the Configuration/Consensus wiring seam.

The acceptance pin lives here: a hung launch can no longer wedge the
coalescer — the wave times out, retries, degrades to the host fallback,
and subsequent submissions still flush.
"""

import asyncio
import gc
import threading
import time
import weakref

import pytest

from smartbft_tpu.config import ConfigError, Configuration
from smartbft_tpu.crypto.provider import (
    AsyncBatchCoalescer,
    HostVerifyEngine,
    JaxVerifyEngine,
    Keyring,
    P256CryptoProvider,
    VerifyFaultPolicy,
    VerifyResultMismatch,
)
from smartbft_tpu.metrics import InMemoryProvider, TPUCryptoMetrics
from smartbft_tpu.testing.engine_faults import (
    CoalescedTrivialCrypto,
    FaultyEngine,
    always_valid_engine,
)
from smartbft_tpu.types import VerifyPlaneDown


def tight_policy(**kw) -> VerifyFaultPolicy:
    base = dict(launch_timeout=0.08, launch_retries=2, backoff_base=0.01,
                backoff_max=0.04, backoff_jitter=0.0, breaker_threshold=3,
                probe_interval=0.02, probe_backoff_max=0.05)
    base.update(kw)
    return VerifyFaultPolicy(**base)


async def wait_until(cond, timeout: float = 8.0, step: float = 0.01) -> None:
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "condition not met in time"
        await asyncio.sleep(step)


# -- acceptance: a hung launch cannot wedge the plane -------------------------

def test_hung_launch_times_out_retries_and_degrades_to_host():
    """ACCEPTANCE: inject a never-returning engine call; the wave must time
    out, retry, trip the breaker, and be served by the host fallback —
    and later submissions must still flush (the plane is not wedged)."""
    engine = FaultyEngine(always_valid_engine())
    fallback = always_valid_engine()
    co = AsyncBatchCoalescer(
        engine, window=0.001, policy=tight_policy(), fallback_engine=fallback
    )

    async def run():
        engine.hang()
        # first wave: every device attempt hits the deadline, the breaker
        # opens, the host fallback serves the submitters
        assert await asyncio.wait_for(co.submit([("a",)]), 10) == [True]
        assert co.breaker_open
        assert co.fault_stats.launch_timeouts >= 1
        assert co.fault_stats.breaker_opens == 1
        assert co.fault_stats.host_fallback_batches == 1
        # the plane is not wedged: new submissions flush (degraded mode
        # routes them straight to the fallback, no deadline wait)
        t0 = time.monotonic()
        assert await asyncio.wait_for(co.submit([("b",), ("c",)]), 10) \
            == [True, True]
        assert time.monotonic() - t0 < 2.0
        assert co.fault_stats.host_fallback_batches == 2
        # device recovery: heal, the canary probe closes the breaker, and
        # the next wave runs on the device engine again
        device_launches = engine.stats.launches
        engine.heal()
        await wait_until(lambda: not co.breaker_open)
        assert co.fault_stats.breaker_closes == 1
        assert co.fault_stats.probe_successes == 1
        assert await co.submit([("d",)]) == [True]
        assert engine.stats.launches > device_launches

    try:
        asyncio.run(run())
    finally:
        engine.heal()  # release any still-parked daemon worker


def test_hung_launch_without_fallback_fails_fast_then_recovers():
    """No fallback configured: exhausted waves surface VerifyPlaneDown (the
    ONLY terminal error of a policy-armed plane), later waves fail fast
    while the breaker is open instead of queueing behind the dead device,
    and the probe still restores the device after heal."""
    engine = FaultyEngine(always_valid_engine())
    co = AsyncBatchCoalescer(engine, window=0.001, policy=tight_policy())

    async def run():
        engine.hang()
        with pytest.raises(VerifyPlaneDown):
            await asyncio.wait_for(co.submit([("a",)]), 10)
        assert co.breaker_open
        t0 = time.monotonic()
        with pytest.raises(VerifyPlaneDown):
            await asyncio.wait_for(co.submit([("b",)]), 10)
        assert time.monotonic() - t0 < 1.0  # fast-fail, not deadline x retries
        engine.heal()
        await wait_until(lambda: not co.breaker_open)
        assert await co.submit([("c",)]) == [True]

    try:
        asyncio.run(run())
    finally:
        engine.heal()


# -- retry/backoff ------------------------------------------------------------

def test_transient_failures_are_retried_and_never_surface():
    engine = FaultyEngine(always_valid_engine())
    co = AsyncBatchCoalescer(
        engine, window=0.001, policy=tight_policy(launch_retries=3),
        fallback_engine=always_valid_engine(),
    )

    async def run():
        engine.fail_next(2)
        assert await asyncio.wait_for(co.submit([("a",)]), 10) == [True]

    asyncio.run(run())
    assert co.fault_stats.retries == 2
    assert co.fault_stats.launch_failures == 2
    assert not co.breaker_open and co.fault_stats.breaker_opens == 0
    assert co.fault_stats.host_fallback_batches == 0


def test_permanent_kernel_error_trips_breaker_immediately():
    """A compile-class error never succeeds on retry: one failure opens the
    breaker (no retry burn-down) and the wave degrades to host."""
    engine = FaultyEngine(always_valid_engine())
    co = AsyncBatchCoalescer(
        engine, window=0.001, policy=tight_policy(breaker_threshold=5),
        fallback_engine=always_valid_engine(),
    )

    async def run():
        engine.permanent_error()
        assert await asyncio.wait_for(co.submit([("a",)]), 10) == [True]
        assert co.breaker_open
        assert co.fault_stats.launch_failures == 1  # no pointless retries
        assert co.fault_stats.host_fallback_batches == 1
        engine.heal()
        await wait_until(lambda: not co.breaker_open)

    asyncio.run(run())


# -- breaker metrics ----------------------------------------------------------

def test_breaker_transitions_are_counted_in_tpu_metrics():
    mem = InMemoryProvider()
    engine = FaultyEngine(always_valid_engine())
    co = AsyncBatchCoalescer(
        engine, window=0.001, policy=tight_policy(),
        fallback_engine=always_valid_engine(), metrics=TPUCryptoMetrics(mem),
    )

    async def run():
        engine.permanent_error()
        await co.submit([("a",)])
        assert mem.gauges["consensus.tpu.verify_breaker_open"] == 1.0
        engine.heal()
        await wait_until(lambda: not co.breaker_open)

    asyncio.run(run())
    assert mem.counters["consensus.tpu.count_breaker_open"] == 1
    assert mem.counters["consensus.tpu.count_breaker_close"] == 1
    assert mem.counters["consensus.tpu.count_launch_failures"] == 1
    assert mem.counters["consensus.tpu.count_host_fallback_batches"] == 1
    assert mem.gauges["consensus.tpu.verify_breaker_open"] == 0.0


# -- result-length guard (satellite) ------------------------------------------

class ShortEngine:
    """Returns one result regardless of batch size — the silent mis-slice
    bug the guard closes."""

    def __init__(self):
        self.calls = 0

    def verify(self, items):
        self.calls += 1
        return [True]


def test_result_length_mismatch_raises_loudly_legacy():
    co = AsyncBatchCoalescer(ShortEngine(), window=0.001)

    async def run():
        with pytest.raises(RuntimeError, match="refusing to mis-slice"):
            await asyncio.wait_for(co.submit([("a",), ("b",), ("c",)]), 5)

    asyncio.run(run())


def test_result_length_mismatch_counts_as_launch_failure_with_policy():
    co = AsyncBatchCoalescer(
        ShortEngine(), window=0.001, policy=tight_policy(launch_retries=1),
        fallback_engine=always_valid_engine(),
    )

    async def run():
        # the mismatch fails the device attempts; the fallback serves
        assert await asyncio.wait_for(co.submit([("a",), ("b",)]), 5) \
            == [True, True]

    asyncio.run(run())
    assert co.fault_stats.launch_failures >= 1
    assert co.fault_stats.host_fallback_batches == 1


# -- double-flush window (satellite) ------------------------------------------

def test_double_flush_race_is_harmless_no_op():
    """When max_batch fills while a window flush is already watching the
    batch, two _flush_after tasks race: the first swaps the batch out, the
    second must find the batch no longer its own and be a no-op — every
    future resolves exactly once with its own verdicts, and the engine sees
    each item exactly once."""

    class RecordingEngine:
        def __init__(self):
            self.calls = []

        def verify(self, items):
            self.calls.append(list(items))
            return [it[0] == "ok" for it in items]

    engine = RecordingEngine()
    co = AsyncBatchCoalescer(engine, window=0.05, max_batch=2)

    async def run():
        f1 = asyncio.get_running_loop().create_task(co.submit([("ok", 1)]))
        await asyncio.sleep(0)  # the window flush is now watching it
        # this fill crosses max_batch and schedules a SECOND, immediate
        # flush while the first is still pending
        f2 = asyncio.get_running_loop().create_task(
            co.submit([("bad", 2), ("ok", 3)])
        )
        r1 = await asyncio.wait_for(f1, 5)
        r2 = await asyncio.wait_for(f2, 5)
        # outlast the window so the watch has ended too
        await asyncio.sleep(0.1)
        return r1, r2

    r1, r2 = asyncio.run(run())
    assert r1 == [True] and r2 == [False, True]
    seen = [it for call in engine.calls for it in call]
    assert sorted(seen) == [("bad", 2), ("ok", 1), ("ok", 3)]  # each item once


# -- configuration / wiring seams ---------------------------------------------

def test_config_verify_knobs_validate():
    Configuration(self_id=1).validate()
    with pytest.raises(ConfigError, match="verify_launch_timeout"):
        Configuration(self_id=1, verify_launch_timeout=0).validate()
    with pytest.raises(ConfigError, match="verify_launch_retries"):
        Configuration(self_id=1, verify_launch_retries=-1).validate()
    with pytest.raises(ConfigError, match="verify_breaker_threshold"):
        Configuration(self_id=1, verify_breaker_threshold=0).validate()
    pol = VerifyFaultPolicy.from_config(
        Configuration(self_id=1, verify_launch_timeout=7.0,
                      verify_launch_retries=5, verify_breaker_threshold=2,
                      verify_probe_interval=0.5)
    )
    assert (pol.launch_timeout, pol.launch_retries,
            pol.breaker_threshold, pol.probe_interval) == (7.0, 5, 2, 0.5)


def test_device_provider_arms_fault_stack_by_default():
    """A provider over a device-shaped engine must come out of __init__
    with deadlines + a host fallback of the same scheme — no embedder
    wiring required for the hung-device protection."""
    rings = Keyring.generate([1, 2, 3, 4], seed=b"vp")
    prov = P256CryptoProvider(rings[1], engine=JaxVerifyEngine(pad_sizes=(4,)))
    co = prov.coalescer
    assert co.policy is not None
    assert isinstance(co.fallback_engine, HostVerifyEngine)
    assert co.fallback_engine.scheme is prov.scheme
    # host engines keep the legacy contract until wired explicitly
    host_prov = P256CryptoProvider(rings[2], engine=HostVerifyEngine())
    assert host_prov.coalescer.policy is None


def test_configure_fault_policy_explicit_wins_defaults_rewire():
    rings = Keyring.generate([1, 2], seed=b"vp2")
    # an EXPLICIT constructor policy is never overridden by config wiring
    explicit = tight_policy()
    prov = P256CryptoProvider(
        rings[1], engine=HostVerifyEngine(), fault_policy=explicit
    )
    mem = InMemoryProvider()
    prov.configure_fault_policy(
        policy=VerifyFaultPolicy(), metrics=TPUCryptoMetrics(mem)
    )
    assert prov.coalescer.policy is explicit
    assert prov.coalescer.metrics is not None  # metrics slot was empty

    # but the DEFAULT-armed device policy must yield to Configuration-
    # derived wiring — and a later re-wire (reconfig) must also land
    dev = P256CryptoProvider(rings[2], engine=JaxVerifyEngine(pad_sizes=(4,)))
    assert dev.coalescer.policy is not None  # armed out of the box
    from_cfg = VerifyFaultPolicy.from_config(
        Configuration(self_id=2, verify_launch_timeout=7.5)
    )
    dev.configure_fault_policy(policy=from_cfg)
    assert dev.coalescer.policy is from_cfg
    rewired = VerifyFaultPolicy.from_config(
        Configuration(self_id=2, verify_launch_timeout=9.0)
    )
    dev.configure_fault_policy(policy=rewired)
    assert dev.coalescer.policy is rewired


def test_trivial_coalesced_crypto_round_trip():
    """The chaos harness's provider: trivial semantics, real coalescer."""
    co = AsyncBatchCoalescer(always_valid_engine(), window=0.001)
    crypto = CoalescedTrivialCrypto(3, co)
    from smartbft_tpu.messages import Proposal

    sig = crypto.sign_proposal(Proposal(payload=b"x"), b"aux")
    assert sig.signer == 3 and sig.msg == b"aux"

    async def run():
        return await crypto.verify_consenter_sigs_batch_async(
            [sig], Proposal(payload=b"x")
        )

    assert asyncio.run(run()) == [b"aux"]


# -- the resident launch thread (PR 38) ---------------------------------------

class ThreadNotingEngine(FaultyEngine):
    """An always-valid FaultyEngine that notes the thread of every call."""

    def __init__(self):
        super().__init__(always_valid_engine())
        self.threads: list = []

    def verify(self, items) -> list[bool]:
        self.threads.append(threading.current_thread())
        return super().verify(items)


def join_all(threads, timeout: float = 5.0) -> None:
    deadline = time.monotonic() + timeout
    for t in threads:
        t.join(max(deadline - time.monotonic(), 0.0))
    assert not [t for t in threads if t.is_alive()]


def test_live_waves_run_on_one_resident_launch_thread():
    """Twenty waves, one thread: no launch starts a thread of its own,
    and the thread ends once the loop that owned it has closed."""
    engine = ThreadNotingEngine()
    co = AsyncBatchCoalescer(engine, window=0.001,
                             policy=tight_policy(launch_timeout=5.0))

    async def run():
        for i in range(20):
            assert await co.submit([(f"w{i}",)]) == [True]

    asyncio.run(run())
    assert len(engine.threads) == 20 and len(set(engine.threads)) == 1
    assert engine.threads[0].name == "smartbft-verify-launch"
    assert co.fault_stats.launch_threads_started == 1
    assert co.fault_snapshot()["launch_threads_started"] == 1
    # idle and not yet gone, the thread holds its coalescer (and what
    # that reaches: a whole deployment) no longer
    gone = weakref.ref(co)
    del co
    gc.collect()
    assert gone() is None
    join_all(engine.threads)


def test_abandoned_launch_orphans_its_thread_and_the_next_starts_afresh():
    """PR 3's contract on the resident thread: a hang past the deadline
    fails the wave over as ever; the hung thread is an orphan that counts
    its late arrival and then ends, and the next wave runs on a NEW
    resident thread."""
    engine = ThreadNotingEngine()
    co = AsyncBatchCoalescer(
        engine, window=0.001,
        policy=tight_policy(launch_retries=0, breaker_threshold=5),
        fallback_engine=always_valid_engine(),
    )

    async def run():
        engine.hang()
        assert await asyncio.wait_for(co.submit([("a",)]), 10) == [True]
        assert co.fault_stats.launch_timeouts == 1
        assert co.fault_stats.host_fallback_batches == 1
        assert not co.breaker_open
        (orphan,) = engine.threads
        assert orphan.is_alive()
        engine.heal()
        await wait_until(lambda: co.fault_stats.abandoned_late_arrivals == 1)
        await wait_until(lambda: not orphan.is_alive())
        assert await co.submit([("b",)]) == [True]
        assert engine.threads[-1] is not orphan
        assert co.fault_stats.launch_threads_started == 2
        assert co.fault_stats.abandoned_late_arrivals == 1

    try:
        asyncio.run(run())
    finally:
        engine.heal()
    join_all(engine.threads)


def test_no_launch_thread_outlives_its_loop_and_its_orphans_call():
    """The loop closes while an abandoned launch is still parked in the
    device: once that call returns, nothing of the plane is left
    running."""
    engine = ThreadNotingEngine()
    co = AsyncBatchCoalescer(
        engine, window=0.001,
        policy=tight_policy(launch_retries=0, breaker_threshold=5),
        fallback_engine=always_valid_engine(),
    )

    async def run():
        assert await co.submit([("a",)]) == [True]
        engine.hang()
        assert await asyncio.wait_for(co.submit([("b",)]), 10) == [True]

    try:
        asyncio.run(run())
        assert engine.threads[-1].is_alive()  # parked past its loop
    finally:
        engine.heal()
    assert len(set(engine.threads)) == 1
    join_all(engine.threads)


def test_handoff_waits_carry_the_launch_only_while_the_recorder_is_on():
    from smartbft_tpu.obs.recorder import TraceRecorder

    co = AsyncBatchCoalescer(always_valid_engine(), window=0.001,
                             policy=tight_policy(launch_timeout=5.0))

    async def waves(tag):
        for i in range(3):
            assert await co.submit([(f"{tag}{i}",)]) == [True]

    asyncio.run(waves("off"))
    assert co.recorder.recorded == 0
    rec = TraceRecorder(node="verify")
    co.attach_recorder(rec)
    asyncio.run(waves("on"))  # a new loop: a new resident thread
    by_kind = {}
    for e in rec.events():
        by_kind.setdefault(e.kind, []).append(e)
    launches = [e.launch for e in by_kind["verify.launch"]]
    assert launches == [4, 5, 6]
    for kind in ("verify.handin", "verify.handback"):
        assert [e.launch for e in by_kind[kind]] == launches
        assert all(e.dur >= 0.0 for e in by_kind[kind])
    assert [e.extra for e in by_kind["verify.handin"]] == \
        [{"threads_started": 2}] * 3


# -- tier-1-speed bench row pin (satellite: CI/tooling) -----------------------

def test_throughput_row_carries_breaker_metrics(tmp_path):
    """benchmarks/throughput.py must export the breaker block in every JSON
    row — degraded runs are never silently reported as device runs."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parent.parent / "benchmarks" / "throughput.py"
    spec = importlib.util.spec_from_file_location("bench_throughput", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    row = asyncio.run(
        mod.run_cluster("host", 4, 4, 2, (8,), scheme_name="p256")
    )
    breaker = row["breaker"]
    for key in ("open", "degraded", "opens", "closes", "launch_failures",
                "launch_timeouts", "retries", "host_fallback_batches",
                "policy_configured"):
        assert key in breaker, breaker
    assert breaker["open"] is False and breaker["opens"] == 0
    # the Consensus facade wired the Configuration policy into the plane
    assert breaker["policy_configured"] is True
