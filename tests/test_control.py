"""Self-driving control plane (ISSUE 20): the pure policy core, the
knob derivation, the transition arbiter (the autoscaler/controller
double-transition pin), the reconfig mirror round-trip for the new
control knobs, the pooled control client, session retry-after, the
delta-quantile recency window, the selfdrive bench-row family + its
baseline oscillation guard, and the full remediation_storm round."""

import asyncio
import json
import socket
import threading

import pytest

from smartbft_tpu.config import Configuration
from smartbft_tpu.control import (
    ControlPolicy,
    TransitionArbiter,
    count_reversals,
    derive_knobs,
)

# ---------------------------------------------------------------------------
# fixtures


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _policy(clock, **over):
    kw = dict(
        interval=1.0, cooldown=10.0, hysteresis=30.0, idle_hold=5.0,
        budget_actions=4, budget_window=100.0, min_shards=1, max_shards=4,
        high_occupancy=0.85, low_occupancy=0.25, clock=clock,
    )
    kw.update(over)
    return ControlPolicy(**kw)


HEALTHY = {"status": "healthy", "reasons": []}
LATENCY_BURN = {"status": "degraded",
                "reasons": [{"slo": "latency.commit_p99_ms"}]}
DEGRADED_VC = {"status": "degraded",
               "reasons": [{"slo": "viewchange.detection_seconds"}]}


def _occ(fill, waiters=0, shed=0, capacity=4096):
    return {"fill": fill, "total_waiters": waiters, "shed_admission": shed,
            "shed_timeout": 0, "total_capacity": capacity}


def _signals(fill=0.5, **extra):
    sig = {"occupancy": _occ(fill), "rtt_s": None, "commit_gap_s": None,
           "drain_rate": None}
    sig.update(extra)
    return sig


# ---------------------------------------------------------------------------
# candidate detection


def test_latency_burn_scales_out_before_knee():
    clock = FakeClock()
    pol = _policy(clock)
    # fill far below the knee: the burn alone must trigger the action
    rem = pol.decide(LATENCY_BURN, _signals(fill=0.2), num_shards=2)
    assert rem.status == "act"
    assert rem.action == "scale_out"
    assert rem.cause == "latency.commit_p99_ms"
    assert rem.target_shards == 3


def test_saturation_scales_out_on_fill():
    clock = FakeClock()
    pol = _policy(clock)
    rem = pol.decide(HEALTHY, _signals(fill=0.95), num_shards=2)
    assert rem.status == "act"
    assert rem.action == "scale_out"
    assert rem.cause == "pool.fill"


def test_scale_out_respects_max_shards():
    clock = FakeClock()
    pol = _policy(clock, max_shards=2)
    rem = pol.decide(LATENCY_BURN, _signals(fill=0.2), num_shards=2)
    assert rem.status == "idle"


def test_idle_must_be_sustained_before_scale_in():
    clock = FakeClock()
    pol = _policy(clock, idle_hold=5.0)
    rem = pol.decide(HEALTHY, _signals(fill=0.05), num_shards=3)
    assert rem.status == "idle"  # hold timer just started
    clock.advance(3.0)
    # a non-idle tick resets the hold
    pol.decide(HEALTHY, _signals(fill=0.5), num_shards=3)
    clock.advance(3.0)
    rem = pol.decide(HEALTHY, _signals(fill=0.05), num_shards=3)
    assert rem.status == "idle"  # timer restarted, 5 s not yet sustained
    clock.advance(6.0)
    rem = pol.decide(HEALTHY, _signals(fill=0.05), num_shards=3)
    assert rem.status == "act"
    assert rem.action == "scale_in"
    assert rem.target_shards == 2


def test_scale_in_never_below_min_shards():
    clock = FakeClock()
    pol = _policy(clock, min_shards=2, idle_hold=1.0)
    pol.decide(HEALTHY, _signals(fill=0.05), num_shards=2)
    clock.advance(5.0)
    rem = pol.decide(HEALTHY, _signals(fill=0.05), num_shards=2)
    assert rem.status == "idle"


def test_retune_gated_on_unhealthy_verdict():
    clock = FakeClock()
    base = Configuration()
    sig = _signals(fill=0.5, rtt_s=0.004)
    pol = _policy(clock)
    rem = pol.decide(HEALTHY, sig, num_shards=2,
                     current_config=base, base_config=base)
    assert rem.status == "idle"  # healthy steady state: zero actions
    rem = pol.decide(DEGRADED_VC, sig, num_shards=2,
                     current_config=base, base_config=base)
    assert rem.status == "act"
    assert rem.action == "retune"
    assert rem.cause == "viewchange.detection_seconds"
    assert "request_forward_timeout" in rem.knobs


# ---------------------------------------------------------------------------
# veto chain


def test_transition_veto_wins_over_breaker():
    clock = FakeClock()
    pol = _policy(clock)
    rem = pol.decide(LATENCY_BURN, _signals(), num_shards=2,
                     in_transition=True, breaker_open=True)
    assert rem.status == "veto"
    assert pol.counters["veto_transition"] == 1
    assert pol.counters["veto_breaker"] == 0


def test_breaker_veto_suppresses_action():
    clock = FakeClock()
    pol = _policy(clock)
    rem = pol.decide(LATENCY_BURN, _signals(), num_shards=2,
                     breaker_open=True)
    assert rem.status == "veto"
    assert rem.action == "scale_out"  # the veto names what it suppressed
    assert pol.counters["veto_breaker"] == 1
    assert pol.counters["decisions"] == 0


def test_cooldown_blocks_repeat_and_failure_rearms_from_completion():
    clock = FakeClock()
    pol = _policy(clock, cooldown=10.0)
    rem = pol.decide(LATENCY_BURN, _signals(), num_shards=2)
    assert rem.status == "act"
    clock.advance(2.0)
    again = pol.decide(LATENCY_BURN, _signals(), num_shards=2)
    assert again.status == "veto"
    assert pol.counters["veto_cooldown"] == 1
    # the action ran for 8 s and then FAILED: cooldown re-arms from the
    # completion, not the decision — t=10 would otherwise already be free
    clock.advance(6.0)
    pol.note_result(rem, ok=False)
    clock.advance(4.0)  # t=12: past the original t=10 expiry
    still = pol.decide(LATENCY_BURN, _signals(), num_shards=2)
    assert still.status == "veto"
    clock.advance(7.0)  # t=19 >= 8 + 10
    free = pol.decide(LATENCY_BURN, _signals(), num_shards=3)
    assert free.status == "act"
    assert pol.counters["failed"] == 1


def test_global_budget_caps_actions_across_kinds():
    clock = FakeClock()
    base = Configuration()
    pol = _policy(clock, budget_actions=2, budget_window=100.0,
                  cooldown=1.0, hysteresis=0.0)
    assert pol.decide(LATENCY_BURN, _signals(), num_shards=2).status == "act"
    clock.advance(2.0)
    rem = pol.decide(DEGRADED_VC, _signals(rtt_s=0.004), num_shards=3,
                     current_config=base, base_config=base)
    assert rem.status == "act" and rem.action == "retune"
    clock.advance(2.0)
    third = pol.decide(LATENCY_BURN, _signals(), num_shards=3)
    assert third.status == "veto"
    assert pol.counters["veto_budget"] == 1
    # the window ages out
    clock.advance(200.0)
    assert pol.decide(LATENCY_BURN, _signals(), num_shards=3).status == "act"


def test_reversal_hysteresis_vetoes_flip_flop():
    clock = FakeClock()
    pol = _policy(clock, cooldown=1.0, hysteresis=30.0, idle_hold=1.0)
    assert pol.decide(LATENCY_BURN, _signals(), num_shards=2).status == "act"
    # idle sustains, cooldown expired — but scaling back in 10 s after
    # scaling out is exactly the oscillation the hysteresis exists for
    clock.advance(5.0)
    pol.decide(HEALTHY, _signals(fill=0.05), num_shards=3)
    clock.advance(5.0)
    rem = pol.decide(HEALTHY, _signals(fill=0.05), num_shards=3)
    assert rem.status == "veto"
    assert rem.action == "scale_in"
    assert pol.counters["veto_reversal"] == 1
    assert pol.reversals() == 0  # vetoed — never entered the acted log
    # past the hysteresis window the scale-in is legitimate (the idle
    # hold kept accruing through the veto)
    clock.advance(31.0)
    rem = pol.decide(HEALTHY, _signals(fill=0.05), num_shards=3)
    assert rem.status == "act" and rem.action == "scale_in"
    assert pol.reversals() == 0


def test_knob_reversal_filter_drops_a_b_a():
    clock = FakeClock()
    base = Configuration(request_forward_timeout=2.0)
    pol = _policy(clock, cooldown=1.0, hysteresis=30.0)
    sig = _signals(rtt_s=0.004)  # derives fwd = 8 * 0.004 = 0.032
    rem = pol.decide(DEGRADED_VC, sig, num_shards=2,
                     current_config=base, base_config=base)
    assert rem.status == "act"
    assert rem.knobs["request_forward_timeout"] == 0.032
    cur = Configuration(request_forward_timeout=0.032)
    # RTT jitter suggests flipping straight back to the boot value:
    # inside the hysteresis window that knob is filtered, leaving no
    # candidate at all
    clock.advance(5.0)
    sig2 = _signals(rtt_s=0.25)  # derives fwd = 2.0 (the base ceiling)
    rem2 = pol.decide(DEGRADED_VC, sig2, num_shards=2,
                      current_config=cur, base_config=base)
    assert rem2.status == "idle"


def test_count_reversals():
    assert count_reversals([], 10.0) == 0
    log = [(0.0, "scale_out", "x"), (5.0, "scale_in", "y")]
    assert count_reversals(log, 10.0) == 1
    assert count_reversals(log, 2.0) == 0  # outside the window
    assert count_reversals([(0.0, "retune", "z"), (1.0, "retune", "z")],
                           10.0) == 0


# ---------------------------------------------------------------------------
# derive_knobs


def test_derive_knobs_clamps_and_quantizes():
    base = Configuration(request_forward_timeout=2.0,
                         request_batch_max_interval=0.05,
                         transport_outbox_cap=4096)
    cur = base
    # floor: 8 * 0.0001 = 0.8 ms < the 10 ms forward floor
    knobs = derive_knobs(base, cur, rtt_s=0.0001)
    assert knobs["request_forward_timeout"] == 0.010
    # ceiling: 8 * 10 s clamps to the BASE config value — which equals
    # current, so the deadband drops it entirely
    assert derive_knobs(base, cur, rtt_s=10.0) == {}
    # hold clamps to request_batch_max_interval
    knobs = derive_knobs(base, cur, commit_gap_s=3.0)
    assert knobs["verify_flush_hold"] == 0.05
    # outbox floor and ceiling
    assert derive_knobs(base, cur, drain_rate=10.0)[
        "transport_outbox_cap"] == 256
    assert derive_knobs(base, cur, drain_rate=1e9) == {}  # ceiling == cur
    # ms quantization: 8 * 0.0123456 = 0.0987648 -> 0.099
    assert derive_knobs(base, cur, rtt_s=0.0123456)[
        "request_forward_timeout"] == 0.099


def test_derive_knobs_deadband_filters_jitter():
    base = Configuration(request_forward_timeout=2.0,
                         control_knob_deadband=0.25)
    cur = Configuration(request_forward_timeout=0.1)
    # derived 0.112 is a 12% move from current 0.1 — under the deadband
    assert derive_knobs(base, cur, rtt_s=0.014) == {}
    # a 60% move clears it
    assert derive_knobs(base, cur, rtt_s=0.020)[
        "request_forward_timeout"] == 0.16


# ---------------------------------------------------------------------------
# TransitionArbiter: the autoscaler/controller double-transition pin


def test_arbiter_mutual_exclusion_and_nonreentrancy():
    arb = TransitionArbiter()
    assert arb.try_acquire("controller")
    assert not arb.try_acquire("autoscaler")
    assert not arb.try_acquire("controller")  # strictly non-reentrant
    assert arb.contended == 2
    arb.release("autoscaler")  # not the holder: no-op
    assert arb.holder == "controller"
    arb.release("controller")
    assert arb.holder is None
    assert arb.try_acquire("autoscaler")


class _StubShardSet:
    """Saturated shard set whose reshard blocks until released — the
    window in which the OLD check-then-act autoscaler could double-fire."""

    def __init__(self):
        self.num_shards = 2
        self.reshard_in_progress = False
        self.resharding = asyncio.Event()
        self.proceed = asyncio.Event()
        self.reshard_calls = 0

    def occupancy(self):
        return _occ(0.95)

    async def reshard(self, target, make_shard=None):
        self.reshard_calls += 1
        self.reshard_in_progress = True
        self.resharding.set()
        try:
            await self.proceed.wait()
            self.num_shards = target
            return {"to_shards": target}
        finally:
            self.reshard_in_progress = False


def test_autoscaler_and_controller_cannot_double_transition():
    from smartbft_tpu.shard.autoscale import OccupancyAutoscaler, run_autoscaler

    async def scenario():
        sset = _StubShardSet()
        arb = TransitionArbiter()
        # the controller wins the arbiter and starts a (slow) reshard
        assert arb.try_acquire("controller")
        ctl_reshard = asyncio.create_task(sset.reshard(3))
        await sset.resharding.wait()
        # the legacy loop ticks furiously against a SATURATED snapshot —
        # without the arbiter it would fire its own reshard here
        auto = OccupancyAutoscaler(high=0.85, low=0.15, cooldown=0.0,
                                   min_shards=1, max_shards=8)
        stop = asyncio.Event()
        loop = asyncio.create_task(run_autoscaler(
            sset, auto, make_shard=lambda sid, epoch: None,
            interval=0.001, stop=stop, arbiter=arb))
        await asyncio.sleep(0.05)
        assert sset.reshard_calls == 1  # only the controller's
        assert arb.contended > 0
        # controller finishes and releases; the loop may now transition
        sset.proceed.set()
        await ctl_reshard
        arb.release("controller")
        await asyncio.sleep(0.05)
        stop.set()
        executed = await loop
        assert executed >= 1
        assert sset.reshard_calls == 1 + executed
        return True

    assert asyncio.run(scenario())


# ---------------------------------------------------------------------------
# reconfig mirror round-trip for the control knobs


def test_config_mirror_roundtrips_control_knobs():
    from smartbft_tpu.testing.reconfig import mirror_config, unmirror_config

    cfg = Configuration(
        control_interval=0.5, control_cooldown=20.0,
        control_hysteresis=12.0, control_idle_hold=5.0,
        control_budget_actions=6, control_budget_window=60.0,
        control_knob_deadband=0.1, control_forward_rtt_multiplier=4.0,
        control_hold_commit_multiplier=0.25,
        control_outbox_drain_window=1.5,
    )
    back = unmirror_config(mirror_config(cfg))
    for f in ("control_interval", "control_cooldown", "control_hysteresis",
              "control_idle_hold", "control_budget_actions",
              "control_budget_window", "control_knob_deadband",
              "control_forward_rtt_multiplier",
              "control_hold_commit_multiplier",
              "control_outbox_drain_window"):
        assert getattr(back, f) == getattr(cfg, f), f


# ---------------------------------------------------------------------------
# session retry-after + delta-quantile (the controller's signal sources)


def test_session_retry_after_ms():
    from smartbft_tpu.core.readplane import session_retry_after_ms

    assert session_retry_after_ms(10, 10, 0.05) == 0  # already caught up
    assert session_retry_after_ms(12, 10, 0.05) == 0
    # 4 decisions behind at 50 ms/decision = 200 ms
    assert session_retry_after_ms(6, 10, 0.05) == 200
    # idle replica (no gap EWMA): the floor applies, not zero
    assert session_retry_after_ms(6, 10, None) == 10
    assert session_retry_after_ms(6, 10, None, floor_ms=25) == 25
    # a huge gap never tells the client to go away for minutes
    assert session_retry_after_ms(0, 10**6, 1.0) == 5000


def test_delta_quantile_sees_only_the_recency_window():
    from smartbft_tpu.metrics import LogScaleHistogram

    h = LogScaleHistogram()
    for _ in range(100):
        h.observe(0.010)  # a bad spell: 10 ms samples
    baseline = list(h.buckets)
    assert h.quantile(0.99) == pytest.approx(0.010, rel=0.25)
    for _ in range(50):
        h.observe(0.0001)  # recovery: 100 us
    # lifetime p99 is still pinned by the spell; the delta is not
    assert h.quantile(0.99) == pytest.approx(0.010, rel=0.25)
    assert h.delta_quantile(0.99, baseline) == pytest.approx(1e-4, rel=0.3)
    assert h.delta_quantile(0.99, list(h.buckets)) == 0.0  # empty window


# ---------------------------------------------------------------------------
# pooled ControlClient (ISSUE 20 satellite: connect once, reuse forever)


class _LineServer(threading.Thread):
    """One-connection-at-a-time line-JSON echo server; counts accepts."""

    def __init__(self):
        super().__init__(daemon=True)
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(4)
        self.port = self.sock.getsockname()[1]
        self.accepts = 0
        self.drop_next = threading.Event()
        self.dropped = threading.Event()
        self.stop = threading.Event()

    def run(self):
        self.sock.settimeout(0.2)
        while not self.stop.is_set():
            try:
                conn, _ = self.sock.accept()
            except socket.timeout:
                continue
            self.accepts += 1
            buf = b""
            with conn:
                conn.settimeout(0.5)
                while not self.stop.is_set():
                    if self.drop_next.is_set():
                        self.drop_next.clear()
                        self.dropped.set()
                        break  # kill the connection mid-session
                    try:
                        chunk = conn.recv(65536)
                    except socket.timeout:
                        continue
                    except OSError:
                        break
                    if not chunk:
                        break
                    buf += chunk
                    while b"\n" in buf:
                        line, buf = buf.split(b"\n", 1)
                        req = json.loads(line)
                        conn.sendall(
                            (json.dumps({"ok": True, "echo": req}) + "\n")
                            .encode())


def test_control_client_pools_and_reconnects():
    from smartbft_tpu.net.cluster import ControlClient

    srv = _LineServer()
    srv.start()
    try:
        client = ControlClient(f"tcp://127.0.0.1:{srv.port}", timeout=5.0)
        for i in range(5):
            assert client.call(cmd="ping", i=i)["ok"] is True
        assert client.stats["connects"] == 1
        assert client.stats["calls"] == 5
        assert client.stats["reuses"] == 4
        assert client.stats["reconnects"] == 0
        assert srv.accepts == 1
        # the server tears the cached connection down (replica restart):
        # exactly one transparent reconnect, the call still succeeds
        srv.drop_next.set()
        assert srv.dropped.wait(2.0)  # connection actually torn down
        assert client.call(cmd="ping", i=99)["ok"] is True
        assert client.stats["reconnects"] == 1
        assert client.stats["connects"] == 2
        client.close()
    finally:
        srv.stop.set()
        srv.join(timeout=2.0)


# ---------------------------------------------------------------------------
# the full reflex arc under injected faults


def test_remediation_storm_round():
    """Spike -> scale_out on the latency burn BEFORE the knee; idle tail
    -> scale_in; engine hang -> vetoed-silent behind the breaker; muted
    leader -> retune only; zero actions outside fault windows, zero
    flip-flops, invariants green."""
    from smartbft_tpu.testing.chaos import remediation_storm_round

    stats = asyncio.run(remediation_storm_round(seed=1, verbose=False))
    assert stats["actions"] >= 3
    assert stats["actions_per_fault"] <= 2.0
    assert stats["reversals"] == 0
    assert stats["scale_out"] >= 1
    assert stats["scale_in"] >= 1
    assert stats["retune"] >= 1
    assert stats["vetoes"].get("veto_breaker", 0) >= 1
    assert stats["final_status"] == "healthy"
    assert stats["ctl_spans"] == stats["actions"]
