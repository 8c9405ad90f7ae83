"""The flight recorder's switch, busy/wait spans, account and readers
(ISSUE 25).

* the switch follows ``jax.profiler.start_trace`` / ``stop_trace`` and
  flips every live recorder; a recorder forced on by hand is left alone;
* with the switch off a site reads no clock and allocates nothing;
* busy spans: self time on a nested and on a two-thread span list, a span
  that contains an await is refused, ``close_for_await`` ends the spans an
  await would otherwise cross, ``busy_steps`` keeps a task's semantics;
* the account: exact segment values on injected ``perf_counter`` stamps,
  segments summing to ``batch.propose`` -> deliver per decision, the tail
  after the interval's end taken back out of the sums;
* a short wall-driven n=4 run with the profiler on: nothing dropped,
  nothing refused, and the ``.xplane.pb`` holds ``tpubft.*`` events on the
  loop thread's line and on lines of the threads that ran launches and
  fsync waves;
* every new ``chipbench/layer_metrics`` reader on a hand-built account,
  and returning None, not raising, on an empty one;
* the loop hook (ISSUE 37): there only while a session runs, and the
  original ``Handle._run`` back after it, also when the off edge raises;
  handles named by owner (``loop.program`` / ``loop.embedder`` /
  ``loop.callback``, a ``busy=`` task keeping its kind); busy intervals,
  lock wait and CPU outside handles on synthetic clocks;
  ``assemble_timeline`` on synthetic lists; the ``launch`` block; the
  eleven readers on the account of the profiled run.
"""

import asyncio
import glob
import importlib.util
import os
import threading
import time
import types

import pytest

from smartbft_tpu import obs
from smartbft_tpu.obs import (
    SpanEvent,
    TraceRecorder,
    assemble_account,
    decision_rows,
)
from smartbft_tpu.obs import recorder as recmod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def fold_busy(events) -> dict:
    """thread -> kind -> [calls, self seconds, seconds] over the busy
    spans among ``events``: the account's sums recomputed from a list."""
    out: dict = {}
    for e in events:
        if e.self_s < 0.0:
            continue
        acc = out.setdefault(e.thread, {}).setdefault(e.kind, [0, 0.0, 0.0])
        acc[0] += 1
        acc[1] += e.self_s
        acc[2] += e.dur
    return out


def _raises():
    raise AssertionError("a site read the clock of a recorder that is off")


# -- the switch ----------------------------------------------------------------


def _profile(tmp_path, body):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()


def test_switch_follows_the_profiler_and_flips_every_live_recorder(tmp_path):
    a = TraceRecorder(node="a", enabled=False)
    b = TraceRecorder(node="b", enabled=False, clock=_raises)
    forced = TraceRecorder(node="f", clock=Clock(5.0))
    obs.poll_profiler()
    assert not a.enabled and not b.enabled and forced.enabled

    def while_on():
        obs.poll_profiler()
        assert a.enabled and b.enabled
        # on by the profiler: perf_counter stamps, whatever was injected
        before = time.perf_counter()
        ev = b.record("req.submit", key="c:1")
        assert before <= ev.t <= time.perf_counter()
        # a recorder born while the session runs comes up switched on
        assert TraceRecorder(node="late", enabled=False).enabled
        # forced on by hand: its own clock, no annotations, left alone
        assert forced.record("x").t == 5.0 and forced._annotate is None
        span = a.begin("view.ingest", view=1, seq=2)
        a.end(span)
        obs.poll_profiler()

    _profile(tmp_path, while_on)
    obs.poll_profiler()
    assert not a.enabled and not b.enabled and forced.enabled
    acc = obs.last_summary()
    assert acc["interval"]["wall_s"] > 0 and acc["loop"]["cpu_s"] >= 0
    assert acc["busy"][threading.current_thread().name]["view.ingest"][
        "calls"] == 1
    assert acc["dropped"] == 0 and acc["refused"] == {}


def test_off_costs_no_clock_read_and_no_allocation(tmp_path):
    """Every component of a cluster holds a recorder whose clock raises,
    switched off: requests commit, so no site touched it."""
    from smartbft_tpu.testing.app import wait_for
    from smartbft_tpu.testing.sharded import ShardedCluster

    async def run():
        cluster = ShardedCluster(str(tmp_path), shards=1, n=4, depth=2,
                                 crypto="trivial", window=0.002)
        recs = list(cluster._recorders.values())
        assert len(recs) >= 6 and not any(r.enabled for r in recs)
        for r in recs:
            r._clock = _raises
        await cluster.start()
        try:
            for j in range(8):
                await cluster.submit(cluster.client_for_shard(0, j % 3),
                                     f"r{j}")
            await wait_for(lambda: cluster.committed_requests() >= 8,
                           cluster.scheduler, 120.0)
        finally:
            await cluster.stop()
        assert all(r.recorded == 0 and not r.events() for r in recs)
        assert cluster.trace_recorders() == []

    asyncio.run(run())


# -- busy spans ----------------------------------------------------------------


def test_self_time_of_nested_spans():
    clock = Clock()
    rec = TraceRecorder(clock=clock, node="n1")
    outer = rec.begin("view.run")
    clock.t += 1.0
    inner = rec.begin("vote.sign", seq=7)
    clock.t += 0.25
    leaf = rec.begin("codec")
    clock.t += 0.5
    rec.end(leaf)
    rec.end(inner)
    clock.t += 2.0
    ev = rec.end(outer)
    assert ev.dur == pytest.approx(3.75) and ev.self_s == pytest.approx(3.0)
    me = threading.current_thread().name
    got = fold_busy(rec.events())[me]
    assert got["codec"] == [1, pytest.approx(0.5), pytest.approx(0.5)]
    assert got["vote.sign"] == [1, pytest.approx(0.25), pytest.approx(0.75)]
    assert got["view.run"] == [1, pytest.approx(3.0), pytest.approx(3.75)]
    # self times partition the outermost span: nothing counted twice
    assert sum(v[1] for v in got.values()) == pytest.approx(3.75)


def test_self_time_is_kept_per_thread():
    clock = Clock()
    rec = TraceRecorder(clock=clock, node="n1")
    opened, release = threading.Event(), threading.Event()

    def fsync():
        span = rec.begin("wal.fsync")
        opened.set()
        release.wait(5.0)
        rec.end(span)

    outer = rec.begin("view.run")
    th = threading.Thread(target=fsync, name="wave-thread")
    th.start()
    assert opened.wait(5.0)
    clock.t += 2.0
    release.set()
    th.join()
    rec.end(outer)
    got = fold_busy(rec.events())
    # the other thread's span is no child of this thread's: both keep
    # their whole duration as self time
    assert got["wave-thread"]["wal.fsync"][1] == pytest.approx(2.0)
    assert got[threading.current_thread().name]["view.run"][1] == \
        pytest.approx(2.0)


def test_busy_span_that_contains_an_await_is_refused():
    rec = TraceRecorder(clock=Clock(), node="n1")

    async def suspends():
        span = rec.begin("view.ingest")
        await asyncio.sleep(0.01)  # NOT synchronous work: a wait
        return rec.end(span)

    async def neighbour():
        await asyncio.sleep(0)
        span = rec.begin("deliver")
        await asyncio.sleep(0.05)
        return rec.end(span)

    async def run():
        return await asyncio.gather(suspends(), neighbour())

    before = dict(recmod._refused)
    first, second = asyncio.run(run())
    # the first to end is not the innermost open on the thread: refused
    # (dropped and counted, never raised); no busy time is counted for it
    assert first is None
    assert recmod._refused.get("view.ingest", 0) == \
        before.get("view.ingest", 0) + 1
    assert not [e for e in rec.events() if e.kind == "view.ingest"]
    assert recmod._state().stack == [] or second is None


def test_close_for_await_ends_open_spans_before_a_suspension():
    clock = Clock()
    rec = TraceRecorder(clock=clock, node="set")
    span = rec.begin("front.submit")
    clock.t += 0.5
    obs.close_for_await()  # what Pool.submit does before it parks
    clock.t += 30.0  # the wait: no busy time
    assert rec.end(span) is None
    (ev,) = rec.events()
    assert ev.kind == "front.submit" and ev.dur == pytest.approx(0.5)


def test_wait_span_is_one_event_at_its_end_and_never_busy():
    clock = Clock()
    rec = TraceRecorder(clock=clock, node="verify")
    started = rec.now()
    clock.t += 0.007
    ev = rec.wait("verify.wait", started, extra={"items": 3})
    assert ev.dur == pytest.approx(0.007) and ev.self_s < 0
    assert fold_busy(rec.events()) == {}
    # begun while the recorder was off: nothing to record
    assert rec.wait("verify.wait", None) is None


def test_busy_steps_keeps_a_tasks_result_errors_and_cancellation(tmp_path):
    from smartbft_tpu.utils.tasks import create_logged_task

    rec = TraceRecorder(node="n1", enabled=False)

    async def work(n):
        total = 0
        for i in range(n):
            await asyncio.sleep(0)
            total += i
        return total

    async def boom():
        await asyncio.sleep(0)
        raise KeyError("kaput")

    async def forever(cleaned):
        try:
            await asyncio.Event().wait()
        finally:
            cleaned.append(True)

    class Quiet:
        def errorf(self, *a):
            pass

    async def run():
        cleaned = []
        t1 = create_logged_task(work(4), name="w", busy=(rec, "view.run"))
        t2 = create_logged_task(boom(), name="b", logger=Quiet(),
                                busy=(rec, "view.run"))
        t3 = create_logged_task(forever(cleaned), name="f",
                                busy=(rec, "view.run"))
        assert await t1 == 6
        with pytest.raises(KeyError):
            await t2
        await asyncio.sleep(0)
        t3.cancel()
        with pytest.raises(asyncio.CancelledError):
            await t3
        assert cleaned == [True]

    asyncio.run(run())  # recorder off: pass-through
    assert rec.recorded == 0

    def while_on():
        obs.poll_profiler()
        asyncio.run(run())
        obs.poll_profiler()

    _profile(tmp_path, while_on)
    obs.poll_profiler()
    steps = [e for e in rec.events() if e.kind == "view.run"]
    # every step of every task is one busy span: 5 + 2 + 2
    assert len(steps) == 9 and all(e.self_s >= 0 for e in steps)


def test_launch_span_carries_the_threads_launch_and_cpu(tmp_path):
    out = {}

    def launch():
        recmod.set_thread_launch(41)
        with recmod.launch_span("verify.pack"):
            sum(range(20000))
        with recmod.launch_span("verify.device"):
            time.sleep(0.01)
        out["done"] = True

    launch()  # off: the shared no-op, nothing recorded
    assert recmod.launch_span("verify.pack") is recmod.launch_span("x")

    def while_on():
        obs.poll_profiler()
        th = threading.Thread(target=launch, name="smartbft-verify-launch")
        th.start()
        th.join()
        obs.poll_profiler()

    _profile(tmp_path, while_on)
    obs.poll_profiler()
    busy = obs.last_summary()["busy"]["smartbft-verify-launch"]
    assert busy["verify.pack"]["calls"] == busy["verify.device"]["calls"] == 1
    # the device span slept: wall time without CPU is how long it stood
    assert busy["verify.device"]["dur_s"] >= 0.009
    assert busy["verify.device"]["cpu_s"] < busy["verify.device"]["dur_s"]
    events = [e for e in obs.PROCESS.events() if e.kind.startswith("verify.")]
    assert [e.launch for e in events] == [41, 41]


def test_garbage_collections_are_busy_time_of_their_own(tmp_path):
    """While the profiler is on a collection is busy time of kind ``gc``
    on the thread that triggered it, taken out of the self time of the
    span it interrupted; off, no hook is installed."""
    import gc

    rec = TraceRecorder(node="n1", enabled=False)
    assert recmod._gc_span not in gc.callbacks

    def while_on():
        obs.poll_profiler()
        assert recmod._gc_span in gc.callbacks
        span = rec.begin("deliver")
        gc.collect()
        ev = rec.end(span)
        obs.poll_profiler()
        return ev

    out = []
    _profile(tmp_path, lambda: out.append(while_on()))
    obs.poll_profiler()
    assert recmod._gc_span not in gc.callbacks
    (ev,) = out
    busy = obs.last_summary()["busy"][threading.current_thread().name]
    assert busy["gc"]["calls"] >= 1 and busy["gc"]["self_s"] > 0
    # the collection ran inside the span and is no part of its self time
    assert ev.self_s <= ev.dur - busy["gc"]["self_s"] + 1e-6
    assert busy["deliver"]["self_s"] == pytest.approx(ev.self_s)


# -- the account ---------------------------------------------------------------


def _decision(node, view, seq, t0, deltas_ms, proposer=True):
    """The five marks of one decision, ``deltas_ms`` apart."""
    kinds = ("batch.propose", "quorum.prepare", "wal.persist",
             "quorum.commit", "decision.deliver")
    out, t = [], t0
    for kind, step in zip(kinds, (0.0,) + tuple(deltas_ms)):
        t += step / 1e3
        extra = {"count": 100}
        if kind == "decision.deliver" and proposer:
            extra["proposer"] = True
        out.append(SpanEvent(t, kind, node, view=view, seq=seq, extra=extra))
    return out


def test_decision_segments_are_exact_and_sum_to_propose_to_deliver():
    t0 = 81234.123456  # a perf_counter reading, not a tick
    events = []
    events += _decision("s0n1", 0, 1, t0, (10.0, 2.0, 8.0, 0.5))
    events += _decision("s0n1", 0, 2, t0 + 1, (12.0, 3.0, 9.0, 0.7))
    events += _decision("s0n1", 0, 3, t0 + 2, (30.0, 1.0, 7.0, 0.6))
    # a follower's marks for the same slots: no batch.propose, no row
    events += [e for e in _decision("s0n2", 0, 1, t0 + 0.001,
                                    (9.0, 2.0, 8.0, 0.5), proposer=False)
               if e.kind != "batch.propose"]
    # a later re-record of a mark (an assist) does not move the segment
    events.append(SpanEvent(t0 + 5, "quorum.prepare", "s0n1", view=0, seq=1))
    rows = decision_rows(events)
    assert [(r["node"], r["seq"]) for r in rows] == [
        ("s0n1", 1), ("s0n1", 2), ("s0n1", 3)]
    for r in rows:
        parts = sum(r[s] for s in obs.DECISION_SEGMENTS)
        assert parts == pytest.approx(r["total_ms"], abs=1e-9)
    assert [r["prepare_wave"] for r in rows] == [
        pytest.approx(10.0), pytest.approx(12.0), pytest.approx(30.0)]
    # medians are of the raw values (12.0), not of a bucket's edge
    acc = _account(events, t1=t0 + 10)
    assert sorted(acc["segments"]["prepare_wave"])[1] == pytest.approx(12.0)
    assert acc["counters"]["decisions"] == 3
    assert acc["counters"]["requests_proposed"] == 300


def test_account_counts_handovers_and_forwards_dropped_for_no_lead():
    """``req.handover`` (the outgoing leader's recorder) and
    ``req.not_leader`` (the receiver's) are counters of the account, from
    every recorder, inside the interval only; an interval with neither
    reads 0, not absent."""
    events = [SpanEvent(1.0 + k / 100, "req.handover", "s0n1", key=f"c:{k}",
                        dur=0.02) for k in range(5)]
    events += [SpanEvent(1.5, "req.handover", "s0n2", key="c:9", dur=0.01),
               SpanEvent(1.6, "req.not_leader", "s0n3", key="c:9",
                         extra={"sender": 2}),
               SpanEvent(9.0, "req.handover", "s0n4", key="c:10", dur=0.01)]
    c = _account(events, t1=2.0)["counters"]
    assert (c["handovers"], c["not_leader_forwards"]) == (6, 1)
    c = _account([], t1=2.0)["counters"]
    assert (c["handovers"], c["not_leader_forwards"]) == (0, 0)


class _Ring:
    """What assemble_account needs of a recorder."""

    def __init__(self, events, dropped=0):
        self._events = events
        self.recorded = len(events)
        self.dropped = dropped

    def events(self):
        return list(self._events)


def _account(events, *, t1, busy=None, t0=0.0, cpu=1.0):
    return assemble_account([_Ring(events)], busy or {}, t0=t0, t1=t1,
                            loop_cpu_s=cpu, loop_thread="MainThread",
                            ticks=3)


def test_account_takes_the_tail_after_the_interval_back_out():
    """stop_trace blocks the loop thread, so the tick that sees the
    profiler off comes late: the interval ends at the last tick that saw
    it on, and busy spans ended after it leave the sums again."""
    inside = SpanEvent(10.0, "deliver", "s0n1", dur=0.4, self_s=0.3,
                       thread="MainThread")
    late = SpanEvent(12.5, "deliver", "s0n1", dur=0.2, self_s=0.2,
                     thread="MainThread")
    late_other = SpanEvent(12.6, "wal.fsync", "s0n1", dur=0.1, self_s=0.1,
                           thread="asyncio_0")
    busy = {"MainThread": {"deliver": [2, 0.5, 0.6, 0.0]},
            "asyncio_0": {"wal.fsync": [1, 0.1, 0.1, 0.0]}}
    acc = _account([inside, late, late_other], t1=11.0, busy=busy, t0=9.0,
                   cpu=1.5)
    d = acc["busy"]["MainThread"]["deliver"]
    assert d["calls"] == 1 and d["self_s"] == pytest.approx(0.3)
    assert acc["busy"]["asyncio_0"]["wal.fsync"]["calls"] == 0
    assert acc["counters"]["fsync_waves"] == 0
    assert acc["loop"]["busy_self_s"] == pytest.approx(0.3)
    assert acc["interval"]["wall_s"] == pytest.approx(2.0)
    assert acc["loop"]["cpu_s"] == 1.5


def test_account_joins_requests_to_their_batch_at_the_proposer():
    t0 = 500.0
    events = _decision("s0n1", 0, 1, t0 + 0.100, (20.0, 2.0, 8.0, 1.0))
    for i, at in enumerate((0.010, 0.040, 0.070)):
        events.append(SpanEvent(t0 + at, "req.submit", "s0n1", key=f"c:{i}"))
        events.append(SpanEvent(t0 + 0.1311, "req.deliver", "s0n1",
                                key=f"c:{i}", view=0, seq=1))
    events.append(SpanEvent(t0 + 0.2, "verify.wait", "verify", dur=0.007))
    acc = _account(events, t1=t0 + 1)
    assert sorted(acc["waits"]["pool.wait"]) == [
        pytest.approx(30.0), pytest.approx(60.0), pytest.approx(90.0)]
    assert sorted(acc["waits"]["req.total"])[0] == pytest.approx(61.1)
    assert acc["waits"]["verify.wait"] == [pytest.approx(7.0)]


# -- a wall-driven run with the profiler on --------------------------------------


def _traced_run(tmp: str) -> dict:
    """n=4, real provider stack over the toy device kernel, group-commit
    WALs, wall clock; the profiler on for ~1 s of closed-loop traffic."""
    import jax

    from smartbft_tpu.crypto.provider import JaxVerifyEngine
    from smartbft_tpu.testing import toy_scheme
    from smartbft_tpu.testing.sharded import ShardedCluster, sharded_config
    from smartbft_tpu.utils.clock import WallClockDriver

    trace_dir = os.path.join(tmp, "trace")
    out = {}

    async def run():
        engine = JaxVerifyEngine(pad_sizes=(8, 64), scheme=toy_scheme)
        cluster = ShardedCluster(os.path.join(tmp, "wal"), shards=1, n=4,
                                 depth=1, crypto="toy", engine=engine,
                                 window=0.002, journal=False,
                                 config_fn=lambda _s, i: sharded_config(
                                     i, wal_group_commit=True))
        driver = WallClockDriver(cluster.scheduler, tick_interval=0.005)
        driver.start()
        await cluster.start()
        try:
            while not cluster.shard_list[0].ready():
                await asyncio.sleep(0.01)
            ready = [f"c{i}" for i in range(8)]
            seq, inflight, committed = {}, {}, 0

            async def turn():
                nonlocal committed
                for e in cluster.poll():
                    for key in e.request_ids:
                        client = inflight.pop(key, None)
                        if client is not None:
                            committed += 1
                            ready.append(client)
                while ready:
                    c = ready.pop()
                    k = seq[c] = seq.get(c, -1) + 1
                    inflight[f"{c}:r{k}"] = c
                    await cluster.submit(c, f"r{k}")
                await asyncio.sleep(0.002)

            end = time.perf_counter() + 0.5
            while time.perf_counter() < end:  # warm: compile off the trace
                await turn()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            # from a thread of its own, as the benchmark starts it; kept
            # alive until the trace is written, or a worker thread that
            # inherits its id would show on a line named after it
            up, done = threading.Event(), threading.Event()

            def start():
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                up.set()
                done.wait(60.0)

            starter = threading.Thread(target=start)
            starter.start()
            assert up.wait(60.0)
            before = committed
            end = time.perf_counter() + 1.0
            while time.perf_counter() < end:
                await turn()
            out["committed_traced"] = committed - before
            jax.profiler.stop_trace()
            done.set()
            starter.join()
            end = time.perf_counter() + 0.2
            while time.perf_counter() < end:  # ticks that see it off
                await turn()
            out["still_on"] = sum(r.enabled
                                  for r in cluster._recorders.values())
        finally:
            await cluster.stop()
            await driver.stop()

    asyncio.run(run())
    out["account"] = obs.last_summary()
    (out["xplane"],) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return out


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """:func:`_traced_run` in a process of its own: the profiler names a
    thread's line when it first sees its thread id, so threads that
    inherit the ids of an earlier session's dead threads (the tests above
    profile too) would show under those threads' names."""
    import json
    import subprocess
    import sys

    tmp = str(tmp_path_factory.mktemp("traced"))
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), tmp],
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    with open(os.path.join(tmp, "out.json")) as f:
        return json.load(f)


def test_profiled_run_drops_nothing_and_accounts_for_the_loop(traced_run):
    acc = traced_run["account"]
    assert traced_run["committed_traced"] > 0
    assert acc["dropped"] == 0 and acc["refused"] == {}
    assert 0.5 < acc["interval"]["wall_s"] < 3.0
    assert 0 < acc["loop"]["cpu_s"] <= acc["interval"]["wall_s"] * 1.05
    c = acc["counters"]
    assert c["decisions"] > 0 and c["launches"] > 0 and c["signatures"] > 0
    assert c["fsync_waves"] > 0 and c["requests_proposed"] > 0
    loop = acc["busy"][acc["loop"]["thread"]]
    for kind in ("view.run", "view.ingest", "vote.sign", "deliver",
                 "net.ingest", "net.route", "codec", "wal.append",
                 "front.submit", "batch.cut"):
        assert loop[kind]["calls"] > 0, kind
    # the account names most of the loop thread's CPU
    assert acc["loop"]["busy_self_s"] > 0.5 * acc["loop"]["cpu_s"]
    # every decision row's segments sum to its propose -> deliver time
    assert len(acc["decisions"]) > 0
    for row in acc["decisions"]:
        assert sum(row[s] for s in obs.DECISION_SEGMENTS) == \
            pytest.approx(row["total_ms"], abs=1e-6)
    assert acc["waits"]["pool.wait"] and acc["waits"]["verify.wait"]
    # off again: every recorder the switch turned on is off
    assert traced_run["still_on"] == 0


def test_xplane_holds_program_spans_on_their_threads_lines(traced_run):
    from chipbench.trace import load_xplane

    events = [e for e in load_xplane(traced_run["xplane"])
              if e.name.startswith("tpubft.")]
    lines = {}
    for e in events:
        lines.setdefault(e.name, set()).add(e.line)
    loop_lines = lines["tpubft.view.ingest"]
    assert len(loop_lines) == 1
    for name in ("tpubft.view.run", "tpubft.deliver", "tpubft.wal.append",
                 "tpubft.batch.propose", "tpubft.decision.deliver"):
        assert lines[name] == loop_lines, name
    # the threads that ran launches and fsync waves have lines of their
    # own.  (A line is named when the profiler first sees its thread id:
    # a worker that inherits the id of a dead thread of the process shows
    # under that thread's name, so not EVERY such line can be told from
    # the loop's by name.)
    for name in ("tpubft.verify.pack", "tpubft.verify.device",
                 "tpubft.wal.fsync"):
        assert lines[name] - loop_lines, name
    # pack precedes device inside each launch, on the launch's thread
    dev = sorted((e for e in events if e.name == "tpubft.verify.device"),
                 key=lambda e: e.start_ns)
    pack = sorted((e for e in events if e.name == "tpubft.verify.pack"),
                  key=lambda e: e.start_ns)
    # (the trace may end between a launch's pack and its device span)
    assert dev and 0 <= len(pack) - len(dev) <= 1
    assert all(p.end_ns <= d.start_ns for p, d in zip(pack, dev))


# -- the loop hook (ISSUE 37) ------------------------------------------------------


def test_loop_hook_is_there_only_while_the_profiler_is_on(tmp_path,
                                                          monkeypatch):
    """``Handle._run`` is wrapped at the on edge and is the ORIGINAL
    object again after the off edge, also when the off edge raises; a
    session whose on edge comes outside a running loop hooks nothing."""
    from asyncio import events

    from smartbft_tpu.obs import account as accmod

    original = events.Handle.__dict__["_run"]
    seen = []

    async def session():
        obs.poll_profiler()
        seen.append(events.Handle.__dict__["_run"])
        for _ in range(3):  # whole handles between the two ticks
            await asyncio.sleep(0)
        obs.poll_profiler()

    _profile(tmp_path / "a", lambda: asyncio.run(session()))
    assert seen[0] is not original and recmod._switch.hook is not None
    obs.poll_profiler()
    assert events.Handle.__dict__["_run"] is original
    assert recmod._switch.hook is None
    acc = obs.last_summary()
    assert acc["loop_steps"]["covered"] and "timeline" in acc
    assert acc["loop"]["turns"] >= 1

    def boom(*a, **kw):
        raise RuntimeError("the fold failed")

    monkeypatch.setattr(accmod, "assemble_account", boom)
    _profile(tmp_path / "b", lambda: asyncio.run(session()))
    with pytest.raises(RuntimeError):
        obs.poll_profiler()
    assert events.Handle.__dict__["_run"] is original
    monkeypatch.undo()

    # no running loop at the on edge: declined, and the account says so
    _profile(tmp_path / "c", obs.poll_profiler)
    obs.poll_profiler()
    acc = obs.last_summary()
    assert acc["loop_steps"] == {"covered": False}
    assert "timeline" not in acc and "turns" not in acc["loop"]
    assert events.Handle.__dict__["_run"] is original


def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_loop_hook_names_every_handle_by_its_owner(tmp_path):
    """A program task without ``busy=`` is ``loop.program``; one with it
    keeps its kind and adds nothing to ``loop.program``; a foreign task's
    step gives ``loop.embedder`` its SELF time only, the program span
    opened inside it taken out; a plain callback is ``loop.callback``."""
    from smartbft_tpu.utils.clock import Scheduler, WallClockDriver
    from smartbft_tpu.utils.tasks import create_logged_task

    rec = TraceRecorder(node="n1", enabled=False)
    out = {}

    async def kept():  # a program task with a kind of its own
        for _ in range(3):
            await asyncio.sleep(0)
            _spin(0.001)

    async def foreign():  # the embedder's, calling into the program
        await asyncio.sleep(0)
        t0 = time.perf_counter()
        _spin(0.001)
        span = rec.begin("deliver")
        _spin(0.002)
        out["deliver"] = rec.end(span)
        out["step_s"] = time.perf_counter() - t0

    def plain_callback():
        _spin(0.001)

    async def session():
        driver = WallClockDriver(Scheduler(), tick_interval=0.002)
        driver.start()  # the program's, no kind: it polls the profiler
        while not rec.enabled:
            await asyncio.sleep(0.001)
        task = create_logged_task(kept(), name="k", busy=(rec, "view.run"))
        asyncio.get_running_loop().call_soon(plain_callback)
        await asyncio.gather(task, asyncio.ensure_future(foreign()))
        await asyncio.sleep(0.01)  # a tick after the last of them
        await driver.stop()

    _profile(tmp_path, lambda: asyncio.run(session()))
    obs.poll_profiler()
    acc = obs.last_summary()
    busy = acc["busy"][acc["loop"]["thread"]]
    owners = {o["name"]: o for o in acc["loop_steps"]["owners"]}
    assert acc["refused"] == {}
    # the driver: a step a tick, under its coroutine's name
    assert owners["WallClockDriver._run"]["kind"] == "loop.program"
    assert owners["WallClockDriver._run"]["calls"] >= 2
    # ``busy=``: the spans keep the calls, the hook adds the rest of each
    # step to the SAME kind and nothing to ``loop.program``
    k = next(n for n in owners if n.endswith("kept"))
    assert owners[k]["kind"] == "view.run" and owners[k]["calls"] == 4
    assert busy["view.run"]["calls"] == 4
    assert busy["view.run"]["self_s"] >= 0.003
    assert busy["loop.program"]["self_s"] < 0.003
    # the embedder's step: its own 1 ms, not the 2 ms of ``deliver``
    f = next(n for n in owners if n.endswith("foreign"))
    assert owners[f]["kind"] == "loop.embedder"
    assert busy["deliver"]["self_s"] == pytest.approx(out["deliver"].self_s)
    assert 0.001 <= owners[f]["self_s"] \
        <= out["step_s"] - out["deliver"].dur + 0.0005
    cb = next(n for n in owners if n.endswith("plain_callback"))
    assert owners[cb]["kind"] == "loop.callback"
    assert owners[cb]["calls"] == 1 and owners[cb]["self_s"] >= 0.001
    assert busy["loop.callback"]["calls"] >= 1
    # the loop thread's CPU closes, and what the spans do not name is small
    loop = acc["loop"]
    assert loop["steps_cpu_s"] + loop["outside_s"] == \
        pytest.approx(loop["cpu_s"])
    assert loop["busy_self_s"] == pytest.approx(loop["steps_wall_s"],
                                                rel=0.02)
    t = acc["timeline"]
    assert t["both_s"] + t["loop_only_s"] + t["launch_only_s"] \
        + t["neither_s"] == pytest.approx(acc["interval"]["wall_s"])
    assert t["launch_only_s"] == t["both_s"] == 0.0


class _Annotation:
    """Stands for ``TraceAnnotation``: counts what was entered and left."""

    entered = left = 0

    def __init__(self, name):
        assert name == "tpubft.loop.busy"

    def __enter__(self):
        _Annotation.entered += 1

    def __exit__(self, *exc):
        _Annotation.left += 1


def _hooked_handles(handles, *, loop_cpu_s):
    """Run ``(gap before, wall, CPU)`` handles through the hook on a pair
    of synthetic clocks -> the account."""
    from asyncio import events

    from smartbft_tpu.obs import loophook

    wall, cpu = Clock(100.0), Clock(7.0)

    def work(w, c):
        wall.t += w
        cpu.t += c

    async def main():
        hook = loophook.install(_Annotation, clock=wall, cpu_clock=cpu)
        try:
            for gap, w, c in handles:
                wall.t += gap
                events.Handle._run(events.Handle(
                    work, (w, c), asyncio.get_running_loop()))
            hook.tick()
            wall.t += 1.0  # after the last tick: dropped
            events.Handle._run(events.Handle(
                work, (0.5, 0.5), asyncio.get_running_loop()))
        finally:
            hook.remove()
        return hook.block()

    steps = asyncio.run(main())
    return assemble_account([], {}, t0=100.0, t1=wall.t - 1.5,
                            loop_cpu_s=loop_cpu_s, loop_thread="MainThread",
                            loop_steps=steps)


def test_busy_intervals_merge_under_50_us_and_not_over():
    _Annotation.entered = _Annotation.left = 0
    acc = _hooked_handles([(0.0, 1e-3, 1e-3), (40e-6, 2e-3, 2e-3),
                           (60e-6, 1e-3, 1e-3), (49e-6, 1e-3, 1e-3)],
                          loop_cpu_s=5e-3)
    steps = acc["loop_steps"]
    assert acc["loop"]["turns"] == 4  # the one after the last tick is out
    assert steps["intervals"] == 2
    assert steps["busy_s"] == pytest.approx(5e-3 + 40e-6 + 49e-6)
    (work,) = steps["owners"]
    assert work["kind"] == "loop.callback" and work["calls"] == 4
    assert work["self_s"] == pytest.approx(5e-3)
    # nothing waits in the loop's ready queue: an annotation a handle
    assert _Annotation.entered == _Annotation.left == 5


def test_lock_wait_and_outside_on_a_synthetic_pair_of_clocks():
    acc = _hooked_handles([(0.0, 4e-3, 1e-3), (1e-3, 2e-3, 2e-3)],
                          loop_cpu_s=3.5e-3)
    loop = acc["loop"]
    assert loop["steps_wall_s"] == loop["runs_wall_s"] == pytest.approx(6e-3)
    assert loop["steps_cpu_s"] == pytest.approx(3e-3) and loop["runs"] == 2
    # inside a handle and off the CPU; on the CPU and outside any handle
    assert loop["lock_wait_s"] == pytest.approx(3e-3)
    assert loop["outside_s"] == pytest.approx(0.5e-3)
    assert loop["between_s"] == pytest.approx(0.0)  # a handle a run here
    assert loop["busy_self_s"] == pytest.approx(6e-3)  # the hook's kinds


@pytest.mark.parametrize("loop,launch,fsync,want", [
    # disjoint
    ([(1.0, 2.0)], [(3.0, 4.0)], [(5.0, 6.0)],
     dict(both_s=0.0, loop_only_s=1.0, launch_only_s=1.0, neither_s=8.0,
          neither_fsync_s=1.0)),
    # nested: the launch inside a handle, the fsync inside the launch
    ([(1.0, 9.0)], [(2.0, 4.0)], [(2.5, 3.0)],
     dict(both_s=2.0, loop_only_s=6.0, launch_only_s=0.0, neither_s=2.0,
          neither_fsync_s=0.0)),
    # overlapping, out of order, over both edges, two launches that touch
    ([(6.0, 8.0), (-1.0, 2.0), (1.5, 3.0)], [(2.0, 5.0), (5.0, 7.0),
                                            (9.5, 12.0)],
     [(4.0, 9.75)],
     dict(both_s=2.0, loop_only_s=3.0, launch_only_s=3.5, neither_s=1.5,
          neither_fsync_s=1.5)),
    # empty
    ([], [], [], dict(both_s=0.0, loop_only_s=0.0, launch_only_s=0.0,
                      neither_s=10.0, neither_fsync_s=0.0)),
], ids=["disjoint", "nested", "overlapping", "empty"])
def test_timeline_splits_the_interval_and_sums_to_the_wall(loop, launch,
                                                           fsync, want):
    got = obs.assemble_timeline(loop, launch, fsync, t0=0.0, t1=10.0)
    assert got == pytest.approx(want)
    assert got["both_s"] + got["loop_only_s"] + got["launch_only_s"] \
        + got["neither_s"] == 10.0


def test_account_has_the_launch_block_and_clips_the_timeline():
    """``launch``: the three launch kinds over every thread, wall minus
    thread CPU; the timeline takes launch and fsync spans from the ring,
    clipped at the interval's edges, not dropped."""
    busy = {"smartbft-verify-launch": {
        "verify.pack": [2, 0.5, 0.5, 0.2],
        "verify.device": [2, 2.0, 2.0, 0.1]},
        "MainThread": {"deliver": [1, 0.25, 0.25, 0.0]}}
    events = [
        SpanEvent(1.5, "verify.pack", dur=0.5, self_s=0.5,
                  thread="smartbft-verify-launch"),
        SpanEvent(2.5, "verify.device", dur=1.0, self_s=1.0,
                  thread="smartbft-verify-launch"),
        SpanEvent(4.5, "verify.device", dur=1.0, self_s=1.0,
                  thread="smartbft-verify-launch"),
        SpanEvent(3.75, "wal.fsync", dur=0.5, self_s=0.5, thread="x"),
    ]
    acc = assemble_account(
        [_Ring(events)], busy, t0=0.0, t1=4.0, loop_cpu_s=1.0,
        loop_thread="MainThread",
        loop_steps={"turns": 2, "wall_s": 1.0, "runs": 1, "runs_wall_s": 1.0,
                    "cpu_s": 0.75, "owners": {}, "kinds": {},
                    "intervals": [2.0, 3.0]})
    # the launch that ended after the interval left the sums and is
    # clipped into the timeline
    assert acc["launch"] == {
        "launches": 1,
        "verify.pack": {"dur_s": 0.5, "cpu_s": 0.2, "off_cpu_s": 0.3},
        "verify.device": {"dur_s": 1.0, "cpu_s": 0.1, "off_cpu_s": 0.9}}
    assert acc["timeline"] == pytest.approx(dict(
        both_s=0.5, loop_only_s=0.5, launch_only_s=1.5, neither_s=1.5,
        neither_fsync_s=0.25))
    # without the hook's sums: the launch block alone
    bare = _account(events, t1=4.0, busy=busy)
    assert bare["launch"]["launches"] == 1 and "timeline" not in bare
    assert bare["loop_steps"] == {"covered": False}


def test_account_sums_each_coalescers_last_threads_started():
    """``launch.threads_started`` (PR 38): the count each coalescer's
    last ``verify.handin`` of the interval carried, summed over the
    coalescers; a hand-in after the interval does not count, and an
    account with no hand-in (an earlier program's) has no such key."""
    def handin(t, n):
        return SpanEvent(t, "verify.handin", "verify", dur=0.001,
                         extra={"threads_started": n})

    a = _Ring([handin(1.0, 1), handin(2.0, 2), handin(5.0, 3)])
    b = _Ring([handin(1.5, 1),
               SpanEvent(1.6, "verify.handback", "verify", dur=0.002)])
    acc = assemble_account([a, b], {}, t0=0.0, t1=4.0, loop_cpu_s=1.0,
                           loop_thread="MainThread")
    assert acc["launch"]["threads_started"] == 3
    assert acc["waits"]["verify.handin"] == pytest.approx([1.0, 1.0, 1.0])
    assert acc["waits"]["verify.handback"] == pytest.approx([2.0])
    assert "threads_started" not in _account([], t1=4.0)["launch"]


def test_profiled_run_hands_every_launch_to_one_resident_thread(traced_run):
    """In a real profiled cluster the live waves ran on the ONE launch
    thread its coalescer started before the trace, and both hand-offs
    were timed."""
    acc = traced_run["account"]
    assert acc["launch"]["threads_started"] == 1
    assert acc["waits"]["verify.handin"] and acc["waits"]["verify.handback"]
    value = _reader("launch_handoff_ms")(types.SimpleNamespace(account=acc))
    assert value is not None and 0.0 < value < 1e3
    assert _reader("launch_handoff_ms")(
        types.SimpleNamespace(account=ACCOUNT)) is None


def test_profiled_run_closes_the_loops_account(traced_run):
    """A whole run under the profiler (ISSUE 37): every handle the loop
    ran is named, so the busy self time on the loop thread is the wall
    inside handles; its CPU closes; the timeline sums to the wall; the
    request path of a cluster without envelopes has no ``req.admit``."""
    acc = traced_run["account"]
    loop, steps = acc["loop"], acc["loop_steps"]
    assert steps["covered"] and steps["intervals"] > 0
    assert loop["turns"] > 100 and acc["refused"] == {}
    assert loop["busy_self_s"] == pytest.approx(loop["steps_wall_s"],
                                                rel=0.02)
    assert loop["steps_cpu_s"] + loop["outside_s"] == \
        pytest.approx(loop["cpu_s"])
    assert loop["steps_wall_s"] <= loop["runs_wall_s"]
    assert 0 < loop["runs"] <= loop["turns"]
    assert -0.02 <= loop["lock_wait_s"] < loop["runs_wall_s"]
    busy = acc["busy"][loop["thread"]]
    assert busy["loop.embedder"]["calls"] > 0  # the test's own ``turn``
    assert busy["loop.callback"]["calls"] > 0
    assert busy["loop.program"]["calls"] > 0
    assert "req.admit" not in busy
    assert busy["vc.run"]["calls"] > 0  # the view changer's steps, named
    names = {o["name"]: o["kind"] for o in steps["owners"]}
    assert names.get("View._run", "view.run") == "view.run"
    assert any(kind == "loop.embedder" for kind in names.values())
    t = acc["timeline"]
    assert t["both_s"] + t["loop_only_s"] + t["launch_only_s"] \
        + t["neither_s"] == pytest.approx(acc["interval"]["wall_s"])
    assert min(t.values()) >= -1e-9
    assert t["both_s"] + t["launch_only_s"] > 0  # launches were out
    launch = acc["launch"]
    assert launch["launches"] == acc["counters"]["launches"]
    assert launch["verify.device"]["off_cpu_s"] == pytest.approx(
        launch["verify.device"]["dur_s"] - launch["verify.device"]["cpu_s"])


def test_xplane_holds_the_loops_busy_time_without_holes(traced_run):
    """``tpubft.loop.busy`` is on the loop thread's line, and every
    program span of that line lies inside one of them."""
    from chipbench.trace import load_xplane

    events = [e for e in load_xplane(traced_run["xplane"])
              if e.name.startswith("tpubft.")]
    busy = sorted((e for e in events if e.name == "tpubft.loop.busy"),
                  key=lambda e: e.start_ns)
    assert busy and len({e.line for e in busy}) == 1
    line = busy[0].line
    assert {e.line for e in events if e.name == "tpubft.view.run"} == {line}
    inside = [e for e in events
              if e.line == line and e.name != "tpubft.loop.busy"
              and e.start_ns >= busy[1].start_ns
              and e.end_ns <= busy[-2].end_ns]
    assert len(inside) > 50
    starts = [b.start_ns for b in busy]
    import bisect

    for e in inside:
        b = busy[bisect.bisect_right(starts, e.start_ns) - 1]
        assert b.start_ns <= e.start_ns and e.end_ns <= b.end_ns + 1, e


# -- the readers -----------------------------------------------------------------

READERS = {
    "loop_cpu_pct": 80.0,
    "host_unnamed_pct": 25.0,
    "view_us_per_decision": 30000.0,
    "deliver_us_per_decision": 5000.0,
    "wal_append_us_per_decision": 2000.0,
    "seg_prepare_wave_ms": 12.0,
    "seg_wal_persist_ms": 3.0,
    "seg_commit_wave_ms": 9.0,
    "seg_deliver_ms": 0.7,
    "pool_wait_ms": 60.0,
    "verify_wait_ms": 7.0,
    "wal_fsync_ms": 1.5,
    "fsyncs_per_decision": 12.0,
    "verify_pack_ms_per_launch": 4.0,
    "verify_device_ms_per_launch": 2.5,
    "loop_gc_pct": 5.0,
}

ACCOUNT = {
    "interval": {"t0": 10.0, "t1": 12.0, "wall_s": 2.0, "ticks": 200},
    "loop": {"thread": "MainThread", "cpu_s": 1.6, "busy_self_s": 1.2},
    "busy": {
        "MainThread": {
            "view.run": {"calls": 40, "self_s": 0.05, "dur_s": 0.09,
                         "cpu_s": 0.0},
            "view.ingest": {"calls": 90, "self_s": 0.03, "dur_s": 0.03,
                            "cpu_s": 0.0},
            "vote.sign": {"calls": 8, "self_s": 0.01, "dur_s": 0.01,
                          "cpu_s": 0.0},
            "deliver": {"calls": 12, "self_s": 0.015, "dur_s": 0.015,
                        "cpu_s": 0.0},
            "wal.append": {"calls": 36, "self_s": 0.006, "dur_s": 0.006,
                           "cpu_s": 0.0},
            "gc": {"calls": 9, "self_s": 0.08, "dur_s": 0.08, "cpu_s": 0.0},
        },
        "smartbft-verify-launch": {
            "verify.pack": {"calls": 5, "self_s": 0.020, "dur_s": 0.020,
                            "cpu_s": 0.019},
            "verify.device": {"calls": 5, "self_s": 0.0125, "dur_s": 0.0125,
                              "cpu_s": 0.002},
        },
        "asyncio_0": {
            "wal.fsync": {"calls": 36, "self_s": 0.05, "dur_s": 0.05,
                          "cpu_s": 0.0},
        },
    },
    "counters": {"decisions": 3, "requests_proposed": 300, "launches": 5,
                 "signatures": 8, "fsync_waves": 36},
    "segments": {"prepare_wave": [10.0, 12.0, 30.0],
                 "wal_persist": [2.0, 3.0, 4.0],
                 "commit_wave": [8.0, 9.0, 11.0],
                 "deliver": [0.5, 0.7, 0.9]},
    "decisions": [],
    "waits": {"pool.wait": [30.0, 60.0, 90.0], "req.total": [61.0, 91.0],
              "verify.wait": [6.0, 7.0, 9.0], "verify.hold": [],
              "wal.persist": [1.0]},
    "durations": {"wal.fsync": [1.0, 1.5, 4.0]},
    "recorders": 7, "recorded": 1000, "dropped": 0, "refused": {},
}


def _reader(name):
    path = os.path.join(ROOT, "chipbench", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_hand_built_account(name):
    run = types.SimpleNamespace(account=ACCOUNT)
    assert _reader(name)(run) == pytest.approx(READERS[name])


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_finds_nothing_in_an_empty_account(name):
    """An account with no decisions, launches or waits — and a program
    that has no account at all — give None, never a raise or a zero."""
    empty = {
        "interval": {"t0": 0.0, "t1": 0.0, "wall_s": 0.0, "ticks": 0},
        "loop": {"thread": "MainThread", "cpu_s": 0.0, "busy_self_s": 0.0},
        "busy": {}, "segments": {s: [] for s in obs.DECISION_SEGMENTS},
        "counters": {"decisions": 0, "requests_proposed": 0, "launches": 0,
                     "signatures": 0, "fsync_waves": 0},
        "decisions": [], "waits": {}, "durations": {},
        "recorders": 0, "recorded": 0, "dropped": 0, "refused": {},
    }
    read = _reader(name)
    assert read(types.SimpleNamespace(account=empty)) is None
    assert read(types.SimpleNamespace(account={})) is None


#: the mesh deployment's readers (PR 32), on ACCOUNT with a mesh block
MESH_READERS = {
    "mesh_place_ms_per_launch": 1.5,
    "mesh_fill_pct": 12.5,
    "mesh_spanning_pct": 75.0,
}
MESH_ACCOUNT = dict(
    ACCOUNT,
    busy=dict(ACCOUNT["busy"], **{"smartbft-verify-launch": dict(
        ACCOUNT["busy"]["smartbft-verify-launch"],
        **{"verify.place": {"calls": 4, "self_s": 0.006, "dur_s": 0.006,
                            "cpu_s": 0.001}})}),
    mesh={"launches": 4, "spanning": 3, "used": 256, "launched": 2048,
          "used_by_device": [64, 64, 64, 64],
          "launched_by_device": [512, 512, 512, 512]})


@pytest.mark.parametrize("name", sorted(MESH_READERS))
def test_mesh_reader_on_a_hand_built_account(name):
    run = types.SimpleNamespace(account=MESH_ACCOUNT)
    assert _reader(name)(run) == pytest.approx(MESH_READERS[name])


@pytest.mark.parametrize("name", sorted(MESH_READERS))
def test_mesh_reader_finds_nothing_without_a_mesh(name):
    """The parent's account (no ``mesh`` block, no ``verify.place``
    span), a one-chip cell's (a block that saw no launch) and no account
    at all give None, never a raise or a zero."""
    read = _reader(name)
    no_launch = dict(MESH_ACCOUNT, mesh={
        "launches": 0, "spanning": 0, "used": 0, "launched": 0,
        "used_by_device": [], "launched_by_device": []})
    for account in (ACCOUNT, no_launch, {}):
        assert read(types.SimpleNamespace(account=account)) is None


#: the readers of the loop hook's blocks (PR 37); their values on a
#: hand-made account are ``chipbench/tests/test_chipbench_loop_readers.py``'s
LOOP_READERS = (
    "loop_turns_per_decision", "loop_embedder_pct",
    "request_path_us_per_req", "loop_lock_wait_pct",
    "loop_outside_handles_pct", "launch_lock_wait_ms_per_launch",
    "verify_device_kernel_pct", "tl_both_pct", "tl_launch_only_pct",
    "tl_loop_only_pct", "tl_neither_pct")


@pytest.mark.parametrize("name", LOOP_READERS)
def test_loop_reader_on_a_traced_runs_account(traced_run, name):
    """Each reads a number off the account of a real profiled run (with a
    stand-in for the device trace's kernel seconds), and nothing off the
    parent's shape of account."""
    import math

    acc = traced_run["account"]
    trace = types.SimpleNamespace(busy_s=1e-4)
    value = _reader(name)(types.SimpleNamespace(account=acc, trace=trace))
    # (the CPU clock is read outside the wall clock's bracket, so an idle
    # loop's lock wait may read a hair under zero)
    assert value is not None and math.isfinite(value)
    assert value >= (-1.0 if name == "loop_lock_wait_pct" else 0.0)
    if name.endswith("_pct") and name != "verify_device_kernel_pct":
        assert value <= 100.0
    assert _reader(name)(types.SimpleNamespace(account=ACCOUNT,
                                               trace=trace)) is None


def test_the_timeline_readers_sum_to_a_hundred(traced_run):
    run = types.SimpleNamespace(account=traced_run["account"])
    assert sum(_reader(f"tl_{part}_pct")(run) for part in
               ("both", "launch_only", "loop_only", "neither")) == \
        pytest.approx(100.0)


def test_account_folds_mesh_launches_by_device():
    """``verify.lanes`` marks that carry ``per_device`` (the mesh
    engine's) feed ``lanes`` by kernel AND the ``mesh`` block; a
    one-device engine's marks feed ``lanes`` alone; an interval with no
    mesh launch reads zeros, not absent."""
    def mark(t, kernel, lanes, used, per_device=None):
        extra = {"kernel": kernel, "lanes": lanes, "used": used}
        if per_device is not None:
            extra["per_device"] = per_device
        return SpanEvent(t, "verify.lanes", extra=extra)

    acc = _account([
        mark(1.0, "comb", 512, 23, [6, 6, 6, 5]),
        mark(1.1, "comb", 512, 2, [1, 1, 0, 0]),
        mark(1.2, "xla", 8, 3, [1, 1, 1, 0]),
        mark(1.3, "comb", 128, 9),            # a one-device engine's
        mark(9.0, "comb", 512, 40, [10] * 4),  # after the interval
    ], t1=2.0)
    assert acc["lanes"] == {
        "comb": {"launches": 3, "launched": 1152, "used": 34},
        "xla": {"launches": 1, "launched": 8, "used": 3}}
    assert acc["mesh"] == {
        "launches": 3, "spanning": 1, "used": 28, "launched": 1032,
        "used_by_device": [8, 8, 7, 5],
        "launched_by_device": [258, 258, 258, 258]}
    assert _account([], t1=2.0)["mesh"]["launches"] == 0


def test_the_mesh_readers_are_declared_for_the_mesh_cell_alone():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in MESH_READERS:
        m = declared[name]
        assert m["workloads"] == ["mesh16.saturated"]
        assert m["layer"] == "verify plane"
        assert m["source"] in ("program_span", "program_counter")


def test_every_new_reader_is_declared_in_the_benchmark():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for name in READERS:
        m = declared[name]
        assert m["better"] == "lower" and m["moves"] in e2e
        assert m["source"] in ("program_span", "program_counter")
        assert "workloads" not in m  # a number in both cells


if __name__ == "__main__":  # the traced_run fixture's child process
    import json
    import sys

    result = _traced_run(sys.argv[1])
    with open(os.path.join(sys.argv[1], "out.json"), "w") as fh:
        json.dump(result, fh)
