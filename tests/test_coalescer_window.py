"""The coalescer's window is the longest a batch may wait for company, not
how long it always waits: a batch closes when its submitters have gone
quiet (``QUIET_TURNS`` idle turns of the loop without an arrival), when
``max_batch`` fills, or at ``window`` seconds from its first submit.

Host engine, no JAX; every case well under a second.
"""

import asyncio
import threading
import time

import pytest

from smartbft_tpu.crypto.provider import (
    IDLE_TURN,
    QUIET_TURNS,
    AsyncBatchCoalescer,
    WindowStats,
)
from smartbft_tpu.obs import TraceRecorder, assemble_account

REASONS = ("quiet", "window", "full", "drain", "flip")


class Engine:
    """Records every call; a call can be made to take ``seconds`` or to
    stand at a gate until the test opens it."""

    def __init__(self, seconds=0.0):
        self.calls = []
        self.seconds = seconds
        self.gate = None
        self.running = 0
        self.most_running = 0
        self._lock = threading.Lock()

    def verify(self, items):
        with self._lock:
            self.calls.append(list(items))
            self.running += 1
            self.most_running = max(self.most_running, self.running)
        try:
            if self.gate is not None:
                assert self.gate.wait(5.0)
            if self.seconds:
                time.sleep(self.seconds)
            return [it[0] == "ok" for it in items]
        finally:
            with self._lock:
                self.running -= 1


def item(i, ok=True):
    return ("ok" if ok else "bad", i)


def closes(co):
    snap = co.window_stats.snapshot(co.window)
    return {r: snap[r] for r in REASONS if snap[r]}


async def turns(n):
    for _ in range(n):
        await asyncio.sleep(0)


def submit_soon(co, *items):
    return asyncio.ensure_future(co.submit(list(items)))


def test_lone_submitter_does_not_wait_the_window_out():
    engine = Engine()
    co = AsyncBatchCoalescer(engine, window=0.5)

    async def run():
        t0 = time.perf_counter()
        got = await asyncio.wait_for(co.submit([item(1), item(2, False)]), 5)
        return got, time.perf_counter() - t0

    got, took = asyncio.run(run())
    assert got == [True, False]
    assert took < 0.1
    assert closes(co) == {"quiet": 1}
    assert engine.calls == [[item(1), item(2, False)]]
    assert co.window_stats.open_ms < 100.0


@pytest.mark.parametrize("n", [2, 4, 64])
def test_submitters_ready_in_one_turn_share_one_launch(n):
    engine = Engine()
    co = AsyncBatchCoalescer(engine, window=0.5)

    async def run():
        futs = [submit_soon(co, item(i, i % 3 > 0)) for i in range(n)]
        return await asyncio.wait_for(asyncio.gather(*futs), 5)

    got = asyncio.run(run())
    assert got == [[i % 3 > 0] for i in range(n)]
    assert len(engine.calls) == 1 and len(engine.calls[0]) == n
    assert closes(co) == {"quiet": 1}


@pytest.mark.parametrize("m", [3, 25])
def test_submitters_that_arrive_one_a_turn_share_one_launch(m):
    engine = Engine()
    co = AsyncBatchCoalescer(engine, window=0.5)

    async def run():
        futs = []
        for i in range(m):
            futs.append(submit_soon(co, item(i)))
            await asyncio.sleep(0)
        return await asyncio.wait_for(asyncio.gather(*futs), 5)

    got = asyncio.run(run())
    assert got == [[True]] * m
    assert len(engine.calls) == 1 and len(engine.calls[0]) == m
    assert closes(co) == {"quiet": 1}


def test_a_pause_shorter_than_the_quiet_turns_does_not_split_the_batch():
    engine = Engine()
    co = AsyncBatchCoalescer(engine, window=0.5)

    async def run():
        first = submit_soon(co, item(1))
        # the submit's own turn, then one turn fewer than would close it
        await turns(1 + QUIET_TURNS - 1)
        second = submit_soon(co, item(2))
        return await asyncio.wait_for(asyncio.gather(first, second), 5)

    assert asyncio.run(run()) == [[True], [True]]
    assert engine.calls == [[item(1), item(2)]]
    assert closes(co) == {"quiet": 1}


def test_submitters_apart_by_many_turns_get_a_launch_each():
    engine = Engine()
    co = AsyncBatchCoalescer(engine, window=0.5)

    async def run():
        first = await asyncio.wait_for(co.submit([item(1)]), 5)
        second = await asyncio.wait_for(co.submit([item(2)]), 5)
        return first, second

    assert asyncio.run(run()) == ([True], [True])
    assert engine.calls == [[item(1)], [item(2)]]
    assert closes(co) == {"quiet": 2}


def test_turns_of_a_busy_loop_are_not_quiet_and_the_batch_rides_the_cap():
    """No submit arrives for many turns, but every turn the loop is at
    other work for longer than IDLE_TURN: replicas may still be on their
    way, so the batch waits for them up to the window."""
    engine = Engine()
    window = 0.06
    co = AsyncBatchCoalescer(engine, window=window)

    async def run():
        async def other_work():
            while True:
                time.sleep(4 * IDLE_TURN)  # a replica handling messages
                await asyncio.sleep(0)

        worker = asyncio.ensure_future(other_work())
        t0 = time.perf_counter()
        first = submit_soon(co, item(1))
        await asyncio.sleep(window / 3)  # ten and more busy turns later
        assert not first.done()
        late = submit_soon(co, item(2))
        got = await asyncio.wait_for(asyncio.gather(first, late), 5)
        took = time.perf_counter() - t0
        worker.cancel()
        return got, took

    got, took = asyncio.run(run())
    assert got == [[True], [True]]
    assert engine.calls == [[item(1), item(2)]]  # ONE launch, not two
    assert closes(co) == {"window": 1}
    assert window <= took < window + 0.25


def test_a_trickle_that_never_pauses_is_cut_by_the_window():
    engine = Engine()
    window = 0.05
    co = AsyncBatchCoalescer(engine, window=window, max_batch=1 << 30)

    async def run():
        futs = [submit_soon(co, item(0))]
        t0 = time.perf_counter()

        async def trickle():  # one more submitter every turn of the loop
            while True:
                await asyncio.sleep(0)
                futs.append(submit_soon(co, item(len(futs))))

        feeder = asyncio.ensure_future(trickle())
        await asyncio.wait_for(asyncio.shield(futs[0]), 5)
        took = time.perf_counter() - t0
        feeder.cancel()
        await asyncio.wait_for(asyncio.gather(*futs), 5)
        return took

    took = asyncio.run(run())
    # the first submitter waited the window out, and no longer than the
    # window and one (instant) launch; slack for a loaded test machine
    assert window <= took < window + 0.25
    assert closes(co)["window"] == 1  # what came after it: drain or quiet
    assert len(engine.calls[0]) > 2  # company did join while it waited


def test_max_batch_closes_at_once():
    engine = Engine()
    co = AsyncBatchCoalescer(engine, window=30.0, max_batch=4)

    async def run():
        t0 = time.perf_counter()
        futs = [submit_soon(co, item(i), item(i + 100)) for i in range(2)]
        await asyncio.wait_for(asyncio.gather(*futs), 5)
        return time.perf_counter() - t0

    assert asyncio.run(run()) < 1.0
    assert len(engine.calls) == 1 and len(engine.calls[0]) == 4
    assert closes(co) == {"full": 1}


def test_a_window_of_zero_closes_at_once_on_the_windows_account():
    engine = Engine()
    co = AsyncBatchCoalescer(engine, window=0.0)
    assert asyncio.run(asyncio.wait_for(co.submit([item(1)]), 5)) == [True]
    assert closes(co) == {"window": 1}


def test_arrivals_during_a_launch_ride_the_drain_and_never_a_second_one():
    engine = Engine()
    engine.gate = threading.Event()
    co = AsyncBatchCoalescer(engine, window=0.5)

    async def run():
        first = submit_soon(co, item(0))
        while not engine.running:  # the first launch stands at the gate
            await asyncio.sleep(0.001)
        late = []
        for i in range(1, 6):  # arrivals with pauses far beyond the quiet turns
            late.append(submit_soon(co, item(i)))
            await turns(5 * QUIET_TURNS)
        assert len(engine.calls) == 1 and not any(f.done() for f in late)
        engine.gate.set()
        return await asyncio.wait_for(asyncio.gather(first, *late), 5)

    assert asyncio.run(run()) == [[True]] * 6
    assert engine.most_running == 1
    assert engine.calls == [[item(0)], [item(i) for i in range(1, 6)]]
    assert closes(co) == {"quiet": 1, "drain": 1}


def test_the_counts_by_reason_sum_to_the_launches():
    engine = Engine(seconds=0.002)
    co = AsyncBatchCoalescer(engine, window=0.01, max_batch=8)

    async def run():
        futs = []
        for i in range(60):
            futs.append(submit_soon(co, *(item(i * 10 + j)
                                          for j in range(1 + i % 5))))
            if i % 7 == 0:
                await asyncio.sleep(0.003)
            elif i % 2:
                await asyncio.sleep(0)
        co.note_view_flip(span=0.01)
        futs.append(submit_soon(co, item(9999)))
        await asyncio.wait_for(asyncio.gather(*futs), 10)

    asyncio.run(run())
    snap = co.mesh_snapshot()["window"]
    assert snap["window_s"] == 0.01
    assert sum(snap[r] for r in REASONS) == len(engine.calls) == co._launch_seq
    assert snap["open_ms"] > 0.0
    assert sum(len(c) for c in engine.calls) == sum(
        1 + i % 5 for i in range(60)) + 1


def test_a_flip_closes_a_growing_batch_on_its_own_account():
    engine = Engine()
    co = AsyncBatchCoalescer(engine, window=30.0)

    async def run():
        first = submit_soon(co, item(1))
        await asyncio.sleep(0)  # enqueued; the watch has not run out yet
        co.note_view_flip()
        warm = await asyncio.wait_for(first, 5)
        # inside the warm span a lone submit is flushed with no watch at all
        return warm, await asyncio.wait_for(co.submit([item(2)]), 5)

    assert asyncio.run(run()) == ([True], [True])
    assert closes(co) == {"flip": 2}


def test_window_stats_snapshot_names_every_reason():
    stats = WindowStats()
    for reason in REASONS:
        stats.note(reason, 0.001)
    snap = stats.snapshot(0.005)
    assert [snap[r] for r in REASONS] == [1] * len(REASONS)
    assert snap["open_ms"] == pytest.approx(5.0)
    assert snap["window_s"] == 0.005


def test_verify_window_is_a_wait_of_the_account_with_its_count():
    engine = Engine()
    rec = TraceRecorder(clock=time.perf_counter, node="verify")
    co = AsyncBatchCoalescer(engine, window=0.5)
    co.attach_recorder(rec)
    t0 = time.perf_counter()

    async def run():
        for wave in range(3):
            futs = [submit_soon(co, item(wave * 10 + i)) for i in range(4)]
            await asyncio.wait_for(asyncio.gather(*futs), 5)

    asyncio.run(run())
    marks = [e for e in rec.events() if e.kind == "verify.window"]
    assert [e.launch for e in marks] == [1, 2, 3]
    assert [e.extra for e in marks] == [
        {"closed_by": "quiet", "items": 4, "submitters": 4}] * 3
    assert all(0.0 <= e.dur < 0.1 for e in marks)
    acc = assemble_account([rec], {}, t0=t0, t1=time.perf_counter(),
                           loop_cpu_s=1.0, loop_thread="MainThread")
    waits = acc["waits"]["verify.window"]
    assert len(waits) == 3 == len(engine.calls)
    assert all(0.0 <= ms < 100.0 for ms in waits)
    # a submitter's own wait covers its batch's time open
    assert len(acc["waits"]["verify.wait"]) == 12
    assert min(acc["waits"]["verify.wait"]) >= 0.0


def test_no_verify_window_wait_while_the_recorder_is_off():
    engine = Engine()
    co = AsyncBatchCoalescer(engine, window=0.5)
    assert asyncio.run(asyncio.wait_for(co.submit([item(1)]), 5)) == [True]
    assert not co.recorder.enabled and not co.recorder.events()
    assert closes(co) == {"quiet": 1}  # the counts are always on
