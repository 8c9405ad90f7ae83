"""The generator: the closed loop keeps exactly ``clients`` in flight, the
open loop never skips backlog and times each request from when it was
due."""

import asyncio
import random
import time
from types import SimpleNamespace

import pytest

from chipbench.load import LoadLoop, OpenLoopPump, ZipfClients, find_knee


class FakeCluster:
    """Commits what was submitted, ``per_poll`` requests a poll, in order;
    checks the closed loop's invariant at every step."""

    def __init__(self, per_poll=7, fail_every=0, stall_at=None):
        self.pending = []
        self.per_poll = per_poll
        self.submitted = 0
        self.committed = 0
        self.peak = 0
        self.fail_every = fail_every
        self.stall_at = stall_at
        self.polls = 0
        self.outstanding_at_poll = []

    async def submit(self, client, rid):
        self.submitted += 1
        if self.fail_every and self.submitted % self.fail_every == 0:
            raise RuntimeError("no leader")
        self.pending.append(f"{client}:{rid}")
        self.peak = max(self.peak, len(self.pending))

    def poll(self):
        self.polls += 1
        if self.stall_at == self.polls:
            time.sleep(0.15)  # the caller's loop stalls; arrivals are due
        self.outstanding_at_poll.append(len(self.pending))
        out, self.pending = (self.pending[:self.per_poll],
                             self.pending[self.per_poll:])
        self.committed += len(out)
        return [SimpleNamespace(request_ids=out)] if out else []


def spec(**kw):
    return dict({"loop": "closed", "clients": 25, "client_skew": 0,
                 "poll_ms": 1, "drain_s": 5}, **kw)


def test_closed_loop_keeps_exactly_clients_in_flight():
    cluster = FakeCluster(per_poll=7)
    loop = LoadLoop(cluster, spec(), seed=11)
    asyncio.run(loop.run(0.05, 0.3))
    # after the first submit round every poll finds exactly `clients`
    # outstanding: one request per client, never more, never fewer
    assert cluster.outstanding_at_poll[0] == 0
    t0, t1 = loop.window
    during = cluster.outstanding_at_poll[1:]
    assert cluster.peak == 25 and loop.peak_inflight == 25
    assert max(during) == 25
    # until the window closes nothing but 25; then the drain empties it
    closed = [n for n in during if n != 25]
    assert closed == sorted(closed, reverse=True), "only the drain shrinks"
    assert not loop.inflight and loop.never_committed() == 0
    assert cluster.committed == cluster.submitted == len(loop.commits)
    # each client's requests are committed in its own order, one at a time
    per_client = {}
    for key in loop.committed_keys:
        client, rid = key.split(":")
        assert int(rid[1:]) == per_client.get(client, 0)
        per_client[client] = int(rid[1:]) + 1
    assert len(per_client) == 25
    # the window holds only commits stamped inside it
    assert all(t0 <= c[0] < t1 for c in loop.window_commits())
    assert 0.29 <= t1 - t0 <= 0.5
    assert loop.attempted == sum(1 for c in loop.commits if c[2])


def test_same_seed_same_clients_other_seed_same_set_in_other_order():
    a = LoadLoop(FakeCluster(), spec(), seed=2 ** 31 + 11)
    b = LoadLoop(FakeCluster(), spec(), seed=2 ** 31 + 11)
    c = LoadLoop(FakeCluster(), spec(), seed=5)
    assert a._ready == b._ready
    assert len(set(c._ready)) == len(set(a._ready)) == 25


def test_failed_submits_are_counted_and_the_client_goes_on():
    cluster = FakeCluster(per_poll=50, fail_every=10)
    loop = LoadLoop(cluster, spec(clients=10), seed=3)
    asyncio.run(loop.run(0.0, 0.2))
    assert loop.errored > 0 and loop.shed == 0
    assert loop.failed_submits == loop.errored
    assert "no leader" in loop.error_samples[0]
    assert cluster.committed == len(loop.commits) > 10


def test_pump_never_skips_backlog():
    rate = 1000.0
    one = OpenLoopPump(rate, random.Random(5), start=0.0)
    two = OpenLoopPump(rate, random.Random(5), start=0.0)
    # a caller that stalls for a whole second gets every missed arrival,
    # each with its own due time, exactly as a caller that never stalled
    stalled = one.due_times(1.0)
    stepped = []
    for k in range(1, 101):
        stepped += two.due_times(k / 100.0)
    assert stalled == stepped
    assert 850 < len(stalled) < 1150
    assert stalled == sorted(stalled) and stalled[-1] <= 1.0
    assert one.due_times(1.0) == []  # and hands each out once


def test_open_loop_times_from_due_and_reports_lateness():
    cluster = FakeCluster(per_poll=10_000, stall_at=40)
    loop = LoadLoop(cluster, spec(loop="open", rate_per_s=2000,
                                  clients=64, client_skew=1.1), seed=9)
    asyncio.run(loop.run(0.02, 0.4))
    assert len(loop.lateness) == cluster.submitted
    # the stall made the generator late, and it says so
    assert max(loop.lateness) >= 0.1
    # nothing due during the stall was dropped: about rate x time arrived
    t0, t1 = loop.window
    assert loop.attempted == pytest.approx(2000 * (t1 - t0), rel=0.25)
    # latency counts from the due time, so it includes the generator's
    # lateness: the requests due during the stall waited at least that long
    assert max(c[1] for c in loop.commits) >= 0.1
    assert all(lat >= 0 for _, lat, _ in loop.commits)


def test_zipf_skew_and_uniform():
    rng = random.Random(1)
    hot = ZipfClients(512, 1.1, prefix="h")
    draws = [hot.sample(rng) for _ in range(4000)]
    assert 0.10 < draws.count("h0") / 4000 < 0.20  # the hottest: ~14 %
    flat = ZipfClients(10, 0.0)
    assert {flat.sample(rng) for _ in range(500)} == {
        f"zipf{i}" for i in range(10)}


def test_find_knee():
    rows = [
        {"offered_per_s": 100, "goodput_per_s": 99, "shed_share": 0.0},
        {"offered_per_s": 200, "goodput_per_s": 195, "shed_share": 0.0},
        {"offered_per_s": 400, "goodput_per_s": 260, "shed_share": 0.0},
        {"offered_per_s": 800, "goodput_per_s": 700, "shed_share": 0.3},
    ]
    knee = find_knee(rows)
    assert knee["last_ok"]["offered_per_s"] == 200
    assert knee["first_overloaded"]["offered_per_s"] == 400
    assert not knee["beyond_sweep"]
    assert find_knee(rows[:2])["beyond_sweep"]
