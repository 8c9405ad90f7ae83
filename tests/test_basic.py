"""Multi-replica integration tests on the in-process network.

Modeled on /root/reference/test/basic_test.go (TestBasic and friends): N full
Consensus instances in one process connected by the channel mesh, trivial
crypto, logical-time scheduler driven in lockstep with the asyncio loop.
"""

import asyncio

import pytest

from smartbft_tpu.testing.app import App, SharedLedgers, fast_config, wait_for
from smartbft_tpu.testing.network import Network
from smartbft_tpu.utils.clock import Scheduler


def make_nodes(n, tmp_path, scheduler=None, network=None, shared=None, config_fn=None):
    scheduler = scheduler or Scheduler()
    network = network or Network(seed=1)
    shared = shared or SharedLedgers()
    apps = []
    for i in range(1, n + 1):
        cfg = config_fn(i) if config_fn else fast_config(i)
        app = App(
            i, network, shared, scheduler,
            wal_dir=str(tmp_path / f"wal-{i}"), config=cfg,
        )
        apps.append(app)
    return apps, scheduler, network, shared


async def start_all(apps):
    for app in apps:
        await app.start()


async def stop_all(apps):
    for app in apps:
        await app.stop()


def test_basic_4_nodes(tmp_path):
    """TestBasic (basic_test.go:32-61): submit one request, all nodes commit."""

    async def run():
        apps, scheduler, network, shared = make_nodes(4, tmp_path)
        await start_all(apps)
        await apps[0].submit("client-a", "req-1", b"payload")
        await wait_for(lambda: all(a.height() >= 1 for a in apps), scheduler)
        for app in apps:
            ledger = app.ledger()
            infos = app.requests_from_proposal(ledger[0].proposal)
            assert [str(i) for i in infos] == ["client-a:req-1"]
        await stop_all(apps)

    asyncio.run(run())


def test_many_requests_batching(tmp_path):
    """Requests accumulate into batches; all nodes converge on same ledger."""

    async def run():
        apps, scheduler, network, shared = make_nodes(4, tmp_path)
        await start_all(apps)
        total = 50
        for k in range(total):
            await apps[0].submit("client-a", f"req-{k}")
        await wait_for(
            lambda: all(
                sum(len(a.requests_from_proposal(d.proposal)) for d in a.ledger()) == total
                for a in apps
            ),
            scheduler,
            timeout=60.0,
        )
        # ledgers byte-identical across nodes
        ref = [d.proposal for d in apps[0].ledger()]
        for app in apps[1:]:
            assert [d.proposal for d in app.ledger()] == ref
        await stop_all(apps)

    asyncio.run(run())


def test_request_forwarded_to_leader(tmp_path):
    """A request submitted at a follower reaches the leader via the forward
    timeout (basic_test.go RequestForward scenarios)."""

    async def run():
        apps, scheduler, network, shared = make_nodes(4, tmp_path)
        await start_all(apps)
        # node 2 is a follower (leader of view 0 is node 1)
        await apps[1].submit("client-b", "req-fwd")
        await wait_for(lambda: all(a.height() >= 1 for a in apps), scheduler, timeout=60.0)
        infos = apps[0].requests_from_proposal(apps[0].ledger()[0].proposal)
        assert [str(i) for i in infos] == ["client-b:req-fwd"]
        await stop_all(apps)

    asyncio.run(run())


def test_restart_follower_catches_up(tmp_path):
    """Crash-restart a follower; it recovers from its WAL and continues."""

    async def run():
        apps, scheduler, network, shared = make_nodes(4, tmp_path)
        await start_all(apps)
        await apps[0].submit("c", "r1")
        await wait_for(lambda: all(a.height() >= 1 for a in apps), scheduler)
        # restart follower node 4
        await apps[3].restart()
        await apps[0].submit("c", "r2")
        await wait_for(lambda: all(a.height() >= 2 for a in apps), scheduler, timeout=60.0)
        assert [d.proposal for d in apps[3].ledger()] == [
            d.proposal for d in apps[0].ledger()
        ]
        await stop_all(apps)

    asyncio.run(run())


def test_leader_rotation(tmp_path):
    """With rotation on, leadership moves between nodes across decisions
    (basic_test.go rotation scenarios)."""

    async def run():
        def rot_config(i):
            import dataclasses

            return dataclasses.replace(
                fast_config(i), leader_rotation=True, decisions_per_leader=2
            )

        apps, scheduler, network, shared = make_nodes(4, tmp_path, config_fn=rot_config)
        await start_all(apps)
        leaders = set()
        for k in range(8):
            await apps[0].submit("c", f"r{k}")
            await wait_for(
                lambda k=k: all(a.height() >= k + 1 for a in apps), scheduler, timeout=60.0
            )
            leaders.add(apps[0].consensus.get_leader_id())
        assert len(leaders) >= 2, f"leadership never rotated: {leaders}"
        await stop_all(apps)

    asyncio.run(run())


@pytest.mark.parametrize("rotation", [True, False], ids=["rotation", "static"])
def test_open_loop_arrivals_are_handed_over_with_the_lead(tmp_path, rotation):
    """Poisson arrivals that follow the leader, under ``Configuration()``'s
    rotation (every 3 decisions, forward timeout 2 s): what reaches a
    leader after the last batch of its turn was cut is still in its pool
    when the lead passes on.  It goes to the new leader with the lead
    (Controller._decide -> Pool.restart_timers(handover=True)) instead of
    waiting until the lead comes round again (on the parent tree the same
    drive's slowest request waits 2.2 s; here 0.07 s).  The rate keeps the
    leftovers under one batch, the hand-over's budget: a deeper backlog is
    capacity's, and stays."""
    import dataclasses
    import random

    from smartbft_tpu.testing.load import OpenLoopPump

    rate, span, step, batch, per_leader = 500.0, 3.0, 0.01, 10, 3
    turn = per_leader * batch / rate  # three batches at the offered rate

    def config(i):
        return dataclasses.replace(
            fast_config(i), leader_rotation=rotation,
            decisions_per_leader=per_leader if rotation else 0,
            request_forward_timeout=2.0, request_complain_timeout=20.0)

    async def run():
        apps, scheduler, network, shared = make_nodes(4, tmp_path, config_fn=config)
        await start_all(apps)
        pump = OpenLoopPump(rate, random.Random(7), start=scheduler.now())
        due, latency, submits, seen = {}, {}, [], 0
        t_end = scheduler.now() + span
        while scheduler.now() < t_end or len(latency) < len(due):
            assert scheduler.now() < t_end + 30.0, "requests never committed"
            if scheduler.now() < t_end:
                for _ in range(pump.due(scheduler.now())):
                    rid = f"r{len(due)}"
                    due[f"c:{rid}"] = scheduler.now()
                    lead = apps[0].consensus.get_leader_id()
                    submits.append(asyncio.ensure_future(
                        apps[lead - 1].submit("c", rid)))
            scheduler.advance_by(step)
            # the loop runs dry within 20 turns (nothing here leaves it:
            # blocking WAL, in-process network), so the logical clock
            # never outruns the protocol, whatever the machine's load
            for _ in range(60):
                await asyncio.sleep(0)
            ledger = apps[0].ledger()
            for d in ledger[seen:]:
                for info in apps[0].requests_from_proposal(d.proposal):
                    latency[str(info)] = scheduler.now() - due[str(info)]
            seen = len(ledger)
        await asyncio.gather(*submits)
        await wait_for(lambda: len({a.height() for a in apps}) == 1, scheduler)

        # every request on all four ledgers exactly once, in one order
        for a in apps:
            ids = [str(i) for d in a.ledger()
                   for i in a.requests_from_proposal(d.proposal)]
            assert sorted(ids) == sorted(due), f"node {a.id}"
            assert [d.proposal for d in a.ledger()] == \
                [d.proposal for d in apps[0].ledger()]
        handed = [a.consensus.pool.occupancy()["handovers"] for a in apps]
        early = [a.consensus.controller.not_leader_forwards for a in apps]
        assert [a.consensus.pool.flip_drains for a in apps] == [0] * 4
        if rotation:
            turns = apps[0].height() // per_leader
            # every replica handed over, on average more than one request
            # a hand-over; none came before the new leader led (lockstep:
            # all four deliver before the clock reaches the floor)
            assert min(handed) > 0 and sum(handed) > turns, (handed, turns)
            assert early == [0] * 4
            assert max(latency.values()) <= turn + 0.1, max(latency.values())
        else:
            assert handed == [0] * 4 and early == [0] * 4
        await stop_all(apps)

    asyncio.run(run())
