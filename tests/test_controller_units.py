"""Direct unit tests for the Controller: the sync ladder, message routing,
the pool-timeout chain handlers, and the deliver-vs-sync guard.

Mirrors /root/reference/internal/bft/controller_test.go — real Controller,
hand-rolled fakes for every collaborator (the reference uses mockery
doubles; support.go:13-70).
"""

from __future__ import annotations

import asyncio
import types
from typing import Optional

import pytest

from smartbft_tpu.codec import encode
from smartbft_tpu.core.controller import Controller, MutuallyExclusiveDeliver
from smartbft_tpu.core.util import InFlightData
from smartbft_tpu.core.view import ViewSequence, ViewSequencesHolder
from smartbft_tpu.messages import (
    Commit,
    HeartBeat,
    NewViewRecord,
    StateTransferRequest,
    StateTransferResponse,
    ViewMetadata,
)
from smartbft_tpu.types import (
    Checkpoint,
    Decision,
    Proposal,
    Reconfig,
    RequestInfo,
    SyncResponse,
    ViewAndSeq,
)
from smartbft_tpu.utils.logging import RecordingLogger


# ---------------------------------------------------------------- fakes


class FakeSynchronizer:
    def __init__(self, response: Optional[SyncResponse] = None):
        self.response = response or SyncResponse(
            latest=Decision(proposal=Proposal()),
            reconfig=Reconfig(in_latest_decision=False),
        )
        self.calls = 0

    def sync(self) -> SyncResponse:
        self.calls += 1
        return self.response


class FakeCollector:
    def __init__(self, response: Optional[ViewAndSeq] = None):
        self.response = response
        self.cleared = 0

    def clear_collected(self) -> None:
        self.cleared += 1

    async def collect_state_responses(self):
        return self.response

    def handle_message(self, sender, m):
        self.handled = (sender, m)


class FakeViewChanger:
    def __init__(self):
        self.informed: list[int] = []
        self.closed = False

    def inform_new_view(self, view: int) -> None:
        self.informed.append(view)

    def close(self) -> None:
        self.closed = True

    def handle_view_message(self, sender, m):
        pass

    def handle_message(self, sender, m):
        pass


class FakeState:
    def __init__(self):
        self.saved: list = []

    def save(self, record) -> None:
        self.saved.append(record)


class FakeComm:
    def __init__(self, nodes):
        self._nodes = nodes
        self.sent: list[tuple[int, object]] = []
        self.txs: list[tuple[int, bytes]] = []

    def send_consensus(self, target, m):
        self.sent.append((target, m))

    def send_transaction(self, target, req):
        self.txs.append((target, req))

    def nodes(self):
        return list(self._nodes)


class FakeVerifier:
    def __init__(self, vseq: int = 0):
        self.vseq = vseq
        self.bad: set[bytes] = set()

    def verification_sequence(self) -> int:
        return self.vseq

    def verify_request(self, raw):
        if raw in self.bad:
            raise ValueError("revoked")
        return RequestInfo(client_id="c", request_id=raw.decode())


class FakePool:
    def __init__(self):
        self.pruned = 0
        self.prune_removed: list[bytes] = []
        self.removed: list[RequestInfo] = []
        self.timers_restarted = 0
        self._requests = [b"a", b"b"]
        self.room = True

    def has_room(self) -> bool:
        return self.room

    def retry_after_hint(self) -> float:
        return 0.25

    def occupancy(self) -> dict:
        return {"size": 2}

    def prune(self, predicate) -> None:
        self.pruned += 1
        self.prune_removed = [r for r in self._requests if predicate(r) is not None]
        self._requests = [r for r in self._requests if predicate(r) is None]

    def remove_request(self, info) -> None:
        self.removed.append(info)

    def restart_timers(self, **kw) -> None:
        self.timers_restarted += 1

    def mark_in_flight(self, infos) -> None:
        pass

    def release_in_flight(self) -> None:
        pass


class FakeMonitor:
    def __init__(self):
        self.stopped_sends = 0
        self.heartbeats: list = []
        self.injected: list = []

    def stop_leader_send_msg(self):
        self.stopped_sends += 1

    def heartbeat_was_sent(self):
        self.heartbeats.append(1)

    def inject_artificial_heartbeat(self, sender, hb):
        self.injected.append((sender, hb))

    def process_msg(self, sender, m):
        self.processed = (sender, m)


class FakeFailureDetector:
    def __init__(self):
        self.complaints: list[tuple[int, bool]] = []

    def complain(self, view, stop_view):
        self.complaints.append((view, stop_view))


def make_controller(
    *,
    self_id=2,
    nodes=(1, 2, 3, 4),
    synchronizer=None,
    collector=None,
    checkpoint_md: Optional[ViewMetadata] = None,
    vseq=0,
):
    checkpoint = Checkpoint()
    if checkpoint_md is not None:
        checkpoint.set(
            Proposal(metadata=encode(checkpoint_md), verification_sequence=vseq), []
        )
    c = Controller(
        self_id=self_id,
        n=len(nodes),
        nodes_list=list(nodes),
        leader_rotation=False,
        decisions_per_leader=0,
        request_pool=FakePool(),
        batcher=None,
        leader_monitor=FakeMonitor(),
        verifier=FakeVerifier(vseq=vseq),
        logger=RecordingLogger("ctrl"),
        assembler=None,
        application=None,
        synchronizer=synchronizer or FakeSynchronizer(),
        signer=None,
        request_inspector=None,
        proposer_builder=None,
        checkpoint=checkpoint,
        failure_detector=FakeFailureDetector(),
        view_changer=FakeViewChanger(),
        collector=collector or FakeCollector(),
        state=FakeState(),
        in_flight=InFlightData(),
        comm=FakeComm(list(nodes)),
        view_sequences=ViewSequencesHolder(),
    )
    c.view_sequences.store(ViewSequence(view_active=True, proposal_seq=1))
    return c


def decision_with(view=0, seq=0, dec=0, vseq=0) -> Decision:
    md = ViewMetadata(view_id=view, latest_sequence=seq, decisions_in_view=dec)
    return Decision(
        proposal=Proposal(metadata=encode(md), verification_sequence=vseq),
        signatures=(),
    )


# ---------------------------------------------------------------- _sync ladder


def test_sync_learns_nothing_returns_zeros():
    """Empty sync + failed fetch-state -> (0,0,0) (controller.go:553-556)."""
    async def run():
        c = make_controller(collector=FakeCollector(response=None))
        assert await c._sync() == (0, 0, 0)
        assert c.collector.cleared == 1

    asyncio.run(run())


def test_sync_advances_checkpoint_on_higher_sequence():
    """latest_seq > controller seq adopts the decision (controller.go:539-547)."""
    async def run():
        sync = FakeSynchronizer(SyncResponse(
            latest=decision_with(view=0, seq=5, dec=2, vseq=7),
            reconfig=Reconfig(in_latest_decision=False),
        ))
        c = make_controller(synchronizer=sync, collector=FakeCollector(None))
        view, seq, dec = await c._sync()
        assert (view, seq, dec) == (0, 6, 3)  # seq+1, dec+1
        prop, _ = c.checkpoint.get()
        assert prop.verification_sequence == 7
        assert c.verification_sequence == 7

    asyncio.run(run())


def test_sync_adopts_higher_view_from_latest_metadata():
    async def run():
        sync = FakeSynchronizer(SyncResponse(
            latest=decision_with(view=3, seq=5),
            reconfig=Reconfig(in_latest_decision=False),
        ))
        c = make_controller(synchronizer=sync, collector=FakeCollector(None))
        view, seq, dec = await c._sync()
        assert view == 3 and seq == 6
        assert c.view_changer.informed == [3]  # controller.go:580-581

    asyncio.run(run())


def test_sync_fetch_state_adopts_collected_view():
    """Collected view > ours with seq == latest+1 saves a NewViewRecord and
    adopts the view (controller.go:560-575)."""
    async def run():
        sync = FakeSynchronizer(SyncResponse(
            latest=decision_with(view=1, seq=5, dec=1),
            reconfig=Reconfig(in_latest_decision=False),
        ))
        collector = FakeCollector(ViewAndSeq(view=4, seq=6))
        c = make_controller(synchronizer=sync, collector=collector)
        view, seq, dec = await c._sync()
        assert (view, seq, dec) == (4, 6, 0)
        assert len(c.state.saved) == 1
        rec = c.state.saved[0]
        assert isinstance(rec, NewViewRecord)
        assert rec.metadata.view_id == 4 and rec.metadata.latest_sequence == 5
        assert c.view_changer.informed == [4]

    asyncio.run(run())


def test_sync_stale_state_response_returns_zeros():
    """response.view <= ours and latest_view < ours -> nothing learned
    (controller.go:558-559)."""
    async def run():
        sync = FakeSynchronizer(SyncResponse(
            latest=decision_with(view=0, seq=0),
            reconfig=Reconfig(in_latest_decision=False),
        ))
        c = make_controller(synchronizer=sync, collector=FakeCollector(ViewAndSeq(view=1, seq=1)))
        c.curr_view_number = 2
        assert await c._sync() == (0, 0, 0)

    asyncio.run(run())


def test_sync_caught_up_keeps_decisions_in_view():
    """A sync that learns NOTHING new (latest == controller seq) on a node
    whose latest decision belongs to the current view must count the next
    decision as latest_dec + 1, not restart the view at 0 — the dec=0
    restart makes the node reject the leader's correct next proposal
    forever ("invalid decisions in view"), the wedge the socket
    kill-rejoin soak hit via wall-clock straggler syncs."""
    async def run():
        latest = decision_with(view=1, seq=8, dec=0)
        sync = FakeSynchronizer(SyncResponse(
            latest=latest, reconfig=Reconfig(in_latest_decision=False),
        ))
        c = make_controller(
            synchronizer=sync, collector=FakeCollector(None),
            checkpoint_md=ViewMetadata(view_id=1, latest_sequence=8,
                                       decisions_in_view=0),
        )
        c.curr_view_number = 1
        view, seq, dec = await c._sync()
        assert (view, seq, dec) == (1, 9, 1)

    asyncio.run(run())


def test_sync_caught_up_restarted_node_adopts_view_with_correct_dec():
    """Same caught-up shape but the controller restarted at a stale view:
    the ledger's last decision carries (view 1, dec 0) while the
    controller still thinks view 0 — adopting view 1 must land at
    dec = latest_dec + 1 so the node accepts the leader's next
    proposal."""
    async def run():
        latest = decision_with(view=1, seq=8, dec=0)
        sync = FakeSynchronizer(SyncResponse(
            latest=latest, reconfig=Reconfig(in_latest_decision=False),
        ))
        c = make_controller(
            synchronizer=sync, collector=FakeCollector(None),
            checkpoint_md=ViewMetadata(view_id=1, latest_sequence=8,
                                       decisions_in_view=0),
        )
        view, seq, dec = await c._sync()
        assert (view, seq, dec) == (1, 9, 1)
        assert c.view_changer.informed == [1]

    asyncio.run(run())


def test_sync_reconfig_closes_controller_and_viewchanger():
    async def run():
        sync = FakeSynchronizer(SyncResponse(
            latest=decision_with(view=0, seq=1),
            reconfig=Reconfig(in_latest_decision=True, current_nodes=(1, 2, 3)),
        ))
        c = make_controller(synchronizer=sync, collector=FakeCollector(None))
        await c._sync()
        assert c.stopped()
        assert c.view_changer.closed

    asyncio.run(run())


def test_sync_prunes_stale_in_flight():
    """Synced past the in-flight proposal -> cleared (controller.go:682-705)."""
    async def run():
        sync = FakeSynchronizer(SyncResponse(
            latest=decision_with(view=0, seq=5),
            reconfig=Reconfig(in_latest_decision=False),
        ))
        c = make_controller(synchronizer=sync, collector=FakeCollector(None))
        in_flight_md = ViewMetadata(view_id=0, latest_sequence=4)
        c.in_flight.store_proposal(Proposal(metadata=encode(in_flight_md)))
        await c._sync()
        assert c.in_flight.in_flight_proposal() is None

    asyncio.run(run())


def test_sync_keeps_fresh_in_flight():
    async def run():
        sync = FakeSynchronizer(SyncResponse(
            latest=decision_with(view=0, seq=5),
            reconfig=Reconfig(in_latest_decision=False),
        ))
        c = make_controller(synchronizer=sync, collector=FakeCollector(None))
        in_flight_md = ViewMetadata(view_id=0, latest_sequence=6)  # ahead of sync
        c.in_flight.store_proposal(Proposal(metadata=encode(in_flight_md)))
        await c._sync()
        assert c.in_flight.in_flight_proposal() is not None

    asyncio.run(run())


def test_sync_on_start_merges_higher_view_and_seq():
    """controller.go:763-778."""
    async def run():
        sync = FakeSynchronizer(SyncResponse(
            latest=decision_with(view=2, seq=9, dec=4),
            reconfig=Reconfig(in_latest_decision=False),
        ))
        c = make_controller(synchronizer=sync, collector=FakeCollector(None))
        view, seq, dec = await c._sync_on_start(1, 3, 1)
        assert (view, seq, dec) == (2, 10, 5)
        # nothing learned keeps the start values
        c2 = make_controller(collector=FakeCollector(None))
        assert await c2._sync_on_start(1, 3, 1) == (1, 3, 1)

    asyncio.run(run())


def test_reconfig_during_sync_prunes_revoked_requests():
    """Verification-sequence advance re-validates the pool
    (controller.go:733-746)."""
    c = make_controller()
    c.verifier.vseq = 1  # advanced vs controller's cached 0
    c.verifier.bad = {b"b"}
    c.maybe_prune_revoked_requests()
    assert c.verification_sequence == 1
    assert c.request_pool.pruned == 1
    assert c.request_pool.prune_removed == [b"b"]
    # unchanged sequence: no prune
    c.maybe_prune_revoked_requests()
    assert c.request_pool.pruned == 1


# ---------------------------------------------------------------- routing


def test_state_transfer_request_answered_with_current_state():
    c = make_controller(checkpoint_md=ViewMetadata(latest_sequence=7))
    c.curr_view_number = 2
    c.view_sequences.store(ViewSequence(view_active=True, proposal_seq=8))
    c.process_messages(3, StateTransferRequest())
    assert c.comm.sent == [(3, StateTransferResponse(view_num=2, sequence=8))]


def test_state_transfer_response_routed_to_collector():
    c = make_controller()
    resp = StateTransferResponse(view_num=1, sequence=2)
    c.process_messages(4, resp)
    assert c.collector.handled == (4, resp)


def test_heartbeat_routed_to_monitor():
    c = make_controller()
    hb = HeartBeat(view=0, seq=1)
    c.process_messages(1, hb)
    assert c.leader_monitor.processed == (1, hb)


def test_protocol_msg_from_leader_injects_artificial_heartbeat():
    """controller.go:330-332: leader traffic doubles as a heartbeat."""
    c = make_controller()  # static leader of view 0 is node 1
    commit = Commit(view=0, seq=3, digest="d")
    c.process_messages(1, commit)
    assert c.leader_monitor.injected == [(1, HeartBeat(view=0, seq=3))]
    c.process_messages(3, Commit(view=0, seq=3, digest="d"))  # non-leader
    assert len(c.leader_monitor.injected) == 1


# ---------------------------------------------------------------- timeout chain


def test_request_timeout_forwards_to_leader_when_follower():
    c = make_controller(self_id=2)  # leader is 1
    c.on_request_timeout(b"r", RequestInfo("c", "r"))
    assert c.comm.txs == [(1, b"r")]


def test_request_timeout_noop_when_leader():
    c = make_controller(self_id=1)
    c.on_request_timeout(b"r", RequestInfo("c", "r"))
    assert c.comm.txs == []


def test_leader_fwd_timeout_complains_when_follower():
    c = make_controller(self_id=2)
    c.curr_view_number = 4  # static leader of view 4 is node 1
    c.on_leader_fwd_request_timeout(b"r", RequestInfo("c", "r"))
    assert c.failure_detector.complaints == [(4, True)]


def test_leader_fwd_timeout_stops_suppression_when_leader():
    c = make_controller(self_id=1)
    c.on_leader_fwd_request_timeout(b"r", RequestInfo("c", "r"))
    assert c.leader_monitor.stopped_sends == 1
    assert c.failure_detector.complaints == []


def test_heartbeat_timeout_checks_reported_leader():
    c = make_controller(self_id=2)  # current leader: 1
    c.on_heartbeat_timeout(0, 3)  # stale report about another leader
    assert c.failure_detector.complaints == []
    c.on_heartbeat_timeout(0, 1)
    assert c.failure_detector.complaints == [(0, True)]
    # the leader itself never complains
    c2 = make_controller(self_id=1)
    c2.on_heartbeat_timeout(0, 1)
    assert c2.failure_detector.complaints == []


def test_broadcast_skips_self_and_signals_heartbeat():
    c = make_controller(self_id=1)  # leader
    c.broadcast_consensus(Commit(view=0, seq=1, digest="d"))
    assert sorted(t for t, _ in c.comm.sent) == [2, 3, 4]
    assert c.leader_monitor.heartbeats  # protocol msg as leader
    c.comm.sent.clear()
    c.broadcast_consensus(StateTransferRequest())
    assert len(c.leader_monitor.heartbeats) == 1  # non-protocol: no signal


# ---------------------------------------------------------------- deliver guard


def test_mutually_exclusive_deliver_defers_to_sync_result():
    """A view-change deliver that raced a completed sync adopts the sync's
    checkpoint instead of re-delivering (controller.go:928-965)."""
    async def run():
        sync_latest = decision_with(view=1, seq=9)
        sync = FakeSynchronizer(SyncResponse(
            latest=sync_latest, reconfig=Reconfig(in_latest_decision=False)
        ))
        c = make_controller(
            synchronizer=sync, checkpoint_md=ViewMetadata(latest_sequence=9)
        )
        deliver = MutuallyExclusiveDeliver(c)
        pending_md = ViewMetadata(view_id=1, latest_sequence=8)
        out = await deliver.deliver(Proposal(metadata=encode(pending_md)), [])
        assert sync.calls == 1
        prop, _ = c.checkpoint.get()
        assert prop == sync_latest.proposal
        assert not out.in_latest_decision

    asyncio.run(run())


def test_mutually_exclusive_deliver_delivers_fresh_decision():
    async def run():
        class App:
            def __init__(self):
                self.delivered = []

            def deliver(self, proposal, signatures):
                self.delivered.append(proposal)
                return Reconfig(in_latest_decision=False)

        c = make_controller(checkpoint_md=ViewMetadata(latest_sequence=3))
        app = App()
        c.application = app
        deliver = MutuallyExclusiveDeliver(c)
        md = ViewMetadata(view_id=0, latest_sequence=4)
        prop = Proposal(metadata=encode(md))
        await deliver.deliver(prop, [])
        assert app.delivered == [prop]
        got, _ = c.checkpoint.get()
        assert got == prop

    asyncio.run(run())


# ---------------------------------------------------------------- rotation


def test_check_if_rotate_detects_leader_change():
    c = make_controller()
    c.leader_rotation = True
    c.decisions_per_leader = 1
    c.curr_decisions_in_view = 1  # decision 0 -> leader 1; decision 1 -> leader 2
    # the answer is the node whose turn ended: the one that hands its
    # pool over (Controller._decide)
    assert c._check_if_rotate([]) == 1
    c.decisions_per_leader = 10  # same leader for both
    assert c._check_if_rotate([]) == 0


@pytest.mark.parametrize("traced", [False, True], ids=["off", "traced"])
def test_forward_to_a_replica_that_does_not_lead_is_dropped_and_counted(traced):
    """handle_request's drop branch: counted always, marked when a
    recorder is on, and nothing reaches the pool; the leader's own branch
    counts nothing."""
    from smartbft_tpu.core.pool import AdmissionRejected
    from smartbft_tpu.obs import TraceRecorder

    async def run():
        c = make_controller(self_id=2)  # view 0: node 1 leads
        c.recorder = TraceRecorder(node="n2", enabled=traced)
        c.request_inspector = types.SimpleNamespace(
            request_id=c.verifier.verify_request)
        submitted = []

        async def submit(raw, *, forwarded=False):
            submitted.append((raw, forwarded))

        c.request_pool.submit = submit
        assert await c.handle_request(3, b"early") is None
        assert await c.handle_request(4, b"early-too") is None
        assert c.not_leader_forwards == 2 and submitted == []
        marks = [e for e in c.recorder.events() if e.kind == "req.not_leader"]
        assert [(e.key, e.extra) for e in marks] == (
            [("c:early", {"sender": 3}), ("c:early-too", {"sender": 4})]
            if traced else [])
        c.id = 1  # the same forward at the node that leads is pooled
        assert await c.handle_request(3, b"early") is None
        assert c.not_leader_forwards == 2
        assert submitted == [(b"early", True)]
        # ... unless it would have to park on space: this is the node's
        # inbox task, which the commits that free the space come through,
        # so it is refused at once (the forwarder keeps its copy)
        c.request_pool.room = False
        shed = await c.handle_request(3, b"later")
        assert isinstance(shed, AdmissionRejected)
        assert shed.retry_after == 0.25 and shed.occupancy == {"size": 2}
        assert submitted == [(b"early", True)]

    asyncio.run(run())
