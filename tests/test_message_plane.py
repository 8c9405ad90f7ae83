"""Vectorized message plane: call-count gates + satellite regressions.

Everything here is COUNT-based (never wall-clock), so the gates stay green
in CI regardless of host weather:

- encode-once broadcast: exactly 1 ``codec`` encode + <=1 decode per
  broadcast on the in-process network at n=8;
- wave-batched ingest: a full prepare wave registers through ONE
  ``ingest_batch`` call / ONE ``handle_message_batch`` dispatch;
- deep-window launch amortization (k in {16, 32}): launches << decisions
  through a shared coalescer under the full protocol;
- copy-on-write corruption: mutating one recipient's message can never
  leak into another replica's ingest (broadcasts share one decoded
  object);
- bounded intern/decode memos: a Byzantine flood of unique messages
  cannot grow memo memory without limit (LRU eviction, counted);
- BLS cross-replica dedupe: two replicas aggregating the same decision
  produce byte-identical canonical verify items.
"""

import asyncio
import dataclasses
import os

import pytest

from smartbft_tpu.codec import encode
from smartbft_tpu.core.util import SignerIndex, VoteSet, iter_bits
from smartbft_tpu.messages import (
    Commit,
    HeartBeat,
    Prepare,
    PrePrepare,
    Proposal,
    Signature,
    ViewMetadata,
    deep_copy_message,
    intern_memo_len,
    unmarshal_interned,
    wire_of,
)
from smartbft_tpu.messages import INTERN_MEMO_BOUND, marshal
from smartbft_tpu.metrics import PROTOCOL_PLANE
from smartbft_tpu.testing.app import App, SharedLedgers, fast_config, wait_for
from smartbft_tpu.testing.network import Network
from smartbft_tpu.utils.clock import Scheduler
from smartbft_tpu.utils.memo import LruMemo


class Sink:
    """Recording stub consensus: counts batch dispatches and messages."""

    def __init__(self):
        self.batches = []
        self.messages = []

    def handle_message(self, sender, msg):
        self.messages.append((sender, msg))

    def handle_message_batch(self, items):
        self.batches.append(list(items))
        self.messages.extend(items)

    async def handle_request(self, sender, req):
        pass


def _mesh(n: int):
    net = Network(seed=3)
    sinks = {}
    for i in range(1, n + 1):
        node = net.add_node(i)
        node.consensus = sinks[i] = Sink()
    net.start()
    return net, sinks


async def _drain(net, sinks, want_total: int):
    for _ in range(2000):
        if sum(len(s.messages) for s in sinks.values()) >= want_total:
            return
        await asyncio.sleep(0.001)
    raise AssertionError(
        f"only {sum(len(s.messages) for s in sinks.values())} of "
        f"{want_total} messages arrived"
    )


# -- encode-once broadcast ----------------------------------------------------

def test_broadcast_encodes_exactly_once_n8():
    """The tier-1 call-count gate: ONE encode and at most one decode for a
    fresh message broadcast to 7 peers."""

    async def run():
        net, sinks = _mesh(8)
        before = PROTOCOL_PLANE.snapshot()
        net.broadcast_consensus(1, Prepare(view=0, seq=1, digest="gate-d1"))
        await _drain(net, sinks, 7)
        after = PROTOCOL_PLANE.snapshot()
        await net.stop()
        assert after["broadcasts"] - before["broadcasts"] == 1
        assert after["encodes"] - before["encodes"] == 1
        assert after["decodes"] - before["decodes"] <= 1
        # the other 6 recipients were served by the intern memo
        assert after["decode_interned_hits"] - before["decode_interned_hits"] >= 6
        # every recipient got an equal message, all sharing ONE object
        got = [s.messages[0][1] for i, s in sinks.items() if i != 1]
        assert len(got) == 7
        assert all(m.digest == "gate-d1" for m in got)
        assert all(m is got[0] for m in got)

    asyncio.run(run())


def test_rebroadcast_reuses_the_wire_memo():
    """Re-broadcasting the same message object (view re-entry, assist
    resends) encodes ZERO additional times."""

    async def run():
        net, sinks = _mesh(4)
        m = Prepare(view=0, seq=3, digest="memo-d")
        net.broadcast_consensus(1, m)
        await _drain(net, sinks, 3)
        before = PROTOCOL_PLANE.snapshot()
        net.broadcast_consensus(1, m)
        await _drain(net, sinks, 6)
        after = PROTOCOL_PLANE.snapshot()
        await net.stop()
        assert after["encodes"] - before["encodes"] == 0
        assert after["encode_memo_hits"] - before["encode_memo_hits"] >= 1

    asyncio.run(run())


# -- wave-batched ingest ------------------------------------------------------

def test_full_prepare_wave_dispatches_in_one_batch_call():
    """7 prepares from 7 senders queued in one tick reach the consensus
    through ONE handle_message_batch call."""

    async def run():
        net, sinks = _mesh(8)
        # enqueue the whole wave before the receiver's serve task runs
        for sender in range(2, 8 + 1):
            net.send_consensus(sender, 1, Prepare(view=0, seq=4, digest="w"))
        await _drain(net, sinks, 7)
        await net.stop()
        sink = sinks[1]
        assert len(sink.messages) == 7
        assert len(sink.batches) == 1, [len(b) for b in sink.batches]
        assert len(sink.batches[0]) == 7

    asyncio.run(run())


def test_windowed_view_ingest_batch_registers_wave_in_one_call(tmp_path):
    """WindowedView.ingest_batch registers a whole prepare wave (one call,
    one work wakeup) into the slot's bitmask vote set."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parent / "test_pipeline.py"
    spec = importlib.util.spec_from_file_location("tp_helpers", path)
    tp = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tp)

    v = tp.make_wview(self_id=2, leader_id=1, proposal_sequence=1, window=4)
    md = encode(ViewMetadata(view_id=0, latest_sequence=1, decisions_in_view=0))
    pp = PrePrepare(view=0, seq=1, proposal=Proposal(payload=b"b", metadata=md))
    digest = __import__("smartbft_tpu.types", fromlist=["proposal_digest"]) \
        .proposal_digest(pp.proposal)
    wave = [(s, Prepare(view=0, seq=1, digest=digest)) for s in (1, 3, 4)]
    v.ingest_batch([(1, pp)] + wave)
    slot = v.slots[1]
    # the whole wave (senders 1,3,4 minus self=2) registered in one call
    assert len(slot.prepares) == 3
    assert slot.pre_prepare is pp
    # bitmask semantics: popcount len + per-signer payloads, no objects
    assert slot.prepares.mask.bit_count() == 3
    assert [slot.prepares.signer_id(i) for i in iter_bits(slot.prepares.mask)] \
        == [1, 3, 4]


# -- deep-window launch amortization (k in {16, 32}) --------------------------

@pytest.mark.parametrize("depth", [16, 32])
def test_launches_much_fewer_than_decisions_deep_windows(tmp_path, depth):
    """Count-based k-table gate: a 16-decision burst through a shared
    coalescer at k in {16,32} must launch FAR fewer waves than decisions
    (the PERF.md table's invariant, weather-proof form)."""

    async def run():
        from smartbft_tpu.crypto.provider import (
            AsyncBatchCoalescer, HostVerifyEngine, Keyring, P256CryptoProvider,
        )

        scheduler = Scheduler()
        network = Network(seed=17)
        shared = SharedLedgers()
        node_ids = [1, 2, 3, 4]
        rings = Keyring.generate(node_ids, seed=b"kgate")
        engine = HostVerifyEngine()
        coalescer = AsyncBatchCoalescer(engine, window=0.02, max_batch=4096,
                                        dedupe=True)
        cfg = lambda i: dataclasses.replace(
            fast_config(i), leader_rotation=False, decisions_per_leader=0,
            pipeline_depth=depth, request_batch_max_count=2,
            request_batch_max_interval=0.02,
        )
        apps = [
            App(i, network, shared, scheduler,
                wal_dir=os.path.join(str(tmp_path), f"wal-{depth}-{i}"),
                config=cfg(i),
                crypto=P256CryptoProvider(rings[i], coalescer=coalescer))
            for i in node_ids
        ]
        for a in apps:
            await a.start()
        total = 32  # 16 decisions at batch 2

        def committed(a):
            return sum(len(a.requests_from_proposal(d.proposal)) for d in a.ledger())

        for k in range(total):
            await apps[0].submit("c", f"r{k}")
        await wait_for(lambda: all(committed(a) >= total for a in apps),
                       scheduler, 240.0)
        decisions = len(apps[0].ledger())
        launches = engine.stats.launches
        for a in apps:
            await a.stop()
        assert decisions >= 8
        # "much fewer": at most a quarter — the measured table reaches
        # ceil(D/k) (1-2 here); the slack absorbs host preemption splits
        assert launches <= max(1, decisions // 4), (launches, decisions)

    asyncio.run(run())


# -- copy-on-write corruption -------------------------------------------------

def test_corruption_of_one_recipient_cannot_leak_to_others():
    """Broadcasts share ONE decoded object; the mutate hook gets a deep
    copy, so even an IN-PLACE mutation corrupts only the targeted link."""

    async def run():
        net, sinks = _mesh(4)
        original = Prepare(view=0, seq=9, digest="pristine")

        def corrupt_for_2(target, msg):
            if target == 2:
                # worst-case hook: in-place mutation of a frozen message
                object.__setattr__(msg, "digest", "corrupted")
            return msg

        net.nodes[1].mutate_send = corrupt_for_2
        net.broadcast_consensus(1, original)
        await _drain(net, sinks, 3)
        await net.stop()
        assert sinks[2].messages[0][1].digest == "corrupted"
        assert sinks[3].messages[0][1].digest == "pristine"
        assert sinks[4].messages[0][1].digest == "pristine"
        # the sender's original is untouched (copy-on-write)
        assert original.digest == "pristine"

    asyncio.run(run())


def test_deep_copy_message_is_independent_and_memo_free():
    pp = PrePrepare(view=1, seq=2, proposal=Proposal(payload=b"p"))
    wire_of(pp)  # populate the wire memo on the original
    cp = deep_copy_message(pp)
    assert cp == pp and cp is not pp and cp.proposal is not pp.proposal
    assert getattr(cp, "_wire_memo", None) is None
    assert getattr(cp, "_digest_memo", None) is None


# -- bounded memos ------------------------------------------------------------

def test_byzantine_flood_of_unique_messages_bounds_intern_memo():
    """A flood of distinct wire payloads (unique-digest prepares) must not
    grow the intern memo past its LRU bound; evictions are counted."""
    before = PROTOCOL_PLANE.snapshot()
    flood = INTERN_MEMO_BOUND + 500
    for i in range(flood):
        unmarshal_interned(marshal(Prepare(view=0, seq=i, digest=f"u{i}")))
    after = PROTOCOL_PLANE.snapshot()
    assert intern_memo_len() <= INTERN_MEMO_BOUND
    assert after["intern_evictions"] - before["intern_evictions"] >= 500


def test_sig_msg_decode_memo_is_lru_bounded():
    """The consenter sig-msg decode memo evicts one-at-a-time under a
    unique-message flood (bounded memory, honest entries keep hitting)."""
    from smartbft_tpu.crypto.provider import Keyring, P256CryptoProvider

    rings = Keyring.generate([1, 2], seed=b"memo")
    provider = P256CryptoProvider(rings[1])
    memo = provider._sig_msg_memo
    assert isinstance(memo, LruMemo)
    bound = memo.bound
    for i in range(bound + 64):
        memo.get_or(b"junk-%d" % i, lambda: object())
    assert len(memo) <= bound
    assert memo.evictions >= 64


def test_lru_memo_keeps_recently_used_entries():
    memo = LruMemo(bound=2)
    memo.put("a", 1)
    memo.put("b", 2)
    assert memo.get("a") == 1  # refresh 'a'
    memo.put("c", 3)           # evicts 'b' (least recently used)
    assert memo.get("b") is None
    assert memo.get("a") == 1 and memo.get("c") == 3
    assert memo.evictions == 1


# -- BLS cross-replica canonical aggregation ----------------------------------

def test_bls_two_replicas_aggregate_byte_identical_items():
    """Two replicas holding the same decision's votes (in different orders,
    one with an extra vote) must produce BYTE-IDENTICAL canonical aggregate
    items — the precondition for cross-replica dedupe in the shared
    coalescer (PERF.md round-5 row [4]'s named lever)."""
    from smartbft_tpu import crypto
    from smartbft_tpu.crypto import bls12381
    from smartbft_tpu.crypto.provider import BlsCryptoProvider, Keyring

    node_ids = [1, 2, 3, 4]
    rings = Keyring.generate(node_ids, seed=b"blsdedupe", scheme=bls12381)

    class LaneRecorder:
        def __init__(self):
            self.calls = []

        def verify(self, items):
            self.calls.append(list(items))
            return [True] * len(items)

    prov_a = BlsCryptoProvider(rings[1], engine=LaneRecorder())
    prov_b = BlsCryptoProvider(rings[2], engine=LaneRecorder())

    proposal = Proposal(payload=b"decision", metadata=b"")
    sigs = {
        i: BlsCryptoProvider(rings[i], engine=LaneRecorder()).sign_proposal(
            proposal, b"aux-%d" % i
        )
        for i in node_ids
    }
    # same collected votes, different arrival orders (extras ABOVE the
    # canonical subset do not perturb it: {2,3} stays the lowest pair)
    batch_a = [sigs[2], sigs[3]]
    batch_b = [sigs[4], sigs[3], sigs[2]]

    res_a = prov_a.verify_consenter_sigs_batch(batch_a, proposal)
    res_b = prov_b.verify_consenter_sigs_batch(batch_b, proposal)
    assert all(r is not None for r in res_a)
    assert all(r is not None for r in res_b)

    lane_a = prov_a.engine.calls[0][0]
    lane_b = prov_b.engine.calls[0][0]
    # n=4 -> quorum 3 -> canonical subset = lowest 2 signer ids present:
    # {2,3} for both replicas despite order/extras -> identical bytes
    assert lane_a == lane_b
    assert isinstance(lane_a[1], bytes) and isinstance(lane_a[2], bytes)


# -- bitmask vote set ---------------------------------------------------------

def test_vote_set_bitmask_popcount_and_payload_arrays():
    index = SignerIndex([1, 2, 3, 4])
    vs = VoteSet(lambda _s, m: isinstance(m, Prepare), index)
    assert vs.register_vote(3, Prepare(view=0, seq=1, digest="d")) is not None
    assert vs.register_vote(3, Prepare(view=0, seq=1, digest="d")) is None
    assert vs.register_vote(9, Prepare(view=0, seq=1, digest="d")) is None
    assert vs.register_vote(1, Prepare(view=0, seq=1, digest="e")) is not None
    assert len(vs) == 2 and vs.mask == 0b101
    assert vs.payloads[index.index_of(1)].digest == "e"
    assert [s for s, _ in vs.items()] == [1, 3]
    assert 3 in vs.voted and 2 not in vs.voted
    vs.clear()
    assert len(vs) == 0 and vs.mask == 0


def test_vote_set_dynamic_mode_preserves_arrival_order():
    vs = VoteSet(lambda _s, m: True)
    vs.register_vote(7, HeartBeat(view=1))
    vs.register_vote(2, HeartBeat(view=2))
    assert [v.sender for v in vs.votes] == [7, 2]
    assert len(vs.voted) == 2


# -- the plane's counters under the full protocol ------------------------------

def test_cluster_run_moves_the_protocol_plane_counters(tmp_path):
    """A whole n=4 cluster run (``benchmarks/throughput.py:run_cluster``,
    the run behind ``chip_smoke.py``'s cluster phase) moves every term of
    the process plane, with the structural invariants intact."""
    import importlib.util
    import pathlib

    from smartbft_tpu.metrics import ProtocolPlaneTimers

    path = pathlib.Path(__file__).resolve().parent.parent / "benchmarks" / "throughput.py"
    spec = importlib.util.spec_from_file_location("bench_throughput_pp", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    before = PROTOCOL_PLANE.snapshot()
    asyncio.run(mod.run_cluster("host", 4, 4, 2, (8,), scheme_name="p256"))
    plane = ProtocolPlaneTimers.delta(before, PROTOCOL_PLANE.snapshot())
    for key in ("ingest_us", "route_us", "vote_reg_us", "codec_us",
                "broadcasts", "encodes", "decodes", "decode_interned_hits",
                "intern_evictions", "batch_ingests", "msgs_ingested"):
        assert key in plane, plane
    assert plane["broadcasts"] > 0
    # the structural invariant: at most one encode per broadcast
    assert plane["encodes"] <= plane["broadcasts"]
    assert plane["decodes"] <= plane["encodes"]
    assert plane["ingest_us"] > 0 and plane["route_us"] > 0
