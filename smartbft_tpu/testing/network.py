"""In-process network simulator with fault injection.

Re-design of /root/reference/test/network.go:18-252: a map of node id ->
Node, each with a bounded inbox drained by its own asyncio task.  Faults are
injectable per node and per peer: probabilistic message loss, message
mutation hooks, full disconnects, and drop-on-overflow.

**Vectorized message plane.**  Messages travel as wire BYTES (the canonical
tagged codec — what any real transport carries), but the plane is
vectorized so fan-out costs O(1) codec work instead of O(n):

* **Encode-once broadcast** — ``broadcast_consensus`` encodes the message
  once (``messages.wire_of``, memoized on the frozen instance) and enqueues
  the same bytes at every recipient;
* **Interned decode** — delivery decodes through a bounded LRU keyed by
  wire bytes (``messages.unmarshal_interned``), so the n-1 identical
  payloads of one broadcast decode once and all recipients share one
  frozen message object.  Receivers treat ingested messages as IMMUTABLE;
  fault hooks that mutate messages get a deep copy (copy-on-write), so
  corrupting one recipient's message cannot leak into another's ingest;
* **Wave-batched ingest** — a node's serve task drains everything queued in
  its inbox per wakeup and hands the whole run to
  ``Consensus.handle_message_batch`` in one call, so a quorum wave of votes
  registers in one scheduler tick instead of ~n call chains.

All costs and call counts feed :data:`smartbft_tpu.metrics.
PROTOCOL_PLANE` by default, or a per-group plane in sharded mode.

**Consensus groups (sharded mode).**  Transport keys are namespaced by a
GROUP id: several independent consensus groups ("shards") can reuse node
ids 1..n on ONE in-process mesh without inbox collisions.  ``Network.
group(gid)`` returns a :class:`GroupNet` facade exposing the exact Comm
surface a single-group embedder sees (``add_node`` / ``send_consensus`` /
``broadcast_consensus`` / ``node_ids`` / fault injection), all scoped to
that group; group 0 is the implicit default, so pre-sharding callers are
untouched.  ``mute``/``partition``/``heal`` take the shard scope the same
way — a partition in one group never cuts links in another.  Each group
may carry its own :class:`~smartbft_tpu.metrics.ProtocolPlaneTimers` for
per-shard cost attribution (the aggregate stays readable through
``metrics.protocol_plane_snapshot()``).
"""

from __future__ import annotations

import asyncio
import random
from time import perf_counter
from typing import Callable, Optional

from ..codec import CodecError
from ..messages import (
    Message,
    deep_copy_message,
    marshal,
    unmarshal_interned,
    wire_of,
)
from ..metrics import PROTOCOL_PLANE, install_plane, reset_plane
from ..obs.recorder import PROCESS as _REC
from ..utils.tasks import create_logged_task

INCOMING_BUFFER = 1000  # network.go:18-20


def _marshal_timed(msg: Message, plane) -> bytes:
    """Plain (un-memoized) encode with codec accounting — the path
    mutated (per-target) copies take."""
    span = _REC.begin("codec") if _REC.enabled else None
    t0 = perf_counter()
    w = marshal(msg)
    plane.codec_us += (perf_counter() - t0) * 1e6
    if span is not None:
        _REC.end(span)
    plane.encodes += 1
    return w


class Node:
    """One endpoint: wraps a Consensus instance's handle_message/
    handle_request behind an inbox task (network.go:200-241)."""

    def __init__(self, node_id: int, network: "Network", rng: random.Random,
                 group: int = 0):
        self.id = node_id
        self.network = network
        self.group = group  # consensus-group (shard) namespace
        self.rng = rng
        self.consensus = None  # set by the harness (an App or Consensus)
        self.running = False
        self.lossy = False
        self.muted = False  # outbound-only silence (chaos leader-mute)
        self.loss_probability = 0.0
        self.peer_loss_probability: dict[int, float] = {}
        self.mutate_send: Optional[Callable[[int, Message], Optional[Message]]] = None
        self.filters: list[Callable[[Message, int], bool]] = []
        self._inbox: asyncio.Queue = asyncio.Queue(maxsize=INCOMING_BUFFER)
        self._task: Optional[asyncio.Task] = None
        self.dropped = 0
        self.malformed = 0  # undecodable wire payloads (Byzantine/corrupt)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        if self.running:
            return
        self.running = True
        self._task = create_logged_task(
            self._serve(),
            name=f"netnode-{self.id}" if self.group == 0
            else f"netnode-g{self.group}-{self.id}",
            # each drained batch's decode + dispatch is one busy span
            busy=(_REC, "net.ingest"),
        )

    async def stop(self) -> None:
        self.running = False
        if self._task is not None:
            self._inbox.put_nowait(None)
            await self._task
            self._task = None

    async def _serve(self) -> None:
        """Wave-batched drain: each wakeup collects EVERYTHING already
        queued and dispatches it as one batch — a whole prepare/commit wave
        registers in one ``handle_message_batch`` call instead of ~n
        per-message call chains."""
        while True:
            item = await self._inbox.get()
            batch: list = []
            stop = False
            while True:
                if item is None or not self.running:
                    stop = True
                    break
                batch.append(item)
                try:
                    item = self._inbox.get_nowait()
                except asyncio.QueueEmpty:
                    break
            if batch:
                try:
                    await self._dispatch(batch)
                except Exception:  # pragma: no cover — harness robustness
                    import traceback

                    traceback.print_exc()
                    raise
            if stop:
                return

    async def _dispatch(self, batch: list) -> None:
        """Decode (interned) and route one drained batch, preserving the
        arrival order across kinds.  The node's group plane is installed as
        the task-context accounting target for the duration, so protocol-
        core sites (vote registration) attribute to the right shard."""
        plane = self.network.plane_of(self.group)
        t0 = perf_counter()
        codec0 = plane.codec_us
        vote0 = plane.vote_reg_us
        token = install_plane(plane)
        try:
            run: list = []  # consecutive consensus (sender, msg) pairs
            for kind, sender, payload in batch:
                if kind == "consensus":
                    msg = payload
                    if isinstance(payload, (bytes, bytearray)):
                        try:
                            msg = unmarshal_interned(payload, plane)
                        except CodecError:
                            self.malformed += 1
                            plane.malformed_dropped += 1
                            continue
                    run.append((sender, msg))
                else:
                    await self._flush_consensus(run)
                    await self.consensus.handle_request(sender, payload)
            await self._flush_consensus(run)
        finally:
            reset_plane(token)
        # disjoint accounting: decode time (codec_us) and view registration
        # (vote_reg_us) accrued inside this tick are reported in their own
        # terms — ingest_us is the drain/dispatch REMAINDER, so the four
        # plane terms sum without double-counting
        plane.ingest_us += (
            (perf_counter() - t0) * 1e6
            - (plane.codec_us - codec0)
            - (plane.vote_reg_us - vote0)
        )
        plane.batch_ingests += 1
        plane.msgs_ingested += len(batch)

    async def _flush_consensus(self, run: list) -> None:
        if not run:
            return
        c = self.consensus
        batch_async = getattr(c, "handle_message_batch_async", None)
        if batch_async is not None:
            await batch_async(list(run))
            run.clear()
            return
        batch_sync = getattr(c, "handle_message_batch", None)
        if batch_sync is not None:
            batch_sync(list(run))
            run.clear()
            return
        # injected doubles without the batch surface
        for sender, msg in run:
            # async intake: a backpressure-configured cluster blocks THIS
            # node's delivery task on a full component inbox (the
            # reference's full-channel semantics); in drop mode it behaves
            # exactly like the sync intake
            intake = getattr(c, "handle_message_async", None)
            if intake is not None:
                await intake(sender, msg)
            else:  # injected doubles without the async surface
                c.handle_message(sender, msg)
        run.clear()

    # -- ingress -----------------------------------------------------------

    def _offer(self, kind: str, sender: int, payload) -> None:
        if not self.running:
            return
        try:
            self._inbox.put_nowait((kind, sender, payload))
        except asyncio.QueueFull:
            self.dropped += 1  # drop on overflow (network.go:135-139)

    # -- fault injection (test_app.go:129-195) -----------------------------

    def disconnect(self) -> None:
        self.lossy = True
        self.loss_probability = 1.0

    def disconnect_from(self, peer: int) -> None:
        self.peer_loss_probability[peer] = 1.0

    def connect_to(self, peer: int) -> None:
        self.peer_loss_probability.pop(peer, None)

    def connect(self) -> None:
        self.lossy = False
        self.loss_probability = 0.0
        self.peer_loss_probability.clear()

    def lose_messages(self, probability: float) -> None:
        self.lossy = probability > 0
        self.loss_probability = probability

    def mute(self) -> None:
        """Outbound-only silence: the node still RECEIVES everything but
        none of its sends leave — the classic mute-leader fault (a process
        that is alive and ingesting but whose egress is wedged).  Distinct
        from disconnect(), which severs both directions."""
        self.muted = True

    def unmute(self) -> None:
        self.muted = False

    def add_filter(self, f: Callable[[Message, int], bool]) -> None:
        """Keep a message iff every filter returns True (network.go:232-234)."""
        self.filters.append(f)

    def clear_filters(self) -> None:
        self.filters.clear()

    def _drops(self, peer: int) -> bool:
        """Sender-side check: per-peer loss (disconnect_from) OR global loss.

        Per-peer loss is consulted on the SENDER only, matching the
        reference (network.go): DisconnectFrom(x) stops my sends to x but
        x's messages still reach me unless x also disconnects.
        """
        # max(): like the reference's independent r < q OR r < w checks, a
        # per-peer probability never shields a peer from the global loss
        p = max(self.peer_loss_probability.get(peer, 0.0),
                self.loss_probability if self.lossy else 0.0)
        return p > 0 and self.rng.random() < p

    def _drops_inbound(self, peer: int) -> bool:
        """Receiver-side check: only the node-wide loss state applies."""
        p = self.loss_probability if self.lossy else 0.0
        return p > 0 and self.rng.random() < p


class Network:
    """The mesh (network.go:34-74).

    ``plane`` is the default cost-attribution sink (the process-wide
    :data:`~smartbft_tpu.metrics.PROTOCOL_PLANE` unless given); per-GROUP
    planes registered via :meth:`group` override it for that group's
    traffic.  Transport keys are ``(group, node_id)`` internally: shards
    reuse node ids 1..n without inbox collisions; ``self.nodes`` stays the
    group-0 map so every pre-sharding caller is untouched."""

    def __init__(self, seed: int = 0, plane=None):
        self.plane = PROTOCOL_PLANE if plane is None else plane
        self.rng = random.Random(seed)
        self._groups: dict[int, dict[int, Node]] = {0: {}}
        self._group_planes: dict[int, object] = {}
        #: (group, node, peer) -> loss probability the link had BEFORE
        #: partition() cut it.  heal() restores exactly these links to
        #: their prior state (0.0 entries are removed), leaving
        #: independently injected disconnect_from() cuts and fractional
        #: losses intact.  Partitions are per group: shards never share
        #: links, so a cut in one group cannot touch another.
        self._partition_cuts: dict[tuple[int, int, int], float] = {}

    # -- group namespacing -------------------------------------------------

    @property
    def nodes(self) -> dict[int, Node]:
        """Back-compat: the default group's node map."""
        return self._groups[0]

    def group(self, gid: int, plane=None) -> "GroupNet":
        """A group-scoped facade over this mesh (see :class:`GroupNet`).

        ``plane``: optional per-group ProtocolPlaneTimers — all codec /
        route / ingest / vote-registration cost of this group's traffic is
        attributed there (per-shard attribution), while the process
        aggregate stays readable via ``metrics.protocol_plane_snapshot``."""
        self._groups.setdefault(gid, {})
        if plane is not None:
            self._group_planes[gid] = plane
        return GroupNet(self, gid)

    def plane_of(self, gid: int):
        return self._group_planes.get(gid, self.plane)

    def group_ids(self) -> list[int]:
        return sorted(self._groups.keys())

    def _gmap(self, group: int) -> dict[int, Node]:
        return self._groups.setdefault(group, {})

    def add_node(self, node_id: int, group: int = 0) -> Node:
        node = Node(node_id, self, self.rng, group=group)
        self._gmap(group)[node_id] = node
        return node

    def node_ids(self, group: int = 0) -> list[int]:
        return sorted(self._gmap(group).keys())

    def start(self) -> None:
        for gmap in self._groups.values():
            for node in gmap.values():
                node.start()

    async def stop(self) -> None:
        for gmap in self._groups.values():
            for node in gmap.values():
                await node.stop()

    # -- transport ---------------------------------------------------------

    def send_consensus(self, source: int, target: int, msg: Message,
                       group: int = 0) -> None:
        gmap = self._gmap(group)
        src = gmap.get(source)
        dst = gmap.get(target)
        if src is None or dst is None:
            return
        # sender-side faults
        if src.muted or src._drops(target):
            return
        if src.mutate_send is not None:
            # copy-on-write: decoded messages are shared/interned objects —
            # a mutation hook must never touch the original in place
            msg = src.mutate_send(target, deep_copy_message(msg))
            if msg is None:
                return
        # receiver-side faults
        if dst._drops_inbound(source):
            return
        for f in dst.filters:
            if not f(msg, source):
                return
        plane = self.plane_of(group)
        plane.sends += 1
        dst._offer("consensus", source, wire_of(msg, plane))

    def broadcast_consensus(self, source: int, msg: Message,
                            targets: Optional[list[int]] = None,
                            group: int = 0) -> None:
        """Encode-once fan-out to ``targets`` (default: every other node
        of ``group``).

        The canonical encoding is computed at most ONCE (memoized on the
        frozen message instance) and the same wire bytes are enqueued at
        all n-1 recipients; delivery decodes through the intern memo, so
        the whole broadcast costs 1 encode + <=1 decode.  Per-link faults
        (loss, filters) still apply per recipient, and a mutation hook
        forces a per-target copy + re-encode for the targets it touches —
        correctness over cheapness under fault injection."""
        gmap = self._gmap(group)
        src = gmap.get(source)
        if src is None:
            return
        plane = self.plane_of(group)
        plane.broadcasts += 1
        if src.muted:
            return  # outbound silence: nothing leaves, nothing encodes
        # busy span: one per fan-out (the encode inside it is a codec span)
        span = _REC.begin("net.route") if _REC.enabled else None
        try:
            self._fan_out(gmap, src, source, msg, targets, plane)
        finally:
            if span is not None:
                _REC.end(span)

    def _fan_out(self, gmap, src, source: int, msg: Message,
                 targets: Optional[list[int]], plane) -> None:
        t0 = perf_counter()
        codec0 = plane.codec_us
        wire: Optional[bytes] = None
        if src.mutate_send is None:
            wire = wire_of(msg, plane)  # ONE encode for the whole fan-out
        target_ids = targets if targets is not None else gmap
        for target in target_ids:
            if target == source:
                continue
            dst = gmap.get(target)
            if dst is None:
                continue
            if src._drops(target):
                continue
            m, w = msg, wire
            if src.mutate_send is not None:
                # copy-on-write (see send_consensus)
                m = src.mutate_send(target, deep_copy_message(msg))
                if m is None:
                    continue
                w = None
            if dst._drops_inbound(source):
                continue
            veto = False
            for f in dst.filters:
                if not f(m, source):
                    veto = True
                    break
            if veto:
                continue
            if w is None:
                if m == msg:
                    # hook did not change this target's copy
                    w = wire_of(msg, plane)
                else:
                    w = _marshal_timed(m, plane)
            dst._offer("consensus", source, w)
        # disjoint accounting: the encode time spent inside this fan-out is
        # already in codec_us — subtract it so route_us + codec_us +
        # ingest_us + vote_reg_us sum without double-counting
        plane.route_us += (
            (perf_counter() - t0) * 1e6
            - (plane.codec_us - codec0)
        )

    def send_transaction(self, source: int, target: int, request: bytes,
                         group: int = 0) -> None:
        gmap = self._gmap(group)
        src = gmap.get(source)
        dst = gmap.get(target)
        if src is None or dst is None:
            return
        if src.muted or src._drops(target) or dst._drops_inbound(source):
            return
        dst._offer("request", source, request)

    # -- faults (chaos harness; all take the optional shard scope) ---------

    def mute(self, node_id: int, group: int = 0) -> None:
        self._gmap(group)[node_id].mute()

    def unmute(self, node_id: int, group: int = 0) -> None:
        self._gmap(group)[node_id].unmute()

    def partition(self, *groups: list[int], shard: int = 0) -> None:
        """Split ONE consensus group's mesh into disjoint partitions:
        messages cross partition boundaries in neither direction until
        :meth:`heal`.  Nodes not named in any partition form an implicit
        final one.  ``shard`` scopes the cut — other groups' links are
        untouched (shards never share links in the first place)."""
        gmap = self._gmap(shard)
        named = {n for g in groups for n in g}
        rest = [n for n in gmap if n not in named]
        all_groups = [list(g) for g in groups] + ([rest] if rest else [])
        group_of = {n: i for i, g in enumerate(all_groups) for n in g}
        for nid, node in gmap.items():
            for peer in gmap:
                if peer != nid and group_of.get(peer) != group_of.get(nid):
                    # a link some other fault already cut stays its fault's
                    # responsibility — heal() must not reconnect it; a
                    # fractional pre-existing loss is remembered so heal()
                    # restores it instead of clearing the link
                    prior = node.peer_loss_probability.get(peer, 0.0)
                    key = (shard, nid, peer)
                    if prior < 1.0 and key not in self._partition_cuts:
                        self._partition_cuts[key] = prior
                    node.disconnect_from(peer)

    def heal(self, shard: Optional[int] = None) -> None:
        """Undo :meth:`partition` — exactly the link cuts it installed,
        restoring any pre-partition fractional loss; independently injected
        per-peer cuts (disconnect_from) and node-level faults
        (mute/disconnect/loss) are left as-is.  ``shard``: heal only that
        group's cuts; None (default) heals every group."""
        remaining: dict[tuple[int, int, int], float] = {}
        for (gid, nid, peer), prior in self._partition_cuts.items():
            if shard is not None and gid != shard:
                remaining[(gid, nid, peer)] = prior
                continue
            node = self._gmap(gid).get(nid)
            if node is not None:
                if prior > 0.0:
                    node.peer_loss_probability[peer] = prior
                else:
                    node.peer_loss_probability.pop(peer, None)
        self._partition_cuts = remaining


class GroupNet:
    """Group-scoped view of a :class:`Network`: the exact transport surface
    a single-group embedder uses (what ``testing.app.App`` calls), with
    every operation namespaced to one consensus group — so S shards reuse
    node ids 1..n over ONE mesh with zero inbox collisions.  Handed to
    each shard's Apps by the sharded harness in place of the raw Network.
    """

    def __init__(self, network: Network, gid: int):
        self.network = network
        self.gid = gid

    @property
    def plane(self):
        return self.network.plane_of(self.gid)

    @property
    def nodes(self) -> dict[int, Node]:
        return self.network._gmap(self.gid)

    def add_node(self, node_id: int) -> Node:
        return self.network.add_node(node_id, group=self.gid)

    def node_ids(self) -> list[int]:
        return self.network.node_ids(self.gid)

    def start(self) -> None:
        for node in self.nodes.values():
            node.start()

    async def stop(self) -> None:
        for node in self.nodes.values():
            await node.stop()

    # -- transport (Comm surface) ------------------------------------------

    def send_consensus(self, source: int, target: int, msg: Message) -> None:
        self.network.send_consensus(source, target, msg, group=self.gid)

    def broadcast_consensus(self, source: int, msg: Message,
                            targets: Optional[list[int]] = None) -> None:
        self.network.broadcast_consensus(source, msg, targets, group=self.gid)

    def send_transaction(self, source: int, target: int, request: bytes) -> None:
        self.network.send_transaction(source, target, request, group=self.gid)

    # -- shard-scoped faults ----------------------------------------------

    def mute(self, node_id: int) -> None:
        self.network.mute(node_id, group=self.gid)

    def unmute(self, node_id: int) -> None:
        self.network.unmute(node_id, group=self.gid)

    def partition(self, *groups: list[int]) -> None:
        self.network.partition(*groups, shard=self.gid)

    def heal(self) -> None:
        self.network.heal(shard=self.gid)
