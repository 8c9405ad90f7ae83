"""The view-change sub-protocol: ViewChange -> ViewData -> NewView.

Re-design of /root/reference/internal/bft/viewchanger.go:52-1363 — the most
intricate component of the protocol.  Structure:

- Nodes broadcast ``ViewChange{next_view}``; at f+1 (SpeedUpViewChange) or
  quorum-1 they join, persist a ViewChange record, abort the current view,
  and send signed ``ViewData`` (checkpoint + in-flight proposal) to the new
  leader (viewchanger.go:364-456).
- The new leader validates each ViewData — including delivering a last
  decision it is one behind on (checkLastDecision ladder, :501-666) — and at
  quorum runs ``check_in_flight`` (the agreed-in-flight decision rule,
  :813-908) before broadcasting ``NewView``.
- Every node validates the NewView's quorum of ViewData (:931-1095), commits
  an agreed in-flight proposal by spinning up a special View with itself as
  leader pre-seeded in PREPARED (:1186-1306), persists a NewView record, and
  informs the Controller.

Quorum signature checks on last decisions (``validate_last_decision``,
:681-727) are batched through the Verifier — the second TPU batching target
after commit processing.

Timing (resend interval, view-change timeout with exponential backoff) is
tick-driven from the shared Scheduler.  Ticks are delivered as events to the
main loop, except during the in-flight wait where a live tick callback
drives the timeout — mirroring the reference's two select sites.
"""

from __future__ import annotations

import asyncio
from typing import Optional

from ..api import Logger, Signer, Verifier
from ..codec import decode, encode
from ..messages import (
    Commit,
    Message,
    NewView,
    NewViewRecord,
    Proposal,
    Signature,
    SignedViewData,
    ViewChange,
    ViewChangeRecord,
    ViewData,
    ViewMetadata,
)
from ..metrics import BlacklistMetrics, ViewChangeMetrics, ViewMetrics
from ..types import Checkpoint, VerifyPlaneDown, blacklist_of, proposal_digest
from .pool import remove_delivered_requests
from .state import PREPARED
from .util import InFlightData, NextViews, VoteSet, compute_quorum, get_leader_id
from .view import View, ViewSequencesHolder, verify_sigs_batch
from ..utils.tasks import create_logged_task


def validate_in_flight(in_flight_proposal: Optional[Proposal], last_sequence: int) -> None:
    """viewchanger.go:788-806 — raises if invalid."""
    if in_flight_proposal is None:
        return
    if not in_flight_proposal.metadata:
        raise ValueError("in flight proposal metadata is nil")
    md = decode(ViewMetadata, in_flight_proposal.metadata)
    if md.latest_sequence != last_sequence + 1:
        raise ValueError(
            f"the in flight proposal sequence is {md.latest_sequence} while the last "
            f"decision sequence is {last_sequence}"
        )


def validate_in_flight_ladder(vd: ViewData, last_sequence: int) -> None:
    """Ladder extension of :func:`validate_in_flight` (pipelined window):
    rung 0 sits at last_sequence+1 and every ``in_flight_more[i]`` must be
    the consecutive rung above it.  Raises if invalid."""
    validate_in_flight(vd.in_flight_proposal, last_sequence)
    # wire invariant FIRST: flag count == rung count always, even when the
    # rung list is empty — otherwise a ViewData with orphan prepared flags
    # (empty in_flight_more, non-empty in_flight_more_prepared) passes
    # validation and the invariant is only accidentally upheld downstream
    if len(vd.in_flight_more_prepared) != len(vd.in_flight_more):
        raise ValueError("in flight ladder prepared flags do not match rung count")
    if not vd.in_flight_more:
        return
    if vd.in_flight_proposal is None:
        raise ValueError("in flight ladder extension without a first rung")
    for i, prop in enumerate(vd.in_flight_more):
        if not prop.metadata:
            raise ValueError("in flight proposal metadata is nil")
        md = decode(ViewMetadata, prop.metadata)
        if md.latest_sequence != last_sequence + 2 + i:
            raise ValueError(
                f"in flight ladder rung {i + 1} has sequence {md.latest_sequence}, "
                f"expected {last_sequence + 2 + i}"
            )


async def validate_last_decision(
    vd: ViewData, quorum: int, n: int, verifier: Verifier
) -> int:
    """viewchanger.go:681-727 — verify a quorum of consenter signatures on
    the last decision (batched); returns its sequence.  Raises if invalid."""
    if vd.last_decision is None:
        raise ValueError("the last decision is not set")
    if not vd.last_decision.metadata:
        return 0  # genesis proposal: nothing to validate
    md = decode(ViewMetadata, vd.last_decision.metadata)
    if md.view_id >= vd.next_view:
        raise ValueError(
            f"last decision view {md.view_id} is greater or equal to requested next view {vd.next_view}"
        )
    num_sigs = len(vd.last_decision_signatures)
    if num_sigs < quorum:
        raise ValueError(f"there are only {num_sigs} last decision signatures")
    seen: set[int] = set()
    unique_sigs = []
    for sig in vd.last_decision_signatures:
        if sig.signer in seen:
            continue
        seen.add(sig.signer)
        unique_sigs.append(sig)
    # shared dispatch incl. the loop-stall warning for slow sync verifiers
    results = await verify_sigs_batch(verifier, unique_sigs, vd.last_decision)
    valid = sum(1 for r in results if r is not None)
    if any(r is None for r in results):
        raise ValueError("last decision signature is invalid")
    if valid < quorum:
        raise ValueError(f"there are only {valid} valid last decision signatures")
    return md.latest_sequence


def max_last_decision_sequence(messages: list[ViewData]) -> int:
    """viewchanger.go:910-929."""
    mx = 0
    for vd in messages:
        if vd.last_decision is None:
            raise ValueError("The last decision is not set")
        if not vd.last_decision.metadata:
            continue
        md = decode(ViewMetadata, vd.last_decision.metadata)
        mx = max(mx, md.latest_sequence)
    return mx


def _in_flight_rungs(vd: ViewData) -> dict[int, tuple[Proposal, bool]]:
    """seq -> (proposal, prepared) for every in-flight rung a ViewData
    carries: the reference-shaped singular field plus the pipelined-window
    extension (``in_flight_more``).  Raises on nil-metadata rungs, like the
    reference's check (viewchanger.go:837-841)."""
    rungs: dict[int, tuple[Proposal, bool]] = {}
    if vd.in_flight_proposal is not None:
        if not vd.in_flight_proposal.metadata:
            raise ValueError("view data message has in flight proposal with nil metadata")
        md = decode(ViewMetadata, vd.in_flight_proposal.metadata)
        rungs[md.latest_sequence] = (vd.in_flight_proposal, vd.in_flight_prepared)
    for i, prop in enumerate(vd.in_flight_more):
        if not prop.metadata:
            raise ValueError("view data message has in flight proposal with nil metadata")
        md = decode(ViewMetadata, prop.metadata)
        prepared = (
            vd.in_flight_more_prepared[i] if i < len(vd.in_flight_more_prepared) else False
        )
        rungs[md.latest_sequence] = (prop, prepared)
    return rungs


def _check_rung(
    entries: list[Optional[tuple[Proposal, bool]]], f: int, quorum: int
) -> tuple[Optional[Proposal], int]:
    """One rung of the agreed-in-flight decision rule: the A/B counters of
    viewchanger.go:813-908 over per-ViewData entries at ONE sequence.

    ``entries[i]`` is (proposal, prepared) if ViewData i carries an
    in-flight rung at the sequence under examination, else None (covers
    no-in-flight, wrong-sequence, and absent rungs — all of which the
    reference counts identically).  Returns (chosen_proposal_or_None,
    no_in_flight_count)."""
    possible: list[dict] = []
    no_in_flight_count = 0
    for e in entries:
        if e is None or not e[1]:
            no_in_flight_count += 1
        if e is not None and e[1] and not any(p["proposal"] == e[0] for p in possible):
            possible.append({"proposal": e[0], "preprepared": 0, "no_argument": 0})
    for e in entries:
        for p in possible:
            if e is None:
                p["no_argument"] += 1
            elif e[0] == p["proposal"]:
                p["no_argument"] += 1
                p["preprepared"] += 1
    for p in possible:
        if p["preprepared"] < f + 1:
            continue  # condition A2 fails
        if p["no_argument"] < quorum:
            continue  # condition A1 fails
        return p["proposal"], no_in_flight_count
    return None, no_in_flight_count


def check_in_flight(
    messages: list[ViewData], f: int, quorum: int, n: int, verifier: Verifier
) -> tuple[bool, bool, Optional[Proposal]]:
    """The agreed-in-flight-proposal decision rule (viewchanger.go:813-908).

    Returns (ok, no_in_flight, proposal):
      condition A — some prepared proposal at the expected sequence has >=f+1
        pre-prepare witnesses (A2) and >=quorum no-argument votes (A1);
      condition B — >=quorum of messages support that nothing is in flight.
    """
    expected_sequence = max_last_decision_sequence(messages) + 1
    entries = [_in_flight_rungs(vd).get(expected_sequence) for vd in messages]
    chosen, no_in_flight_count = _check_rung(entries, f, quorum)
    if chosen is not None:
        return True, False, chosen
    if no_in_flight_count >= quorum:
        return True, True, None
    return False, False, None


def check_in_flight_ladder(
    messages: list[ViewData], f: int, quorum: int, n: int, verifier: Verifier
) -> tuple[bool, list[Proposal]]:
    """Multi-in-flight generalization of :func:`check_in_flight` for the
    pipelined window (pipeline_depth > 1; no reference counterpart).

    Applies the A/B rule rung by rung starting at max-last-decision+1:
    every rung where condition A holds contributes an agreed proposal that
    MUST be committed before the new view starts (a commit quorum may have
    existed for it); the first rung where condition B holds terminates the
    ladder (quorum intersection: nothing at or above it can have gathered
    a commit quorum, because commit broadcasts are in-order within the
    window — see core/pipeline.py).  A rung satisfying neither fails the
    whole check, exactly as the single-slot rule does.

    Returns (ok, agreed_proposals_in_sequence_order).  With no ladder
    extensions present this reduces exactly to check_in_flight: one rung,
    then B on the empty rung above it.
    """
    expected_sequence = max_last_decision_sequence(messages) + 1
    all_rungs = [_in_flight_rungs(vd) for vd in messages]
    agreed: list[Proposal] = []
    # the ladder is bounded by the highest rung any ViewData carries
    highest = max((max(r) for r in all_rungs if r), default=0)
    while expected_sequence <= highest + 1:
        entries = [rungs.get(expected_sequence) for rungs in all_rungs]
        chosen, no_in_flight_count = _check_rung(entries, f, quorum)
        if chosen is not None:
            agreed.append(chosen)
            expected_sequence += 1
            continue
        if no_in_flight_count >= quorum:
            return True, agreed
        return False, []
    return True, agreed


class _InFlightDecider:
    """Decider/FailureDetector/Synchronizer facade handed to the special
    in-flight View (viewchanger.go:1308-1345)."""

    def __init__(self, vc: "ViewChanger"):
        self.vc = vc

    async def decide(self, proposal, signatures, requests) -> None:
        vc = self.vc
        if vc._in_flight_view is not None:
            vc._in_flight_view._stop()
        vc.logger.debugf("Delivering to app from in-flight Decide the last decision proposal")
        reconfig = await vc.application.deliver(proposal, signatures)
        if reconfig.in_latest_decision:
            vc.close()
        remove_delivered_requests(vc.requests_timer, requests, vc.logger)
        vc.pruner.maybe_prune_revoked_requests()
        if vc._in_flight_decide is not None and not vc._in_flight_decide.done():
            vc._in_flight_decide.set_result(True)

    def complain(self, view_num: int, stop_view: bool) -> None:
        self.vc.logger.panicf(
            "Node %d has complained while in the view for the in flight proposal",
            self.vc.self_id,
        )

    def sync(self) -> None:
        vc = self.vc
        vc.logger.debugf(
            "Node %d is calling sync because the in flight proposal view has asked to sync",
            vc.self_id,
        )
        vc.synchronizer.sync()
        if vc._in_flight_sync is not None and not vc._in_flight_sync.done():
            vc._in_flight_sync.set_result(True)


class ViewChanger:
    #: how long a fresh run loop waits for a cancelled prior loop to
    #: actually finish before escalating (clear vote state + force sync);
    #: tests tighten it
    STRAGGLER_WAIT: float = 5.0

    #: scheduler-seconds of state quiet before a mutation-driven standby
    #: rebuild fires.  Short enough to land well inside the detection
    #: floor (a complaint is at least DETECTION_FLOOR=50ms of silence
    #: away), long enough that a window of back-to-back commits costs one
    #: timer reschedule per mutation instead of one ViewData sign each
    STANDBY_REBUILD_DEBOUNCE: float = 0.02

    def __init__(
        self,
        *,
        self_id: int,
        n: int,
        nodes_list: list[int],
        leader_rotation: bool,
        decisions_per_leader: int,
        speed_up_view_change: bool,
        logger: Logger,
        signer: Signer,
        verifier: Verifier,
        checkpoint: Checkpoint,
        in_flight: InFlightData,
        state,
        resend_timeout: float,
        view_change_timeout: float,
        in_msg_q_size: int,
        backpressure: bool = False,
        metrics_view_change: Optional[ViewChangeMetrics] = None,
        metrics_blacklist: Optional[BlacklistMetrics] = None,
        metrics_view: Optional[ViewMetrics] = None,
        vc_phases=None,
        recorder=None,
        scheduler=None,
    ):
        self.self_id = self_id
        self.n = n
        self.nodes_list = nodes_list
        self.leader_rotation = leader_rotation
        self.decisions_per_leader = decisions_per_leader
        self.speed_up_view_change = speed_up_view_change
        self.logger = logger
        self.signer = signer
        self.verifier = verifier
        self.checkpoint = checkpoint
        self.in_flight = in_flight
        self.state = state
        self.resend_timeout = resend_timeout
        self.view_change_timeout = view_change_timeout
        self.in_msg_q_size = in_msg_q_size
        self.backpressure = backpressure
        self._space_event = asyncio.Event()
        self.metrics = metrics_view_change
        self.metrics_blacklist = metrics_blacklist
        self.metrics_view = metrics_view
        #: optional obs.ViewChangePhaseTracker — marks the complain →
        #: depose → ViewData → new-view pipeline's transition points so
        #: the flight recorder can decompose failover time (ISSUE 12);
        #: None = no decomposition (unit tests constructing a bare
        #: ViewChanger pay nothing)
        self.vc_phases = vc_phases
        from ..obs.recorder import standby

        self.recorder = standby(recorder)

        # wired later by the Consensus facade (consensus.go:445-450,466-470)
        self.comm = None  # Controller (broadcast + send)
        self.synchronizer = None  # Controller (sync trigger)
        self.application = None  # MutuallyExclusiveDeliver
        self.controller = None  # ViewController: view_changed / abort_view
        self.requests_timer = None  # Pool
        self.pruner = None  # Controller
        self.view_sequences: Optional[ViewSequencesHolder] = None

        self.quorum = 0
        self.f = 0
        self.curr_view = 0
        self.real_view = 0
        self.next_view = 0
        self._events: asyncio.Queue = asyncio.Queue()
        self._queued_msgs = 0  # network messages in-queue (bounded; internal events are not)
        self._dropped_msgs = 0
        # start barrier (consensus.go:507-511 waitForEachOther): the run loop
        # holds off processing until the Controller finished starting, so a
        # message racing a start/reconfig cannot hit a half-wired ViewChanger.
        self.controller_started_event: Optional[asyncio.Event] = None
        self._stopped = False
        self._task: Optional[asyncio.Task] = None
        self._prior_tasks: set[asyncio.Task] = set()
        self._restore_on_start = False

        self.view_change_msgs = VoteSet(lambda _s, m: isinstance(m, ViewChange))
        self.view_data_msgs = VoteSet(lambda _s, m: isinstance(m, SignedViewData))
        self.nvs = NextViews()

        self._last_tick = 0.0
        self._last_resend = 0.0
        self._start_view_change_time = 0.0
        self._check_timeout = False
        self._back_off_factor = 1
        self._committed_during_view_change: Optional[ViewMetadata] = None
        self._pending_changes = 0

        # hot-standby ViewData (ISSUE 15): when THIS node is the
        # deterministic next leader, the tick loop pre-builds (and signs)
        # its ViewData from the live checkpoint/ladder state, keyed on
        # (next_view, checkpoint.version, in_flight.version) so any
        # protocol progress invalidates the cache.  On complaint quorum
        # _prepare_view_data_msg then returns the cached message instead
        # of reconstructing + re-signing state under the depose — the new
        # leader registers its own vote immediately and starts collecting
        # the quorum one round trip sooner.
        self._standby_msg: Optional[SignedViewData] = None
        self._standby_key: Optional[tuple] = None
        self.standby_prebuilds = 0
        self.standby_hits = 0
        # Event-driven prebuild (ISSUE 15 residual b): checkpoint/ladder
        # mutations notify _note_state_mutation, which debounces on the
        # shared scheduler (mutation bursts — every commit bumps both
        # versions several times — collapse to ONE rebuild, fired only
        # once the state goes quiet) and enqueues a "standby" event.  The
        # tick-time prebuild stays as the no-scheduler fallback and
        # belt-and-braces refresh; the event path is what closes the
        # cache-hit gap, because the moment mutations STOP (leader dead,
        # cluster idle) is exactly when the next complaint finds the
        # cache key still matching.
        self.scheduler = scheduler
        self._standby_timer = None
        self._standby_event_queued = False
        self.standby_event_rebuilds = 0

        self._in_flight_view: Optional[View] = None
        self._in_flight_decide: Optional[asyncio.Future] = None
        self._in_flight_sync: Optional[asyncio.Future] = None
        self._in_flight_tick_cb = None

    # ------------------------------------------------------------------ life

    def start(self, start_view_number: int) -> None:
        """viewchanger.go:119-159."""
        self.quorum, self.f = compute_quorum(self.n)
        self.curr_view = start_view_number
        self.real_view = self.curr_view
        self.next_view = self.curr_view
        self._set_view_metrics()
        self.nvs.clear()
        self.view_change_msgs.clear()
        self.view_data_msgs.clear()
        self._back_off_factor = 1
        self._stopped = False
        # reuse safety: a prior life's run loop may still be winding down if
        # the caller close()d without awaiting stop() — cancel it so two
        # loops never compete on one queue, then drain its backlog (a stale
        # ("stop",) sentinel would kill the fresh run loop on its first turn)
        if self._task is not None and not self._task.done():
            self._task.cancel()
            self._prior_tasks.add(self._task)
        # transitive: a prior life may ITSELF still be waiting on an even
        # older cancelled loop — a rapid double restart must not let the
        # oldest loop interleave with the newest (wait on ALL live priors)
        self._prior_tasks = {t for t in self._prior_tasks if not t.done()}
        while not self._events.empty():
            self._events.get_nowait()
        self._queued_msgs = 0
        self._pending_changes = 0
        self._standby_event_queued = False
        # event-driven standby prebuild: subscribe to checkpoint/ladder
        # mutations (single-subscriber seam; this ViewChanger owns it)
        if self.checkpoint is not None:
            self.checkpoint.on_mutate = self._note_state_mutation
        if self.in_flight is not None:
            self.in_flight.on_mutate = self._note_state_mutation
        self._task = create_logged_task(
            self._run(frozenset(self._prior_tasks)),
            name=f"viewchanger-{self.self_id}", logger=self.logger,
            busy=(self.recorder, "vc.run"),
        )

    def _set_view_metrics(self) -> None:
        if self.metrics:
            self.metrics.current_view.set(self.curr_view)
            self.metrics.real_view.set(self.real_view)
            self.metrics.next_view.set(self.next_view)

    def close(self) -> None:
        if not self._stopped:
            self._stopped = True
            if self._standby_timer is not None:
                self._standby_timer.cancel()
                self._standby_timer = None
            if self.controller_started_event is not None:
                self.controller_started_event.set()  # release the start barrier
            self._space_event.set()  # release blocked async senders
            self._events.put_nowait(("stop",))
            for fut in (self._in_flight_decide, self._in_flight_sync):
                if fut is not None and not fut.done():
                    fut.set_result(False)

    async def stop(self) -> None:
        self.close()
        if self._task is not None:
            await self._task
            self._task = None

    # ------------------------------------------------------------------ inputs

    def handle_message(self, sender: int, m: Message) -> None:
        if self._stopped:
            return
        # Bounded message intake (consensus.go:406 IncomingMessageBufferSize):
        # only network messages count toward the bound — internal control
        # events (change/inform/tick/stop) must never be dropped.
        if self._queued_msgs >= self.in_msg_q_size:
            self._dropped_msgs += 1
            if self._dropped_msgs == 1 or self._dropped_msgs % 1000 == 0:
                self.logger.warnf(
                    "ViewChanger inbox full (%d), dropped %d messages from %d",
                    self.in_msg_q_size, self._dropped_msgs, sender,
                )
            return
        self._queued_msgs += 1
        self._events.put_nowait(("msg", sender, m))

    async def handle_message_async(self, sender: int, m: Message) -> None:
        """Async intake: with ``backpressure`` on, a full intake BLOCKS the
        sending task until the run loop drains below the bound — the
        reference's full-channel semantics (viewchanger.go:206)."""
        if not self.backpressure:
            self.handle_message(sender, m)
            return
        while not self._stopped and self._queued_msgs >= self.in_msg_q_size:
            self._space_event.clear()
            await self._space_event.wait()
        if self._stopped:
            return
        self._queued_msgs += 1
        self._events.put_nowait(("msg", sender, m))

    def handle_view_message(self, sender: int, m: Message) -> None:
        """Pass view messages to the in-flight view (viewchanger.go:1347-1356)."""
        view = self._in_flight_view
        if view is not None:
            self.logger.debugf("Node %d is passing a message to the in flight view", self.self_id)
            view.handle_message(sender, m)

    def start_view_change(self, view: int, stop_view: bool) -> None:
        """External trigger (viewchanger.go:356-361); 2-slot like the
        reference's buffered channel."""
        if self._stopped or self._pending_changes >= 2:
            return
        self._pending_changes += 1
        self._events.put_nowait(("change", view, stop_view))

    def inform_new_view(self, view: int) -> None:
        if self._stopped:
            return
        self._events.put_nowait(("inform", view))

    def restore_trigger(self) -> None:
        """Restore a persisted ViewChange on startup (consensus.go:487-494)."""
        self._events.put_nowait(("restore",))

    def tick(self, now: float) -> None:
        """Driven by the shared scheduler Ticker."""
        if self._stopped:
            return
        if self._in_flight_tick_cb is not None:
            self._in_flight_tick_cb(now)
            return
        self._events.put_nowait(("tick", now))

    # ------------------------------------------------------------------ loop

    async def _run(self, prior_tasks: frozenset = frozenset()) -> None:
        if prior_tasks:
            # prior lives' cancelled loops may be suspended mid-_process_msg
            # (not at the queue.get); let their cancellations land before
            # this loop touches shared ViewChanger state, so two loops never
            # interleave.  asyncio.wait never propagates the tasks' outcomes.
            # Bounded: an embedder callback that swallows cancellation must
            # not brick the ViewChanger forever — after the timeout, escalate
            # SAFELY: discard the shared view-change bookkeeping a straggler
            # may still be mutating (vote sets rebuild from peer resends —
            # the resend timer re-broadcasts every resend_timeout) and force
            # a sync so this node re-derives its position from the cluster
            # instead of from potentially interleaved state.
            _, stragglers = await asyncio.wait(prior_tasks, timeout=self.STRAGGLER_WAIT)
            if stragglers:
                self.logger.errorf(
                    "ViewChanger %d: %d prior run loop(s) ignored cancellation "
                    "for %.1fs; clearing view-change vote state and forcing a "
                    "sync instead of sharing it with a live straggler",
                    self.self_id, len(stragglers), self.STRAGGLER_WAIT,
                )
                self.view_change_msgs.clear()
                self.view_data_msgs.clear()
                self.nvs.clear()
                self._check_timeout = False
                if self.synchronizer is not None:
                    self.synchronizer.sync()
        if self.controller_started_event is not None:
            await self.controller_started_event.wait()  # viewchanger.go:156
        while True:
            evt = await self._events.get()
            kind = evt[0]
            # close() may have released the start barrier with a message
            # backlog still queued ahead of the stop sentinel — never process
            # it against a half-started controller
            if kind == "stop" or self._stopped:
                return
            try:
                if kind == "msg":
                    self._queued_msgs -= 1
                    self._space_event.set()  # wake blocked async senders
                    await self._process_msg(evt[1], evt[2])
                elif kind == "change":
                    self._pending_changes -= 1
                    self._start_view_change(evt[1], evt[2])
                elif kind == "tick":
                    self._last_tick = evt[1]
                    if self.vc_phases is not None:
                        self.vc_phases.note_tick()  # live in-VC gauge
                    self._check_if_resend_view_change(evt[1])
                    self._check_if_timeout(evt[1])
                    self._maybe_prebuild_standby()
                elif kind == "standby":
                    self._standby_event_queued = False
                    before = self.standby_prebuilds
                    self._maybe_prebuild_standby()
                    if self.standby_prebuilds != before:
                        self.standby_event_rebuilds += 1
                elif kind == "inform":
                    self._inform_new_view(evt[1])
                elif kind == "restore":
                    await self._process_view_change_msg(restore=True)
            except Exception as e:
                self.logger.errorf("ViewChanger %d event %s failed: %r", self.self_id, kind, e)
                raise

    # ------------------------------------------------------------------ timing

    def get_leader(self) -> int:
        return get_leader_id(
            self.curr_view, self.n, self.nodes_list, self.leader_rotation,
            0, self.decisions_per_leader, self._blacklist(),
        )

    def _blacklist(self) -> list[int]:
        prop, _ = self.checkpoint.get()
        return blacklist_of(prop)

    # -- hot-standby ViewData (ISSUE 15) -----------------------------------

    def _note_state_mutation(self) -> None:
        """Checkpoint / in-flight ladder mutation hook (loop-synchronous:
        every mutation site runs on the shared event loop).  Debounced —
        the rebuild fires only once the state stays quiet for
        STANDBY_REBUILD_DEBOUNCE, so a burst of per-commit version bumps
        costs timer reschedules, not ViewData signatures."""
        if self._stopped:
            return
        if self.scheduler is not None:
            if self._standby_timer is not None:
                self._standby_timer.cancel()
            self._standby_timer = self.scheduler.schedule(
                self.STANDBY_REBUILD_DEBOUNCE, self._fire_standby_rebuild
            )
        else:
            # no scheduler wired (bare unit-test construction): rebuild
            # eagerly on the next loop turn
            self._fire_standby_rebuild()

    def _fire_standby_rebuild(self) -> None:
        self._standby_timer = None
        if self._stopped or self._standby_event_queued:
            return
        self._standby_event_queued = True  # 1-slot: coalesce until processed
        self._events.put_nowait(("standby",))

    def _standby_state_key(self, next_view: int) -> tuple:
        """Everything a ViewData is built from, as cheap version counters:
        the checkpoint (last decision + signatures) and the in-flight
        ladder.  Any commit, prepare, sync prune, or window move bumps
        one of them and invalidates the cache."""
        return (
            next_view,
            self.checkpoint.version,
            getattr(self.in_flight, "version", -1),
        )

    def _maybe_prebuild_standby(self) -> None:
        """Tick hook (off the commit hot path): when this node would lead
        view curr_view+1, keep a signed ViewData for it pre-built from
        the LIVE state.  Non-next-leaders drop the cache — it would never
        be consulted with a matching key."""
        if self._stopped or self.comm is None or self.signer is None:
            return
        try:
            next_leader = get_leader_id(
                self.curr_view + 1, self.n, self.nodes_list,
                self.leader_rotation, 0, self.decisions_per_leader,
                self._blacklist(),
            )
        except Exception:  # noqa: BLE001 — e.g. everyone blacklisted
            return
        if next_leader != self.self_id:
            self._standby_msg = None
            self._standby_key = None
            return
        key = self._standby_state_key(self.curr_view + 1)
        if self._standby_msg is not None and key == self._standby_key:
            return
        self._standby_msg = self._build_view_data_msg(self.curr_view + 1)
        self._standby_key = key
        self.standby_prebuilds += 1
        if self.vc_phases is not None:
            self.vc_phases.note_standby(prebuilt=True)

    def _check_if_resend_view_change(self, now: float) -> None:
        """viewchanger.go:232-252."""
        if self._last_resend + self.resend_timeout > now:
            return
        if self._check_timeout:
            self.comm.broadcast_consensus(ViewChange(next_view=self.next_view))
            if self.metrics:
                self.metrics.count_complaints_sent.add(1)
            self.logger.debugf(
                "Node %d resent a view change message with next view %d",
                self.self_id, self.next_view,
            )
        self._last_resend = now

    def _check_if_timeout(self, now: float) -> bool:
        """viewchanger.go:254-270 — exponential backoff."""
        if not self._check_timeout:
            return False
        if self._start_view_change_time + self.view_change_timeout * self._back_off_factor > now:
            return False
        self.logger.debugf(
            "Node %d got a view change timeout, the current view is %d",
            self.self_id, self.curr_view,
        )
        self._check_timeout = False
        self._back_off_factor += 1
        if self.metrics:
            self.metrics.count_sync_escalations.add(1)
        rec = self.recorder
        if rec.enabled:
            rec.record("vc.timeout_sync", view=self.curr_view)
        if self.vc_phases is not None:
            # the round is being recycled (sync + restart): close it as
            # abandoned so its marks don't read as an in-progress VC
            self.vc_phases.timeout_escalated()
        self.synchronizer.sync()
        self.start_view_change(self.curr_view, False)
        return True

    # ------------------------------------------------------------------ msgs

    async def _process_msg(self, sender: int, m: Message) -> None:
        """viewchanger.go:272-326."""
        if isinstance(m, ViewChange):
            if self.metrics:
                self.metrics.count_complaints_received.add(1)
            self.nvs.register_next(m.next_view, sender)
            if m.next_view == self.curr_view + 1:
                self.view_change_msgs.register_vote(sender, m)
                await self._process_view_change_msg(restore=False)
                return
            if (
                self.next_view == self.curr_view + 1
                and m.next_view > self.real_view
                and m.next_view < self.curr_view + 1
                and self.nvs.send_recv(m.next_view, sender)
            ):
                # help the lagging nodes
                self.comm.broadcast_consensus(ViewChange(next_view=m.next_view))
                if self.metrics:
                    self.metrics.count_complaints_sent.add(1)
                self.logger.warnf(
                    "Node %d got viewChange from %d with view %d, expected view %d, helping lagging nodes",
                    self.self_id, sender, m.next_view, self.curr_view + 1,
                )
                return
            self.logger.warnf(
                "Node %d got viewChange from %d with view %d, expected view %d",
                self.self_id, sender, m.next_view, self.curr_view + 1,
            )
            return

        if isinstance(m, SignedViewData):
            if not await self._validate_view_data_msg(m, sender):
                return
            self.view_data_msgs.register_vote(sender, m)
            await self._process_view_data_msg()
            return

        if isinstance(m, NewView):
            leader = self.get_leader()
            if sender != leader:
                self.logger.warnf(
                    "Node %d got newView from %d, expected sender to be %d the next leader",
                    self.self_id, sender, leader,
                )
                return
            await self._process_new_view_msg(m)

    def _inform_new_view(self, view: int) -> None:
        """viewchanger.go:335-353."""
        if view < self.curr_view:
            return
        self.logger.debugf("Node %d was informed of a new view %d", self.self_id, view)
        if self.vc_phases is not None:
            # a sync installed the view around the VC pipeline
            self.vc_phases.abandoned_by_sync(view)
        self.curr_view = view
        self.real_view = view
        self.next_view = view
        self._set_view_metrics()
        self.nvs.clear()
        self.view_change_msgs.clear()
        self.view_data_msgs.clear()
        self._check_timeout = False
        self._back_off_factor = 1
        # a sync installed a new view around the VC pipeline — still a
        # flip: fast-forward the stalled backlog to the new leader
        self.requests_timer.restart_timers(flip=True)

    def _start_view_change(self, view: int, stop_view: bool) -> None:
        """viewchanger.go:364-391."""
        if view < self.curr_view:
            return
        if self.next_view == self.curr_view + 1:
            self.logger.debugf(
                "Node %d has already started view change with last view %d",
                self.self_id, self.curr_view,
            )
            self._check_timeout = True
            return
        self.next_view = self.curr_view + 1
        if self.metrics:
            self.metrics.next_view.set(self.next_view)
            self.metrics.count_complaints_sent.add(1)
        if self.vc_phases is not None:
            self.vc_phases.armed(self.next_view)
        self.requests_timer.stop_timers()
        self.comm.broadcast_consensus(ViewChange(next_view=self.next_view))
        self.logger.debugf(
            "Node %d started view change, last view is %d", self.self_id, self.curr_view
        )
        if stop_view:
            self.controller.abort_view(self.curr_view)
        self._start_view_change_time = self._last_tick
        self._check_timeout = True

    async def _process_view_change_msg(self, restore: bool) -> None:
        """viewchanger.go:393-431."""
        if (len(self.view_change_msgs.voted) == self.f + 1 and self.speed_up_view_change) or restore:
            self.logger.debugf(
                "Node %d is joining view change, last view is %d", self.self_id, self.curr_view
            )
            self._start_view_change(self.curr_view, True)
        if len(self.view_change_msgs.voted) < self.quorum - 1 and not restore:
            return
        if not self.speed_up_view_change:
            self.logger.debugf(
                "Node %d is joining view change (quorum), last view is %d",
                self.self_id, self.curr_view,
            )
            self._start_view_change(self.curr_view, True)
        if not restore:
            self.state.save(ViewChangeRecord(view_change=ViewChange(next_view=self.curr_view)))
        self.controller.abort_view(self.curr_view)
        self.curr_view = self.next_view
        if self.metrics:
            self.metrics.current_view.set(self.curr_view)
        if self.vc_phases is not None:
            # complaint quorum reached: this node committed to next view
            self.vc_phases.joined(self.curr_view)
        self.view_change_msgs.clear()
        self.view_data_msgs.clear()
        msg = self._prepare_view_data_msg()
        leader = self.get_leader()
        if leader == self.self_id:
            self.view_data_msgs.register_vote(self.self_id, msg)
        else:
            self.comm.send_consensus(leader, msg)
        if self.vc_phases is not None:
            self.vc_phases.viewdata_sent(self.curr_view)
        self.logger.debugf(
            "Node %d sent view data msg, with next view %d, to the new leader %d",
            self.self_id, self.curr_view, leader,
        )

    def _prepare_view_data_msg(self) -> SignedViewData:
        """viewchanger.go:433-456, fronted by the hot-standby cache: a
        pre-built message whose state key still matches the live
        checkpoint/ladder is returned as-is (the one-round-trip failover
        path); anything else is built fresh."""
        key = self._standby_state_key(self.curr_view)
        if self._standby_msg is not None and key == self._standby_key:
            self.standby_hits += 1
            if self.vc_phases is not None:
                self.vc_phases.note_standby(hit=True)
            return self._standby_msg
        return self._build_view_data_msg(self.curr_view)

    def _build_view_data_msg(self, next_view: int) -> SignedViewData:
        """The pipelined window adds the in-flight LADDER (every
        undelivered consecutive rung above the checkpoint)."""
        last_decision, last_decision_signatures = self.checkpoint.get()
        in_flight = self._get_in_flight(last_decision)
        prepared = self.in_flight.is_in_flight_prepared()
        more: list[Proposal] = []
        more_prepared: list[bool] = []
        ladder = self.in_flight.ladder()
        if ladder:
            last_seq = 0
            if last_decision is not None and last_decision.metadata:
                last_seq = decode(ViewMetadata, last_decision.metadata).latest_sequence
            # consecutive prefix starting right above the checkpoint; stale
            # rungs (<= last_seq, e.g. committed during the view change)
            # are dropped, gaps cut the ladder
            want = last_seq + 1
            rungs: list[tuple[Proposal, bool]] = []
            for seq, prop, prepped in ladder:
                if seq < want:
                    continue
                if seq != want:
                    break
                rungs.append((prop, prepped))
                want += 1
            if rungs:
                in_flight, prepared = rungs[0]
                more = [p for p, _ in rungs[1:]]
                more_prepared = [pr for _, pr in rungs[1:]]
            else:
                in_flight, prepared = None, False
        vd = ViewData(
            next_view=next_view,
            last_decision=last_decision,
            last_decision_signatures=list(last_decision_signatures),
            in_flight_proposal=in_flight,
            in_flight_prepared=prepared,
            in_flight_more=more,
            in_flight_more_prepared=more_prepared,
        )
        vd_bytes = encode(vd)
        sig = self.signer.sign(vd_bytes)
        return SignedViewData(raw_view_data=vd_bytes, signer=self.self_id, signature=sig)

    def _get_in_flight(self, last_decision: Proposal) -> Optional[Proposal]:
        """viewchanger.go:458-499."""
        in_flight = self.in_flight.in_flight_proposal()
        if in_flight is None:
            return None
        if not in_flight.metadata:
            self.logger.panicf("Node %d's in flight proposal metadata is not set", self.self_id)
        in_flight_md = decode(ViewMetadata, in_flight.metadata)
        if last_decision is None:
            self.logger.panicf("%d The given last decision is nil", self.self_id)
        if not last_decision.metadata:
            return in_flight  # first proposal after genesis
        last_md = decode(ViewMetadata, last_decision.metadata)
        if in_flight_md.latest_sequence == last_md.latest_sequence:
            return None  # not an actual in-flight proposal
        if (
            in_flight_md.latest_sequence + 1 == last_md.latest_sequence
            and self._committed_during_view_change is not None
            and self._committed_during_view_change.latest_sequence == last_md.latest_sequence
        ):
            self.logger.infof(
                "Node %d's in flight proposal sequence is %d while already committed decision %d "
                "(committed during the view change)",
                self.self_id, in_flight_md.latest_sequence, last_md.latest_sequence,
            )
            return None
        return in_flight

    # ------------------------------------------------------------------ viewdata (leader)

    async def _validate_view_data_msg(self, svd: SignedViewData, sender: int) -> bool:
        """viewchanger.go:501-533."""
        if self.get_leader() != self.self_id:
            self.logger.warnf(
                "Node %d got viewData from %d, but is not the next leader of view %d",
                self.self_id, sender, self.curr_view,
            )
            return False
        try:
            vd = decode(ViewData, svd.raw_view_data)
        except Exception as e:
            self.logger.errorf(
                "Node %d was unable to decode viewData message from %d: %s",
                self.self_id, sender, e,
            )
            return False
        if vd.next_view != self.curr_view:
            self.logger.warnf(
                "Node %d got viewData from %d with next view %d, but is in view %d",
                self.self_id, sender, vd.next_view, self.curr_view,
            )
            return False
        valid, last_decision_sequence = await self._check_last_decision(svd, sender)
        if not valid:
            self.logger.warnf(
                "Node %d got viewData from %d, but the check of the last decision didn't pass",
                self.self_id, sender,
            )
            return False
        try:
            validate_in_flight_ladder(vd, last_decision_sequence)
        except ValueError as e:
            self.logger.warnf(
                "Node %d got viewData from %d, but the in flight proposal is invalid: %s",
                self.self_id, sender, e,
            )
            return False
        return True

    def _extract_current_sequence(self) -> tuple[int, Proposal]:
        """viewchanger.go:668-679."""
        my_last_decision, _ = self.checkpoint.get()
        if not my_last_decision.metadata:
            return 0, my_last_decision
        md = decode(ViewMetadata, my_last_decision.metadata)
        return md.latest_sequence, my_last_decision

    async def _check_last_decision(
        self, svd: SignedViewData, sender: int
    ) -> tuple[bool, int]:
        """The checkLastDecision ladder (viewchanger.go:535-666)."""
        try:
            vd = decode(ViewData, svd.raw_view_data)
        except Exception:
            return False, 0
        if vd.last_decision is None:
            return False, 0

        my_sequence, my_last_decision = self._extract_current_sequence()

        if not vd.last_decision.metadata:  # genesis proposal
            if my_sequence > 0:
                return False, 0  # we are ahead
            return True, 0

        last_md = decode(ViewMetadata, vd.last_decision.metadata)
        if last_md.view_id >= vd.next_view:
            return False, 0
        if last_md.latest_sequence > my_sequence + 1:
            return False, 0  # future decision; might lack config to validate
        if last_md.latest_sequence < my_sequence:
            return False, 0  # past decision
        if last_md.latest_sequence == my_sequence:
            # same sequence: verify message signature + compare decisions
            if svd.signer != sender:
                return False, 0
            try:
                self.verifier.verify_signature(
                    Signature(signer=svd.signer, value=svd.signature, msg=svd.raw_view_data)
                )
            except Exception as e:
                self.logger.warnf(
                    "Node %d got viewData from %d, but signature is invalid: %s",
                    self.self_id, sender, e,
                )
                return False, 0
            if vd.last_decision != my_last_decision:
                self.logger.warnf(
                    "Node %d got viewData from %d at same sequence but last decisions differ",
                    self.self_id, sender,
                )
                return False, 0
            return True, last_md.latest_sequence

        if last_md.latest_sequence != my_sequence + 1:
            return False, 0

        # We are one behind: validate the decision and deliver it.
        try:
            await validate_last_decision(vd, self.quorum, self.n, self.verifier)
        except (ValueError, VerifyPlaneDown) as e:
            # VerifyPlaneDown: the verify plane is down, not the message —
            # drop it as unvalidatable; the sender's resend timer retries
            self.logger.warnf(
                "Node %d got viewData from %d, but the last decision is invalid: %s",
                self.self_id, sender, e,
            )
            return False, 0

        await self._deliver_decision(vd.last_decision, list(vd.last_decision_signatures))
        md = decode(ViewMetadata, vd.last_decision.metadata)
        self._committed_during_view_change = md

        if self._stopped:  # a reconfig may have stopped us during delivery
            return False, 0

        if svd.signer != sender:
            return False, 0
        try:
            self.verifier.verify_signature(
                Signature(signer=svd.signer, value=svd.signature, msg=svd.raw_view_data)
            )
        except Exception:
            return False, 0
        return True, last_md.latest_sequence

    async def _process_view_data_msg(self) -> None:
        """Leader: quorum of ViewData -> NewView (viewchanger.go:747-785)."""
        if len(self.view_data_msgs.voted) < self.quorum:
            return
        self.logger.debugf("Node %d got a quorum of viewData messages", self.self_id)
        messages = [decode(ViewData, v.msg.raw_view_data) for v in self.view_data_msgs.votes]
        ok, _ = check_in_flight_ladder(messages, self.f, self.quorum, self.n, self.verifier)
        if not ok:
            self.logger.debugf("Node %d checked the in flight and it was invalid", self.self_id)
            return
        if self.vc_phases is not None:
            # new leader: quorum of ViewData validated, NewView going out
            self.vc_phases.viewdata_quorum(self.curr_view)
        my_msg = self._prepare_view_data_msg()  # it might have changed by now
        signed_msgs = [my_msg]
        for vote in self.view_data_msgs.votes:
            if vote.sender == self.self_id:
                continue
            signed_msgs.append(vote.msg)
        nv = NewView(signed_view_data=signed_msgs)
        self.logger.debugf("Node %d is broadcasting a new view msg", self.self_id)
        self.comm.broadcast_consensus(nv)
        await self._process_msg(self.self_id, nv)  # also process at self
        self.view_data_msgs.clear()

    # ------------------------------------------------------------------ newview (all)

    async def _validate_new_view_msg(self, msg: NewView) -> tuple[bool, bool, bool]:
        """viewchanger.go:931-1095 — returns (valid, called_sync, called_deliver)."""
        seen: set[int] = set()
        valid_count = 0
        my_sequence, my_last_decision = self._extract_current_sequence()

        for svd in msg.signed_view_data:
            if svd.signer in seen:
                continue
            seen.add(svd.signer)
            try:
                vd = decode(ViewData, svd.raw_view_data)
            except Exception as e:
                self.logger.errorf("Unable to decode viewData in newView: %s", e)
                return False, False, False
            if vd.next_view != self.curr_view:
                self.logger.warnf(
                    "Node %d processing newView: nextView is %d while currView is %d",
                    self.self_id, vd.next_view, self.curr_view,
                )
                return False, False, False
            if vd.last_decision is None:
                return False, False, False

            if not vd.last_decision.metadata:  # genesis
                if my_sequence > 0:
                    try:
                        validate_in_flight_ladder(vd, 0)
                    except ValueError:
                        return False, False, False
                    valid_count += 1
                    continue
                try:
                    self.verifier.verify_signature(
                        Signature(signer=svd.signer, value=svd.signature, msg=svd.raw_view_data)
                    )
                    validate_in_flight_ladder(vd, 0)
                except Exception:
                    return False, False, False
                valid_count += 1
                continue

            last_md = decode(ViewMetadata, vd.last_decision.metadata)
            if last_md.view_id >= vd.next_view:
                return False, False, False

            if last_md.latest_sequence > my_sequence + 1:
                # future decision — sync
                self.synchronizer.sync()
                return True, True, False

            if last_md.latest_sequence < my_sequence:
                try:
                    validate_in_flight_ladder(vd, last_md.latest_sequence)
                except ValueError:
                    return False, False, False
                valid_count += 1
                continue

            if last_md.latest_sequence == my_sequence:
                try:
                    self.verifier.verify_signature(
                        Signature(signer=svd.signer, value=svd.signature, msg=svd.raw_view_data)
                    )
                except Exception:
                    return False, False, False
                if vd.last_decision != my_last_decision:
                    return False, False, False
                try:
                    validate_in_flight_ladder(vd, last_md.latest_sequence)
                except ValueError:
                    return False, False, False
                valid_count += 1
                continue

            if last_md.latest_sequence != my_sequence + 1:
                return False, False, False

            # one behind — validate, deliver, then verify message sig
            try:
                await validate_last_decision(vd, self.quorum, self.n, self.verifier)
            except (ValueError, VerifyPlaneDown) as e:
                self.logger.warnf("newView last decision invalid: %s", e)
                return False, False, False
            await self._deliver_decision(
                vd.last_decision, list(vd.last_decision_signatures)
            )
            if self._stopped:
                return False, False, False
            try:
                self.verifier.verify_signature(
                    Signature(signer=svd.signer, value=svd.signature, msg=svd.raw_view_data)
                )
                validate_in_flight_ladder(vd, last_md.latest_sequence)
            except Exception:
                return False, False, False
            return True, False, True

        if valid_count < self.quorum:
            self.logger.warnf(
                "Node %d processing newView: only %d valid view data messages (quorum %d)",
                self.self_id, valid_count, self.quorum,
            )
            return False, False, False
        return True, False, False

    async def _process_new_view_msg(self, msg: NewView) -> None:
        """viewchanger.go:1110-1167."""
        valid, called_sync, called_deliver = await self._validate_new_view_msg(msg)
        while called_deliver:
            self.logger.debugf("Node %d processed newView and delivered a proposal", self.self_id)
            valid, called_sync, called_deliver = await self._validate_new_view_msg(msg)
        if not valid:
            self.logger.warnf("Node %d processing newView: message invalid", self.self_id)
            return
        if called_sync:
            return

        messages = [
            decode(ViewData, svd.raw_view_data) for svd in msg.signed_view_data
        ]
        ok, agreed = check_in_flight_ladder(
            messages, self.f, self.quorum, self.n, self.verifier
        )
        if not ok:
            self.logger.debugf("In flight check by node %d did not pass", self.self_id)
            return
        # commit every agreed in-flight proposal, in sequence order: each
        # commit advances the checkpoint, satisfying the next rung's
        # last-decision precondition (single-rung ladders are the
        # reference-shaped case, viewchanger.go:1110-1167)
        for in_flight_proposal in agreed:
            if self._stopped:
                return
            # skip rungs this node already delivered: with pipelining a node
            # can hold commit quorums (and a checkpoint) SEVERAL sequences
            # past the quorum's reported max — the single-slot protocol
            # could only ever be one ahead, which _commit_in_flight_proposal
            # handles; two-plus ahead would hit its sequence panic
            rung_md = decode(ViewMetadata, in_flight_proposal.metadata)
            my_sequence, _ = self._extract_current_sequence()
            if rung_md.latest_sequence <= my_sequence:
                self.logger.debugf(
                    "Node %d already delivered rung %d, skipping its in-flight commit",
                    self.self_id, rung_md.latest_sequence,
                )
                continue
            if not await self._commit_in_flight_proposal(in_flight_proposal):
                self.logger.warnf(
                    "Node %d was unable to commit the in flight proposal, not changing the view",
                    self.self_id,
                )
                return

        my_sequence, _ = self._extract_current_sequence()
        self.state.save(
            NewViewRecord(
                metadata=ViewMetadata(view_id=self.curr_view, latest_sequence=my_sequence)
            )
        )
        if self._stopped:
            return
        self.real_view = self.curr_view
        if self.metrics:
            self.metrics.real_view.set(self.real_view)
        if self.vc_phases is not None:
            # NewView validated + persisted; first_commit starts here
            self.vc_phases.newview_done(self.curr_view)
        self.nvs.clear()
        self.controller.view_changed(self.curr_view, my_sequence + 1)
        # the FLIP: the new view is installed and the pool still holds the
        # backlog that stalled through the depose — fast-forward its
        # forward timers so it reaches the new leader's first deep windows
        # instead of waiting out a full request timeout per window
        self.requests_timer.restart_timers(flip=True)
        self._check_timeout = False
        self._back_off_factor = 1

    async def _deliver_decision(self, proposal: Proposal, signatures: list[Signature]) -> None:
        """viewchanger.go:1169-1184."""
        reconfig = await self.application.deliver(proposal, signatures)
        if reconfig.in_latest_decision:
            self.close()
        remove_delivered_requests(
            self.requests_timer, self.verifier.requests_from_proposal(proposal), self.logger
        )
        self.pruner.maybe_prune_revoked_requests()

    # ------------------------------------------------------------------ in-flight commit

    async def _commit_in_flight_proposal(self, proposal: Optional[Proposal]) -> bool:
        """Spin up a special PREPARED View with self as leader to commit the
        agreed in-flight proposal (viewchanger.go:1186-1306)."""
        my_last_decision, _ = self.checkpoint.get()
        if proposal is None:
            self.logger.panicf("The in flight proposal is nil")
        proposal_md = decode(ViewMetadata, proposal.metadata)

        if my_last_decision.metadata:
            last_md = decode(ViewMetadata, my_last_decision.metadata)
            if last_md.latest_sequence == proposal_md.latest_sequence:
                if my_last_decision != proposal:
                    self.logger.warnf(
                        "Node %d last decision differs from in-flight proposal at same sequence",
                        self.self_id,
                    )
                    return False
                return True  # already decided on it
            if last_md.latest_sequence != proposal_md.latest_sequence - 1:
                self.logger.panicf(
                    "Node %d got in-flight proposal with sequence %d while last decision is %d",
                    self.self_id, proposal_md.latest_sequence, last_md.latest_sequence,
                )

        decider = _InFlightDecider(self)
        view = View(
            retrieve_checkpoint=self.checkpoint.get,
            decisions_per_leader=self.decisions_per_leader,
            self_id=self.self_id,
            n=self.n,
            nodes_list=self.nodes_list,
            number=proposal_md.view_id,
            leader_id=self.self_id,  # so no byzantine leader causes a complain
            quorum=self.quorum,
            decider=decider,
            failure_detector=decider,
            synchronizer=decider,
            logger=self.logger,
            comm=self.comm,
            verifier=self.verifier,
            signer=self.signer,
            membership_notifier=None,
            proposal_sequence=proposal_md.latest_sequence,
            decisions_in_view=0,
            state=self.state,
            in_msg_q_size=self.in_msg_q_size,
            view_sequences=self.view_sequences,
            metrics_view=self.metrics_view,
            metrics_blacklist=self.metrics_blacklist,
        )
        view.phase = PREPARED
        view.in_flight_proposal = proposal
        # The normal path populates in_flight_requests at proposal verify
        # time (view._process_pre_prepare); this special view skips that
        # phase, so without this the decide() hand-off prunes NOTHING from
        # the request pool on ANY node — the deposed leader keeps the
        # committed batch pooled and forwards it to the new leader (within
        # one flip-drain tick since ISSUE 15), which re-proposes it at a
        # fresh sequence: measured duplicate delivery under spurious-depose
        # churn at deep overload (mux ShardStreamViolation at 1600/s).
        view.in_flight_requests = self.verifier.requests_from_proposal(proposal)
        view.my_proposal_sig = self.signer.sign_proposal(proposal, b"")
        view.last_broadcast_sent = Commit(
            view=view.number,
            seq=view.proposal_sequence,
            digest=proposal_digest(proposal),
            signature=Signature(
                signer=view.my_proposal_sig.signer,
                value=view.my_proposal_sig.value,
                msg=view.my_proposal_sig.msg,
            ),
        )

        loop = asyncio.get_running_loop()
        self._in_flight_decide = loop.create_future()
        self._in_flight_sync = loop.create_future()
        timeout_fut: asyncio.Future = loop.create_future()

        # wait two ticks before starting (viewchanger.go:1262-1264)
        ticks_before_start = 2
        started = False

        def on_tick(now: float) -> None:
            nonlocal ticks_before_start, started
            self._last_tick = now
            if not started:
                ticks_before_start -= 1
                if ticks_before_start <= 0:
                    started = True
                    self._in_flight_view = view
                    view.start()
                    self.logger.debugf(
                        "Node %d started a view %d for the in flight proposal",
                        self.self_id, view.number,
                    )
                return
            if self._check_if_timeout(now) and not timeout_fut.done():
                timeout_fut.set_result(True)

        self._in_flight_tick_cb = on_tick
        try:
            done, _ = await asyncio.wait(
                [self._in_flight_decide, self._in_flight_sync, timeout_fut],
                return_when=asyncio.FIRST_COMPLETED,
            )
            if self._in_flight_decide.done() and self._in_flight_decide.result():
                self.logger.infof(
                    "In-flight view %d with latest sequence %d has committed a decision",
                    view.number, view.proposal_sequence,
                )
                return True
            if self._in_flight_sync.done():
                self.logger.infof(
                    "In-flight view %d with latest sequence %d has asked to sync",
                    view.number, view.proposal_sequence,
                )
                return False
            self.logger.infof(
                "Timeout expired waiting on in-flight view %d to commit %d",
                view.number, view.proposal_sequence,
            )
            return False
        finally:
            self._in_flight_tick_cb = None
            self._in_flight_decide = None
            self._in_flight_sync = None
            if self._in_flight_view is not None:
                await self._in_flight_view.abort()
                self._in_flight_view = None
