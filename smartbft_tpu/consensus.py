"""The Consensus facade: composition root and public API.

Re-design of /root/reference/pkg/consensus/consensus.go:28-523.  Validates
configuration, wires ViewChanger / StateCollector / Controller / Pool /
Batcher / HeartbeatMonitor, computes the start view/seq from the checkpoint
metadata plus WAL-restored ViewChange/NewView records, and runs the reconfig
loop: when a delivered decision or a sync carries a reconfiguration, stop
all components, swap config and node set, rebuild, restart.

All timing flows through one tick-driven Scheduler; production attaches a
WallClockDriver, tests advance it manually.
"""

from __future__ import annotations

import asyncio
from typing import Optional, Sequence

from . import api as bft_api
from .codec import decode
from .config import Configuration
from .core.batcher import BatchBuilder
from .core.controller import Controller
from .core.heartbeat import FOLLOWER, LEADER, HeartbeatMonitor
from .core.misbehavior import MisbehaviorTable
from .core.pool import Pool, PoolOptions
from .core.proposer import ProposalMaker
from .core.state import PersistedState
from .core.statecollector import StateCollector
from .core.util import InFlightData
from .core.view import ViewSequencesHolder
from .core.viewchanger import ViewChanger
from .messages import Message, ViewMetadata
from .metrics import MetricsBundle
from .types import Checkpoint, Proposal, Reconfig, Signature, SyncResponse
from .utils.clock import Scheduler, Ticker, WallClockDriver
from .utils.tasks import create_logged_task


def _scaled_rtt_fn(mult: float, comm):
    """An ``mult * comm.rtt_seconds()`` provider when ``mult`` is armed
    and the transport measures RTT (SocketComm does); None otherwise —
    consumers keep their configured constants, and each clamps the
    derived value into its own [floor, constant]."""
    rtt_fn = getattr(comm, "rtt_seconds", None)
    if mult <= 0 or rtt_fn is None:
        return None

    def derive():
        rtt = rtt_fn()
        return None if rtt is None else mult * rtt

    return derive


class Consensus:
    """Public entry points: start / stop / submit_request / handle_message /
    handle_request / get_leader_id (consensus.go:28-68,108,283-317)."""

    def __init__(
        self,
        *,
        config: Configuration,
        application: bft_api.Application,
        assembler: bft_api.Assembler,
        wal: bft_api.WriteAheadLog,
        wal_initial_content: Sequence[bytes],
        comm: bft_api.Comm,
        signer: bft_api.Signer,
        verifier: bft_api.Verifier,
        membership_notifier: Optional[bft_api.MembershipNotifier],
        request_inspector: bft_api.RequestInspector,
        synchronizer: bft_api.Synchronizer,
        logger: bft_api.Logger,
        metadata: ViewMetadata,
        last_proposal: Proposal,
        last_signatures: Sequence[Signature],
        scheduler: Optional[Scheduler] = None,
        metrics: Optional[MetricsBundle] = None,
        viewchanger_tick_interval: float = 1.0,
        heartbeat_tick_interval: float = 1.0,
        recorder=None,
    ):
        self.config = config
        self.application = application
        self.assembler = assembler
        self.wal = wal
        self.wal_initial_content = list(wal_initial_content)
        self.comm = comm
        self.signer = signer
        self.verifier = verifier
        self.membership_notifier = membership_notifier
        self.request_inspector = request_inspector
        self.synchronizer = synchronizer
        self.logger = logger
        self.metadata = metadata
        self.last_proposal = last_proposal
        self.last_signatures = list(last_signatures)
        self.metrics = metrics or MetricsBundle()
        self.scheduler = scheduler if scheduler is not None else Scheduler()
        # flight recorder (ISSUE 12): the embedder passes an
        # obs.TraceRecorder to trace this replica; the default is a
        # disabled one of this replica's own, which keeps every
        # instrumentation site at one attribute read until a profiler
        # session switches it on.  The VC phase tracker rides the SAME
        # injectable clock as every other timer and outlives
        # reconfig-rebuilt components.
        from .obs import ViewChangePhaseTracker, standby

        self.recorder = standby(recorder, node=f"n{config.self_id}")
        self.vc_phases = ViewChangePhaseTracker(
            clock=self.scheduler.now, node=f"n{config.self_id}",
            recorder=self.recorder, metrics=self.metrics.view_change,
        )
        # per-sender misbehavior accounting (ISSUE 18): node-LOCAL — fed
        # by the verifier's per-signer invalid-verdict attribution
        # (configure_misbehavior seam), read by the Controller to shed
        # shunned senders' votes at intake and revoke their forwarded-
        # request admission bypass; decayed on a ticker (redemption).
        self.misbehavior = MisbehaviorTable(
            self_id=config.self_id,
            shun_threshold=config.misbehavior_shun_threshold,
            logger=logger,
            recorder=self.recorder,
        )
        # committed-state read hook (ISSUE 19): the embedder registers a
        # callable (key: str) -> Optional[tuple[bytes, int, bytes, int]]
        # = (value, height, state_digest, anchor_height) answered from
        # COMMITTED state only.  The facade exposes it (read_committed)
        # so read-plane callers hold one handle per replica; consensus
        # itself never calls it — reads bypass the pool/proposer/verify
        # plane entirely, that is the whole point.
        self.read_hook = None
        self._own_scheduler = scheduler is None
        self._clock_driver: Optional[WallClockDriver] = None
        self.viewchanger_tick_interval = viewchanger_tick_interval
        self.heartbeat_tick_interval = heartbeat_tick_interval

        self.nodes: list[int] = []
        self.num_nodes = 0
        self._node_set: set[int] = set()

        self.pool: Optional[Pool] = None
        self.controller: Optional[Controller] = None
        self.view_changer: Optional[ViewChanger] = None
        self.collector: Optional[StateCollector] = None
        self.state: Optional[PersistedState] = None
        self.in_flight: Optional[InFlightData] = None
        self.checkpoint = Checkpoint()

        self._running = False
        self._stopping = False
        self._reconfig_queue: asyncio.Queue = asyncio.Queue()
        self._run_task: Optional[asyncio.Task] = None
        self._tickers: list[Ticker] = []
        self._restore_view_change = False

    # ------------------------------------------------------------------ SPI glue

    def complain(self, view_num: int, stop_view: bool) -> None:
        """FailureDetector for the Controller/View (consensus.go:70-74)."""
        if self.view_changer is not None:
            self.view_changer.start_view_change(view_num, stop_view)

    @property
    def blocking_deliver(self) -> bool:
        """Forward the embedder app's deliver-blocking capability so the
        controller can skip the executor offload for in-memory delivers."""
        return getattr(self.application, "blocking_deliver", True)

    def deliver(self, proposal: Proposal, signatures) -> Reconfig:
        """Application wrapper that detects reconfig (consensus.go:76-84).
        Runs on an executor thread — route reconfigs back thread-safely."""
        reconfig = self.application.deliver(proposal, signatures)
        if reconfig.in_latest_decision:
            self.logger.debugf("Detected a reconfig in deliver")
            self._loop.call_soon_threadsafe(self._reconfig_queue.put_nowait, reconfig)
        return reconfig

    def sync(self) -> SyncResponse:
        """Synchronizer wrapper that detects reconfig (consensus.go:86-100).
        Runs on an executor thread."""
        sync_response = self.synchronizer.sync()
        if sync_response.reconfig.in_latest_decision:
            self.logger.debugf("Detected a reconfig in sync")
            self._loop.call_soon_threadsafe(
                self._reconfig_queue.put_nowait,
                Reconfig(
                    in_latest_decision=True,
                    current_nodes=sync_response.reconfig.current_nodes,
                    current_config=sync_response.reconfig.current_config,
                ),
            )
        return sync_response

    # ------------------------------------------------------------------ public

    def get_leader_id(self) -> int:
        """consensus.go:103-107 — zero when not running."""
        if not self._running or self.controller is None:
            return 0
        return self.controller.get_leader_id()

    def _wire_verify_plane(self) -> None:
        """Arm the verifier's verify-plane fault machinery from this node's
        Configuration (launch deadline, retry budget, breaker threshold,
        probe cadence) and attach the TPU metrics bundle, so breaker
        transitions are counted where the embedder can see them.  The
        coalescer fills only unset pieces (a shared cross-replica coalescer
        keeps its explicit settings); verifiers without the seam no-op."""
        configure = getattr(self.verifier, "configure_fault_policy", None)
        if configure is not None:
            from .crypto.provider import VerifyFaultPolicy

            try:
                configure(
                    policy=VerifyFaultPolicy.from_config(self.config),
                    metrics=self.metrics.tpu,
                )
            except Exception as e:  # noqa: BLE001 — wiring must not kill start
                self.logger.warnf("verify-plane fault wiring failed: %r", e)
        # per-sender misbehavior accounting (ISSUE 18): verifiers with the
        # seam feed every per-signer invalid verdict into this node's
        # MisbehaviorTable; verifiers without it stay attribution-only.
        configure_misbehavior = getattr(
            self.verifier, "configure_misbehavior", None)
        if configure_misbehavior is not None:
            try:
                configure_misbehavior(self.misbehavior)
            except Exception as e:  # noqa: BLE001 — wiring must not kill start
                self.logger.warnf("misbehavior-table wiring failed: %r", e)
        # occupancy-aware flush gating (verify_flush_hold): wired before
        # the mesh so a graduated engine's first waves already gate.
        # configure_hold keeps explicit constructor holds (the shared-
        # coalescer contract, like the fault policy).
        configure_hold = getattr(self.verifier, "configure_flush_hold", None)
        if configure_hold is not None:
            try:
                configure_hold(self.config.verify_flush_hold)
            except Exception as e:  # noqa: BLE001 — wiring must not kill start
                self.logger.warnf("verify flush-hold wiring failed: %r", e)
        # mesh graduation (verify_mesh_devices > 0): swap the coalescer's
        # engine onto an N-device mesh — 1D batch-axis or (topology "2d")
        # the seq x vote quorum mesh — idempotent across colocated
        # replicas sharing one coalescer and across reconfigs; an
        # unbuildable mesh downgrades loudly inside the provider (counted)
        # instead of raising, so only unexpected wiring errors land here.
        if self.config.verify_mesh_devices > 0:
            configure_mesh = getattr(self.verifier, "configure_verify_mesh",
                                     None)
            if configure_mesh is not None:
                # a pre-topology provider implementation gets the width
                # alone — probed by SIGNATURE, never by catching
                # TypeError (a TypeError raised inside mesh construction
                # must surface in the log, not silently downgrade a "2d"
                # config to the 1D mesh)
                kwargs = {"metrics": self.metrics.tpu}
                try:
                    import inspect

                    params = inspect.signature(configure_mesh).parameters
                    if "topology" in params:
                        kwargs["topology"] = self.config.verify_mesh_topology
                except (TypeError, ValueError):
                    # unsignaturable callable (C extension, mock): assume
                    # the current provider surface
                    kwargs["topology"] = self.config.verify_mesh_topology
                try:
                    configure_mesh(self.config.verify_mesh_devices, **kwargs)
                except Exception as e:  # noqa: BLE001 — ditto
                    self.logger.warnf("verify-mesh wiring failed: %r", e)

    async def start(self) -> None:
        """consensus.go:108-165."""
        self._loop = asyncio.get_running_loop()
        self.validate_configuration(self.comm.nodes())
        self._wire_verify_plane()
        # WAL persistence spans (ISSUE 13): the log records wal.append /
        # wal.fsync durations into this replica's recorder (and its own
        # bounded histograms either way); WALs without the seam no-op
        attach_wal_recorder = getattr(self.wal, "attach_recorder", None)
        if attach_wal_recorder is not None:
            attach_wal_recorder(self.recorder)

        self._set_nodes(self.comm.nodes())
        self.in_flight = InFlightData()
        self.state = PersistedState(
            self.in_flight, self.wal_initial_content, self.logger, self.wal,
            group_commit=self.config.wal_group_commit,
        )
        self.checkpoint.set(self.last_proposal, self.last_signatures)

        self._create_components()
        self._create_pool()
        self._continue_create_components()

        view, seq, dec = self._set_view_and_seq(
            self.metadata.view_id,
            self.metadata.latest_sequence,
            self.metadata.decisions_in_view,
        )

        self._run_task = create_logged_task(
            self._run(), name=f"consensus-{self.config.self_id}",
            logger=self.logger,
        )

        if self._own_scheduler:
            self._clock_driver = WallClockDriver(self.scheduler)
            self._clock_driver.start()

        await self._start_components(view, seq, dec, config_sync=True)
        self._running = True

    async def _run(self) -> None:
        """Reconfig/stop loop (consensus.go:167-184)."""
        try:
            while True:
                reconfig = await self._reconfig_queue.get()
                if reconfig is None:
                    return
                await self._reconfig(reconfig)
                if self._stopping:
                    return
        finally:
            self.logger.infof("Exiting")
            self._running = False

    async def _reconfig(self, reconfig: Reconfig) -> None:
        """consensus.go:186-253."""
        self.logger.debugf("Starting reconfig")
        await self.view_changer.stop()
        await self.controller.stop(pool_pause=True)
        self.collector.stop()
        self._stop_tickers()

        if self.config.self_id not in reconfig.current_nodes:
            self.logger.infof("Evicted in reconfiguration, shutting down")
            self._stopping = True
            return

        if reconfig.current_config is not None:
            self.config = reconfig.current_config.with_node_locals(self.config)
        try:
            self.validate_configuration(list(reconfig.current_nodes))
        except ValueError as e:
            if "does not contain the SelfID" in str(e):
                self._stopping = True
                return
            raise

        self._set_nodes(list(reconfig.current_nodes))
        self._wire_verify_plane()  # the reconfig may carry new verify knobs
        self._create_components()
        self.pool.change_options(
            self.controller,
            PoolOptions(
                queue_size=self.pool._opts.queue_size,
                forward_timeout=self.config.request_forward_timeout,
                complain_timeout=self.config.request_complain_timeout,
                auto_remove_timeout=self.config.request_auto_remove_timeout,
                request_max_bytes=self.config.request_max_bytes,
                submit_timeout=self.config.request_pool_submit_timeout,
                admission_high_water=self.config.admission_high_water,
                forward_timeout_fn=self._forward_timeout_fn(),
                flip_drain_limit=self._flip_drain_limit(),
                handover_limit=self._handover_limit(),
            ),
        )
        self._continue_create_components()

        proposal, _ = self.checkpoint.get()
        md = decode(ViewMetadata, proposal.metadata) if proposal.metadata else ViewMetadata()
        view, seq, dec = self._set_view_and_seq(
            md.view_id, md.latest_sequence, md.decisions_in_view
        )
        await self._start_components(view, seq, dec, config_sync=False)
        self.pool.restart_timers()
        self.metrics.consensus.count_consensus_reconfig.add(1)
        self.logger.debugf("Reconfig is done")

    async def stop(self) -> None:
        """consensus.go:283-291."""
        self._stopping = True
        if self.view_changer is not None:
            await self.view_changer.stop()
        if self.controller is not None:
            await self.controller.stop()
        if self.collector is not None:
            self.collector.stop()
        self._stop_tickers()
        if self._clock_driver is not None:
            await self._clock_driver.stop()
            self._clock_driver = None
        self._reconfig_queue.put_nowait(None)
        if self._run_task is not None:
            await self._run_task
            self._run_task = None
        self._running = False

    def handle_message(self, sender: int, m: Message) -> None:
        """consensus.go:293-300 — membership filter then dispatch."""
        if sender not in self._node_set:
            self.logger.warnf("Received message from unexpected node %d", sender)
            return
        if self.controller is not None:
            self.controller.process_messages(sender, m)

    async def handle_message_async(self, sender: int, m: Message) -> None:
        """Async intake: lets a backpressure-configured cluster block the
        delivering transport task on full component inboxes (the
        reference's full-channel sender semantics, view.go:190)."""
        if sender not in self._node_set:
            self.logger.warnf("Received message from unexpected node %d", sender)
            return
        if self.controller is not None:
            await self.controller.process_messages_async(sender, m)

    def handle_message_batch(self, items) -> None:
        """Wave-batched intake: one transport tick's (sender, msg) pairs
        dispatched in a single call — consecutive view-bound runs register
        into the view as one wave (see Controller.process_messages_batch)."""
        filtered = self._filter_members(items)
        if filtered and self.controller is not None:
            self.controller.process_messages_batch(filtered)

    async def handle_message_batch_async(self, items) -> None:
        """Backpressure-capable mirror of :meth:`handle_message_batch`."""
        filtered = self._filter_members(items)
        if filtered and self.controller is not None:
            await self.controller.process_messages_batch_async(filtered)

    def _filter_members(self, items) -> list:
        filtered = []
        for sender, m in items:
            if sender not in self._node_set:
                self.logger.warnf("Received message from unexpected node %d", sender)
                continue
            filtered.append((sender, m))
        return filtered

    async def handle_request(self, sender: int, req: bytes):
        """Returns the pool-shed exception (admission / submit-timeout)
        when the forwarded request was refused by the overload machinery —
        the socket transport turns it into a structured REJECT frame for
        the forwarder — and None otherwise."""
        if self.controller is not None:
            return await self.controller.handle_request(sender, req)
        return None

    async def submit_request(self, req: bytes, *, internal: bool = False) -> None:
        """consensus.go:309-317.  ``internal`` marks a control-plane
        submission (reshard barrier, operator command): it bypasses the
        client-facing admission gate — under sustained overload the gate
        would otherwise shed the very commands that remediate the
        overload (a scale-out's barrier, a pool-resizing reconfig) —
        while still riding the pool's hard capacity bound and submit
        deadline."""
        if self.get_leader_id() == 0:
            raise RuntimeError("no leader")
        await self.controller.submit_request(req, forwarded=internal)

    def misbehavior_snapshot(self) -> dict:
        """This node's per-sender misbehavior accounting (ISSUE 18):
        lifetime cause counts, decayed shun scores, the current shun set,
        intake sheds, and shared-blacklist corroborations — read by the
        chaos oracles and the bench `byzantine` row."""
        return self.misbehavior.snapshot()

    def read_committed(self, key: str):
        """Read-plane entry (ISSUE 19): the embedder-registered committed-
        state read, or None when no hook is installed / nothing committed
        for ``key``.  Returns (value, height, state_digest, anchor_height)
        — the stamp a quorum-read client matches ``f+1`` ways and a
        follower-read client checks against its staleness bound.  Never
        touches the pool, the proposer, or the verify plane."""
        if self.read_hook is None:
            return None
        return self.read_hook(key)

    def delivery_frontier(self) -> dict:
        """The committed delivery frontier this replica has reached: the
        latest delivered sequence (checkpoint metadata), the view it
        belongs to, and the commit inter-arrival EWMA — the freshness
        reference a read client compares reply heights against (empty
        before start)."""
        if self.controller is None:
            return {}
        return self.controller.delivery_frontier()

    def pool_occupancy(self) -> dict:
        """This node's request-pool backpressure snapshot (empty before
        start).  The sharded front door (shard.ShardSet) reads this from
        each shard's submit target to expose one combined submit/
        backpressure surface over the per-shard pools."""
        if self.pool is None:
            return {}
        return self.pool.occupancy()

    def pool_pending_infos(self) -> list:
        """RequestInfos still pooled on this node (empty before start) —
        the per-shard drain probe of a live reshard (shard front doors
        union this over a shard's replicas to decide when a moved
        key-range has fully drained)."""
        if self.pool is None:
            return []
        return self.pool.pending_infos()

    # ------------------------------------------------------------------ wiring

    def validate_configuration(self, nodes: list[int]) -> None:
        """consensus.go:342-364."""
        self.config.validate()
        node_set = set()
        for val in nodes:
            if val == 0:
                raise ValueError(f"nodes contains node id 0 which is not permitted, nodes: {nodes}")
            node_set.add(val)
        if self.config.self_id not in node_set:
            raise ValueError(
                f"nodes does not contain the SelfID: {self.config.self_id}, nodes: {nodes}"
            )
        if len(node_set) != len(nodes):
            raise ValueError(f"nodes contains duplicate IDs, nodes: {nodes}")

    def _set_nodes(self, nodes: list[int]) -> None:
        self.nodes = sorted(nodes)
        self.num_nodes = len(nodes)
        self._node_set = set(nodes)

    def _create_components(self) -> None:
        """consensus.go:387-450."""
        self.view_changer = ViewChanger(
            self_id=self.config.self_id,
            n=self.num_nodes,
            nodes_list=self.nodes,
            leader_rotation=self.config.leader_rotation,
            # window granularity pre-multiplies by the window depth so every
            # get_leader_id / blacklist computation stays reference-shaped
            decisions_per_leader=self.config.effective_decisions_per_leader,
            speed_up_view_change=self.config.speed_up_view_change,
            logger=self.logger,
            signer=self.signer,
            verifier=self.verifier,
            checkpoint=self.checkpoint,
            in_flight=self.in_flight,
            state=self.state,
            resend_timeout=self.config.view_change_resend_interval,
            view_change_timeout=self.config.view_change_timeout,
            in_msg_q_size=self.config.incoming_message_buffer_size,
            backpressure=self.config.inbox_backpressure,
            metrics_view_change=self.metrics.view_change,
            metrics_blacklist=self.metrics.blacklist,
            metrics_view=self.metrics.view,
            vc_phases=self.vc_phases,
            recorder=self.recorder,
            # debounce clock for the event-driven standby prebuild
            scheduler=self.scheduler,
        )
        self.collector = StateCollector(
            self_id=self.config.self_id,
            n=self.num_nodes,
            logger=self.logger,
            collect_timeout=self.config.collect_timeout,
            scheduler=self.scheduler,
            # adaptive detection (ISSUE 15): the state-fetch leg of a
            # failover gives up on missing peers at measured network
            # scale instead of always burning the constant
            collect_timeout_fn=self._rtt_scaled_fn(),
        )
        view_sequences = ViewSequencesHolder()
        self.controller = Controller(
            self_id=self.config.self_id,
            n=self.num_nodes,
            nodes_list=self.nodes,
            leader_rotation=self.config.leader_rotation,
            decisions_per_leader=self.config.effective_decisions_per_leader,
            request_pool=self.pool,  # set for real in _create_pool on first start
            batcher=None,
            leader_monitor=None,
            verifier=self.verifier,
            logger=self.logger,
            assembler=self.assembler,
            application=self,  # facade: detects reconfigs (consensus.go:430)
            synchronizer=self,  # facade: detects reconfigs
            signer=self.signer,
            request_inspector=self.request_inspector,
            proposer_builder=None,
            checkpoint=self.checkpoint,
            failure_detector=self,  # facade: complain -> view changer
            view_changer=self.view_changer,
            collector=self.collector,
            state=self.state,
            in_flight=self.in_flight,
            comm=self.comm,
            view_sequences=view_sequences,
            metrics_view=self.metrics.view,
            metrics_consensus=self.metrics.consensus,
            recorder=self.recorder,
            vc_phases=self.vc_phases,
            # the commit inter-arrival EWMA lives in scheduler time — the
            # same domain as the heartbeat/complain timers it feeds
            clock=self.scheduler.now,
            # intake-side shun enforcement (ISSUE 18): survives reconfig
            # rebuilds because the table lives on the facade
            misbehavior=self.misbehavior,
        )
        # ViewChanger wiring (consensus.go:445-450,466-470)
        self.view_changer.application = self.controller.deliver
        self.view_changer.comm = self.controller
        self.view_changer.synchronizer = self.controller
        self.view_changer.controller = self.controller
        self.view_changer.pruner = self.controller
        self.view_changer.view_sequences = view_sequences

        self.controller.proposer_builder = self._proposal_maker(view_sequences)

    def _proposal_maker(self, view_sequences: ViewSequencesHolder) -> ProposalMaker:
        """consensus.go:319-340."""
        return ProposalMaker(
            decisions_per_leader=self.config.effective_decisions_per_leader,
            checkpoint=self.checkpoint,
            state=self.state,
            comm=self.controller,
            decider=self.controller,
            logger=self.logger,
            metrics_blacklist=self.metrics.blacklist,
            metrics_view=self.metrics.view,
            signer=self.signer,
            membership_notifier=self.membership_notifier,
            self_id=self.config.self_id,
            synchronizer=self.controller,
            failure_detector=self,
            verifier=self.verifier,
            n=self.num_nodes,
            nodes_list=self.nodes,
            in_msg_q_size=self.config.incoming_message_buffer_size,
            view_sequences=view_sequences,
            pipeline_depth=self.config.pipeline_depth,
            backpressure=self.config.inbox_backpressure,
            recorder=self.recorder,
        )

    def _forward_timeout_fn(self):
        """The RTT-derived forward-timeout provider (ISSUE 14
        satellite)."""
        return _scaled_rtt_fn(
            self.config.request_forward_rtt_multiplier, self.comm)

    def _rtt_scaled_fn(self):
        """The adaptive-detection RTT provider (ISSUE 15): shared by the
        heartbeat monitor's complain-timer derivation and the state
        collector's collect-timeout derivation — both legs of the same
        failover path."""
        return _scaled_rtt_fn(self.config.heartbeat_rtt_multiplier, self.comm)

    def _flip_drain_limit(self) -> int:
        """The flip-time backlog fast-forward budget in REQUESTS: enough
        to fill flip_drain_windows deep windows of the new view at once
        (ISSUE 15)."""
        return (self.config.flip_drain_windows
                * self.config.pipeline_depth
                * self.config.request_batch_max_count)

    def _handover_limit(self) -> int:
        """A rotation's hand-over budget in REQUESTS: the new leader's
        first window, and nothing where flip_drain_windows is 0
        (ISSUE 31; PoolOptions.handover_limit says why one)."""
        return (min(self.config.flip_drain_windows, 1)
                * self.config.pipeline_depth
                * self.config.request_batch_max_count)

    def _create_pool(self) -> None:
        """consensus.go:139-151."""
        self.pool = Pool(
            self.logger,
            self.request_inspector,
            self.controller,
            PoolOptions(
                queue_size=self.config.request_pool_size,
                forward_timeout=self.config.request_forward_timeout,
                complain_timeout=self.config.request_complain_timeout,
                auto_remove_timeout=self.config.request_auto_remove_timeout,
                request_max_bytes=self.config.request_max_bytes,
                submit_timeout=self.config.request_pool_submit_timeout,
                admission_high_water=self.config.admission_high_water,
                forward_timeout_fn=self._forward_timeout_fn(),
                flip_drain_limit=self._flip_drain_limit(),
                handover_limit=self._handover_limit(),
            ),
            self.scheduler,
            metrics=self.metrics.pool,
            recorder=self.recorder,
        )
        self.controller.request_pool = self.pool

    def _continue_create_components(self) -> None:
        """consensus.go:452-463."""
        batcher = BatchBuilder(
            self.pool,
            self.scheduler,
            self.config.request_batch_max_count,
            self.config.request_batch_max_bytes,
            self.config.request_batch_max_interval,
            adaptive=self.config.request_batch_adaptive,
            fill_slack=self.config.request_batch_fill_slack,
        )
        self.pool._on_submitted = batcher.on_submitted
        leader_monitor = HeartbeatMonitor(
            self.logger,
            self.config.leader_heartbeat_timeout,
            self.config.leader_heartbeat_count,
            self.controller,
            self.num_nodes,
            self.controller,
            self.controller.view_sequences,
            self.config.num_of_ticks_behind_before_syncing,
            pipeline_depth=self.config.pipeline_depth,
            # detection instrumentation (ROADMAP item 1): the silence-to-
            # complain interval lands in the VC phase tracker + the
            # viewchange metric bundle — round 15 showed DETECTION, not
            # the VC protocol, owns ~99% of the failover cliff
            vc_phases=self.vc_phases,
            # adaptive detection (ISSUE 15): the effective complain timer
            # derives from the transport's RTT EWMA and the controller's
            # commit inter-arrival EWMA, with the configured constant as
            # ceiling/fallback and anti-thrash backoff per repeated
            # complaint against the same view
            rtt_multiplier=self.config.heartbeat_rtt_multiplier,
            backoff_base=self.config.detection_backoff_base,
            backoff_max=self.config.detection_backoff_max,
            rtt_fn=getattr(self.comm, "rtt_seconds", None),
            commit_interval_fn=self.controller.commit_interval_seconds,
            metrics=self.metrics.view_change,
            # receipt-time clock for the observed-gap EWMA — the same
            # time domain as the ticks that consume the derived timer
            now_fn=self.scheduler.now,
        )
        self.controller.batcher = batcher
        self.controller.leader_monitor = leader_monitor
        self.view_changer.requests_timer = self.pool

    def _set_view_and_seq(self, view: int, seq: int, dec: int) -> tuple[int, int, int]:
        """consensus.go:465-505."""
        new_view, new_seq = view, seq
        # decisions in view is incremented after delivery; expect dec+1 next,
        # unless genesis
        new_dec = dec + 1
        if seq == 0:
            new_dec = 0
        view_change = self.state.load_view_change_if_applicable()
        self._restore_view_change = False
        if view_change is not None and view_change.next_view >= view:
            self.logger.debugf("Restoring from view change with view %d", view_change.next_view)
            new_view = view_change.next_view
            self._restore_view_change = True
        view_seq = self.state.load_new_view_if_applicable()
        if view_seq is not None and view_seq.seq >= seq:
            self.logger.debugf(
                "Restoring from new view with view %d and seq %d", view_seq.view, view_seq.seq
            )
            new_view = view_seq.view
            new_seq = view_seq.seq
            new_dec = 0
        return new_view, new_seq, new_dec

    async def _start_components(
        self, view: int, seq: int, dec: int, config_sync: bool
    ) -> None:
        """consensus.go:513-523 (+507-511 waitForEachOther barrier)."""
        self.collector.start()
        self.view_changer.controller_started_event = asyncio.Event()
        self.view_changer.start(view)
        if self._restore_view_change:
            self.view_changer.restore_trigger()
        self._tickers.append(
            Ticker(self.scheduler, self.viewchanger_tick_interval,
                   lambda: self.view_changer.tick(self.scheduler.now()))
        )
        self._tickers.append(
            # misbehavior decay (ISSUE 18): halve per-sender provable
            # scores on a fixed cadence — the redemption path that
            # releases shunned senders once they stop forging
            Ticker(self.scheduler, self.config.misbehavior_decay_interval,
                   lambda: self.misbehavior.decay())
        )
        self._tickers.append(
            # ADAPTIVE cadence (ISSUE 15): the monitor's check interval
            # derives from its effective complain timer (a quarter of it,
            # never above the configured base), closing the granularity
            # gap where a fixed tick let arm-to-fire overshoot a shrunk
            # timer by multiples.  The lambdas re-resolve the monitor so
            # a reconfig-rebuilt controller keeps feeding the live one.
            Ticker(self.scheduler, self.heartbeat_tick_interval,
                   lambda: self.controller.leader_monitor.tick(self.scheduler.now()),
                   interval_fn=lambda: self.controller.leader_monitor
                   .suggested_tick_interval(self.heartbeat_tick_interval))
        )
        try:
            await self.controller.start(
                view, seq + 1, dec, self.config.sync_on_start if config_sync else False
            )
        finally:
            # always release the barrier — a failed start must not leave the
            # viewchanger task parked forever (controller.go:813)
            self.view_changer.controller_started_event.set()

    def _stop_tickers(self) -> None:
        for t in self._tickers:
            t.stop()
        self._tickers.clear()
