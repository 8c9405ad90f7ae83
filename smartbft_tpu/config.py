"""Consensus configuration.

Python re-design of the reference's 21-field configuration struct
(/root/reference/pkg/types/config.go:14-187).  Durations are float seconds
(the reference uses ``time.Duration``); all timeouts are consumed by the
tick-driven time source in :mod:`smartbft_tpu.utils.clock`, so sub-tick
precision is not meaningful.
"""

from __future__ import annotations

from dataclasses import dataclass, replace


class ConfigError(ValueError):
    pass


@dataclass(frozen=True)
class Configuration:
    # Identity
    self_id: int = 0

    # Batching (config.go:18-28)
    request_batch_max_count: int = 100
    request_batch_max_bytes: int = 10 * 1024 * 1024
    request_batch_max_interval: float = 0.05
    # Arrival-driven batch formation (README "Arrival-driven proposing").
    # Off (default): the BatchBuilder waits the full
    # request_batch_max_interval for a partial wave — the fixed cadence tax
    # the round-17 critical path showed as a 31-37% propose_wait share at
    # every offered rate.  On: the builder consults the pool's arrival-rate
    # EWMA and proposes the moment the in-formation wave provably cannot
    # fill within the remaining interval (deficit / arrival_rate >
    # fill_slack * time_left), while a wave the rate predicts WILL fill is
    # still allowed to form to full depth.  Low offered rates thus propose
    # immediately (propose_wait ~ 0) and saturation still forms deep
    # amortizing waves; the max interval stays the hard deadline either way.
    # fill_slack > 1 keeps waiting past the strict prediction (deeper waves,
    # more residual wait); < 1 gives up earlier (lower latency, shallower
    # waves).
    request_batch_adaptive: bool = False
    request_batch_fill_slack: float = 1.0

    # Buffers / pool (config.go:30-35).
    # When a View/ViewChanger inbox reaches incoming_message_buffer_size:
    # - inbox_backpressure=False (default): further messages are DROPPED
    #   (with a rate-limited warning).  Dropping bounds a Byzantine
    #   flooder's memory without letting it stall the shared event loop;
    #   the cost is that an honest burst near the bound (e.g. a view-change
    #   storm at large n) can shed prepares/commits/view-data and pay an
    #   extra view change.  Size the bound generously for large clusters —
    #   the throughput harness uses max(2000, 40*n).
    # - inbox_backpressure=True: the SENDING task blocks until space frees,
    #   matching the reference's full-channel semantics (view.go:190,
    #   viewchanger.go:206).  Requires the transport to deliver through the
    #   async intake (Consensus.handle_message_async); transports calling
    #   the sync intake still get drop semantics.
    # Pipelined views (pipeline_depth > 1) use direct ingest with no inbox:
    # vote-set dedup and the slot window bound memory, so neither policy
    # applies there.
    incoming_message_buffer_size: int = 200
    inbox_backpressure: bool = False
    request_pool_size: int = 400

    # Group-commit WAL durability (no reference counterpart — the reference
    # fsyncs inline on every append, writeaheadlog.go:469-472).  ON: protocol
    # saves append immediately and await a shared batched fsync wave, so the
    # disk never blocks the event loop.  Deterministic logical-clock tests
    # turn it OFF (see testing.app.fast_config): awaiting a real executor
    # round-trip lets the test clock race ahead of the protocol.
    wal_group_commit: bool = True

    # Request timeout chain (config.go:37-45)
    request_forward_timeout: float = 2.0
    request_complain_timeout: float = 20.0
    request_auto_remove_timeout: float = 180.0

    # RTT-derived forward timing (no reference counterpart — the
    # reference's forward timeout is a constant; round 16's cluster
    # timeline measured follower-submitted requests spending 97.6% of
    # their latency waiting out that constant).  When > 0 and the
    # transport measures RTT (smartbft_tpu.net.SocketComm does, from
    # dial and sync round trips), the EFFECTIVE forward timeout becomes
    # clamp(multiplier * measured_rtt, 10 ms, request_forward_timeout):
    # the configured constant stays the ceiling and the fallback (no
    # transport measurement, in-process Comm, cold links).  0 (default)
    # keeps the constant — reference-faithful.
    request_forward_rtt_multiplier: float = 0.0

    # View change (config.go:47-51)
    view_change_resend_interval: float = 5.0
    view_change_timeout: float = 20.0

    # Heartbeats (config.go:53-62)
    leader_heartbeat_timeout: float = 60.0
    leader_heartbeat_count: int = 10
    num_of_ticks_behind_before_syncing: int = 10

    # Adaptive failover detection (no reference counterpart — the
    # reference's complain timer is the constant above; round 16 measured
    # detection arm-to-fire up to 21.8 s under a muted leader while the
    # VC protocol itself runs in 35-52 ms, making DETECTION ~99% of the
    # failover cliff).  When heartbeat_rtt_multiplier > 0 the EFFECTIVE
    # complain timer becomes
    #   clamp(multiplier * max(rtt_ewma, commit_interval_ewma,
    #         observed_heartbeat_gap_ewma) * backoff,
    #         DETECTION_FLOOR, leader_heartbeat_timeout)
    # where rtt_ewma is the transport's measured per-peer RTT envelope
    # (SocketComm, PR 14) and commit_interval_ewma is the Controller's
    # commit inter-arrival EWMA (the Pool._drain_rate idiom) — both
    # CLUSTER-VISIBLE signals, so the leader's heartbeat emission cadence
    # (effective timeout / leader_heartbeat_count) shrinks in step with
    # the followers' complain timers; the observed-gap term (sampled
    # with the receipt-time clock — tick-quantized samples would feed
    # the tick cadence back into the derivation and run it up to the
    # ceiling) additionally guarantees a follower never complains faster
    # than a multiple of the emission cadence its leader actually
    # demonstrates.  The derived timer only applies to a leader this
    # follower has OBSERVED in the current view (first-observation
    # grace): until the new leader's first sign of life the constant
    # governs, so warm followers carrying hair-trigger signals from the
    # previous view cannot spuriously depose a cold-signal leader whose
    # own derivation paces its first emission at ceiling/count.
    # The configured constant stays the ceiling AND the
    # fallback (no measurement yet, in-process Comm with no RTT, cold
    # cluster).  The monitor's tick cadence is derived from the effective
    # timeout too, so arm-to-fire can never overshoot the timer by
    # multiples (the round-16 granularity gap).  ``backoff`` widens the
    # timer by detection_backoff_base per consecutive complain against
    # the SAME view (capped at detection_backoff_max, and always at the
    # ceiling), so a flaky network that keeps killing view changes backs
    # detection off instead of thrashing leadership; installing a higher
    # view resets it.  0 (default) keeps the constant — reference-
    # faithful.
    heartbeat_rtt_multiplier: float = 0.0
    detection_backoff_base: float = 2.0
    detection_backoff_max: float = 8.0

    # Backlog drain at a change of leader (ISSUE 15 — round 16's critical
    # path put 98% of forced-VC request time in `propose_wait`:
    # followers' pooled requests wait out a full request_forward_timeout
    # before reaching the NEW leader after the flip).  When > 0, a
    # view-flip timer restart fast-forwards the oldest
    #   flip_drain_windows * pipeline_depth * request_batch_max_count
    # pooled requests (their forward timers arm at the floor instead of
    # the full timeout), so the new view's first proposals batch the
    # stalled backlog into deep windows immediately; the rest of the
    # pool keeps the ordinary timeout chain.  Leader-side pool dedup
    # absorbs the duplicates this may forward.  The same leg serves a
    # ROTATION's hand-over (ISSUE 31): the replica whose turn just ended
    # forwards the oldest ONE window (pipeline_depth *
    # request_batch_max_count) of what its pool still holds and has not
    # proposed to the new leader, instead of keeping it until the lead
    # comes round again (every rotation re-arms the forward timeout,
    # which is longer than a turn, so there it never fired at all); the
    # other replicas re-arm as upstream does.  0 disables both (every
    # timer restarts at the full forward timeout — reference-faithful).
    flip_drain_windows: int = 4

    # State collection (config.go:64-66)
    collect_timeout: float = 1.0

    # Flags (config.go:68-75)
    sync_on_start: bool = False
    speed_up_view_change: bool = False

    # Leader rotation (config.go:77-80).
    # rotation_granularity selects the unit decisions_per_leader counts:
    # - "decision" (reference-faithful): a leader term spans
    #   decisions_per_leader decisions, and every pre-prepare chains to the
    #   PREVIOUS decision's commit certificate (view.go:606-647).  Requires
    #   pipeline_depth == 1 — a pipelined leader proposes s+1 before s's
    #   certificate exists.
    # - "window": a leader term spans decisions_per_leader WINDOWS of
    #   pipeline_depth decisions each, and only the FIRST pre-prepare of
    #   each window chains (to the last decision of the previous window —
    #   the window anchor).  Within a window the full k-deep pipeline runs;
    #   at window boundaries the pipeline drains so the anchor certificate
    #   exists before the next window opens.  This is how rotation +
    #   blacklisting co-host with pipeline_depth > 1.
    leader_rotation: bool = True
    decisions_per_leader: int = 3
    rotation_granularity: str = "decision"

    # Request limits (config.go:82-87)
    request_max_bytes: int = 10 * 1024
    request_pool_submit_timeout: float = 5.0

    # Admission control at the front door (no reference counterpart — the
    # reference's pool blocks submitters on a weighted semaphore forever;
    # a service past its saturation knee must SHED, not queue unboundedly:
    # PBFT's own overload story assumes excess load is dropped, and queue
    # growth past the knee buys only latency, never goodput).  Consumed by
    # core.pool.Pool via PoolOptions; rides ConfigMirror/reconfig.
    # - admission_high_water: fraction of request_pool_size at which
    #   submit stops queueing and fails fast with AdmissionRejected
    #   (retry-after hint derived from the measured drain rate).  The
    #   gate input counts pooled requests PLUS parked submitters.  1.0
    #   (default) disables shedding — pure bounded-wait semantics.
    # request_pool_submit_timeout above doubles as the TOTAL bound a
    # submitter may spend parked on pool space (one deadline across every
    # re-park), so even with the gate off callers shed instead of wedging.
    admission_high_water: float = 1.0

    # Pipelined in-flight window (no reference counterpart — the reference
    # keeps exactly one sequence in flight: the leader re-acquires the
    # propose token only after the current decision delivers,
    # controller.go:555-557, and only pipelines vote COLLECTION one ahead,
    # view.go:107-113).  pipeline_depth k >= 2 lets the leader keep up to k
    # consecutive sequences outstanding (propose s+1 before s delivers);
    # replicas run a per-sequence slot machine with in-order commit
    # broadcast and in-order delivery.  The payoff is batched quorum
    # verification ACROSS decisions: k commit waves coalesce into one
    # device launch instead of k.  Under the launch shadow the leader may
    # keep up to 2k sequences outstanding (it fills window w+1's protocol
    # plane while window w's verify wave is on device), and replicas hold
    # at most 3k slots (one extra window of frontier-skew tolerance on
    # intake) — so the per-view memory bound is 3k slots x one proposal
    # each.  Deep windows (k=16/32) are the launch-amortization lever; the
    # validation cap below keeps the slot ladder, the view-change ladder
    # message, and crash-restore replay bounded.  Requires leader_rotation
    # off — the rotation protocol chains each pre-prepare to the PREVIOUS
    # decision's commit certificate (view.go:606-647), which a pipelined
    # leader does not yet hold.  k = 1 is the reference-faithful default.
    pipeline_depth: int = 1

    # Verify-plane fault tolerance (no reference counterpart — the
    # reference verifies each signature on its own goroutine, view.go:537-
    # 541, which cannot hang or fail as a unit; routing the quorum-verify
    # hot path through one shared device engine makes the device a single
    # point of failure).  Consumed when the Consensus facade wires a
    # CryptoProvider's coalescer (crypto/provider.VerifyFaultPolicy.
    # from_config).  These three durations are WALL-CLOCK seconds even
    # under the logical test clock: the engine runs on worker threads the
    # tick scheduler cannot observe.
    # - verify_launch_timeout: deadline per coalescer flush; on expiry the
    #   in-flight launch is abandoned (its late result discarded) and the
    #   wave enters the retry path.  Default is generous against the
    #   measured 0.11-1.5 s launch-weather range (PERF.md).
    # - verify_launch_retries: re-submissions (exponential backoff with
    #   jitter) of a failed/timed-out wave before it falls back to host.
    # - verify_breaker_threshold: consecutive launch failures that trip
    #   the host-fallback circuit breaker open (a permanent kernel error
    #   trips it immediately).
    # - verify_probe_interval: cadence of the background canary probe that
    #   re-tries the device while the breaker is open.
    # - verify_mesh_devices: device-mesh width of the verify plane.  0
    #   (default) keeps the single-device engine.  N >= 1 graduates the
    #   coalescer's engine onto an N-device mesh at start/reconfig
    #   (CryptoProvider.configure_verify_mesh): every coalesced wave is
    #   padded to a device-count multiple, partitioned along the batch
    #   axis (NamedSharding(mesh, P('batch'))), and verified in ONE
    #   logical launch spanning the mesh.  The fault knobs above apply
    #   per MESH launch unchanged (deadline abandons the whole mesh
    #   launch, the breaker degrades every shard to host together).
    #   DEGRADED MODE: a host with fewer visible devices than configured
    #   keeps the single-device engine LOUDLY, with a counted downgrade
    #   (consensus.tpu.count_mesh_downgrades) — it never dies at start.
    # - verify_mesh_topology: the mesh SHAPE when verify_mesh_devices > 0.
    #   "1d" (default) partitions the batch axis (MeshVerifyEngine);
    #   "2d" graduates onto the seq x vote QuorumMeshVerifyEngine, whose
    #   per-sequence quorum counts psum across the 'vote' mesh axis —
    #   quorum counting itself rides the device collective — while
    #   per-item verdicts stay bit-identical to the 1D engine.
    # - verify_flush_hold: occupancy-aware flush gating (wall-clock
    #   seconds; 0 disables).  A coalescer flush whose wave sits below a
    #   pad-ladder rung may HOLD up to this hard deadline while per-tag
    #   submit-rate tracking predicts more shards' waves inbound, so one
    #   deeper launch replaces several shallow ones (fixed-launch-
    #   overhead amortization).  The hold is bypassed outright while the
    #   breaker is open (host fallback must not wait), past max_batch,
    #   and for rung-exact waves; hold decisions are exported in the
    #   bench `mesh` block (waves_held, held_ms, depth_gain_items).
    verify_launch_timeout: float = 30.0
    verify_launch_retries: int = 2
    verify_breaker_threshold: int = 3
    verify_probe_interval: float = 2.0
    verify_mesh_devices: int = 0
    verify_mesh_topology: str = "1d"
    verify_flush_hold: float = 0.0

    # Per-sender misbehavior accounting (ISSUE 18 — no reference
    # counterpart: the reference drops an invalid vote and forgets who
    # sent it).  Every cryptographically provable invalid verdict
    # (bad signature value, digest-binding forgery, unknown signer) is
    # attributed to its signer in a node-LOCAL MisbehaviorTable; a sender
    # whose decayed score crosses the threshold is shunned — its
    # Prepare/Commit votes are dropped at intake BEFORE reaching the
    # verify plane (a vote-forgery flood stops costing device launches)
    # and its forwarded client requests lose the admission-gate bypass.
    # Local-only by design: the shared window-boundary blacklist stays a
    # pure function of replicated view-change evidence.
    # - misbehavior_shun_threshold: provable-invalid score at which a
    #   sender is shunned (honest senders score ~0; an honest replica's
    #   votes simply verify).
    # - misbehavior_decay_interval: seconds between score-halving ticks —
    #   the redemption path: a sender that stops forging drains below
    #   half the threshold and is released.
    misbehavior_shun_threshold: int = 8
    misbehavior_decay_interval: float = 30.0

    # Real-socket transport (smartbft_tpu/net/ — no reference counterpart:
    # the reference is a library whose embedder supplies Comm; these knobs
    # configure the transport we ship).  Consumed by SocketComm.from_config
    # and round-tripped by testing.reconfig.ConfigMirror like every other
    # knob, so a reconfiguration cannot silently reset the transport —
    # EXCEPT transport_listen, which is per-node like self_id (each
    # replica binds its OWN address) and is therefore restored from the
    # local config on receipt (with_node_locals), never mirrored.
    # - transport_listen: this node's own listen address ("tcp://host:port",
    #   port 0 for ephemeral, or "uds:///path"); empty = in-process Comm,
    #   no socket transport.
    # - transport_outbox_cap: max frames buffered per peer while its link
    #   is down/slow; beyond it the OLDEST frame is dropped and counted
    #   (loud-but-bounded — a dead peer must never grow a live replica's
    #   memory without bound).
    # - transport_reconnect_backoff_base/_max: exponential redial backoff
    #   bounds (seconds, wall-clock; each sleep gets ±25% jitter so n
    #   replicas redialing a restarted peer do not thundering-herd it).
    # - transport_max_frame_bytes: frame-length sanity cap; a length
    #   prefix above it poisons the connection (dropped, counted) before
    #   any allocation happens.
    transport_listen: str = ""
    transport_outbox_cap: int = 4096
    transport_reconnect_backoff_base: float = 0.05
    transport_reconnect_backoff_max: float = 2.0
    transport_max_frame_bytes: int = 16 * 1024 * 1024

    # Elastic shards (smartbft_tpu/shard/ — no reference counterpart: the
    # reference is one consensus instance; sharding and live resharding are
    # this codebase's scale story).  Consumed by ShardSet.reshard and
    # shard.autoscale.OccupancyAutoscaler.from_config; round-tripped by
    # testing.reconfig.ConfigMirror so a reconfiguration cannot silently
    # reset the elasticity envelope.
    # - reshard_drain_deadline: wall-clock seconds a live reshard may
    #   spend waiting for barrier commits + moved-key-range drain before
    #   the transition aborts and parked moved-client submits raise
    #   ShardEpochError (unmoved clients are never delayed).
    # - autoscale_high_occupancy / autoscale_low_occupancy: combined pool
    #   fill fractions (ShardSet.occupancy()['fill']) above which the
    #   autoscaler scales OUT / below which it scales IN.
    # - autoscale_cooldown: seconds after any reshard (executed or failed)
    #   before the autoscaler decides again — the anti-flap gate.
    # - autoscale_min_shards / autoscale_max_shards: the elasticity bounds.
    reshard_drain_deadline: float = 30.0
    autoscale_high_occupancy: float = 0.85
    autoscale_low_occupancy: float = 0.15
    autoscale_cooldown: float = 60.0
    autoscale_min_shards: int = 1
    autoscale_max_shards: int = 8

    # Snapshots + log compaction (smartbft_tpu/snapshot/ — the PBFT
    # stable-checkpoint discipline, ISSUE 17).  Consumed by the socket
    # ReplicaApp and the in-process testing App; round-tripped by
    # testing.reconfig.ConfigMirror so a reconfiguration cannot silently
    # turn compaction off (or on) for part of the cluster.
    # - snapshot_interval_decisions: capture a snapshot (and truncate the
    #   ledger/WAL prefix behind it) every N committed decisions.  0
    #   (default) disables snapshots entirely — full-chain catch-up and
    #   unbounded ledger growth, the pre-ISSUE-17 behavior, which several
    #   existing harness oracles (committed_ids over the whole history)
    #   rely on.
    # - snapshot_chunk_bytes: FT_SNAP_RESP chunk payload size for state
    #   transfer; must leave frame-envelope headroom under
    #   transport_max_frame_bytes (validated below).
    snapshot_interval_decisions: int = 0
    snapshot_chunk_bytes: int = 1024 * 1024

    # The read/serving plane (smartbft_tpu/core/readplane.py — ISSUE 19,
    # Castro–Liskov's read-only optimization).  Reads execute at replicas
    # against committed state with NO ordering and bypass the write
    # path's pool/admission gate entirely; they get their own
    # token-bucket gate so a read storm degrades reads, never writes.
    # Consumed by the socket ReplicaApp and the in-process testing App;
    # round-tripped by ConfigMirror like every other knob.
    # - read_gate_rate: sustained reads/second one replica serves before
    #   shedding (0 = gate off, every read answered — the default, since
    #   committed-state reads are one dict lookup under the lock).
    # - read_gate_burst: bucket depth — the burst a replica absorbs
    #   before the rate limit bites.
    # - read_watch_buffer: per-subscriber committed-stream notification
    #   cap; past it the OLDEST notification is dropped and counted
    #   (the transport outbox-cap discipline — a slow subscriber must
    #   never grow replica memory without bound).
    # - read_max_watches: concurrent subscriptions one replica carries;
    #   registration past it is refused loudly.
    read_gate_rate: float = 0.0
    read_gate_burst: int = 256
    read_watch_buffer: int = 256
    read_max_watches: int = 64

    # The self-driving control plane (smartbft_tpu/control/ — ISSUE 20,
    # the verdict→action reflex arc).  Consumed by ControlPolicy /
    # ControlLoop; round-tripped by ConfigMirror so a reconfiguration
    # retunes the controller itself along with everything else.
    # - control_interval: seconds between controller ticks.
    # - control_cooldown: per-ACTION cooldown (scale_out, scale_in and
    #   retune each have their own clock); re-armed on failure too.
    # - control_hysteresis: window within which an action that UNDOES a
    #   recent one (scale-in after scale-out, a knob flipped back to its
    #   previous value) is vetoed — the anti-oscillation guard.
    # - control_idle_hold: sustained-idle seconds before scale-in fires.
    # - control_budget_actions / control_budget_window: global anti-thrash
    #   budget — at most N actions of ANY kind per window.
    # - control_knob_deadband: relative change a derived knob must exceed
    #   before a retune commits it (EWMA jitter must not reconfigure the
    #   cluster).
    # - control_forward_rtt_multiplier: derived request_forward_timeout =
    #   multiplier x measured transport RTT EWMA (clamped to the
    #   boot-time value; PR 15's request_forward_rtt_multiplier pattern,
    #   but COMMITTED through reconfig rather than applied locally).
    # - control_hold_commit_multiplier: derived verify_flush_hold =
    #   multiplier x commit inter-arrival EWMA.
    # - control_outbox_drain_window: derived transport_outbox_cap =
    #   measured pool drain rate x this window (seconds of backlog the
    #   outbox may hold).
    control_interval: float = 1.0
    control_cooldown: float = 30.0
    control_hysteresis: float = 120.0
    control_idle_hold: float = 60.0
    control_budget_actions: int = 4
    control_budget_window: float = 300.0
    control_knob_deadband: float = 0.25
    control_forward_rtt_multiplier: float = 8.0
    control_hold_commit_multiplier: float = 0.5
    control_outbox_drain_window: float = 2.0

    def validate(self) -> None:
        def positive(name: str) -> None:
            v = getattr(self, name)
            if v <= 0:
                raise ConfigError(f"{name} should be greater than zero")

        if self.self_id == 0:
            raise ConfigError("self_id should be greater than zero")
        for field in (
            "request_batch_max_count",
            "request_batch_max_bytes",
            "request_batch_max_interval",
            "incoming_message_buffer_size",
            "request_pool_size",
            "request_forward_timeout",
            "request_complain_timeout",
            "request_auto_remove_timeout",
            "view_change_resend_interval",
            "view_change_timeout",
            "leader_heartbeat_timeout",
            "leader_heartbeat_count",
            "num_of_ticks_behind_before_syncing",
            "collect_timeout",
            "request_max_bytes",
            "request_pool_submit_timeout",
            "verify_launch_timeout",
            "verify_breaker_threshold",
            "verify_probe_interval",
            "transport_outbox_cap",
            "transport_reconnect_backoff_base",
            "transport_reconnect_backoff_max",
            "transport_max_frame_bytes",
            "reshard_drain_deadline",
            "autoscale_cooldown",
            "request_batch_fill_slack",
            "control_interval",
            "control_cooldown",
            "control_hysteresis",
            "control_budget_window",
            "control_outbox_drain_window",
        ):
            positive(field)
        if self.control_idle_hold < 0:
            raise ConfigError("control_idle_hold should not be negative")
        if self.control_budget_actions < 1:
            raise ConfigError("control_budget_actions should be at least 1")
        if not (0.0 <= self.control_knob_deadband < 1.0):
            raise ConfigError(
                "control_knob_deadband should be in [0, 1), got "
                f"{self.control_knob_deadband}"
            )
        if self.control_forward_rtt_multiplier < 0:
            raise ConfigError(
                "control_forward_rtt_multiplier should not be negative"
            )
        if self.control_hold_commit_multiplier < 0:
            raise ConfigError(
                "control_hold_commit_multiplier should not be negative"
            )
        if not (0.0 < self.autoscale_low_occupancy
                < self.autoscale_high_occupancy <= 1.0):
            raise ConfigError(
                "autoscale occupancy thresholds must satisfy "
                "0 < low < high <= 1, got "
                f"low={self.autoscale_low_occupancy} "
                f"high={self.autoscale_high_occupancy}"
            )
        if not (1 <= self.autoscale_min_shards <= self.autoscale_max_shards):
            raise ConfigError(
                "autoscale shard bounds must satisfy 1 <= min <= max, got "
                f"{self.autoscale_min_shards}..{self.autoscale_max_shards}"
            )
        if self.verify_launch_retries < 0:
            raise ConfigError("verify_launch_retries should not be negative")
        if self.request_forward_rtt_multiplier < 0:
            raise ConfigError(
                "request_forward_rtt_multiplier should not be negative "
                "(0 keeps the constant request_forward_timeout)"
            )
        if self.heartbeat_rtt_multiplier < 0:
            raise ConfigError(
                "heartbeat_rtt_multiplier should not be negative "
                "(0 keeps the constant leader_heartbeat_timeout)"
            )
        if self.detection_backoff_base < 1.0:
            raise ConfigError(
                "detection_backoff_base must be at least 1 (the per-round "
                "complain-timer widening factor; 1 disables backoff)"
            )
        if self.detection_backoff_max < self.detection_backoff_base:
            raise ConfigError(
                "detection_backoff_max must be at least "
                "detection_backoff_base (it caps the cumulative backoff "
                "multiplier)"
            )
        if self.flip_drain_windows < 0:
            raise ConfigError(
                "flip_drain_windows should not be negative "
                "(0 disables the flip-time backlog fast-forward)"
            )
        if self.verify_mesh_devices < 0:
            raise ConfigError(
                "verify_mesh_devices should not be negative "
                "(0 = single-device verify plane)"
            )
        if self.verify_mesh_topology not in ("1d", "2d"):
            raise ConfigError(
                "verify_mesh_topology should be '1d' (batch-axis mesh) or "
                "'2d' (seq x vote quorum mesh), got "
                f"{self.verify_mesh_topology!r}"
            )
        if self.verify_flush_hold < 0:
            raise ConfigError(
                "verify_flush_hold should not be negative "
                "(0 disables occupancy-aware flush gating)"
            )
        if self.misbehavior_shun_threshold < 1:
            raise ConfigError(
                "misbehavior_shun_threshold should be at least 1, got "
                f"{self.misbehavior_shun_threshold}"
            )
        if self.misbehavior_decay_interval <= 0:
            raise ConfigError(
                "misbehavior_decay_interval should be positive (the decay "
                "tick is also the shun-release/redemption path), got "
                f"{self.misbehavior_decay_interval}"
            )
        if self.snapshot_interval_decisions < 0:
            raise ConfigError(
                "snapshot_interval_decisions should not be negative "
                "(0 disables snapshots and log compaction)"
            )
        if self.snapshot_chunk_bytes <= 0:
            raise ConfigError(
                "snapshot_chunk_bytes should be greater than zero"
            )
        if self.snapshot_chunk_bytes > self.transport_max_frame_bytes - 65536:
            raise ConfigError(
                "snapshot_chunk_bytes must sit at least 64 KiB under "
                "transport_max_frame_bytes (chunk + envelope must fit one "
                "frame, or every state transfer poisons its connection)"
            )
        if self.read_gate_rate < 0:
            raise ConfigError(
                "read_gate_rate should not be negative "
                "(0 disables the read gate)"
            )
        for name in ("read_gate_burst", "read_watch_buffer",
                     "read_max_watches"):
            if getattr(self, name) < 1:
                raise ConfigError(f"{name} should be at least 1")
        if not (0.0 < self.admission_high_water <= 1.0):
            raise ConfigError(
                "admission_high_water must be in (0, 1] (a fraction of "
                f"request_pool_size; 1.0 disables shedding), got "
                f"{self.admission_high_water}"
            )
        if self.transport_reconnect_backoff_base > self.transport_reconnect_backoff_max:
            raise ConfigError(
                "transport_reconnect_backoff_base is bigger than "
                "transport_reconnect_backoff_max"
            )
        # a frame must be able to carry a maximum-size proposal plus its
        # metadata/signature envelope, or every send of a full batch
        # poisons the receiving connection and the cluster loops on
        # reconnect without ever committing
        if self.transport_max_frame_bytes < self.request_batch_max_bytes + 65536:
            raise ConfigError(
                "transport_max_frame_bytes must exceed request_batch_max_bytes "
                "by at least 64 KiB of proposal envelope headroom"
            )
        if self.request_batch_max_count > self.request_batch_max_bytes:
            raise ConfigError("request_batch_max_count is bigger than request_batch_max_bytes")
        if self.request_forward_timeout > self.request_complain_timeout:
            raise ConfigError("request_forward_timeout is bigger than request_complain_timeout")
        if self.request_complain_timeout > self.request_auto_remove_timeout:
            raise ConfigError("request_complain_timeout is bigger than request_auto_remove_timeout")
        if self.view_change_resend_interval > self.view_change_timeout:
            raise ConfigError("view_change_resend_interval is bigger than view_change_timeout")
        if self.leader_rotation and self.decisions_per_leader == 0:
            raise ConfigError("decisions_per_leader should be greater than zero when leader rotation is active")
        if not self.leader_rotation and self.decisions_per_leader != 0:
            raise ConfigError("decisions_per_leader should be zero when leader rotation is off")
        if self.pipeline_depth < 1:
            raise ConfigError("pipeline_depth should be at least 1")
        if self.pipeline_depth > 256:
            raise ConfigError(
                "pipeline_depth is capped at 256: replicas hold up to "
                "3*pipeline_depth proposal slots per view (base window + "
                "launch shadow + intake skew) and the view-change ViewData "
                "carries one in-flight rung per undelivered sequence"
            )
        if self.rotation_granularity not in ("decision", "window"):
            raise ConfigError(
                "rotation_granularity should be 'decision' or 'window', "
                f"got {self.rotation_granularity!r}"
            )
        if (
            self.pipeline_depth > 1
            and self.leader_rotation
            and self.rotation_granularity != "window"
        ):
            raise ConfigError(
                "pipeline_depth > 1 with leader_rotation requires "
                "rotation_granularity='window' (per-decision rotation chains "
                "every pre-prepare to the previous decision's commit "
                "certificate, which a pipelined leader does not yet hold; "
                "window granularity chains only at window boundaries)"
            )

    @property
    def effective_decisions_per_leader(self) -> int:
        """decisions_per_leader expressed in DECISIONS regardless of
        granularity: window granularity multiplies by the window depth so a
        term spans decisions_per_leader whole windows.  This is the value
        every get_leader_id / blacklist computation consumes — it must be
        derived identically on every replica (it is pure config)."""
        if (
            self.leader_rotation
            and self.rotation_granularity == "window"
            and self.pipeline_depth > 1
        ):
            return self.decisions_per_leader * self.pipeline_depth
        return self.decisions_per_leader

    def with_self_id(self, self_id: int) -> "Configuration":
        return replace(self, self_id=self_id)

    def with_node_locals(self, prev: "Configuration") -> "Configuration":
        """Restore the per-node fields a cluster-wide reconfiguration must
        never overwrite: ``self_id`` and this node's own listen address
        (each replica binds its OWN ``transport_listen``; a committed
        config carries the proposer's)."""
        return replace(
            self,
            self_id=prev.self_id,
            transport_listen=prev.transport_listen,
        )


#: Reasonable defaults for a ~10ms-RTT cluster (config.go:92-113).
DEFAULT_CONFIG = Configuration()
