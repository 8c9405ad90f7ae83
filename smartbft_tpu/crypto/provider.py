"""Signer/Verifier crypto providers: host signing, batched TPU verification.

The reference treats Signer/Verifier as opaque app plugins
(/root/reference/pkg/api/dependencies.go:47-71) and verifies each commit
signature on its own goroutine (/root/reference/internal/bft/view.go:537-541).
Here the crypto seam is a first-class component:

* :class:`Keyring` — node-id -> public key registry + own private key
  (key types are scheme-opaque).
* :class:`CryptoProvider` — implements the crypto subset of the
  Verifier/Signer SPI for a pluggable signature scheme (P-256, Ed25519).
  Signing is host-side (one signature per decision; never hot).
  Verification goes through a pluggable engine:
    - :class:`HostVerifyEngine`  — pure-Python ints; the CPU baseline.
    - :class:`JaxVerifyEngine`   — pads votes into fixed-size lanes and runs
      ONE jitted verify-kernel launch per flush; an asyncio micro-batcher
      coalesces concurrent quorum checks (across sequences and view-change
      validations) into shared launches, which is where the cross-request
      x cross-replica batching of BASELINE.md configs[2] comes from.

Wire format of a consenter signature (Signature.msg): canonical encoding of
:class:`ConsenterSigMsg` binding the proposal digest and the auxiliary data
(the reference smuggles PreparesFrom aux the same way, view.go:472-481).
"""

from __future__ import annotations

import asyncio
import logging
import os
import queue
import random
import threading
import time
import weakref
from dataclasses import asdict, dataclass, field
from typing import Optional, Sequence

import numpy as np

from ..codec import decode, encode, wiremsg
from ..messages import Proposal, Signature
from ..obs.recorder import (
    launch_span,
    name_this_thread,
    note_lanes,
    set_thread_launch,
    standby,
)
from ..types import VerifyPlaneDown, proposal_digest
from ..utils.memo import LruMemo
from ..utils.tasks import create_logged_task
from . import bls12381, ed25519, p256


@wiremsg
class ConsenterSigMsg:
    """The exact bytes a consenter signs for a commit vote."""

    proposal_digest: str = ""
    aux: bytes = b""


class Keyring:
    """Public keys of all replicas + this replica's private key.

    Key types are scheme-opaque: P-256 uses (int, (qx, qy)); Ed25519 uses
    (bytes, bytes).  The keyring never interprets them — only the scheme
    module does.
    """

    def __init__(self, self_id: int, private_key,
                 public_keys: dict[int, object]):
        self.self_id = self_id
        self.private_key = private_key
        self.public_keys = dict(public_keys)

    @classmethod
    def generate(cls, node_ids: Sequence[int], seed: bytes = b"smartbft",
                 scheme=p256):
        """Deterministic keyring set for tests/benches: one per node id."""
        keys = {nid: scheme.keygen(seed + b"-%d" % nid) for nid in node_ids}
        return {
            nid: cls(nid, keys[nid][0], {n: k[1] for n, k in keys.items()})
            for nid in node_ids
        }


# ---------------------------------------------------------------------------
# verify engines
# ---------------------------------------------------------------------------

#: what can serve a verify launch: the static-key comb Pallas kernel, the
#: generic (arbitrary-key) Pallas kernel, the XLA kernel, or host code
KERNELS = ("comb", "pallas", "xla", "host")
#: why the host refused a lane before any kernel saw it: a scalar that is
#: not reduced (S >= L, RFC 8032 5.1.7), or a signature / key that is not
#: of its length or does not decode
HOST_REFUSALS = ("s_not_reduced", "malformed")


@dataclass
class VerifyStats:
    """Batch-occupancy + latency accounting (BASELINE.md metrics).

    ``metrics``: optionally a :class:`smartbft_tpu.metrics.TPUCryptoMetrics`
    bundle — every record() then also feeds the embedder's metrics
    provider (batch-fill histogram, per-sig latency, counters).

    ``launches_by_kernel`` says what served each launch — one of
    :data:`KERNELS` — so a run can refuse a result the comb kernel did
    not produce instead of inferring it from the platform."""

    launches: int = 0
    sigs_verified: int = 0
    slots_used: int = 0
    total_kernel_seconds: float = 0.0
    metrics: object = None
    launches_by_kernel: dict = field(
        default_factory=lambda: dict.fromkeys(KERNELS, 0))
    #: lanes launched (padding included) and lanes used, per kernel
    lanes_by_kernel: dict = field(
        default_factory=lambda: dict.fromkeys(KERNELS, 0))
    used_by_kernel: dict = field(
        default_factory=lambda: dict.fromkeys(KERNELS, 0))
    #: lanes the host refused before the device saw them, by cause
    #: (:data:`HOST_REFUSALS`): a kernel whose marshalling checks lengths
    #: and ranges on whole arrays (Ed25519's arbitrary-key kernel) counts
    #: them here; those lanes launch as padding and verify False
    host_refused: dict = field(
        default_factory=lambda: dict.fromkeys(HOST_REFUSALS, 0))

    def record(self, n_sigs: int, n_slots: int, seconds: float,
               kernel: str = "host", refused: Optional[dict] = None) -> None:
        if refused:
            for cause, n in refused.items():
                self.host_refused[cause] += n
        self.launches += 1
        self.launches_by_kernel[kernel] += 1
        self.lanes_by_kernel[kernel] += n_slots
        self.used_by_kernel[kernel] += n_sigs
        self.sigs_verified += n_sigs
        self.slots_used += n_slots
        self.total_kernel_seconds += seconds
        if self.metrics is not None:
            self.metrics.count_batches.add(1)
            self.metrics.count_sigs_verified.add(n_sigs)
            if n_slots:
                self.metrics.batch_fill_percent.observe(100.0 * n_sigs / n_slots)
            if n_sigs:
                self.metrics.verify_latency_per_sig_us.observe(
                    1e6 * seconds / n_sigs
                )

    @property
    def batch_fill_pct(self) -> float:
        return 100.0 * self.sigs_verified / self.slots_used if self.slots_used else 0.0

    @property
    def us_per_sig(self) -> float:
        if not self.sigs_verified:
            return 0.0
        return 1e6 * self.total_kernel_seconds / self.sigs_verified


@dataclass
class MeshVerifyStats(VerifyStats):
    """VerifyStats for a device-mesh engine: every record also accounts
    pad waste and per-device launch fill (a batch-axis-partitioned wave
    places its items contiguously, so padding lands on the TAIL devices —
    the per-device fill vector makes that visible instead of hiding it in
    the overall mean).  Exported through ``MeshVerifyEngine.mesh_snapshot``
    into the ``mesh`` block of every bench row."""

    devices: int = 1
    pad_slots: int = 0
    launches_spanning_all_devices: int = 0
    last_device_fill_pct: list = field(default_factory=list)
    #: distinct devices that held the last launch's inputs / its output,
    #: read off the arrays' shardings — the mesh width as it RAN, beside
    #: ``devices``, the width as it was built
    last_io_devices: tuple = (0, 0)
    #: launches whose inputs or output were laid out over FEWER devices
    #: than the engine was built with (a launch of one signature need not
    #: USE every device, ``launches_spanning_all_devices``; it is still
    #: laid out over all of them)
    launches_below_width: int = 0

    def record(self, n_sigs: int, n_slots: int, seconds: float,
               kernel: str = "xla",
               per_device: Optional[list] = None,
               io_devices: tuple = (0, 0)) -> None:
        """``per_device``: the engine's actual per-device item counts for
        this launch (the strided-placement engine reports them exactly);
        None falls back to the contiguous-placement model (items fill
        devices front to back, padding on the tail)."""
        super().record(n_sigs, n_slots, seconds, kernel)
        self.last_io_devices = tuple(io_devices)
        if min(self.last_io_devices) < self.devices:
            self.launches_below_width += 1
        pad = max(n_slots - n_sigs, 0)
        self.pad_slots += pad
        per_dev = max(1, n_slots // max(1, self.devices))
        if per_device is not None:
            fills = [round(100.0 * got / per_dev, 1) for got in per_device]
        else:
            fills = []
            for d in range(self.devices):
                got = min(max(n_sigs - d * per_dev, 0), per_dev)
                fills.append(round(100.0 * got / per_dev, 1))
        self.last_device_fill_pct = fills
        if fills and min(fills) > 0:
            self.launches_spanning_all_devices += 1
        m = self.metrics
        if m is not None and hasattr(m, "count_mesh_launches"):
            m.count_mesh_launches.add(1)
            m.count_mesh_pad_slots.add(pad)
            if fills:
                m.mesh_device_fill_percent.observe(min(fills))

    def mesh_block(self, capacity: int = 0) -> dict:
        """The JSON-able engine half of the bench ``mesh`` block."""
        return {
            "devices": self.devices,
            "launches": self.launches,
            "items": self.sigs_verified,
            "slots": self.slots_used,
            "fill_pct": round(self.batch_fill_pct, 1),
            "pad_slots": self.pad_slots,
            "pad_waste_pct": round(100.0 * self.pad_slots / self.slots_used, 1)
            if self.slots_used else 0.0,
            "capacity_items_per_launch": int(capacity),
            "device_fill_pct_last": list(self.last_device_fill_pct),
            "io_devices_last": list(self.last_io_devices),
            "launches_spanning_all_devices": self.launches_spanning_all_devices,
            "launches_below_width": self.launches_below_width,
        }


class LaunchTimeout(Exception):
    """A coalescer flush exceeded its launch deadline.  The wave was
    abandoned: the worker thread keeps running, but its late result is
    discarded on arrival (counted in VerifyFaultStats)."""


class VerifyResultMismatch(RuntimeError):
    """An engine returned a different number of results than it was given
    items.  Silently slicing such a batch would mis-assign verdicts across
    every coalesced submitter, so the wave fails loudly instead and the
    mismatch counts as a launch failure."""


@dataclass(frozen=True)
class VerifyFaultPolicy:
    """Fault-tolerance knobs for the verify plane.

    All durations are WALL-CLOCK seconds (the engine runs on worker
    threads, outside any logical test clock).  ``launch_timeout`` is the
    per-flush deadline (None disables deadlines); ``launch_retries`` is
    how many times a failed/timed-out wave is re-submitted with
    exponential backoff (+ jitter) before falling back to the host engine;
    ``breaker_threshold`` consecutive launch failures trip the
    host-fallback circuit breaker open (a permanent kernel error trips it
    immediately); while open, a background canary probe re-tries the
    device every ``probe_interval`` seconds (backing off to
    ``probe_backoff_max``) and flips the breaker closed on success.
    """

    launch_timeout: Optional[float] = 30.0
    launch_retries: int = 2
    backoff_base: float = 0.05
    backoff_max: float = 2.0
    backoff_jitter: float = 0.5
    breaker_threshold: int = 3
    probe_interval: float = 2.0
    probe_backoff_max: float = 30.0

    @classmethod
    def from_config(cls, config) -> "VerifyFaultPolicy":
        """Map the Configuration.verify_* knobs onto a policy."""
        return cls(
            launch_timeout=config.verify_launch_timeout,
            launch_retries=config.verify_launch_retries,
            breaker_threshold=config.verify_breaker_threshold,
            probe_interval=config.verify_probe_interval,
        )


@dataclass
class TagStats:
    """Per-tag (per-shard) attribution of coalesced verify traffic."""

    items: int = 0       # verify items this tag submitted
    waves: int = 0       # flushes containing >=1 of this tag's items
    solo_waves: int = 0  # flushes containing ONLY this tag's items


@dataclass
class ShardAttribution:
    """Wave-composition accounting for a shared coalescer.

    The sharded deployment's whole point is that one device launch carries
    verify items from MANY consensus groups (cross-shard fill); these
    counters make that measured instead of asserted.  Tags are opaque
    (shard ids in practice); untagged submissions are legal and only
    counted in ``waves``.  Updated at flush time — when the wave's
    composition is fixed — so failed launches still attribute."""

    waves: int = 0          # coalesced flushes total
    tagged_waves: int = 0   # flushes with >=1 tagged submission
    mixed_waves: int = 0    # flushes mixing >=2 distinct tags — the
    #                         cross-shard-coalescing witness
    max_tags_in_wave: int = 0
    per_tag: dict = field(default_factory=dict)

    def note_wave(self, futures) -> None:
        self.waves += 1
        counts: dict = {}
        for entry in futures:
            _fut, _start, n, tag = entry
            if tag is None:
                continue
            counts[tag] = counts.get(tag, 0) + n
        if not counts:
            return
        self.tagged_waves += 1
        if len(counts) >= 2:
            self.mixed_waves += 1
        self.max_tags_in_wave = max(self.max_tags_in_wave, len(counts))
        for tag, n in counts.items():
            st = self.per_tag.get(tag)
            if st is None:
                st = self.per_tag[tag] = TagStats()
            st.items += n
            st.waves += 1
            if len(counts) == 1:
                st.solo_waves += 1

    def snapshot(self) -> dict:
        """JSON-able block for bench rows and the tier-1 coalescing gate."""
        return {
            "waves": self.waves,
            "tagged_waves": self.tagged_waves,
            "mixed_waves": self.mixed_waves,
            "max_tags_in_wave": self.max_tags_in_wave,
            "per_tag": {
                str(tag): {"items": st.items, "waves": st.waves,
                           "solo_waves": st.solo_waves}
                for tag, st in sorted(self.per_tag.items(), key=lambda kv: str(kv[0]))
            },
        }


@dataclass
class FlushHoldStats:
    """Occupancy-aware flush-gating accounting (ISSUE 11 tentpole a).

    Every decision the gate takes is exported (``mesh_snapshot``'s
    ``hold`` block rides every bench row): how many waves were held, for
    how long, how many items the holds actually gained (``depth_gain``
    — the wave-deepening payoff), and the two bounded-latency outs —
    holds that ran out the hard ``verify_flush_hold`` deadline and
    flushes that skipped the hold because the breaker was open (host
    fallback must never wait on device-occupancy predictions)."""

    waves_held: int = 0
    held_ms: float = 0.0
    depth_gain_items: int = 0
    deadline_expired: int = 0
    breaker_bypass: int = 0

    def snapshot(self, hold_s: float) -> dict:
        return {
            "hold_s": float(hold_s),
            "waves_held": self.waves_held,
            "held_ms": round(self.held_ms, 2),
            "depth_gain_items": self.depth_gain_items,
            "deadline_expired": self.deadline_expired,
            "breaker_bypass": self.breaker_bypass,
        }


#: consecutive quiet turns of the event loop after which the coalescer
#: takes a batch's submitters to have gone quiet and closes it.  Replicas
#: that share a loop submit a round's votes within one turn of each other
#: (measured on the v5e host: PERF.md section 5), so the second quiet turn
#: is the margin
QUIET_TURNS = 2

#: a turn is quiet only if no submit arrived in it AND it took no longer
#: than this (seconds): the loop had nothing else to do.  An idle turn is
#: the watch's own wake-up (~0.1 ms on the v5e host); a turn of milliseconds
#: is a busy loop still working through the messages that make replicas
#: submit, and closing under it cut a committee's vote wave into launches
#: of one (n=16 on four chips: 2.4 x the launches, -6 % throughput)
IDLE_TURN = 0.0005


@dataclass
class WindowStats:
    """Why each batch left the coalescer, always on: ``quiet`` (its
    submitters stopped arriving), ``window`` (a trickle that never paused
    ran into the cap), ``full`` (``max_batch``), ``drain`` (it gathered
    behind a launch in flight and left when that launch ended), ``flip``
    (the eager flush of a view change), with the summed time from each
    batch's first submit to its close.  Exported beside the hold's
    accounting (``mesh_snapshot``'s ``window`` block)."""

    quiet: int = 0
    window: int = 0
    full: int = 0
    drain: int = 0
    flip: int = 0
    open_ms: float = 0.0

    def note(self, closed_by: str, open_s: float) -> None:
        setattr(self, closed_by, getattr(self, closed_by) + 1)
        self.open_ms += 1e3 * open_s

    def snapshot(self, window_s: float) -> dict:
        return {"window_s": float(window_s), **asdict(self),
                "open_ms": round(self.open_ms, 2)}


class TagRateTracker:
    """Per-tag submit-cadence tracking: the occupancy signal behind
    flush gating (the PR 8 drain-rate-EWMA idiom, pointed at ARRIVALS).

    Each ``submit(tag=...)`` notes wall time; the inter-submit gap per
    tag folds into an EWMA.  :meth:`any_imminent` answers the gate's one
    question — does any recently-live tag plausibly deliver another wave
    within the remaining hold budget?  A tag is *live* while the time
    since its last submit is within ``slack`` expected gaps (cold tags
    borrow the coalescer window as their gap estimate), and *imminent*
    while its predicted next arrival fits in the remaining budget.
    Untagged submissions track under ``None`` — single-group
    deployments still deepen their waves."""

    __slots__ = ("_last", "_ewma", "slack", "default_gap")

    #: tags silent this long are evicted outright — far beyond any
    #: plausible hold budget (sub-second), so eviction can never hide a
    #: tag a live hold could still be waiting for.  Bounds both memory
    #: and the any_imminent scan under shard churn (the PR 7 autoscaler
    #: retires shard ids over a long-lived process's life).
    EVICT_AFTER = 60.0
    #: dict size that triggers an eviction sweep in note() — sweeps are
    #: O(tags) but amortized across at least this many submits
    EVICT_SWEEP_AT = 128

    def __init__(self, default_gap: float = 0.002, slack: float = 4.0):
        self._last: dict = {}
        self._ewma: dict = {}
        self.slack = slack
        self.default_gap = default_gap

    def note(self, tag, now: float) -> None:
        prev = self._last.get(tag)
        if prev is not None:
            gap = max(now - prev, 1e-6)
            # sub-window gaps are the SAME logical wave (a shard's n
            # replicas submit the same quorum check within microseconds)
            # — folding them in would teach the tracker a microsecond
            # "cadence" and make every tag look quiet the moment its
            # burst ends; only inter-wave gaps carry cadence signal
            if gap >= self.default_gap:
                old = self._ewma.get(tag)
                self._ewma[tag] = gap if old is None \
                    else 0.5 * old + 0.5 * gap
        elif len(self._last) >= self.EVICT_SWEEP_AT:
            # a NEW tag on a full tracker: sweep out long-dead tags so
            # retired shards can never grow the dict without bound
            dead = [t for t, ts in self._last.items()
                    if now - ts > self.EVICT_AFTER]
            for t in dead:
                del self._last[t]
                self._ewma.pop(t, None)
        self._last[tag] = now

    def any_imminent(self, now: float, remaining: float,
                     budget: Optional[float] = None) -> bool:
        if budget is None:
            budget = self.slack * self.default_gap
        for tag, last in self._last.items():
            gap = self._ewma.get(tag)
            if gap is None:
                # cold tag (one submit seen, no cadence yet): stay
                # optimistic within the hold budget — the hard deadline
                # bounds the cost, and a second wave teaches the cadence
                if now - last <= budget:
                    return True
                continue
            if now - last > self.slack * gap:
                continue  # tag went quiet — stop predicting it
            # overdue counts as "any moment now"; otherwise the predicted
            # arrival must fit inside what is left of the hold budget
            if last + gap <= now + remaining:
                return True
        return False


@dataclass
class VerifyFaultStats:
    """Plain counters for the fault machinery — introspectable without a
    metrics provider; benches export them in every JSON row."""

    launch_failures: int = 0
    launch_timeouts: int = 0
    retries: int = 0
    breaker_opens: int = 0
    breaker_closes: int = 0
    host_fallback_batches: int = 0
    probe_attempts: int = 0
    probe_successes: int = 0
    abandoned_late_arrivals: int = 0
    #: resident launch threads the coalescer started: one for its loop,
    #: and one more after each abandoned launch (:class:`_LaunchThread`)
    launch_threads_started: int = 0


class HostVerifyEngine:
    """Sequential pure-Python verification — the CPU baseline engine."""

    # sequential engine: coalescing gains nothing, don't add window latency
    preferred_coalesce_window = 0.0

    def __init__(self, scheme=p256, metrics=None) -> None:
        self.scheme = scheme
        self.stats = VerifyStats(metrics=metrics)
        self._lock = threading.Lock()

    def _verify_one(self, item) -> bool:
        """Per-item hook; subclasses swap in other sequential backends."""
        return self.scheme.verify_item(item)

    def verify(self, items) -> list[bool]:
        t0 = time.perf_counter()
        out = [self._verify_one(item) for item in items]
        dt = time.perf_counter() - t0
        with self._lock:
            self.stats.record(len(items), len(items), dt)
        return out


class KernelCompileError(RuntimeError):
    """A device kernel failed on its FIRST launch at a shape — which is
    where jit compiles it.  Never a fallback: the engine raises it naming
    kernel and shape (at prewarm, when the ladder is prewarmed), and the
    coalescer opens the breaker on it at once instead of retrying."""


class JaxVerifyEngine:
    """Padded, jit-cached, batched signature verification on the JAX device.

    Lane sizes are fixed (powers of two) so at most ``len(pad_sizes)``
    kernels ever compile; every call pads up to the next size.  Thread-safe;
    the jit cache is shared.

    Which kernel serves a chunk is decided by what the engine can observe,
    never by a failure: on a TPU the static-key comb kernel takes every
    chunk whose signer keys are registrable and the arbitrary-key Pallas
    kernel (P-256's, Ed25519's) the rest; on other backends, and for
    schemes without a Pallas kernel, the XLA kernel does.  A kernel that
    fails raises — wrapped in
    :class:`KernelCompileError` when it was that shape's first launch —
    and ``stats.launches_by_kernel`` records which kernel served.
    """

    preferred_coalesce_window = 0.002  # the cap on a batch's wait for fan-in

    #: may a chunk be routed into a Pallas kernel?  An engine that places
    #: its lanes on a mesh and leaves the split to ``jit``
    #: (ShardedVerifyEngine, the 2D engine) opts out: pallas_call has no
    #: partitioning rules, so sharded lanes routed into it would silently
    #: collapse the mesh to one device.  An engine that wraps the kernel in
    #: ``shard_map`` itself (MeshVerifyEngine) stays in, for the kernels it
    #: wraps (:meth:`_pallas_kernels`)
    supports_pallas = True

    def __init__(self,
                 pad_sizes: Sequence[int] = (8, 32, 128, 512, 2048, 4096,
                                             8192, 16384),
                 scheme=p256, metrics=None,
                 ring: Optional[Sequence] = None,
                 request_pad_sizes: Optional[Sequence[int]] = None):
        """``ring`` / ``request_pad_sizes``: see :meth:`pin_ring`.

        ``pad_sizes``: the top rung bounds how much of a large cluster's
        quorum wave one launch can absorb (n=128 -> 10880 signatures);
        per-launch overhead is fixed, so bigger is better.  A size only
        compiles a kernel when a batch of that shape first occurs — call
        :meth:`prewarm_shapes` at startup to pay those compiles before
        protocol traffic (a mid-protocol compile can outlast heartbeat
        timeouts; benchmarks/throughput.py prewarms every rung)."""
        import jax  # deferred: engine construction may precede platform pin

        self._jax = jax
        self._metrics = metrics
        self.scheme = scheme
        self.pad_sizes = tuple(sorted(pad_sizes))
        self._kernel = jax.jit(scheme.verify_kernel)
        # The Pallas kernels are the DEFAULT path whenever the backend is a
        # TPU — a production embedder gets the fast path with no env
        # plumbing (see _use_pallas for the SMARTBFT_PALLAS override).  The
        # backend probe is LAZY — deciding at the first kernel call, when
        # backend init is inevitable anyway — so constructing an engine
        # never initializes jax (platform pins like force_cpu still work
        # after).
        self._pallas_on: Optional[bool] = None
        # static-key comb path (pallas_comb / pallas_ed25519): the fastest
        # route — host-precomputed per-replica comb tables, 32 point-op
        # levels per verify.  Used for every chunk whose signer keys are
        # registrable.
        self._comb, self._pallas_kernel = \
            self._pallas_kernels(scheme) if self.supports_pallas \
            else (None, None)
        #: the arbitrary-key Pallas kernel's own marshalling, where it is
        #: not the scheme's ``verify_inputs``: Ed25519's leaves R to the
        #: kernel and reports the lanes it refused
        self._pallas_prep = None
        if scheme is ed25519 and self._pallas_kernel is not None:
            from .pallas_ed25519 import prep_inputs

            self._pallas_prep = prep_inputs
        #: (kernel, shape) pairs that have launched once, i.e. compiled
        self._launched: set = set()
        self._lock = threading.Lock()
        self.stats = VerifyStats(metrics=metrics)
        #: the static keys (None: every key handed in is registrable, the
        #: contract before rings) and the ladder of everything else
        self._ring: Optional[frozenset] = None
        self.request_pad_sizes = tuple(sorted(request_pad_sizes or ())) \
            or self.pad_sizes
        if ring is not None:
            self.pin_ring(ring)

    def _pallas_kernels(self, scheme) -> tuple:
        """-> (comb verifier, arbitrary-key Pallas kernel) of ``scheme``,
        None where it has none."""
        if scheme is p256:
            from . import pallas_ecdsa
            from .pallas_comb import CombVerifier

            return CombVerifier(), pallas_ecdsa.ecdsa_verify
        if scheme is ed25519:
            from .pallas_ed25519 import Ed25519CombVerifier, ed25519_verify

            return Ed25519CombVerifier(), ed25519_verify
        return None, None

    def _use_pallas(self) -> bool:
        """Default the Pallas kernels on when the backend is a TPU.

        Called lazily from the first kernel invocation (never at engine
        construction — see __init__): any set SMARTBFT_PALLAS value other
        than "1" disables, "1" forces on, unset asks the backend.  A
        backend that fails to initialize raises here, like any other use
        of it would."""
        if self._comb is None and self._pallas_kernel is None:
            return False
        flag = os.environ.get("SMARTBFT_PALLAS")
        if flag is not None:
            return flag == "1"
        return self._jax.default_backend() == "tpu"

    def _guarded_launch(self, kernel: str, size: int, fn):
        """Run one kernel launch (``fn`` returns the host mask, or None
        for "not this kernel's chunk").  A failure on the first launch at
        a shape — the compile — is re-raised as KernelCompileError naming
        kernel and shape.  Later failures are runtime faults of a kernel
        that compiled and propagate as they are: the coalescer's deadline
        / retry / breaker / host-fallback stack handles a sick device."""
        try:
            out = fn()
        except Exception as exc:
            key = self._shape_key(kernel, size)
            if key in self._launched:
                raise
            raise KernelCompileError(
                f"{kernel} kernel "
                f"({getattr(self.scheme, '__name__', self.scheme)}) failed "
                f"on its first launch at {key[1]} lanes"
                + (f", {key[2]} key slots" if len(key) > 2 else "")
                + f": {type(exc).__name__}: {exc}"
            ) from exc
        if out is not None:
            self._launched.add(self._shape_key(kernel, size))
        return out

    def _shape_key(self, kernel: str, size: int) -> tuple:
        # read AFTER the launch: the comb kernel's compiled shape includes
        # the registry's key slots, which the launch itself may grow
        if kernel == "comb":
            return (kernel, size, self._comb.registry.slots())
        return (kernel, size)

    @staticmethod
    def _rung(sizes: Sequence[int], n: int) -> int:
        for s in sizes:
            if n <= s:
                return s
        return sizes[-1]

    def _pad_to(self, n: int) -> int:
        return self._rung(self.pad_sizes, n)

    def pin_ring(self, pubs) -> None:
        """Make the STATIC keys known: the orderers' ring(s), whose comb
        tables are worth building.  From then on the engine serves two key
        classes: ring keys ride the comb kernel on ``pad_sizes``, every
        other key (a channel's client identities: thousands, far over the
        comb registry's 128) rides the arbitrary-key kernel on
        ``request_pad_sizes`` — a flush holding both becomes one launch
        of each, verdicts reassembled in submission order.  A key outside
        the ring is then never registered, at first use or by
        :meth:`prewarm_keys` (which ignores it), so the registry's key
        slots, part of every comb rung's compiled shape, never grow.
        Additive: colocated groups pin a ring each.

        An engine told no ring registers every key it is handed, as
        before; on a backend without the Pallas kernels both classes ride
        the XLA kernel, each on its ladder."""
        pubs = list(pubs)
        if self._comb is not None:
            self._comb.prewarm_keys(pubs)
        self._ring = (self._ring or frozenset()) | frozenset(pubs)

    def verify(self, items) -> list[bool]:
        """items: scheme.make_item tuples -> validity per item."""
        if not items:
            return []
        ring = self._ring
        if ring is None or not self.supports_pallas:
            # no ring told, or an engine whose lanes jit partitions: one
            # kernel, one ladder.  (MeshVerifyEngine does split a pinned
            # ring: ring keys -> the comb kernel on every device, the
            # rest -> the sharded XLA kernel, its _verify_chunk's
            # ``generic``)
            return self._verify_class(items, False)
        # a key is the last field of an item in every scheme
        inside, outside = [], []
        for i, it in enumerate(items):
            (inside if it[-1] in ring else outside).append(i)
        if not outside or not inside:
            return self._verify_class(items, not inside)
        out: list = [None] * len(items)
        for idxs, generic in ((inside, False), (outside, True)):
            verdicts = self._verify_class([items[i] for i in idxs], generic)
            for i, v in zip(idxs, verdicts):
                out[i] = v
        return out

    def _verify_class(self, items, generic: bool) -> list[bool]:
        """One key class through its kernel, in chunks of its ladder's
        largest rung."""
        out: list[bool] = []
        cap = (self.request_pad_sizes if generic else self.pad_sizes)[-1]
        for off in range(0, len(items), cap):
            chunk = items[off : off + cap]
            out.extend(self._verify_chunk(chunk, True) if generic
                       else self._verify_chunk(chunk))
        return out

    def _place(self, a):
        """Hook for subclasses to place padded inputs (e.g. mesh-sharded)."""
        return a

    def prewarm_keys(self, pubs) -> None:
        """Register a known key set (e.g. the whole keyring) with the comb
        registry up front, so no verify path ever re-traces mid-protocol.
        With a ring pinned, keys outside it are left alone."""
        if self._comb is None:
            return
        if self._ring is not None:
            pubs = [pub for pub in pubs if pub in self._ring]
        self._comb.prewarm_keys(pubs)

    def prewarm_shapes(self, item, sizes: Optional[Sequence[int]] = None) -> None:
        """Compile every pad-ladder shape up front with copies of ``item``
        (one scheme verify item whose key is registered/registrable).

        Kernel shapes otherwise compile on first use — fine for benches,
        but in a live protocol the first large quorum wave would stall for
        the compile (possibly past heartbeat/view-change timeouts).

        With a ring pinned, an ``item`` whose key is outside it compiles
        the arbitrary-key kernel's rungs (``request_pad_sizes``)."""
        if sizes is None:
            generic = (self._ring is not None and self.supports_pallas
                       and item[-1] not in self._ring)
            sizes = self.request_pad_sizes if generic else self.pad_sizes
        for size in sizes:
            self.verify([item] * size)

    def _launch(self, items, size: int, generic: bool = False):
        """One padded chunk through the kernel this engine's backend and
        the chunk's keys select -> (kernel name, host mask, the lanes the
        host refused by cause or None).  ``generic``:
        the chunk's keys are outside the pinned ring, so the comb kernel
        is not asked."""
        if self._pallas_on is None:
            self._pallas_on = self._use_pallas()
        if self._pallas_on and self._comb is not None and not generic:
            # None: a key the registry cannot hold — the chunk rides the
            # arbitrary-key kernel below
            mask = self._guarded_launch(
                "comb", size, lambda: self._comb.verify(items, size))
            if mask is not None:
                return "comb", mask, None
        n = len(items)
        name, kernel = ("pallas", self._pallas_kernel) \
            if self._pallas_on and self._pallas_kernel is not None \
            else ("xla", self._kernel)
        refused = None
        with launch_span("verify.pack"):
            if name == "pallas" and self._pallas_prep is not None:
                with launch_span("verify.prep", lanes=n):
                    arrays, ok, refused = self._pallas_prep(items)
                arrays = (*arrays, ok)
            else:
                arrays = self.scheme.verify_inputs(items)
            padded = [
                self._place(np.concatenate(
                    [a, np.zeros((size - n,) + a.shape[1:], a.dtype)]
                ))
                for a in arrays
            ]

        def launch():
            with launch_span("verify.device"):
                return np.asarray(kernel(*padded))

        return name, self._guarded_launch(name, size, launch), refused

    def _verify_chunk(self, items, generic: bool = False) -> list[bool]:
        n = len(items)
        size = self._rung(self.request_pad_sizes, n) if generic \
            else self._pad_to(n)
        t0 = time.perf_counter()
        kernel, mask, refused = self._launch(items, size, generic)
        dt = time.perf_counter() - t0
        with self._lock:
            self.stats.record(n, size, dt, kernel, refused)
        note_lanes(kernel, size, n, refused=refused)
        return [bool(v) for v in mask[:n]]


def prewarm_verify_engine(engine, scheme=None,
                          sizes: Optional[Sequence[int]] = None) -> None:
    """Compile every pad-ladder shape of ``engine`` with a generated
    probe item — the device-rig prewarm helper (ISSUE 11 satellite).

    Pair with :func:`smartbft_tpu.utils.jaxenv.enable_compile_cache`:
    with the persistent compilation cache on, the first process pays each
    mesh shape's XLA compile ONCE and every later process — each bench
    subprocess, each sweep point — loads it from disk, so the 2–3 min
    per-process compile tax (PERF.md "cold-compile budget") stops
    poisoning device bench rows.  A shape the compiler refuses raises
    :class:`KernelCompileError` here, before any protocol traffic.  No-op
    for engines without a pad ladder (host engines compile nothing).

    An engine with a pinned ring (:meth:`JaxVerifyEngine.pin_ring`) has
    two kernels and this compiles both: the probe key is outside every
    ring, so the first pass compiles the arbitrary-key kernel's rungs
    (``request_pad_sizes``); a second pass presents the probe's signature
    under a ring key (a comb launch needs a registered key, not a valid
    signature) and compiles the comb kernel's rungs (``pad_sizes``)."""
    prewarm = getattr(engine, "prewarm_shapes", None)
    if prewarm is None:
        return
    scheme = scheme if scheme is not None else engine.scheme
    sk, pub = scheme.keygen(b"smartbft-prewarm-probe")
    item = scheme.make_item(b"p", scheme.sign_raw(sk, b"p"), pub)
    prewarm(item, sizes)
    ring = getattr(engine, "_ring", None)
    if ring and sizes is None and getattr(engine, "supports_pallas", False):
        prewarm(item[:-1] + (next(iter(ring)),), None)


def _hand_back(loop, fut: asyncio.Future, call) -> None:
    """Run ``call()`` on the calling (worker) thread and settle ``fut``
    on ``loop`` with its result or exception, whenever that is —
    possibly long after every awaiter gave up; a loop closed meanwhile
    gets nothing."""
    try:
        res = call()
    except BaseException as exc:  # noqa: BLE001 — ferried to the loop
        setter, payload = fut.set_exception, exc
    else:
        setter, payload = fut.set_result, res

    def resolve() -> None:
        if not fut.done():
            setter(payload)

    try:
        loop.call_soon_threadsafe(resolve)
    except RuntimeError:
        pass  # loop closed while the launch was in flight


class _Launch:
    """One live wave's engine call handed to the launch thread.  With
    the recorder on at the hand-in, ``launch`` is its id and ``t_in`` /
    ``t_out`` the stamps of ``verify.handin`` (the loop's hand-in -> the
    call's first line on the thread) and ``verify.handback`` (the call's
    return -> the awaiting coroutine resumed on the loop)."""

    __slots__ = ("engine", "pending", "fut", "loop", "worker", "launch",
                 "t_in", "t_out")

    def __init__(self, engine, pending: list, loop, worker):
        self.engine, self.pending = engine, pending
        self.loop, self.worker = loop, worker
        self.fut: asyncio.Future = loop.create_future()
        self.launch = -1
        self.t_in: Optional[float] = None
        self.t_out: Optional[float] = None


class _LaunchThread:
    """The coalescer's resident launch thread: ONE daemon thread, blocked
    on a queue, runs every live wave's engine call in turn, so a launch
    pays neither a thread's start nor JAX's first dispatch on a new
    thread.  It serves one loop and ends once that loop has closed, or
    after the call it is in when :meth:`retire` (an abandoned launch)
    queues its end."""

    #: how often an idle thread looks whether its loop has closed
    IDLE_CHECK_S = 0.5

    def __init__(self, loop, serve):
        self.loop = loop
        #: ``_Launch -> None``, raises nothing; held weakly, so that an
        #: idle thread keeps no coalescer (nor the deployment its
        #: recorder reaches) alive after the loop has gone
        self._serve = weakref.WeakMethod(serve)
        self._jobs: queue.SimpleQueue = queue.SimpleQueue()
        threading.Thread(target=self._run, name="smartbft-verify-launch",
                         daemon=True).start()

    def put(self, job: _Launch) -> None:
        self._jobs.put(job)

    def retire(self) -> None:
        self._jobs.put(None)

    def _run(self) -> None:
        # named at the OS before its first annotation (a hand-in's), or
        # the profiler's line of the thread keeps a nameless one
        name_this_thread()
        while True:
            try:
                job = self._jobs.get(timeout=self.IDLE_CHECK_S)
            except queue.Empty:
                if self.loop.is_closed():
                    return
                continue
            serve = self._serve()
            if job is None or serve is None:
                return
            serve(job)
            serve = job = None  # idle, the thread holds nothing of it


class AsyncBatchCoalescer:
    """Merges concurrent verify calls into shared kernel launches.

    The protocol core awaits ``submit(items)``; submissions that arrive
    together are flushed as one engine call on a worker thread.  A batch
    closes as soon as its submitters have gone quiet (no new arrival for
    :data:`QUIET_TURNS` idle turns of the loop), when ``max_batch`` fills, or
    when ``window`` seconds have passed since its first submit: the window
    is the LONGEST a batch may wait for company, not how long it always
    waits.  This is the TPU analog of the reference's per-signature
    goroutine fan-out — except the fan-*in* is explicit, so one launch
    serves many sequences and replicas.
    """

    def __init__(self, engine, window: float = 0.002, max_batch: int = 2048,
                 dedupe: bool = False,
                 policy: Optional[VerifyFaultPolicy] = None,
                 fallback_engine=None, metrics=None,
                 hold: Optional[float] = None):
        """``dedupe``: verify each DISTINCT item once per flush and fan the
        verdict out to every submitter.  Verification is a pure function of
        (message, signature, key), so this is sound; it pays off when many
        colocated replicas share one engine — a quorum wave then contains
        each commit signature up to n times (every replica checks the same
        votes), and deduplication collapses an n*(quorum-1) wave to at most
        n distinct lanes.  The reference never shares a verifier across
        replicas, so it has no analogous seam (view.go:537-541 is
        per-replica fan-out).  Off by default: single-replica engines see
        no repeats, and the dict pass would be pure overhead.

        ``policy``: a :class:`VerifyFaultPolicy` arming launch deadlines,
        retry/backoff, and the host-fallback circuit breaker.  None keeps
        the legacy contract: one attempt, failures surface to submitters as
        plain RuntimeError.  With a policy, transient failures are retried,
        exhausted waves route to ``fallback_engine`` (consensus keeps
        committing at CPU speed), and only a wave that exhausts retries AND
        the fallback raises :class:`~smartbft_tpu.types.VerifyPlaneDown`.
        ``metrics``: an optional TPUCryptoMetrics bundle counting launch
        failures/timeouts/retries and breaker transitions.

        ``hold``: occupancy-aware flush gating (the
        ``Configuration.verify_flush_hold`` knob).  When > 0, a flush
        whose wave is below a pad-ladder rung briefly HOLDS — up to
        ``hold`` wall-clock seconds, the hard latency bound — while the
        per-tag submit-rate tracker predicts more waves inbound, so one
        deeper launch replaces several shallow ones (the fixed-launch-
        overhead economics of PAPERS.md [7]).  The hold never engages
        when the breaker is open (host fallback must not wait), never
        past ``max_batch``, and flushes the moment the wave lands
        exactly on a rung (zero pad waste beats more depth).  None/0
        keeps the legacy eager-window contract."""
        self.engine = engine
        self.window = window
        self.max_batch = max_batch
        self.dedupe = dedupe
        self.policy = policy
        #: a constructor-supplied policy is EXPLICIT and never overridden;
        #: defaulted/config-wired policies stay re-wirable (configure())
        self._policy_explicit = policy is not None
        self.fallback_engine = fallback_engine
        self.metrics = metrics
        if metrics is not None:
            metrics.breaker_state.set(0.0)  # healthy until proven otherwise
        self.fault_stats = VerifyFaultStats()
        self.shard_stats = ShardAttribution()
        #: occupancy-aware flush gating (ISSUE 11): hold budget seconds
        #: (0 = eager legacy flushing), per-tag arrival tracker, and the
        #: exported decision accounting.  A constructor-supplied hold is
        #: EXPLICIT like a constructor policy (configure_hold never
        #: overrides it); config-wired holds stay re-wirable.
        self.hold = float(hold) if hold else 0.0
        self._hold_explicit = hold is not None
        self.hold_stats = FlushHoldStats()
        #: why and after how long each batch closed (always on)
        self.window_stats = WindowStats()
        self._tag_rates = TagRateTracker(default_gap=max(window, 0.001))
        #: mesh graduation accounting (CryptoProvider.configure_verify_mesh
        #: writes these; they live on the coalescer because the coalescer
        #: is the ONE shared object in sharded mode — like the breaker)
        self.mesh_configured = 0   # Configuration.verify_mesh_devices wired
        self.mesh_downgrades = 0   # loud unbuildable-mesh downgrades
        #: flight recorder (obs.TraceRecorder, disabled unless tracing) —
        #: verify wait/hold/launch spans + breaker transitions,
        #: correlated by a per-coalescer launch id.  Shared like the
        #: breaker: ONE recorder serves every colocated shard.
        self.recorder = standby(node="verify")
        self._launch_seq = 0
        #: the tags of the launch in flight's submitters (recorder on)
        self._launch_tags: list = []
        self._pending: list[tuple] = []
        self._futures: list[tuple[asyncio.Future, int, int, object]] = []
        #: the open batch: its number (what a watch checks to see that the
        #: batch is still its own) and the instant of its first submit, on
        #: the monotonic clock and on the recorder's (None: recorder off)
        self._batch_seq = 0
        self._batch_opened = 0.0
        self._batch_opened_rec: Optional[float] = None
        self._flush_scheduled = False
        self._launch_inflight = False
        self._lock = asyncio.Lock()
        self._log = logging.getLogger("smartbft_tpu.crypto")
        self._breaker_is_open = False
        self._consecutive_failures = 0
        self._probe_task: Optional[asyncio.Task] = None
        #: the resident thread live waves' engine calls run on (started
        #: by the first launch that needs it)
        self._launcher: Optional[_LaunchThread] = None
        #: a known-well-formed item from the last wave, re-verified by the
        #: breaker probe as the device-health canary
        self._canary: Optional[tuple] = None
        #: flip-warm mode (ISSUE 15): until this wall-clock instant the
        #: plane flushes EAGERLY — no coalescing window, no occupancy
        #: hold.  Armed by note_view_flip when a view change installs a
        #: new view: the mesh idled through the depose, and the flip's
        #: first deep-window waves must launch at once so the stalled
        #: backlog lands on a warm plane instead of re-paying the
        #: batching latency it was tuned for in steady state.
        self._warm_until = 0.0
        self.flip_warms = 0
        self.flip_warm_bypasses = 0

    # -- late wiring ---------------------------------------------------------

    def configure(self, policy: Optional[VerifyFaultPolicy] = None,
                  fallback_engine=None, metrics=None,
                  explicit: bool = False) -> None:
        """Late fault-plane wiring (the Consensus facade calls this at
        start AND on every reconfig with Configuration-derived values).

        A policy supplied at construction is explicit and is never
        overridden; a defaulted or previously config-wired policy IS
        replaced, so Configuration.verify_* knobs (and reconfigs carrying
        new ones) actually reach the plane.  Fallback engine and metrics
        fill only when unset — the coalescer may be shared across
        colocated replicas and churning instances would be pointless."""
        if policy is not None and (explicit or not self._policy_explicit):
            self.policy = policy
            self._policy_explicit = self._policy_explicit or explicit
        if fallback_engine is not None and self.fallback_engine is None:
            self.fallback_engine = fallback_engine
        if metrics is not None and self.metrics is None:
            self.metrics = metrics
            self.metrics.breaker_state.set(1.0 if self._breaker_is_open else 0.0)

    def attach_recorder(self, recorder) -> None:
        """Point the verify plane's trace events at ``recorder`` (the
        harness/embedder wires its own; the default disabled recorder
        keeps the hot path at one attribute read per site)."""
        self.recorder = standby(recorder, node="verify")

    def configure_hold(self, hold: Optional[float],
                       explicit: bool = False) -> None:
        """Late flush-gating wiring (``Consensus._wire_verify_plane``
        applies ``Configuration.verify_flush_hold`` here at start and on
        every reconfig).  Same precedence contract as :meth:`configure`:
        a constructor-supplied hold is explicit and never overridden; a
        defaulted or previously config-wired one IS replaced."""
        if hold is None:
            return
        if explicit or not self._hold_explicit:
            self.hold = max(0.0, float(hold))
            self._hold_explicit = self._hold_explicit or explicit

    #: how long flip-warm mode lasts (wall seconds): long enough for the
    #: new view's first deep windows to stage and launch their quorum
    #: waves, short enough that steady-state coalescing resumes within
    #: the same failover transient
    FLIP_WARM_SPAN = 0.25

    def note_view_flip(self, span: Optional[float] = None) -> None:
        """A view change just installed a new view (ISSUE 15): flush any
        pending wave immediately and run windowless/holdless for
        ``span`` seconds.  Safe from any caller on the event loop; a
        caller without a running loop (unit code) just arms the mode."""
        self._warm_until = time.monotonic() + (
            span if span is not None else self.FLIP_WARM_SPAN
        )
        self.flip_warms += 1
        if self._pending and not self._launch_inflight:
            # flush NOW even when a windowed flush is already watching
            # the batch: the immediate task swaps the batch out and the
            # watch, finding the batch no longer its own, ends without a
            # flush.
            # Probe for the loop BEFORE building the coroutine: a no-loop
            # caller just arms the mode (the next submit flushes eagerly),
            # and an abandoned coroutine would warn "never awaited".
            try:
                asyncio.get_running_loop()
            except RuntimeError:
                return
            create_logged_task(
                self._flush_after(0.0, "flip"), name="coalescer-flush-flip",
                busy=(self.recorder, "verify.flush"),
            )
            self._flush_scheduled = True

    def note_view_depose(self, span: Optional[float] = None) -> None:
        """The current view is being torn down for a view change (ISSUE
        15): same eager-flush transient as the flip — in-window waves
        already handed to the plane launch NOW instead of idling in the
        coalescing window/hold while the VC sub-protocol runs, so the
        plane stays busy through the depose and the flip lands warm."""
        self.note_view_flip(span)

    def _flip_warm(self) -> bool:
        return time.monotonic() < self._warm_until

    @property
    def breaker_open(self) -> bool:
        return self._breaker_is_open

    @property
    def busy(self) -> bool:
        """Submissions are waiting for a flush or riding a launch."""
        return bool(self._pending) or self._launch_inflight \
            or self._flush_scheduled

    def fault_snapshot(self) -> dict:
        """One JSON-able dict for bench rows: breaker state + fault counts,
        so a degraded run is never silently reported as a device run."""
        s = self.fault_stats
        return {
            "policy_configured": self.policy is not None,
            "open": self._breaker_is_open,
            "degraded": self._breaker_is_open or s.host_fallback_batches > 0,
            "opens": s.breaker_opens,
            "closes": s.breaker_closes,
            "launch_failures": s.launch_failures,
            "launch_timeouts": s.launch_timeouts,
            "retries": s.retries,
            "host_fallback_batches": s.host_fallback_batches,
            "probe_attempts": s.probe_attempts,
            "probe_successes": s.probe_successes,
            "abandoned_late_arrivals": s.abandoned_late_arrivals,
            "launch_threads_started": s.launch_threads_started,
            # ISSUE 15: view-flip warm transients (eager windowless
            # flushing) and the occupancy holds they bypassed
            "flip_warms": self.flip_warms,
            "flip_warm_bypasses": self.flip_warm_bypasses,
        }

    def shard_snapshot(self) -> dict:
        """Wave-composition attribution (see :class:`ShardAttribution`)."""
        return self.shard_stats.snapshot()

    def mesh_snapshot(self) -> dict:
        """The ``mesh`` block of every bench row: which verify plane ran
        (single device or an N-device mesh), per-launch fill per device,
        pad waste, and the loud-downgrade count — so a row measured on a
        downgraded single-device plane is never mistaken for a mesh run."""
        eng = self.engine
        devices = int(getattr(eng, "devices", 0))
        out = {
            "enabled": devices > 0,
            "devices": devices if devices > 0 else 1,
            "configured_devices": self.mesh_configured,
            "downgrades": self.mesh_downgrades,
            "topology": getattr(eng, "topology", "1d"),
            # occupancy-aware flush gating decisions (ISSUE 11): every
            # hold the gate took, its cost, and its depth payoff
            "hold": self.hold_stats.snapshot(self.hold),
            # why each batch closed, and how long batches stood open
            "window": self.window_stats.snapshot(self.window),
        }
        snap = getattr(eng, "mesh_snapshot", None)
        if snap is not None:
            try:
                out.update(snap())
            except Exception:  # noqa: BLE001 — a stats hiccup must not
                pass           # poison a bench row assembly
        return out

    async def submit(self, items, tag=None) -> list[bool]:
        """``tag``: opaque attribution label (the submitter's shard id in
        sharded mode) — flush composition is tracked per tag in
        :attr:`shard_stats`, so cross-shard launch mixing is measurable."""
        if not items:
            return []
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        rec = self.recorder
        t_enqueue = rec.now() if rec.enabled else None
        now = time.monotonic()
        self._tag_rates.note(tag, now)
        async with self._lock:
            start = len(self._pending)
            if not start:  # this submit opens a batch
                self._batch_opened = now
                self._batch_opened_rec = t_enqueue
            self._pending.extend(items)
            self._futures.append((fut, start, len(items), tag))
            # _flush_scheduled covers exactly the CURRENT batch: it resets
            # when a flush swaps the batch out.  While a launch is already
            # in flight nothing is scheduled here — completion-triggered
            # flushing (below) drains whatever accumulated the moment the
            # engine frees, which is what lets k pipelined decisions'
            # quorum waves merge into one launch: queueing a second launch
            # behind a busy device would only split the batch without
            # finishing any earlier.
            if self._launch_inflight:
                pass
            elif len(self._pending) >= self.max_batch:
                create_logged_task(
                    self._flush_after(0.0, "full"),
                    name="coalescer-flush-full",
                    busy=(self.recorder, "verify.flush"),
                )
                self._flush_scheduled = True
            elif not self._flush_scheduled:
                self._flush_scheduled = True
                # the batch's first submit: watch it until its submitters
                # have gone quiet, for at most ``window`` seconds.
                # Flip-warm mode: the failover transient flushes eagerly
                # (no watch at all) so the new view's first waves launch
                # at once
                flush = self._flush_after(0.0, "flip") if self._flip_warm() \
                    else self._flush_after(self.window)
                create_logged_task(
                    flush, name="coalescer-flush",
                    busy=(self.recorder, "verify.flush"),
                )
        if t_enqueue is None:
            return await fut
        try:
            return await fut
        finally:
            # a wait: this submitter's enqueue -> its verdict resolved
            rec.wait("verify.wait", t_enqueue,
                     extra={"items": len(items), "tag": str(tag)})

    def _rung_exact(self, n: int) -> bool:
        """A wave sitting exactly on a pad-ladder rung has zero pad
        waste — holding it can only trade guaranteed-perfect fill for
        speculative depth, so the gate flushes it immediately."""
        sizes = getattr(self.engine, "pad_sizes", None)
        return bool(sizes) and n in sizes

    async def _maybe_hold(self) -> None:
        """Occupancy-aware flush gating: briefly hold this flush while
        the per-tag arrival tracker predicts more waves inbound, bounded
        by the hard ``hold`` deadline.  See the constructor docstring
        for the never-hold conditions (breaker open, full batch,
        rung-exact wave)."""
        budget = self.hold
        if budget <= 0.0:
            return
        if self._flip_warm():
            # the failover transient must not trade latency for depth
            self.flip_warm_bypasses += 1
            return
        rec = self.recorder
        t_hold = rec.now() if rec.enabled else None
        start = time.monotonic()
        start_depth: Optional[int] = None
        quantum = max(min(self.window, budget / 4.0), 0.001)
        expired = False
        while True:
            now = time.monotonic()
            held = now - start
            async with self._lock:
                if self._launch_inflight or not self._pending:
                    break  # another flush task took the batch
                n = len(self._pending)
                if self._breaker_is_open:
                    if start_depth is None:
                        self.hold_stats.breaker_bypass += 1
                    break  # host fallback must not wait on predictions
                if n >= self.max_batch or self._rung_exact(n):
                    break
                if held >= budget:
                    expired = True
                    break
                if not self._tag_rates.any_imminent(now, budget - held,
                                                    budget):
                    break
                if start_depth is None:
                    start_depth = n
            await asyncio.sleep(quantum)
        if start_depth is not None:
            held_s = time.monotonic() - start
            self.hold_stats.waves_held += 1
            self.hold_stats.held_ms += 1e3 * held_s
            async with self._lock:
                gain = max(len(self._pending) - start_depth, 0)
            self.hold_stats.depth_gain_items += gain
            if expired:
                self.hold_stats.deadline_expired += 1
            if self.metrics is not None \
                    and hasattr(self.metrics, "count_waves_held"):
                self.metrics.count_waves_held.add(1)
                self.metrics.count_hold_depth_gain.add(gain)
            if rec.enabled:
                rec.wait("verify.hold", t_hold,
                         extra={"depth_gain": gain, "expired": expired})

    async def _watch(self, window: float) -> Optional[str]:
        """Wait for the open batch's company, a turn of the loop at a
        time: until :data:`QUIET_TURNS` turns in a row were quiet (no
        submit joined the batch and the loop did nothing else for longer
        than :data:`IDLE_TURN`: everyone who was going to submit has), or
        ``window`` seconds have passed since its first submit (a trickle
        that never pauses is cut there, as it always was).  -> why the
        batch closes, or None when another flush (``max_batch``, a view
        flip) has taken it meanwhile.  It watches only what the coalescer
        itself observes, its arrivals and the length of its own turns, so
        it adapts to any deployment: one replica a process closes after
        two idle turns, a committee on a busy loop rides the cap."""
        batch = self._batch_seq
        deadline = self._batch_opened + window
        seen, quiet = len(self._pending), 0
        woke = time.monotonic()
        while True:
            await asyncio.sleep(0)
            now = time.monotonic()
            idle, woke = now - woke <= IDLE_TURN, now
            n = len(self._pending)
            if self._batch_seq != batch or not n:
                return None
            if n > seen or not idle:
                seen, quiet = n, 0
            else:
                quiet += 1
                if quiet >= QUIET_TURNS:
                    return "quiet"
            if now >= deadline:
                return "window"

    async def _flush_after(self, window: float,
                           closed_by: str = "window") -> None:
        """Close the open batch and launch it: at once for ``window`` 0
        (``closed_by`` says on whose account), else when :meth:`_watch`
        says so."""
        if window:
            closed_by = await self._watch(window)
            if closed_by is None:
                return
        await self._maybe_hold()
        # swap under the lock, verify outside it — submissions arriving
        # during the kernel launch accumulate into the NEXT batch
        async with self._lock:
            if self._launch_inflight:
                # a completion-triggered flush will pick the batch up
                self._flush_scheduled = False
                return
            pending, futures = self._pending, self._futures
            self._pending, self._futures = [], []
            self._flush_scheduled = False
            opened, opened_rec = self._batch_opened, self._batch_opened_rec
            if pending:
                self._launch_inflight = True
                self._batch_seq += 1
        if not pending:
            return
        self.window_stats.note(closed_by, time.monotonic() - opened)
        # attribution happens when the wave's composition is fixed, so a
        # failed launch still counts its shard mix
        self.shard_stats.note_wave(futures)
        self._launch_seq += 1
        launch_id = self._launch_seq
        rec = self.recorder
        t_launch = None
        if rec.enabled:
            self._launch_tags = sorted(
                {str(f[3]) for f in futures if f[3] is not None})
            # a wait: the batch's first enqueue -> the batch swapped out
            rec.wait("verify.window", opened_rec, launch=launch_id,
                     extra={"closed_by": closed_by, "items": len(pending),
                            "submitters": len(futures)})
            t_launch = rec.now()
        try:
            results = await self._launch_wave(pending)
        except Exception as exc:
            if rec.enabled:
                rec.wait("verify.launch", t_launch, launch=launch_id,
                         extra={"items": len(pending), "failed": True})
            err = exc if isinstance(exc, VerifyPlaneDown) else RuntimeError(
                f"batch verify failed: {exc!r}"
            )
            for fut, _, _, _ in futures:
                if not fut.done():
                    fut.set_exception(err)
            await self._launch_done()
            return
        if rec.enabled:
            # a wait: the wave's round trip through the launch's thread
            rec.wait("verify.launch", t_launch, launch=launch_id,
                     extra={"items": len(pending)})
        for fut, start, count, _tag in futures:
            if not fut.done():
                fut.set_result(results[start : start + count])
        await self._launch_done()

    async def _launch_done(self) -> None:
        """Completion-triggered flush: drain accumulated submissions now."""
        async with self._lock:
            self._launch_inflight = False
            if self._pending and not self._flush_scheduled:
                self._flush_scheduled = True
                create_logged_task(
                    self._flush_after(0.0, "drain"),
                    name="coalescer-flush-drain",
                    busy=(self.recorder, "verify.flush"),
                )

    # -- the fault machinery -------------------------------------------------

    async def _launch_wave(self, pending: list) -> list[bool]:
        """One coalesced wave through the fault machinery: deadline ->
        retry/backoff -> host fallback.  Raises VerifyPlaneDown only when
        every stage is exhausted; transient device errors never surface to
        the protocol plane."""
        pol = self.policy
        if pol is None:  # legacy contract: one attempt, no deadline
            return await asyncio.to_thread(self._verify_batch, pending)
        self._canary = pending[0]
        attempts = 1 + max(0, pol.launch_retries)
        delay = pol.backoff_base
        last_exc: Optional[Exception] = None
        for attempt in range(attempts):
            if self._breaker_is_open:
                break  # degraded mode: don't queue waves behind a dead device
            try:
                results = await self._call_engine_with_deadline(
                    self.engine, pending, pol.launch_timeout
                )
            except Exception as exc:  # noqa: BLE001 — classified below
                last_exc = exc
                self._note_launch_failure(exc)
                if self._breaker_is_open or attempt + 1 >= attempts:
                    continue
                self.fault_stats.retries += 1
                if self.metrics is not None:
                    self.metrics.count_launch_retries.add(1)
                await asyncio.sleep(
                    delay * (1.0 + pol.backoff_jitter * random.random())
                )
                delay = min(delay * 2.0, pol.backoff_max)
                continue
            self._consecutive_failures = 0
            return results
        if self.fallback_engine is not None:
            try:
                results = await asyncio.to_thread(
                    self._verify_batch, pending, self.fallback_engine
                )
            except Exception as exc:  # noqa: BLE001 — terminal either way
                raise VerifyPlaneDown(
                    f"batch verify failed: device path exhausted "
                    f"({last_exc!r}) and the host fallback failed too: "
                    f"{exc!r}"
                ) from exc
            self.fault_stats.host_fallback_batches += 1
            if self.metrics is not None:
                self.metrics.count_host_fallback_batches.add(1)
            return results
        if last_exc is None:
            # breaker already open on entry: no device attempt was made
            raise VerifyPlaneDown(
                "batch verify failed: circuit breaker open (failing fast) "
                "and no fallback engine is configured"
            )
        raise VerifyPlaneDown(
            f"batch verify failed after {attempts} launch attempt(s) and "
            f"no fallback engine is configured: {last_exc!r}"
        ) from last_exc

    def _hand_in(self, engine, pending: list) -> _Launch:
        """Queue one engine call for the resident launch thread, which
        is started on first use, and anew on another loop or after an
        abandoned launch (:meth:`_abandon`)."""
        loop = asyncio.get_running_loop()
        worker = self._launcher
        if worker is None or worker.loop is not loop:
            if worker is not None:
                worker.retire()
            self.fault_stats.launch_threads_started += 1
            worker = self._launcher = _LaunchThread(loop, self._serve_launch)
        job = _Launch(engine, pending, loop, worker)
        rec = self.recorder
        if rec.enabled:
            job.launch, job.t_in = self._launch_seq, rec.now()
        worker.put(job)
        return job

    def _serve_launch(self, job: _Launch) -> None:
        """The launch thread's side of one hand-in."""
        rec = self.recorder
        if job.t_in is not None:
            rec.wait("verify.handin", job.t_in, launch=job.launch,
                     extra={"threads_started":
                            self.fault_stats.launch_threads_started})

        def call() -> list[bool]:
            try:
                return self._verify_batch(job.pending, job.engine)
            finally:
                if job.t_in is not None:
                    job.t_out = rec.now()

        _hand_back(job.loop, job.fut, call)

    def _abandon(self, job: _Launch) -> None:
        """A launch past its deadline: its thread is left to finish the
        call it is in and then ends (an orphan, one per abandoned
        launch), and the next launch starts a fresh resident thread, so
        a hung device cannot wedge the flush pipeline."""
        if self._launcher is job.worker:
            self._launcher = None
        job.worker.retire()
        self._discard_late(job.fut)

    def _spawn_probe(self, item) -> asyncio.Future:
        """Run the breaker probe's engine call on a DAEMON thread of its
        own (off the hot path; a parked probe is re-awaited, not
        re-spawned)."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        threading.Thread(
            target=_hand_back, name="smartbft-verify-launch", daemon=True,
            args=(loop, fut,
                  lambda: self._verify_batch([item], self.engine)),
        ).start()
        return fut

    def _discard_late(self, fut: asyncio.Future) -> None:
        """Mark an abandoned launch: count + log its late arrival and
        retrieve any exception so asyncio never warns at GC time."""

        def discard(f: asyncio.Future) -> None:
            self.fault_stats.abandoned_late_arrivals += 1
            exc = f.exception()
            self._log.warning(
                "abandoned verify launch completed late (%s)",
                "successfully" if exc is None else f"with {exc!r}",
            )

        fut.add_done_callback(discard)

    async def _call_engine_with_deadline(self, engine, pending: list,
                                         timeout: Optional[float]):
        """Run one engine call on the resident launch thread under the
        launch deadline.  On expiry the launch is ABANDONED
        (:meth:`_abandon`): its late result is discarded on arrival, and
        the caller gets LaunchTimeout."""
        if timeout is None:
            return await asyncio.to_thread(self._verify_batch, pending, engine)
        job = self._hand_in(engine, pending)
        try:
            results = await asyncio.wait_for(asyncio.shield(job.fut), timeout)
        except asyncio.TimeoutError:
            self._abandon(job)
            raise LaunchTimeout(
                f"verify launch exceeded its {timeout:.3f}s deadline; "
                "wave abandoned"
            ) from None
        if job.t_out is not None:
            self.recorder.wait("verify.handback", job.t_out,
                               launch=job.launch)
        return results

    def _note_launch_failure(self, exc: Exception) -> None:
        self._consecutive_failures += 1
        self.fault_stats.launch_failures += 1
        timed_out = isinstance(exc, LaunchTimeout)
        if timed_out:
            self.fault_stats.launch_timeouts += 1
        if self.metrics is not None:
            self.metrics.count_launch_failures.add(1)
            if timed_out:
                self.metrics.count_launch_timeouts.add(1)
        permanent = isinstance(exc, KernelCompileError)
        self._log.warning(
            "verify launch failure (consecutive %d): %s: %s",
            self._consecutive_failures, type(exc).__name__, exc,
        )
        if permanent or (
            self._consecutive_failures >= max(1, self.policy.breaker_threshold)
        ):
            self._open_breaker(
                "permanent kernel error" if permanent
                else f"{self._consecutive_failures} consecutive launch failures"
            )

    def _open_breaker(self, reason: str) -> None:
        if self._breaker_is_open:
            return
        self._breaker_is_open = True
        self.fault_stats.breaker_opens += 1
        if self.metrics is not None:
            self.metrics.count_breaker_open.add(1)
            self.metrics.breaker_state.set(1.0)
        if self.recorder.enabled:
            self.recorder.record("ctl.breaker_open",
                                 extra={"reason": reason})
        self._log.warning(
            "verify-plane circuit breaker OPEN (%s); %s",
            reason,
            "waves degrade to the host fallback engine"
            if self.fallback_engine is not None else
            "NO fallback engine configured — waves fail fast until the "
            "device recovers",
        )
        if self._probe_task is None or self._probe_task.done():
            self._probe_task = create_logged_task(
                self._probe_loop(), name="verify-breaker-probe"
            )

    def _close_breaker(self) -> None:
        self._breaker_is_open = False
        self._consecutive_failures = 0
        self.fault_stats.breaker_closes += 1
        if self.metrics is not None:
            self.metrics.count_breaker_close.add(1)
            self.metrics.breaker_state.set(0.0)
        if self.recorder.enabled:
            self.recorder.record("ctl.breaker_close")
        self._log.warning(
            "verify-plane circuit breaker CLOSED: device engine recovered"
        )

    async def _probe_loop(self) -> None:
        """Background canary: while the breaker is open, periodically
        re-verify ONE item on the device — off the hot path, live waves
        stay on the fallback — and flip the breaker closed on the first
        call that completes.

        A probe whose thread is still PARKED in a hung device is re-awaited
        on the next round instead of spawning a fresh thread, so a
        long-lived outage holds at most one outstanding probe thread (plus
        the abandoned wave that tripped the breaker), not one per probe."""
        pol = self.policy
        delay = pol.probe_interval
        fut: Optional[asyncio.Future] = None
        try:
            while self._breaker_is_open:
                await asyncio.sleep(delay)
                item = self._canary
                if item is None:
                    continue
                self.fault_stats.probe_attempts += 1
                if fut is not None and fut.done():
                    # the parked probe concluded during the sleep: consume
                    # it — a late success still proves the device healthy,
                    # and a late failure must be retrieved (else asyncio
                    # warns at GC) before a fresh probe spawns
                    exc = fut.exception()
                    fut = None
                    if exc is None:
                        self.fault_stats.probe_successes += 1
                        self._close_breaker()
                        return
                    self._log.info(
                        "verify-plane probe completed late with %r", exc
                    )
                if fut is None:
                    fut = self._spawn_probe(item)
                try:
                    await asyncio.wait_for(
                        asyncio.shield(fut), pol.launch_timeout
                    )
                except asyncio.TimeoutError:
                    self._log.info(
                        "verify-plane probe still pending after %.2fs; "
                        "re-checking in %.2fs", pol.launch_timeout, delay,
                    )
                    delay = min(delay * 2.0, pol.probe_backoff_max)
                    continue
                except Exception as exc:  # noqa: BLE001 — device still down
                    fut = None  # concluded (handled here), not parked
                    self._log.info(
                        "verify-plane probe failed (%r); next probe in %.2fs",
                        exc, delay,
                    )
                    delay = min(delay * 2.0, pol.probe_backoff_max)
                    continue
                self.fault_stats.probe_successes += 1
                self._close_breaker()
                return
        finally:
            if fut is not None and not fut.done():
                self._discard_late(fut)  # loop torn down mid-probe

    # -- the engine call -----------------------------------------------------

    def _verify_batch(self, pending: list, engine=None) -> list[bool]:
        """One engine call for the flushed batch, optionally deduplicated."""
        engine = self.engine if engine is None else engine
        name_this_thread()
        if self.recorder.enabled:
            # the engine's pack / device spans on this thread carry it,
            # its lane marks the submitters' tags
            set_thread_launch(self._launch_seq, self._launch_tags)
        if not self.dedupe:
            return self._engine_call(engine, pending)
        try:
            first: dict = {}
            for it in pending:
                first.setdefault(it, len(first))
        except TypeError:
            # unhashable scheme items — dedupe silently degrades to 1:1
            return self._engine_call(engine, pending)
        if len(first) == len(pending):
            return self._engine_call(engine, pending)
        distinct = self._engine_call(engine, list(first))
        return [distinct[first[it]] for it in pending]

    @staticmethod
    def _engine_call(engine, items: list) -> list[bool]:
        """engine.verify + the result-length guard: a short/long result
        would silently mis-slice every submitter's future."""
        results = engine.verify(items)
        if len(results) != len(items):
            raise VerifyResultMismatch(
                f"engine {type(engine).__name__} returned {len(results)} "
                f"results for {len(items)} items — refusing to mis-slice "
                "the coalesced wave"
            )
        return results


# ---------------------------------------------------------------------------
# SPI provider
# ---------------------------------------------------------------------------

class CryptoProvider:
    """Crypto subset of the Signer/Verifier SPI over a :class:`Keyring`.

    The application's Verifier implementation delegates
    sign/verify-signature duties here and keeps request/proposal semantics
    (payload checks, request extraction) to itself.  ``scheme`` selects the
    signature system (:mod:`p256` default; :mod:`ed25519` — BASELINE.md
    configs[3] — via :class:`Ed25519CryptoProvider`); the engine must be
    built for the same scheme.
    """

    scheme = p256

    def __init__(self, keyring: Keyring, engine=None,
                 coalesce_window: Optional[float] = None,
                 coalescer: Optional[AsyncBatchCoalescer] = None,
                 fault_policy: Optional[VerifyFaultPolicy] = None,
                 fallback_engine=None):
        """``coalescer``: share one AsyncBatchCoalescer across providers —
        the cross-REPLICA batching axis of BASELINE configs[2]: when many
        replicas run against one chip, their concurrent quorum checks merge
        into shared kernel launches instead of queueing per-replica ones.

        ``fault_policy`` / ``fallback_engine``: verify-plane fault
        tolerance (see AsyncBatchCoalescer).  Device-shaped engines (those
        with a pad ladder) default to the full stack — launch deadlines,
        retry/backoff, and a host-fallback breaker built from the same
        scheme — so a hung or failing device can never wedge consensus;
        host engines keep the legacy single-attempt contract unless a
        policy is supplied (or wired later by the Consensus facade)."""
        self.keyring = keyring
        #: opaque attribution tag (the shard id in sharded deployments) —
        #: every coalesced submission from this provider carries it, so a
        #: shared coalescer can report per-shard items and cross-shard
        #: launch mixing (ShardAttribution).  Settable post-construction;
        #: None = untagged (single-group deployments).
        self.verify_tag: Optional[object] = None
        # LRU-bounded with an eviction counter: the keys are adversary-
        # chosen wire bytes, so a Byzantine flood of unique sig messages
        # churns the tail one entry at a time instead of wiping the honest
        # working set (and can never grow memory past the bound)
        self._sig_msg_memo: LruMemo[bytes, "ConsenterSigMsg"] = LruMemo(8192)
        # per-signer invalid-verdict attribution (ISSUE 18): every failed
        # consenter-sig verdict names WHO signed it instead of vanishing
        # into the aggregate failure count.  invalid_by_signer is the
        # always-on local export; the labeled counter and misbehavior
        # table are wired late (configure_fault_policy /
        # configure_misbehavior) by the Consensus facade.
        self.invalid_by_signer: dict[int, dict[str, int]] = {}
        self._invalid_vote_counter = None
        self.misbehavior = None
        if coalescer is not None and engine is not None \
                and coalescer.engine is not engine:
            raise ValueError("shared coalescer wraps a different engine")
        self.engine = (
            engine if engine is not None
            else coalescer.engine if coalescer is not None
            else HostVerifyEngine(scheme=self.scheme)
        )
        eng_scheme = getattr(self.engine, "scheme", self.scheme)
        if eng_scheme is not self.scheme:
            raise ValueError("engine scheme does not match provider scheme")
        # membership keys are static per configuration: register them with
        # the engine's comb-table path up front (no-op for other engines)
        if hasattr(self.engine, "prewarm_keys"):
            try:
                self.engine.prewarm_keys(self.keyring.public_keys.values())
            except ValueError as exc:
                # Import only on the error path: a raised CombRegistryFull
                # implies pallas_comb is already loaded, and the happy path
                # must not pull pallas machinery into configurations where
                # the comb path is disabled.
                from .pallas_comb import CombRegistryFull

                if not isinstance(exc, CombRegistryFull):
                    raise ValueError(
                        f"invalid key in keyring: {exc}") from exc
                # A long-lived shared engine can accumulate more distinct
                # keys than the comb registry holds (e.g. across many
                # reconfigs).  That only disables the comb fast path for
                # this provider's overflow keys — the generic kernel still
                # verifies them — so degrade loudly instead of failing
                # construction.
                import logging

                logging.getLogger("smartbft_tpu.crypto").warning(
                    "comb key registry full; provider %s falls back to the "
                    "generic verify kernel for unregistered keys: %s",
                    self.keyring.self_id, exc,
                )
        if coalescer is not None:
            self._coalescer = coalescer
            coalescer.configure(
                policy=fault_policy, fallback_engine=fallback_engine,
                explicit=fault_policy is not None,
            )
            return
        if coalesce_window is None:
            coalesce_window = getattr(
                self.engine, "preferred_coalesce_window", 0.002
            )
        # let one coalesced flush fill the engine's largest launch — a
        # smaller max_batch would split big quorum waves into multiple
        # launches and multiply the fixed per-launch overhead
        max_batch = getattr(self.engine, "pad_sizes", (2048,))[-1]
        default_policy = None
        if getattr(self.engine, "pad_sizes", None) is not None:
            # device-shaped engine: arm the fault stack by default — the
            # device is otherwise a single point of failure the reference's
            # per-goroutine host verify never had.  The default policy is
            # wired as NON-explicit so Configuration.verify_* knobs (via
            # Consensus._wire_verify_plane) still take effect.
            if fault_policy is None:
                default_policy = VerifyFaultPolicy()
            if fallback_engine is None:
                fallback_engine = HostVerifyEngine(scheme=self.scheme)
        self._coalescer = AsyncBatchCoalescer(
            self.engine, window=coalesce_window, max_batch=max_batch,
            policy=fault_policy, fallback_engine=fallback_engine,
        )
        if default_policy is not None:
            self._coalescer.configure(policy=default_policy)

    @property
    def coalescer(self) -> AsyncBatchCoalescer:
        return self._coalescer

    def configure_fault_policy(self, policy: Optional[VerifyFaultPolicy] = None,
                               metrics=None, fallback_engine=None) -> None:
        """Late verify-plane wiring (Consensus.start calls this with
        Configuration-derived values + the metrics bundle).  Fills only
        unset pieces, so explicit construction and shared-coalescer setups
        win.  A device-shaped engine without a fallback gets a host engine
        of the same scheme, realizing the degrade-to-CPU breaker path."""
        if (fallback_engine is None and policy is not None
                and self._coalescer.fallback_engine is None
                and getattr(self._coalescer.engine, "pad_sizes", None)
                is not None):
            fallback_engine = HostVerifyEngine(scheme=self.scheme)
        if metrics is not None and self._invalid_vote_counter is None:
            self._invalid_vote_counter = getattr(
                metrics, "count_invalid_votes", None)
        self._coalescer.configure(
            policy=policy, fallback_engine=fallback_engine, metrics=metrics
        )

    def configure_misbehavior(self, table) -> None:
        """Late misbehavior wiring (Consensus._wire_verify_plane): every
        per-signer invalid verdict this provider attributes also feeds the
        node's :class:`~smartbft_tpu.core.misbehavior.MisbehaviorTable`,
        which the Controller reads to shed shunned senders at intake."""
        self.misbehavior = table

    def _note_invalid(self, signer, cause: str) -> None:
        """Attribute one failed verdict to ``signer`` — local dict, the
        labeled ``consensus.tpu.count_invalid_votes`` counter, and the
        misbehavior table when wired.  Never raises: attribution must not
        turn a clean rejection into a verify-plane error."""
        try:
            by_cause = self.invalid_by_signer.setdefault(int(signer), {})
            by_cause[cause] = by_cause.get(cause, 0) + 1
            if self._invalid_vote_counter is not None:
                self._invalid_vote_counter.with_labels(str(signer)).add(1)
            if self.misbehavior is not None:
                self.misbehavior.note(int(signer), cause)
        except Exception:
            logging.getLogger("smartbft_tpu.crypto").warning(
                "invalid-vote attribution failed for signer %r", signer,
                exc_info=True,
            )

    def configure_flush_hold(self, hold: Optional[float],
                             explicit: bool = False) -> None:
        """Late occupancy-gating wiring: apply the
        ``Configuration.verify_flush_hold`` knob to the (possibly
        shared) coalescer.  Same precedence as the fault policy — an
        explicitly constructed hold wins over config-wired values."""
        self._coalescer.configure_hold(hold, explicit=explicit)

    def note_view_flip(self) -> None:
        """Controller seam (ISSUE 15): a view change installed a new
        view — run the (possibly shared) coalescer flip-warm so the new
        view's first quorum waves launch without coalescing latency."""
        self._coalescer.note_view_flip()

    def note_view_depose(self) -> None:
        """View seam (ISSUE 15): the view is aborting for a view change —
        flush its in-flight waves eagerly (see the coalescer's
        note_view_depose)."""
        self._coalescer.note_view_depose()

    def _quorum_threshold(self) -> int:
        """ceil((n+f+1)/2) over this keyring's membership — the quorum
        the 2D engine's psum'd vote counts decide against (the same
        expression every View uses; verdicts do NOT depend on it)."""
        n = len(self.keyring.public_keys)
        f = (n - 1) // 3
        return (n + f + 2) // 2

    def configure_verify_mesh(self, devices: int, metrics=None,
                              topology: str = "1d") -> None:
        """Graduate the coalescer's engine onto an N-device mesh — the
        ``Configuration.verify_mesh_devices`` knob, wired by
        ``Consensus._wire_verify_plane`` at start and on every reconfig.

        Idempotent and shared-coalescer-safe: the first provider wired
        swaps the engine in; colocated providers (sharded mode — S groups,
        ONE coalescer) see a mesh of the requested width already installed
        (``devices`` attribute, delegated through fault-injection wrappers)
        and no-op.  The PR 3 fault contract then holds per MESH launch for
        free: the deadline/retry/breaker machinery wraps ``engine.verify``,
        so expiry abandons the whole mesh launch, retries re-dispatch it,
        the breaker degrades every shard to the host fallback together and
        the canary recovers them back onto the mesh.

        ``topology`` selects the mesh shape (the
        ``Configuration.verify_mesh_topology`` knob): ``"1d"`` (default)
        is the batch-axis :class:`~smartbft_tpu.parallel.MeshVerifyEngine`;
        ``"2d"`` graduates onto the seq×vote
        :class:`~smartbft_tpu.parallel.QuorumMeshVerifyEngine`, whose
        per-sequence quorum counts ``psum`` across the 'vote' mesh axis —
        quorum counting rides the collective instead of the host — while
        per-item verdicts stay bit-identical to the 1D engine.

        **Degraded mode**: when the mesh is unbuildable (fewer visible
        devices than configured) the current single-device engine stays,
        LOUDLY, with a counted downgrade (``coalescer.mesh_downgrades`` +
        ``consensus.tpu.count_mesh_downgrades``) — a mis-provisioned host
        serves at reduced width instead of dying."""
        if devices <= 0:
            return
        co = self._coalescer
        co.mesh_configured = int(devices)
        # prefer the coalescer's own metrics bundle (the shared one every
        # provider feeds) over a caller-supplied per-node bundle; fill the
        # unset slot like configure_fault_policy so later wirings and the
        # downgrade counter read the same bundle
        if metrics is not None and co.metrics is None:
            co.configure(metrics=metrics)
        metrics = co.metrics if co.metrics is not None else metrics
        current = co.engine
        if int(getattr(current, "devices", 0)) == int(devices) \
                and getattr(current, "topology", "1d") == topology:
            self.engine = current
            return  # already this mesh (possibly FaultyEngine-wrapped)
        from ..parallel.engine import (
            MeshUnavailable,
            MeshVerifyEngine,
            QuorumMeshVerifyEngine,
        )

        try:
            if topology == "2d":
                engine = QuorumMeshVerifyEngine(
                    devices=int(devices), scheme=self.scheme,
                    quorum=self._quorum_threshold(), metrics=metrics,
                )
            else:
                # the current engine donates its pad ladder ONLY when it
                # actually carries a batch ladder: a 2D engine's
                # pad_sizes is the single seq_tile*vote_tile rung, and
                # inheriting it on a 2d->1d reconfig would silently cap
                # the rebuilt 1D mesh far below the derived
                # MESH_PER_DEVICE_LANES ladder
                donor = None if getattr(current, "topology", "1d") == "2d" \
                    else getattr(current, "pad_sizes", None)
                engine = MeshVerifyEngine(
                    devices=int(devices), scheme=self.scheme,
                    pad_sizes=donor, metrics=metrics,
                )
                # the static keys the replaced engine was told stay told:
                # the mesh serves a ring as it does (ring -> comb, the
                # rest -> the sharded XLA kernel)
                ring = getattr(getattr(current, "inner", None) or current,
                               "_ring", None)
                if ring:
                    engine.pin_ring(ring)
        except MeshUnavailable as exc:
            co.mesh_downgrades += 1
            if metrics is not None and hasattr(metrics, "count_mesh_downgrades"):
                metrics.count_mesh_downgrades.add(1)
            logging.getLogger("smartbft_tpu.crypto").warning(
                "verify mesh UNBUILDABLE (%s); DOWNGRADED to the "
                "single-device %s (downgrade %d counted)",
                exc, type(current).__name__, co.mesh_downgrades,
            )
            return
        inner = getattr(current, "inner", None)
        if inner is not None:
            # a fault-injection wrapper (testing.engine_faults.FaultyEngine)
            # around a single-device engine: graduate INSIDE it — swapping
            # the wrapper out would silently disconnect chaos fault
            # injection from the live plane
            current.inner = engine
            current.scheme = engine.scheme
            current.pad_sizes = engine.pad_sizes
            current.devices = engine.devices
            current.topology = engine.topology
            engine = current
        else:
            co.engine = engine
        # one coalesced flush should be able to fill the mesh's largest
        # launch — a smaller cap would split waves and waste the new width
        co.max_batch = max(co.max_batch, engine.pad_sizes[-1])
        if co.fallback_engine is None:
            co.fallback_engine = HostVerifyEngine(scheme=self.scheme)
        if metrics is not None and hasattr(metrics, "mesh_devices"):
            metrics.mesh_devices.set(float(engine.devices))
        self.engine = engine

    # -- Signer -------------------------------------------------------------

    def sign(self, data: bytes) -> bytes:
        return self.scheme.sign_raw(self.keyring.private_key, data)

    def sign_proposal(self, proposal: Proposal, auxiliary_input: bytes) -> Signature:
        msg = encode(ConsenterSigMsg(
            proposal_digest=proposal_digest(proposal), aux=auxiliary_input
        ))
        return Signature(signer=self.keyring.self_id, value=self.sign(msg), msg=msg)

    # -- Verifier (crypto methods) -------------------------------------------

    def _item(self, signature: Signature):
        pub = self.keyring.public_keys.get(signature.signer)
        if pub is None:
            raise ValueError(f"unknown signer {signature.signer}")
        return self.scheme.make_item(signature.msg, signature.value, pub)

    def _check_binding(self, signature: Signature, proposal: Proposal,
                       digest: Optional[str] = None) -> bytes:
        """Digest binding check; returns aux.  Raises on mismatch.

        ``digest``: the proposal's digest if the caller already computed it
        — hashing a batch-sized proposal costs ~50 us, and quorum
        validation checks one proposal against dozens of signatures.  The
        sig-msg decode is memoized per provider (``_sig_msg_memo``, one
        LRU of 8192 entries for each replica's own provider, nothing
        shared between the replicas of a process): a replica re-checks
        the same wire bytes on every path that validates a quorum (~42k
        decodes per n=64 bench run before the memo)."""
        decoded = self._sig_msg_memo.get_or(
            signature.msg, lambda: decode(ConsenterSigMsg, signature.msg)
        )
        if digest is None:
            digest = proposal_digest(proposal)
        if decoded.proposal_digest != digest:
            raise ValueError(
                f"signature of {signature.signer} binds digest "
                f"{decoded.proposal_digest[:12]}.. not the proposal's"
            )
        return decoded.aux

    def verify_consenter_sig(self, signature: Signature, proposal: Proposal) -> bytes:
        try:
            aux = self._check_binding(signature, proposal)
        except Exception:
            self._note_invalid(signature.signer, "binding_mismatch")
            raise
        try:
            item = self._item(signature)
        except Exception:
            self._note_invalid(signature.signer, "unknown_signer")
            raise
        ok = self.engine.verify([item])[0]
        if not ok:
            self._note_invalid(signature.signer, "invalid_sig")
            raise ValueError(f"invalid consenter signature from {signature.signer}")
        return aux

    # batch verification = collect/bind (shared below) + a scheme-overridable
    # mask step (_verify_items); BLS swaps in its aggregate fast path there

    def _verify_items(self, items) -> list[bool]:
        return self.engine.verify(items)

    async def _verify_items_async(self, items) -> list[bool]:
        return await self.verify_items_async(items)

    async def verify_items_async(self, items) -> list[bool]:
        """The request path into the shared coalescer: scheme verify items
        by ANY key (a channel's client envelopes,
        :class:`~smartbft_tpu.crypto.envelope.EnvelopeVerifier`), one
        submission, verdicts in order.  Votes keep
        :meth:`verify_consenter_sigs_batch_async`."""
        return await self._coalescer.submit(items, tag=self.verify_tag)

    def _collect(self, signatures: Sequence[Signature], proposal: Proposal):
        auxes: list[Optional[bytes]] = []
        items, idxs = [], []
        digest = proposal_digest(proposal)  # once per batch, not per sig
        for i, sig in enumerate(signatures):
            # the two pre-engine rejections attribute separately: a digest-
            # binding forgery is a different lie than an out-of-membership
            # signer claim, and both are cheaper than the engine verdict
            # they used to be indistinguishable from
            try:
                aux = self._check_binding(sig, proposal, digest)
            except Exception:
                auxes.append(None)
                self._note_invalid(sig.signer, "binding_mismatch")
                continue
            try:
                items.append(self._item(sig))
            except Exception:
                auxes.append(None)
                self._note_invalid(sig.signer, "unknown_signer")
                continue
            idxs.append(i)
            auxes.append(aux)
        return auxes, items, idxs

    def _apply_mask(self, auxes, idxs, mask, signatures=None):
        for pos, i in enumerate(idxs):
            if not mask[pos]:
                auxes[i] = None
                if signatures is not None:
                    self._note_invalid(signatures[i].signer, "invalid_sig")
        return auxes

    def verify_consenter_sigs_batch(
        self, signatures: Sequence[Signature], proposal: Proposal
    ) -> list[Optional[bytes]]:
        auxes, items, idxs = self._collect(signatures, proposal)
        return self._apply_mask(auxes, idxs, self._verify_items(items),
                                signatures)

    async def verify_consenter_sigs_batch_async(
        self, signatures: Sequence[Signature], proposal: Proposal
    ) -> list[Optional[bytes]]:
        """Async path the View prefers: coalesces with concurrent callers."""
        # busy span: the loop's share of a quorum check (bind + pack items)
        rec = self._coalescer.recorder
        span = rec.begin("verify.collect") if rec.enabled else None
        try:
            auxes, items, idxs = self._collect(signatures, proposal)
        finally:
            if span is not None:
                rec.end(span)
        return self._apply_mask(auxes, idxs,
                                await self._verify_items_async(items),
                                signatures)

    def verify_signature(self, signature: Signature) -> None:
        try:
            item = self._item(signature)
        except Exception as exc:
            cause = ("unknown_signer"
                     if signature.signer not in self.keyring.public_keys
                     else "invalid_sig")
            self._note_invalid(signature.signer, cause)
            raise ValueError(f"malformed signature from {signature.signer}: {exc}")
        try:
            ok = self.engine.verify([item])[0]
        except Exception as exc:
            raise ValueError(f"malformed signature from {signature.signer}: {exc}")
        if not ok:
            self._note_invalid(signature.signer, "invalid_sig")
            raise ValueError(f"invalid signature from {signature.signer}")

    def auxiliary_data(self, msg: bytes) -> bytes:
        try:
            return decode(ConsenterSigMsg, msg).aux
        except Exception:
            return b""


class P256CryptoProvider(CryptoProvider):
    """ECDSA P-256 provider (the default scheme)."""

    scheme = p256


class Ed25519CryptoProvider(CryptoProvider):
    """Ed25519 provider — the alt-curve variant of BASELINE.md configs[3]."""

    scheme = ed25519


class BlsCryptoProvider(CryptoProvider):
    """BLS12-381 aggregate provider — BASELINE.md configs[4]:
    one pairing equation per quorum.

    Same-message aggregation requires every consenter to sign identical
    bytes, so this provider signs the PROPOSAL DIGEST ONLY; the per-signer
    auxiliary data (PreparesFrom witness lists, view.go:472-481) still
    travels in ``Signature.msg`` but is NOT covered by the signature.
    Deployments that rely on authenticated aux for blacklist redemption
    should use the P-256/Ed25519 providers (or treat redemption as
    advisory) — the tradeoff is the price of quorum collapse.

    Verification strategy (the FastAggregateVerify shape of the IETF BLS
    draft): aggregate the whole batch into ONE kernel lane (sum of G1 sigs,
    sum of G2 pubkeys); only if that single pairing check fails fall back to
    per-signature lanes to attribute the bad vote.  Two consequences:

    * **Rogue keys.** Same-message aggregation is sound only when every
      registered public key has a verified proof of possession (otherwise
      pk_b = b*g2 - pk_a lets b fabricate a "quorum" containing a vote a
      never cast).  Pass ``pops`` (signer id -> ``bls12381.pop_prove``
      output) to enforce this at construction; deployments that omit it
      MUST verify possession during key registration instead.
    * **Set-level attestation.** When the aggregate check passes, it
      attests that the quorum *as a set* signed the digest; the individual
      ``Signature.value`` byte strings are not separately attested (a relay
      could offset two of them by equal-and-opposite G1 points without
      changing the sum).  All quorum-cert validation in this framework goes
      through this batch path, so replicas agree; code that needs a single
      signature attributable on its own must call
      :meth:`verify_consenter_sig`, which never aggregates.
    """

    scheme = bls12381

    def __init__(self, keyring: Keyring, engine=None,
                 coalesce_window: Optional[float] = None,
                 coalescer=None, pops: Optional[dict[int, bytes]] = None):
        super().__init__(keyring, engine, coalesce_window, coalescer)
        if pops is not None:
            for nid, pub in keyring.public_keys.items():
                pop = pops.get(nid)
                if pop is None or not bls12381.pop_verify(pub, pop):
                    raise ValueError(
                        f"missing/invalid proof of possession for node {nid}"
                    )

    def _signed_bytes(self, msg: bytes) -> bytes:
        """The digest-only bytes actually covered by the BLS signature."""
        decoded = decode(ConsenterSigMsg, msg)
        return encode(ConsenterSigMsg(proposal_digest=decoded.proposal_digest))

    def sign(self, data: bytes) -> bytes:
        try:
            data = self._signed_bytes(data)
        except Exception:
            pass  # non-consenter payloads (e.g. ViewData) sign as-is
        return self.scheme.sign_raw(self.keyring.private_key, data)

    def _item(self, signature: Signature):
        pub = self.keyring.public_keys.get(signature.signer)
        if pub is None:
            raise ValueError(f"unknown signer {signature.signer}")
        try:
            msg = self._signed_bytes(signature.msg)
        except Exception:
            msg = signature.msg
        return self.scheme.make_item(msg, signature.value, pub)

    def _aggregate_lane(self, items):
        """One lane for the whole batch, or None if no collapse is possible."""
        if len(items) <= 1:
            return None
        try:
            return self.scheme.aggregate_items(items)
        except ValueError:
            return None  # mixed messages / degenerate sums

    def _quorum_minus_one(self) -> int:
        n = len(self.keyring.public_keys)
        f = (n - 1) // 3
        return max(2, (n + f + 1 + 1) // 2 - 1)  # ceil((n+f+1)/2) - 1

    def _canonical_split(self, signatures, items, idxs):
        """Canonicalized aggregation: the CANONICAL quorum subset — the
        lowest quorum-1 signer ids present — aggregates into one lane;
        leftovers get per-item lanes.

        Cross-replica dedupe (PERF.md round-5 row [4]'s named lever):
        without canonicalization every replica aggregates ITS OWN collected
        subset, so the aggregated items of two replicas checking the same
        decision never match and the shared coalescer's dedupe pass cannot
        collapse them.  Sorting by signer id and capping at quorum-1 makes
        replicas that hold the same votes produce BYTE-IDENTICAL aggregate
        items (aggregation is a commutative point sum over the canonical
        codec's byte encodings), so an n-replica wave dedupes to one lane.

        Returns (lane, chosen_positions, rest_positions) or None when no
        aggregation applies (<=1 item / mixed messages)."""
        if len(items) <= 1:
            return None
        order = sorted(range(len(items)),
                       key=lambda p: signatures[idxs[p]].signer)
        chosen = order[: self._quorum_minus_one()]
        if len(chosen) <= 1:
            return None
        rest = order[len(chosen):]
        try:
            lane = self.scheme.aggregate_items([items[p] for p in chosen])
        except ValueError:
            return None  # mixed messages / degenerate sums
        return lane, chosen, rest

    @staticmethod
    def _merge_split_verdicts(split, results, chosen_results, n_items) -> list[bool]:
        """Fan the [lane, rest...] result vector (plus, on lane failure,
        the per-item re-attribution of the chosen subset) onto positions.
        Rest verdicts are REUSED either way — a failed canonical lane only
        costs re-verifying the chosen items, never the whole batch."""
        _, chosen, rest = split
        mask = [False] * n_items
        for j, p in enumerate(rest):
            mask[p] = results[1 + j]
        if results[0]:
            for p in chosen:
                mask[p] = True
        else:
            for j, p in enumerate(chosen):
                mask[p] = chosen_results[j]
        return mask

    def _verify_items(self, items) -> list[bool]:
        lane = self._aggregate_lane(items)
        if lane is not None and self.engine.verify([lane])[0]:
            return [True] * len(items)
        return self.engine.verify(items)

    async def _verify_items_async(self, items) -> list[bool]:
        """Aggregate path with coalescing: the single aggregated lane joins
        other in-flight quorums in one shared kernel launch."""
        lane = self._aggregate_lane(items)
        if lane is not None and (
            await self._coalescer.submit([lane], tag=self.verify_tag)
        )[0]:
            return [True] * len(items)
        return await self._coalescer.submit(items, tag=self.verify_tag)

    def verify_consenter_sigs_batch(
        self, signatures: Sequence[Signature], proposal: Proposal
    ) -> list:
        auxes, items, idxs = self._collect(signatures, proposal)
        split = self._canonical_split(signatures, items, idxs)
        if split is None:
            return self._apply_mask(auxes, idxs, self._verify_items(items),
                                    signatures)
        lane, chosen, rest = split
        results = self.engine.verify([lane] + [items[p] for p in rest])
        chosen_results = None
        if not results[0]:
            # canonical lane failed: attribute only the chosen subset
            chosen_results = self.engine.verify([items[p] for p in chosen])
        mask = self._merge_split_verdicts(split, results, chosen_results, len(items))
        return self._apply_mask(auxes, idxs, mask, signatures)

    async def verify_consenter_sigs_batch_async(
        self, signatures: Sequence[Signature], proposal: Proposal
    ) -> list:
        auxes, items, idxs = self._collect(signatures, proposal)
        split = self._canonical_split(signatures, items, idxs)
        if split is None:
            return self._apply_mask(auxes, idxs,
                                    await self._verify_items_async(items),
                                    signatures)
        lane, chosen, rest = split
        results = await self._coalescer.submit(
            [lane] + [items[p] for p in rest], tag=self.verify_tag
        )
        chosen_results = None
        if not results[0]:
            chosen_results = await self._coalescer.submit(
                [items[p] for p in chosen], tag=self.verify_tag
            )
        mask = self._merge_split_verdicts(split, results, chosen_results, len(items))
        return self._apply_mask(auxes, idxs, mask, signatures)
