"""View-change sub-phase decomposition: where do the seconds go?

PERF round 12 crowned the forced view change the worst failure mode
(p99 21x healthy, the only phase that sheds) — but nothing could say
WHERE inside the complain → depose → ViewData → new-view pipeline the
time went.  :class:`ViewChangePhaseTracker` is that instrument: the
ViewChanger and Controller mark the pipeline's transition points on one
injectable clock, and every completed view change yields a per-phase
breakdown whose phase durations SUM to its end-to-end duration by
construction (consecutive deltas on one clock), so the decomposition
can never silently disagree with the total it explains.

Phase vocabulary (each phase is the interval ENDING at its mark):

==================  =====================================================
``complain``        complain armed (this node started/joined a view
                    change) → complaint quorum reached (node commits to
                    the next view)
``depose``          quorum → ViewData prepared + sent to the new leader
                    (includes aborting the current view)
``viewdata_collect``  (new leader only) ViewData sent → quorum of
                    ViewData collected and the in-flight check passed
``newview``         ViewData sent/collected → NewView validated and the
                    NewViewRecord persisted (includes committing agreed
                    in-flight rungs)
``first_commit``    new view installed → first decision delivered in it
==================  =====================================================

Memory is bounded: one in-flight mark set, a ``keep``-deep deque of raw
per-VC records (the bench block's input), and fixed-bucket histograms.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Optional, Sequence

from ..metrics import LogScaleHistogram
from .recorder import pct as _pct, standby

__all__ = ["ViewChangePhaseTracker", "assemble_viewchange_block"]

#: mark -> the phase name of the interval that ENDS at this mark, in
#: pipeline order (missing marks skip; the next present mark's phase
#: absorbs the interval, keeping sum == total)
_MARK_PHASE = (
    ("joined", "complain"),
    ("viewdata_sent", "depose"),
    ("viewdata_quorum", "viewdata_collect"),
    ("newview", "newview"),
)

PHASES = tuple(p for _, p in _MARK_PHASE) + ("first_commit",)


class ViewChangePhaseTracker:
    """Per-node view-change sub-phase clock.  One instance per Consensus
    (it outlives reconfig-rebuilt ViewChangers), fed by the ViewChanger's
    transition points and closed by the Controller's first delivery in
    the new view."""

    def __init__(self, *, clock=None, node: str = "", recorder=None,
                 metrics=None, keep: int = 64):
        self._clock = clock if clock is not None else time.monotonic
        self.node = node
        self.recorder = standby(recorder)
        #: optional ViewChangeMetrics bundle — the time-in-view-change
        #: gauge and round counter feed it so Prometheus/statsd see VC
        #: health without the trace enabled
        self.metrics = metrics
        self.open = False
        self._view = -1
        self._marks: dict[str, float] = {}
        self.rounds = 0
        self.abandoned = 0
        self.completed_total = 0
        #: raw per-VC records (bounded) — the assemble block's input
        self._records: deque = deque(maxlen=max(int(keep), 1))
        self.spans = {p: LogScaleHistogram() for p in PHASES}
        self.total_hist = LogScaleHistogram()
        #: heartbeat-timeout arm-to-fire samples (ms, bounded) — the
        #: DETECTION latency round 15 blamed for ~99% of the VC cliff,
        #: now a first-class column of the viewchange bench block
        self._detections: deque = deque(maxlen=max(int(keep), 1))
        self.detections_total = 0
        #: the latest EFFECTIVE complain-timer derivation (ISSUE 15):
        #: {timeout_s, rtt_s, commit_interval_s, backoff_round} — one
        #: dict, overwritten in place by the heartbeat monitor so the
        #: bench block publishes what the timer actually was
        self.effective_timer: Optional[dict] = None
        #: hot-standby ViewData accounting (ISSUE 15): prebuilds the
        #: next-leader tick produced, and cache hits at ViewData-send
        #: time (a hit = the one-round-trip failover path was taken)
        self.standby_prebuilds = 0
        self.standby_hits = 0

    # -- marks (ViewChanger) ----------------------------------------------

    def armed(self, next_view: int) -> None:
        """This node started (or joined) a view change toward
        ``next_view``.  A re-arm toward a HIGHER view while one is open
        is a new round (timeout escalation): the stale round is counted
        abandoned, its partial marks discarded."""
        if self.open:
            if next_view <= self._view:
                return  # duplicate arm of the same round
            self._abandon("re-armed")
        self.open = True
        self._view = next_view
        self._marks = {"armed": self._clock()}
        self.rounds += 1
        if self.metrics is not None:
            self.metrics.count_view_change_rounds.add(1)
        rec = self.recorder
        if rec.enabled:
            rec.record("vc.armed", node=self.node, view=next_view)

    def detection(self, seconds: float) -> None:
        """A heartbeat/complain timer FIRED after ``seconds`` of armed
        silence (HeartbeatMonitor hook).  No tracing required: the sample
        feeds the viewchange metrics bundle (gauge + counter) and the
        bounded pool the bench block summarizes."""
        ms = max(seconds, 0.0) * 1e3
        self._detections.append(ms)
        self.detections_total += 1
        if self.metrics is not None:
            self.metrics.heartbeat_detection_seconds.set(max(seconds, 0.0))
            self.metrics.count_heartbeat_timeouts.add(1)
        rec = self.recorder
        if rec.enabled:
            rec.record("vc.detected", node=self.node, dur=max(seconds, 0.0))

    def note_effective_timer(self, timeout_s: float, rtt_s: float,
                             commit_interval_s: float,
                             backoff_round: int) -> None:
        """The heartbeat monitor's current effective complain timer and
        its inputs (ISSUE 15 satellite) — overwritten in place, O(1)."""
        self.effective_timer = {
            "timeout_s": round(timeout_s, 6),
            "rtt_s": round(rtt_s, 6),
            "commit_interval_s": round(commit_interval_s, 6),
            "backoff_round": backoff_round,
        }

    def note_standby(self, prebuilt: bool = False, hit: bool = False) -> None:
        """Hot-standby ViewData accounting (ISSUE 15)."""
        if prebuilt:
            self.standby_prebuilds += 1
        if hit:
            self.standby_hits += 1

    def _mark(self, name: str, kind: str, view: int) -> None:
        if not self.open or view < self._view or name in self._marks:
            return
        self._marks[name] = self._clock()
        rec = self.recorder
        if rec.enabled:
            rec.record(kind, node=self.node, view=self._view)

    def joined(self, view: int) -> None:
        """Complaint quorum reached; the node committed to the next view."""
        self._mark("joined", "vc.quorum", view)

    def viewdata_sent(self, view: int) -> None:
        self._mark("viewdata_sent", "vc.viewdata_sent", view)

    def viewdata_quorum(self, view: int) -> None:
        """(New leader) quorum of ViewData validated; NewView going out."""
        self._mark("viewdata_quorum", "vc.viewdata_quorum", view)

    def newview_done(self, view: int) -> None:
        self._mark("newview", "vc.newview", view)

    # -- closure (Controller) ---------------------------------------------

    def decision(self, view: int, backlog: int = -1) -> None:
        """A decision delivered; the first one at/after the VC's view with
        the NewView processed closes the open round as COMPLETED.
        ``backlog`` (when >= 0) is the caller's request-pool depth at the
        flip — the stalled work the new view must drain, the other half
        of the round-15 cliff."""
        if not self.open or "newview" not in self._marks \
                or view < self._view:
            return
        now = self._clock()
        marks = self._marks
        t0 = marks["armed"]
        phases: dict[str, float] = {}
        prev = t0
        for mark, phase in _MARK_PHASE:
            t = marks.get(mark)
            if t is None:
                continue
            phases[phase] = max(t - prev, 0.0)
            prev = t
        phases["first_commit"] = max(now - prev, 0.0)
        total = max(now - t0, 0.0)
        for phase, dt in phases.items():
            self.spans[phase].observe(dt)
        self.total_hist.observe(total)
        self.completed_total += 1
        record = {
            "view": self._view,
            "node": self.node,
            "total_ms": round(total * 1e3, 3),
            "phases": {p: round(dt * 1e3, 3) for p, dt in phases.items()},
        }
        if backlog >= 0:
            record["backlog_at_flip"] = backlog
        self._records.append(record)
        if self.metrics is not None:
            self.metrics.time_in_view_change.set(total)
            if backlog >= 0:
                self.metrics.backlog_at_view_flip.set(backlog)
        rec = self.recorder
        if rec.enabled:
            rec.record("vc.complete", node=self.node, view=self._view,
                       dur=total,
                       extra={p: round(dt * 1e3, 3)
                              for p, dt in phases.items()})
        self.open = False
        self._marks = {}

    def abandoned_by_sync(self, view: int) -> None:
        """A sync/inform installed the new view around the VC protocol —
        the open round never completed through the pipeline."""
        if self.open and view >= self._view:
            self._abandon("sync")

    def timeout_escalated(self) -> None:
        """The view-change timeout fired: the ViewChanger is forcing a
        sync and RESTARTING the round (viewchanger.go:254-270 backoff
        escalation).  The open round is recycled — count it abandoned so
        its stale marks cannot keep reading as a still-in-progress view
        change (a restarted replica that restored a moot VC round would
        otherwise report viewchange.active_seconds growing forever)."""
        if self.open:
            self._abandon("timeout")

    def _abandon(self, reason: str) -> None:
        self.abandoned += 1
        rec = self.recorder
        if rec.enabled:
            rec.record("vc.abandoned", node=self.node, view=self._view,
                       extra={"reason": reason})
        self.open = False
        self._marks = {}

    def note_tick(self) -> None:
        """Tick hook: keep the time-in-view-change gauge live while a
        round is open (it freezes at the total on completion)."""
        if self.open and self.metrics is not None:
            self.metrics.time_in_view_change.set(
                max(self._clock() - self._marks["armed"], 0.0)
            )

    # -- reading -----------------------------------------------------------

    def records(self) -> list[dict]:
        return list(self._records)

    def snapshot(self) -> dict:
        return {
            "completed": self.completed_total,
            "rounds": self.rounds,
            "abandoned": self.abandoned,
            "open": self.open,
            "phases": {p: h.snapshot() for p, h in self.spans.items()},
            "total": self.total_hist.snapshot(),
            "last": self._records[-1] if self._records else None,
        }


def _timer_block(trackers: Sequence["ViewChangePhaseTracker"]) -> dict:
    """Fold the per-node effective-timer derivations into one summary."""
    samples = [t.effective_timer for t in trackers
               if getattr(t, "effective_timer", None)]
    if not samples:
        return {"derived": False}
    timeouts = [s["timeout_s"] for s in samples]
    return {
        "derived": True,
        "nodes": len(samples),
        "timeout_s_min": min(timeouts),
        "timeout_s_max": max(timeouts),
        "rtt_s_max": max(s["rtt_s"] for s in samples),
        "commit_interval_s_max": max(s["commit_interval_s"]
                                     for s in samples),
        "backoff_round_max": max(s["backoff_round"] for s in samples),
    }


def assemble_viewchange_block(trackers: Sequence["ViewChangePhaseTracker"]
                              ) -> dict:
    """Fold N per-node trackers into the ONE ``viewchange`` block a bench
    row carries (pure function, PR 8 idiom).  Percentiles are EXACT over
    the pooled raw per-VC records (VCs are rare, the records are bounded
    deques), so the published decomposition is the measured distribution,
    not a merge of approximations.  ``sums_consistent`` pins the
    instrument's core promise: every record's phase durations sum to its
    end-to-end total (worst residual reported in ms)."""
    recs = [r for t in trackers for r in t.records()]
    totals = sorted(r["total_ms"] for r in recs)
    per_phase: dict[str, list] = {p: [] for p in PHASES}
    worst_residual = 0.0
    for r in recs:
        for p, ms in r["phases"].items():
            per_phase.setdefault(p, []).append(ms)
        worst_residual = max(
            worst_residual,
            abs(sum(r["phases"].values()) - r["total_ms"]),
        )
    phases = {}
    sum_total = sum(totals)
    mean_total = (sum_total / len(totals)) if totals else 0.0
    for p, vals in per_phase.items():
        vals.sort()
        mean = (sum(vals) / len(vals)) if vals else 0.0
        phases[p] = {
            "count": len(vals),
            "p50_ms": round(_pct(vals, 0.50), 3),
            "p95_ms": round(_pct(vals, 0.95), 3),
            "p99_ms": round(_pct(vals, 0.99), 3),
            "max_ms": round(vals[-1], 3) if vals else 0.0,
            "mean_ms": round(mean, 3),
            # the decomposition column PERF round 15 publishes: the
            # fraction of ALL measured view-change time spent in this
            # phase (shares sum to ~1 across phases, modulo residual)
            "share": round(sum(vals) / sum_total, 3) if sum_total else 0.0,
        }
    dominant = max(
        (p for p in phases if phases[p]["count"]),
        key=lambda p: phases[p]["share"], default=None,
    )
    detections = sorted(d for t in trackers
                        for d in getattr(t, "_detections", ()))
    backlogs = sorted(r["backlog_at_flip"] for r in recs
                      if "backlog_at_flip" in r)
    return {
        "count": len(recs),
        "rounds": sum(t.rounds for t in trackers),
        "abandoned": sum(t.abandoned for t in trackers),
        # ROADMAP item 1 gauges: complain-timer arm-to-fire time (the
        # detection latency that precedes every armed round) and the
        # per-replica pool backlog at the view flip (the stalled work the
        # new view drains) — both measured, no tracing required
        "detection": {
            "count": sum(getattr(t, "detections_total", 0)
                         for t in trackers),
            "p50_ms": round(_pct(detections, 0.50), 3),
            "p95_ms": round(_pct(detections, 0.95), 3),
            "p99_ms": round(_pct(detections, 0.99), 3),
            "max_ms": round(detections[-1], 3) if detections else 0.0,
        },
        "backlog_at_flip": {
            "count": len(backlogs),
            "p50": _pct(backlogs, 0.50),
            "max": backlogs[-1] if backlogs else 0,
        },
        # ISSUE 15: the effective (derived) complain timer across the
        # pooled trackers — min/max of the last per-node derivations plus
        # the worst backoff round — and the hot-standby ViewData cache
        # accounting (hits = view changes that took the one-round-trip
        # prebuilt path)
        "timer": _timer_block(trackers),
        "standby": {
            "prebuilds": sum(getattr(t, "standby_prebuilds", 0)
                             for t in trackers),
            "hits": sum(getattr(t, "standby_hits", 0) for t in trackers),
        },
        "end_to_end": {
            "count": len(totals),
            "p50_ms": round(_pct(totals, 0.50), 3),
            "p95_ms": round(_pct(totals, 0.95), 3),
            "p99_ms": round(_pct(totals, 0.99), 3),
            "max_ms": round(totals[-1], 3) if totals else 0.0,
            "mean_ms": round(mean_total, 3),
        },
        "phases": phases,
        "dominant_phase": dominant,
        "sums_consistent": worst_residual <= 0.005,
        "worst_residual_ms": round(worst_residual, 4),
    }
