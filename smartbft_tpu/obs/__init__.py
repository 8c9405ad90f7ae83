"""Flight recorder: request-scoped protocol tracing + VC decomposition.

The observability plane ISSUE 12 builds: a bounded-memory
:class:`~smartbft_tpu.obs.recorder.TraceRecorder` of structured span
events (injectable clock; every component holds a real, disabled one,
switched on with the profiler session or by hand, so the hot path pays
one attribute check when tracing is off), the account of the last
interval the profiler was on (:func:`last_summary`), a :class:`~smartbft_tpu.obs.vcphases.ViewChangePhaseTracker` that
decomposes the complain → depose → ViewData → new-view → first-commit
pipeline into measured sub-phases, and the pure ``assemble_*`` helpers
that fold either into JSON-able blocks.  ``python -m
smartbft_tpu.obs.report`` renders a recorder dump as a text timeline +
per-span-type percentile summary.
"""

from .account import assemble_account, assemble_timeline  # noqa: F401
from .critpath import (  # noqa: F401
    DECISION_SEGMENTS,
    SEGMENTS,
    assemble_critical_path_block,
    decision_rows,
)
from .health import (  # noqa: F401
    HealthMonitor,
    aggregate_cluster_verdict,
)
from .recorder import (  # noqa: F401
    PROCESS,
    SpanEvent,
    TraceRecorder,
    assemble_trace_block,
    close_for_await,
    last_summary,
    poll_profiler,
    standby,
)
from .slo import (  # noqa: F401
    SLOEvaluator,
    SLORule,
    SLOSpec,
    default_slo_spec,
)
from .vcphases import (  # noqa: F401
    ViewChangePhaseTracker,
    assemble_viewchange_block,
)

__all__ = [
    "DECISION_SEGMENTS",
    "PROCESS",
    "SEGMENTS",
    "SpanEvent",
    "TraceRecorder",
    "assemble_account",
    "assemble_critical_path_block",
    "assemble_timeline",
    "assemble_trace_block",
    "close_for_await",
    "decision_rows",
    "last_summary",
    "poll_profiler",
    "standby",
    "ViewChangePhaseTracker",
    "assemble_viewchange_block",
    "HealthMonitor",
    "aggregate_cluster_verdict",
    "SLOEvaluator",
    "SLORule",
    "SLOSpec",
    "default_slo_spec",
]
