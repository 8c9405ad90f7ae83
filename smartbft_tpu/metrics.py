"""Metrics SPI + default providers + the consensus metric bundles.

Re-design of /root/reference/pkg/metrics/provider.go:11-169 (Fabric-style
Provider/Counter/Gauge/Histogram with label support), the no-op provider
(pkg/metrics/disabled/provider.go), and the five metric bundles of
/root/reference/pkg/api/metrics.go:106-548 — plus the TPU-plane additions
required by BASELINE.json: signature-batch occupancy ("batch-fill %") and
verify-latency histograms.

The in-memory provider doubles as the benchmark introspection surface.
"""

from __future__ import annotations

import abc
import contextvars
import math
import threading
import weakref
from dataclasses import dataclass, field
from typing import Optional, Sequence


@dataclass(frozen=True)
class MetricOpts:
    namespace: str = ""
    subsystem: str = ""
    name: str = ""
    help: str = ""
    label_names: tuple[str, ...] = ()
    buckets: tuple[float, ...] = ()
    #: statsd naming format with %{#namespace}/%{#subsystem}/%{#name} and
    #: %{label} placeholders (pkg/metrics/namer.go); empty = dotted default
    statsd_format: str = ""

    @property
    def full_name(self) -> str:
        return ".".join(p for p in (self.namespace, self.subsystem, self.name) if p)


class Counter(abc.ABC):
    @abc.abstractmethod
    def add(self, delta: float) -> None: ...

    @abc.abstractmethod
    def with_labels(self, *label_values: str) -> "Counter": ...


class Gauge(abc.ABC):
    @abc.abstractmethod
    def set(self, value: float) -> None: ...

    @abc.abstractmethod
    def add(self, delta: float) -> None: ...

    @abc.abstractmethod
    def with_labels(self, *label_values: str) -> "Gauge": ...


class Histogram(abc.ABC):
    @abc.abstractmethod
    def observe(self, value: float) -> None: ...

    @abc.abstractmethod
    def with_labels(self, *label_values: str) -> "Histogram": ...


class Provider(abc.ABC):
    @abc.abstractmethod
    def new_counter(self, opts: MetricOpts) -> Counter: ...

    @abc.abstractmethod
    def new_gauge(self, opts: MetricOpts) -> Gauge: ...

    @abc.abstractmethod
    def new_histogram(self, opts: MetricOpts) -> Histogram: ...


# ---------------------------------------------------------------------------
# Disabled (no-op) provider — the default, as in the reference
# (pkg/consensus/consensus.go:113-115).
# ---------------------------------------------------------------------------


class _NopCounter(Counter):
    def add(self, delta: float) -> None:
        pass

    def with_labels(self, *label_values: str) -> Counter:
        return self


class _NopGauge(Gauge):
    def set(self, value: float) -> None:
        pass

    def add(self, delta: float) -> None:
        pass

    def with_labels(self, *label_values: str) -> Gauge:
        return self


class _NopHistogram(Histogram):
    def observe(self, value: float) -> None:
        pass

    def with_labels(self, *label_values: str) -> Histogram:
        return self


class DisabledProvider(Provider):
    def new_counter(self, opts: MetricOpts) -> Counter:
        return _NopCounter()

    def new_gauge(self, opts: MetricOpts) -> Gauge:
        return _NopGauge()

    def new_histogram(self, opts: MetricOpts) -> Histogram:
        return _NopHistogram()


# ---------------------------------------------------------------------------
# In-memory provider
# ---------------------------------------------------------------------------


def escape_label_value(value) -> str:
    """Prometheus text-format label-value escaping (backslash, quote,
    newline) — applied when the label pair is FORMED so the exposition
    stays parseable whatever the embedder labels with."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _label_suffix(label_names: tuple, label_values: tuple) -> str:
    """Label key suffix.  With declared names: Prometheus-style
    {name="value",...} with text-format escaping; without: the legacy
    {v1,v2} value form."""
    if label_names:
        pairs = ",".join(
            f'{n}="{escape_label_value(v)}"'
            for n, v in zip(label_names, label_values)
        )
        return "{" + pairs + "}"
    return "{" + ",".join(str(v) for v in label_values) + "}"


class _MemCounter(Counter):
    def __init__(self, store: dict, key: str, label_names: tuple = ()):
        self._store = store
        self._key = key
        self._label_names = label_names
        store.setdefault(key, 0.0)

    def add(self, delta: float) -> None:
        self._store[self._key] = self._store.get(self._key, 0.0) + delta

    def with_labels(self, *label_values: str) -> Counter:
        return _MemCounter(
            self._store,
            self._key + _label_suffix(self._label_names, label_values),
        )


class _MemGauge(Gauge):
    def __init__(self, store: dict, key: str, label_names: tuple = ()):
        self._store = store
        self._key = key
        self._label_names = label_names
        store.setdefault(key, 0.0)

    def set(self, value: float) -> None:
        self._store[self._key] = value

    def add(self, delta: float) -> None:
        self._store[self._key] = self._store.get(self._key, 0.0) + delta

    def with_labels(self, *label_values: str) -> Gauge:
        return _MemGauge(
            self._store,
            self._key + _label_suffix(self._label_names, label_values),
        )


class _MemHistogram(Histogram):
    def __init__(self, store: dict, key: str, label_names: tuple = ()):
        self._store = store
        self._key = key
        self._label_names = label_names
        store.setdefault(key, [])

    def observe(self, value: float) -> None:
        self._store.setdefault(self._key, []).append(value)

    def with_labels(self, *label_values: str) -> Histogram:
        return _MemHistogram(
            self._store,
            self._key + _label_suffix(self._label_names, label_values),
        )


class InMemoryProvider(Provider):
    """Thread-compatible in-memory metrics, introspectable by tests/bench."""

    def __init__(self) -> None:
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, float] = {}
        self.histograms: dict[str, list[float]] = {}

    def new_counter(self, opts: MetricOpts) -> Counter:
        return _MemCounter(self.counters, opts.full_name)

    def new_gauge(self, opts: MetricOpts) -> Gauge:
        return _MemGauge(self.gauges, opts.full_name)

    def new_histogram(self, opts: MetricOpts) -> Histogram:
        return _MemHistogram(self.histograms, opts.full_name)

    def histogram_quantile(self, name: str, q: float) -> Optional[float]:
        vals = sorted(self.histograms.get(name, []))
        if not vals:
            return None
        idx = min(len(vals) - 1, int(q * len(vals)))
        return vals[idx]


# ---------------------------------------------------------------------------
# Naming / format plumbing + exporters
# (pkg/metrics/provider.go:19-127, namer.go: the reference carries
# statsd-format strings and Prometheus naming on MetricOpts; here the same
# capability is two concrete exporter providers with no external deps)
# ---------------------------------------------------------------------------


def statsd_name(opts: MetricOpts, label_values: Sequence[str] = ()) -> str:
    """Expand a statsd naming format.

    ``opts.statsd_format`` supports the reference's placeholders:
    ``%{#namespace}``, ``%{#subsystem}``, ``%{#name}`` and ``%{label}`` for
    each declared label name.  Default format: dotted fqname plus dotted
    label values in declaration order.
    """
    fmt = opts.statsd_format
    if not fmt:
        parts = [p for p in (opts.namespace, opts.subsystem, opts.name) if p]
        return ".".join(list(parts) + [str(v) for v in label_values])
    out = (fmt.replace("%{#namespace}", opts.namespace)
              .replace("%{#subsystem}", opts.subsystem)
              .replace("%{#name}", opts.name))
    for lname, lval in zip(opts.label_names, label_values):
        out = out.replace("%%{%s}" % lname, str(lval))
    return out


def prometheus_name(opts: MetricOpts) -> str:
    """Prometheus fqname: namespace_subsystem_name, snake-cased."""
    parts = [p for p in (opts.namespace, opts.subsystem, opts.name) if p]
    return "_".join(parts).replace(".", "_").replace("-", "_")


class _StatsdMetric:
    def __init__(self, provider: "StatsdProvider", opts: MetricOpts,
                 kind: str, label_values: tuple = ()):
        self._p = provider
        self._opts = opts
        self._kind = kind
        self._labels = label_values

    def _emit(self, value: float) -> None:
        self._p.emit(
            f"{statsd_name(self._opts, self._labels)}:{value:g}|{self._kind}"
        )


class _StatsdCounter(_StatsdMetric, Counter):
    def add(self, delta: float) -> None:
        self._emit(delta)

    def with_labels(self, *label_values: str) -> Counter:
        return _StatsdCounter(self._p, self._opts, self._kind, label_values)


class _StatsdGauge(_StatsdMetric, Gauge):
    def set(self, value: float) -> None:
        name = statsd_name(self._opts, self._labels)
        if value < 0:
            # bare negative values are deltas in the statsd protocol; an
            # absolute negative set needs a zero-reset first (the standard
            # emitter workaround)
            self._p.emit(f"{name}:0|g")
        self._p.emit(f"{name}:{value:g}|g")

    def add(self, delta: float) -> None:
        self._p.emit(
            f"{statsd_name(self._opts, self._labels)}:{'+' if delta >= 0 else ''}{delta:g}|g"
        )

    def with_labels(self, *label_values: str) -> Gauge:
        return _StatsdGauge(self._p, self._opts, self._kind, label_values)


class _StatsdHistogram(_StatsdMetric, Histogram):
    def observe(self, value: float) -> None:
        # the library records latencies in SECONDS (time.monotonic deltas);
        # statsd timers are milliseconds by convention
        self._emit(value * 1000.0)

    def with_labels(self, *label_values: str) -> Histogram:
        return _StatsdHistogram(self._p, self._opts, self._kind, label_values)


class StatsdProvider(Provider):
    """Emits statsd wire lines (``name:value|c|g|ms``) to a sink callable.

    The embedder supplies ``sink`` (e.g. a UDP socket's sendto); the default
    collects lines in ``self.lines`` for inspection.  Naming honors
    ``MetricOpts.statsd_format`` placeholders exactly like the reference's
    statsd namer (pkg/metrics/namer.go).
    """

    def __init__(self, sink=None):
        self.lines: list[str] = []
        self._sink = sink if sink is not None else self.lines.append
        self._lock = threading.Lock()

    def emit(self, line: str) -> None:
        with self._lock:
            self._sink(line)

    def new_counter(self, opts: MetricOpts) -> Counter:
        return _StatsdCounter(self, opts, "c")

    def new_gauge(self, opts: MetricOpts) -> Gauge:
        return _StatsdGauge(self, opts, "g")

    def new_histogram(self, opts: MetricOpts) -> Histogram:
        return _StatsdHistogram(self, opts, "ms")


class PrometheusProvider(InMemoryProvider):
    """In-memory provider with a Prometheus text-format exposition surface.

    ``expose()`` renders every registered metric in the text format a
    Prometheus scrape endpoint serves (# HELP / # TYPE + samples); the
    embedder mounts it behind its own HTTP handler.
    """

    def __init__(self) -> None:
        super().__init__()
        self._meta: dict[str, tuple[str, str]] = {}  # fqname -> (type, help)

    def _register(self, opts: MetricOpts, kind: str) -> str:
        fq = prometheus_name(opts)
        self._meta[fq] = (kind, opts.help)
        return fq

    def new_counter(self, opts: MetricOpts) -> Counter:
        return _MemCounter(self.counters, self._register(opts, "counter"),
                           tuple(opts.label_names))

    def new_gauge(self, opts: MetricOpts) -> Gauge:
        return _MemGauge(self.gauges, self._register(opts, "gauge"),
                         tuple(opts.label_names))

    def new_histogram(self, opts: MetricOpts) -> Histogram:
        return _MemHistogram(self.histograms, self._register(opts, "histogram"),
                             tuple(opts.label_names))

    @staticmethod
    def _split(key: str) -> tuple[str, str]:
        """'fq{a,b}' -> (fq, 'a,b'); plain keys have no label suffix.

        Legacy value-only label suffixes (metrics built with
        ``with_labels`` but no declared ``label_names`` — the {v1,v2}
        store-key form) are rewritten to a parseable
        ``label="v1,v2"`` pair: the raw form is NOT legal text-format
        exposition, and a scraper would reject the whole page over it.
        The test is "does it parse as valid pairs", not "contains =" —
        a legacy value like ``query=slow`` carries an '=' and is still
        not exposition grammar."""
        if key.endswith("}") and "{" in key:
            base, labels = key[:-1].split("{", 1)
            if not _labels_are_valid_pairs(labels):
                labels = f'label="{escape_label_value(labels)}"'
            return base, labels
        return key, ""

    def expose(self) -> str:
        out: list[str] = []
        emitted: set[str] = set()

        def header(fq: str) -> None:
            if fq in emitted or fq not in self._meta:
                return
            kind, help_ = self._meta[fq]
            if help_:
                out.append(f"# HELP {fq} {help_}")
            out.append(f"# TYPE {fq} {kind}")
            emitted.add(fq)

        for key, val in sorted(self.counters.items()):
            fq, labels = self._split(key)
            header(fq)
            out.append(f"{fq}{{{labels}}} {val:g}" if labels else f"{fq} {val:g}")
        for key, val in sorted(self.gauges.items()):
            fq, labels = self._split(key)
            header(fq)
            out.append(f"{fq}{{{labels}}} {val:g}" if labels else f"{fq} {val:g}")
        for key, vals in sorted(self.histograms.items()):
            fq, labels = self._split(key)
            header(fq)
            suffix = f"{{{labels}}}" if labels else ""
            # a catch-all le bucket keeps strict parsers / promtool happy
            inf_labels = (labels + "," if labels else "") + 'le="+Inf"'
            out.append(f"{fq}_bucket{{{inf_labels}}} {len(vals):g}")
            out.append(f"{fq}_count{suffix} {len(vals):g}")
            out.append(f"{fq}_sum{suffix} {sum(vals):g}")
        return "\n".join(out) + "\n"


# ---------------------------------------------------------------------------
# Prometheus exposition lint (ISSUE 14 satellite): a pure validator of the
# text format, so cmd=metrics stays SCRAPEABLE as counters keep accreting.
# ---------------------------------------------------------------------------

import re as _re

_METRIC_NAME_RE = _re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_NAME_RE = _re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")
_SAMPLE_RE = _re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>.*)\})?"
    r"\s+(?P<value>\S+)"
    r"(?:\s+(?P<ts>-?\d+))?\s*$"
)
# one label pair with text-format escapes inside the quoted value; the
# name charset is deliberately loose here — the strict check happens
# against _LABEL_NAME_RE so a bad NAME reports as such, not as syntax
_LABEL_PAIR_RE = _re.compile(
    r'\s*(?P<name>[^=,"{}\s]+)\s*=\s*'
    r'"(?P<value>(?:[^"\\\n]|\\\\|\\"|\\n)*)"\s*(?:,|$)'
)
_TYPES = {"counter", "gauge", "histogram", "summary", "untyped"}
#: suffixes a histogram/summary family's samples may carry
_HIST_SUFFIXES = ("_bucket", "_count", "_sum", "_created")


def _labels_are_valid_pairs(labels: str) -> bool:
    """True when ``labels`` fully parses as text-format label pairs
    (valid names, quoted + escaped values) — the PrometheusProvider
    legacy-suffix rewrite keys off this, and the lint uses the same
    pair grammar."""
    pos = 0
    while pos < len(labels):
        m = _LABEL_PAIR_RE.match(labels, pos)
        if m is None or not _LABEL_NAME_RE.match(m.group("name")):
            return False
        pos = m.end()
    return pos > 0


def _sample_family(name: str, types: dict) -> Optional[str]:
    """The declared family a sample name belongs to, if any."""
    if name in types:
        return name
    for suffix in _HIST_SUFFIXES:
        if name.endswith(suffix) and name[: -len(suffix)] in types:
            return name[: -len(suffix)]
    return None


def lint_prometheus_text(text: str) -> list[str]:
    """Validate a Prometheus text-format exposition; returns [] when
    clean, else one message per problem (line-numbered).

    Checks the grammar a strict scraper/promtool enforces: metric/label
    name charset, quoted + escaped label values, float-parseable sample
    values, at most ONE ``# TYPE`` (and ``# HELP``) per family with the
    TYPE preceding that family's first sample, a known type keyword, no
    duplicate (name, labelset) samples, and histogram/summary samples
    restricted to the legal suffixes of their declared family."""
    problems: list[str] = []
    types: dict[str, str] = {}
    helps: set[str] = set()
    sampled_families: set[str] = set()
    seen_samples: set[tuple] = set()
    for ln, line in enumerate(text.splitlines(), start=1):
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) < 3 or parts[1] not in ("HELP", "TYPE"):
                continue  # free-form comment: legal
            name = parts[2]
            if not _METRIC_NAME_RE.match(name):
                problems.append(f"line {ln}: bad metric name {name!r}")
                continue
            if parts[1] == "TYPE":
                kind = parts[3].strip() if len(parts) > 3 else ""
                if kind not in _TYPES:
                    problems.append(
                        f"line {ln}: unknown TYPE {kind!r} for {name}"
                    )
                if name in types:
                    problems.append(
                        f"line {ln}: duplicate TYPE line for {name}"
                    )
                if name in sampled_families:
                    problems.append(
                        f"line {ln}: TYPE for {name} after its samples"
                    )
                types[name] = kind
            else:
                if name in helps:
                    problems.append(
                        f"line {ln}: duplicate HELP line for {name}"
                    )
                helps.add(name)
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            problems.append(f"line {ln}: unparseable sample {line!r}")
            continue
        name = m.group("name")
        labels_raw = m.group("labels")
        labelset = ""
        if labels_raw is not None:
            pos = 0
            pairs = []
            while pos < len(labels_raw):
                pm = _LABEL_PAIR_RE.match(labels_raw, pos)
                if pm is None:
                    problems.append(
                        f"line {ln}: bad label syntax at {labels_raw[pos:]!r}"
                        " (unescaped quote/backslash/newline?)"
                    )
                    pairs = None
                    break
                if not _LABEL_NAME_RE.match(pm.group("name")):
                    problems.append(
                        f"line {ln}: bad label name {pm.group('name')!r}"
                    )
                pairs.append((pm.group("name"), pm.group("value")))
                pos = pm.end()
            if pairs is None:
                continue
            labelset = ",".join(f'{n}="{v}"' for n, v in sorted(pairs))
        try:
            float(m.group("value"))
        except ValueError:
            if m.group("value") not in ("+Inf", "-Inf", "NaN"):
                problems.append(
                    f"line {ln}: sample value {m.group('value')!r} is not "
                    "a float"
                )
        key = (name, labelset)
        if key in seen_samples:
            problems.append(
                f"line {ln}: duplicate sample {name}{{{labelset}}}"
            )
        seen_samples.add(key)
        family = _sample_family(name, types)
        if family is not None:
            sampled_families.add(family)
            kind = types.get(family)
            # summaries deliberately get no bare-sample check: quantile
            # samples legally use the bare family name
            if kind == "histogram" and name == family:
                problems.append(
                    f"line {ln}: histogram {family} exposes a bare sample "
                    f"(only {'/'.join(_HIST_SUFFIXES)} are legal)"
                )
            if kind in ("counter", "gauge") and name != family:
                problems.append(
                    f"line {ln}: {kind} {family} exposes suffixed sample "
                    f"{name}"
                )
    return problems


# ---------------------------------------------------------------------------
# Metric bundles (pkg/api/metrics.go)
# ---------------------------------------------------------------------------


def _c(p: Provider, subsystem: str, name: str, help: str = "") -> Counter:
    return p.new_counter(MetricOpts(namespace="consensus", subsystem=subsystem, name=name, help=help))


def _g(p: Provider, subsystem: str, name: str, help: str = "") -> Gauge:
    return p.new_gauge(MetricOpts(namespace="consensus", subsystem=subsystem, name=name, help=help))


def _h(p: Provider, subsystem: str, name: str, help: str = "") -> Histogram:
    return p.new_histogram(MetricOpts(namespace="consensus", subsystem=subsystem, name=name, help=help))


class RequestPoolMetrics:
    """metrics.go:106-172 — seven request-pool metrics."""

    def __init__(self, p: Provider):
        self.count_of_requests = _g(p, "pool", "count_of_requests")
        self.count_of_failed_add_requests = _c(p, "pool", "count_of_failed_add_requests")
        self.count_of_leader_forward_requests = _c(p, "pool", "count_of_leader_forward_requests")
        self.count_leader_forward_timeout = _c(p, "pool", "count_leader_forward_timeout")
        self.count_of_complain_timeout = _c(p, "pool", "count_of_complain_timeout")
        self.count_of_deleted_requests = _c(p, "pool", "count_of_deleted_requests")
        self.latency_of_requests = _h(p, "pool", "latency_of_requests")


class BlacklistMetrics:
    """metrics.go:239-258."""

    def __init__(self, p: Provider):
        self.count_black_list = _g(p, "blacklist", "count_black_list")
        self.nodes_in_black_list = _g(p, "blacklist", "nodes_in_black_list")


class ConsensusMetrics:
    """metrics.go:299-343."""

    def __init__(self, p: Provider):
        self.count_consensus_reconfig = _c(p, "consensus", "count_consensus_reconfig")
        self.latency_sync = _h(p, "consensus", "latency_sync")


class ViewMetrics:
    """metrics.go:346-460 — per-view protocol progress metrics."""

    def __init__(self, p: Provider):
        self.view_number = _g(p, "view", "number")
        self.leader_id = _g(p, "view", "leader_id")
        self.proposal_sequence = _g(p, "view", "proposal_sequence")
        self.decisions_in_view = _g(p, "view", "decisions_in_view")
        self.phase = _g(p, "view", "phase")
        self.count_txs_in_batch = _g(p, "view", "count_txs_in_batch")
        self.count_batch_all = _c(p, "view", "count_batch_all")
        self.count_txs_all = _c(p, "view", "count_txs_all")
        self.size_of_batch = _c(p, "view", "size_of_batch")
        self.latency_batch_processing = _h(p, "view", "latency_batch_processing")
        self.latency_batch_save = _h(p, "view", "latency_batch_save")


class ViewChangeMetrics:
    """metrics.go:520-548 — plus the VC-health instrumentation ISSUE 12
    wires for real: complaint traffic, rounds, sync escalations, and a
    live time-in-view-change gauge, fed from the ViewChanger (and its
    phase tracker) so Prometheus/statsd providers see failover health
    without the flight recorder enabled."""

    def __init__(self, p: Provider):
        self.current_view = _g(p, "viewchange", "current_view")
        self.next_view = _g(p, "viewchange", "next_view")
        self.real_view = _g(p, "viewchange", "real_view")
        #: ViewChange messages this node broadcast (starts + resends +
        #: lagging-node help)
        self.count_complaints_sent = _c(
            p, "viewchange", "count_complaints_sent")
        #: ViewChange messages received from peers
        self.count_complaints_received = _c(
            p, "viewchange", "count_complaints_received")
        #: view-change rounds armed on this node (a timeout escalation
        #: toward a higher view is a new round)
        self.count_view_change_rounds = _c(p, "viewchange", "count_rounds")
        #: timeout escalations that forced a sync mid-view-change
        self.count_sync_escalations = _c(
            p, "viewchange", "count_sync_escalations")
        #: seconds in the CURRENT view change (live, tick-updated) —
        #: freezes at the end-to-end total when the round completes
        self.time_in_view_change = _g(
            p, "viewchange", "time_in_view_change_seconds")
        #: complain-timer arm-to-fire time of the LAST heartbeat-timeout
        #: firing (seconds): the detection latency PERF round 15 blamed
        #: for ~99% of the failover cliff, now a first-class gauge
        self.heartbeat_detection_seconds = _g(
            p, "viewchange", "heartbeat_detection_seconds")
        #: heartbeat-timeout firings (each arms/rearms a complain)
        self.count_heartbeat_timeouts = _c(
            p, "viewchange", "count_heartbeat_timeouts")
        #: request-pool depth at the view flip (the stalled backlog the
        #: new view must drain before request p99 recovers)
        self.backlog_at_view_flip = _g(
            p, "viewchange", "backlog_at_view_flip")
        #: the EFFECTIVE (derived) complain timer and its inputs
        #: (ISSUE 15): detection_timeout_seconds is what the monitor will
        #: actually wait before complaining — the RTT/commit-EWMA-derived
        #: value after backoff and ceiling clamp; the *_input gauges are
        #: its live signal terms (0 when the signal is unmeasured) and
        #: detection_backoff_round the consecutive-complaint widening
        #: round against the current view
        self.detection_timeout_seconds = _g(
            p, "viewchange", "detection_timeout_seconds")
        self.detection_rtt_seconds = _g(
            p, "viewchange", "detection_rtt_input_seconds")
        self.detection_commit_interval_seconds = _g(
            p, "viewchange", "detection_commit_interval_input_seconds")
        self.detection_backoff_round = _g(
            p, "viewchange", "detection_backoff_round")


class TPUCryptoMetrics:
    """TPU-plane additions (BASELINE.json): batch occupancy + verify latency.

    PER-INSTANCE by construction (one bundle per provider) — nothing here
    is process-global, so counters from colocated shards/nodes never smear
    unless the embedder deliberately shares one provider.  The sharded
    harness DOES share one (the verify plane is one coalescer, so its
    fill/latency/breaker counters are inherently whole-plane); an embedder
    that instead builds per-shard providers reads the roll-up with
    :func:`tpu_counters_aggregate`."""

    def __init__(self, p: Provider):
        self.batch_fill_percent = _h(p, "tpu", "batch_fill_percent")
        self.verify_latency_per_sig_us = _h(p, "tpu", "verify_latency_per_sig_us")
        self.count_sigs_verified = _c(p, "tpu", "count_sigs_verified")
        self.count_batches = _c(p, "tpu", "count_batches")
        # verify-plane fault tolerance (launch deadlines / retry / breaker):
        # transitions are counted here AND mirrored into every bench JSON
        # row, so a degraded (host-fallback) run is never silently reported
        # as a device run
        self.count_launch_failures = _c(p, "tpu", "count_launch_failures")
        self.count_launch_timeouts = _c(p, "tpu", "count_launch_timeouts")
        self.count_launch_retries = _c(p, "tpu", "count_launch_retries")
        self.count_breaker_open = _c(p, "tpu", "count_breaker_open")
        self.count_breaker_close = _c(p, "tpu", "count_breaker_close")
        self.count_host_fallback_batches = _c(
            p, "tpu", "count_host_fallback_batches"
        )
        #: 1.0 while the host-fallback circuit breaker is open (degraded
        #: mode: waves verify on CPU), 0.0 when the device engine serves
        self.breaker_state = _g(p, "tpu", "verify_breaker_open")
        # mesh verify plane (ISSUE 10): the graduated multi-device path.
        # mesh_devices is the installed mesh width (0 = single-device);
        # per-launch accounting (launch count, pad-slot waste, the MINIMUM
        # per-device fill of each launch — padding lands on tail devices)
        # plus the loud unbuildable-mesh downgrade counter, so a degraded
        # single-device run is never mistaken for a mesh run
        self.mesh_devices = _g(p, "tpu", "mesh_devices")
        self.count_mesh_launches = _c(p, "tpu", "count_mesh_launches")
        self.count_mesh_pad_slots = _c(p, "tpu", "count_mesh_pad_slots")
        self.count_mesh_downgrades = _c(p, "tpu", "count_mesh_downgrades")
        self.mesh_device_fill_percent = _h(p, "tpu", "mesh_device_fill_percent")
        # occupancy-aware flush gating (ISSUE 11): how many flushes held
        # for predicted-inbound waves, and how many items those holds
        # actually gained — the wave-deepening payoff, mirrored in the
        # `hold` sub-block of every bench row's `mesh` block
        self.count_waves_held = _c(p, "tpu", "count_waves_held")
        self.count_hold_depth_gain = _c(p, "tpu", "count_hold_depth_gain")
        #: invalid vote verdicts ATTRIBUTED BY SIGNER (ISSUE 18): the
        #: provider increments `.with_labels(str(signer))` on every failed
        #: consenter-sig verdict (bad signature value, digest-binding
        #: forgery, unknown signer), so a forgery flood shows WHO instead
        #: of vanishing into the aggregate failure count — the export the
        #: per-sender misbehavior table and bench `byzantine` rows read
        self.count_invalid_votes = _c(
            p, "tpu", "count_invalid_votes",
            help="failed consenter-sig verdicts attributed by signer id",
        )


def tpu_counters_aggregate(providers: Sequence[InMemoryProvider]) -> dict:
    """Explicit aggregate view over per-shard TPU metric providers.

    Sums every ``.tpu.`` counter across the given
    :class:`InMemoryProvider` instances; gauges sum too (a 0/1 gauge like
    ``verify_breaker_open`` aggregates to "how many providers are
    degraded"); histograms contribute their observation counts under
    ``<name>_count``.  For an embedder that gives each shard its own
    provider, this is the one-call roll-up (the in-process harness instead
    shares one provider across the shared plane — see
    :class:`TPUCryptoMetrics`)."""
    out: dict = {}
    for p in providers:
        for store in (p.counters, p.gauges):
            for key, val in store.items():
                if ".tpu." in key:
                    out[key] = out.get(key, 0.0) + val
        for key, vals in p.histograms.items():
            if ".tpu." in key:
                out[key + "_count"] = out.get(key + "_count", 0.0) + len(vals)
    return out


# ---------------------------------------------------------------------------
# Commit-latency accounting (the open-loop service surface: README
# "Overload behavior", testing/load.py)
# ---------------------------------------------------------------------------


class LogScaleHistogram:
    """Fixed-bucket log-scale histogram with BOUNDED memory.

    The in-memory provider's histograms append every observation — fine
    for bench windows, fatal for a service recording one sample per
    request forever.  This histogram is a fixed array of geometric
    buckets (default: 1 µs low edge, √2 growth, 64 buckets ≈ 1 µs..100 s
    span), so a billion observations cost the same 64 ints.  Quantiles
    come from the cumulative bucket walk and are reported at the bucket's
    geometric midpoint — ≤ ~±19% relative error at √2 growth, far inside
    the run-to-run noise of any latency measurement this repo makes."""

    __slots__ = ("low", "growth", "buckets", "count", "total", "max_seen",
                 "min_seen", "_log_low", "_log_growth")

    def __init__(self, low: float = 1e-6, growth: float = 2.0 ** 0.5,
                 nbuckets: int = 64):
        self.low = low
        self.growth = growth
        self.buckets = [0] * nbuckets
        self.count = 0
        self.total = 0.0
        self.max_seen = 0.0
        self.min_seen = float("inf")
        self._log_low = math.log(low)
        self._log_growth = math.log(growth)

    def observe(self, value: float) -> None:
        self.count += 1
        self.total += value
        if value > self.max_seen:
            self.max_seen = value
        if value < self.min_seen:
            self.min_seen = value
        if value <= self.low:
            idx = 0
        else:
            idx = int((math.log(value) - self._log_low) / self._log_growth)
            idx = min(max(idx, 0), len(self.buckets) - 1)
        self.buckets[idx] += 1

    def quantile(self, q: float) -> float:
        """The q-quantile (0..1) at the owning bucket's geometric midpoint,
        clamped into the observed [min, max] envelope; 0.0 when empty."""
        if not self.count:
            return 0.0
        rank = max(1, int(q * self.count + 0.999999))  # ceil, 1-based
        seen = 0
        for i, n in enumerate(self.buckets):
            seen += n
            if seen >= rank:
                mid = self.low * (self.growth ** (i + 0.5))
                return min(max(mid, self.min_seen), self.max_seen)
        return self.max_seen

    def delta_quantile(self, q: float, baseline: list) -> float:
        """The q-quantile of the observations recorded SINCE ``baseline``
        (a prior copy of ``buckets``) — the recency window a cumulative
        histogram cannot otherwise express.  Same-geometry buckets
        subtract element-wise exactly, so this is the true distribution
        of the delta; the [min, max] clamp uses the lifetime envelope
        (per-window extremes are not tracked — ≤ one bucket of extra
        slack at the edges).  0.0 when nothing landed since the
        baseline.  A health plane needs this: a verdict judged on the
        lifetime p99 can never clear after one bad spell."""
        counts = [n - b for n, b in zip(self.buckets, baseline)]
        total = sum(counts)
        if total <= 0:
            return 0.0
        rank = max(1, int(q * total + 0.999999))
        seen = 0
        for i, n in enumerate(counts):
            seen += n
            if seen >= rank:
                mid = self.low * (self.growth ** (i + 0.5))
                return min(max(mid, self.min_seen), self.max_seen)
        return self.max_seen

    def snapshot(self) -> dict:
        """JSON-able percentile block (milliseconds, the service unit)."""
        ms = 1e3
        return {
            "count": self.count,
            "p50_ms": round(self.quantile(0.50) * ms, 3),
            "p95_ms": round(self.quantile(0.95) * ms, 3),
            "p99_ms": round(self.quantile(0.99) * ms, 3),
            "mean_ms": round(self.total / self.count * ms, 3)
            if self.count else 0.0,
            "max_ms": round(self.max_seen * ms, 3),
        }

    def merge_from(self, other: "LogScaleHistogram") -> None:
        """Fold ``other``'s observations into this histogram EXACTLY —
        same-geometry fixed buckets sum element-wise, so a merge over N
        per-replica histograms is the true combined distribution (the
        obs.assemble_trace_block roll-up), never a
        percentile-of-percentiles."""
        if (other.low != self.low or other.growth != self.growth
                or len(other.buckets) != len(self.buckets)):
            raise ValueError("cannot merge histograms of different geometry")
        for i, n in enumerate(other.buckets):
            self.buckets[i] += n
        self.count += other.count
        self.total += other.total
        if other.max_seen > self.max_seen:
            self.max_seen = other.max_seen
        if other.min_seen < self.min_seen:
            self.min_seen = other.min_seen

    def nonzero_buckets(self) -> dict:
        """Sparse bucket dump for the bench row's ``histogram`` block:
        {upper_edge_ms: count} for every non-empty bucket."""
        out = {}
        for i, n in enumerate(self.buckets):
            if n:
                edge_ms = self.low * (self.growth ** (i + 1)) * 1e3
                out[f"{edge_ms:.3g}"] = n
        return out


class CommitLatencyTracker:
    """Per-request submit→commit latency for a sharded front door.

    The ShardSet stamps each request's arrival at ``submit`` (BEFORE any
    admission/backpressure wait — the latency a client experiences
    includes the queueing) and resolves the stamp when the request id
    appears in the combined committed stream.  Aggregated into
    :class:`LogScaleHistogram` buckets per shard + overall, with shed
    counters (requests refused by admission control or timed out of the
    space wait) alongside — a latency distribution without its shed rate
    is survivor bias.

    **Phases.**  ``begin_phase(name)`` opens a named window (histogram +
    shed deltas) that subsequent commits/sheds also land in — how the
    degraded-mode SLO runs attribute p99 to "breaker open" vs "view
    change" vs "reshard" without re-running the workload per fault.

    **Bounded memory.**  Histograms are fixed arrays; the pending-stamp
    map is capped at ``max_pending`` — beyond it the OLDEST stamp is
    dropped and counted (an overloaded front door sheds; it never grows
    an unbounded latency map).  ``clock`` is injectable: wall
    ``time.monotonic`` in production/bench, the logical ``Scheduler.now``
    in deterministic tests."""

    def __init__(self, clock=None, max_pending: int = 65536):
        import collections
        import time as _time

        self._clock = clock if clock is not None else _time.monotonic
        self._pending: "collections.OrderedDict[str, float]" = \
            collections.OrderedDict()
        self.max_pending = max_pending
        self.dropped_stamps = 0
        self.aggregate = LogScaleHistogram()
        self.per_shard: dict[int, LogScaleHistogram] = {}
        self.shed = {"admission": 0, "timeout": 0, "other": 0}
        self._phases: "dict[str, dict]" = {}
        self._phase_order: list[str] = []
        self._current_phase: Optional[dict] = None

    # -- stamping ----------------------------------------------------------

    def on_submitted(self, key: str) -> bool:
        """Stamp ``key``'s arrival (front-door entry, pre-queueing).

        A key already pending keeps its ORIGINAL stamp — a client
        retrying a still-in-flight request experiences latency from its
        FIRST submit, and overwriting would let the pool's dedup path
        erase the measurement of exactly the slow (hence retried)
        requests.  Returns True when a fresh stamp was created."""
        key = str(key)
        if key in self._pending:
            return False
        self._pending[key] = self._clock()
        if len(self._pending) > self.max_pending:
            self._pending.popitem(last=False)
            self.dropped_stamps += 1
        return True

    def discard(self, key: str) -> None:
        """Drop a stamp without counting anything (e.g. a submit that
        turned out to be a duplicate of an ALREADY-COMMITTED request —
        no commit is coming, and it was not shed either)."""
        self._pending.pop(str(key), None)

    def on_shed(self, key: Optional[str], kind: str) -> None:
        """The stamped submit was refused (``admission`` / ``timeout`` /
        ``other``): drop its stamp, count the shed."""
        if key is not None:
            self._pending.pop(str(key), None)
        kind = kind if kind in self.shed else "other"
        self.shed[kind] += 1
        if self._current_phase is not None:
            self._current_phase["shed"][kind] += 1

    def on_committed(self, key: str, shard_id: int) -> None:
        """Resolve a stamp against the committed stream; unstamped ids
        (barrier commands, requests submitted around the tracker) no-op."""
        t0 = self._pending.pop(str(key), None)
        if t0 is None:
            return
        dt = max(self._clock() - t0, 0.0)
        self.aggregate.observe(dt)
        hist = self.per_shard.get(shard_id)
        if hist is None:
            hist = self.per_shard[shard_id] = LogScaleHistogram()
        hist.observe(dt)
        if self._current_phase is not None:
            self._current_phase["hist"].observe(dt)

    def on_committed_batch(self, entries) -> None:
        """Resolve a whole committed wave of
        :class:`~smartbft_tpu.shard.mux.CommittedEntry` in one pass: one
        clock read and one per-shard histogram lookup per wave instead of
        per request — the egress half of the batched deliver fan-out."""
        now = None
        for e in entries:
            hist = None  # resolved lazily: entries of pure control traffic
            for key in e.request_ids:  # must not materialize a histogram
                t0 = self._pending.pop(key, None)
                if t0 is None:
                    continue
                if now is None:
                    now = self._clock()
                if hist is None:
                    hist = self.per_shard.get(e.shard_id)
                    if hist is None:
                        hist = self.per_shard[e.shard_id] = LogScaleHistogram()
                dt = max(now - t0, 0.0)
                self.aggregate.observe(dt)
                hist.observe(dt)
                if self._current_phase is not None:
                    self._current_phase["hist"].observe(dt)

    # -- phases ------------------------------------------------------------

    def begin_phase(self, name: str) -> None:
        """Open (or re-open) the named attribution window; subsequent
        commits and sheds land in it until the next begin_phase."""
        phase = self._phases.get(name)
        if phase is None:
            phase = self._phases[name] = {
                "hist": LogScaleHistogram(),
                "shed": {k: 0 for k in self.shed},
            }
            self._phase_order.append(name)
        self._current_phase = phase

    def end_phase(self) -> None:
        self._current_phase = None

    # -- reading -----------------------------------------------------------

    def pending(self) -> int:
        return len(self._pending)

    def snapshot(self) -> dict:
        """The JSON-able ``latency`` block every open-loop bench row
        carries (schema pinned by tests/test_overload.py)."""
        out = dict(self.aggregate.snapshot())
        out["shed"] = dict(self.shed)
        # the raw distribution (sparse {upper_edge_ms: count}), bounded at
        # 64 entries — what the bench row's "histogram" promise refers to
        out["histogram"] = self.aggregate.nonzero_buckets()
        out["pending_stamps"] = len(self._pending)
        out["dropped_stamps"] = self.dropped_stamps
        out["per_shard"] = {
            s: h.snapshot() for s, h in sorted(self.per_shard.items())
        }
        if self._phase_order:
            out["phases"] = {
                name: dict(self._phases[name]["hist"].snapshot(),
                           shed=dict(self._phases[name]["shed"]))
                for name in self._phase_order
            }
        return out


# ---------------------------------------------------------------------------
# Protocol-plane timers (the vectorized message plane's measurement surface)
# ---------------------------------------------------------------------------


class ProtocolPlaneTimers:
    """Process-wide accumulator for the message plane's hot-path terms.

    The round-6 ceiling decomposition (PERF.md) showed the paired ratio
    bound by the PROTOCOL plane, dominated by per-message routing, vote
    registration, and (in any real transport) per-recipient codec work.
    These counters make that cost measured instead of asserted: the
    in-process network, the controller dispatch, and the views accumulate
    wall-time (microseconds) and call counts here; a snapshot delta over
    a window is what ``chipbench``'s ``protocol_us_per_decision`` reads.

    Accumulation is a couple of float adds per WAVE (never per message),
    so the accounting itself stays off the path it measures.  The four
    timers are DISJOINT: the network subtracts the codec time accrued
    inside a fan-out from ``route_us`` and the codec + vote-registration
    time accrued inside an ingest tick from ``ingest_us``, so
    ``ingest_us + route_us + vote_reg_us + codec_us`` is the plane total
    without double-counting.  (``route_us`` is the sender side: fault
    checks + enqueue; ``ingest_us`` is the receiver-side drain/dispatch
    remainder; ``codec_us`` covers every marshal/unmarshal wherever it
    runs; ``vote_reg_us`` is view-level wave registration.)

    **Per-instance attribution (sharded mode).**  Timers are PER-INSTANCE:
    every constructed ``ProtocolPlaneTimers`` joins a process-wide
    registry, and :func:`protocol_plane_snapshot` returns the AGGREGATE
    across all instances — so embedders that only ever touch the default
    :data:`PROTOCOL_PLANE` singleton see exactly the old behavior, while a
    sharded deployment hands each consensus group its own plane (via
    ``testing.network.Network.group(gid, plane=...)``) and can attribute
    message-plane cost per shard AND still read the whole-process
    aggregate from the same back-compat function.
    """

    __slots__ = (
        "name", "__weakref__",
        "ingest_us", "route_us", "vote_reg_us", "codec_us",
        "broadcasts", "sends", "encodes", "encode_memo_hits",
        "decodes", "decode_interned_hits", "intern_evictions",
        "batch_ingests", "msgs_ingested", "malformed_dropped",
    )

    #: process-wide registry of every live plane — the aggregate view.
    #: Weak references: a plane lives exactly as long as its owner (a
    #: Network/cluster holds a strong ref), so long-lived processes that
    #: build many clusters (benches, soaks) neither grow the registry
    #: without bound nor smear dead clusters' counters into the aggregate.
    _registry: "list[weakref.ref[ProtocolPlaneTimers]]" = []
    _registry_lock = threading.Lock()

    #: slots that carry measurement (everything except the identity field)
    _COUNTER_SLOTS: tuple[str, ...] = ()

    def __init__(self, name: str = "") -> None:
        self.name = name
        self.reset()
        with ProtocolPlaneTimers._registry_lock:
            ProtocolPlaneTimers._registry.append(weakref.ref(self))

    def reset(self) -> None:
        self.ingest_us = 0.0    # node batch-drain -> dispatch, total
        self.route_us = 0.0     # sender-side fan-out (fault checks + enqueue)
        self.vote_reg_us = 0.0  # view-level wave registration (slots/vote sets)
        self.codec_us = 0.0     # marshal + (interned) unmarshal wall time
        self.broadcasts = 0           # broadcast_consensus fan-outs
        self.sends = 0                # single-target consensus sends
        self.encodes = 0              # actual marshal() compilations
        self.encode_memo_hits = 0     # wire bytes served from the message memo
        self.decodes = 0              # actual unmarshal() runs (intern misses)
        self.decode_interned_hits = 0  # deliveries served by the intern memo
        self.intern_evictions = 0     # bounded intern memo evictions
        self.batch_ingests = 0        # node ingest ticks (batches drained)
        self.msgs_ingested = 0        # messages across those ticks
        self.malformed_dropped = 0    # undecodable wire payloads dropped

    def snapshot(self) -> dict:
        return {name: getattr(self, name)
                for name in ProtocolPlaneTimers._COUNTER_SLOTS}

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        return {
            k: round(after[k] - before[k], 1)
            if isinstance(after[k], float) else after[k] - before[k]
            for k in after
        }

    @staticmethod
    def sum_snapshots(snapshots: Sequence[dict]) -> dict:
        """Element-wise sum — the aggregate view over per-shard planes."""
        out: dict = {
            k: 0.0 if k.endswith("_us") else 0
            for k in ProtocolPlaneTimers._COUNTER_SLOTS
        }
        for snap in snapshots:
            for k, v in snap.items():
                out[k] = out.get(k, 0) + v
        return {k: round(v, 1) if isinstance(v, float) else v
                for k, v in out.items()}


ProtocolPlaneTimers._COUNTER_SLOTS = tuple(
    s for s in ProtocolPlaneTimers.__slots__
    if s not in ("name", "__weakref__")
)


#: the process-wide DEFAULT instance — what every accounting site feeds
#: unless the embedder wired a per-instance plane (one in-process cluster
#: = one plane, the single-group deployment the original benches measure)
PROTOCOL_PLANE = ProtocolPlaneTimers(name="default")


def protocol_plane_instances() -> "list[ProtocolPlaneTimers]":
    """Every live plane (default singleton first) — per-shard attribution.
    Dead weakrefs (planes whose owning cluster was collected) are pruned."""
    with ProtocolPlaneTimers._registry_lock:
        alive: list = []
        out: list = []
        for ref in ProtocolPlaneTimers._registry:
            plane = ref()
            if plane is not None:
                alive.append(ref)
                out.append(plane)
        ProtocolPlaneTimers._registry[:] = alive
        return out


def protocol_plane_snapshot() -> dict:
    """AGGREGATE snapshot across every plane instance in the process.

    Back-compat contract: when only the default :data:`PROTOCOL_PLANE`
    exists (every pre-sharding embedder), this is exactly its snapshot;
    with per-shard planes wired it is their element-wise sum, so existing
    bench/JSON consumers keep reading whole-process numbers."""
    return ProtocolPlaneTimers.sum_snapshots(
        [p.snapshot() for p in protocol_plane_instances()]
    )


#: task-context plane installed by the transport around an ingest dispatch,
#: so accounting sites deep in the protocol core (view/pipeline vote
#: registration) attribute to the right shard without plumbing a plane
#: through every constructor.  None = use the process default.
_CURRENT_PLANE: "contextvars.ContextVar[Optional[ProtocolPlaneTimers]]" = (
    contextvars.ContextVar("smartbft_protocol_plane", default=None)
)


def current_plane() -> ProtocolPlaneTimers:
    """The plane the calling context should feed: the per-shard plane the
    transport installed for this dispatch, or the process default."""
    p = _CURRENT_PLANE.get()
    return PROTOCOL_PLANE if p is None else p


def install_plane(plane: Optional[ProtocolPlaneTimers]):
    """Install ``plane`` as this context's accounting target (the network
    wraps each ingest dispatch); returns the token for :func:`reset_plane`."""
    return _CURRENT_PLANE.set(plane)


def reset_plane(token) -> None:
    _CURRENT_PLANE.reset(token)


class MetricsBundle:
    """All bundles wired from one provider — what Consensus hands to components."""

    def __init__(self, p: Optional[Provider] = None):
        p = p or DisabledProvider()
        self.provider = p
        self.pool = RequestPoolMetrics(p)
        self.blacklist = BlacklistMetrics(p)
        self.consensus = ConsensusMetrics(p)
        self.view = ViewMetrics(p)
        self.view_change = ViewChangeMetrics(p)
        self.tpu = TPUCryptoMetrics(p)
