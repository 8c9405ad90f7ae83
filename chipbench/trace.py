"""From a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The reduction works on a plain list of :class:`Event` so that it can be
checked against a small synthetic list (``tests/test_trace.py``);
:func:`load_xplane` is the only part that touches JAX.

* device busy time — the union of the intervals in which an operation ran
  on a device (the device plane's ``XLA Ops`` line), averaged over the
  device planes; the idle share is 1 - busy / traced span;
* a kernel's device time — the summed durations of the events whose name
  holds the kernel's pattern, read from the ``XLA Modules`` line (one
  event per launch of a jitted program) and, where that line is missing,
  from the ops line;
* the breakdown — device operations by summed time, and the device's idle
  time split over the harness's own host annotations
  (``chipbench.submit`` / ``poll`` / ``yield``) it lies under.

``python3 -m chipbench.trace <file.xplane.pb>`` prints what a trace holds.
"""

from __future__ import annotations

import bisect
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Iterable, NamedTuple, Optional, Sequence

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "chipbench."


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_xplane(path: str) -> list[Event]:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def short_name(name: str) -> str:
    """The trace prints a device operation as its whole HLO line; keep the
    instruction's name and its result shape:
    ``%ecdsa_verify_comb.1 = u32[1,512]{1,0:T(1,128)} custom-call(...)`` ->
    ``ecdsa_verify_comb.1 u32[1,512]``."""
    left, sep, right = name.partition(" = ")
    if not sep:
        return name[:120]
    shape = right.split("{", 1)[0].split(" ", 1)[0]
    return f"{left.lstrip('%')} {shape}"[:120]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "host" not in name.lower()


def union(intervals: Iterable[tuple[float, float]]) -> list[list[float]]:
    """Merge ``(start, end)`` intervals -> disjoint, sorted."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def _line_events(events: Sequence[Event], plane: str,
                 prefer: str, fallback: Optional[str]) -> list[Event]:
    mine = [e for e in events if e.plane == plane]
    for want in (prefer, fallback):
        if want is None:
            continue
        hit = [e for e in mine if e.line == want]
        if hit:
            return hit
    return mine


@dataclass
class TraceSummary:
    devices: list = field(default_factory=list)
    #: seconds in which an operation ran, averaged over the device planes
    busy_s: float = 0.0
    #: first device or harness event to the last, in the trace's own clock
    span_s: float = 0.0
    device_events: int = 0
    #: [name, seconds] by summed device time, at most 10
    device_ops: list = field(default_factory=list)
    #: [what the host was doing, seconds of device idleness], at most 10
    idle_gaps: list = field(default_factory=list)
    #: name -> (seconds, launches) of the per-launch line
    modules: dict = field(default_factory=dict)

    def kernel_seconds(self, pattern: str) -> tuple[float, int]:
        """(device seconds, launches) of the events whose name holds
        ``pattern`` (case-blind), summed over devices."""
        pat = pattern.lower()
        secs, count = 0.0, 0
        for name, (s, c) in self.modules.items():
            if pat in name.lower():
                secs += s
                count += c
        return secs, count


def reduce_trace(events: Sequence[Event]) -> TraceSummary:
    out = TraceSummary()
    out.devices = sorted({e.plane for e in events
                          if is_device_plane(e.plane)})
    host = [e for e in events if e.name.startswith(HOST_PREFIX)]
    index = _HostIndex(host)
    if not out.devices:
        return out
    by_name: dict = defaultdict(float)
    modules: dict = defaultdict(lambda: [0.0, 0])
    busy_total = 0.0
    gaps: dict = defaultdict(float)
    dev_start, dev_end = float("inf"), float("-inf")
    for plane in out.devices:
        ops = _line_events(events, plane, OPS_LINE, MODULES_LINE)
        out.device_events += len(ops)
        for e in ops:
            by_name[short_name(e.name)] += e.dur_ns
        for e in _line_events(events, plane, MODULES_LINE, OPS_LINE):
            modules[e.name][0] += e.dur_ns
            modules[e.name][1] += 1
        busy = union((e.start_ns, e.end_ns) for e in ops)
        busy_total += sum(b - a for a, b in busy)
        if busy:
            dev_start = min(dev_start, busy[0][0])
            dev_end = max(dev_end, busy[-1][1])
        # idle gaps of this device: between consecutive busy intervals,
        # and from / to the ends of the span the harness annotated
        edges = [[index.start, index.start]] + busy + \
            [[index.end, index.end]] if host and busy else busy
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b > a:
                index.share(a, b, gaps)
    starts = [e.start_ns for e in host] + [dev_start]
    ends = [e.end_ns for e in host] + [dev_end]
    if dev_end > dev_start:
        out.span_s = (max(ends) - min(starts)) / 1e9
    ndev = len(out.devices)
    out.busy_s = busy_total / ndev / 1e9
    out.device_ops = [[n, s / 1e9] for n, s in sorted(
        by_name.items(), key=lambda kv: -kv[1])[:10]]
    out.idle_gaps = [[n, s / ndev / 1e9] for n, s in sorted(
        gaps.items(), key=lambda kv: -kv[1])[:10]]
    out.modules = {n: (s / 1e9, c) for n, (s, c) in modules.items()}
    return out


class _HostIndex:
    """The harness's annotations, sorted, for overlap queries.  They come
    from one thread, one after the other, so sorting by start also sorts
    by end."""

    def __init__(self, host: Sequence[Event]):
        self.events = sorted(host, key=lambda e: e.start_ns)
        self.ends = [e.end_ns for e in self.events]
        self.start = self.events[0].start_ns if host else 0.0
        self.end = max(self.ends) if host else 0.0

    def share(self, a: float, b: float, into: dict) -> None:
        """Add the idle gap ``[a, b)`` to ``into``, split over the
        annotations it lies under; what lies under none is
        ``unattributed``."""
        left = b - a
        for i in range(bisect.bisect_right(self.ends, a), len(self.events)):
            e = self.events[i]
            if e.start_ns >= b:
                break
            o = min(b, e.end_ns) - max(a, e.start_ns)
            if o > 0:
                into[e.name] += o
                left -= o
        if left > 0:
            into["unattributed"] += left


def describe(events: Sequence[Event], top: int = 12) -> str:
    """What a trace holds: planes, lines, and the heaviest names of each
    line — for reading one trace by hand."""
    lines: dict = defaultdict(list)
    for e in events:
        lines[(e.plane, e.line)].append(e)
    rows = []
    for (plane, line), evs in sorted(lines.items()):
        total = sum(e.dur_ns for e in evs) / 1e9
        rows.append(f"{plane} | {line}: {len(evs)} events, {total:.4f}s")
        names: dict = defaultdict(lambda: [0.0, 0])
        for e in evs:
            names[e.name][0] += e.dur_ns
            names[e.name][1] += 1
        for name, (s, c) in sorted(names.items(),
                                   key=lambda kv: -kv[1][0])[:top]:
            rows.append(f"    {s / 1e9:.6f}s x{c}  {name[:100]}")
    return "\n".join(rows)


if __name__ == "__main__":
    evs = load_xplane(sys.argv[1])
    print(describe(evs))
    s = reduce_trace(evs)
    print(f"devices {s.devices} busy {s.busy_s:.6f}s span {s.span_s:.6f}s "
          f"ops {s.device_ops[:5]} gaps {s.idle_gaps[:5]}")
