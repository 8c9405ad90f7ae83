"""The account of one on-interval: where the host's time went.

:func:`assemble_account` folds the recorders the profiler switched on
into ONE plain dict — what :func:`smartbft_tpu.obs.last_summary` returns
and every ``chipbench/layer_metrics`` reader of this account imports.
Pure function over recorders' events and the running busy sums; touches
no JAX.

The interval is the switch's own: from the tick that first saw the
profiler on to the last tick that still saw it on (``stop_trace`` blocks
the loop thread while it writes, so the tick that sees it off comes
late).  Busy spans that ended after that last tick are taken back out of
the running sums, so the sums, the loop thread's CPU and the counts all
cover the same span of time.

=====================  ====================================================
``interval``           ``t0``, ``t1`` (perf_counter), ``wall_s``, ``ticks``
``loop``               the loop thread: ``thread``, ``cpu_s`` (``sys_s`` of it
                       in the kernel), ``busy_self_s``
``busy``               thread -> kind -> ``calls``, ``self_s``, ``dur_s``,
                       ``cpu_s`` (thread CPU, where the span reads it);
                       kind ``gc`` is the interpreter's collections
``collector``          ``passes``: ``count`` and ``seconds`` of the
                       collections by generation (0, 1, 2; 2 is a full
                       pass), ``frozen`` (``gc.get_freeze_count()``) and
                       ``thresholds`` in force at the off edge
``counters``           ``decisions`` (delivered by the replica that
                       proposed them), ``requests_proposed``, ``launches``,
                       ``signatures``, ``fsync_waves``, ``handovers``
                       (requests a replica forwarded as it handed the lead
                       over, ``req.handover``), ``not_leader_forwards``
                       (forwards dropped where they came: that replica did
                       not lead, ``req.not_leader``)
``lanes``              kernel -> ``launches``, ``launched`` (lanes, padding
                       included) and ``used`` (``verify.lanes`` marks)
``mesh``               the launches laid out over a device mesh (the
                       ``verify.lanes`` marks that carry ``per_device``):
                       ``launches``, ``spanning`` (of them, those that used
                       every device), ``used`` and ``launched`` lanes, and
                       the same two ``by_device``; zeros and empty lists
                       where no launch was
``rejected``           cause -> client envelopes refused (``req.rejected``)
``channels``           colocated groups behind ONE verify plane.
                       ``per_channel``: the group's tag (a replica's
                       recorder is ``s<tag>n<i>``, its submits to the
                       coalescer carry the tag) -> ``decisions`` and
                       ``requests`` (delivered by the replica that
                       proposed them), ``verify_wait_ms`` (the median
                       ``verify.wait`` of its replicas; None where none)
                       over ``verify_waits``, ``rejected`` by cause.  The
                       shared plane, from the ``verify.lanes`` marks that
                       carry their submitters' tags: ``launches`` (waves
                       of the coalescer), ``mixed_launches`` (of them,
                       those that carried two or more groups' items),
                       ``kernels``: kernel -> ``launches`` and ``used``
                       lanes of those waves.  Empty where no recorder
                       was a group's
``segments``           segment -> ms per decision (:func:`decision_rows`)
``decisions``          the rows themselves: ``view``, ``seq``, ``node``,
                       ``total_ms`` and one ms value per segment
``waits``              wait kind -> ms per wait (``request.verify``: the
                       front door's enqueue -> verdict of one envelope;
                       ``proposal.verify``: a follower's pre-prepare in
                       hand -> all its envelopes judged); ``pool.wait`` and
                       ``req.total`` per request delivered by its proposer
``durations``          ``wal.fsync`` -> ms per fsync (a busy span on the
                       executor thread that ran the wave)
``recorders``, ``recorded``, ``dropped``
``refused``            kind -> busy spans dropped for containing an await
=====================  ====================================================
"""

from __future__ import annotations

import statistics
from typing import Optional, Sequence

from .critpath import DECISION_SEGMENTS, decision_rows

__all__ = ["assemble_account"]

#: wait kinds recorded as such, taken as they are
_WAIT_KINDS = ("verify.wait", "verify.hold", "verify.window", "wal.persist",
               "request.verify", "proposal.verify")


def _fold_mesh_launch(mesh: dict, mark: dict) -> None:
    """One mesh launch's ``verify.lanes`` mark into the ``mesh`` block."""
    used = mark["per_device"]
    if len(mesh["used_by_device"]) < len(used):
        grow = len(used) - len(mesh["used_by_device"])
        mesh["used_by_device"] += [0] * grow
        mesh["launched_by_device"] += [0] * grow
    each = mark["lanes"] // len(used)
    for d, n in enumerate(used):
        mesh["used_by_device"][d] += n
        mesh["launched_by_device"][d] += each
    mesh["launches"] += 1
    mesh["spanning"] += min(used) > 0
    mesh["used"] += mark["used"]
    mesh["launched"] += mark["lanes"]


def _group_of(node: str) -> Optional[str]:
    """The group tag in a replica recorder's label ``s<tag>n<i>`` (or
    ``s<tag>g<gen>n<i>``), None for any other label."""
    if not node.startswith("s"):
        return None
    digits = node[1:].split("n", 1)[0].split("g", 1)[0]
    return digits if digits.isdigit() else None


def _channels_block(per_channel: dict, waits_of: dict, launches: dict,
                    kernels: dict) -> dict:
    """``per_channel``: tag -> running counts; ``waits_of``: tag -> its
    ``verify.wait`` values; ``launches``: wave id -> its tags."""
    if not per_channel:
        return {}
    for tag, ch in per_channel.items():
        waits = waits_of.get(tag, ())
        ch["verify_waits"] = len(waits)
        ch["verify_wait_ms"] = statistics.median(waits) if waits else None
    return {
        "per_channel": dict(sorted(per_channel.items())),
        "launches": len(launches),
        "mixed_launches": sum(len(t) >= 2 for t in launches.values()),
        "kernels": kernels,
    }


def assemble_account(recorders: Sequence, busy: dict, *, t0: float,
                     t1: float, loop_cpu_s: float, loop_thread: str,
                     loop_sys_s: float = 0.0, ticks: int = 0,
                     refused: Optional[dict] = None,
                     collections: Sequence = (), frozen: int = 0,
                     thresholds: Sequence = ()) -> dict:
    """See the module docstring.  ``busy``: thread -> kind -> ``[calls,
    self_s, dur_s, cpu_s]``, the running sums at the off edge;
    ``collections``: ``(thread, end, seconds, generation)`` per garbage
    collection while on; ``frozen`` and ``thresholds``: the collector's
    freeze count and thresholds at the off edge."""
    events = [e for r in recorders for e in r.events()]
    busy = {th: {k: list(v) for k, v in kinds.items()}
            for th, kinds in busy.items()}
    inside = []
    for e in events:
        if e.t <= t1:
            inside.append(e)
        elif e.self_s >= 0.0:
            acc = busy.get(e.thread, {}).get(e.kind)
            if acc is not None:
                acc[0] -= 1
                acc[1] -= e.self_s
                acc[2] -= e.dur
                acc[3] -= ((e.extra or {}).get("cpu_ms", 0.0)) / 1e3
    passes = [{"count": 0, "seconds": 0.0} for _ in range(3)]
    for thread, t_end, dur, gen in collections:
        if t_end <= t1:  # garbage collections: busy time of kind ``gc``
            acc = busy.setdefault(thread, {}).setdefault(
                "gc", [0, 0.0, 0.0, 0.0])
            acc[0] += 1
            acc[1] += dur
            acc[2] += dur
            passes[gen]["count"] += 1
            passes[gen]["seconds"] += dur
    rows = decision_rows(inside)
    proposed = {(r["node"], r["view"], r["seq"]): r["t"] for r in rows}
    counters = {"decisions": 0, "requests_proposed": 0, "launches": 0,
                "signatures": 0, "fsync_waves": 0, "handovers": 0,
                "not_leader_forwards": 0}
    waits: dict = {k: [] for k in _WAIT_KINDS}
    lanes: dict = {}
    mesh = {"launches": 0, "spanning": 0, "used": 0, "launched": 0,
            "used_by_device": [], "launched_by_device": []}
    rejected: dict = {}
    submits: dict = {}
    delivered = []
    fsync_ms: list = []
    per_channel: dict = {}
    channel_waits: dict = {}
    tagged_launches: dict = {}
    tagged_kernels: dict = {}

    def channel(tag: str) -> dict:
        ch = per_channel.get(tag)
        if ch is None:
            ch = per_channel[tag] = {"decisions": 0, "requests": 0,
                                     "rejected": {}}
        return ch

    for r in recorders:  # a group is there even where it did nothing
        tag = _group_of(getattr(r, "node", ""))
        if tag is not None:
            channel(tag)
    for e in inside:
        kind = e.kind
        if kind == "decision.deliver":
            if (e.extra or {}).get("proposer"):
                counters["decisions"] += 1
                tag = _group_of(e.node)
                if tag is not None:
                    ch = channel(tag)
                    ch["decisions"] += 1
                    ch["requests"] += e.extra.get("count", 0)
        elif kind == "batch.propose":
            counters["requests_proposed"] += (e.extra or {}).get("count", 0)
        elif kind == "verify.device":
            counters["launches"] += 1
        elif kind == "vote.sign":
            counters["signatures"] += 1
        elif kind == "wal.fsync":
            counters["fsync_waves"] += 1
            fsync_ms.append(e.dur * 1e3)
        elif kind == "req.submit":
            submits.setdefault((e.node, e.key), e.t)
        elif kind == "req.deliver":
            delivered.append(e)
        elif kind == "verify.lanes":
            x = e.extra
            per = lanes.setdefault(
                x["kernel"], {"launches": 0, "launched": 0, "used": 0})
            per["launches"] += 1
            per["launched"] += x["lanes"]
            per["used"] += x["used"]
            if "per_device" in x:
                _fold_mesh_launch(mesh, x)
            if "tags" in x:
                tagged_launches[e.launch] = x["tags"]
                per = tagged_kernels.setdefault(
                    x["kernel"], {"launches": 0, "used": 0})
                per["launches"] += 1
                per["used"] += x["used"]
        elif kind == "req.rejected":
            cause = (e.extra or {}).get("cause", "?")
            rejected[cause] = rejected.get(cause, 0) + 1
            tag = _group_of(e.node)
            if tag is not None:
                by = channel(tag)["rejected"]
                by[cause] = by.get(cause, 0) + 1
        elif kind == "verify.wait" and e.dur >= 0.0:
            channel_waits.setdefault((e.extra or {}).get("tag"),
                                     []).append(e.dur * 1e3)
        elif kind == "req.handover":
            counters["handovers"] += 1
        elif kind == "req.not_leader":
            counters["not_leader_forwards"] += 1
        if kind in waits and e.dur >= 0.0:
            waits[kind].append(e.dur * 1e3)
    pool_wait, total = [], []
    for e in delivered:  # the proposing replica's only
        t_submit = submits.get((e.node, e.key))
        t_propose = proposed.get((e.node, e.view, e.seq))
        if t_submit is None or t_propose is None:
            continue
        pool_wait.append((t_propose - t_submit) * 1e3)
        total.append((e.t - t_submit) * 1e3)
    waits["pool.wait"] = pool_wait
    waits["req.total"] = total
    return {
        "interval": {"t0": t0, "t1": t1, "wall_s": t1 - t0, "ticks": ticks},
        "loop": {
            "thread": loop_thread,
            "cpu_s": loop_cpu_s,
            "sys_s": loop_sys_s,
            "busy_self_s": sum(v[1] for v in
                               busy.get(loop_thread, {}).values()),
        },
        "busy": {th: {k: {"calls": v[0], "self_s": v[1], "dur_s": v[2],
                          "cpu_s": v[3]}
                      for k, v in sorted(kinds.items())}
                 for th, kinds in sorted(busy.items())},
        "collector": {"passes": passes, "frozen": frozen,
                      "thresholds": list(thresholds)},
        "counters": counters,
        "lanes": lanes,
        "mesh": mesh,
        "rejected": rejected,
        "channels": _channels_block(per_channel, channel_waits,
                                    tagged_launches, tagged_kernels),
        "segments": {seg: [r[seg] for r in rows]
                     for seg in DECISION_SEGMENTS},
        "decisions": rows,
        "waits": waits,
        "durations": {"wal.fsync": fsync_ms},
        "recorders": len(recorders),
        "recorded": sum(r.recorded for r in recorders),
        "dropped": sum(r.dropped for r in recorders),
        "refused": dict(refused or {}),
    }
