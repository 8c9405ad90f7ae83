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
                       in the kernel), ``busy_self_s``.  Where the loop
                       hook covered it: ``turns`` (handles the loop ran)
                       and ``steps_wall_s`` (wall inside them); ``runs``,
                       ``runs_wall_s``, ``steps_cpu_s``: count (of those
                       that ended: a loop that never idles is in ONE), wall
                       and thread-CPU of its runs of back-to-back handles (a
                       run from its first handle's start to its last's
                       end: the handles and the loop's pops between
                       them); ``lock_wait_s`` = ``runs_wall_s`` -
                       ``steps_cpu_s`` (inside a run and off the CPU:
                       behind the interpreter lock, or the OS took the
                       core); ``between_s`` = ``runs_wall_s`` -
                       ``steps_wall_s`` (inside a run and between its
                       handles: the loop's pops, its zero-timeout
                       ``select`` calls, the hook's own bookkeeping);
                       ``outside_s`` = ``cpu_s`` - ``steps_cpu_s`` (the
                       thread's CPU outside any run: the blocking
                       ``select`` and ``_run_once`` around it)
``loop_steps``         ``covered`` (False where the loop could not be
                       hooked, and then nothing else).  ``owners``: the 12
                       owners of handles with the most self time, each
                       ``name`` (a task's coroutine ``__qualname__``, a
                       callback's), ``kind``, ``calls``, ``self_s``;
                       ``other``: the rest summed; ``intervals`` and
                       ``busy_s``: count and seconds of the loop's merged
                       busy intervals inside the interval
``launch``             the thread(s) that ran verify launches: per kind
                       (``verify.pack`` / ``verify.place`` /
                       ``verify.device``) ``dur_s``, ``cpu_s``,
                       ``off_cpu_s`` = their difference (the kernel, or a
                       wait for the interpreter lock); ``launches``;
                       ``threads_started``: the resident launch threads
                       each coalescer had started by its last
                       ``verify.handin`` of the interval (their always-on
                       count, ``VerifyFaultStats.launch_threads_started``),
                       summed; absent where no hand-in was recorded
``timeline``           the interval split by two facts (:func:`assemble_timeline`):
                       ``both_s``, ``loop_only_s``, ``launch_only_s``,
                       ``neither_s`` (sum ``wall_s``), ``neither_fsync_s``;
                       only where the loop hook covered the loop
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
                       included) and ``used`` (``verify.lanes`` marks);
                       ``host_refused``: cause -> lanes of them the host
                       refused before the device, where a mark said
``prep``               the host marshalling of Ed25519's arbitrary-key
                       launches (``verify.prep`` spans that ended in the
                       interval): ``calls``, ``lanes`` prepared, ``self_s``
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
                       hand -> all its envelopes judged; ``verify.handin``:
                       a launch handed in on the loop -> its engine call
                       begun on the launch thread; ``verify.handback``:
                       that call returned -> its awaiter resumed on the
                       loop); ``pool.wait`` and
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

__all__ = ["assemble_account", "assemble_timeline"]

#: wait kinds recorded as such, taken as they are
_WAIT_KINDS = ("verify.wait", "verify.hold", "verify.window", "wal.persist",
               "request.verify", "proposal.verify", "verify.handin",
               "verify.handback")


#: busy spans of the thread that runs a verify launch
LAUNCH_KINDS = ("verify.pack", "verify.place", "verify.device")
#: owners the ``loop_steps`` block lists by name
TOP_OWNERS = 12


def _union(intervals, t0: float, t1: float) -> list:
    """``(start, end)`` intervals clipped to ``[t0, t1]`` -> disjoint,
    sorted."""
    merged: list = []
    for a, b in sorted((max(a, t0), min(b, t1)) for a, b in intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def _overlap_s(u: list, v: list) -> float:
    """Seconds in which two disjoint, sorted interval lists both hold."""
    total, i, j = 0.0, 0, 0
    while i < len(u) and j < len(v):
        total += max(0.0, min(u[i][1], v[j][1]) - max(u[i][0], v[j][0]))
        if u[i][1] < v[j][1]:
            i += 1
        else:
            j += 1
    return total


def assemble_timeline(loop: Sequence, launch: Sequence, fsync: Sequence,
                      *, t0: float, t1: float) -> dict:
    """Split ``[t0, t1]`` by two facts at every instant: the loop thread
    is inside a handle (``loop``) or not, the launch's thread is inside a
    span of a verify launch (``launch``) or not; ``fsync``: when an fsync
    wave was out.  Each a list of ``(start, end)`` on one clock, in any
    order, overlapping or not.  ``launch_only_s``: the loop had nothing to
    run while a launch was out; ``neither_s`` is the remainder, so the
    four sum to the wall."""
    wall = max(t1 - t0, 0.0)
    on_loop, on_launch = _union(loop, t0, t1), _union(launch, t0, t1)
    both = _overlap_s(on_loop, on_launch)
    loop_only = sum(b - a for a, b in on_loop) - both
    launch_only = sum(b - a for a, b in on_launch) - both
    waves = _union(fsync, t0, t1)
    either = _union([*on_loop, *on_launch], t0, t1)
    return {
        "both_s": both, "loop_only_s": loop_only,
        "launch_only_s": launch_only,
        "neither_s": wall - both - loop_only - launch_only,
        "neither_fsync_s": sum(b - a for a, b in waves)
        - _overlap_s(waves, either),
    }


def _loop_steps_block(steps: dict, handles: list) -> dict:
    """``handles``: the loop's busy intervals, merged and clipped."""
    ranked = sorted(steps["owners"].items(), key=lambda kv: -kv[1][1])
    rest = ranked[TOP_OWNERS:]
    return {
        "covered": True,
        "owners": [{"name": name, "kind": kind, "calls": v[0], "self_s": v[1]}
                   for (kind, name), v in ranked[:TOP_OWNERS]],
        "other": {"calls": sum(v[0] for _, v in rest),
                  "self_s": sum(v[1] for _, v in rest)},
        "intervals": len(handles),
        "busy_s": sum(b - a for a, b in handles),
    }


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
                     thresholds: Sequence = (),
                     loop_steps: Optional[dict] = None) -> dict:
    """See the module docstring.  ``busy``: thread -> kind -> ``[calls,
    self_s, dur_s, cpu_s]``, the running sums at the off edge;
    ``loop_steps``: the loop hook's own sums (``loophook.LoopHook.block``;
    its ``kinds`` are added to the loop thread's busy sums here), None
    where it declined;
    ``collections``: ``(thread, end, seconds, generation)`` per garbage
    collection while on; ``frozen`` and ``thresholds``: the collector's
    freeze count and thresholds at the off edge."""
    events: list = []
    started = []  # a coalescer's launch threads, by its last hand-in
    for r in recorders:
        mine = r.events()
        events += mine
        counts = [e.extra["threads_started"] for e in mine
                  if e.kind == "verify.handin" and e.t <= t1]
        if counts:
            started.append(max(counts))
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
    prep = {"calls": 0, "lanes": 0, "self_s": 0.0}
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
            if "refused" in x:
                by = per.setdefault("host_refused", {})
                for cause, n in x["refused"].items():
                    by[cause] = by.get(cause, 0) + n
            if "per_device" in x:
                _fold_mesh_launch(mesh, x)
            if "tags" in x:
                tagged_launches[e.launch] = x["tags"]
                per = tagged_kernels.setdefault(
                    x["kernel"], {"launches": 0, "used": 0})
                per["launches"] += 1
                per["used"] += x["used"]
        elif kind == "verify.prep" and e.self_s >= 0.0:
            prep["calls"] += 1
            prep["lanes"] += e.extra["lanes"]
            prep["self_s"] += e.self_s
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
    launch: dict = {"launches": counters["launches"]}
    if started:
        launch["threads_started"] = sum(started)
    for kind in LAUNCH_KINDS:
        sums = [v for per in busy.values() for k, v in per.items()
                if k == kind]
        if sums:
            dur, cpu = sum(v[2] for v in sums), sum(v[3] for v in sums)
            launch[kind] = {"dur_s": dur, "cpu_s": cpu,
                            "off_cpu_s": dur - cpu}
    loop = {"thread": loop_thread, "cpu_s": loop_cpu_s, "sys_s": loop_sys_s}
    hooked: dict = {"loop_steps": {"covered": False}}
    if loop_steps is not None:
        sums = busy.setdefault(loop_thread, {})
        for kind, add in loop_steps["kinds"].items():
            acc = sums.setdefault(kind, [0, 0.0, 0.0, 0.0])
            for i, v in enumerate(add):
                acc[i] += v
        loop.update(
            turns=loop_steps["turns"], steps_wall_s=loop_steps["wall_s"],
            runs=loop_steps["runs"], runs_wall_s=loop_steps["runs_wall_s"],
            steps_cpu_s=loop_steps["cpu_s"],
            lock_wait_s=loop_steps["runs_wall_s"] - loop_steps["cpu_s"],
            between_s=loop_steps["runs_wall_s"] - loop_steps["wall_s"],
            outside_s=loop_cpu_s - loop_steps["cpu_s"])
        flat = loop_steps["intervals"]
        handles = _union(zip(flat[::2], flat[1::2]), t0, t1)
        hooked["loop_steps"] = _loop_steps_block(loop_steps, handles)
        # spans that straddle an edge are clipped, not dropped
        hooked["timeline"] = assemble_timeline(
            handles,
            [(e.t - e.dur, e.t) for e in events
             if e.kind in LAUNCH_KINDS and e.self_s >= 0.0],
            [(e.t - e.dur, e.t) for e in events
             if e.kind == "wal.fsync" and e.self_s >= 0.0],
            t0=t0, t1=t1)
    loop["busy_self_s"] = sum(v[1] for v in busy.get(loop_thread, {}).values())
    return {
        "interval": {"t0": t0, "t1": t1, "wall_s": t1 - t0, "ticks": ticks},
        "loop": loop,
        **hooked,
        "launch": launch,
        "busy": {th: {k: {"calls": v[0], "self_s": v[1], "dur_s": v[2],
                          "cpu_s": v[3]}
                      for k, v in sorted(kinds.items())}
                 for th, kinds in sorted(busy.items())},
        "collector": {"passes": passes, "frozen": frozen,
                      "thresholds": list(thresholds)},
        "counters": counters,
        "lanes": lanes,
        "prep": prep,
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
