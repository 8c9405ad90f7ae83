"""Run one cell of the benchmark once.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches JAX.  It stamps the device and
exits non-zero, printing no result, unless JAX finds a TPU with the chips
the cell asks for (``--allow-cpu`` is for rehearsals and tests only; the
result line then says ``cpu``).

Set-up: the engine, the cluster's own public keys registered, one seeded
wave with corrupted lanes checked lane by lane against OpenSSL, the
cell's own pad ladder prewarmed, the cluster started through the
program's front door, a warm-up of traffic.  Then the measured window of
``--seconds``, no further submit, drain, the checks that decide
``correct``, and the result as the last line of standard output.  With
``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics and the device's busy time from a
profiler trace of a few seconds inside the window.
"""

from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Optional  # noqa: E402

from . import deploy, gates, reference, stats  # noqa: E402
from .load import LoadLoop  # noqa: E402


def say(msg: str) -> None:
    print(msg, flush=True)


def process_age() -> float:
    """Seconds since this process started (from /proc), so that ``setup_s``
    counts the interpreter's own start-up too; 0 where /proc is missing."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


_AGE_AT_IMPORT = process_age()


@dataclass
class Run:
    """What one measured window held — the argument of every per-layer
    reader (``layer_metrics/<name>.py: read(run)``)."""

    #: (first instant, last instant) of the window on the host clock
    window: tuple = (0.0, 0.0)
    #: commit stamp of every request the run committed, window or not
    commit_stamps: list = field(default_factory=list)
    #: decisions / requests whose commit fell inside the window
    decisions: int = 0
    requests: int = 0
    #: latencies (seconds) of the requests committed inside the window
    latencies: list = field(default_factory=list)
    setup_s: float = 0.0
    #: ProtocolPlaneTimers delta over the window (disjoint host timers)
    plane: dict = field(default_factory=dict)
    #: VerifyStats delta over the window: launches, sigs_verified,
    #: slots_used, host_seconds (host clock around the engine call),
    #: by_kernel
    verify: dict = field(default_factory=dict)
    #: the reduced profiler trace (None in an untraced run)
    trace: Optional[object] = None
    #: VerifyStats delta over the traced span, and that span on the host
    #: clock
    trace_verify: dict = field(default_factory=dict)
    trace_window_s: float = 0.0
    config: dict = field(default_factory=dict)
    polls: int = 0

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


# -- end-to-end metrics: taken by the harness itself, host clock ------------

END_TO_END = {
    "throughput_tps": lambda r: stats.rate_in_window(r.commit_stamps,
                                                     *r.window),
    "commit_p50_ms": lambda r: 1e3 * stats.percentile(r.latencies, 50),
    "commit_p95_ms": lambda r: 1e3 * stats.percentile(r.latencies, 95),
    "setup_s": lambda r: r.setup_s,
}


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def read_layer_metric(name: str, run: Run) -> Optional[float]:
    """Load ``layer_metrics/<name>.py`` by file (a metric's name may hold
    dots and dashes) and call its ``read(run)``."""
    path = os.path.join(deploy.HERE, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_metric_" + "".join(
            c if c.isalnum() else "_" for c in name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    value = module.read(run)
    return None if value is None else float(value)


def collect_metrics(bench: dict, cell: str, run: Run, traced: bool) -> dict:
    out = {}
    if not traced:
        for m in bench["end_to_end"]:
            if applies(m, cell):
                out[m["name"]] = {"value": END_TO_END[m["name"]](run),
                                  "unit": m["unit"]}
        return out
    for m in bench["per_layer"]:
        if not applies(m, cell):
            continue
        value = read_layer_metric(m["name"], run)
        if value is not None:  # a reader that finds nothing returns nothing
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# -- evidence and judgement ----------------------------------------------------


def verify_snapshot(engine) -> dict:
    s = engine.stats
    return {"launches": s.launches, "sigs_verified": s.sigs_verified,
            "slots_used": s.slots_used,
            "host_seconds": s.total_kernel_seconds,
            "by_kernel": dict(s.launches_by_kernel)}


def verify_delta(a: dict, b: dict) -> dict:
    out = {k: b[k] - a[k] for k in a if k != "by_kernel"}
    out["by_kernel"] = {k: b["by_kernel"].get(k, 0) - a["by_kernel"].get(k, 0)
                        for k in b["by_kernel"]}
    return out


def judge(*, wave_faults: list, ledger_faults: list, window_faults: list,
          settled: bool, attempted: int,
          committed: int) -> tuple[bool, list]:
    """-> (correct, reasons).  ``correct`` is about the system's answers
    and the path that served them; requests that merely failed are counted
    in ``failed`` and do not by themselves make a run incorrect."""
    reasons = list(wave_faults) + list(window_faults) + list(ledger_faults)
    if not settled:
        reasons.append("replicas did not reach one height after the drain")
    if attempted <= 0:
        reasons.append("no request was submitted inside the window")
    if committed <= 0:
        reasons.append("nothing committed inside the window")
    return (not reasons), reasons


def emit(result: dict, reasons: list) -> None:
    """The reasons, then the result as the LAST line of stdout."""
    for r in reasons:
        say(f"chipbench: NOT CORRECT: {r}")
    print(json.dumps(result), flush=True)


# -- the run -------------------------------------------------------------------


def prewarm(engine, ladder, scheme, keys, seed: int, config: dict,
            log: gates.CompileLog) -> list:
    """Register the ring's keys, check one seeded wave against the plain
    reference, launch every rung of the ladder once -> wave faults."""
    if hasattr(engine, "prewarm_keys"):
        engine.prewarm_keys([pub for _, pub in keys])
    lanes = int(config.get("setup_wave_lanes", 512))
    if ladder:
        lanes = max(s for s in ladder if s <= max(lanes, min(ladder)))
    items, expect = gates.make_wave(scheme, random.Random(seed), keys, lanes)
    mark, t0 = len(log.events), time.perf_counter()
    got = list(engine.verify(items))
    secs = time.perf_counter() - t0
    faults = reference.mask_faults(got, reference.p256_verdicts(items),
                                   expect)
    say(f"chipbench: set-up wave, {lanes} lanes, {expect.count(False)} "
        f"corrupted: mask {'==' if not faults else '!='} OpenSSL; first "
        f"call {secs:.1f}s; compiled: {log.since(mark)}")
    good = items[expect.index(True)]
    for size in ladder:
        mark, t0 = len(log.events), time.perf_counter()
        engine.verify([good] * size)
        say(f"chipbench: prewarm rung {size} lanes x {len(keys)} keys: "
            f"{time.perf_counter() - t0:.1f}s; compiled: {log.since(mark)}")
    return faults


class Tracer:
    """The profiler over ``trace_s`` seconds inside the window, Python
    tracer off; stamps the VerifyStats at both ends.

    The profiler is started from a thread of its own at the window's first
    tick: on the chip its start has taken from 0.1 s to 8.5 s (PERF.md),
    and the generator must not stand still for that."""

    def __init__(self, engine, out_dir: str, trace_s: float):
        self.engine = engine
        self.dir = out_dir
        self.trace_s = trace_s
        self.thread: Optional[threading.Thread] = None
        self.before: Optional[dict] = None
        self.after: Optional[dict] = None
        self.asked = self.t0 = self.t1 = 0.0
        self.failed = False

    def _start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.before = verify_snapshot(self.engine)
        self.t0 = time.perf_counter()  # set last: tick() reads it

    def tick(self, now: float) -> None:
        if self.thread is None:
            self.asked = now
            self.thread = threading.Thread(target=self._start,
                                           name="chipbench-profiler")
            self.thread.start()
        elif self.t0 and now - self.t0 >= self.trace_s:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.thread is None or self.after is not None:
            return
        self.thread.join()
        if not self.t0:  # the profiler's thread raised; its traceback says why
            if not self.failed:
                say("chipbench: the profiler did not start; no trace")
            self.failed = True
            return
        self.t1 = time.perf_counter()
        self.after = verify_snapshot(self.engine)
        jax.profiler.stop_trace()
        say(f"chipbench: the profiler took {self.t0 - self.asked:.2f}s to "
            f"start, traced {self.t1 - self.t0:.2f}s, and took "
            f"{time.perf_counter() - self.t1:.2f}s to write the trace")

    def summary(self):
        from . import trace as tr

        files = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            return None
        return tr.reduce_trace(tr.load_xplane(sorted(files)[-1]))


async def measure(args, config: dict, workload: dict, engine,
                  log: gates.CompileLog, tmp: str):
    """Start the deployment, run the loop, gather the evidence."""
    from smartbft_tpu.metrics import ProtocolPlaneTimers
    from smartbft_tpu.utils.clock import WallClockDriver

    cluster = deploy.build_cluster(config, engine, os.path.join(tmp, "wal"))
    driver = WallClockDriver(cluster.scheduler,
                             tick_interval=config["scheduler_tick_s"])
    shard = cluster.shard_list[0]
    annotate = None
    tracer = None
    if args.trace:
        import jax

        annotate = jax.profiler.TraceAnnotation
        tracer = Tracer(engine, args.trace_dir or os.path.join(tmp, "trace"),
                        float(workload.get("trace_s", 3.0)))
    loop = LoadLoop(cluster, workload, args.seed, annotate=annotate)
    marks: dict = {}

    def snap() -> dict:
        return {"verify": verify_snapshot(engine),
                "plane": shard.plane.snapshot(),
                "compiles": len(log.events)}

    def on_close() -> None:
        marks["close"] = snap()
        if tracer is not None:
            tracer.stop()

    driver.start()
    try:
        await cluster.start()
        deadline = time.perf_counter() + 60.0
        while not shard.ready():
            if time.perf_counter() > deadline:
                raise SystemExit("chipbench: no leader after 60 s")
            await asyncio.sleep(0.01)
        await loop.run(float(workload.get("warmup_s", 2.0)), args.seconds,
                       on_open=lambda: marks.__setitem__("open", snap()),
                       on_close=on_close,
                       on_tick=tracer.tick if tracer else None)
        settled = await deploy.settle(cluster)
        mux_fault = []
        try:
            cluster.check_invariants()  # the program's own loud checks
        except Exception as e:  # noqa: BLE001 — reported, not raised
            mux_fault.append(f"the program's own invariant check: {e!r}")
        ledgers = deploy.ledgers(cluster)
        breaker = cluster.coalescer.fault_snapshot()
        mesh = cluster.coalescer.mesh_snapshot()
    finally:
        with contextlib.suppress(Exception):
            await cluster.stop()
        await driver.stop()
    plane = ProtocolPlaneTimers.delta(marks["open"]["plane"],
                                      marks["close"]["plane"])
    return {
        "loop": loop, "settled": settled, "ledgers": ledgers,
        "breaker": breaker, "mesh": mesh, "mux_fault": mux_fault,
        "plane": plane, "tracer": tracer,
        "verify": verify_delta(marks["open"]["verify"],
                               marks["close"]["verify"]),
        "compiles": log.events[marks["open"]["compiles"]:
                               marks["close"]["compiles"]],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsals and tests only: run without a TPU "
                         "(the result line then says cpu)")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler's files here (default: a "
                         "temporary directory, removed at exit)")
    args = ap.parse_args(argv)

    bench, cell, config, workload = deploy.load_cell(args.workload)
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    device = gates.stamp_device(cell["chips"], args.allow_cpu)

    import jax
    import jaxlib

    from smartbft_tpu import native
    from smartbft_tpu.utils.jaxenv import enable_compile_cache

    enable_compile_cache()
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — a version string for a report line
        libtpu = "?"
    say(f"chipbench: cell {cell['name']} seed {args.seed} seconds "
        f"{args.seconds:g} trace {args.trace}; device {device}; jax "
        f"{jax.__version__}, jaxlib {jaxlib.__version__}, libtpu {libtpu}; "
        f"compile cache at {jax.config.jax_compilation_cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')}); native framing "
        f"library in use: {native.using_native()}")
    log = gates.CompileLog()
    engine, ladder = deploy.build_engine(config)
    scheme = deploy.get_scheme(config["scheme"])
    keys = deploy.ring_keys(config)
    say(f"chipbench: {config['name']}: {config['replicas']} replicas, depth "
        f"{config['pipeline_depth']}, engine {config['engine']}, pad ladder "
        f"{ladder} (the program's), {len(keys)} ring keys")
    wave_faults = prewarm(engine, ladder, scheme, keys, args.seed, config, log)
    hits = sum(1 for _, _, hit in log.events if hit)
    say(f"chipbench: set-up compiled {len(log.events)} program(s), {hits} "
        f"served by the cache, at {time.perf_counter() - _T_IMPORT:.1f}s")

    tmp = tempfile.mkdtemp(prefix="chipbench-")
    say(f"chipbench: WALs under {tmp} ({deploy.filesystem_of(tmp)})")
    try:
        ev = asyncio.run(measure(args, config, workload, engine, log, tmp))
        loop: LoadLoop = ev["loop"]
        summary = ev["tracer"].summary() if ev["tracer"] else None
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    t0, t1 = loop.window
    commits = loop.window_commits()
    decisions = loop.window_decisions()
    run = Run(
        window=(t0, t1),
        commit_stamps=[c[0] for c in loop.commits],
        decisions=len(decisions),
        requests=len(commits), latencies=[c[1] for c in commits],
        setup_s=(t0 - _T_IMPORT) + _AGE_AT_IMPORT,
        plane=ev["plane"], verify=ev["verify"], trace=summary,
        config=config, polls=loop.polls,
    )
    tracer = ev["tracer"]
    if tracer is not None and tracer.after is not None:
        run.trace_verify = verify_delta(tracer.before, tracer.after)
        run.trace_window_s = tracer.t1 - tracer.t0

    ledger_faults = ev["mux_fault"] + reference.ledger_faults(
        ev["ledgers"], loop.committed_keys)
    measured_keys = [k for k, c in zip(loop.committed_keys, loop.commits)
                     if c[2]]
    failed = (loop.failed_submits + loop.never_committed()
              + reference.not_exactly_once(ev["ledgers"], measured_keys))
    window_faults = gates.window_faults(
        by_kernel=ev["verify"]["by_kernel"],
        expected_kernel=config["expected_kernel"], breaker=ev["breaker"],
        mesh=ev["mesh"], compiles=ev["compiles"])
    correct, reasons = judge(
        wave_faults=wave_faults, ledger_faults=ledger_faults,
        window_faults=window_faults, settled=ev["settled"],
        attempted=loop.attempted, committed=len(commits))

    n = len(run.latencies)
    late = ""
    if loop.lateness:
        late = (f"; generator lateness p50 "
                f"{1e3 * stats.percentile(loop.lateness, 50):.2f} ms, p95 "
                f"{1e3 * stats.percentile(loop.lateness, 95):.2f} ms, max "
                f"{1e3 * max(loop.lateness):.2f} ms")
    say(f"chipbench: window {run.window_s:.3f}s: {run.requests} requests in "
        f"{run.decisions} decisions committed; {n} latency samples, "
        f"{stats.samples_beyond(n, 95)} beyond the 95th percentile; "
        f"attempted {loop.attempted}, shed {loop.shed}, errored "
        f"{loop.errored} {loop.error_samples}, never committed "
        f"{loop.never_committed()}, peak in flight {loop.peak_inflight}, "
        f"{loop.polls} polls{late}")
    v = ev["verify"]
    say(f"chipbench: verify plane in the window: {v['launches']} launches "
        f"{ {k: c for k, c in v['by_kernel'].items() if c} }, "
        f"{v['sigs_verified']} signatures in {v['slots_used']} lanes; "
        f"breaker {ev['breaker']}; compiles in the window: "
        f"{len(ev['compiles'])}; plane {ev['plane']}")

    dev = dict(device, memory_peak_bytes=gates.memory_peak_bytes())
    result = {"correct": correct, "attempted": loop.attempted,
              "failed": failed, "metrics": {}, "device": dev}
    if n:
        result["metrics"] = collect_metrics(bench, cell["name"], run,
                                            bool(args.trace))
    if args.trace:
        dev["window_s"] = run.trace_window_s
        dev["busy_s"] = summary.busy_s if summary else 0.0
        if summary is not None:
            say(f"chipbench: trace: devices {summary.devices}, "
                f"{summary.device_events} device events, busy "
                f"{summary.busy_s:.6f}s of {run.trace_window_s:.3f}s on the "
                f"host clock ({summary.span_s:.3f}s in the trace's own), "
                f"modules {sorted(summary.modules.items(), key=lambda kv: -kv[1][0])[:6]}")
            result["breakdown"] = {"device_ops": summary.device_ops,
                                   "idle_gaps": summary.idle_gaps}
    emit(result, reasons)
    return 0


if __name__ == "__main__":
    sys.exit(main())
