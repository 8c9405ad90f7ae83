"""Find the knee of one open-loop workload: one process, one set-up, one
fresh cluster per offered rate.

    python3 -m chipbench.sweep --workload <cell> --rates 500,1000,2000 --seconds 10

Prints one JSON row per rate (offered, goodput, shed share, p50/p95 from
the due time, generator lateness) and then the knee by
:func:`chipbench.load.find_knee` (goodput >= 0.9 x offered, shed < 1 %).
A paced cell then runs at four fifths of the knee, an overload cell above
it.  Rehearsed on the CPU only so far; its first use on the chip belongs
to the PR that adds ``default4.paced``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import shutil
import sys
import tempfile

from . import deploy, gates, stats
from .load import find_knee
from .run import measure, prewarm, say


def row_of(rate: float, ev: dict) -> dict:
    loop = ev["loop"]
    t0, t1 = loop.window
    lats = [c[1] for c in loop.window_commits()]
    return {
        "offered_per_s": rate,
        "goodput_per_s": len(lats) / (t1 - t0),
        "attempted": loop.attempted,
        "shed_share": loop.failed_submits / loop.attempted
        if loop.attempted else 0.0,
        "errored": loop.errored,
        "never_committed": loop.never_committed(),
        "p50_ms": 1e3 * stats.percentile(lats, 50) if lats else None,
        "p95_ms": 1e3 * stats.percentile(lats, 95) if lats else None,
        "lateness_p95_ms": 1e3 * stats.percentile(loop.lateness, 95)
        if loop.lateness else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    args.trace, args.trace_dir = 0, None

    _bench, cell, config, workload = deploy.load_cell(args.workload)
    device = gates.stamp_device(cell["chips"], args.allow_cpu)

    from smartbft_tpu.utils.jaxenv import enable_compile_cache

    enable_compile_cache()
    log = gates.CompileLog()
    engine, ladder = deploy.build_engine(config)
    faults = prewarm(engine, ladder, deploy.get_scheme(config["scheme"]),
                     deploy.ring_keys(config), args.seed, config, log)
    if faults:
        raise SystemExit(f"chipbench.sweep: {faults}")
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        spec = dict(workload, loop="open", rate_per_s=rate)
        spec.setdefault("client_skew", 1.1)
        tmp = tempfile.mkdtemp(prefix="chipbench-sweep-")
        try:
            ev = asyncio.run(measure(args, config, spec, engine, log, tmp))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        rows.append(row_of(rate, ev))
        say(json.dumps(rows[-1]))
    print(json.dumps({"device": device, "knee": find_knee(rows)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
