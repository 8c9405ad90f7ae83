"""What the event loop itself costs, as a share of its thread's CPU: the
thread's CPU outside any run of back-to-back handles (``loop.outside_s``:
the blocking ``select`` call and ``_run_once`` around it) plus the wall
seconds between the handles of a run (``loop.between_s``: the loop's
pops, its zero-timeout ``select`` calls, the hook's own bookkeeping),
over ``loop.cpu_s``.  Where system calls are emulated a ``select`` costs
microseconds, so a loop that turns thousands of times a second for
little work reads high here."""

from chipbench.account import account


def read(run):
    acc = account(run)
    loop = (acc or {}).get("loop", {})
    if "outside_s" not in loop or not loop.get("cpu_s"):
        return None
    return 100.0 * (loop["outside_s"] + loop["between_s"]) / loop["cpu_s"]
