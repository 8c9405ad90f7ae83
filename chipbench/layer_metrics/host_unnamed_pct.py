"""Share of the loop thread's CPU that no program span names: 100 x (1 -
busy self time on the loop thread / its CPU), over the account's
on-interval.  Self time is wall time, so a span that stood blocked (a
lock, the interpreter lock) pulls this down, even below zero; the
harness's own generator and asyncio's bookkeeping push it up."""

from chipbench.account import account


def read(run):
    acc = account(run)
    if not acc or not acc.get("loop", {}).get("cpu_s"):
        return None
    return 100.0 * (1.0 - acc["loop"]["busy_self_s"] / acc["loop"]["cpu_s"])
