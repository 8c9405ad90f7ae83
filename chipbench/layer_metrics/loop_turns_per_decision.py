"""Handles the event loop ran per decision: the loop hook's ``loop.turns``
(every task step, callback and timer handle run on the loop thread while
the recorders were on) over the account's decisions.  Each turn costs the
loop its own bookkeeping whatever the handle does."""

from chipbench.account import account


def read(run):
    acc = account(run)
    if not acc or "turns" not in acc.get("loop", {}) \
            or not acc.get("counters", {}).get("decisions"):
        return None
    return acc["loop"]["turns"] / acc["counters"]["decisions"]
