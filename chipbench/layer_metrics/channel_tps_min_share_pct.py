"""The least-served channel's committed requests over the mean channel's,
from the account's ``channels`` block (requests delivered by the replica
that proposed them, per channel, over the traced interval): 100 where the
channels are served alike, low where one starves."""

from chipbench.account import account


def read(run):
    per = ((account(run) or {}).get("channels") or {}).get("per_channel")
    if not per:
        return None
    served = [c["requests"] for c in per.values()]
    if not sum(served):
        return None
    return 100.0 * min(served) * len(served) / sum(served)
