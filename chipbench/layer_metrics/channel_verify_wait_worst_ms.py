"""The largest, over the channels, of the median ``verify.wait`` of a
channel's replicas (enqueue at the shared coalescer -> verdict), from the
account's ``channels`` block: the channel the shared plane serves worst."""

from chipbench.account import account


def read(run):
    per = ((account(run) or {}).get("channels") or {}).get("per_channel")
    waits = [c["verify_wait_ms"] for c in (per or {}).values()
             if c.get("verify_wait_ms") is not None]
    return max(waits) if waits else None
