"""fsync calls per decision: ``wal.fsync`` spans (one per WAL per
group-commit wave, all replicas) over the account's decisions."""

from chipbench.account import account


def read(run):
    acc = account(run)
    c = (acc or {}).get("counters", {})
    if not c.get("decisions") or not c.get("fsync_waves"):
        return None
    return c["fsync_waves"] / c["decisions"]
