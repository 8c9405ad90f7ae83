"""Host microseconds of WAL appends per decision: self time of the
``wal.append`` busy spans (loop thread: encode, frame, write; the fsync
is the wave's), summed over ALL replicas, over the account's decisions."""

from chipbench.account import per_decision_us


def read(run):
    return per_decision_us(run, ("wal.append",))
