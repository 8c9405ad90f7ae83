"""Median ``verify.wait``: from a replica's enqueue of a quorum's
signatures at the coalescer to its verdict future resolved (window, hold,
launch and hand-back)."""

from chipbench.account import median_ms


def read(run):
    return median_ms(run, "waits", "verify.wait")
