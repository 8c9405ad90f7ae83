"""Median ``proposal.verify``: a follower's pre-prepare in hand to all
its envelopes judged (one batch submission to the coalescer)."""

from chipbench.account import median_ms


def read(run):
    return median_ms(run, "waits", "proposal.verify")
