"""Median, over the account's decisions, of the time from the proposing
replica's commit quorum to its ``decision.deliver`` mark."""

from chipbench.account import median_ms


def read(run):
    return median_ms(run, "segments", "deliver")
