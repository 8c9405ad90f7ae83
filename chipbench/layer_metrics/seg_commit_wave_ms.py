"""Median, over the account's decisions, of the time from the proposing
replica's durable commit record to its commit quorum
(``quorum.commit``): the commit votes' round and their verification."""

from chipbench.account import median_ms


def read(run):
    return median_ms(run, "segments", "commit_wave")
