"""Median, over the account's decisions, of the time from the proposing
replica's prepare quorum to its commit record being durable
(``wal.persist``: sign, append, the shared fsync wave awaited)."""

from chipbench.account import median_ms


def read(run):
    return median_ms(run, "segments", "wal_persist")
