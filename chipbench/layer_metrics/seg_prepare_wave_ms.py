"""Median, over the account's decisions, of the time from the proposing
replica's ``batch.propose`` to its prepare quorum (``quorum.prepare``), on
its own ``perf_counter`` timeline; exact, from the raw values."""

from chipbench.account import median_ms


def read(run):
    return median_ms(run, "segments", "prepare_wave")
