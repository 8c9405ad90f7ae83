"""Median ``wal.fsync``: one WAL's fsync inside a group-commit wave, on
the executor thread that ran it."""

from chipbench.account import median_ms


def read(run):
    return median_ms(run, "durations", "wal.fsync")
