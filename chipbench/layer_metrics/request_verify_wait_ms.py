"""Median ``request.verify``: the front door's enqueue of one client
envelope at the coalescer to its verdict, before the pool takes it."""

from chipbench.account import median_ms


def read(run):
    return median_ms(run, "waits", "request.verify")
