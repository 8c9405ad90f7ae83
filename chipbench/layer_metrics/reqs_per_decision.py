"""Requests per committed decision over the window (front door, pool,
batcher): how full the batcher cut its batches.  Counted by the harness
from the committed stream."""


def read(run):
    if not run.decisions:
        return None
    return run.requests / run.decisions
