"""Median, over the requests the account saw proposed and delivered, of
the time from a request's ``req.submit`` to the ``batch.propose`` of its
batch at the proposing replica: queueing in the pool and the batcher, not
protocol."""

from chipbench.account import median_ms


def read(run):
    return median_ms(run, "waits", "pool.wait")
