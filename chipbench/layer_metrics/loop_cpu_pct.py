"""Share of the account's on-interval in which the event loop's thread
was on a CPU: its ``time.thread_time()`` delta over the interval's wall
seconds, both read at the recorder's on and off edges.  Near 100: the one
thread that runs every replica is saturated."""

from chipbench.account import account


def read(run):
    acc = account(run)
    if not acc or not acc.get("interval", {}).get("wall_s"):
        return None
    return 100.0 * acc["loop"]["cpu_s"] / acc["interval"]["wall_s"]
