"""Share of the loop thread's CPU spent in the interpreter's garbage
collections over the account's on-interval: self time of the ``gc`` busy
spans on the loop thread (each collection is taken out of the span it
interrupted) over that thread's CPU.  A full collection of a 64-replica
heap takes hundreds of milliseconds and stalls every replica at once."""

from chipbench.account import account


def read(run):
    acc = account(run)
    if not acc or not acc.get("loop", {}).get("cpu_s"):
        return None
    loop = acc["busy"].get(acc["loop"]["thread"], {})
    return 100.0 * loop.get("gc", {}).get("self_s", 0.0) / acc["loop"]["cpu_s"]
