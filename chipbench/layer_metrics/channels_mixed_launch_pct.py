"""Share of the coalescer's launches whose lanes came from two or more
channels, from the ``channels`` block of the program's account (the
``verify.lanes`` marks that carry their submitters' tags): how far the
colocated channels share the verify plane's launches."""

from chipbench.account import account


def read(run):
    ch = (account(run) or {}).get("channels")
    if not ch or not ch.get("launches"):
        return None
    return 100.0 * ch["mixed_launches"] / ch["launches"]
