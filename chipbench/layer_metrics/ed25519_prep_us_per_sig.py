"""Host microseconds per lane of Ed25519's arbitrary-key marshalling, from
the program's account: the self time of its ``verify.prep`` spans (the
SHA-512 binding hash, the vectorised length and s < L checks, the
packing, on the launch's thread) over the lanes they prepared."""

from chipbench.account import account


def read(run):
    prep = (account(run) or {}).get("prep")
    if not prep or not prep["lanes"]:
        return None
    return 1e6 * prep["self_s"] / prep["lanes"]
