"""Device microseconds of Ed25519's arbitrary-key kernel per signature it
verified, from the profiler trace: the summed device durations of the
launches of the XLA module ``jit_ed25519_verify`` (by that name exactly,
``ed25519_work.MODULE``), over the lanes the program's account says the
arbitrary-key kernel used in the traced interval."""

from chipbench import ed25519_work
from chipbench.account import account


def read(run):
    acc = account(run)
    used = ((acc or {}).get("lanes") or {}).get("pallas", {}).get("used")
    if run.trace is None or not used:
        return None
    seconds = ed25519_work.device_seconds(run.trace)
    return 1e6 * seconds / used if seconds else None
