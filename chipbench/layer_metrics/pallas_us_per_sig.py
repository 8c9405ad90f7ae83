"""Device microseconds of the arbitrary-key Pallas kernel per signature
it verified, from the profiler trace: the summed device durations of the
launches of the XLA module ``jit_ecdsa_verify`` (by that name exactly:
``jit_ecdsa_verify_comb`` is the comb kernel's), over the lanes the
program's account says that kernel used in the traced interval."""

import re

from chipbench.account import account

#: the module's name as the trace prints it, with or without its
#: ``(fingerprint)`` suffix
MODULE = re.compile(r"^jit_ecdsa_verify(\(\d+\))?$")


def read(run):
    acc = account(run)
    used = ((acc or {}).get("lanes") or {}).get("pallas", {}).get("used")
    if run.trace is None or not used:
        return None
    seconds = sum(s for name, (s, _n) in run.trace.modules.items()
                  if MODULE.match(name))
    return 1e6 * seconds / used if seconds else None
