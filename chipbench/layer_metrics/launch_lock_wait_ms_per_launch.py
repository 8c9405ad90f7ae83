"""Milliseconds a launch that its thread was neither on a CPU nor waiting
for the kernel: the off-CPU seconds of ``verify.pack``, ``verify.place``
and ``verify.device`` (the account's ``launch`` block) minus the trace's
kernel seconds (``run.trace.busy_s``), over the account's launches,
floored at 0.  What is left is the wait for the interpreter lock (or for
a core).  In ``mesh16.saturated`` ``busy_s`` is the mean over the four
planes, which read alike."""

from chipbench.account import account

KINDS = ("verify.pack", "verify.place", "verify.device")


def read(run):
    acc = account(run)
    launch = (acc or {}).get("launch", {})
    if not launch.get("launches") or getattr(run, "trace", None) is None:
        return None
    off = sum(launch[k]["off_cpu_s"] for k in KINDS if k in launch)
    return max(0.0, 1e3 * (off - run.trace.busy_s) / launch["launches"])
