"""Share of the host's ``verify.device`` span that the kernel ran:
``run.trace.busy_s`` (the trace's device-busy seconds) over the span's
``dur_s`` in the account's ``launch`` block.  The span brackets every
kernel, so this joins the two clocks: well over 100 means their intervals
disagree (the trace runs a tick or two longer than the account's interval
at each end, as for ``comb_us_per_sig``).  In ``mesh16.saturated``
``busy_s`` is the mean over the four planes, which read alike."""

from chipbench.account import account


def read(run):
    acc = account(run)
    span = (acc or {}).get("launch", {}).get("verify.device", {})
    if not span.get("dur_s") or getattr(run, "trace", None) is None:
        return None
    return 100.0 * run.trace.busy_s / span["dur_s"]
