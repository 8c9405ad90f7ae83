"""Host milliseconds per launch around the device call: the
``verify.device`` busy span (dispatch to the mask read back), over the
account's launches.  Its thread-CPU beside it in the account says how
long the launch stood blocked."""

from chipbench.account import per_launch_ms


def read(run):
    return per_launch_ms(run, "verify.device")
