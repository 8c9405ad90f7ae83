"""Host milliseconds per launch before the device call: the
``verify.pack`` busy span (register, hash, pack, pad) on the thread that
runs the launch, over the account's launches."""

from chipbench.account import per_launch_ms


def read(run):
    return per_launch_ms(run, "verify.pack")
