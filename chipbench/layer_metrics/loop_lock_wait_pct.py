"""Share of the account's interval in which the loop thread was inside a
handle and NOT on a CPU: ``loop.lock_wait_s`` (wall minus thread-CPU of
every handle the loop ran) over ``interval.wall_s``.  The loop stood
behind the interpreter lock, or the OS took its core."""

from chipbench.loop_account import share_of_wall_pct


def read(run):
    return share_of_wall_pct(run, "loop", "lock_wait_s")
