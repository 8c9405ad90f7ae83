"""Share of the account's interval in which the loop thread was inside a
handle AND the launch's thread inside a span of a verify launch: the two
ran side by side, or one of them stood behind the interpreter lock
(``loop_lock_wait_pct``, ``launch_lock_wait_ms_per_launch``):
``timeline.both_s`` over ``interval.wall_s``.  The four ``tl_*`` shares
sum to 100."""

from chipbench.loop_account import share_of_wall_pct


def read(run):
    return share_of_wall_pct(run, "timeline", "both_s")
