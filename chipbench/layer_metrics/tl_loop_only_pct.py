"""Share of the account's interval in which the loop thread was inside a
handle and no verify launch was out: ``timeline.loop_only_s`` over
``interval.wall_s``.  The four ``tl_*`` shares sum to 100."""

from chipbench.loop_account import share_of_wall_pct


def read(run):
    return share_of_wall_pct(run, "timeline", "loop_only_s")
