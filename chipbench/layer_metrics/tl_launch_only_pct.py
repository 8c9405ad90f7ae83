"""Share of the account's interval in which a verify launch was out and
the loop thread was inside no handle: the loop had nothing to run and
waited for the launch, which is the convoy's cost:
``timeline.launch_only_s`` over ``interval.wall_s``.  The four ``tl_*``
shares sum to 100."""

from chipbench.loop_account import share_of_wall_pct


def read(run):
    return share_of_wall_pct(run, "timeline", "launch_only_s")
