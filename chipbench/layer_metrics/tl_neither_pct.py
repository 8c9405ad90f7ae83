"""Share of the account's interval in which the loop thread was inside no
handle and no verify launch was out: everything waited, for an fsync wave
(``timeline.neither_fsync_s`` of it), a timer or the selector:
``timeline.neither_s`` over ``interval.wall_s``.  The four ``tl_*`` shares
sum to 100."""

from chipbench.loop_account import share_of_wall_pct


def read(run):
    return share_of_wall_pct(run, "timeline", "neither_s")
