"""What the readers of the loop hook's blocks share (PR 37): a part of
the account's interval as a share of its wall seconds."""

from __future__ import annotations

from typing import Optional

from .account import account


def share_of_wall_pct(run, block: str, key: str) -> Optional[float]:
    """100 x ``account[block][key]`` / ``interval.wall_s``; None where
    the account lacks the block or the key (an earlier commit's, or a
    loop that was not hooked)."""
    acc = account(run)
    if not acc or key not in acc.get(block, {}) \
            or not acc.get("interval", {}).get("wall_s"):
        return None
    return 100.0 * acc[block][key] / acc["interval"]["wall_s"]
