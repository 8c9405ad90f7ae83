"""Milliseconds a verify round spends handing its launch between the loop
and the launch's thread (PR 38): the median ``verify.handin`` (the loop's
hand-in -> the engine call's first line on the launch thread) plus the
median ``verify.handback`` (the call's return -> the awaiting coroutine
resumed on the loop).  A program without the two waits (before PR 38)
reports nothing."""

from chipbench.account import median_ms


def read(run):
    handin = median_ms(run, "waits", "verify.handin")
    handback = median_ms(run, "waits", "verify.handback")
    if handin is None or handback is None:
        return None
    return handin + handback
