"""Lanes the arbitrary-key kernel used per decision, from the program's
account: about 1010 at blocks of 500 when the three followers' copies of
an envelope merge into one lane (dedupe), about 2010 when they do not."""

from chipbench.account import account


def read(run):
    acc = account(run) or {}
    per = (acc.get("lanes") or {}).get("pallas")
    decisions = acc.get("counters", {}).get("decisions")
    if not per or not decisions:
        return None
    return per["used"] / decisions
