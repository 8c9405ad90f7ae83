"""Lanes the arbitrary-key kernel used per launch of it, over the tagged
launches of the account's ``channels`` block: how many envelopes (of
however many channels) one launch of that kernel carries."""

from chipbench.account import account


def read(run):
    per = (((account(run) or {}).get("channels") or {}).get("kernels")
           or {}).get("pallas")
    if not per or not per["launches"]:
        return None
    return per["used"] / per["launches"]
