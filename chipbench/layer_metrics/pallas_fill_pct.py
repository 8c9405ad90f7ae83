"""Lanes the arbitrary-key kernel used over the lanes it launched
(padding included), from the program's account of the traced interval:
how well the request ladder fits the envelope waves."""

from chipbench.account import account


def read(run):
    per = ((account(run) or {}).get("lanes") or {}).get("pallas")
    if not per or not per["launched"]:
        return None
    return 100.0 * per["used"] / per["launched"]
