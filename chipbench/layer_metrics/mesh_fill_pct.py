"""Lanes the mesh launches used over the lanes they launched (padding
included), from the program's account of the traced interval (its ``mesh``
block, fed by the ``verify.lanes`` marks of the mesh engine): how well
the mesh's ladder, whose smallest comb rung is devices x 128 lanes, fits
the coalesced vote waves."""

from chipbench.account import account


def read(run):
    mesh = (account(run) or {}).get("mesh")
    if not mesh or not mesh.get("launched"):
        return None
    return 100.0 * mesh["used"] / mesh["launched"]
