"""Host milliseconds a mesh launch spends laying its lanes out: the
``verify.place`` busy span (scatter to the strided rows, then every input
handed to its device's shard) on the thread that runs the launch, over
the mesh launches of the account's ``mesh`` block.  ``verify.pack`` before
it (hashing, limbs) and ``verify.device`` after it (dispatch to the
gathered mask) keep their meaning, so the three add up to a mesh launch's
time on its thread."""

from chipbench.account import account, busy_self_s


def read(run):
    acc = account(run) or {}
    launches = (acc.get("mesh") or {}).get("launches")
    placed = any("verify.place" in kinds
                 for kinds in acc.get("busy", {}).values())
    if not launches or not placed:
        return None
    return 1e3 * busy_self_s(acc, ("verify.place",)) / launches
