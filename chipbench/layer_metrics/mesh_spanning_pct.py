"""Share of the mesh launches that USED every device (at least one real
lane on each; strided placement gives that to any wave of at least as many
signatures as devices), from the account's ``mesh`` block.  Every launch
is LAID OUT over every device whatever it holds: that is the deployment's
own gate (``deployments/mesh.py``), not this number."""

from chipbench.account import account


def read(run):
    mesh = (account(run) or {}).get("mesh")
    if not mesh or not mesh.get("launches"):
        return None
    return 100.0 * mesh["spanning"] / mesh["launches"]
