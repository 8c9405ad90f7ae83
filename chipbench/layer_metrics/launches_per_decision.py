"""Verify launches per decision (``VerifyStats.launches`` delta over the
window / decisions): how well the coalescer merges the replicas' quorum
checks into shared launches."""


def read(run):
    if not run.decisions or not run.verify:
        return None
    return run.verify["launches"] / run.decisions
