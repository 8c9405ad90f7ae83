"""Host microseconds of view work per decision: self time of the
``view.run`` (the view tasks' steps between awaits), ``view.ingest`` (a
drained wave registered) and ``vote.sign`` busy spans, summed over ALL
replicas, over the decisions the account counted (delivered by the
replica that proposed them) in its own on-interval."""

from chipbench.account import per_decision_us


def read(run):
    return per_decision_us(run, ("view.run", "view.ingest", "vote.sign"))
