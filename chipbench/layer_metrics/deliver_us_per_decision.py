"""Host microseconds of delivery per decision: self time of the
``deliver`` busy spans (controller deliver + the application's deliver +
pool removal), summed over ALL replicas, over the account's decisions."""

from chipbench.account import per_decision_us


def read(run):
    return per_decision_us(run, ("deliver",))
