"""Test-only layer metric: harness polls per committed decision."""


def read(run):
    if not run.decisions:
        return None
    return run.polls / run.decisions
