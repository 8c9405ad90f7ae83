"""Share of the traced span in which no operation ran on the device:
1 - (union of the device's operation intervals) / (traced span on the host
clock), averaged over the chips used."""


def read(run):
    if run.trace is None or not run.trace.devices or not run.trace_window_s:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace_window_s)
