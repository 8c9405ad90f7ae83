"""Host microseconds of protocol-plane work per decision: the program's
four disjoint ``ProtocolPlaneTimers`` (ingest, route, vote registration,
codec) summed over ALL replicas of the deployment, as a delta over the
window, divided by the decisions committed in it."""


def read(run):
    if not run.decisions or not run.plane:
        return None
    total = sum(run.plane[k] for k in
                ("ingest_us", "route_us", "vote_reg_us", "codec_us"))
    return total / run.decisions
