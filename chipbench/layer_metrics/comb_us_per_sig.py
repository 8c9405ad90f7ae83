"""Device microseconds of the comb kernel per signature verified, from the
profiler trace: the summed device durations of the launches whose XLA
module name holds ``comb`` (``jit_ecdsa_verify_comb``), over the
signatures the engine verified while the trace ran."""

KERNEL = "comb"


def read(run):
    if run.trace is None or not run.trace_verify:
        return None
    sigs = run.trace_verify["sigs_verified"]
    seconds, launches = run.trace.kernel_seconds(KERNEL)
    if not sigs or not launches:
        return None
    return 1e6 * seconds / sigs
