"""Host milliseconds per verify launch: ``VerifyStats.total_kernel_seconds``
over launches.  Despite its name in the program that counter is the HOST
clock around the whole engine call (hash, pack, transfer, kernel,
readback), so it is reported as host time; the kernel's device time is
``comb_us_per_sig``."""


def read(run):
    if not run.verify or not run.verify["launches"]:
        return None
    return 1e3 * run.verify["host_seconds"] / run.verify["launches"]
