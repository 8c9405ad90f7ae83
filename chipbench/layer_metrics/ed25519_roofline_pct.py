"""Ed25519's arbitrary-key kernel as a share of its roofline: its work
counted from its shapes (``chipbench/ed25519_work.py``: the MXU flops of
the one-hot table selects, the HBM bytes of operands, table and mask)
over the lanes and launches the program's account gives it in the traced
interval, divided by the kernel's traced device time, against the chip's
published bf16 and HBM peaks (``chipbench/peaks.json``); the larger
share.  A floor: the VPU integer work that binds the kernel has no
published peak."""

from chipbench import ed25519_work, peaks
from chipbench.account import account


def read(run):
    acc = account(run)
    per = ((acc or {}).get("lanes") or {}).get("pallas")
    if run.trace is None or not per or not per["launched"]:
        return None
    seconds = ed25519_work.device_seconds(run.trace)
    if not seconds:
        return None
    import jax

    try:
        peak = peaks.peaks_for(jax.devices()[0].device_kind)
    except KeyError:  # not a chip with published peaks: the CPU
        return None
    flops = ed25519_work.mxu_flops(per["launched"]) / seconds
    moved = ed25519_work.hbm_bytes(per["launched"], per["launches"]) / seconds
    return 100.0 * max(flops / peak["bf16_flops_per_s"],
                       moved / peak["hbm_bytes_per_s"])
