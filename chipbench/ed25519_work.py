"""The work of Ed25519's arbitrary-key kernel (``jit_ed25519_verify``),
counted from its shapes, for its roofline share.

Its shapes, written out again (``smartbft_tpu/crypto/pallas_ed25519.py``):
a lane is five (16,) uint32 limb operands (S, h, R, -A's x and y), one
uint32 host mask and one uint32 verdict; a grid step is ``TILE`` lanes;
B's comb table is one (96, 256) bf16 block, the same for every step.

* **MXU** — B's half selects its comb entry by a one-hot matmul each of
  the ``STRIDE`` comb steps: (96 x 256) @ (256 x lanes), 2 flops a
  multiply-add.  Padding lanes compute too, so lanes LAUNCHED count.
* **HBM** — each launch reads its lanes' operands and the table once and
  writes the mask.

What the kernel is bound by is the VPU's 32-bit integer work (the
Montgomery field arithmetic of ~3,100 multiplications a lane), and no
published peak of the v5e covers that unit: a share of the MXU or HBM
peak is a floor of how busy the chip is, not its ceiling.
"""

from __future__ import annotations

import re

#: the kernel's XLA module as the trace prints it, with or without its
#: ``(fingerprint)`` suffix (by that name exactly: ``jit_eddsa_verify_comb``
#: is the Ed25519 comb kernel's)
MODULE = re.compile(r"^jit_ed25519_verify(\(\d+\))?$")

NL = 16              # 16-bit limbs of a field element
ROWS, TSIZE = 96, 256  # B's comb table: split-byte (X, Y, T) rows x entries
STRIDE = 32          # comb steps, one one-hot select each
OPERANDS = 5         # S, h, R, -A.x, -A.y


def device_seconds(trace) -> float:
    """The kernel's summed device seconds in a reduced trace."""
    return sum(s for name, (s, _n) in trace.modules.items()
               if MODULE.match(name))


def mxu_flops(lanes: int) -> float:
    """One-hot select flops of ``lanes`` launched lanes."""
    return 2.0 * ROWS * TSIZE * STRIDE * lanes


def hbm_bytes(lanes: int, launches: int) -> float:
    """Bytes in and out of HBM: per lane the operands, the host mask and
    the verdict; per launch the comb table."""
    per_lane = OPERANDS * NL * 4 + 4 + 4
    return float(per_lane * lanes + 2 * ROWS * TSIZE * launches)
