"""The pad ladders of a deployment behind ONE shared verify engine.

A ladder is the set of lane counts the engine pads a launch to; every
rung is one compiled program (``PERF.md``: ~11 s warm and ~21 s cold per
comb rung on a v5e, ~63 s cold for the arbitrary-key Pallas kernel), so a
ladder is as short as the traffic allows.  Two waves shape it:

* the QUORUM wave — every replica checks its quorum of votes, all by the
  orderers' ring, on the static-key comb kernel: :func:`auto_pad_sizes`;
* the REQUEST wave — a block's client envelopes, signed by keys outside
  the ring, on the arbitrary-key kernel: :func:`request_pad_sizes`.

The two kernels keep a ladder each (``JaxVerifyEngine(pad_sizes=...,
request_pad_sizes=...)``): a shared one would compile every vote rung for
the arbitrary-key kernel and every request rung for the comb kernel, and
neither ever launches there.
"""

from __future__ import annotations

__all__ = ["auto_pad_sizes", "request_pad_sizes"]

#: lanes of one Mosaic block: the Pallas kernels' batch tile
BLOCK = 128
#: the engine's widest launch
TOP = 16384


def _round_up(n: int, block: int) -> int:
    return min(-(-n // block) * block, TOP)


def request_pad_sizes(request_wave: int) -> tuple:
    """The arbitrary-key kernel's ladder for blocks of ``request_wave``
    envelopes (``Configuration.request_batch_max_count``): ONE rung, which
    holds a whole block (500 -> 512): the front door's burst after a
    commit, and the followers' copies of a proposal after dedupe.  A rung
    beneath it was compiled and never launched under load (every flush of
    `fabric4.saturated` rode the block's rung, 98 % full, PERF.md PR 28);
    a lone envelope pays 8 ms of device time on the wide rung instead of 2,
    inside a 5 ms coalescing window, and set-up pays ~63 s less cold."""
    if request_wave <= 0:
        return ()
    return (_round_up(request_wave, BLOCK),)


def auto_pad_sizes(n: int, scheme_name: str = "p256",
                   pipeline: int = 1) -> tuple:
    """The pad ladder for an n-replica cluster behind ONE shared engine:
    one decision's quorum wave coalesces into ONE launch with near-full
    lanes, and the coalescer's max_batch trigger fires the moment the wave
    completes instead of waiting the window out."""
    import inspect

    from .provider import JaxVerifyEngine

    quorum = (n + (n - 1) // 3 + 1 + 1) // 2  # util.go:176-180
    # the shared engine's per-decision wave: every replica checks its
    # quorum; BLS collapses each check to ONE aggregated pairing lane
    wave = n if scheme_name == "bls" else n * (quorum - 1)
    # top rung = the wave rounded up to a 128-lane Mosaic block (n=64:
    # 2688 exactly — the power-of-two ladder padded it to 4096, wasting
    # ~34% of every launch); smaller rungs come from the production
    # engine's default ladder so bench shapes match deployed shapes
    block = 8 if scheme_name == "bls" else BLOCK
    top = _round_up(wave, block)
    defaults = inspect.signature(JaxVerifyEngine).parameters[
        "pad_sizes"].default
    rungs = {s for s in defaults if s < top} | {top}
    if pipeline > 1:
        # deduped steady-state launch for a full window train: one
        # distinct signature per replica per decision, and under the
        # launch shadow up to 2k decisions' waves can sit in one
        # coalesced flush -> k*n and 2k*n lanes
        rungs |= {_round_up(k * n, block)
                  for k in (pipeline, 2 * pipeline)}
    return tuple(sorted(rungs))
