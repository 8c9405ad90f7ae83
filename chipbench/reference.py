"""The plain reference: the same questions answered without the program.

* Signature verdicts: ECDSA P-256 over SHA-256 through the ``cryptography``
  wheel (OpenSSL), one signature at a time, from the raw ``(message, r, s,
  public point)`` of each lane.  The set-up wave's device mask must equal
  these verdicts lane by lane, and these must equal the lanes the harness
  corrupted.
* Ledger semantics: total order identical on all replicas, every
  committed request on every ledger exactly once — decided from the
  ledgers' raw contents, not from the program's own invariant checks.
"""

from __future__ import annotations

from collections import Counter
from typing import Sequence

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import (
    encode_dss_signature,
)


def p256_verdicts(items: Sequence[tuple]) -> list[bool]:
    """``items``: ``(message bytes, r, s, (x, y))`` per lane."""
    out = []
    for msg, r, s, pub in items:
        try:
            key = ec.EllipticCurvePublicNumbers(
                pub[0], pub[1], ec.SECP256R1()).public_key()
            key.verify(encode_dss_signature(r, s), msg,
                       ec.ECDSA(hashes.SHA256()))
            out.append(True)
        except (InvalidSignature, ValueError):
            out.append(False)
    return out


def mask_faults(got: Sequence, ref: Sequence[bool],
                expect: Sequence[bool]) -> list[str]:
    """Why the device mask is wrong, or [] if it is right."""
    got = [bool(v) for v in got]
    faults = []
    if list(ref) != list(expect):
        faults.append("set-up wave: the OpenSSL reference disagrees with "
                      "the lanes the harness corrupted")
    wrong = [i for i, (g, r) in enumerate(zip(got, ref)) if g != r]
    if wrong or len(got) != len(ref):
        faults.append(
            f"set-up wave: {len(wrong)} lane(s) differ from OpenSSL, first "
            f"{wrong[:8]} (got {len(got)} lanes of {len(ref)})")
    return faults


def ledger_faults(ledgers: dict, committed_keys: Sequence[str]) -> list[str]:
    """``ledgers``: replica id -> its committed request keys in ledger
    order.  ``committed_keys``: what the front door's committed stream
    showed the load generator.  -> why the guarantees do not hold, or []."""
    faults = []
    ids = sorted(ledgers)
    first = ledgers[ids[0]]
    for i in ids[1:]:
        if ledgers[i] != first:
            m = min(len(first), len(ledgers[i]))
            at = next((k for k in range(m) if first[k] != ledgers[i][k]), m)
            faults.append(
                f"ledger of replica {i} differs from replica {ids[0]}'s at "
                f"position {at} (lengths {len(ledgers[i])} / {len(first)})")
            if len(faults) >= 3:
                break
    counts = Counter(first)
    twice = [k for k, c in counts.items() if c > 1]
    if twice:
        faults.append(f"{len(twice)} request(s) committed more than once, "
                      f"first {twice[:3]}")
    missing = [k for k in committed_keys if k not in counts]
    if missing:
        faults.append(f"{len(missing)} request(s) seen on the committed "
                      f"stream are on no ledger, first {missing[:3]}")
    return faults


def not_exactly_once(ledgers: dict, keys: Sequence[str]) -> int:
    """How many of ``keys`` are NOT on every ledger exactly once."""
    ids = sorted(ledgers)
    first = ledgers[ids[0]]
    distinct = [first] + [ledgers[i] for i in ids[1:] if ledgers[i] != first]
    counters = [Counter(led) for led in distinct]
    return sum(1 for k in keys if any(c[k] != 1 for c in counters))
