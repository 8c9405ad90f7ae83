"""The table of device peaks, keyed by ``device_kind`` as JAX reports it.
A device that is not in the table is an error, not a default.  Nothing
reads it for a roofline share yet: that needs an operation count of the
comb kernel from its shapes, which nothing has today (PERF.md, Open
questions)."""

from __future__ import annotations

import json
import os


def peaks_for(device_kind: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    try:
        return dict(table["devices"][device_kind], source=table["source"])
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"chipbench/peaks.json (has {sorted(table['devices'])})"
        ) from None
