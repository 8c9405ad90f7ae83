"""The program's own account of the traced span, for the per-layer
readers that divide it.

While a profiler session runs, the program's flight recorder is on
(``smartbft_tpu/obs/recorder.py``: it follows the session by itself) and
keeps, on ``time.perf_counter()``: busy self time by kind and thread, the
loop thread's CPU, its own counts taken at the same sites, per-decision
segments and wait spans.  ``smartbft_tpu.obs.last_summary()`` is that
account for the last interval the profiler was on.  Every reader here
divides by the account's OWN counts over its OWN interval, never by the
harness's window.

A program without the account (an earlier commit) has no such function:
:func:`account` then returns None, each reader returns None, and the
metric is left out of the line.
"""

from __future__ import annotations

from typing import Optional, Sequence

from . import stats


def account(run) -> Optional[dict]:
    """The account a reader reads: ``run.account`` where a test hands one
    in, else the program's last one; None where there is none."""
    given = getattr(run, "account", None)
    if given is not None:
        return given or None
    try:
        from smartbft_tpu import obs
    except ImportError:
        return None
    last = getattr(obs, "last_summary", None)
    return (last() or None) if last is not None else None


def busy_self_s(acc: dict, kinds: Sequence[str]) -> float:
    """Self seconds of ``kinds`` over every thread and replica."""
    return sum(v["self_s"] for per in acc.get("busy", {}).values()
               for k, v in per.items() if k in kinds)


def per_decision_us(run, kinds: Sequence[str]) -> Optional[float]:
    acc = account(run)
    if not acc or not acc.get("counters", {}).get("decisions"):
        return None
    return 1e6 * busy_self_s(acc, kinds) / acc["counters"]["decisions"]


def per_launch_ms(run, kind: str) -> Optional[float]:
    acc = account(run)
    if not acc or not acc.get("counters", {}).get("launches"):
        return None
    return 1e3 * busy_self_s(acc, (kind,)) / acc["counters"]["launches"]


def median_ms(run, group: str, name: str) -> Optional[float]:
    """Median of one of the account's raw value lists (``segments`` or
    ``waits``), in ms; None where the list is empty."""
    acc = account(run)
    values = (acc or {}).get(group, {}).get(name)
    if not values:
        return None
    return stats.percentile(values, 50)
