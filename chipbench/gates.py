"""The gates that decide ``correct`` (copied from ``chip_smoke.py``'s,
which raise; these return reasons so the result line can say
``correct: false`` with the reason on the line before it)."""

from __future__ import annotations

import random


def stamp_device(chips: int, allow_cpu: bool = False) -> dict:
    """The device as JAX reports it — or exit non-zero with no result."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    on_tpu = dev["platform"] == "tpu"
    if (on_tpu and dev["count"] < chips) or not (on_tpu or allow_cpu):
        raise SystemExit(
            f"chipbench: needs {chips} TPU chip(s), JAX found {dev}")
    return dev


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend does not
    report it, as the CPU's does not)."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileLog:
    """Every XLA backend compile of this process, from jax's monitoring
    events: what compiled, for how long, and whether the persistent cache
    served it."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.events: list[tuple[str, float, bool]] = []
        self._hit = False
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._hit = True  # precedes its compile's duration event

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((kw.get("fun_name", "?"), secs, self._hit))
            self._hit = False

    def since(self, mark: int) -> str:
        evs = self.events[mark:]
        big = [f"{name} {secs:.1f}s cache={'hit' if hit else 'miss'}"
               for name, secs, hit in evs if secs >= 1.0 or hit]
        return (", ".join(big) or "nothing over 1 s") + \
            f" ({len(evs)} compile(s) in all)"


def make_wave(scheme, rng: random.Random, keys: list, n: int):
    """``n`` real signatures round-robined over ``keys`` (``(private,
    public)`` pairs, all of the cluster's own ring), about one lane in
    eleven corrupted four ways -> (items, expected verdicts).  Messages,
    signers and corrupted lanes come from ``rng``; the signatures' nonces
    are the signer's own."""
    bad = set(rng.sample(range(n), max(4, n // 11)))
    items, expect = [], []
    for i in range(n):
        sk, pub = keys[i % len(keys)]
        msg = rng.randbytes(48)
        sig = scheme.sign_raw(sk, msg)
        if i in bad:
            how = i % 4
            if how == 0:    # a bit of the first half (r)
                sig = bytes([sig[0] ^ 0x20]) + sig[1:]
            elif how == 1:  # a bit of the second half (s)
                sig = sig[:40] + bytes([sig[40] ^ 0x01]) + sig[41:]
            elif how == 2:  # another message
                msg = msg[:-1] + bytes([msg[-1] ^ 0xFF])
            else:           # another ring member's key
                pub = keys[(i + 1) % len(keys)][1]
        items.append(scheme.make_item(msg, sig, pub))
        expect.append(i not in bad)
    return items, expect


def window_faults(*, by_kernel: dict, expected_kernel: str, breaker: dict,
                  mesh: dict, compiles: list) -> list[str]:
    """Why the measured window was not served as the configuration says,
    or [].  ``by_kernel``: launches of the window per kernel (a delta of
    ``VerifyStats.launches_by_kernel``); ``breaker`` / ``mesh``: the
    coalescer's fault and mesh snapshots; ``compiles``: the CompileLog
    events between the window's first and last instant."""
    faults = []
    served = {k: v for k, v in by_kernel.items() if v}
    if not served:
        faults.append("no verify launch inside the window")
    elif set(served) != {expected_kernel}:
        faults.append(f"launches by kernel {served}, want all under "
                      f"{expected_kernel!r}")
    if breaker.get("open") or breaker.get("opens"):
        faults.append(f"the verify breaker opened ({breaker.get('opens')} "
                      "time(s))")
    if breaker.get("host_fallback_batches"):
        faults.append(f"{breaker['host_fallback_batches']} wave(s) fell "
                      "back to the host verifier")
    if breaker.get("launch_failures") or breaker.get("launch_timeouts"):
        faults.append(f"{breaker.get('launch_failures', 0)} launch "
                      f"failure(s), {breaker.get('launch_timeouts', 0)} "
                      "timeout(s)")
    if mesh.get("downgrades"):
        faults.append(f"{mesh['downgrades']} mesh downgrade(s)")
    if compiles:
        names = [name for name, _, _ in compiles[:4]]
        faults.append(f"{len(compiles)} XLA compile(s) inside the window: "
                      f"{names}")
    return faults
