"""Test-only: the Ed25519 fabric deployment with one thing broken
underneath its reference, by ``"fault"``: a front door that verifies
nothing, or an engine that takes a scalar S >= L as if it were reduced."""

from __future__ import annotations

from chipbench import deploy

fabric_ed25519 = deploy.load_deployment("fabric_ed25519")

CONFIG_KEYS = fabric_ed25519.CONFIG_KEYS | {"fault"}
WORKLOAD_KEYS = fabric_ed25519.WORKLOAD_KEYS


class _Engine:
    """The OpenSSL engine with its verdicts bent by ``fault``."""

    def __init__(self, inner, fault: str):
        self._inner = inner
        self._fault = fault
        self.scheme, self.stats = inner.scheme, inner.stats
        self.preferred_coalesce_window = inner.preferred_coalesce_window

    def verify(self, items) -> list:
        if self._fault == "door_open":
            self._inner.verify(items)  # counted as launched, then ignored
            return [True] * len(items)
        reduced = []
        for msg, sig, pub in items:  # "s_unreduced": S taken mod L
            s = int.from_bytes(sig[32:], "little") % fabric_ed25519.L
            reduced.append((msg, sig[:32] + s.to_bytes(32, "little"), pub))
        return self._inner.verify(reduced)


class Deployment(fabric_ed25519.Deployment):

    def build_engine(self):
        engine, ladder = super().build_engine()
        return _Engine(engine, self.config["fault"]), ladder
