"""Test-only: the fabric deployment with one thing broken underneath its
reference, by ``"fault"``, to show each of ``reference_faults``' reasons
ending a run ``correct: false``."""

from __future__ import annotations

import dataclasses

from chipbench import deploy

fabric = deploy.load_deployment("fabric")

CONFIG_KEYS = fabric.CONFIG_KEYS | {"fault"}
WORKLOAD_KEYS = fabric.WORKLOAD_KEYS


class Deployment(fabric.Deployment):

    @property
    def fault(self):
        return self.config.get("fault")

    async def forge(self, client: str, i: int) -> None:
        if self.fault != "forged_let_through":
            return await super().forge(client, i)
        # a "forgery" that is in fact honestly signed: the front door
        # takes it, it commits, and OpenSSL accepts it
        n = len(self.forged)
        rid = f"f{n}"
        raw = self.envelope(self._clients[i], client, rid)
        entry = [f"{client}:{rid}", raw, "none", "pending"]
        self.forged.append(entry)
        await self.cluster.submit(client, rid, envelope=raw)
        entry[3] = None

    def envelope(self, signer, client: str, rid: str) -> bytes:
        raw = super().envelope(signer, client, rid)
        if self.fault == "honest_refused" and rid == "r3":
            raw = fabric.flip(raw, len(raw) - 1, 0x01)  # spoilt in transit
        return raw

    def plane_snapshot(self) -> dict:
        out = super().plane_snapshot()
        if self.fault == "lanes_fewer":
            self._lane_marks[-1] = dict.fromkeys(self._lane_marks[-1], 0)
        return out

    async def settle(self, timeout: float = 60.0) -> bool:
        ok = await super().settle(timeout)
        if self.fault == "ledger_altered":
            # one envelope of replica 2's first block loses a payload bit
            from smartbft_tpu.codec import decode, encode
            from smartbft_tpu.testing.app import BatchPayload

            app = self.cluster.shard_list[0].apps[1]
            ledger = app.shared.ledgers[app.id]
            at = next(k for k, d in enumerate(ledger) if d.proposal.payload)
            batch = decode(BatchPayload, ledger[at].proposal.payload)
            reqs = list(batch.requests)
            reqs[0] = fabric.flip(reqs[0], len(reqs[0]) - 200, 0x10)
            ledger[at] = dataclasses.replace(
                ledger[at], proposal=dataclasses.replace(
                    ledger[at].proposal,
                    payload=encode(BatchPayload(requests=reqs))))
        return ok
