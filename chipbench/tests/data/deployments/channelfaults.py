"""Test-only: the channels deployment with one thing broken underneath its
reference, by ``"fault"``, to show each reason this deployment adds to
``reference_faults`` ending a run ``correct: false``."""

from __future__ import annotations

from chipbench import deploy

channels = deploy.load_deployment("channels")

CONFIG_KEYS = channels.CONFIG_KEYS | {"fault"}
WORKLOAD_KEYS = channels.WORKLOAD_KEYS


class Deployment(channels.Deployment):

    @property
    def fault(self):
        return self.config.get("fault")

    def build(self, engine, wal_root: str) -> None:
        super().build(engine, wal_root)
        if self.fault in ("one_enrolled_set", "door_and_replicas_blind"):
            # one set, handed to every channel (what enroll did before
            # channels had names)
            everyone = [pub for _, pub in self.clients()]
            self.cluster.enroll(dict.fromkeys(self.names, everyone))
        if self.fault in ("door_hashes_client", "door_and_replicas_blind"):
            # a front door that ignores the envelope's channel and hashes
            # the client, as it did before channels had names
            placed = self.cluster.set.submit

            async def submit(client, rid, payload=b"", *, envelope=None,
                             channel=None):
                return await placed(client, envelope,
                                    request_key=f"{client}:{rid}")

            self.cluster.submit = submit
        if self.fault == "door_and_replicas_blind":
            # and replicas that do not look at the name either
            for sh in self.cluster.shard_list:
                for app in sh.apps:
                    app.envelopes.channel = None
        if self.fault == "mixed_not_counted":
            stats = self.coalescer.shard_stats
            note = stats.note_wave

            def note_wave(futures):
                before = stats.mixed_waves
                note(futures)
                stats.mixed_waves = before

            stats.note_wave = note_wave

    async def settle(self, timeout: float = 60.0) -> bool:
        ok = await super().settle(timeout)
        if self.fault == "ledger_copied":
            # the first block of channel 0 turns up at the end of every
            # ledger of channel 1 as well
            first, second = self.cluster.shard_list[:2]
            block = next(d for d in first.apps[0].ledger()
                         if d.proposal.payload)
            for app in second.apps:
                app.shared.ledgers[app.id].append(block)
        return ok
