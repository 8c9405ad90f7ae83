"""Several Fabric BFT channels on one orderer host: one front door, one
coalescer, one chip.

Built on the Fabric channel (``deployments/fabric.py``): the same signed
envelopes, enrolled identities, forgeries and OpenSSL pass, on
``ShardedCluster(shards=<channels>, crypto="p256")``.  What this file adds
is what makes the shards CHANNELS, as a Fabric orderer process serves them
(one SmartBFT chain per channel, the envelope names its channel):

* **names** — ``channels``: one name per shard.  The replicas of shard
  ``k`` order for the ``k``-th name and refuse every envelope that does
  not name it (``ShardedCluster.enroll(<mapping>)``).
* **populations** — ``identities.enrolled`` lists each channel's OWN
  enrolled population.  A client's identity is its INDEX in the load
  loop's names (``c<seed>-<i>``): the first population's indexes are the
  first channel's clients, and so on.  The workload divides its
  ``clients`` over the channels by Zipf(``channel_skew``) (largest
  remainders); a division that is not the configuration's populations is
  refused, not run.
* **envelopes** — every envelope names its client's channel in a header
  inside the signed bytes; the front door reads it there.
* **a sixth forgery** — in its turn, beside fabric's five: an envelope
  that names ANOTHER channel, honestly signed by this client, whose
  identity is enrolled on its own channel only.
* **its reference** (:meth:`Deployment.reference_faults`) — fabric's
  OpenSSL pass, then the isolation rule read from the ledgers' raw bytes
  (an envelope is on the ledgers of the channel it names and of no other;
  its creator is enrolled THERE), and the verify plane's attribution:
  every engine call is read lane by lane (a vote by its signer's ring, an
  envelope by the channel it names), and a call that carried two
  channels' lanes has to be one the program counted as mixed.

It needs a program whose front door takes an addressed submit; one
without (any before PR 34) is refused at once, before JAX is touched.
"""

from __future__ import annotations

import bisect
import hashlib
import inspect
import time

from chipbench import deploy

fabric = deploy.load_deployment("fabric")
sharded = fabric.sharded

CONFIG_KEYS = fabric.CONFIG_KEYS | {"channels"}
WORKLOAD_KEYS = fabric.WORKLOAD_KEYS | {"channel_skew"}

refuse = sharded.refuse

#: the header that names a channel, at the start of an envelope's payload
#: (written out again: nothing of the program is imported to read one)
MAGIC = b"\x00tpubft.channel\x00"


def named_channel(signed: bytes):
    """The plain reading of the channel an envelope's signed bytes name,
    or None: u32 client, u32 request id, u32 payload; the payload starts
    with the magic, one length byte and the name."""
    at = 4 + int.from_bytes(signed[:4], "big")
    at += 8 + int.from_bytes(signed[at:at + 4], "big")
    head = at + len(MAGIC)
    if signed[at:head] != MAGIC or head >= len(signed):
        return None
    return signed[head + 1:head + 1 + signed[head]].decode(errors="replace")


def zipf_division(total: int, parts: int, skew: float) -> list:
    """``total`` clients over ``parts`` channels, channel ``k`` weighing
    ``1 / (k + 1) ** skew``, by largest remainders."""
    weights = [1.0 / (k + 1) ** skew for k in range(parts)]
    exact = [total * w / sum(weights) for w in weights]
    out = [int(x) for x in exact]
    by_remainder = sorted(range(parts), key=lambda k: exact[k] - out[k],
                          reverse=True)
    for k in by_remainder[:total - sum(out)]:
        out[k] += 1
    return out


class Deployment(fabric.Deployment):

    #: one channel is fabric4 itself behind the addressed door (a control)
    SHARDS = (1, 2, 4)

    def __init__(self, config: dict, cell: dict, workload: dict):
        # check() runs first thing in the base class: what it reads of
        # this class is here before it
        self.populations = [int(n) for n in config["identities"]["enrolled"]]
        self.workload = workload
        # fabric's deployment takes the host's total
        super().__init__(
            dict(config, identities={"enrolled": sum(self.populations)}),
            cell, workload)
        self.names = [str(n) for n in config["channels"]]
        #: identity index -> channel: the first index of each population
        self._first = [sum(self.populations[:k])
                       for k in range(len(self.populations))]
        #: [key, raw, what came back: None = accepted] per sixth forgery
        self.crossed: list = []
        self._forges = 0
        #: honest envelopes turned away for their channel
        self.misplaced: list = []
        #: (instant, channels whose lanes the call carried, mixed waves
        #: the program counted since the call before) per engine call
        self.calls: list = []
        self._marks: list = []
        self._orderers: list = []

    def check(self) -> None:
        super().check()
        from smartbft_tpu.testing.sharded import ShardedCluster

        if "channel" not in inspect.signature(
                ShardedCluster.submit).parameters:
            refuse("this program's front door has no addressed submit "
                   "(ShardedCluster.submit(channel=)): it cannot serve "
                   "named channels")
        c, w = self.config, self.workload
        names = c["channels"]
        if len(names) != c["shards"] or len(self.populations) != c["shards"]:
            refuse(f"{c['shards']} shards need as many channels and "
                   f"enrolled populations, got {names} / {self.populations}")
        division = zipf_division(int(w["clients"]), c["shards"],
                                 float(w["channel_skew"]))
        if division != self.populations:
            refuse(f"{w['clients']} clients at channel_skew "
                   f"{w['channel_skew']} divide into {division}, the "
                   f"configuration enrols {self.populations}")

    # -- who is on which channel -------------------------------------------------

    def identity(self, client: str) -> int:
        return int(client.rsplit("-", 1)[1])

    def shard_of(self, identity: int) -> int:
        return bisect.bisect_right(self._first, identity) - 1

    def enrolled(self) -> dict:
        """channel name -> the public keys enrolled on it."""
        pubs = [pub for _, pub in self.clients()]
        return {name: pubs[first:first + n] for name, first, n in
                zip(self.names, self._first, self.populations)}

    def orderers(self) -> list:
        """Every channel's ring, as ShardedCluster derives them: the comb
        registry holds them all before the ladder is prewarmed."""
        from smartbft_tpu.crypto.provider import Keyring

        if not self._orderers:
            ids = list(range(1, self.config["replicas"] + 1))
            for s in range(self.config["shards"]):
                rings = Keyring.generate(ids, seed=b"shard-%d" % s,
                                         scheme=self.scheme())
                self._orderers += [
                    (rings[i].private_key, rings[i].public_keys[i])
                    for i in ids]
        return self._orderers

    # -- build ---------------------------------------------------------------------

    def build(self, engine, wal_root: str) -> None:
        sharded.Deployment.build(self, engine, wal_root)
        self.cluster.enroll(self.enrolled())
        self.presign()
        self.watch(engine)

    def watch(self, engine) -> None:
        """Read every engine call from here on, lane by lane, beside what
        the program's always-on attribution says of it."""
        per = self.config["replicas"]
        ring_of = {pub: k // per
                   for k, (_, pub) in enumerate(self.orderers())}
        shard_named = {name: s for s, name in enumerate(self.names)}
        attribution = self.coalescer.shard_stats
        inner, calls = engine.verify, self.calls
        seen = [attribution.mixed_waves]

        def verify(items):
            carried = set()
            for item in items:
                s = ring_of.get(item[3])
                carried.add(shard_named.get(named_channel(item[0]))
                            if s is None else s)
            mixed = attribution.mixed_waves
            calls.append((time.perf_counter(), len(carried),
                          mixed - seen[0]))
            seen[0] = mixed
            return inner(items)

        engine.verify = verify

    def plane_snapshot(self) -> dict:
        self._marks.append(time.perf_counter())
        return super().plane_snapshot()

    # -- the front door --------------------------------------------------------------

    def envelope(self, signer, client: str, rid: str, channel=None) -> bytes:
        from smartbft_tpu.crypto.envelope import sign_envelope

        payload = hashlib.shake_256(f"{client}:{rid}".encode()).digest(
            self.payload_bytes)
        if channel is None:
            channel = self.names[self.shard_of(self.identity(client))]
        return sign_envelope(*signer, client, rid, payload, channel=channel)

    async def submit(self, client: str, rid: str) -> None:
        try:
            await super().submit(client, rid)
        except Exception as e:  # noqa: BLE001 — kept, then the loop's to count
            if getattr(e, "cause", None) in ("wrong_channel",
                                             "unknown_channel"):
                self.misplaced.append((f"{client}:{rid}", repr(e)))
            raise

    async def forge(self, client: str, i: int) -> None:
        """Fabric's five corruptions in turn, and in its turn the sixth."""
        self._forges += 1
        if self._forges % 6 or len(self.names) < 2:
            return await super().forge(client, i)
        rid = f"x{len(self.crossed)}"
        other = self.names[(self.shard_of(i) + 1) % len(self.names)]
        raw = self.envelope(self._clients[i], client, rid, channel=other)
        entry = [f"{client}:{rid}", raw, "pending"]
        self.crossed.append(entry)
        try:
            await self.cluster.submit(client, rid, envelope=raw)
            entry[2] = None
        except Exception as e:  # noqa: BLE001 — what came back is the record
            entry[2] = f"{type(e).__name__}: {e}"

    # -- the reference ---------------------------------------------------------------

    def reference_faults(self, ev: dict) -> list:
        """Fabric's reasons, then this deployment's (never one fewer)."""
        faults = super().reference_faults(ev)
        t0 = time.perf_counter()

        if self.misplaced:
            faults.append(
                f"{len(self.misplaced)} honest envelope(s) were turned away "
                f"for their channel: the front door did not place them by "
                f"the channel they name, first {self.misplaced[0]}")

        # the sixth forgery: refused, and on no ledger
        let_in = [x for x in self.crossed if x[2] is None]
        if let_in:
            faults.append(
                f"{len(let_in)} envelope(s) of an identity enrolled on "
                f"another channel only were ACCEPTED at the front door, "
                f"first {let_in[0][0]}")
        unanswered = [x for x in self.crossed if x[2] == "pending"]
        if unanswered:
            faults.append(f"{len(unanswered)} such envelope(s) got no answer")

        # isolation, from the ledgers' raw bytes
        creators = [{pub[0].to_bytes(32, "big") + pub[1].to_bytes(32, "big")
                     for pub in pubs} for pubs in self.enrolled().values()]
        judged: dict = {}  # (shard, block bytes) -> (keys, foreign, strangers)
        held: list = [set() for _ in self.names]
        foreign = strangers = 0
        first_foreign = first_stranger = None
        for (shard, replica), block, raws in self.ledger_envelopes():
            got = judged.get((shard, block))
            if got is None:
                keys, f, s = [], 0, 0
                for raw in raws:
                    parsed = fabric.parse_envelope(raw)
                    if parsed is None:
                        f += 1
                        continue
                    keys.append(parsed[0])
                    if named_channel(parsed[1]) != self.names[shard]:
                        f += 1
                    elif raw[-132:-68] not in creators[shard]:
                        s += 1
                got = judged[(shard, block)] = (keys, f, s)
                held[shard].update(keys)
            if got[1] and first_foreign is None:
                first_foreign = (shard, replica)
            if got[2] and first_stranger is None:
                first_stranger = (shard, replica)
            foreign += got[1]
            strangers += got[2]
        if foreign:
            faults.append(
                f"{foreign} committed envelope(s) name another channel "
                f"than the one that ordered them (or none), first on the "
                f"ledger of {first_foreign}")
        if strangers:
            faults.append(
                f"{strangers} committed envelope(s) whose creator is not "
                f"enrolled on the channel that ordered them, first on the "
                f"ledger of {first_stranger}")
        twice = set()
        for a in range(len(held)):
            for b in range(a + 1, len(held)):
                twice |= held[a] & held[b]
        if twice:
            faults.append(f"{len(twice)} envelope(s) are on the ledgers of "
                          f"two channels, first {sorted(twice)[:3]}")

        # the verify plane's attribution, call by call over the window
        lo, hi = self._marks[:2] if len(self._marks) >= 2 else (0.0, 0.0)
        window = [c for c in self.calls if lo <= c[0] < hi]
        mixed = [c for c in window if c[1] >= 2]
        uncounted = [c for c in mixed if c[2] < 1]
        if uncounted:
            faults.append(
                f"{len(uncounted)} of the {len(mixed)} launch(es) of the "
                f"window that carried two channels' lanes were not counted "
                f"as mixed by the program")
        by_channel = [len(h) for h in held]
        print(f"chipbench: channels: {dict(zip(self.names, by_channel))} "
              f"envelopes ordered by channel; {len(self.crossed)} envelopes "
              f"named another channel than their identity's, "
              f"{len(self.crossed) - len(let_in) - len(unanswered)} "
              f"refused; {len(window)} launches in the window, "
              f"{len(mixed)} carried two or more channels' lanes, the "
              f"program counted {sum(c[2] for c in window)} mixed; "
              f"isolation read in {time.perf_counter() - t0:.1f}s",
              flush=True)
        return faults
