"""A Fabric BFT ordering channel: every client envelope is signed, and
every replica verifies it on the chip.

Built on the default deployment (``deployments/sharded.py``): the same
``ShardedCluster(shards=1, crypto="p256")`` behind one shared engine and
coalescer.  What this file adds is the request path of a Fabric channel:

* **identities** — ``identities.enrolled`` client keys, derived per
  identity index as the replicas' keys are (``p256.keygen`` of a fixed
  seed: the deployment is not handed ``--seed``; client names, request
  ids and so payloads carry it).  A client name takes the next free
  identity when it is first seen.  The replicas hold the enrolled set
  (``ShardedCluster(enrolled=...)``); it stands for the MSP's cached
  certificate validation.
* **envelopes** — ``submit(client, rid)`` submits the client's envelope
  (``envelope.payload_bytes`` of payload drawn from the client name and
  request id, the creator's public point, ``r || s`` by the native
  signer) through ``cluster.submit``.  Signing is the clients' work, not
  the orderers': on the harness's thread it took a fifth of the loop
  (PERF.md, PR 28), so each client's first ``presigned_per_client``
  envelopes are signed during set-up.  That needs the client names, which
  carry ``--seed``; the harness does not hand it over, so it is read from
  the command line, and a name or request id that was not foreseen is
  signed when it is submitted, timed and counted.
* **forgeries** — beside every ``forged_every``-th honest submit one
  FORGED envelope of the same client (a fresh request id; one of five
  corruptions in turn) goes through the same front door, outside the load
  loop's accounting; what came back is kept.
* **the engine** — the program's engine on the program's ladders, the
  orderers' ring pinned (ring keys ride the comb kernel, client keys the
  arbitrary-key kernel), both kernels' rungs compiled by the program's
  prewarm entry before the harness's own wave.
* **its reference** (:func:`reference_faults`) — OpenSSL on the raw bytes
  of every envelope, one at a time (``chipbench/reference.py``), held
  against what the system did with it.
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import time

from chipbench import deploy, reference

sharded = deploy.load_deployment("sharded")

CONFIG_KEYS = sharded.CONFIG_KEYS | {"envelope", "identities"}
WORKLOAD_KEYS = {"forged_every", "presigned_per_client"}

refuse = sharded.refuse

#: the trailer of an envelope: u32(64) creator(64) u32(64) signature(64)
_TRAILER = 136
#: the five corruptions, in turn
FORGERIES = ("bit_of_r", "bit_of_s", "byte_of_payload", "another_enrolled_key",
             "key_not_enrolled")
#: which kernel serves a key outside the orderers' ring, by engine kind
ENVELOPE_KERNEL = {"jax": "pallas", "openssl": "host"}


def parse_envelope(raw: bytes):
    """The plain reading of an envelope's bytes, written out again without
    the program -> ``(key "client:request", signed bytes, creator point,
    r, s)``, or None unless it is one."""
    cut = len(raw) - _TRAILER
    if cut < 12 or raw[cut:cut + 4] != b"\x00\x00\x00\x40" \
            or raw[cut + 68:cut + 72] != b"\x00\x00\x00\x40":
        return None
    n = int.from_bytes(raw[:4], "big")
    m = int.from_bytes(raw[4 + n:8 + n], "big")
    if 8 + n + m > cut:
        return None
    key = raw[4:4 + n].decode() + ":" + raw[8 + n:8 + n + m].decode()
    creator, sig = raw[cut + 4:cut + 68], raw[cut + 72:]
    pub = (int.from_bytes(creator[:32], "big"),
           int.from_bytes(creator[32:], "big"))
    return (key, raw[:cut], pub, int.from_bytes(sig[:32], "big"),
            int.from_bytes(sig[32:], "big"))


def plain_verdict(raw: bytes, enrolled: set) -> bool:
    """Is this envelope one the channel may order?  Its creator is
    enrolled and OpenSSL accepts the creator's signature over its bytes."""
    got = parse_envelope(raw)
    if got is None:
        return False
    _key, signed, pub, r, s = got
    return pub in enrolled and \
        reference.p256_verdicts([(signed, r, s, pub)])[0]


def client_names(workload: dict) -> list:
    """The closed loop's client names as ``load.LoadLoop`` makes them from
    ``--seed`` (read from the command line: see the module docstring), or
    [] where they cannot be foreseen."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--seed", type=int, default=None)
    seed = ap.parse_known_args()[0].seed
    if seed is None or workload.get("loop") != "closed":
        return []
    return [f"c{seed:x}-{i}" for i in range(int(workload["clients"]))]


def flip(raw: bytes, at: int, mask: int) -> bytes:
    return raw[:at] + bytes([raw[at] ^ mask]) + raw[at + 1:]


class Deployment(sharded.Deployment):

    def __init__(self, config: dict, cell: dict, workload: dict):
        super().__init__(config, cell, workload)
        self.payload_bytes = int(config["envelope"]["payload_bytes"])
        self.n_enrolled = int(config["identities"]["enrolled"])
        self.forged_every = int(workload.get("forged_every", 0))
        self.workload = workload
        #: (client, request id) -> the envelope signed during set-up
        self._presigned: dict = {}
        self.signed_inline = 0
        self._clients: list = []       # (private, public) per identity
        self._outsider = None          # a key that is NOT enrolled
        self._identity_of: dict = {}   # client name -> identity index
        #: "client:request" -> sha256 of the envelope as submitted
        self.submitted: dict = {}
        self.refused_honest: list = []  # (key, repr of what was raised)
        #: (key, raw, how, what came back: None = accepted)
        self.forged: list = []
        self._forging: set = set()
        self._honest = 0
        self.sign_s = 0.0
        #: used lanes by kernel at the window's two instants
        self._lane_marks: list = []

    def check(self) -> None:
        super().check()
        c = self.config
        if c["engine"] not in ENVELOPE_KERNEL:
            refuse(f"engine {c['engine']!r} is not implemented for signed "
                   "envelopes (jax; openssl for rehearsals)")
        if set(c["envelope"]) != {"payload_bytes"} \
                or set(c["identities"]) != {"enrolled"}:
            refuse("envelope / identities keys nothing reads")

    # -- keys ----------------------------------------------------------------

    def orderers(self) -> list:
        return super().keys()

    def clients(self) -> list:
        if not self._clients:
            p256 = self.scheme()
            self._clients = [p256.keygen(b"fabric-client-%d" % i)
                             for i in range(self.n_enrolled)]
            self._outsider = p256.keygen(b"fabric-not-enrolled")
        return self._clients

    def keys(self) -> list:
        """The orderers' keys, then the enrolled clients': the harness's
        set-up wave round-robins its lanes over them, so it crosses both
        kernels."""
        return self.orderers() + self.clients()

    # -- engine ----------------------------------------------------------------

    def build_engine(self):
        if self.config["engine"] != "jax":
            return super().build_engine()
        from smartbft_tpu.crypto.ladder import request_pad_sizes
        from smartbft_tpu.crypto.provider import (JaxVerifyEngine,
                                                  prewarm_verify_engine)

        votes = self.pad_ladder()
        requests = request_pad_sizes(
            self.config["configuration"]["request_batch_max_count"])
        engine = JaxVerifyEngine(
            pad_sizes=votes, scheme=self.scheme(),
            ring=[pub for _, pub in self.orderers()],
            request_pad_sizes=requests)
        t0 = time.perf_counter()
        prewarm_verify_engine(engine)
        print(f"chipbench: fabric: the program's prewarm compiled the comb "
              f"ladder {votes} and the arbitrary-key ladder {requests} in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
        # the harness warms each rung it is told with one valid item: told
        # both ladders, its set-up wave takes the widest rung at or under
        # setup_wave_lanes
        return engine, tuple(sorted(set(votes) | set(requests)))

    def build(self, engine, wal_root: str) -> None:
        super().build(engine, wal_root)
        self.cluster.enroll([pub for _, pub in self.clients()])
        self.presign()

    def presign(self) -> None:
        """The clients' signing, done before traffic: every foreseen
        client's first ``presigned_per_client`` envelopes."""
        ahead = int(self.workload.get("presigned_per_client", 0))
        names = client_names(self.workload)[:len(self.clients())]
        t0 = time.perf_counter()
        for name in names:
            signer = self._clients[self.identity(name)]
            for k in range(ahead):
                rid = f"r{k}"
                self._presigned[(name, rid)] = self.envelope(signer, name,
                                                             rid)
        n = len(self._presigned)
        print(f"chipbench: fabric: {n} envelopes of {len(names)} clients "
              f"signed ahead in {time.perf_counter() - t0:.1f}s "
              f"({1e6 * (time.perf_counter() - t0) / max(n, 1):.1f} us "
              "each)", flush=True)

    def plane_snapshot(self) -> dict:
        # called at the window's two instants: the lanes by kernel then
        stats = self.coalescer.engine.stats
        self._lane_marks.append(dict(getattr(stats, "used_by_kernel", {})))
        return super().plane_snapshot()

    # -- the front door ------------------------------------------------------------

    def envelope(self, signer, client: str, rid: str) -> bytes:
        from smartbft_tpu.crypto.envelope import sign_envelope

        payload = hashlib.shake_256(f"{client}:{rid}".encode()).digest(
            self.payload_bytes)
        return sign_envelope(*signer, client, rid, payload)

    def identity(self, client: str) -> int:
        i = self._identity_of.get(client)
        if i is None:
            i = len(self._identity_of)
            if i >= len(self.clients()):
                raise RuntimeError(
                    f"{i + 1} client names, {len(self._clients)} enrolled "
                    "identities")
            self._identity_of[client] = i
        return i

    async def submit(self, client: str, rid: str) -> None:
        i = self.identity(client)
        raw = self._presigned.pop((client, rid), None)
        if raw is None:
            t0 = time.perf_counter()
            raw = self.envelope(self._clients[i], client, rid)
            self.sign_s += time.perf_counter() - t0
            self.signed_inline += 1
        key = f"{client}:{rid}"
        self.submitted[key] = hashlib.sha256(raw).digest()
        self._honest += 1
        if self.forged_every and self._honest % self.forged_every == 0:
            task = asyncio.ensure_future(self.forge(client, i))
            self._forging.add(task)
            task.add_done_callback(self._forging.discard)
        try:
            await self.cluster.submit(client, rid, envelope=raw)
        except Exception as e:  # noqa: BLE001 — kept, then the loop's to count
            if type(e).__name__ == "EnvelopeRejected":
                self.refused_honest.append((key, repr(e)))
            raise

    async def forge(self, client: str, i: int) -> None:
        """One forged envelope of ``client`` through the same front door."""
        n = len(self.forged)
        how = FORGERIES[n % len(FORGERIES)]
        rid = f"f{n}"
        entry = [f"{client}:{rid}", b"", how, "pending"]
        self.forged.append(entry)
        signer = self._clients[i]
        if how == "key_not_enrolled":
            signer = self._outsider
        raw = self.envelope(signer, client, rid)
        end = len(raw)
        if how == "bit_of_r":
            raw = flip(raw, end - 64, 0x20)
        elif how == "bit_of_s":
            raw = flip(raw, end - 24, 0x01)
        elif how == "byte_of_payload":
            raw = flip(raw, end - _TRAILER - 1, 0xFF)
        elif how == "another_enrolled_key":
            other = self._clients[(i + 1) % len(self._clients)][1]
            raw = raw[:end - 132] + other[0].to_bytes(32, "big") \
                + other[1].to_bytes(32, "big") + raw[end - 68:]
        entry[1] = raw
        try:
            await self.cluster.submit(client, rid, envelope=raw)
            entry[3] = None
        except Exception as e:  # noqa: BLE001 — what came back is the record
            entry[3] = f"{type(e).__name__}: {e}"

    async def settle(self, timeout: float = 60.0) -> bool:
        if self._forging:
            await asyncio.wait(self._forging, timeout=timeout)
        return await super().settle(timeout)

    # -- the reference ---------------------------------------------------------------

    def ledger_envelopes(self):
        """Every envelope on every ledger, read from each replica's own
        ledger -> ``(ledger id, raw envelope)``, each distinct block's
        bytes cut apart once."""
        from smartbft_tpu.codec import decode
        from smartbft_tpu.testing.app import BatchPayload

        cut: dict = {}
        for sh in self.cluster.shard_list:
            for app in sh.apps:
                for d in app.ledger():
                    payload = d.proposal.payload
                    if not payload:
                        continue
                    raws = cut.get(payload)
                    if raws is None:
                        raws = cut[payload] = decode(BatchPayload,
                                                     payload).requests
                    yield (sh.shard_id, app.id), payload, raws

    def reference_faults(self, ev: dict) -> list:
        """Reasons this deployment adds (never removes one)."""
        faults = []
        enrolled = {pub for _, pub in self._clients}
        forged_keys = {f[0] for f in self.forged}

        # forged envelopes: refused, on no ledger, and OpenSSL agrees
        let_in = [f for f in self.forged if f[3] is None]
        if let_in:
            faults.append(f"{len(let_in)} forged envelope(s) were ACCEPTED "
                          f"at the front door, first {let_in[0][0]} "
                          f"({let_in[0][2]})")
        pending = [f for f in self.forged if f[3] == "pending"]
        if pending:
            faults.append(f"{len(pending)} forged envelope(s) got no answer")
        for lid, keys in ev["ledgers"].items():
            on = [k for k in keys if k in forged_keys]
            if on:
                faults.append(f"{len(on)} forged envelope(s) on the ledger "
                              f"of {lid}, first {on[0]}")
        disagree = [f for f in self.forged if plain_verdict(f[1], enrolled)]
        if disagree:
            faults.append(f"OpenSSL ACCEPTS {len(disagree)} envelope(s) "
                          "this file forged: the forgery is at fault")

        # honest envelopes: none refused
        if self.refused_honest:
            faults.append(f"{len(self.refused_honest)} honest envelope(s) "
                          f"were refused, first {self.refused_honest[0]}")

        # committed envelopes: the bytes submitted, and OpenSSL accepts each
        judged: dict = {}  # block bytes -> (altered, rejected by OpenSSL)
        altered = rejected = checked = 0
        where = None
        t0 = time.perf_counter()
        for lid, block, raws in self.ledger_envelopes():
            got = judged.get(block)
            if got is None:
                a = r = 0
                for raw in raws:
                    parsed = parse_envelope(raw)
                    key = parsed[0] if parsed else None
                    if self.submitted.get(key) != hashlib.sha256(
                            raw).digest():
                        a += 1
                    if not plain_verdict(raw, enrolled):
                        r += 1
                checked += len(raws)
                got = judged[block] = (a, r)
            if (got[0] or got[1]) and where is None:
                where = lid
            altered += got[0]
            rejected += got[1]
        if altered:
            faults.append(f"{altered} committed envelope(s) differ from the "
                          f"bytes submitted, first on the ledger of {where}")
        if rejected:
            faults.append(f"OpenSSL REJECTS {rejected} committed "
                          f"envelope(s), first on the ledger of {where}")

        # a verdict served from anywhere but the device's envelope kernel
        kernel = ENVELOPE_KERNEL[self.config["engine"]]
        loop = ev["loop"]
        in_window = len(loop.window_commits())
        lanes = None
        if len(self._lane_marks) >= 2 and kernel in self._lane_marks[-1]:
            lanes = self._lane_marks[-1][kernel] - self._lane_marks[0][kernel]
        if lanes is None or lanes < in_window:
            faults.append(f"{lanes} lane(s) ran on the {kernel!r} kernel in "
                          f"the window, {in_window} envelope(s) committed "
                          "in it: a verdict came from somewhere else")

        by_how: dict = {}
        for f in self.forged:
            by_how[f[2]] = by_how.get(f[2], 0) + 1
        print(f"chipbench: fabric: {len(self.submitted)} honest envelopes, "
              f"{self.signed_inline} of them signed on the harness's thread "
              f"in {self.sign_s:.3f}s "
              f"({1e6 * self.sign_s / max(self.signed_inline, 1):.1f} us "
              "each); "
              f"{len(self.forged)} forged {by_how}, "
              f"{len(self.forged) - len(let_in) - len(pending)} refused; "
              f"{lanes} lanes on {kernel!r} in the window for {in_window} "
              f"commits; OpenSSL judged {checked} committed envelopes in "
              f"{time.perf_counter() - t0:.1f}s; envelope verdicts by "
              f"replica {self.envelope_counts()}", flush=True)
        return faults

    def envelope_counts(self) -> dict:
        return {app.id: (app.envelopes.accepted, dict(app.envelopes.rejected))
                for sh in self.cluster.shard_list for app in sh.apps
                if getattr(app, "envelopes", None) is not None}
