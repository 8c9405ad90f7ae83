"""A Fabric BFT ordering channel whose identities are Ed25519: every
client envelope carries its creator's 32-byte key and an RFC 8032
signature (PureEdDSA over the envelope's signed bytes), and every replica
verifies it on the chip; the orderers' votes are Ed25519 too.

Built on the Fabric channel (``deployments/fabric.py``): the same
``ShardedCluster(shards=1)`` behind one shared engine and coalescer, the
same enrolled identities, presigned envelopes and forgeries beside the
honest load, on ``crypto="ed25519"``.  What this file changes is what is
scheme-bound:

* **keys** — the orderers' ring and the clients' identities are
  ``ed25519.keygen`` of the same fixed seeds as fabric's;
* **envelopes** — the program's ``sign_envelope(scheme=ed25519)``: the
  trailer is ``u32(32) <key> u32(64) <R || S>``;
* **forgeries** — fabric's five on this trailer, and a sixth of Ed25519's
  own: **S + L**, the same signature with its scalar not reduced, which
  RFC 8032 and OpenSSL refuse;
* **its reference** — OpenSSL's Ed25519 verify (``cryptography``'s
  ``Ed25519PublicKey.verify``) on the raw bytes of every envelope, one at
  a time, held against what the system did with it, as fabric's is.

It needs a program whose envelopes take a scheme; one without is
refused at once, before JAX is touched.
"""

from __future__ import annotations

import hashlib
import inspect
import time

from chipbench import deploy

fabric = deploy.load_deployment("fabric")
sharded = fabric.sharded

CONFIG_KEYS = fabric.CONFIG_KEYS
WORKLOAD_KEYS = fabric.WORKLOAD_KEYS

refuse = sharded.refuse

#: the trailer of an envelope: u32(32) creator(32) u32(64) signature(64)
_TRAILER = 104
_KEY_PREFIX = b"\x00\x00\x00\x20"
_SIG_PREFIX = b"\x00\x00\x00\x40"
#: the group order (RFC 8032 5.1), written out again
L = 2 ** 252 + 27742317777372353535851937790883648493
#: the six corruptions, in turn
FORGERIES = fabric.FORGERIES + ("s_plus_l",)


def parse_envelope(raw: bytes):
    """The plain reading of an envelope's bytes, written out again without
    the program -> ``(key "client:request", signed bytes, creator key,
    signature)``, or None unless it is one."""
    cut = len(raw) - _TRAILER
    if cut < 12 or raw[cut:cut + 4] != _KEY_PREFIX \
            or raw[cut + 36:cut + 40] != _SIG_PREFIX:
        return None
    n = int.from_bytes(raw[:4], "big")
    m = int.from_bytes(raw[4 + n:8 + n], "big")
    if 8 + n + m > cut:
        return None
    key = raw[4:4 + n].decode() + ":" + raw[8 + n:8 + n + m].decode()
    return key, raw[:cut], raw[cut + 4:cut + 36], raw[cut + 40:]


def openssl_verdicts(items) -> list:
    """``(message, signature, key)`` per lane -> OpenSSL's verdicts."""
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey,
    )

    out = []
    for msg, sig, pub in items:
        try:
            Ed25519PublicKey.from_public_bytes(bytes(pub)).verify(
                bytes(sig), msg)
            out.append(True)
        except (InvalidSignature, ValueError):
            out.append(False)
    return out


def plain_verdict(raw: bytes, enrolled: set) -> bool:
    """Is this envelope one the channel may order?  Its creator is
    enrolled and OpenSSL accepts the creator's signature over its bytes."""
    got = parse_envelope(raw)
    if got is None:
        return False
    _key, signed, creator, sig = got
    return creator in enrolled and openssl_verdicts([(signed, sig,
                                                      creator)])[0]


class Deployment(fabric.Deployment):

    def check(self) -> None:
        super().check()
        from smartbft_tpu.crypto import envelope

        if "scheme" not in inspect.signature(
                envelope.sign_envelope).parameters:
            refuse("this program's client envelopes are P-256 only "
                   "(sign_envelope has no scheme=): it cannot serve "
                   "Ed25519 identities")

    # -- scheme, keys, plain reference -----------------------------------------

    def scheme(self):
        if self.config["scheme"] != "ed25519":
            refuse(f"this deployment's identities are Ed25519, the "
                   f"configuration states {self.config['scheme']!r}")
        from smartbft_tpu.crypto import ed25519

        return ed25519

    def reference_verdicts(self, items) -> list:
        """The plain verdicts of the set-up wave's lanes (OpenSSL)."""
        return openssl_verdicts(items)

    def clients(self) -> list:
        if not self._clients:
            ed25519 = self.scheme()
            t0 = time.perf_counter()
            self._clients = [ed25519.keygen(b"fabric-client-%d" % i)
                             for i in range(self.n_enrolled)]
            self._outsider = ed25519.keygen(b"fabric-not-enrolled")
            print(f"chipbench: fabric_ed25519: {len(self._clients)} client "
                  f"identities derived in {time.perf_counter() - t0:.1f}s",
                  flush=True)
        return self._clients

    # -- the front door ------------------------------------------------------------

    def envelope(self, signer, client: str, rid: str) -> bytes:
        from smartbft_tpu.crypto.envelope import sign_envelope

        payload = hashlib.shake_256(f"{client}:{rid}".encode()).digest(
            self.payload_bytes)
        return sign_envelope(*signer, client, rid, payload,
                             scheme=self.scheme())

    async def forge(self, client: str, i: int) -> None:
        """One forged envelope of ``client`` through the same front door."""
        n = len(self.forged)
        how = FORGERIES[n % len(FORGERIES)]
        rid = f"f{n}"
        entry = [f"{client}:{rid}", b"", how, "pending"]
        self.forged.append(entry)
        signer = self._outsider if how == "key_not_enrolled" \
            else self._clients[i]
        raw = self.envelope(signer, client, rid)
        end = len(raw)
        if how == "bit_of_r":
            raw = fabric.flip(raw, end - 64, 0x20)
        elif how == "bit_of_s":
            raw = fabric.flip(raw, end - 24, 0x01)
        elif how == "byte_of_payload":
            raw = fabric.flip(raw, end - _TRAILER - 1, 0xFF)
        elif how == "another_enrolled_key":
            other = self._clients[(i + 1) % len(self._clients)][1]
            raw = raw[:end - 100] + other + raw[end - 68:]
        elif how == "s_plus_l":
            s = int.from_bytes(raw[end - 32:], "little")
            raw = raw[:end - 32] + (s + L).to_bytes(32, "little")
        entry[1] = raw
        try:
            await self.cluster.submit(client, rid, envelope=raw)
            entry[3] = None
        except Exception as e:  # noqa: BLE001 — what came back is the record
            entry[3] = f"{type(e).__name__}: {e}"

    # -- the reference ---------------------------------------------------------------

    def reference_faults(self, ev: dict) -> list:
        """Reasons this deployment adds (never removes one): fabric's,
        read with this file's plain reading of an Ed25519 envelope."""
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
        kernel = fabric.ENVELOPE_KERNEL[self.config["engine"]]
        in_window = len(ev["loop"].window_commits())
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
        stats = self.coalescer.engine.stats
        print(f"chipbench: fabric_ed25519: {len(self.submitted)} honest "
              f"envelopes, {self.signed_inline} of them signed on the "
              f"harness's thread in {self.sign_s:.3f}s "
              f"({1e6 * self.sign_s / max(self.signed_inline, 1):.1f} us "
              "each); "
              f"{len(self.forged)} forged {by_how}, "
              f"{len(self.forged) - len(let_in) - len(pending)} refused; "
              f"{lanes} lanes on {kernel!r} in the window for {in_window} "
              f"commits; lanes the host refused "
              f"{getattr(stats, 'host_refused', None)}; OpenSSL judged "
              f"{checked} committed envelopes in "
              f"{time.perf_counter() - t0:.1f}s; envelope verdicts by "
              f"replica {self.envelope_counts()}", flush=True)
        return faults
