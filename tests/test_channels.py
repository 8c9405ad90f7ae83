"""Several named channels on one orderer host, held to the plain reference.

``ShardedCluster(shards=c, crypto="p256", enrolled={channel: identities})``
on the OpenSSL engine (no kernel is compiled here), seeded keys and
requests, 2 and 4 channels x 4 replicas, blocks of 10.  What each
channel's replicas ordered is what ``smartbft_tpu/testing/
channel_reference.py`` says they had to: the envelopes that name the
channel and that OpenSSL accepts under a creator enrolled THERE, each
once, and nothing else.  An envelope of another channel is refused where
it enters a replica, at a forward and in a proposal, by the classic view
and by the windowed one.
"""

import asyncio
import dataclasses
import random

import pytest

from smartbft_tpu.codec import decode, encode
from smartbft_tpu.crypto import p256
from smartbft_tpu.crypto.envelope import (
    CHANNEL_MAGIC,
    EnvelopeRejected,
    EnvelopeVerifier,
    channel_header,
    envelope_channel,
    sign_envelope,
    split_envelope,
)
from smartbft_tpu.crypto.openssl_engine import OpenSSLVerifyEngine
from smartbft_tpu.messages import PrePrepare
from smartbft_tpu.shard import ChannelNotServed, ShardEpochError
from smartbft_tpu.testing import channel_reference as ref
from smartbft_tpu.testing.app import BatchPayload, wait_for
from smartbft_tpu.testing.app import TestRequest as UnsignedRequest
from smartbft_tpu.testing.sharded import ShardedCluster, sharded_config

from tests.test_envelopes import flip, ledger_requests

NAMES = ("trade", "settle", "audit", "kyc")
FORGERIES = ("bit_of_r", "bit_of_s", "byte_of_payload",
             "another_enrolled_key", "key_not_enrolled",
             "names_another_channel")


def xy(pub) -> bytes:
    """A public point as the reference reads it off an envelope."""
    return pub[0].to_bytes(32, "big") + pub[1].to_bytes(32, "big")


class Host:
    """``channels`` named channels, ``per`` identities enrolled on each
    (and on no other), one outsider, from a seed."""

    def __init__(self, channels: int, per: int, seed: int):
        self.names = NAMES[:channels]
        self.rng = random.Random(seed)
        self.ids = {name: [p256.keygen(b"channels-%d-%s-%d"
                                       % (seed, name.encode(), i))
                           for i in range(per)] for name in self.names}
        self.outsider = p256.keygen(b"channels-%d-outsider" % seed)
        self.enrolled = {name: [pub for _, pub in ids]
                         for name, ids in self.ids.items()}
        #: what the reference is told: channel -> X || Y of its identities
        self.creators = {name: {xy(pub) for pub in pubs}
                         for name, pubs in self.enrolled.items()}
        #: every envelope handed to the host, honest or not
        self.submitted: list = []

    def client(self, name: str, i: int) -> str:
        return f"{name}-client-{i}"

    def honest(self, name: str, i: int, rid: str, size: int = 64) -> bytes:
        raw = sign_envelope(*self.ids[name][i], self.client(name, i), rid,
                            self.rng.randbytes(size), channel=name)
        self.submitted.append(raw)
        return raw

    def forged(self, name: str, i: int, rid: str, how: str) -> bytes:
        """One of fabric's five corruptions of an envelope that names
        ``name``, or the sixth: an envelope that names the NEXT channel,
        honestly signed by an identity enrolled on ``name`` only."""
        signer = self.outsider if how == "key_not_enrolled" \
            else self.ids[name][i]
        names = name
        if how == "names_another_channel":
            names = self.names[(self.names.index(name) + 1)
                               % len(self.names)]
        size = 64
        raw = sign_envelope(*signer, self.client(name, i), rid,
                            self.rng.randbytes(size), channel=names)
        end = len(raw)
        if how == "bit_of_r":
            raw = flip(raw, end - 64 + self.rng.randrange(32), 0x20)
        elif how == "bit_of_s":
            raw = flip(raw, end - 32 + self.rng.randrange(32), 0x01)
        elif how == "byte_of_payload":
            raw = flip(raw, end - 137 - self.rng.randrange(size), 0xFF)
        elif how == "another_enrolled_key":
            other = self.enrolled[name][(i + 1) % len(self.enrolled[name])]
            raw = raw[:end - 132] + xy(other) + raw[end - 68:]
        self.submitted.append(raw)
        return raw


#: what the system says of each forgery
CAUSE = {"bit_of_r": "bad_signature", "bit_of_s": "bad_signature",
         "byte_of_payload": "bad_signature",
         "another_enrolled_key": "bad_signature",
         "key_not_enrolled": "not_enrolled",
         "names_another_channel": "not_enrolled"}


def config(i: int, depth: int = 1, **more):
    # the engine runs on threads in real time while the logical clock
    # races ahead: generous liveness timers (as tests/test_envelopes.py)
    base = dict(
        depth=depth, request_batch_max_count=10,
        request_forward_timeout=60.0, request_complain_timeout=120.0,
        request_auto_remove_timeout=240.0, view_change_resend_interval=60.0,
        view_change_timeout=240.0, leader_heartbeat_timeout=120.0)
    base.update(more)
    return sharded_config(i, **base)


def cluster(tmp_path, host: Host, *, depth: int = 1, enrolled=None, **more):
    return ShardedCluster(
        tmp_path, shards=len(host.names), n=4, depth=depth, crypto="p256",
        engine=OpenSSLVerifyEngine(scheme=p256), window=0.002,
        enrolled=host.enrolled if enrolled is None else enrolled,
        journal=False,
        config_fn=lambda _s, i: config(i, depth, **more))


def ledgers(c, host: Host) -> dict:
    """channel -> its replicas' ledgers, as the reference takes them."""
    return {name: [ledger_requests(a) for a in c.shard(sid).apps]
            for sid, name in enumerate(host.names)}


async def door(c, client: str, rid: str, raw: bytes):
    """Through the host's one front door -> (shard it landed on, None) or
    (None, the structured cause it was refused with)."""
    try:
        return await c.submit(client, rid, envelope=raw), None
    except (EnvelopeRejected, ChannelNotServed) as e:
        return None, e.cause


# -- the header ----------------------------------------------------------------


def test_the_header_rides_inside_the_signed_bytes_and_absent_is_as_ever():
    """(e) a golden envelope: one that names no channel has, byte for
    byte, the layout it always had (the unsigned request plus the
    trailer; the signature is randomised, so OpenSSL judges that); one
    that names a channel differs by the header at the start of its
    payload and nothing else, and the creator's signature covers it."""
    sk, pk = p256.keygen(b"golden")
    golden = bytes.fromhex(
        "00000005616c696365" "000000027237" "00000003706179")
    assert encode(UnsignedRequest(client_id="alice", request_id="r7",
                              payload=b"pay")) == golden
    raw = sign_envelope(sk, pk, "alice", "r7", b"pay")
    signed, creator, sig = split_envelope(raw)
    assert signed == golden and creator == xy(pk)
    assert raw[:-64] == golden + bytes.fromhex("00000040") + xy(pk) \
        + bytes.fromhex("00000040")
    assert envelope_channel(raw) is None and envelope_channel(signed) is None
    assert ref.parse(raw) == ("alice:r7", None, golden, xy(pk), sig)
    assert ref.openssl_accepts(signed, creator, sig)

    named = sign_envelope(sk, pk, "alice", "r7", b"pay", channel="trade")
    header = CHANNEL_MAGIC + b"\x05trade"
    assert channel_header("trade") == header
    assert split_envelope(named)[0] == bytes.fromhex(
        "00000005616c696365" "000000027237") \
        + (len(header) + 3).to_bytes(4, "big") + header + b"pay"
    assert envelope_channel(named) == "trade"
    assert ref.parse(named)[1] == "trade"
    assert ref.may_order(named, "trade", {xy(pk)})
    # the signature covers the name: another name under the same bytes
    at = named.index(b"trade")
    renamed = named[:at] + b"trads" + named[at + 5:]
    assert envelope_channel(renamed) == "trads"
    assert not ref.may_order(renamed, "trads", {xy(pk)})
    # a payload may not pose as a header, and a header cut short is no
    # envelope at all
    with pytest.raises(ValueError):
        sign_envelope(sk, pk, "alice", "r8", CHANNEL_MAGIC + b"\x01x")
    with pytest.raises(ValueError):
        channel_header("")
    cut = encode(UnsignedRequest(client_id="a", request_id="r",
                             payload=CHANNEL_MAGIC + b"\x09ab"))
    with pytest.raises(EnvelopeRejected) as e:
        envelope_channel(cut)
    assert e.value.cause == "malformed"


def test_a_verifier_of_a_named_channel_refuses_every_other_name():
    """The one check all three entries share: ``wrong_channel`` for an
    envelope that names another channel or none, before enrolment is
    looked at and before any device work; a verifier of an unnamed
    channel does not look into payloads (the path it always took)."""
    ids = [p256.keygen(b"verifier-%d" % i) for i in range(3)]
    pubs = [pub for _, pub in ids]
    eng = OpenSSLVerifyEngine(scheme=p256)
    ev = EnvelopeVerifier(pubs, engine=eng, channel="trade")
    good = sign_envelope(*ids[0], "c0", "r0", b"x", channel="trade")
    other = sign_envelope(*ids[1], "c1", "r0", b"x", channel="settle")
    none = sign_envelope(*ids[2], "c2", "r0", b"x")
    ev.check([good])
    for raw in (other, none):
        with pytest.raises(EnvelopeRejected) as e:
            ev.check([good, raw])
        assert e.value.cause == "wrong_channel"
    assert ev.rejected == {"malformed": 0, "not_enrolled": 0,
                           "bad_signature": 0, "wrong_channel": 2}
    assert ev.accepted == 1 and eng.stats.launches == 1
    unnamed = EnvelopeVerifier(pubs, engine=eng)
    unnamed.check([good, other, none])
    assert unnamed.accepted == 3 and not any(unnamed.rejected.values())


# -- (a) the model, end to end ---------------------------------------------------


@pytest.mark.parametrize("channels", [2, 4])
def test_each_channel_orders_exactly_what_names_it(tmp_path, channels):
    """(a) + (c): every honest envelope lands on the channel it names,
    whoever its client is; every forgery (fabric's five and the sixth: an
    identity enrolled on ONE channel naming another) comes back refused
    with its cause; an envelope that names no channel or one nobody
    serves is refused too; the ledgers are the model's."""

    async def run():
        host = Host(channels, per=5, seed=channels)
        c = cluster(tmp_path, host)
        await c.start()
        try:
            work = []
            for sid, name in enumerate(host.names):
                for i in range(5):
                    for k in range(2 + (sid == 0)):  # channel 0 is busier
                        rid = f"r{k}"
                        work.append((host.client(name, i), rid,
                                     host.honest(name, i, rid), sid, None))
                for n, how in enumerate(FORGERIES):
                    rid = f"f{n}"
                    work.append((host.client(name, n % 5), rid,
                                 host.forged(name, n % 5, rid, how), None,
                                 CAUSE[how]))
            stray = sign_envelope(*host.ids[host.names[0]][0], "stray", "r0",
                                  b"x", channel="nobody-serves-this")
            unnamed = sign_envelope(*host.ids[host.names[0]][0], "stray",
                                    "r1", b"x")
            host.submitted += [stray, unnamed]
            work += [("stray", "r0", stray, None, "unknown_channel"),
                     ("stray", "r1", unnamed, None, "wrong_channel")]
            host.rng.shuffle(work)
            came_back = await asyncio.gather(
                *(door(c, client, rid, raw)
                  for client, rid, raw, _sid, _cause in work))
            for (client, rid, _raw, sid, cause), got in zip(work, came_back):
                assert got == (sid, cause), (client, rid, got)
            honest = {name: sum(1 for w in work if w[3] == sid)
                      for sid, name in enumerate(host.names)}
            await wait_for(
                lambda: all(len(ledger_requests(a)) >= honest[name]
                            for sid, name in enumerate(host.names)
                            for a in c.shard(sid).apps),
                c.scheduler, timeout=600.0)
            c.check_invariants()
            assert ref.channel_faults(ledgers(c, host), host.submitted,
                                      host.creators) == []
            for sid, name in enumerate(host.names):
                assert len(ledger_requests(c.shard(sid).apps[0])) \
                    == honest[name] == 10 + 5 * (sid == 0)
                at_door = c.shard(sid).apps[0].envelopes.rejected
                # its own five, and the sixth forgery of the channel
                # before it, which names this one
                assert at_door["bad_signature"] == 4
                assert at_door["not_enrolled"] == 2
            assert sum(a.envelopes.rejected["wrong_channel"]
                       for sh in c.shard_list for a in sh.apps) == 1
        finally:
            await c.stop()

    asyncio.run(run())


def test_the_reference_itself_tells_a_broken_host_from_a_sound_one():
    """The model is not vacuous: ledgers it accepts, and each way a host
    can be wrong that it has to see."""
    host = Host(2, per=2, seed=9)
    a = [host.honest("trade", i, "r0") for i in range(2)]
    b = [host.honest("settle", i, "r0") for i in range(2)]
    sixth = host.forged("trade", 0, "f0", "names_another_channel")
    sound = {"trade": [a, a, a, a], "settle": [b, b, b, b]}
    says = lambda led: ref.channel_faults(led, host.submitted,  # noqa: E731
                                          host.creators)
    assert says(sound) == []
    assert any("not replica 1's" in f for f in says(
        dict(sound, trade=[a, a[::-1], a, a])))
    assert any("more than once" in f for f in says(
        dict(sound, trade=[a + a[:1]] * 4)))
    assert any("name another channel" in f for f in says(
        dict(sound, trade=[a + b[:1]] * 4)))
    assert any("creator is not enrolled there" in f for f in says(
        dict(sound, settle=[b + [sixth]] * 4)))
    assert any("are not on its ledger" in f for f in says(
        dict(sound, settle=[b[:1]] * 4)))
    assert any("the channels are" in f for f in says({"trade": [a] * 4}))


# -- (b) an envelope of channel Y handed to channel X ----------------------------


@pytest.mark.parametrize("depth", [1, 4], ids=["view", "windowed view"])
def test_another_channels_envelope_is_refused_at_entry_forward_and_proposal(
        tmp_path, depth):
    """(b) the set's own door routes by the envelope and cannot misplace
    one, so the envelope of ``settle`` is handed to ``trade``'s replicas
    directly: where it enters one (``AppShard.submit``, ``Controller.
    submit_request``), as a follower's forward, and inside a doctored
    leader's pre-prepare (no follower votes; nothing of the block
    commits).  Its creator is enrolled on BOTH channels and its
    signature is good: the channel check alone refuses it."""

    async def run():
        host = Host(2, per=4, seed=10 + depth)
        both = host.ids["settle"][0]
        enrolled = dict(host.enrolled,
                        trade=host.enrolled["trade"] + [both[1]])
        c = cluster(tmp_path, host, depth=depth, enrolled=enrolled)
        await c.start()
        try:
            trade = c.shard(0)
            assert [sh.channel for sh in c.shard_list] == ["trade", "settle"]
            foreign = [sign_envelope(*both, "roamer", f"r{k}", b"x" * 32,
                                     channel="settle") for k in range(3)]
            leader = trade.app(trade.leader_id())
            followers = [a for a in trade.apps if a is not leader]
            for entry in (trade.submit,
                          followers[0].consensus.submit_request):
                with pytest.raises(EnvelopeRejected) as e:
                    await entry(foreign[0])
                assert e.value.cause == "wrong_channel"
            assert leader.envelopes.rejected["wrong_channel"] == 1
            assert followers[0].envelopes.rejected["wrong_channel"] == 1
            # a forward
            assert await leader.consensus.handle_request(
                followers[1].id, foreign[1]) is None
            await wait_for(
                lambda: leader.consensus.controller.bad_forwards == 1,
                c.scheduler, timeout=600.0)
            assert leader.envelopes.rejected["wrong_channel"] == 2
            assert leader.consensus.pool_occupancy().get("size", 0) == 0

            # a proposal: the leader's pre-prepare carries one, put in at
            # its send
            def inject(_target, msg):
                if isinstance(msg, PrePrepare) and msg.proposal.payload:
                    reqs = list(decode(BatchPayload,
                                       msg.proposal.payload).requests)
                    reqs[0] = foreign[2]
                    return dataclasses.replace(
                        msg, proposal=dataclasses.replace(
                            msg.proposal,
                            payload=encode(BatchPayload(requests=reqs))))
                return msg

            leader.node.mutate_send = inject
            before = [a.envelopes.rejected["wrong_channel"]
                      for a in followers]
            for i in range(4):
                await c.submit(host.client("trade", i), "r0",
                               envelope=host.honest("trade", i, "r0"))
                await c.submit(host.client("settle", i), "r0",
                               envelope=host.honest("settle", i, "r0"))
            await wait_for(
                lambda: all(a.envelopes.rejected["wrong_channel"] > n
                            for a, n in zip(followers, before))
                and all(len(ledger_requests(a)) == 4
                        for a in c.shard(1).apps),
                c.scheduler, timeout=600.0)
            # no follower voted: the block is on no ledger of trade, while
            # settle ordered its own four
            assert [a.height() for a in trade.apps] == [0, 0, 0, 0]
            faults = ref.channel_faults(ledgers(c, host), host.submitted,
                                        host.creators)
            assert faults == ["channel trade: 4 envelope(s) it had to "
                              "order are not on its ledger"]
        finally:
            await c.stop()

    asyncio.run(run())


# -- (d) one hot channel ---------------------------------------------------------


@pytest.mark.parametrize("channels", [2, 4])
def test_a_hot_channel_parks_its_own_submitters_and_no_other(tmp_path,
                                                             channels):
    """(d) ``shard/set.py``'s "one hot shard cannot stall the set", with
    signed envelopes: the hot channel's pool (4 slots) is full and its
    leader is mute, so ITS further submitters park on it; every other
    channel orders all it is given meanwhile, with nobody parked."""

    async def run():
        host = Host(channels, per=8, seed=20 + channels)
        c = cluster(tmp_path, host, request_pool_size=4,
                    request_pool_submit_timeout=300.0)
        await c.start()
        try:
            hot = c.shard(0)
            hot.mute_leader()
            parked = [asyncio.ensure_future(c.submit(
                host.client("trade", i), "r0",
                envelope=host.honest("trade", i, "r0"))) for i in range(7)]
            await wait_for(
                lambda: hot.pool_occupancy().get("waiters") == 3,
                c.scheduler, timeout=600.0)
            # twice their pools' size each: they park too, a moment, on
            # their OWN pools, and are let in as their blocks commit
            others = [asyncio.ensure_future(c.submit(
                host.client(name, i), "r0",
                envelope=host.honest(name, i, "r0")))
                for name in host.names[1:] for i in range(8)]
            await wait_for(
                lambda: all(len(ledger_requests(a)) == 8
                            for sh in c.shard_list[1:] for a in sh.apps),
                c.scheduler, timeout=600.0)
            occ = c.set.occupancy()
            assert occ["per_shard"][0]["size"] == 4
            assert occ["per_shard"][0]["waiters"] == 3
            assert occ["total_waiters"] == 3
            assert [a.height() for a in hot.apps] == [0, 0, 0, 0]
            assert sum(t.done() for t in parked) == 4
            for sid in range(1, channels):
                assert c.committed_requests(sid) == 8
            assert [t.result() for t in others] == [
                sid for sid in range(1, channels) for _ in range(8)]
            for t in parked:
                t.cancel()
            await asyncio.gather(*parked, return_exceptions=True)
        finally:
            await c.stop()

    asyncio.run(run())


# -- (f) no reshard, and the router still serves what names nothing ---------------


@pytest.mark.parametrize("channels", [2, 4])
def test_named_channels_are_not_resharded(tmp_path, channels):
    """(f) ``reshard()`` on a set that serves named channels raises at
    once: no epoch is allocated, no barrier submitted, nothing journaled."""

    async def run():
        host = Host(channels, per=1, seed=30)
        c = ShardedCluster(tmp_path, shards=channels, n=4, crypto="p256",
                           engine=OpenSSLVerifyEngine(scheme=p256),
                           enrolled=host.enrolled)
        assert c.set.channels == {name: sid
                                  for sid, name in enumerate(host.names)}
        for new in (channels + 1, 1):
            with pytest.raises(ShardEpochError, match="named channels"):
                await c.reshard(new)
        assert c.set.reshard_stats == {"transitions": 0, "aborts": 0,
                                       "last": None}
        assert not c.set.reshard_in_progress and c.set.epoch == 0
        assert c.set.journal.replay() == []
        c.set.journal.close()

    asyncio.run(run())


def test_the_names_are_one_per_shard_and_the_router_keeps_the_unnamed(
        tmp_path):
    """The front door is ONE: on a set with named channels (trivial
    scheme: the requests are unsigned, ``channel=`` addresses them) a
    request that names a channel lands there whoever its client is, one
    that names none is placed by the router as ever, one that names a
    stranger is refused with a structured cause."""

    async def run():
        c = ShardedCluster(tmp_path, shards=2, n=4, journal=False)
        for bad in (["a"], ["a", "a"], ["a", ""], ["a", "b", "c"]):
            with pytest.raises(ValueError):
                c.set.name_channels(bad)
        assert c.set.channels == {}
        with pytest.raises(ChannelNotServed):  # an unnamed set serves none
            await c.set.submit("x", b"", channel="a")
        c.set.name_channels(["a", "b"])
        await c.start()
        try:
            to_b = c.client_for_shard(1)  # the router would say shard 1
            assert await c.submit(to_b, "r0", channel="a") == 0
            assert await c.submit(to_b, "r1", channel="b") == 1
            assert await c.submit(to_b, "r2") == 1
            with pytest.raises(ChannelNotServed) as e:
                await c.submit(to_b, "r3", channel="c")
            assert e.value.cause == "unknown_channel"
            assert e.value.channel == "c" and e.value.served == ("a", "b")
            await wait_for(lambda: c.committed_requests() == 3,
                           c.scheduler, 90.0)
            assert c.set.committed_requests(0) == 1
            assert c.set.committed_requests(1) == 2
            c.check_invariants()
        finally:
            await c.stop()

    asyncio.run(run())


def test_the_door_reads_the_channel_from_the_envelope(tmp_path):
    """``submit(channel=)`` beside an envelope that names another channel
    is a caller's mistake, raised before anything is placed; on a set
    whose shards are NOT named channels an envelope is placed by its
    client and its payload is not looked into."""

    async def run():
        host = Host(2, per=1, seed=31)
        c = cluster(tmp_path / "named", host)
        raw = host.honest("settle", 0, "r0")
        with pytest.raises(ValueError, match="names 'settle'"):
            await c.submit("x", "r0", envelope=raw, channel="trade")
        assert c.set.submitted == 0
        one_set = [pub for pubs in host.enrolled.values() for pub in pubs]
        plain = ShardedCluster(tmp_path / "hashed", shards=2, n=4,
                               crypto="p256", journal=False,
                               engine=OpenSSLVerifyEngine(scheme=p256),
                               enrolled=one_set)
        assert plain.set.channels == {}
        assert [a.envelopes.channel for sh in plain.shard_list
                for a in sh.apps] == [None] * 8
        with pytest.raises(ChannelNotServed):
            await plain.submit("x", "r0", channel="settle")

    asyncio.run(run())


# -- (g) the account's channels block ---------------------------------------------


def test_the_accounts_channels_block_against_hand_counted_launches():
    """(g) five waves of the coalescer, written out by hand: two of them
    carried two groups' items, one split over both kernels; decisions,
    requests, refusals and ``verify.wait`` land on the group whose
    replica (``s<tag>n<i>``) or tag they carry."""
    from smartbft_tpu.obs.account import assemble_account
    from smartbft_tpu.obs.recorder import SpanEvent

    def lanes(t, launch, kernel, used, tags):
        extra = {"kernel": kernel, "lanes": 512, "used": used}
        if tags is not None:
            extra["tags"] = tags
        return SpanEvent(t, "verify.lanes", "proc", launch=launch,
                         extra=extra)

    def wait(t, tag, ms):
        return SpanEvent(t, "verify.wait", "verify", dur=ms / 1e3,
                         extra={"items": 3, "tag": tag})

    def decided(t, node, count, proposer=True):
        extra = {"count": count}
        if proposer:
            extra["proposer"] = True
        return SpanEvent(t, "decision.deliver", node, view=0, seq=1,
                         extra=extra)

    events = [
        lanes(1.0, 1, "comb", 8, ["0"]),
        lanes(1.1, 2, "comb", 16, ["0", "1"]),     # mixed
        lanes(1.2, 2, "pallas", 400, ["0", "1"]),  # the same wave
        lanes(1.3, 3, "pallas", 500, ["1"]),
        lanes(1.4, 4, "comb", 24, ["0", "1", "2"]),  # mixed
        lanes(1.5, 5, "pallas", 100, ["2"]),
        lanes(1.6, 6, "comb", 8, None),  # an untagged launch: a prewarm
        lanes(9.0, 7, "comb", 8, ["0"]),  # after the interval
        decided(1.0, "s0n1", 500), decided(1.1, "s0n2", 500, False),
        decided(1.2, "s0n1", 300), decided(1.3, "s1n3", 40),
        decided(1.4, "n1", 7),  # a replica of no group
        wait(1.0, "0", 5.0), wait(1.1, "0", 9.0), wait(1.2, "0", 7.0),
        wait(1.3, "1", 20.0), wait(1.4, "None", 1.0),
        SpanEvent(1.5, "req.rejected", "s1n1",
                  extra={"cause": "wrong_channel"}),
        SpanEvent(1.6, "req.rejected", "s1n1",
                  extra={"cause": "not_enrolled"}),
        SpanEvent(1.7, "req.rejected", "s1n2",
                  extra={"cause": "not_enrolled"}),
    ]

    class Ring:
        recorded = dropped = 0

        def __init__(self, node, mine):
            self.node, self.mine = node, mine

        def events(self):
            return list(self.mine)

    rings = [Ring("all", events), Ring("s2n1", [])]  # group 2 did nothing
    acc = assemble_account(rings, {}, t0=0.0, t1=2.0, loop_cpu_s=1.0,
                           loop_thread="MainThread")
    ch = acc["channels"]
    assert (ch["launches"], ch["mixed_launches"]) == (5, 2)
    assert ch["kernels"] == {"comb": {"launches": 3, "used": 48},
                             "pallas": {"launches": 3, "used": 1000}}
    assert ch["per_channel"] == {
        "0": {"decisions": 2, "requests": 800, "rejected": {},
              "verify_waits": 3, "verify_wait_ms": pytest.approx(7.0)},
        "1": {"decisions": 1, "requests": 40,
              "rejected": {"wrong_channel": 1, "not_enrolled": 2},
              "verify_waits": 1, "verify_wait_ms": pytest.approx(20.0)},
        "2": {"decisions": 0, "requests": 0, "rejected": {},
              "verify_waits": 0, "verify_wait_ms": None},
    }
    # the blocks that were there count what they counted
    assert acc["lanes"]["comb"]["launches"] == 4
    assert acc["counters"]["decisions"] == 4
    assert acc["rejected"] == {"wrong_channel": 1, "not_enrolled": 2}
    # no group among the recorders: no block
    lone = assemble_account([Ring("n1", events[:1])], {}, t0=0.0, t1=2.0,
                            loop_cpu_s=1.0, loop_thread="MainThread")
    assert lone["channels"] == {}


def test_a_profiled_run_of_two_groups_marks_its_launches_with_their_tags(
        tmp_path):
    """The marks are the program's: two groups behind one coalescer (the
    toy device kernel), the profiler on for a second of traffic to both;
    the account's waves and mixed waves are the always-on
    ``ShardAttribution``'s over the same span, give or take the wave in
    flight at either edge."""
    import threading
    import time

    import jax

    from smartbft_tpu import obs
    from smartbft_tpu.crypto.provider import JaxVerifyEngine
    from smartbft_tpu.testing import toy_scheme
    from smartbft_tpu.utils.clock import WallClockDriver

    out = {}

    async def run():
        engine = JaxVerifyEngine(pad_sizes=(8, 64), scheme=toy_scheme)
        c = ShardedCluster(tmp_path / "wal", shards=2, n=4, crypto="toy",
                           engine=engine, window=0.002, journal=False)
        driver = WallClockDriver(c.scheduler, tick_interval=0.005)
        driver.start()
        await c.start()
        try:
            while not all(sh.ready() for sh in c.shard_list):
                await asyncio.sleep(0.01)
            clients = [c.client_for_shard(s, j) for s in (0, 1)
                       for j in range(4)]
            seq = dict.fromkeys(clients, 0)

            async def traffic(seconds):
                end = time.perf_counter() + seconds
                while time.perf_counter() < end:
                    for cid in clients:
                        seq[cid] += 1
                        await c.submit(cid, f"r{seq[cid]}")
                    c.poll()
                    await asyncio.sleep(0.004)

            await traffic(0.3)  # compile off the trace
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            up, done = threading.Event(), threading.Event()

            def start():
                jax.profiler.start_trace(str(tmp_path / "trace"),
                                         profiler_options=opts)
                up.set()
                done.wait(60.0)

            starter = threading.Thread(target=start)
            starter.start()
            assert up.wait(60.0)
            await asyncio.sleep(0.02)  # a tick sees it on
            before = c.coalescer.shard_snapshot()
            await traffic(1.0)
            out["attribution"] = (before, c.coalescer.shard_snapshot())
            jax.profiler.stop_trace()
            done.set()
            starter.join()
            await asyncio.sleep(0.05)  # a tick sees it off
        finally:
            await c.stop()
            await driver.stop()

    asyncio.run(run())
    ch = obs.last_summary()["channels"]
    before, after = out["attribution"]
    waves = after["tagged_waves"] - before["tagged_waves"]
    mixed = after["mixed_waves"] - before["mixed_waves"]
    assert waves > 10 and mixed > 0
    assert abs(ch["launches"] - waves) <= 2
    assert abs(ch["mixed_launches"] - mixed) <= 2
    assert set(ch["kernels"]) == {"xla"}
    assert set(ch["per_channel"]) == {"0", "1"}
    for per in ch["per_channel"].values():
        assert per["decisions"] > 0 and per["requests"] >= per["decisions"]
        assert per["verify_waits"] > 0 and per["verify_wait_ms"] > 0
        assert per["rejected"] == {}
    assert sum(p["decisions"] for p in ch["per_channel"].values()) \
        == obs.last_summary()["counters"]["decisions"]


def test_the_socket_replica_of_a_named_channel_refuses_another_channels(
        tmp_path):
    """The second embedder follows: a socket replica whose spec names its
    channel holds the same verifier, and refuses an envelope of another
    channel in ``verify_request`` and inside a proposal."""
    from smartbft_tpu.messages import Proposal
    from smartbft_tpu.net.launch import ReplicaApp

    host = Host(2, per=3, seed=40)
    base = str(tmp_path)
    r = ReplicaApp({
        "node_id": 1,
        "peers": {i: f"uds:{base}/n{i}.sock" for i in (2, 3, 4)},
        "listen": f"uds:{base}/n1.sock",
        "ledger_path": f"{base}/ledger-1.bin", "wal_dir": f"{base}/wal-1",
        "enrolled": [xy(p).hex() for p in host.enrolled["trade"]],
        "channel": "trade",
    })
    assert r.envelopes.channel == "trade"
    good = [host.honest("trade", i, "r0") for i in range(3)]
    bad = host.honest("settle", 0, "r0")
    assert str(r.verify_request(good[0])) == "trade-client-0:r0"
    with pytest.raises(EnvelopeRejected) as e:
        r.verify_request(bad)
    assert e.value.cause == "wrong_channel"
    with pytest.raises(EnvelopeRejected) as e:
        r.verify_proposal(Proposal(payload=encode(BatchPayload(
            requests=good[:2] + [bad]))))
    assert e.value.cause == "wrong_channel"
    assert r.envelopes.rejected["wrong_channel"] == 2
