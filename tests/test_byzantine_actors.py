"""ISSUE 18 — the Byzantine actor harness: every attack mode the
``--byzantine`` chaos matrix soaks is pinned here as a FAST tier-1
logical-clock scenario, alongside unit pins for the defense substrate
(per-sender misbehavior accounting, provider verify attribution, bounded
decode memos under wire floods, the bench row schema).

The clusters are n=3f+1 with f=1 actor misbehaving on the wire through
``testing.byzantine.ByzantineActor``, running REAL forgery-rejecting
crypto (testing.toy_scheme) over one shared verify plane.  Safety AND
liveness must both hold: every honest request commits fork-free and
exactly-once while the actor lies.
"""

import asyncio

import pytest

from smartbft_tpu.core.misbehavior import (
    OBSERVED_CAUSES,
    PROVABLE_CAUSES,
    MisbehaviorTable,
)
from smartbft_tpu.messages import (
    INTERN_MEMO_BOUND,
    Proposal,
    Signature,
    clear_intern_memo,
    intern_memo_len,
)
from smartbft_tpu.metrics import PROTOCOL_PLANE, InMemoryProvider, MetricsBundle
from smartbft_tpu.testing import toy_scheme
from smartbft_tpu.testing.byzantine import ByzantineActor, sync_poison_round
from smartbft_tpu.testing.chaos import (
    ChaosCluster,
    Invariants,
    byzantine_latency_probe,
    byzantine_round,
)


# -- the five attack modes (the --byzantine matrix, one lean round each) ------

def test_equivocating_leader_cannot_fork(tmp_path):
    """The actor leads and sends a DIFFERENT proposal to every follower at
    the same (view, seq).  No per-target variant may ever commit anywhere
    (the equivocation oracle recomputes this from the actor's send log),
    the cluster stays live, and the shared deterministic blacklist names
    the actor within a bounded number of decisions."""
    asyncio.run(byzantine_round("equivocate", requests=8, verbose=False))


def test_vote_forger_is_attributed_shunned_and_shed(tmp_path):
    """The actor floods forged commit votes (real digest binding, garbage
    signature value) at the shared verify plane.  Every honest replica
    attributes the invalid verdicts to the actor — and ONLY to the actor —
    crosses the shun threshold, and sheds its votes at intake before they
    cost verify launches.  Consensus proceeds: Q = self + 2 honest."""
    asyncio.run(byzantine_round("forge", requests=8, verbose=False))


def test_censoring_leader_detected_under_open_loop_load(tmp_path):
    """The actor leads (static leadership) and silently drops forwarded
    client requests while open-loop spike arrivals land cluster-wide.
    The forward/complain machinery must detect the suppression, depose
    the censor, and the new leader orders everything that pooled at
    honest replicas — nothing is lost."""
    asyncio.run(
        byzantine_round("censor", requests=8, spike_rate=10.0, verbose=False)
    )


def test_stale_view_replay_is_observed_not_punished(tmp_path):
    """The actor records view-0 votes, the cluster moves on (muted leader
    -> view change), and the actor replays the recorded stale votes.
    Replays are COUNTED per sender (stale_view) but never shun: an honest
    replica racing a view change emits the same shape."""
    asyncio.run(byzantine_round("stale", requests=12, verbose=False))


def test_sync_poisoning_rejected_and_liar_donor_shunned(tmp_path):
    """A rejoining replica syncs from donors while one serves forged
    tails (below-quorum certificates) and a garbage snapshot offer, and
    the honest donors keep committing mid-sync.  Every poisoned payload
    is rejected by the certificate checks, the liar is attributed
    (``sync_poisoned``), crosses the donor-shun threshold, and is not
    even asked on the next pass — while the rejoiner still reaches the
    live height from the honest donors."""
    obs = asyncio.run(sync_poison_round(str(tmp_path)))
    assert obs["height"] == obs["target_height"]
    assert obs["sync_poisoned"].get(obs["liar"], 0) >= obs["shun_threshold"]
    assert all(obs["sync_poisoned"].get(p, 0) == 0
               for p in obs["honest_asks"])
    assert obs["liar_asks_total"] == obs["liar_asks_pass1"]
    assert all(c > 0 for c in obs["honest_asks"].values())


# -- satellite: bounded decode memos under a unique-forged-message flood ------

def test_actor_flood_of_unique_wire_messages_bounds_memos(tmp_path):
    """The actor broadcasts thousands of wire-unique forged (unsigned)
    Prepares through the real in-process network: every one churns the
    global intern memo, none may grow it past its LRU bound (eviction
    counters grow instead), and the per-provider sig-msg decode memos
    stay bounded too.  The cluster still orders requests afterwards."""

    async def run():
        cluster = ChaosCluster(str(tmp_path), n=4, depth=1, rotation=True,
                               seed=7, byzantine=True)
        await cluster.start()
        try:
            actor = cluster.install_actor(4)
            clear_intern_memo()
            before = PROTOCOL_PLANE.snapshot()
            flood = INTERN_MEMO_BOUND + 512
            await actor.flood_unique_prepares(flood)
            assert actor.forged_prepares == flood
            # drain the flood through the inboxes AND prove liveness on top
            await cluster.run_schedule([], requests=4, settle_timeout=600.0)
            after = PROTOCOL_PLANE.snapshot()
            assert intern_memo_len() <= INTERN_MEMO_BOUND
            assert (after["intern_evictions"]
                    - before["intern_evictions"]) >= 512
            for a in cluster.live_apps():
                memo = a.crypto._sig_msg_memo
                assert len(memo) <= memo.bound
            Invariants.fork_free(cluster)
        finally:
            await cluster.stop()

    asyncio.run(run())


# -- satellite: per-sender verify attribution in the provider -----------------

def _toy_providers(ids=(1, 2, 3), metrics=None):
    from smartbft_tpu.crypto.provider import Keyring

    rings = Keyring.generate(list(ids), seed=b"attribution",
                             scheme=toy_scheme)
    provs = {i: toy_scheme.ToyCryptoProvider(rings[i]) for i in ids}
    if metrics is not None:
        for p in provs.values():
            p.configure_fault_policy(metrics=metrics)
    return provs


def _proposal():
    return Proposal(header=b"h", payload=b"p", metadata=b"m")


def test_provider_attributes_invalid_sig_to_signer():
    bundle = MetricsBundle(InMemoryProvider())
    provs = _toy_providers(metrics=bundle.tpu)
    prop = _proposal()
    good = provs[2].sign_proposal(prop, b"aux")
    forged = Signature(signer=2, value=b"\x00" * len(good.value),
                       msg=good.msg)
    with pytest.raises(ValueError):
        provs[1].verify_consenter_sig(forged, prop)
    assert provs[1].invalid_by_signer[2]["invalid_sig"] == 1
    # the labeled tpu counter carries the same attribution
    key = "consensus.tpu.count_invalid_votes{2}"
    assert bundle.provider.counters[key] == 1.0
    # an honest signature verifies clean and attributes nothing
    assert provs[1].verify_consenter_sig(good, prop) == b"aux"
    assert 2 in provs[1].invalid_by_signer
    assert provs[1].invalid_by_signer[2] == {"invalid_sig": 1}


def test_provider_batch_path_attributes_each_cause_separately():
    provs = _toy_providers(ids=(1, 2, 3))
    prop = _proposal()
    other = Proposal(header=b"x", payload=b"y", metadata=b"z")
    good = provs[2].sign_proposal(prop, b"a2")
    bad_value = Signature(signer=3, value=b"\x00" * len(good.value),
                          msg=provs[3].sign_proposal(prop, b"a3").msg)
    foreign = provs[3].sign_proposal(other, b"a3")     # binding mismatch
    outsider = Signature(signer=9, value=good.value, msg=good.msg)
    auxes = provs[1].verify_consenter_sigs_batch(
        [good, bad_value, foreign, outsider], prop
    )
    assert auxes == [b"a2", None, None, None]
    by = provs[1].invalid_by_signer
    assert by[3] == {"invalid_sig": 1, "binding_mismatch": 1}
    assert by[9] == {"unknown_signer": 1}
    assert 2 not in by


def test_provider_feeds_misbehavior_table_when_wired():
    provs = _toy_providers(ids=(1, 2))
    table = MisbehaviorTable(self_id=1, shun_threshold=2)
    provs[1].configure_misbehavior(table)
    prop = _proposal()
    good = provs[2].sign_proposal(prop, b"aux")
    forged = Signature(signer=2, value=b"\x00" * len(good.value),
                       msg=good.msg)
    for _ in range(2):
        with pytest.raises(ValueError):
            provs[1].verify_consenter_sig(forged, prop)
    assert table.is_shunned(2)
    assert table.counts(2) == {"invalid_sig": 2}


# -- satellite: the misbehavior table itself ----------------------------------

def test_misbehavior_only_provable_causes_shun():
    t = MisbehaviorTable(self_id=0, shun_threshold=3)
    for cause in OBSERVED_CAUSES:
        t.note(5, cause, n=100)
    assert not t.is_shunned(5) and t.score(5) == 0.0
    for cause in sorted(PROVABLE_CAUSES):
        t.note(5, cause)
    assert t.is_shunned(5)          # 3 provable notes = threshold
    assert t.shun_events == 1
    snap = t.snapshot()
    assert snap["shunned"] == [5]
    assert snap["by_sender"][5]["stale_view"] == 100


def test_misbehavior_never_shuns_self():
    t = MisbehaviorTable(self_id=4, shun_threshold=2)
    t.note(4, "invalid_sig", n=50)
    assert not t.is_shunned(4)
    assert t.snapshot()["by_sender"] == {}


def test_misbehavior_decay_releases_with_hysteresis():
    t = MisbehaviorTable(self_id=0, shun_threshold=4, release_threshold=1)
    t.note(7, "invalid_sig", n=4)
    assert t.is_shunned(7)
    t.decay()                       # 2.0 — above release threshold
    assert t.is_shunned(7)
    t.decay()                       # 1.0 — at the release threshold
    assert not t.is_shunned(7)
    assert t.release_events == 1
    # lifetime counts survive redemption; the score decays to nothing
    assert t.counts(7) == {"invalid_sig": 4}
    t.decay()
    t.decay()
    assert t.score(7) == 0.0


def test_misbehavior_shed_and_corroboration_accounting():
    t = MisbehaviorTable(self_id=0, shun_threshold=2)
    t.note(3, "invalid_sig", n=2)
    t.note_shed(3, n=5)
    # the SHARED blacklist naming a local suspect is corroboration;
    # naming an unsuspected node is not
    t.note_blacklisted([3, 8])
    snap = t.snapshot()
    assert snap["shed_votes"] == {3: 5}
    assert snap["corroborated"] == [3]


def test_misbehavior_validates_thresholds():
    with pytest.raises(ValueError):
        MisbehaviorTable(shun_threshold=0)
    with pytest.raises(ValueError):
        MisbehaviorTable(shun_threshold=2, release_threshold=2)


# -- satellite: the degraded probe beside its control -------------------------

@pytest.mark.slow
def test_byzantine_latency_probe_pair():
    """The paired probes: the forge run shuns + sheds, the no-actor
    control does neither, and both report an honest-path p99.  Slow (two
    full spike runs)."""

    async def paired():
        h = await byzantine_latency_probe(forge=False, rate=10.0)
        d = await byzantine_latency_probe(forge=True, rate=10.0)
        return h, d

    healthy, degraded = asyncio.run(paired())
    assert degraded["shun_events"] > 0 and degraded["shed_votes"] > 0
    assert healthy["shun_events"] == 0
    assert degraded["latency"]["p99_ms"] > 0
    assert healthy["latency"]["p99_ms"] > 0
