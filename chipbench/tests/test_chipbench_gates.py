"""What makes a run ``correct: false``, each case on doctored evidence,
and that the reason is printed on the line before the result."""

import json
import random

import pytest

from chipbench import gates, reference
from chipbench.run import emit, judge

CLEAN_BREAKER = {"open": False, "opens": 0, "host_fallback_batches": 0,
                 "launch_failures": 0, "launch_timeouts": 0}
CLEAN = dict(by_kernel={"comb": 12, "pallas": 0, "xla": 0, "host": 0},
             expected_kernel="comb", breaker=CLEAN_BREAKER,
             mesh={"downgrades": 0}, compiles=[])


def verdict(window=None, ledgers=None, wave=None, settled=True,
            attempted=100, committed=100):
    return judge(wave_faults=wave or [], ledger_faults=ledgers or [],
                 window_faults=window or [], settled=settled,
                 attempted=attempted, committed=committed)


def test_a_clean_window_is_correct():
    assert gates.window_faults(**CLEAN) == []
    assert verdict() == (True, [])


@pytest.mark.parametrize("doctored, says", [
    (dict(by_kernel={"comb": 11, "pallas": 1, "xla": 0, "host": 0}),
     "launches by kernel"),
    (dict(by_kernel={"comb": 0, "host": 5}), "launches by kernel"),
    (dict(by_kernel={"comb": 0, "host": 0}), "no verify launch"),
    (dict(breaker=dict(CLEAN_BREAKER, open=True, opens=1)), "breaker opened"),
    (dict(breaker=dict(CLEAN_BREAKER, host_fallback_batches=2)),
     "fell back to the host"),
    (dict(breaker=dict(CLEAN_BREAKER, launch_failures=1)), "launch failure"),
    (dict(mesh={"downgrades": 1}), "mesh downgrade"),
    (dict(compiles=[("jit_ecdsa_verify_comb", 9.7, False)]),
     "compile(s) inside the window"),
])
def test_each_degraded_window_is_not_correct(doctored, says, capsys):
    faults = gates.window_faults(**dict(CLEAN, **doctored))
    assert faults and says in faults[0]
    correct, reasons = verdict(window=faults)
    assert not correct
    emit({"correct": correct, "attempted": 1, "failed": 0, "metrics": {},
          "device": {}}, reasons)
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1])["correct"] is False
    assert "NOT CORRECT" in lines[-2] and says in lines[-2]


def test_a_ledger_that_differs_is_not_correct():
    good = ["a:r0", "b:r0", "a:r1"]
    assert reference.ledger_faults({1: good, 2: list(good), 3: list(good)},
                                   good) == []
    forked = {1: good, 2: ["a:r0", "a:r1", "b:r0"], 3: list(good)}
    faults = reference.ledger_faults(forked, good)
    assert "replica 2 differs" in faults[0] and "position 1" in faults[0]
    assert not verdict(ledgers=faults)[0]
    short = {1: good, 2: good[:2]}
    assert "lengths 2 / 3" in reference.ledger_faults(short, good)[0]
    twice = {1: good + ["a:r0"], 2: good + ["a:r0"]}
    assert "more than once" in reference.ledger_faults(twice, good)[0]
    assert reference.not_exactly_once(twice, good) == 1
    lost = reference.ledger_faults({1: good, 2: list(good)}, good + ["c:r0"])
    assert "on no ledger" in lost[0]
    assert reference.not_exactly_once(forked, good) == 0
    assert reference.not_exactly_once(short, good) == 1


def test_unsettled_or_empty_runs_are_not_correct():
    assert "one height" in verdict(settled=False)[1][0]
    assert "no request" in verdict(attempted=0)[1][0]
    assert "nothing committed" in verdict(committed=0)[1][0]


def test_setup_wave_is_held_to_openssl_lane_by_lane():
    from smartbft_tpu.crypto import p256

    keys = [p256.keygen(b"gate-%d" % i) for i in range(4)]
    items, expect = gates.make_wave(p256, random.Random(2 ** 31 + 5), keys, 44)
    again, expect2 = gates.make_wave(p256, random.Random(2 ** 31 + 5), keys, 44)
    assert expect == expect2  # same seed, same corrupted lanes and messages
    assert [it[0] for it in items] == [it[0] for it in again]
    assert expect.count(False) == 4
    ref = reference.p256_verdicts(items)
    assert ref == expect  # the plain reference finds exactly those lanes
    assert reference.mask_faults(ref, ref, expect) == []
    wrong = list(ref)
    wrong[7] = not wrong[7]
    faults = reference.mask_faults(wrong, ref, expect)
    assert "1 lane(s) differ" in faults[0] and "[7]" in faults[0]
    assert not verdict(wave=faults)[0]
    assert "disagrees" in reference.mask_faults(ref, ref, [True] * 44)[0]
