"""``launch_handoff_ms`` (PR 38) on a hand-made account: the median
``verify.handin`` plus the median ``verify.handback``; None, never a raise
or a zero, on an account without the two waits (the parent's) and where
there is no account at all."""

import json
import os
import types

import pytest

from chipbench.run import read_layer_metric

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ACCOUNT = {
    "launch": {"launches": 3, "threads_started": 1},
    "waits": {"verify.wait": [4.0, 5.0, 6.0],
              "verify.handin": [0.05, 0.09, 0.07],
              "verify.handback": [0.30, 0.10, 0.20, 0.40]},
    "counters": {"decisions": 1, "launches": 3},
}


def test_reader_sums_the_two_medians():
    run = types.SimpleNamespace(account=ACCOUNT)
    assert read_layer_metric("launch_handoff_ms", run) == \
        pytest.approx(0.07 + 0.25)


@pytest.mark.parametrize("waits", [
    {"verify.wait": [4.0]},
    {"verify.wait": [4.0], "verify.handin": [0.1]},
    {"verify.wait": [4.0], "verify.handin": [], "verify.handback": []},
], ids=["parent", "handin-only", "empty"])
def test_reader_finds_nothing_without_both_waits(waits):
    for account in (dict(ACCOUNT, waits=waits), {}):
        run = types.SimpleNamespace(account=account)
        assert read_layer_metric("launch_handoff_ms", run) is None


def test_reader_is_declared_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (m,) = [m for m in bench["per_layer"] if m["name"] == "launch_handoff_ms"]
    assert m == {"name": "launch_handoff_ms", "unit": "ms",
                 "better": "lower", "source": "program_span",
                 "layer": "verify plane", "moves": "commit_p50_ms"}
