"""The eleven readers of the loop hook's blocks (PR 37) on a hand-made
account; each returns None, never a raise or a zero, on an account without
its block (the parent's) and where there is no account at all."""

import json
import os
import types

import pytest

from chipbench.run import read_layer_metric

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ACCOUNT = {
    "interval": {"t0": 10.0, "t1": 12.0, "wall_s": 2.0, "ticks": 400},
    "loop": {"thread": "MainThread", "cpu_s": 1.6, "busy_self_s": 1.55,
             "turns": 9000, "steps_wall_s": 1.5, "steps_cpu_s": 1.4,
             "runs": 300, "runs_wall_s": 1.54, "lock_wait_s": 0.1,
             "between_s": 0.04, "outside_s": 0.2},
    "loop_steps": {"covered": True, "owners": [], "other": {},
                   "intervals": 700, "busy_s": 1.5},
    "launch": {"launches": 20,
               "verify.pack": {"dur_s": 0.20, "cpu_s": 0.08,
                               "off_cpu_s": 0.12},
               "verify.device": {"dur_s": 0.25, "cpu_s": 0.03,
                                 "off_cpu_s": 0.22}},
    "timeline": {"both_s": 0.3, "loop_only_s": 1.2, "launch_only_s": 0.15,
                 "neither_s": 0.35, "neither_fsync_s": 0.2},
    "busy": {"MainThread": {
        "loop.embedder": {"calls": 4000, "self_s": 0.3, "dur_s": 0.9,
                          "cpu_s": 0.8},
        "front.submit": {"calls": 2000, "self_s": 0.04, "dur_s": 0.09,
                         "cpu_s": 0.0},
        "request.pack": {"calls": 2000, "self_s": 0.05, "dur_s": 0.05,
                         "cpu_s": 0.0},
        "req.admit": {"calls": 2000, "self_s": 0.03, "dur_s": 0.03,
                      "cpu_s": 0.0}}},
    "counters": {"decisions": 5, "launches": 20},
}
TRACE = types.SimpleNamespace(busy_s=0.17)

EXPECTED = {
    "loop_turns_per_decision": 1800.0,
    "loop_embedder_pct": 20.0,
    "request_path_us_per_req": 60.0,
    "loop_lock_wait_pct": 5.0,
    "loop_outside_handles_pct": 15.0,  # (0.2 + 0.04) / 1.6
    "launch_lock_wait_ms_per_launch": 8.5,  # (0.12 + 0.22 - 0.17) / 20
    "verify_device_kernel_pct": 68.0,
    "tl_both_pct": 15.0,
    "tl_launch_only_pct": 7.5,
    "tl_loop_only_pct": 60.0,
    "tl_neither_pct": 17.5,
}

#: what an earlier commit's account holds of these blocks: nothing
PARENT = {k: v for k, v in ACCOUNT.items()
          if k not in ("loop_steps", "launch", "timeline")}
PARENT["loop"] = {k: ACCOUNT["loop"][k]
                  for k in ("thread", "cpu_s", "busy_self_s")}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_hand_made_account(name):
    run = types.SimpleNamespace(account=ACCOUNT, trace=TRACE)
    assert read_layer_metric(name, run) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_without_its_block(name):
    uncovered = dict(PARENT, loop_steps={"covered": False},
                     launch={"launches": 0})
    for account in (PARENT, uncovered, {}):
        run = types.SimpleNamespace(account=account, trace=TRACE)
        assert read_layer_metric(name, run) is None


def test_the_launch_readers_need_the_trace_and_floor_at_zero():
    untraced = types.SimpleNamespace(account=ACCOUNT, trace=None)
    assert read_layer_metric("launch_lock_wait_ms_per_launch",
                             untraced) is None
    assert read_layer_metric("verify_device_kernel_pct", untraced) is None
    long_trace = types.SimpleNamespace(
        account=ACCOUNT, trace=types.SimpleNamespace(busy_s=0.5))
    assert read_layer_metric("launch_lock_wait_ms_per_launch",
                             long_trace) == 0.0


def test_the_readers_are_declared_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert [m["name"] for m in bench["per_layer"][-11:]] == [
        "loop_turns_per_decision", "loop_embedder_pct",
        "request_path_us_per_req", "loop_lock_wait_pct",
        "loop_outside_handles_pct", "launch_lock_wait_ms_per_launch",
        "verify_device_kernel_pct", "tl_both_pct", "tl_launch_only_pct",
        "tl_loop_only_pct", "tl_neither_pct"]
    for name in EXPECTED:
        m = declared[name]
        assert "workloads" not in m and m["moves"] in e2e
        # the two that subtract or divide by the trace's kernel seconds
        # find nothing without a device trace, and say so by their source
        assert m["source"] == {
            "loop_turns_per_decision": "program_counter",
            "launch_lock_wait_ms_per_launch": "device_trace",
            "verify_device_kernel_pct": "device_trace",
        }.get(name, "program_span")
