"""The channels deployment (``deployments/channels.py``) rehearsed on the
CPU: named channels through the harness on the OpenSSL engine to a result
line at 4 and at 2 channels, and each reason it adds to
``reference_faults`` driven to ``correct: false`` from a copy in which one
thing is broken underneath it."""

import json
import os
import shutil

import pytest

from conftest import DATA, ROOT, run_cell

WORKLOAD = {"loop": "closed", "clients": 40, "client_skew": 0,
            "channel_skew": 1.0, "forged_every": 3,
            "presigned_per_client": 3, "warmup_s": 0.5, "poll_ms": 2,
            "trace_s": 1, "drain_s": 10, "why": "test-only",
            "who": "the harness's tests"}
TINY = {
    "name": "chan-tiny", "source": "test-only",
    "deployment": "channelfaults",
    "what": "the channels deployment on the OpenSSL engine, test-only",
    "replicas": 4, "f": 1, "shards": 4,
    "channels": ["north", "east", "south", "west"], "chips": 1,
    "scheme": "p256", "engine": "openssl", "expected_kernel": "host",
    "pipeline_depth": 1,
    "configuration": {"request_batch_max_count": 10,
                      "request_batch_max_interval": 0.05,
                      "leader_rotation": False, "decisions_per_leader": 0},
    "coalescer": {"window_s": 0.002, "dedupe": True},
    "scheduler_tick_s": 0.005,
    "network": {"kind": "in-process", "injected_delay_ms": 0},
    "envelope": {"payload_bytes": 512},
    "identities": {"enrolled": [19, 10, 6, 5]},
    "setup_wave_lanes": 32, "guarantees": {}, "assumed": [], "reduced": [],
}
TWO = dict(TINY, shards=2, channels=["north", "east"],
           identities={"enrolled": [27, 13]})
CELLS = {
    "chan4.rehearsal": TINY,
    "chan2.rehearsal": TWO,
    "chan4.door": dict(TINY, fault="door_hashes_client"),
    "chan4.blind": dict(TINY, fault="door_and_replicas_blind"),
    "chan4.oneset": dict(TINY, fault="one_enrolled_set"),
    "chan4.copied": dict(TINY, fault="ledger_copied"),
    "chan4.mixed": dict(TINY, fault="mixed_not_counted"),
    "chan4.division": dict(TINY, identities={"enrolled": [10, 10, 10, 10]}),
    "chan4.names": dict(TINY, channels=["north", "east"]),
}


@pytest.fixture(scope="module")
def channels_copy(tmp_path_factory) -> str:
    root = str(tmp_path_factory.mktemp("channelscopy"))
    bench_dir = os.path.join(root, "chipbench")
    shutil.copytree(os.path.join(ROOT, "chipbench"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(DATA, "deployments", "channelfaults.py"),
                os.path.join(bench_dir, "deployments"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell, config in CELLS.items():
        name = "cfg-" + cell
        for sub, body in ((f"configs/{name}.json", dict(config, name=name)),
                          (f"workloads/{cell}.json",
                           dict(WORKLOAD, config=name))):
            with open(os.path.join(bench_dir, sub), "w") as f:
                json.dump(body, f)
        bench["configs"].append({
            "name": name, "source": "test-only", "reduced": [],
            "file": f"chipbench/configs/{name}.json", "why": "test-only"})
        bench["workloads"].append({
            "name": cell, "config": name, "traffic": cell.split(".")[1],
            "chips": 1, "why": "test-only"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root


def run(root, cell):
    proc = run_cell(root, "--workload", cell, "--seed", str(2 ** 31 + 34),
                    "--seconds", "2", "--trace", "0", "--allow-cpu")
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    return proc, lines, result


@pytest.mark.parametrize("cell, channels, keys", [
    ("chan4.rehearsal", 4, 16 + 40), ("chan2.rehearsal", 2, 8 + 40)])
def test_named_channels_through_the_harness_on_the_cpu(channels_copy, cell,
                                                       channels, keys):
    proc, lines, out = run(channels_copy, cell)
    assert out and out["correct"] is True and out["failed"] == 0, \
        proc.stdout[-3000:] + proc.stderr[-2000:]
    assert out["attempted"] > 100
    assert "deployment channelfaults" in proc.stdout
    assert f"{keys} keys to register" in proc.stdout  # every ring + clients
    assert "120 envelopes of 40 clients signed ahead" in proc.stdout
    said = next(ln for ln in lines if "chipbench: channels: " in ln)
    by_channel = eval(said.split("channels: ")[1].split(" envelopes")[0])
    assert len(by_channel) == channels and all(by_channel.values())
    # the sixth forgery came round, and every one was refused
    crossed = int(said.split("ordered by channel; ")[1].split(" ")[0])
    assert crossed >= 2 and f", {crossed} refused" in said
    # launches carried two channels' lanes, and the program counted them
    carried = int(said.split(" launches in the window, ")[1].split(" ")[0])
    counted = int(said.split("the program counted ")[1].split(" ")[0])
    assert 0 < carried <= counted
    fabric = next(ln for ln in lines if "chipbench: fabric: " in ln
                  and "honest envelopes" in ln)
    for how in ("bit_of_r", "bit_of_s", "byte_of_payload",
                "another_enrolled_key", "key_not_enrolled"):
        assert how in fabric


@pytest.mark.parametrize("cell, says", [
    ("chan4.door", "the front door did not place them by the channel they "
                   "name"),
    ("chan4.blind", "committed envelope(s) name another channel than the "
                    "one that ordered them"),
    ("chan4.oneset", "enrolled on another channel only were ACCEPTED at the "
                     "front door"),
    ("chan4.oneset", "whose creator is not enrolled on the channel that "
                     "ordered them"),
    ("chan4.copied", "envelope(s) are on the ledgers of two channels"),
    ("chan4.mixed", "were not counted as mixed by the program"),
])
def test_each_reason_the_deployment_adds_ends_not_correct(channels_copy,
                                                          cell, says):
    proc, lines, out = run(channels_copy, cell)
    assert out is not None, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert out["correct"] is False
    reasons = [ln for ln in lines if "NOT CORRECT" in ln]
    assert any(says in ln for ln in reasons), reasons
    n = out["compared"]["ledger_faults"]
    assert n["value"] > n["max"]


@pytest.mark.parametrize("cell, says", [
    ("chan4.division", "divide into [19, 10, 6, 5], the configuration "
                       "enrols [10, 10, 10, 10]"),
    ("chan4.names", "4 shards need as many channels"),
])
def test_a_file_that_contradicts_itself_is_refused(channels_copy, cell,
                                                   says):
    proc, lines, out = run(channels_copy, cell)
    assert proc.returncode != 0 and says in proc.stderr, proc.stderr[-2000:]
    assert out is None


def test_the_real_cell_divides_its_clients_as_the_issue_says():
    from chipbench import deploy

    channels = deploy.load_deployment("channels")
    assert channels.zipf_division(4167, 4, 1.0) == [2000, 1000, 667, 500]
    assert channels.zipf_division(1000, 1, 1.0) == [1000]
    assert channels.zipf_division(40, 4, 0.0) == [10, 10, 10, 10]
    _bench, cell, config, workload, dep = deploy.load_cell(
        "channels4.saturated")
    assert cell["chips"] == 1 and config["reduced"] == []
    assert dep.populations == [2000, 1000, 667, 500]
    assert [dep.shard_of(i) for i in (0, 1999, 2000, 2999, 3000, 3666,
                                      3667, 4166)] == [0, 0, 1, 1, 2, 2, 3, 3]
    signed = bytes.fromhex("00000001610000000162") + (28).to_bytes(4, "big") \
        + channels.MAGIC + b"\x08channel3" + b"pay"
    assert channels.named_channel(signed) == "channel3"
    assert channels.named_channel(signed[:10] + (3).to_bytes(4, "big")
                                  + b"pay") is None


def test_the_four_readers_on_a_small_account():
    """The new readers divide the ``channels`` block of the program's
    account; where the account has none (the parent's), they return
    nothing."""
    from types import SimpleNamespace

    from chipbench import deploy

    def read(name, run):
        return deploy.load_by_file("layer_metrics", name).read(run)

    account = {"channels": {
        "launches": 40, "mixed_launches": 10,
        "kernels": {"pallas": {"launches": 8, "used": 6000},
                    "comb": {"launches": 36, "used": 700}},
        "per_channel": {
            "0": {"decisions": 4, "requests": 2000, "verify_wait_ms": 30.0},
            "1": {"decisions": 4, "requests": 1500, "verify_wait_ms": 45.5},
            "2": {"decisions": 2, "requests": 400, "verify_wait_ms": None},
            "3": {"decisions": 1, "requests": 100, "verify_wait_ms": 12.0}},
    }}
    run = SimpleNamespace(account=account, config={"engine": "jax"})
    assert read("channels_mixed_launch_pct", run) == pytest.approx(25.0)
    assert read("request_lanes_per_launch", run) == pytest.approx(750.0)
    assert read("channel_verify_wait_worst_ms", run) == pytest.approx(45.5)
    assert read("channel_tps_min_share_pct", run) == pytest.approx(10.0)
    for empty in ({"counters": {"decisions": 3}}, {"channels": {}}):
        bare = SimpleNamespace(account=empty, config={"engine": "jax"})
        for name in ("channels_mixed_launch_pct", "request_lanes_per_launch",
                     "channel_verify_wait_worst_ms",
                     "channel_tps_min_share_pct"):
            assert read(name, bare) is None
