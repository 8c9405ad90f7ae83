"""The fabric deployment (``deployments/fabric.py``) rehearsed on the CPU:
signed envelopes through the harness on the OpenSSL engine to a result
line, and each reason of its ``reference_faults`` driven to ``correct:
false`` from a copy in which one thing is broken underneath it."""

import json
import os
import shutil

import pytest

from conftest import DATA, ROOT, run_cell

WORKLOAD = {"loop": "closed", "clients": 40, "client_skew": 0,
            "forged_every": 5, "presigned_per_client": 3, "warmup_s": 0.5, "poll_ms": 2, "trace_s": 1,
            "drain_s": 10, "why": "test-only", "who": "the harness's tests"}
TINY = {
    "name": "fab-tiny", "source": "test-only", "deployment": "fabricfaults",
    "what": "the fabric deployment on the OpenSSL engine, test-only",
    "replicas": 4, "f": 1, "shards": 1, "chips": 1, "scheme": "p256",
    "engine": "openssl", "expected_kernel": "host", "pipeline_depth": 1,
    "configuration": {"request_batch_max_count": 20,
                      "request_batch_max_interval": 0.05,
                      "leader_rotation": False, "decisions_per_leader": 0},
    "coalescer": {"window_s": 0.002, "dedupe": True},
    "scheduler_tick_s": 0.005,
    "network": {"kind": "in-process", "injected_delay_ms": 0},
    "envelope": {"payload_bytes": 3072}, "identities": {"enrolled": 48},
    "setup_wave_lanes": 32, "guarantees": {}, "assumed": [], "reduced": [],
}
CELLS = {
    "fab4.rehearsal": TINY,
    "fab4.forged": dict(TINY, fault="forged_let_through"),
    "fab4.refused": dict(TINY, fault="honest_refused"),
    "fab4.altered": dict(TINY, fault="ledger_altered"),
    "fab4.lanes": dict(TINY, fault="lanes_fewer"),
    "fab4.unread": dict(TINY, colour="blue"),
    "fab4.nested": dict(TINY, envelope={"payload_bytes": 3072, "pad": 1}),
}


@pytest.fixture(scope="module")
def fabric_copy(tmp_path_factory) -> str:
    root = str(tmp_path_factory.mktemp("fabriccopy"))
    bench_dir = os.path.join(root, "chipbench")
    shutil.copytree(os.path.join(ROOT, "chipbench"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(DATA, "deployments", "fabricfaults.py"),
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
    proc = run_cell(root, "--workload", cell, "--seed", str(2 ** 31 + 28),
                    "--seconds", "2", "--trace", "0", "--allow-cpu")
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    return proc, lines, result


def test_signed_envelopes_through_the_harness_on_the_cpu(fabric_copy):
    proc, lines, out = run(fabric_copy, "fab4.rehearsal")
    assert out and out["correct"] is True and out["failed"] == 0, \
        proc.stdout[-3000:] + proc.stderr[-2000:]
    assert out["attempted"] > 100
    assert "deployment fabricfaults" in proc.stdout
    assert "52 keys to register" in proc.stdout  # 4 orderers + 48 clients
    # the clients' first envelopes were signed ahead, the rest when sent
    assert "120 envelopes of 40 clients signed ahead" in proc.stdout
    said = next(ln for ln in lines if "chipbench: fabric: " in ln
                and "honest envelopes" in ln)
    # every forged envelope refused, all five ways, and none judged valid
    forged = int(said.split(" forged ")[0].rsplit(" ", 1)[1])
    assert forged >= 5 and f"{forged} refused" in said
    for how in ("bit_of_r", "bit_of_s", "byte_of_payload",
                "another_enrolled_key", "key_not_enrolled"):
        assert how in said
    # the front door (replica 1 leads) counted them by cause
    at_door = said.split("replica {1: ")[1].split("}")[0]
    assert "'not_enrolled': 0" not in at_door \
        and "'bad_signature': 0" not in at_door


@pytest.mark.parametrize("cell, says", [
    ("fab4.forged", "forged envelope(s) were ACCEPTED at the front door"),
    ("fab4.forged", "forged envelope(s) on the ledger of"),
    ("fab4.refused", "honest envelope(s) were refused"),
    ("fab4.altered", "committed envelope(s) differ from the bytes submitted"),
    ("fab4.altered", "OpenSSL REJECTS 1 committed envelope(s)"),
    ("fab4.lanes", "lane(s) ran on the 'host' kernel in the window"),
])
def test_each_reason_of_the_reference_ends_not_correct(fabric_copy, cell,
                                                       says):
    proc, lines, out = run(fabric_copy, cell)
    assert out is not None, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert out["correct"] is False
    reasons = [ln for ln in lines if "NOT CORRECT" in ln]
    assert any(says in ln for ln in reasons), reasons
    n = out["compared"]["ledger_faults"]
    assert n["value"] > n["max"]


@pytest.mark.parametrize("cell, says", [
    ("fab4.unread", "states ['colour'], which nothing reads"),
    ("fab4.nested", "envelope / identities keys nothing reads"),
])
def test_a_key_nobody_reads_is_refused(fabric_copy, cell, says):
    proc, lines, out = run(fabric_copy, cell)
    assert proc.returncode != 0 and says in proc.stderr, proc.stderr[-2000:]
    assert out is None


def test_the_five_readers_on_a_small_account():
    """The new readers divide the program's account (and, for the kernel's
    time, the trace's modules by the generic kernel's exact name); where
    the account lacks what they read, they return nothing."""
    from types import SimpleNamespace

    from chipbench import deploy
    from chipbench.trace import TraceSummary

    def read(name, run):
        return deploy.load_by_file("layer_metrics", name).read(run)

    trace = TraceSummary(modules={
        "jit_ecdsa_verify(123)": (0.050, 2),
        "jit_ecdsa_verify_comb(77)": (0.004, 6),
    })
    account = {
        "counters": {"decisions": 2},
        "lanes": {"pallas": {"launches": 4, "launched": 2560, "used": 2020},
                  "comb": {"launches": 6, "launched": 48, "used": 18}},
        "waits": {"request.verify": [10.0, 30.0, 20.0],
                  "proposal.verify": [40.0]},
    }
    run = SimpleNamespace(account=account, trace=trace)
    assert read("pallas_us_per_sig", run) == pytest.approx(1e6 * 0.05 / 2020)
    assert read("pallas_fill_pct", run) == pytest.approx(100 * 2020 / 2560)
    assert read("envelope_lanes_per_decision", run) == 1010
    assert read("request_verify_wait_ms", run) == 20.0
    assert read("proposal_verify_wait_ms", run) == 40.0
    old = SimpleNamespace(account={"counters": {"decisions": 2},
                                   "waits": {}}, trace=trace)
    for name in ("pallas_us_per_sig", "pallas_fill_pct",
                 "envelope_lanes_per_decision", "request_verify_wait_ms",
                 "proposal_verify_wait_ms"):
        assert read(name, old) is None
