"""The Ed25519 fabric deployment (``deployments/fabric_ed25519.py``)
rehearsed on the CPU: Ed25519-signed envelopes through the harness on the
OpenSSL engine to a result line, each of its six forgeries refused, and
two doctored engines driven to ``correct: false``."""

import json
import os
import shutil
from types import SimpleNamespace

import pytest

from conftest import DATA, ROOT, run_cell

WORKLOAD = {"loop": "closed", "clients": 40, "client_skew": 0,
            "forged_every": 4, "presigned_per_client": 3, "warmup_s": 0.5,
            "poll_ms": 2, "trace_s": 1, "drain_s": 10, "why": "test-only",
            "who": "the harness's tests"}
TINY = {
    "name": "fabed-tiny", "source": "test-only",
    "deployment": "fabric_ed25519",
    "what": "the Ed25519 fabric deployment on the OpenSSL engine, test-only",
    "replicas": 4, "f": 1, "shards": 1, "chips": 1, "scheme": "ed25519",
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
FAULTY = dict(TINY, deployment="fabric_ed25519faults")
CELLS = {
    "fabed4.rehearsal": TINY,
    "fabed4.door": dict(FAULTY, fault="door_open"),
    "fabed4.unreduced": dict(FAULTY, fault="s_unreduced"),
    "fabed4.p256": dict(TINY, scheme="p256"),
}
FORGERIES = ("bit_of_r", "bit_of_s", "byte_of_payload",
             "another_enrolled_key", "key_not_enrolled", "s_plus_l")


@pytest.fixture(scope="module")
def ed_copy(tmp_path_factory) -> str:
    root = str(tmp_path_factory.mktemp("fabedcopy"))
    bench_dir = os.path.join(root, "chipbench")
    shutil.copytree(os.path.join(ROOT, "chipbench"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(DATA, "deployments", "fabric_ed25519faults.py"),
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
    proc = run_cell(root, "--workload", cell, "--seed", str(2 ** 31 + 39),
                    "--seconds", "2", "--trace", "0", "--allow-cpu")
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    return proc, lines, result


def test_ed25519_envelopes_through_the_harness_on_the_cpu(ed_copy):
    proc, lines, out = run(ed_copy, "fabed4.rehearsal")
    assert out and out["correct"] is True and out["failed"] == 0, \
        proc.stdout[-3000:] + proc.stderr[-2000:]
    assert out["attempted"] > 50
    assert "deployment fabric_ed25519" in proc.stdout
    assert "52 keys to register" in proc.stdout  # 4 orderers + 48 clients
    assert "set-up wave, 32 lanes" in proc.stdout and \
        "mask == the plain reference" in proc.stdout
    said = next(ln for ln in lines if "chipbench: fabric_ed25519: " in ln
                and "honest envelopes" in ln)
    # every forged envelope refused, all six ways
    forged = int(said.split(" forged ")[0].rsplit(" ", 1)[1])
    assert forged >= 6 and f"{forged} refused" in said
    for how in FORGERIES:
        assert f"'{how}'" in said, said


@pytest.mark.parametrize("cell, says", [
    ("fabed4.door", "forged envelope(s) were ACCEPTED at the front door"),
    ("fabed4.door", "forged envelope(s) on the ledger of"),
    ("fabed4.unreduced", "forged envelope(s) were ACCEPTED at the front "
                         "door"),
])
def test_a_doctored_engine_ends_not_correct(ed_copy, cell, says):
    proc, lines, out = run(ed_copy, cell)
    assert out is not None, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert out["correct"] is False
    reasons = [ln for ln in lines if "NOT CORRECT" in ln]
    assert any(says in ln for ln in reasons), reasons
    if cell == "fabed4.unreduced":  # S + L and nothing else got through
        assert "(s_plus_l)" in next(ln for ln in reasons if says in ln)


def test_a_p256_configuration_is_refused(ed_copy):
    proc, _lines, out = run(ed_copy, "fabed4.p256")
    assert proc.returncode != 0 and out is None
    assert "identities are Ed25519" in proc.stderr, proc.stderr[-2000:]


def test_the_three_readers_on_a_small_account(monkeypatch):
    """The new readers divide the program's account (and, for the kernel's
    time, the trace's modules by the kernel's exact name); where the
    account lacks what they read, they return nothing."""
    from chipbench import deploy, ed25519_work, peaks
    from chipbench.trace import TraceSummary

    def read(name, run):
        return deploy.load_by_file("layer_metrics", name).read(run)

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = ("ed25519_us_per_sig", "ed25519_roofline_pct",
             "ed25519_prep_us_per_sig")
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in names:
        assert declared[name]["workloads"] == ["fabric4-ed25519.saturated"]
    trace = TraceSummary(modules={
        "jit_ed25519_verify(123)": (0.010, 2),
        "jit_eddsa_verify_comb(77)": (0.004, 6),
        "jit_ecdsa_verify(5)": (1.0, 1),
    })
    account = {
        "counters": {"decisions": 2},
        "lanes": {"pallas": {"launches": 2, "launched": 1024, "used": 1000,
                             "host_refused": {"s_not_reduced": 1}},
                  "comb": {"launches": 6, "launched": 48, "used": 18}},
        "prep": {"calls": 2, "lanes": 1000, "self_s": 0.008},
    }
    run = SimpleNamespace(account=account, trace=trace)
    assert read("ed25519_us_per_sig", run) == pytest.approx(10.0)
    assert read("ed25519_prep_us_per_sig", run) == pytest.approx(8.0)
    # on the CPU there is no published peak: nothing, not a number
    assert read("ed25519_roofline_pct", run) is None
    v5e = peaks.peaks_for("TPU v5 lite")
    monkeypatch.setattr(peaks, "peaks_for", lambda _kind: v5e)
    want = 100 * max(2 * 96 * 256 * 32 * 1024 / 0.010 / 197e12,
                     (328 * 1024 + 2 * 96 * 256 * 2) / 0.010 / 819e9)
    assert read("ed25519_roofline_pct", run) == pytest.approx(want)
    flops = ed25519_work.mxu_flops(1024)
    assert flops == 2 * 96 * 256 * 32 * 1024
    assert ed25519_work.hbm_bytes(1024, 2) == 328 * 1024 + 2 * 96 * 256 * 2
    old = SimpleNamespace(account={"counters": {"decisions": 2},
                                   "lanes": {}}, trace=trace)
    for name in names:
        assert read(name, old) is None
