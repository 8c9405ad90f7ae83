"""The mesh deployment (``deployments/mesh.py``) rehearsed on four virtual
CPU devices: the harness to a result line through a ``MeshVerifyEngine``,
each new reason driven to ``correct: false``, and the three new readers
on a small account."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import DATA, ROOT, run_cell

FOUR = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
TWO = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
WORKLOAD = {"loop": "closed", "clients": 40, "client_skew": 0,
            "warmup_s": 0.5, "poll_ms": 2, "trace_s": 1, "drain_s": 10,
            "why": "test-only", "who": "the harness's tests"}
TINY = {
    "name": "mesh-tiny", "source": "test-only", "deployment": "meshfaults",
    "what": "the mesh deployment on the toy scheme, test-only",
    "replicas": 4, "f": 1, "shards": 1, "chips": 4, "scheme": "toy",
    "engine": "mesh", "expected_kernel": "xla", "pipeline_depth": 1,
    "configuration": {"request_batch_max_count": 20,
                      "request_batch_max_interval": 0.05,
                      "leader_rotation": False, "decisions_per_leader": 0,
                      "verify_mesh_devices": 4,
                      "verify_mesh_topology": "1d"},
    "coalescer": {"window_s": 0.002, "dedupe": True},
    "scheduler_tick_s": 0.005,
    "network": {"kind": "in-process", "injected_delay_ms": 0},
    "setup_wave_lanes": 64, "guarantees": {}, "assumed": [], "reduced": [],
}


def with_configuration(**fields) -> dict:
    return dict(TINY, configuration=dict(TINY["configuration"], **fields))


CELLS = {
    "m4.rehearsal": TINY,
    # the deployment as the benchmark states it: the comb kernel expected,
    # which no CPU backend serves — the control's reason, on the CPU
    "m4.comboff": dict(TINY, expected_kernel="comb"),
    # a one-device engine behind the stated configuration: the program
    # graduates it to the mesh at start, never prewarmed (the harness's
    # own gate); and behind a program never told of the mesh: it stays
    "m4.onedevice": dict(TINY, engine="jax"),
    "m4.nevertold": dict(TINY, engine="jax", fault="never_told"),
    "m4.narrow": dict(TINY, fault="narrow_launch"),
    "m4.width": with_configuration(verify_mesh_devices=2),
}


@pytest.fixture(scope="module")
def mesh_copy(tmp_path_factory) -> str:
    root = str(tmp_path_factory.mktemp("meshcopy"))
    bench_dir = os.path.join(root, "chipbench")
    shutil.copytree(os.path.join(ROOT, "chipbench"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name in ("meshfaults.py", "toyring.py"):
        shutil.copy(os.path.join(DATA, "deployments", name),
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
            "chips": 4, "why": "test-only"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root


def run(root, cell, devices=FOUR):
    proc = run_cell(root, "--workload", cell, "--seed", str(2 ** 31 + 32),
                    "--seconds", "2", "--trace", "0", "--allow-cpu",
                    env=devices)
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    return proc, lines, result


def test_the_mesh_deployment_through_the_harness_on_four_cpu_devices(
        mesh_copy):
    proc, lines, out = run(mesh_copy, "m4.rehearsal")
    assert out and out["correct"] is True and out["failed"] == 0, \
        proc.stdout[-3000:] + proc.stderr[-2000:]
    assert "deployment meshfaults, engine MeshVerifyEngine" in proc.stdout
    said = json.loads(next(ln for ln in lines if ln.startswith(
        "chipbench: mesh: ")).split("mesh: ", 1)[1])
    assert said["devices"] == 4 and said["launches_below_width"] == 0
    assert said["io_devices_last"] == [4, 4] and said["launches"] > 0
    assert out["compared"]["mesh_downgrades"] == {"value": 0, "max": 0}
    assert out["compared"]["launches_off_kernel"] == {"value": 0, "max": 0}


@pytest.mark.parametrize("cell, devices, says, number", [
    ("m4.comboff", FOUR, "want all under 'comb'", "launches_off_kernel"),
    ("m4.onedevice", FOUR, "the program replaced the engine",
     "engine_replaced"),
    ("m4.nevertold", FOUR, "a JaxVerifyEngine of 1 device(s), not a "
                           "MeshVerifyEngine of 4", "ledger_faults"),
    ("m4.nevertold", FOUR, "configured for a mesh of 0 device(s)",
     "ledger_faults"),
    ("m4.narrow", FOUR, "laid out over fewer than 4 devices",
     "ledger_faults"),
    # a host with two devices told to build a mesh of four: the program
    # keeps the one-device engine and counts a downgrade
    ("m4.onedevice", TWO, "mesh downgrade(s)", "mesh_downgrades"),
    ("m4.onedevice", TWO, "not a MeshVerifyEngine of 4", "ledger_faults"),
])
def test_each_reason_ends_not_correct(mesh_copy, cell, devices, says,
                                      number):
    proc, lines, out = run(mesh_copy, cell, devices)
    assert out is not None, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert out["correct"] is False
    reasons = [ln for ln in lines if "NOT CORRECT" in ln]
    assert any(says in ln for ln in reasons), reasons
    n = out["compared"][number]
    assert n["value"] > n["max"]


def test_a_host_with_fewer_devices_gives_no_result(mesh_copy):
    """The mesh engine of the cell's width cannot be built: the run ends
    non-zero with no result line (on a TPU the harness's device stamp
    refuses fewer chips than the cell asks before that)."""
    proc, lines, out = run(mesh_copy, "m4.rehearsal", TWO)
    assert proc.returncode != 0 and out is None
    assert "MeshUnavailable" in proc.stderr


def test_a_width_the_cell_does_not_run_on_is_refused(mesh_copy):
    proc, lines, out = run(mesh_copy, "m4.width")
    assert proc.returncode != 0 and out is None
    assert "verify_mesh_devices=2, the cell runs on 4" in proc.stderr


def test_a_program_without_the_count_is_refused_at_once(mesh_copy, tmp_path):
    """What the parent commit does with these files: its MeshVerifyStats
    has no ``launches_below_width``, so the cell exits non-zero before
    JAX is touched.  Shown with a stand-in ``smartbft_tpu`` first on the
    path whose stats class lacks the field."""
    pkg = tmp_path / "smartbft_tpu" / "crypto"
    pkg.mkdir(parents=True)
    (tmp_path / "smartbft_tpu" / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "provider.py").write_text("class MeshVerifyStats:\n    pass\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", **FOUR,
               PYTHONPATH=str(tmp_path) + os.pathsep + ROOT)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "m4.rehearsal",
         "--seed", "1", "--seconds", "2", "--trace", "0", "--allow-cpu"],
        cwd=mesh_copy, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "launches_below_width" in proc.stderr
    assert not proc.stdout.strip().endswith("}")


def test_the_three_readers_on_a_small_account():
    from types import SimpleNamespace

    from chipbench import deploy

    def read(name, account):
        return deploy.load_by_file("layer_metrics", name).read(
            SimpleNamespace(account=account))

    account = {
        "busy": {"smartbft-verify-launch": {
            "verify.pack": {"calls": 4, "self_s": 0.004},
            "verify.place": {"calls": 4, "self_s": 0.006},
            "verify.device": {"calls": 4, "self_s": 0.010}}},
        "counters": {"launches": 4},
        "mesh": {"launches": 4, "spanning": 3, "used": 64, "launched": 2048,
                 "used_by_device": [16, 16, 16, 16],
                 "launched_by_device": [512, 512, 512, 512]},
    }
    assert read("mesh_place_ms_per_launch", account) == pytest.approx(1.5)
    assert read("mesh_fill_pct", account) == pytest.approx(3.125)
    assert read("mesh_spanning_pct", account) == pytest.approx(75.0)
    # the parent's account: no mesh block, no verify.place span; and a
    # one-chip cell's: a mesh block that saw no launch
    for lacking in ({}, {"busy": account["busy"], "counters": {}},
                    dict(account, mesh={"launches": 0, "spanning": 0,
                                        "used": 0, "launched": 0}),
                    dict(account, busy={})):
        for name in ("mesh_place_ms_per_launch", "mesh_fill_pct",
                     "mesh_spanning_pct"):
            if lacking.get("mesh", {}).get("launches") and \
                    name != "mesh_place_ms_per_launch":
                continue  # these two read the block alone
            assert read(name, lacking) is None, (name, lacking)
