"""``chipbench.run`` end to end on the CPU with the tiny test-only
configuration, in a temporary copy to which a configuration, cells and a
layer metric were added as new files."""

import filecmp
import json
import os

import pytest

from conftest import ROOT, run_cell

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def last_json(proc):
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_added_files_edit_nothing_that_was_there(bench_copy):
    """The copy gained files and BENCHMARK.json entries; every file the
    benchmark already had is byte for byte what it was."""
    src = os.path.join(ROOT, "chipbench")
    added = []
    for dirpath, dirnames, files in os.walk(os.path.join(bench_copy,
                                                         "chipbench")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in files:
            mine = os.path.join(dirpath, name)
            rel = os.path.relpath(mine, os.path.join(bench_copy, "chipbench"))
            theirs = os.path.join(src, rel)
            if os.path.exists(theirs):
                assert filecmp.cmp(mine, theirs, shallow=False), rel
            else:
                added.append(rel)
    assert sorted(added) == [
        "configs/tiny-n4-openssl.json",
        "layer_metrics/polls_per_decision.py",
        "workloads/tiny4.open.json", "workloads/tiny4.rehearsal.json"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        before = json.load(f)
    with open(os.path.join(bench_copy, "BENCHMARK.json")) as f:
        after = json.load(f)
    for key, value in before.items():  # entries were added, none changed
        if isinstance(value, list) and value and isinstance(value[0], dict):
            assert after[key][:len(value)] == value
        else:
            assert after[key] == value


def test_rehearsal_end_to_end_line(bench_copy):
    out = last_json(run_cell(
        bench_copy, "--workload", "tiny4.rehearsal", "--seed",
        str(2 ** 31 + 12345), "--seconds", "2", "--trace", "0",
        "--allow-cpu"))
    assert KEYS <= set(out)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 100
    assert set(out["metrics"]) == {"throughput_tps", "commit_p50_ms",
                                   "commit_p95_ms", "setup_s"}
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert out["metrics"]["commit_p95_ms"]["value"] >= \
        out["metrics"]["commit_p50_ms"]["value"]
    # a rehearsal never passes for a chip run
    assert out["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(out["device"])


def test_traced_run_reports_the_added_layer_metric(bench_copy):
    out = last_json(run_cell(
        bench_copy, "--workload", "tiny4.rehearsal", "--seed", "7",
        "--seconds", "2", "--trace", "1", "--allow-cpu"))
    assert out["correct"] is True
    got = set(out["metrics"])
    # the added reader is found by name; the two trace readers find no
    # device plane in a CPU rehearsal, return nothing, and are left out
    assert got == {"reqs_per_decision", "protocol_us_per_decision",
                   "launches_per_decision", "verify_ms_per_launch",
                   "polls_per_decision"}
    assert out["metrics"]["reqs_per_decision"]["value"] == pytest.approx(
        20.0, rel=0.2)
    assert out["device"]["window_s"] > 0 and out["device"]["busy_s"] == 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_open_loop_cell_runs_from_a_data_file_alone(bench_copy):
    out = last_json(run_cell(
        bench_copy, "--workload", "tiny4.open", "--seed", "3",
        "--seconds", "2", "--trace", "0", "--allow-cpu"))
    assert out["correct"] is True
    assert 150 <= out["attempted"] <= 500  # ~150/s for 2 s
    assert out["metrics"]["throughput_tps"]["value"] == pytest.approx(
        150, rel=0.35)


def test_wrong_kernel_gives_correct_false_with_the_reason_before(bench_copy):
    """The tiny configuration's launches are served by the host engine; a
    configuration that expects the comb kernel must not pass."""
    path = os.path.join(bench_copy, "chipbench", "configs",
                        "tiny-n4-openssl.json")
    with open(path) as f:
        config = json.load(f)
    with open(path, "w") as f:
        json.dump(dict(config, expected_kernel="comb"), f)
    try:
        proc = run_cell(bench_copy, "--workload", "tiny4.rehearsal",
                        "--seed", "1", "--seconds", "1", "--trace", "0",
                        "--allow-cpu")
    finally:
        with open(path, "w") as f:
            json.dump(config, f)
    lines = proc.stdout.strip().splitlines()
    assert proc.returncode == 0
    assert json.loads(lines[-1])["correct"] is False
    assert "NOT CORRECT" in lines[-2] and "launches by kernel" in lines[-2]
    assert "'host'" in lines[-2]


def test_no_tpu_no_result(bench_copy):
    """Without a TPU a real run exits non-zero and prints no result."""
    proc = run_cell(bench_copy, "--workload", "committee64.saturated",
                    "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert "needs 1 TPU chip" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines()
                if ln.startswith("{")]


def test_unknown_cell_is_refused(bench_copy):
    proc = run_cell(bench_copy, "--workload", "no.such.cell", "--seed", "1",
                    "--seconds", "1", "--trace", "0", "--allow-cpu")
    assert proc.returncode != 0 and "no cell" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines()
                if ln.startswith("{")]


def test_sweep_finds_a_knee_in_one_process(bench_copy):
    proc = run_cell(bench_copy, "--workload", "tiny4.open", "--rates",
                    "100,200", "--seconds", "1.5", "--allow-cpu",
                    module="chipbench.sweep")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    rows, last = lines[:-1], lines[-1]
    assert [r["offered_per_s"] for r in rows] == [100.0, 200.0]
    assert all(r["goodput_per_s"] > 0 and r["p95_ms"] > 0 for r in rows)
    assert last["knee"]["last_ok"]["offered_per_s"] in (100.0, 200.0)
    assert last["device"]["platform"] == "cpu"
