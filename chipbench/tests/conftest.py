"""Shared helpers of the harness's own tests (``python -m pytest
chipbench/tests -q``; tier-1 collects ``tests/`` only)."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def add_tiny_cells(root: str) -> None:
    """Add a configuration, two cells and a layer metric to the benchmark
    copy at ``root`` as NEW files plus entries; no file that was there is
    edited except BENCHMARK.json, which only gains entries."""
    bench_dir = os.path.join(root, "chipbench")
    shutil.copy(os.path.join(DATA, "tiny-n4-openssl.json"),
                os.path.join(bench_dir, "configs"))
    for cell in ("tiny4.rehearsal", "tiny4.open"):
        shutil.copy(os.path.join(DATA, f"{cell}.json"),
                    os.path.join(bench_dir, "workloads"))
    shutil.copy(os.path.join(DATA, "polls_per_decision.py"),
                os.path.join(bench_dir, "layer_metrics"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-n4-openssl", "source": "test-only",
        "file": "chipbench/configs/tiny-n4-openssl.json", "reduced": [],
        "why": "test-only"})
    for cell in ("tiny4.rehearsal", "tiny4.open"):
        bench["workloads"].append({
            "name": cell, "config": "tiny-n4-openssl",
            "traffic": cell.split(".")[1], "chips": 1, "why": "test-only"})
    bench["per_layer"].append({
        "name": "polls_per_decision", "unit": "polls", "better": "lower",
        "source": "program_counter", "layer": "harness",
        "moves": "throughput_tps",
        "workloads": ["tiny4.rehearsal", "tiny4.open"]})
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)


@pytest.fixture(scope="session")
def bench_copy(tmp_path_factory) -> str:
    """A temporary copy of the benchmark (BENCHMARK.json + chipbench/) with
    the tiny test-only cells added as new files."""
    root = str(tmp_path_factory.mktemp("benchcopy"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    add_tiny_cells(root)
    return root


def run_cell(root: str, *argv: str, module: str = "chipbench.run",
             timeout: float = 180.0):
    """``python -m chipbench.run`` (or another ``module``) from ``root`` on
    the CPU, the program importable from the repository ->
    CompletedProcess."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return subprocess.run(
        [sys.executable, "-m", module, *argv], cwd=root, env=env,
        capture_output=True, text=True, timeout=timeout)
