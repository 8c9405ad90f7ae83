"""Every cell of ``BENCHMARK.json`` against the program as it stands.

``chipbench`` refuses, at run time and on the chip, a configuration file
whose ``Configuration`` fields the program no longer has, whose coalescer
arguments the program no longer derives, or whose set-up wave fits no rung
of the program's pad ladder: the cell then ends with no result, one cell at
a time.  These tests hold the same files to the same program here, on the
CPU, so that a product PR that renames a field or moves a derivation fails
before it reaches the chip.

Nothing is imported from ``chipbench``: the files are data, and what they
are held to is the library's own code (``Configuration``,
``ShardedCluster``, ``crypto.ladder``).
"""

import dataclasses
import json
import pathlib
import types

import pytest

from smartbft_tpu.config import Configuration
from smartbft_tpu.crypto.ladder import auto_pad_sizes, request_pad_sizes
from smartbft_tpu.testing.sharded import ShardedCluster

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {entry["name"]: json.loads((ROOT / entry["file"]).read_text())
           for entry in BENCHMARK["configs"]}
WORKLOADS = {w["name"]: w for w in BENCHMARK["workloads"]}

per_config = pytest.mark.parametrize("name", sorted(CONFIGS))


def vote_ladder(config: dict) -> tuple:
    return tuple(auto_pad_sizes(config["replicas"], config["scheme"],
                                config["pipeline_depth"]))


def request_ladder(config: dict) -> tuple:
    """The arbitrary-key kernel's ladder, where requests are signed."""
    if "envelope" not in config:
        return ()
    return tuple(request_pad_sizes(
        config["configuration"]["request_batch_max_count"]))


def test_every_configuration_file_is_declared():
    on_disk = {p.stem for p in (ROOT / "chipbench" / "configs").glob("*.json")}
    assert on_disk == set(CONFIGS)


@per_config
def test_configuration_fields_exist_and_validate(name):
    config = CONFIGS[name]
    built = dataclasses.replace(Configuration(self_id=1),
                                **config["configuration"])
    built.validate()
    assert built.pipeline_depth == config["pipeline_depth"]


@per_config
def test_coalescer_arguments_are_what_the_program_derives(name, tmp_path):
    config = CONFIGS[name]
    fields = dict(config["configuration"])
    # the shared engine only lends its ladder to the derivation
    engine = types.SimpleNamespace(pad_sizes=vote_ladder(config))
    cluster = ShardedCluster(
        tmp_path, shards=config["shards"], n=config["replicas"],
        depth=config["pipeline_depth"], crypto=config["scheme"],
        engine=engine, window=config["coalescer"]["window_s"],
        config_fn=lambda _shard, node: dataclasses.replace(
            Configuration(self_id=node), **fields),
        journal=False,
    )
    assert cluster.coalescer.max_batch == config["coalescer"]["max_batch"]
    assert cluster.coalescer.dedupe == config["coalescer"]["dedupe"]
    assert cluster.coalescer.window == config["coalescer"]["window_s"]


@per_config
def test_pad_ladders_hold_the_configuration_s_waves(name):
    config = CONFIGS[name]
    ladders = [vote_ladder(config), request_ladder(config)]
    for ladder in filter(None, ladders):
        assert list(ladder) == sorted(set(ladder)), ladder
    votes, requests = ladders
    # one decision's vote wave after dedupe: one signature a replica
    assert votes[-1] >= config["replicas"]
    if requests:
        assert requests[-1] >= \
            config["configuration"]["request_batch_max_count"]
    assert config["setup_wave_lanes"] in set(votes) | set(requests)


@pytest.mark.parametrize("cell", sorted(WORKLOADS))
def test_workload_names_files_that_agree(cell):
    workload = WORKLOADS[cell]
    config = CONFIGS[workload["config"]]
    assert config["name"] == workload["config"]
    assert workload["chips"] == config["chips"]
    traffic = ROOT / "chipbench" / "workloads" / f"{cell}.json"
    assert traffic.is_file()
    assert json.loads(traffic.read_text())["config"] == workload["config"]
    assert cell.split(".", 1)[1] == workload["traffic"]
