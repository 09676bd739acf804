"""The per-layer metric that says what a decode step has to read of the
weights (ISSUE 46): ``serve_param_gb`` is a file under
``benchmark/layer_metrics`` of the existing reader kind ``counter`` over
the gauge ``engine/param_gb``, which the engine sets from the tree it was
handed and publishes again in its done poll (the driver clears the
registry after warm-up). Rehearsal numbers are CPU numbers at a toy size:
asserted for their form and for what holds on any clock."""

import json
import os
import time

import pytest

from benchmark import harness, readers, serve_driver
from manifest_cells import PPO_CELLS, every_serve_cell_and_no_ppo_cell
from test_benchmark_rehearsal import quiet_program, shrunk  # noqa: F401  (autouse fixture)

NAME = "serve_param_gb"


def test_the_gauge_reads_the_served_tree_from_a_serve_record():
    import jax

    cell = shrunk("serve-pythia1b4-chat")
    device = harness.require_chips(int(cell["chips"]), allow_cpu=True)
    out = serve_driver.run(cell, 2**31 + 46, 2.0, False, time.time(), device)
    assert out["correct"] is True and out["failed"] == 0
    record = out["record"]
    specs = {s["name"]: s for s in harness.load_layer_metrics("serve-pythia1b4-chat")}
    got = readers.read_all(record, [specs[NAME]])
    assert set(got) == {NAME} and got[NAME]["unit"] == "GB"
    assert got[NAME]["value"] == record["gauges"]["engine/param_gb"] > 0
    # bf16 arithmetic on float32 masters (the configuration's run.dtype and
    # run.param_dtype): the matrices are served at two bytes a value, and
    # all but a few vectors of a transformer are matrices
    assert (cell["config_file"]["run"]["dtype"], cell["config_file"]["run"]["param_dtype"]) == (
        "bfloat16", "float32")
    given = sum(leaf.size * 4 for leaf in jax.tree_util.tree_leaves(
        serve_driver.seeded_params(serve_driver.build_config(cell), 2**31 + 46)))
    assert 0.5 < got[NAME]["value"] * 1e9 / given < 0.6
    # a program that lacks the gauge (the parent commit) reports nothing
    bare = dict(record, gauges={k: v for k, v in record["gauges"].items() if k != "engine/param_gb"})
    assert NAME not in readers.read_all(bare, list(specs.values()))


def test_the_serve_cells_list_the_gauge_and_no_ppo_cell_does():
    with open(os.path.join(os.path.dirname(harness.HERE), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    spec = {m["name"]: m for m in manifest["per_layer"]}[NAME]
    assert (spec["unit"], spec["better"], spec["source"], spec["layer"], spec["moves"]) == (
        "GB", "lower", "program_counter", "rollout engine", "serve_itl_p95_ms")
    assert every_serve_cell_and_no_ppo_cell(spec["workloads"])
    assert set(spec["workloads"]) <= {w["name"] for w in manifest["workloads"]}
    with open(os.path.join(harness.HERE, "layer_metrics", f"{NAME}.json")) as f:
        assert json.load(f) == {"reader": {"kind": "counter", "name": "engine/param_gb"}}


@pytest.mark.parametrize("name", PPO_CELLS)
def test_a_ppo_cell_does_not_read_it(name):
    assert NAME not in {s["name"] for s in harness.load_layer_metrics(name)}
