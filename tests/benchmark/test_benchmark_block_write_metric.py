"""The per-layer metric that says an admission forward writes its columns
into the paged pool by whole blocks (ISSUE 49):
``serve_prefill_block_write_share`` is a file under
``benchmark/layer_metrics`` of the existing reader kind ``histogram`` over
``engine/prefill_block_write_share``, which the engine observes once an
admission forward dispatched, with the answer of the write's own predicate
(``ops/kv_cache.py::writes_whole_blocks``) on that program's call.
Rehearsal numbers are CPU numbers at a toy size: asserted for their form
and for what holds on any clock."""

import json
import os
import time

import pytest

from benchmark import harness, readers, serve_driver
from manifest_cells import PPO_CELLS, every_serve_cell_and_no_ppo_cell
from test_benchmark_rehearsal import quiet_program, shrunk  # noqa: F401  (autouse fixture)

NAME = "serve_prefill_block_write_share"


# the rehearsal's toy widths (16 prompt columns + 8 new tokens: blocks of
# 12, which tile neither the prompt nor a chunk) and a toy of the cells'
# proportions (64 + 16: blocks of 16, a chunk of 16 columns)
@pytest.mark.parametrize("widths,share", [
    ({"seq_length": 64, "max_new_tokens": 16}, 1.0),
    ({}, 0.0),
], ids=["whole_blocks", "blocks_that_tile_nothing"])
def test_the_histogram_reads_the_write_from_a_serve_record(widths, share):
    cell = shrunk("serve-pythia1b4-chat")
    cell["traffic_file"].update(widths)
    device = harness.require_chips(int(cell["chips"]), allow_cpu=True)
    out = serve_driver.run(cell, 2**31 + 49, 2.0, False, time.time(), device)
    assert out["correct"] is True and out["failed"] == 0
    record = out["record"]
    specs = {s["name"]: s for s in harness.load_layer_metrics("serve-pythia1b4-chat")}
    got = readers.read_all(record, [specs[NAME]])
    assert set(got) == {NAME} and got[NAME]["unit"] == "share"
    # every forward the window dispatched wrote the one way its widths
    # allow, and there was one at least
    assert got[NAME]["value"] == share
    assert record["histograms"]["engine/prefill_block_write_share"]["count"] >= 1
    # a program that lacks the histogram (the parent commit) reports nothing
    bare = dict(record, histograms={
        k: v for k, v in record["histograms"].items() if k != "engine/prefill_block_write_share"})
    assert NAME not in readers.read_all(bare, list(specs.values()))


def test_the_serve_cells_list_the_share_and_no_ppo_cell_does():
    with open(os.path.join(os.path.dirname(harness.HERE), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    spec = {m["name"]: m for m in manifest["per_layer"]}[NAME]
    assert (spec["unit"], spec["better"], spec["source"], spec["layer"], spec["moves"]) == (
        "share", "higher", "program_counter", "rollout engine", "serve_itl_p95_ms")
    assert every_serve_cell_and_no_ppo_cell(spec["workloads"])
    assert set(spec["workloads"]) <= {w["name"] for w in manifest["workloads"]}
    with open(os.path.join(harness.HERE, "layer_metrics", f"{NAME}.json")) as f:
        assert json.load(f) == {
            "reader": {"kind": "histogram", "name": "engine/prefill_block_write_share", "stat": "mean"}}


@pytest.mark.parametrize("name", PPO_CELLS)
def test_a_ppo_cell_does_not_read_it(name):
    assert NAME not in {s["name"] for s in harness.load_layer_metrics(name)}
