"""The per-layer metric that says an admission forward writes its columns
into the paged pool by whole blocks (ISSUE 49):
``serve_prefill_block_write_share`` is a file under
``benchmark/layer_metrics`` of the existing reader kind ``histogram`` over
``engine/prefill_block_write_share``, which the engine observes once an
admission forward dispatched, with the answer of the write's own predicate
(``ops/kv_cache.py::writes_whole_blocks``) on that program's call.
And beside it the one that says the pool such a write lands in is viewed
by blocks without moving (ISSUE 64, the gauge PR 63 brought):
``serve_pool_block_bitcast_share``, a ``counter`` reader over
``cache/block_write_bitcast_share``, listed by the serve cells whose models
keep keys and values and by no other.
Rehearsal numbers are CPU numbers at a toy size: asserted for their form
and for what holds on any clock."""

import json
import os
import time

import pytest

from benchmark import harness, readers, serve_driver
from manifest_cells import PPO_CELLS, SERVE_CELLS, every_serve_cell_and_no_ppo_cell, read_manifest
from test_benchmark_rehearsal import quiet_program, shrunk  # noqa: F401  (autouse fixture)

NAME = "serve_prefill_block_write_share"
POOL = "serve_pool_block_bitcast_share"
_REHEARSED = {}


def rehearsed(widths):
    """One rehearsal of pythia's cell a set of widths for the module's
    tests, whichever asks first: the driver's result; nobody writes into it."""
    key = tuple(sorted(widths.items()))
    if key not in _REHEARSED:
        cell = shrunk("serve-pythia1b4-chat")
        cell["traffic_file"].update(widths)
        device = harness.require_chips(int(cell["chips"]), allow_cpu=True)
        _REHEARSED[key] = serve_driver.run(cell, 2**31 + 49, 2.0, False, time.time(), device)
    return _REHEARSED[key]


# the rehearsal's toy widths (16 prompt columns + 8 new tokens: blocks of
# 12, which tile neither the prompt nor a chunk) and a toy of the cells'
# proportions (64 + 16: blocks of 16, a chunk of 16 columns)
@pytest.mark.parametrize("widths,share", [
    ({"seq_length": 64, "max_new_tokens": 16}, 1.0),
    ({}, 0.0),
], ids=["whole_blocks", "blocks_that_tile_nothing"])
def test_the_histogram_reads_the_write_from_a_serve_record(widths, share):
    out = rehearsed(widths)
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


def test_the_cells_that_keep_keys_and_values_list_the_pools_share_and_read_the_gauge():
    spec = {m["name"]: m for m in read_manifest()["per_layer"]}[POOL]
    assert (spec["unit"], spec["better"], spec["source"], spec["layer"], spec["moves"]) == (
        "share", "higher", "program_counter", "rollout engine", "serve_itl_p95_ms")
    # a latent pool has no block view and a state no positions: the engine votes over the layers that are neither
    # and sets nothing where there is none, so the two cells whose every layer is one of those do not list it
    assert set(spec["workloads"]) == {"serve-pythia1b4-chat", "serve-olmoe1b7b-chat", "serve-granite4hs-chat",
                                      "serve-zaya1-8b-reason", "serve-qwen3next-chat512"} < set(SERVE_CELLS)
    assert not set(spec["workloads"]) & set(PPO_CELLS)
    with open(os.path.join(harness.HERE, "layer_metrics", f"{POOL}.json")) as f:
        assert json.load(f) == {"reader": {"kind": "counter", "name": "cache/block_write_bitcast_share"}}
    for name in SERVE_CELLS:
        assert (POOL in {s["name"] for s in harness.load_layer_metrics(name)}) == (name in spec["workloads"])
    # the rehearsal's heads are 8 wide, under one lane row: every layer's block view is a bitcast
    out = rehearsed({})
    assert out["correct"] is True and out["failed"] == 0
    record = out["record"]
    specs = {s["name"]: s for s in harness.load_layer_metrics("serve-pythia1b4-chat")}
    got = readers.read_all(record, [specs[POOL]])
    assert got == {POOL: {"value": 1.0, "unit": "share"}}
    assert got[POOL]["value"] == record["gauges"]["cache/block_write_bitcast_share"]
    # a program that lacks the gauge (the parent of PR 63) reports nothing
    bare = dict(record, gauges={k: v for k, v in record["gauges"].items() if k != "cache/block_write_bitcast_share"})
    assert POOL not in readers.read_all(bare, list(specs.values()))
    assert NAME in readers.read_all(bare, list(specs.values()))


@pytest.mark.parametrize("name", PPO_CELLS)
def test_a_ppo_cell_does_not_read_it(name):
    assert not {NAME, POOL} & {s["name"] for s in harness.load_layer_metrics(name)}
