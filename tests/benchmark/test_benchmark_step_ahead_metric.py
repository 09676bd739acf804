"""The per-layer metric that says the serving loop keeps a decode step in
flight (ISSUE 44): ``serve_step_ahead_share`` is a file under
``benchmark/layer_metrics`` of the existing reader kind ``histogram`` and
resolves through ``readers.read_all`` on the record a tiny CPU rehearsal
of a serve cell makes. Rehearsal numbers are CPU numbers at a toy size:
asserted for their form and for the relations that hold on any clock."""

import json
import os
import time

import pytest

from benchmark import harness, readers, serve_driver
from manifest_cells import PPO_CELLS, SERVE_CELLS, every_serve_cell_and_no_ppo_cell
from test_benchmark_rehearsal import quiet_program, shrunk  # noqa: F401  (autouse fixture)

NAME = "serve_step_ahead_share"


def test_the_share_reads_the_histograms_mean_from_a_serve_record():
    cell = shrunk("serve-pythia1b4-chat")
    device = harness.require_chips(int(cell["chips"]), allow_cpu=True)
    out = serve_driver.run(cell, 2**31 + 44, 2.0, False, time.time(), device)
    assert out["correct"] is True and out["failed"] == 0
    record = out["record"]
    specs = {s["name"]: s for s in harness.load_layer_metrics("serve-pythia1b4-chat")}
    got = readers.read_all(record, [specs[NAME]])
    assert set(got) == {NAME} and got[NAME]["unit"] == "share"
    seen = record["histograms"]["serve/step_ahead"]
    assert got[NAME]["value"] == seen["mean"]
    # most steps are dispatched behind an unread one; a pool that empties
    # between arrivals (this toy rate) restarts from nothing each time
    assert 0.5 < got[NAME]["value"] <= 1.0
    assert seen["min"] == 0.0 and seen["max"] == 1.0
    # one observation an iteration that ran a decode step: those are the
    # iterations the two pump histograms time, less prefill-only ones
    hists = record["histograms"]
    iterations = hists["serve/pump_ms"]["count"] + hists["serve/admit_pump_ms"]["count"]
    assert hists["serve/pump_ms"]["count"] <= seen["count"] <= iterations
    # a program that lacks the histogram (the parent commit) reports nothing
    bare = dict(record, histograms={k: v for k, v in hists.items() if k != "serve/step_ahead"})
    assert NAME not in readers.read_all(bare, list(specs.values()))


@pytest.mark.parametrize("name", SERVE_CELLS + PPO_CELLS)
def test_the_serve_cells_list_the_share_and_the_ppo_cells_do_not(name):
    listed = {s["name"]: s for s in harness.load_layer_metrics(name)}
    if name not in SERVE_CELLS:
        assert NAME not in listed
        return
    spec = listed[NAME]
    assert spec["reader"] == {"kind": "histogram", "name": "serve/step_ahead", "stat": "mean"}
    assert (spec["unit"], spec["better"], spec["source"], spec["layer"], spec["moves"]) == (
        "share", "higher", "program_counter", "rollout engine", "serve_itl_p95_ms")
    assert every_serve_cell_and_no_ppo_cell(spec["workloads"])
    with open(os.path.join(harness.HERE, "layer_metrics", f"{NAME}.json")) as f:
        assert set(json.load(f)) == {"reader"}
