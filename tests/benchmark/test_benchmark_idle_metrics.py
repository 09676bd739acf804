"""The five per-layer metrics that read the serving loop's starved ledger
(ISSUE 43): each is a file under ``benchmark/layer_metrics`` of the
existing reader kind ``histogram`` and resolves through
``readers.read_all`` on the record a tiny CPU rehearsal of a serve cell
makes. Rehearsal numbers are CPU numbers at a toy size: asserted for
their form and for the relations that hold on any clock."""

import json
import os
import time

import pytest

from benchmark import harness, readers, serve_driver
from manifest_cells import PPO_CELLS, SERVE_CELLS, every_serve_cell_and_no_ppo_cell
from test_benchmark_rehearsal import quiet_program, shrunk  # noqa: F401  (autouse fixture)

PARTS = {"serve_starved_tap_mean_ms", "serve_starved_admit_mean_ms",
         "serve_starved_land_mean_ms", "serve_starved_caller_mean_ms"}
IDLE = PARTS | {"serve_starved_mean_ms"}


def test_starved_metrics_read_the_ledger_from_inside():
    cell = shrunk("serve-pythia1b4-chat")
    device = harness.require_chips(int(cell["chips"]), allow_cpu=True)
    out = serve_driver.run(cell, 2**31 + 43, 2.0, False, time.time(), device)
    assert out["correct"] is True and out["failed"] == 0
    record = out["record"]
    specs = {s["name"]: s for s in harness.load_layer_metrics("serve-pythia1b4-chat")}
    assert IDLE <= set(specs)
    wanted = [specs[n] for n in sorted(IDLE | {"serve_pump_p50_ms"})]
    got = readers.read_all(record, wanted)
    assert set(got) == IDLE | {"serve_pump_p50_ms"}
    assert all(got[n]["unit"] == "ms" and got[n]["value"] >= 0.0 for n in IDLE)
    total = got["serve_starved_mean_ms"]["value"]
    # the host sat between a streamed step's tokens and the next dispatch
    assert total > 0.0
    # the four named parts are parts of the total (`other` is the rest)
    assert sum(got[n]["value"] for n in PARTS) <= total * (1 + 1e-9)
    # the chip is starved for less than an iteration lasts
    hists = record["histograms"]
    assert total < hists["serve/pump_ms"]["mean"]
    # every histogram of the family took an observation an iteration
    family = [k for k in hists if k.startswith("serve/starved_ms")]
    assert len(family) == 5
    iterations = hists["serve/pump_ms"]["count"] + hists["serve/admit_pump_ms"]["count"]
    assert all(hists[k]["count"] == iterations for k in family)
    # a program that lacks the histograms (the parent commit) reports none
    bare = dict(record, histograms={k: v for k, v in hists.items() if k not in family})
    assert not IDLE & set(readers.read_all(bare, list(specs.values())))


@pytest.mark.parametrize("name", SERVE_CELLS + PPO_CELLS)
def test_the_serve_cells_list_the_five_and_the_ppo_cells_none(name):
    listed = {s["name"]: s for s in harness.load_layer_metrics(name)}
    if name not in SERVE_CELLS:
        assert not IDLE & set(listed)
        return
    assert IDLE <= set(listed)
    for metric in IDLE:
        spec = listed[metric]
        assert spec["reader"]["kind"] == "histogram" and spec["reader"]["stat"] == "mean"
        assert spec["reader"]["name"].startswith("serve/starved_ms")
        assert (spec["unit"], spec["better"], spec["source"], spec["moves"]) == (
            "ms", "lower", "program_span", "serve_itl_p95_ms")
        assert every_serve_cell_and_no_ppo_cell(spec["workloads"])
        with open(os.path.join(harness.HERE, "layer_metrics", f"{metric}.json")) as f:
            assert set(json.load(f)) == {"reader"}
