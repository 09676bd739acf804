"""The per-layer metrics that read the program from inside (ISSUE 24):
each is a file under ``benchmark/layer_metrics`` and resolves through
``readers.read_all`` on the record a tiny CPU rehearsal of its cell
makes — the spans and histograms the files name are the ones the program
writes. Rehearsal numbers are CPU numbers at a toy size: asserted for
their form and for the relations that hold on any clock."""

import time

import pytest

from benchmark import harness, ppo_driver, readers, serve_driver
from test_benchmark_rehearsal import quiet_program, shrunk  # noqa: F401  (autouse fixture)

SERVE = {"serve_pump_p50_ms", "serve_admit_pump_p50_ms", "serve_step_host_p50_ms",
         "serve_slots_done_waiting"}
PPO = {"collect_wait_ms", "collect_detok_ms", "collect_score_ms", "train_drain_ms",
       "train_residual_ms"}


def rehearse(driver, name, seconds):
    cell = shrunk(name)
    device = harness.require_chips(int(cell["chips"]), allow_cpu=True)
    out = driver.run(cell, 2**31 + 5, seconds, False, time.time(), device)
    assert out["correct"] is True and out["failed"] == 0
    specs = harness.load_layer_metrics(name)
    return out["record"], {s["name"]: s for s in specs}


def test_serving_metrics_read_the_loop_from_inside():
    record, specs = rehearse(serve_driver, "serve-pythia1b4-chat", 2.0)
    assert SERVE <= set(specs)
    got = readers.read_all(record, [specs[n] for n in sorted(SERVE)])
    assert set(got) == SERVE
    assert got["serve_slots_done_waiting"]["unit"] == "slots"
    assert 0.0 <= got["serve_slots_done_waiting"]["value"] <= 16
    for name in SERVE - {"serve_slots_done_waiting"}:
        assert got[name]["unit"] == "ms" and got[name]["value"] > 0.0
    # the host's own share of an iteration is less than the iteration, like with like: `serve/step_host_ms` is
    # observed once an iteration that did device work, beside that iteration's wall in `serve/pump_ms` or in
    # `serve/admit_pump_ms`, so the counts add up and so do the totals (a p50 over all iterations against a p50
    # over those that met no forward compared two different sets: PERF.md section 7 (31))
    host, pump, admit = (record["histograms"].get(f"serve/{k}_ms", {"count": 0})
                         for k in ("step_host", "pump", "admit_pump"))
    total = lambda h: h["count"] * h.get("mean", 0.0)
    assert host["count"] == pump["count"] + admit["count"] and pump["count"] >= 1
    assert total(host) <= (total(pump) + total(admit)) * (1 + 1e-9)
    assert host["max"] <= max(pump["max"], admit.get("max", 0.0))
    # a program that lacks the histograms (the parent commit) reports none
    bare = dict(record, histograms={})
    assert readers.read_all(bare, [specs[n] for n in sorted(SERVE)]) == {}


@pytest.mark.parametrize("name", ["ppo-gpt2m-tldr", "ppo-gpt2m-longgen"])
def test_ppo_metrics_read_the_phase_from_inside(name):
    record, specs = rehearse(ppo_driver, name, 1.0)
    assert PPO <= set(specs)
    got = readers.read_all(record, [specs[n] for n in sorted(PPO | {"collect_decode_ms"})])
    assert set(got) == PPO | {"collect_decode_ms"}
    assert all(m["unit"] == "ms" and m["value"] >= 0.0 for m in got.values())
    # the two children lie inside collect/decode. That they tile it is held by count, which a loaded CPU cannot
    # stretch: each child is opened once inside each parent. By time it is not held here: the spans of a toy
    # rehearsal are under a millisecond, and the parent's own lines between its children are microseconds that a
    # busy worker stretches past any share (the two read 0.3545 ms where nine tenths of the parent were 0.3559
    # under six workers: PERF.md section 7 (8)). On the chip they are 568.0 of 568.1 ms and 1637.8 of 1637.9
    # (ledger, PR 63), which the ledger's per-layer lines keep showing
    inside = got["collect_wait_ms"]["value"] + got["collect_detok_ms"]["value"]
    assert 0.0 < inside <= got["collect_decode_ms"]["value"]
    opened = {k: record["tracer_stats"][k]["count"] for k in ("collect/decode", "collect/wait", "collect/detokenize")}
    assert len(set(opened.values())) == 1 and opened["collect/decode"] >= 1, opened
    # a program without the spans (the parent commit) reports none of them
    stats = {k: v for k, v in record["tracer_stats"].items()
             if k not in ("collect/wait", "collect/detokenize")}
    bare = readers.read_all(dict(record, tracer_stats=stats), list(specs.values()))
    assert "collect_wait_ms" not in bare and "collect_detok_ms" not in bare
    assert "collect_decode_ms" in bare
