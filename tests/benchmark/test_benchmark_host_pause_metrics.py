"""The seven per-layer metrics that read the host's pauses (ISSUE 60): the
collector's counters, ``host-stall``'s, and the span ``phase/begin``. Each
is a file under ``benchmark/layer_metrics`` of a reader kind that exists
(``counter``, ``tracer_span_per_phase``), listed for exactly its driver's
cells, and reads a finite number, 0.0 allowed, on the record a tiny CPU
rehearsal of its cell makes. Rehearsal numbers are CPU numbers at a toy
size: asserted for their form and for the relations that hold on any
clock."""

import json
import math
import os
import time

import pytest

from benchmark import harness, ppo_driver, readers, serve_driver
from manifest_cells import PPO_CELLS, SERVE_CELLS
from test_benchmark_rehearsal import quiet_program, shrunk  # noqa: F401  (autouse fixture)

PPO = {"host_gc_ms_per_s.ppo", "host_gc_max_ms.ppo", "host_stall_ms_per_s.ppo", "phase_begin_ms"}
SERVE = {"host_gc_ms_per_s.serve", "host_gc_max_ms.serve", "host_stall_ms_per_s.serve"}
READS = {
    "host_gc_ms_per_s": {"kind": "counter", "name": "host/gc_ms", "per": "second"},
    "host_gc_max_ms": {"kind": "counter", "name": "host/gc_max_ms"},
    "host_stall_ms_per_s": {"kind": "counter", "name": "host/stall_ms", "per": "second"},
    "phase_begin_ms": {"kind": "tracer_span_per_phase", "span": "phase/begin"},
}


@pytest.mark.parametrize("name", SERVE_CELLS + PPO_CELLS)
def test_each_cell_lists_its_drivers_metrics_and_not_the_others(name):
    listed = {s["name"]: s for s in harness.load_layer_metrics(name)}
    mine, others = (PPO, SERVE) if name in PPO_CELLS else (SERVE, PPO)
    assert mine <= set(listed) and not others & set(listed)
    cells, layer, moves = (
        (PPO_CELLS, "phase loop", "ppo_samples_per_s") if name in PPO_CELLS
        else (SERVE_CELLS, "serving", "serve_itl_p95_ms"))
    for metric in mine:
        spec = listed[metric]
        assert spec["reader"] == READS[metric.rsplit(".", 1)[0]]
        assert sorted(spec["workloads"]) == sorted(cells)
        assert (spec["better"], spec["layer"], spec["moves"]) == ("lower", layer, moves)
        assert spec["unit"] == ("ms/s" if "_per_s" in metric else "ms")
        assert spec["source"] == ("program_span" if metric == "phase_begin_ms" else "program_counter")
        with open(os.path.join(harness.HERE, "layer_metrics", f"{metric}.json")) as f:
            assert set(json.load(f)) == {"reader"}


def rehearse(driver, name, seconds):
    cell = shrunk(name)
    device = harness.require_chips(int(cell["chips"]), allow_cpu=True)
    out = driver.run(cell, 2**31 + 60, seconds, False, time.time(), device)
    assert out["correct"] is True and out["failed"] == 0
    return out["record"], {s["name"]: s for s in harness.load_layer_metrics(name)}


def finite(got, names):
    assert set(got) == names
    assert all(math.isfinite(m["value"]) and m["value"] >= 0.0 for m in got.values())


def test_the_ppo_metrics_read_numbers_in_a_rehearsed_phase():
    record, specs = rehearse(ppo_driver, "ppo-gpt2m-longgen", 1.0)
    got = readers.read_all(record, [specs[n] for n in sorted(PPO)])
    finite(got, PPO)
    # the span was opened once a phase, before the collection's own
    stats = record["tracer_stats"]
    assert stats["phase/begin"]["count"] == stats["phase/collect"]["count"] == record["phases"]
    assert 0.0 < got["phase_begin_ms"]["value"] < stats["phase/collect"]["total_ms"] / record["phases"]
    # a window's collections cannot have lasted longer than the window
    assert got["host_gc_ms_per_s.ppo"]["value"] < 1000.0
    # the longest pause since the process started is at least the window's mean one
    pauses = record["counters"]["host/gc_pauses"]
    if pauses:
        assert got["host_gc_max_ms.ppo"]["value"] >= record["counters"]["host/gc_ms"] / pauses
    # touched once a phase: a window without a stall reads 0.0, not absent
    assert "host/stall_ms" in record["counters"] and "host/stalls" in record["counters"]
    # a program without the hook and the span (the parent commit) reports none of the four
    bare = dict(record, tracer_stats={k: v for k, v in stats.items() if k != "phase/begin"},
                counters={k: v for k, v in record["counters"].items() if not k.startswith("host/")},
                gauges={k: v for k, v in record["gauges"].items() if not k.startswith("host/")})
    assert not PPO & set(readers.read_all(bare, list(specs.values())))


def test_the_serve_metrics_read_numbers_after_the_registry_was_cleared():
    record, specs = rehearse(serve_driver, "serve-pythia1b4-chat", 2.0)
    got = readers.read_all(record, [specs[n] for n in sorted(SERVE)])
    finite(got, SERVE)
    counters = record["counters"]
    # the driver cleared the registry at the window's start: the hook and
    # the loop looked their counters up again, by name
    assert {"host/gc_ms", "host/gc_pauses", "host/stalls", "host/stall_ms"} <= set(counters)
    assert got["host_gc_ms_per_s.serve"]["value"] == counters["host/gc_ms"] / record["window_s"]
    assert got["host_stall_ms_per_s.serve"]["value"] == counters["host/stall_ms"] / record["window_s"]
    # the gauge is the process's longest pause, the set-up's included
    assert got["host_gc_max_ms.serve"]["value"] > 0.0
    bare = dict(record, counters={k: v for k, v in counters.items() if not k.startswith("host/")},
                gauges={k: v for k, v in record["gauges"].items() if not k.startswith("host/")})
    assert not SERVE & set(readers.read_all(bare, list(specs.values())))
