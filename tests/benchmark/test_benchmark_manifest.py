"""``BENCHMARK.json`` against the contract's format rules, and against the
data files the harness finds by name; and that a configuration, a cell and
a per-layer metric can each be added as new files only."""

import json
import os
import re
import shutil

import pytest

from benchmark import harness, readers

REPO = harness.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|head_dim|n_embd|n_inner|expand)")


@pytest.fixture(scope="module")
def manifest():
    path = os.path.join(REPO, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def one_line(text, limit=200):
    return isinstance(text, str) and 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51


def test_names_units_and_lines(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in manifest[group]]
        assert len(seen) == len(set(seen)), group
        names += seen
    metric_names = [e["name"] for e in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert one_line(m["layer"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and one_line(w["why"])
        assert w["chips"] in (1, 4)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"]) and len(c["reduced"]) <= 16


def test_cells_configs_and_traffic_resolve_to_files(manifest):
    config_names = {c["name"] for c in manifest["configs"]}
    pairs = set()
    for w in manifest["workloads"]:
        assert w["config"] in config_names
        cell = harness.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) == (
            w["config"], w["traffic"], w["chips"], w["why"])
        assert cell["traffic_file"]["driver"] in ("ppo", "serve")
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert {w["config"] for w in manifest["workloads"]} == config_names  # each used by a cell
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        with open(os.path.join(REPO, c["file"])) as f:
            cf = json.load(f)
        assert (cf["name"], cf["source"], cf["reduced"]) == (c["name"], c["source"], c["reduced"])
        assert "assumed" in cf and not any(WIDTHS.search(k) for k in cf["reduced"])
        assert os.path.exists(os.path.join(REPO, cf["reference"]))


def test_at_most_one_cell_asks_for_four_chips(manifest):
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def cells_of(metric, manifest):
    return metric.get("workloads") or [w["name"] for w in manifest["workloads"]]


def test_every_metric_resolves_and_every_cell_reports_enough(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"] and e2e["setup_s"]["bound"] <= 0.1
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(cells_of(m, manifest)) <= cells, m["name"]
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        # the metric it moves is reported in every cell this one is read in
        assert set(cells_of(m, manifest)) <= set(cells_of(e2e[m["moves"]], manifest)), m["name"]
    for cell in cells:
        others = [n for n, m in e2e.items() if n != "setup_s" and cell in cells_of(m, manifest)]
        layers = [m for m in manifest["per_layer"] if cell in cells_of(m, manifest)]
        assert others and layers, cell


def test_layer_metric_files_match_the_manifest(manifest):
    listed = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]]
    found = {}
    for cell in cells:
        for spec in harness.load_layer_metrics(cell):
            found.setdefault(spec["name"], spec)
            assert cell in listed[spec["name"]]["workloads"], (spec["name"], cell)
    assert set(found) == set(listed)
    for name, spec in found.items():
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == listed[name][key], (name, key)
        assert spec["reader"]["kind"] in readers.READERS
        assert set(listed[name]["workloads"]) == set(spec["workloads"]) & set(cells)
    layers = {}
    for m in manifest["per_layer"]:  # one spelling per layer
        layers.setdefault(m["layer"].lower().strip(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_a_config_a_cell_and_a_metric_are_added_as_files_only(tmp_path):
    root = tmp_path / "benchmark"
    for kind in ("configs", "workloads", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(harness.HERE, kind), root / kind)
    before = {p: p.read_bytes() for p in root.rglob("*.json")}

    cfg = json.loads((root / "configs" / "gpt2-medium.json").read_text())
    cfg.update(name="gpt2-large", n_embd=1280, n_layer=36, n_head=20,
               source="https://huggingface.co/openai-community/gpt2-large/blob/main/config.json")
    (root / "configs" / "gpt2-large.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "traffic" / "chat.json").read_text())
    mix.update(name="burst", arrivals={"process": "gamma", "cv": 3.0, "knee_per_s": 12.0, "load": 1.25})
    (root / "traffic" / "burst.json").write_text(json.dumps(mix))
    (root / "workloads" / "serve-gpt2l-burst.json").write_text(json.dumps({
        "name": "serve-gpt2l-burst", "config": "gpt2-large", "traffic": "burst", "chips": 1,
        "mesh": {"dp": 1, "fsdp": 1, "tp": 1}, "why": "a later PR's cell"}))
    (root / "layer_metrics" / "serve_e2e_p50_ms.json").write_text(json.dumps({
        "name": "serve_e2e_p50_ms", "unit": "ms", "better": "lower", "source": "program_counter",
        "layer": "serving", "moves": "serve_itl_p95_ms", "workloads": ["serve-gpt2l-burst"],
        "reader": {"kind": "histogram", "name": "serve/e2e_ms", "stat": "p50"}}))

    cell = harness.load_cell("serve-gpt2l-burst", root=str(root))
    assert cell["config_file"]["n_embd"] == 1280
    assert cell["traffic_file"]["arrivals"]["process"] == "gamma"
    assert harness.arch_of(cell["config_file"])["n_layer"] == 36
    specs = harness.load_layer_metrics("serve-gpt2l-burst", root=str(root))
    assert [s["name"] for s in specs] == ["serve_e2e_p50_ms"]
    record = {"histograms": {"serve/e2e_ms": {"count": 3, "p50": 12.5}}}
    assert readers.read_all(record, specs) == {"serve_e2e_p50_ms": {"value": 12.5, "unit": "ms"}}
    # a reader that finds nothing to read returns nothing
    assert readers.read_all({"histograms": {}}, specs) == {}
    assert all(p.read_bytes() == b for p, b in before.items())  # nothing edited
