"""The manifest's cells by the kind of traffic they run, for the tests that
ask "does every serve cell list this metric, and no PPO cell": read from
``BENCHMARK.json`` and each cell's traffic file (``driver``), never pinned
by hand, so a cell that a later PR adds, renames or replaces is followed
and a test asserts membership, not a position in a list.

And the one rule a family's manifest test holds its cell to
(``benchmark/README.md``, "A family's test"), as functions of
``(manifest, root)`` so that they run on a tree that is not the repo's:
:func:`cell_is_listed`, :func:`own_metrics_list_the_cell`,
:func:`lists_what_every_other_serve_cell_lists`. Every family's test
calls them on two trees (``conftest.py``'s ``either_tree``): the repo's,
and :func:`lay_a_later_prs_tree`'s, where a cell, a configuration and
per-layer metrics stand *after* everything the family brought. So none of
them looks at a position, counts the cells or names another family's."""

import json
import os
import shutil
from typing import Any, Dict, Iterable, List, Mapping, Optional, Set, Tuple

from benchmark import harness

Manifest = Dict[str, Any]
FILES_ONLY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "files_only")
COPIED = ("configs", "workloads", "traffic", "layer_metrics", "reference")
FIELDS = ("layer", "moves", "unit", "better", "source")  # what a per-layer entry says beside its name and cells


def read_manifest(root: str = harness.HERE) -> Manifest:
    """The ``BENCHMARK.json`` beside ``root`` (a tree's ``benchmark/``)."""
    with open(os.path.join(os.path.dirname(str(root)), "BENCHMARK.json")) as f:
        return json.load(f)


def load(root: str, kind: str, name: str) -> Dict[str, Any]:
    """``<root>/<kind>/<name>.json``: ``harness.load_json`` for any tree."""
    with open(os.path.join(str(root), kind, f"{name}.json")) as f:
        return json.load(f)


def cells_by_driver(manifest: Optional[Manifest] = None, root: str = harness.HERE) -> Dict[str, List[str]]:
    """``{"serve": [...], "ppo": [...]}`` in the manifest's order."""
    manifest = read_manifest(root) if manifest is None else manifest
    out: Dict[str, List[str]] = {}
    for w in manifest["workloads"]:
        out.setdefault(load(root, "traffic", w["traffic"])["driver"], []).append(w["name"])
    return out


_BY_DRIVER = cells_by_driver()
SERVE_CELLS, PPO_CELLS = _BY_DRIVER["serve"], _BY_DRIVER["ppo"]


def every_serve_cell_and_no_ppo_cell(workloads: Iterable[str]) -> bool:
    """What a serving metric's ``workloads`` list has to hold."""
    listed = set(workloads)
    return set(SERVE_CELLS) <= listed and not set(PPO_CELLS) & listed


def roofline(layer: str, moves: str = "serve_itl_p95_ms") -> Dict[str, str]:
    """The manifest fields of one kernel's share of its roofline."""
    return {"layer": layer, "moves": moves, "unit": "%", "better": "higher", "source": "device_trace"}


def gauge(layer: str, moves: str, unit: str, better: str) -> Dict[str, str]:
    """The manifest fields of a metric read off the program's registry."""
    return {"layer": layer, "moves": moves, "unit": unit, "better": better, "source": "program_counter"}


def one_line(text: Any, limit: int = 200) -> bool:
    return isinstance(text, str) and 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def metric_names(cell: str, root: str) -> Set[str]:
    """The per-layer metrics the harness reads in ``cell`` on that tree."""
    return {s["name"] for s in harness.load_layer_metrics(cell, root=str(root))}


def cell_is_listed(manifest: Manifest, root: str, cell: str, config: str, traffic: str,
                   chips: int = 1) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """``cell`` is in ``workloads`` once, with its configuration, traffic,
    ``chips`` and its workload file's ``why``; ``config`` is in ``configs``
    once, with the file's ``reduced`` and path; and every end-to-end metric
    of the cell's driver (``serve_*``, ``ppo_*``) lists the cell. Returns
    the two entries. Wherever they stand: nothing here reads a position."""
    entries = [w for w in manifest["workloads"] if w["name"] == cell]
    assert len(entries) == 1, (cell, len(entries))
    w = entries[0]
    assert (w["config"], w["traffic"], w["chips"]) == (config, traffic, chips), w
    assert one_line(w["why"]) and w["why"] == load(root, "workloads", cell)["why"]
    configs = [c for c in manifest["configs"] if c["name"] == config]
    assert len(configs) == 1, (config, len(configs))
    c = configs[0]
    assert one_line(c["why"]) and one_line(c["source"])
    assert sorted(c["reduced"]) == sorted(load(root, "configs", config)["reduced"])
    assert c["file"] == f"benchmark/configs/{config}.json"
    driver = load(root, "traffic", traffic)["driver"]
    reported = [m for m in manifest["end_to_end"] if m["name"].startswith(f"{driver}_")]
    assert reported and all(cell in m["workloads"] for m in reported), [m["name"] for m in reported]
    return w, c


def own_metrics_list_the_cell(manifest: Manifest, cell: str, own: Mapping[str, Mapping[str, str]]) -> None:
    """Each metric of ``own`` (name -> the manifest fields the family's test
    holds it to: ``layer``, ``moves``, ``unit``, ``better``, ``source``)
    is *in* ``per_layer``, wherever, and lists ``cell`` and no other."""
    listed = {m["name"]: m for m in manifest["per_layer"]}
    assert set(own) <= set(listed), sorted(set(own) - set(listed))
    for name, fields in own.items():
        m = listed[name]
        assert m["workloads"] == [cell], (name, m["workloads"])
        assert {k: m[k] for k in fields} == dict(fields), (name, m)


def lists_what_every_other_serve_cell_lists(manifest: Manifest, root: str, cell: str) -> None:
    """Every per-layer metric that all the *other* serve cells of this tree
    read, ``cell`` reads too; the others are the tree's serve cells less
    this one, whichever they are. A metric over some serve cells and not
    others is therefore listable (each cell that lacks it has another that
    lacks it among its others); one that fits every serve cell but one
    would still be asked of that one (``benchmark/README.md`` has the edge)."""
    serve = cells_by_driver(manifest, root)["serve"]
    assert cell in serve
    listed = {c: {m["name"] for m in manifest["per_layer"] if c in m["workloads"]} for c in serve}
    others = [names for c, names in listed.items() if c != cell]
    if others:
        missing = set.intersection(*others) - listed[cell]
        assert not missing, (cell, sorted(missing))


def lay_a_later_prs_tree(tmp_path, manifest: Manifest):
    """A copy of the benchmark's data with ``files_only/`` laid over it and
    its manifest entries appended after everything that is there, as a later
    PR would: ``(root, bytes of every file that was there before)``, with
    the new ``BENCHMARK.json`` beside ``root``. ``manifest_entries.json``
    says by name which metrics a new cell joins (``joins``), and which cells
    join every metric that all the serve cells there list
    (``joins_what_every_serve_cell_lists``: reckoned here, so the file keeps
    no list that a later serving metric would leave behind)."""
    root = tmp_path / "benchmark"
    for kind in COPIED:
        shutil.copytree(os.path.join(harness.HERE, kind), root / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    shutil.copytree(os.path.join(FILES_ONLY, "benchmark"), root, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(FILES_ONLY, "manifest_entries.json")) as f:
        entries = json.load(f)
    serve = set(cells_by_driver(manifest)["serve"])
    new = json.loads(json.dumps(manifest))
    for m in new["end_to_end"] + new["per_layer"]:  # a cell's name joins one list a metric it reads
        for cell, names in entries["joins"].items():
            if m["name"] in names:
                m["workloads"].append(cell)
        if serve <= set(m.get("workloads", ())):
            m["workloads"] += entries["joins_what_every_serve_cell_lists"]
    for group in ("configs", "workloads", "per_layer"):
        new[group] += entries[group]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    return root, before
