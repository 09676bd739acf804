"""The manifest's cells by the kind of traffic they run, for the tests that
ask "does every serve cell list this metric, and no PPO cell": read from
``BENCHMARK.json`` and each cell's traffic file (``driver``), never pinned
by hand, so a cell that a later PR adds, renames or replaces is followed
and a test asserts membership, not a position in a list."""

import json
import os
from typing import Dict, Iterable, List

from benchmark import harness


def cells_by_driver() -> Dict[str, List[str]]:
    """``{"serve": [...], "ppo": [...]}`` in the manifest's order."""
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        workloads = json.load(f)["workloads"]
    out: Dict[str, List[str]] = {}
    for w in workloads:
        driver = harness.load_json("traffic", f"{w['traffic']}.json")["driver"]
        out.setdefault(driver, []).append(w["name"])
    return out


_BY_DRIVER = cells_by_driver()
SERVE_CELLS, PPO_CELLS = _BY_DRIVER["serve"], _BY_DRIVER["ppo"]


def every_serve_cell_and_no_ppo_cell(workloads: Iterable[str]) -> bool:
    """What a serving metric's ``workloads`` list has to hold."""
    listed = set(workloads)
    return set(SERVE_CELLS) <= listed and not set(PPO_CELLS) & listed
