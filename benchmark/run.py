#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell, its configuration, its traffic mix and its per-layer
metrics by name under ``benchmark/``, runs the cell's driver in this one
process (which holds the chips), and prints one JSON object as the last
line of its output: with ``--trace 0`` the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, ``busy_s``/``window_s`` and the
breakdown, and in both, last, ``checks``: every number ``correct`` compared
beside its limit, each also on an earlier line and again as the last lines
of the error stream.
Exits nonzero, and prints no result, without a TPU listed in
``benchmark/peaks.json`` or with fewer chips than the cell asks for.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

T_START = time.time()
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float = None, allow_cpu: bool = False, cell: dict = None) -> str:
    """Run one cell and return its result line. ``allow_cpu`` and ``cell``
    (an already-loaded, shrunken cell) are the tests' rehearsal; the
    command below passes neither."""
    os.environ.setdefault("WANDB_DISABLED", "1")
    from benchmark import checks, harness, readers

    t_start = time.time() if t_start is None else t_start
    cell = harness.load_cell(workload) if cell is None else cell
    checks.tolerance_table(cell["config_file"], cell["root"])  # a broken table is refused before the run
    harness.place_compile_cache()
    t_jax = time.time()
    device = harness.require_chips(int(cell["chips"]), allow_cpu=allow_cpu)
    print(f"note setup: import_jax={t_jax - t_start:.1f}s reach_chip={time.time() - t_jax:.1f}s", flush=True)
    print(f"device platform={device['platform']} kind={device['kind']!r} "
          f"count={device['count']} cell={cell['name']} seed={seed}", flush=True)
    kind = cell["traffic_file"]["driver"]
    if kind == "ppo":
        from benchmark import ppo_driver as driver
    elif kind == "serve":
        from benchmark import serve_driver as driver
    else:
        raise ValueError(f"unknown driver {kind!r} in traffic {cell['traffic']!r}")
    out = driver.run(cell, seed, seconds, trace, t_start, device)

    record = out["record"]
    dev = {
        "platform": device["platform"], "kind": device["kind"], "count": device["count"],
        "memory_peak_bytes": record["memory_peak_bytes"],
    }
    breakdown = None
    if trace:
        metrics = readers.read_all(record, harness.load_layer_metrics(cell["name"]))
        reduced = readers.trace_of(record)
        if reduced is not None:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["span_s"]
            breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    else:
        metrics = out["end_to_end"]
    out["checks"].echo(sys.stderr)  # again, as the last lines of the error stream
    return harness.result_line(
        out["correct"], out["attempted"], out["failed"], metrics, dev, breakdown, out["checks"].entries
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmark.harness import NoAccelerator

    try:
        line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), T_START)
    except NoAccelerator as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
