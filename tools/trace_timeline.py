#!/usr/bin/env python3
"""One traced benchmark run, reduced past the result line's ten longest operations.

    python3 tools/trace_timeline.py <checkout> <out.json> [module ...]

Reads the newest ``.xplane.pb`` under ``<checkout>/.bench_trace`` (what
``benchmark/run.py --trace 1`` leaves there) with the benchmark's own reducer
and writes: every module's time and count, the sixty longest operation kinds
with their counts, and for each named module (default ``jit_decode_step``,
``jit_prefill``) the operations of its median execution in order, as
``[start_us, duration_us, gap_before_us, kind]``. Run it on the machine that
made the trace, after the run (it holds no chip: ``JAX_PLATFORMS=cpu``), and
have it write under ``chiprun_out/``.
"""

import glob
import json
import os
import sys


def main(argv) -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    root, out = argv[1], argv[2]
    wanted = argv[3:] or ["jit_decode_step", "jit_prefill"]
    sys.path.insert(0, root)
    from benchmark import trace_reduce as tr

    paths = glob.glob(os.path.join(root, ".bench_trace", "**", "*.xplane.pb"), recursive=True)
    if not paths:
        print(f"no trace under {root}/.bench_trace")
        return 1
    path = max(paths, key=os.path.getmtime)
    # the serving driver's steady slice where the trace has one, else all of it
    red = tr.reduce_trace(path, clip_span="steady")
    devices, _ = tr.read_planes(path)
    dev = devices[min(devices)]
    kinds = sorted(red["ops"].items(), key=lambda kv: -kv[1]["s"])[:60]
    res = {
        "trace": path, "busy_s": red["busy_s"], "span_s": red["span_s"], "modules": red["modules"],
        "ops_kind_s_count": [[n, round(v["s"], 6), v["count"]] for n, v in kinds],
    }
    for want in wanted:
        runs = sorted((e - s, s, e) for s, e, n in dev["modules"] if tr.module_name(n) == want)
        if not runs:
            continue
        dur, s0, e0 = runs[len(runs) // 2]
        ops = sorted((s, e, tr.op_kind(n)) for s, e, n in dev["ops"] if s >= s0 and e <= e0)
        timeline, last = [], s0
        for s, e, n in tr.leaves(ops):
            timeline.append([round((s - s0) / 1e3, 1), round((e - s) / 1e3, 1), round((s - last) / 1e3, 1), n])
            last = max(last, e)
        res[want] = {
            "module_us": dur / 1e3,
            "busy_us": tr.length(tr.union((s, e) for s, e, _ in ops)) / 1e3,
            "timeline": timeline,
        }
    with open(out, "w") as f:
        json.dump(res, f)
    print("reduced", path, json.dumps(red["modules"]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
