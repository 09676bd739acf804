#!/usr/bin/env python3
"""One traced benchmark run, reduced past the result line's ten longest operations.

    python3 tools/trace_timeline.py <checkout> <out.json> [module ...]

Reads the newest ``.xplane.pb`` under ``<checkout>/.bench_trace`` (what
``benchmark/run.py --trace 1`` leaves there) with the benchmark's own reducer
and writes: every module's time and count, the sixty longest operation kinds
with their counts, and for each named module (default ``jit_decode_step``,
``jit_prefill``) the operations of its median execution in order, as
``[start_us, duration_us, gap_before_us, kind]``; and ``idle_by_span``: the
first chip's idle time in the slice, inside a module's interval or between
modules, the latter by the program's own ``trlx/`` host span it fell under.
Run it on the machine that made the trace, after the run (it holds no chip:
``JAX_PLATFORMS=cpu``), and have it write under ``chiprun_out/``.
"""

import bisect
import glob
import json
import os
import sys

PROGRAM_SPAN_PREFIX = "trlx/"  # trlx_tpu/telemetry/tracer.py::ANNOTATION_PREFIX
STEP_MODULES = ("jit_decode_step", "jit_verify_step")  # what an ``engine/dispatch`` span launches


def program_spans(path):
    """The program's own spans on the host planes of the trace at ``path``
    (``benchmark.trace_reduce.read_planes`` keeps the harness's ``bench/``
    spans alone): ``[(start_ns, end_ns, name without the prefix)]``."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_SPAN_PREFIX):
                    s = int(ev.start_ns)
                    spans.append((s, s + int(ev.duration_ns), ev.name[len(PROGRAM_SPAN_PREFIX):]))
    return spans


def idle_by_span(busy, modules, window, spans):
    """Where one chip's idle time inside ``window`` went, in ns.

    ``busy``: the chip's disjoint sorted op intervals; ``modules``: its
    executed programs' intervals; ``spans``: host spans ``(start, end,
    name)``. Idle **inside a module's interval** is the device's own (a
    stall between two ops of one program: nothing the host did), and comes
    back as ``in_module``. Idle **between modules** is split by overlap,
    not by midpoint: each piece goes to the innermost (shortest) span that
    covers it, and to ``caller`` where none does, so a gap that straddles
    two spans is shared between them."""
    from benchmark.trace_reduce import length, subtract, union

    idle = subtract([tuple(window)], busy)
    between = subtract(idle, union(modules))
    out = {"idle": length(idle), "in_module": length(idle) - length(between), "between": {}}
    left = between  # disjoint, sorted: what no shorter span has claimed yet
    for s, e, name in sorted(spans, key=lambda sp: sp[1] - sp[0]):
        lo = hi = bisect.bisect_right(left, s, key=lambda piece: piece[1])
        while hi < len(left) and left[hi][0] < e:
            hi += 1
        if lo == hi:
            continue
        pieces = left[lo:hi]
        kept = subtract(pieces, [(s, e)])
        out["between"][name] = out["between"].get(name, 0) + length(pieces) - length(kept)
        left[lo:hi] = kept
    if left:
        out["between"]["caller"] = length(left)
    return out


def slice_idle(dev, bench, spans):
    """``idle_by_span`` of one chip over the window ``reduce_trace`` takes
    (the steady slice where the trace has one, from the first to the last
    device event in it), in seconds, with the slice's count of iterations
    (``trlx/serve/step``) and decode steps to divide by."""
    from benchmark import trace_reduce as tr

    marks = sorted(h for h in bench if h[2] == tr.HOST_SPAN_PREFIX + "steady")
    ops, modules = dev["ops"], dev["modules"]
    if marks:
        ops, modules = tr.clip(ops, marks[0][:2]), tr.clip(modules, marks[0][:2])
    events = ops + modules
    window = (min(s for s, _, _ in events), max(e for _, e, _ in events))
    got = idle_by_span(
        tr.union((s, e) for s, e, _ in ops), [(s, e) for s, e, _ in modules], window, tr.clip(spans, window)
    )
    steps = [m for m in modules if tr.module_name(m[2]) in STEP_MODULES]
    return {
        "launch_and_tail": launch_and_tail(steps, spans),
        "window_s": (window[1] - window[0]) / 1e9,
        "idle_s": got["idle"] / 1e9,
        "in_module_s": got["in_module"] / 1e9,
        "between_s": {n: ns / 1e9 for n, ns in sorted(got["between"].items(), key=lambda kv: -kv[1])},
        "iterations": sum(1 for s, e, n in spans if n == "serve/step" and window[0] <= s < window[1]),
        "decode_steps": len(steps),
    }


def launch_and_tail(modules, spans):
    """How far the overlap split can be trusted, and what it cannot split.

    Each executed step (``modules``: the decode and verify steps' events)
    is paired with the ``engine/dispatch`` span that launched it (the last that began before the module ended) and the
    first ``engine/fetch`` after that span (the one that waited for it).
    ``launch_us`` (module start less the span's entry) and ``tail_us``
    (the fetch's return less the module's end) each compare the device's
    clock with the host's: a module that starts *before* its own dispatch
    was entered shows by how much the two are apart in this trace, and
    idle pieces shorter than that are under the wrong span. Their sum
    compares host with host and device with device, so it holds whatever
    the skew: the time a step loses to the dispatch call, the launch and
    the transfer's tail together, which the starved ledger leaves to the
    device's clock. Each as ``[p10, p50, p90, max]`` in us (the largest shows
    a stall the deciles hide); ``{}`` without the spans."""
    dispatch = sorted((s, e) for s, e, n in spans if n == "engine/dispatch")
    fetch = sorted((s, e) for s, e, n in spans if n == "engine/fetch")
    launch, tail = [], []
    for s, e, _ in modules:
        i = bisect.bisect_left(dispatch, (e,)) - 1
        j = bisect.bisect_left(fetch, (dispatch[i][1],)) if i >= 0 else len(fetch)
        if j < len(fetch) and fetch[j][1] > e - 5_000_000:  # a step's own fetch, not a later one's
            launch.append((s - dispatch[i][0]) / 1e3)
            tail.append((fetch[j][1] - e) / 1e3)
    if not launch:
        return {}
    deciles = lambda xs: [sorted(xs)[len(xs) * k // 10] for k in (1, 5, 9)] + [max(xs)]
    return {
        "steps": len(launch), "launch_us": deciles(launch), "tail_us": deciles(tail),
        "launch_plus_tail_us": deciles([a + b for a, b in zip(launch, tail)]),
        "dispatch_call_us": deciles([(e - s) / 1e3 for s, e in dispatch]),
    }


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
    devices, bench = tr.read_planes(path)
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
    res["idle_by_span"] = slice_idle(dev, bench, program_spans(path))
    with open(out, "w") as f:
        json.dump(res, f)
    print("reduced", path, json.dumps(red["modules"]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
