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
    others = [m for m in modules if tr.module_name(m[2]) not in STEP_MODULES]
    return {
        "launch_and_tail": launch_and_tail(steps, spans, others),
        "window_s": (window[1] - window[0]) / 1e9,
        "idle_s": got["idle"] / 1e9,
        "in_module_s": got["in_module"] / 1e9,
        "between_s": {n: ns / 1e9 for n, ns in sorted(got["between"].items(), key=lambda kv: -kv[1])},
        "iterations": sum(1 for s, e, n in spans if n == "serve/step" and window[0] <= s < window[1]),
        "decode_steps": len(steps),
    }


def launch_and_tail(modules, spans, others=()):
    """How far the overlap split can be trusted, and what it cannot split.

    Each executed step (``modules``: the decode and verify steps' events)
    is paired with **the fetch of its own outputs**: a fetch waits for its
    program, so it is the first ``engine/fetch`` that returns once the
    step has ended (to within half a step, or 1 ms, of the two clocks),
    whether it was entered behind the step's own dispatch or, since the
    loop reads one step behind (ISSUE 44), an iteration later behind the
    next step's. The least ``fetch return - module end`` over the pairs
    is how far the device's clock stands from the host's at most
    (``clock_shift_us``); on the clock shifted by it the step's
    ``engine/dispatch`` span is the one after its predecessor's (a
    dispatch launches one step, in order: with a step in flight and an
    admission forward ahead of this one, the *next* step's dispatch is
    entered before this one begins too), and the last entered before the
    step began where the trace starts or has a hole.
    ``launch_us`` (module start less the span's entry) and ``tail_us``
    (the fetch's return less the module's end) are unshifted, so each
    compares the device's clock with the host's: a module that starts
    *before* its own dispatch was entered shows by how much the two are
    apart in this trace, and idle pieces shorter than that are under the
    wrong span. Their sum compares host with host and device with device,
    so it holds whatever the skew: from the dispatch's entry to the
    fetch's return, less the step itself. While every step is read out
    before the next is dispatched that is the time a step loses to the
    dispatch call, the launch and the transfer's tail together, which the
    starved ledger leaves to the device's clock; with a step in flight
    (``ahead_share``: the steps whose dispatch was entered while the step
    before them still ran) it is mostly the wait behind that step and
    prices nothing of the host's. What is left to read then is the
    device's own: ``device_gap_us``, from one step's end to the next's
    start less the ``others`` (admission forwards, harvests) that ran
    between them. Each as ``[p10, p50, p90, max]`` in us (the largest
    shows a stall the deciles hide); ``{}`` without the spans."""
    dispatch = sorted((s, e) for s, e, n in spans if n == "engine/dispatch")
    fetch = sorted((s, e) for s, e, n in spans if n == "engine/fetch")
    steps = sorted((s, e) for s, e, _ in modules)
    if not (dispatch and fetch and steps):
        return {}
    tol = min(1_000_000, sorted(e - s for s, e in steps)[len(steps) // 2] // 2)
    ends = [e for _, e in fetch]
    own = []  # (step, the fetch that waited for it)
    for s, e in steps:
        j = bisect.bisect_left(ends, e - tol)
        if j < len(fetch) and ends[j] < e + 5_000_000:  # a step's own fetch, not a later one's
            own.append(((s, e), fetch[j]))
    if not own:
        return {}
    shift = min(f[1] - m[1] for m, f in own)
    entries = [s for s, _ in dispatch]
    launch, tail, ahead = [], [], []
    before = dict(zip(steps[1:], steps))  # a step -> the step ahead of it
    i = None
    for (s, e), f in own:
        last = bisect.bisect_right(entries, s + shift) - 1
        if last < 0:
            continue
        i = i + 1 if i is not None and i + 1 <= last <= i + 2 else last
        launch.append((s - entries[i]) / 1e3)
        tail.append((f[1] - e) / 1e3)
        if (s, e) in before:
            ahead.append(entries[i] < before[(s, e)][1] + shift)
    if not launch:
        return {}
    busy = sorted((s, e) for s, e, _ in others)
    gaps = []
    for (_, e0), (s1, _) in zip(steps, steps[1:]):
        between = sum(min(e, s1) - max(s, e0) for s, e in busy if s < s1 and e > e0)
        gaps.append((s1 - e0 - between) / 1e3)
    deciles = lambda xs: [sorted(xs)[len(xs) * k // 10] for k in (1, 5, 9)] + [max(xs)]
    out = {
        "steps": len(launch), "launch_us": deciles(launch), "tail_us": deciles(tail),
        "launch_plus_tail_us": deciles([a + b for a, b in zip(launch, tail)]),
        "dispatch_call_us": deciles([(e - s) / 1e3 for s, e in dispatch]),
        "clock_shift_us": shift / 1e3,
        "ahead_share": sum(ahead) / len(ahead) if ahead else 0.0,
    }
    if gaps:
        out["device_gap_us"] = deciles(gaps)
    return out


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
