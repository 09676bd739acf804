"""From a profiler trace (``.xplane.pb``) to busy, idle, module, op and
collective times.

``jax.profiler`` writes one XSpace per traced window. Each accelerator is a
plane named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per HLO
operation the core executed (nested where an op such as ``while`` contains
others), ``Async XLA Ops`` the transfers that run beside them (async
copies and slices, and a collective from its ``-start`` to its ``-done``),
and ``XLA Modules`` one event per executed program. An event's name is the
whole HLO instruction (``%copy.7 = bf16[64,560,16,64]{...} copy(...)``).
Host threads are lines of the ``/host:CPU`` plane, where the harness's own
``jax.profiler.TraceAnnotation`` spans (``bench/<name>``) appear on the same
clock.

All arithmetic is on whole nanoseconds, so the reduction of the small
recorded trace under ``benchmark/testdata`` can be asserted exactly.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[int, int]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench/"
COLLECTIVE = re.compile(
    r"(all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute)"
)
_MODULE_ID = re.compile(r"\(\d+\)$")
_INSTRUCTION = re.compile(r"^%?([A-Za-z_\-]+(?:\.[A-Za-z_\-]+)*?)(?:\.\d+)* = \(?([a-z0-9]+\[[0-9,]*\])?")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[List[int]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Points of the disjoint sorted ``a`` that the disjoint sorted ``b``
    does not cover."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: Sequence[Tuple[int, int, str]]) -> Dict[str, int]:
    """Per name, the time of each event that no event nested inside it
    covers (events of one line nest or follow; they do not cross)."""
    out: Dict[str, int] = {}
    stack: List[List] = []  # [end, name, self]

    def close(upto: int) -> None:
        while stack and stack[-1][0] <= upto:
            _, name, own = stack.pop()
            out[name] = out.get(name, 0) + own

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(1 << 62)
    return out


def leaves(events: Sequence[Tuple[int, int, str]]) -> List[Tuple[int, int, str]]:
    """The events that contain no other event: an op such as ``while``
    that only holds others is not work of its own."""
    ordered = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    return [
        ev for i, ev in enumerate(ordered)
        if i + 1 == len(ordered) or ordered[i + 1][0] >= ev[1]
    ]


def op_kind(raw: str) -> str:
    """An HLO instruction as the trace names it, cut to its kind and result
    shape: ``%copy.7 = bf16[64,560,16,64]{3,2,0,1} copy(...)`` becomes
    ``copy bf16[64,560,16,64]``; a bare name stays as it is."""
    m = _INSTRUCTION.match(raw)
    if not m:
        return raw[:80]
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def read_planes(path: str):
    """``{device index: {"ops": [(start, end, name)], "async": [...],
    "modules": [...]}}`` and the host's ``bench/`` spans
    ``[(start, end, name)]``, in ns."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, Dict[str, List[Tuple[int, int, str]]]] = {}
    host: List[Tuple[int, int, str]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            rec = devices.setdefault(int(m.group(1)), {"ops": [], "async": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", ASYNC_LINE: "async", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    rec[key].append((s, s + int(ev.duration_ns), ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        s = int(ev.start_ns)
                        host.append((s, s + int(ev.duration_ns), ev.name))
    return devices, host


def module_name(raw: str) -> str:
    """``jit_train_phase(1234)`` -> ``jit_train_phase``."""
    return _MODULE_ID.sub("", raw)


def reduce_device(ops, modules, async_ops=()) -> Dict[str, object]:
    """One device's busy intervals (the core's own ops), per-module and
    per-op-kind times (an op's own, less what nests inside it) and event
    counts, and its collectives' total and exposed time (ns): a
    collective is exposed while no other operation of the core runs."""
    ops = [(s, e, op_kind(n)) for s, e, n in ops]
    busy = union((s, e) for s, e, _ in ops)
    coll = union(
        (s, e) for s, e, n in list(ops) + [(s, e, op_kind(n)) for s, e, n in async_ops]
        if COLLECTIVE.search(n)
    )
    own = self_times(ops)
    compute = union((s, e) for s, e, n in leaves(ops) if not COLLECTIVE.search(n))
    exposed = subtract(coll, compute)
    per_module: Dict[str, Dict[str, int]] = {}
    for s, e, n in modules:
        rec = per_module.setdefault(module_name(n), {"ns": 0, "count": 0})
        rec["ns"] += e - s
        rec["count"] += 1
    return {
        "busy": busy,
        "busy_ns": length(busy),
        "modules": per_module,
        "op_self_ns": own,
        "op_count": dict(collections.Counter(n for _, _, n in ops)),
        "collective_ns": length(coll),
        "collective_exposed_ns": length(exposed),
    }


def label_gaps(busy: Sequence[Interval], window: Interval,
               host_spans: Sequence[Tuple[int, int, str]]) -> Dict[str, int]:
    """Idle time inside ``window`` by the innermost ``bench/`` host span
    that covered each gap's midpoint (``unlabelled`` where none did). One
    sweep: gaps come in time order, so the spans still open at a midpoint
    are kept in a short list."""
    spans = sorted(host_spans)
    out: Dict[str, int] = {}
    nxt, active = 0, []
    for s, e in subtract([window], busy):
        mid = (s + e) // 2
        while nxt < len(spans) and spans[nxt][0] <= mid:
            active.append(spans[nxt])
            nxt += 1
        active = [h for h in active if h[1] > mid]
        name = (
            min(active, key=lambda h: h[1] - h[0])[2][len(HOST_SPAN_PREFIX):]
            if active else "unlabelled"
        )
        out[name] = out.get(name, 0) + (e - s)
    return out


def clip(events: Sequence[Tuple[int, int, str]], window: Interval) -> List[Tuple[int, int, str]]:
    """The events' parts inside ``window``: one outside it is dropped, one
    across an edge is cut there."""
    lo, hi = window
    return [(max(s, lo), min(e, hi), n) for s, e, n in events if e > lo and s < hi]


def reduce_trace(path: str, clip_span: str = None) -> Dict[str, object]:
    """Everything the per-layer readers and ``device``/``breakdown`` need.

    ``busy_s`` is the mean over devices of the union of op intervals;
    ``span_s`` the time from the first to the last device event (the
    harness reports its own host-clock window beside it). With
    ``clip_span``, and a host span ``bench/<clip_span>`` in the trace,
    every device event is first cut to that span (the first of that
    name): the serving driver's steady slice, without the drain after it."""
    devices, host = read_planes(path)
    if not devices:
        return {"devices": 0}
    marks = sorted(h for h in host if h[2] == HOST_SPAN_PREFIX + str(clip_span))
    if marks:
        window = marks[0][:2]
        devices = {i: {k: clip(v, window) for k, v in d.items()} for i, d in devices.items()}
        host = [h for h in clip(host, window) if h[2] != marks[0][2]]
    per = {
        i: reduce_device(d["ops"], d["modules"], d.get("async", ()))
        for i, d in sorted(devices.items())
    }
    first = min(s for d in devices.values() for s, _, _ in d["ops"] + d["modules"])
    last = max(e for d in devices.values() for _, e, _ in d["ops"] + d["modules"])
    lead = per[min(per)]
    modules: Dict[str, Dict[str, float]] = {}
    for name, rec in lead["modules"].items():
        modules[name] = {"s": rec["ns"] / 1e9, "count": rec["count"]}
    ops = sorted((kv for kv in lead["op_self_ns"].items() if kv[1] > 0), key=lambda kv: -kv[1])
    # every kind, for the readers that take one kernel out of a module; the
    # result line's breakdown keeps the ten longest
    all_ops = {n: {"s": ns / 1e9, "count": lead["op_count"][n]} for n, ns in lead["op_self_ns"].items()}
    gaps = label_gaps(lead["busy"], (first, last), host)
    return {
        "devices": len(per),
        "busy_s": sum(p["busy_ns"] for p in per.values()) / len(per) / 1e9,
        "span_s": (last - first) / 1e9,
        "modules": modules,
        "ops": all_ops,
        "device_ops": [[n, ns / 1e9] for n, ns in ops[:10]],
        "idle_gaps": [
            [n, ns / 1e9] for n, ns in sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
        ],
        "collective_s": lead["collective_ns"] / 1e9,
        "collective_exposed_s": lead["collective_exposed_ns"] / 1e9,
    }
