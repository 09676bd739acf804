"""Per-layer metric readers.

A per-layer metric is a JSON file under ``layer_metrics/`` whose ``reader``
group names one of the kinds below and its arguments. A reader gets the
run's ``record`` (the harness's spans, the program's tracer statistics and
metrics registry, the reduced device trace, the cell's shapes and the
chip's peaks) and returns a number, or ``None`` where it found nothing to
read — the harness then leaves the metric out of the line.

Kinds:

- ``harness_span``: ``span`` — median over phases of the harness's own
  span of that name, ms.
- ``tracer_span_per_phase``: ``span`` — the program's tracer span of that
  name, summed wall per completed phase, ms.
- ``histogram``: ``name``, ``stat`` (``p50``/``p95``/``mean``) — the
  program's metrics registry.
- ``record``: ``key``, ``scale`` — a number the driver put in the record
  (``compile_s_setup``, ``loadgen_lag_p95_ms``).
- ``phase_mfu``: ``span`` — all required FLOPs of a phase (collect and
  train) over the harness span's median, chips and peak, %. Taken over
  the whole phase because with ``phase_overlap`` the first epoch's updates
  run inside the collect span: a train-only time leaves them out.
- ``module_roofline``: ``module`` (regex over XLA module names),
  ``flops`` — required FLOPs over peak over the summed device time of the
  matching modules per phase, %.
- ``decode_hbm_share``: ``module`` (regex) — required bytes of the decode
  steps one call of the matching module makes (``record["decode"]``:
  ``batch``, ``mean_context``, ``steps_per_call``) over peak bandwidth
  over the module's mean device time per call, %. Decode is bound by
  memory traffic, so this is its share of the roofline.
- ``collective``: ``what`` (``ms_per_phase``/``exposed_share``).
- ``memory_peak_gb``: the peak on the fullest chip as the driver read it
  when the window had closed, before the reference ran.
- ``counter``: ``name``[, ``per`` (``phase``/``second``)] - a counter (its
  increments inside the window) or a gauge (its last value) of the
  program's metrics registry, as it is, per completed phase, or per
  second of the window.
- ``op_roofline``: ``op`` (regex over device operation names as
  ``trace_reduce.op_kind`` spells them: kind and result shape), ``count``
  (the name of a function in the family's file, else in
  ``arithmetic.py``) - one kernel's share of its roofline, %:
  ``count(record, ops)`` gets the matching operations of the first chip
  over the traced window (``{name: {"s", "count"}}``) and returns the
  FLOPs and the bytes which that many executions require on one chip at
  this cell's shapes; the reader divides the larger of FLOPs / peak
  FLOP/s and bytes / peak bytes/s by the operations' summed device time.
"""

from __future__ import annotations

import math
import re
from typing import Any, Callable, Dict, Optional

from benchmark import arithmetic, harness


def trace_of(record) -> Optional[Dict[str, Any]]:
    """The run's reduced device trace (reduced once), or ``None`` where no
    trace was taken or it holds no TPU plane."""
    if record.get("xplane") is None:
        return None
    if "trace" not in record:
        from benchmark.trace_reduce import reduce_trace

        record["trace"] = reduce_trace(record["xplane"], record.get("trace_clip"))
    return record["trace"] if record["trace"].get("devices") else None


def _units(record) -> int:
    """Phases (ppo) or 1 (serve): what 'per phase' divides by."""
    return max(1, int(record.get("phases") or 1))


def harness_span(record, spec):
    return harness.median(record["spans"].durations_ms(spec["span"]))


def tracer_span_per_phase(record, spec):
    stats = record.get("tracer_stats", {}).get(spec["span"])
    return stats["total_ms"] / _units(record) if stats else None


def histogram(record, spec):
    summary = record.get("histograms", {}).get(spec["name"])
    if not summary or not summary.get("count"):
        return None
    return summary.get(spec["stat"])


def from_record(record, spec):
    value = record.get(spec["key"])
    return None if value is None else float(value) * spec.get("scale", 1.0)


def _flops(record, which: str) -> float:
    collect, train = record["flops"]
    return {"collect": collect, "train": train}[which]


def phase_mfu(record, spec):
    ms = harness_span(record, spec)
    if not ms:
        return None
    peak = record["device"]["peaks"]["bf16_flops_per_s"]
    return 100.0 * sum(record["flops"]) / (ms / 1e3) / record["chips"] / peak


def _module_seconds(record, pattern: str):
    trace = trace_of(record)
    if trace is None:
        return None, 0
    rx = re.compile(pattern)
    hits = [m for name, m in trace["modules"].items() if rx.search(name)]
    return sum(m["s"] for m in hits), sum(m["count"] for m in hits)


def module_roofline(record, spec):
    seconds, _ = _module_seconds(record, spec["module"])
    if not seconds:
        return None
    peak = record["device"]["peaks"]["bf16_flops_per_s"]
    per_phase = seconds / _units(record)
    return 100.0 * _flops(record, spec["flops"]) / record["chips"] / peak / per_phase


def decode_hbm_share(record, spec):
    seconds, calls = _module_seconds(record, spec["module"])
    if not seconds or not calls or "decode" not in record:
        return None
    d = record["decode"]
    need = arithmetic.decode_step_bytes(
        record["shape"], d["batch"] / record["chips"], d["mean_context"],
        weight_bytes=2, kv_bytes=1 if record["kv_cache_dtype"] == "int8" else 2,
        shards=d.get("weight_shards", 1),
        state_bytes=arithmetic.DTYPE_BYTES.get(record.get("state_dtype")),
    )
    step_s = seconds / (calls * d.get("steps_per_call", 1))
    return 100.0 * need / record["device"]["peaks"]["hbm_bytes_per_s"] / step_s


def collective(record, spec):
    trace = trace_of(record)
    if trace is None or not trace["collective_s"]:
        return None
    if spec["what"] == "ms_per_phase":
        return trace["collective_s"] * 1e3 / _units(record)
    return 100.0 * trace["collective_exposed_s"] / trace["collective_s"]


def memory_peak_gb(record, spec):
    peak = record.get("memory_peak_bytes")
    return peak / 1e9 if peak else None


def counter(record, spec):
    value = record.get("counters", {}).get(spec["name"])
    if value is None:
        value = record.get("gauges", {}).get(spec["name"])
    per = {None: 1.0, "phase": _units(record), "second": record.get("window_s")}[spec.get("per")]
    return value / per if value is not None and per else None


def op_roofline(record, spec):
    trace = trace_of(record)
    if trace is None:
        return None
    rx = re.compile(spec["op"])
    ops = {name: op for name, op in trace["ops"].items() if rx.search(name)}
    seconds = sum(op["s"] for op in ops.values())
    if not seconds:
        return None
    count = getattr(record["cell"]["family"], spec["count"], None) or getattr(arithmetic, spec["count"])
    flops, moved = count(record, ops)
    peaks = record["device"]["peaks"]
    least = max(flops / peaks["bf16_flops_per_s"], moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


READERS: Dict[str, Callable] = {
    "harness_span": harness_span,
    "tracer_span_per_phase": tracer_span_per_phase,
    "histogram": histogram,
    "record": from_record,
    "phase_mfu": phase_mfu,
    "module_roofline": module_roofline,
    "decode_hbm_share": decode_hbm_share,
    "collective": collective,
    "memory_peak_gb": memory_peak_gb,
    "counter": counter,
    "op_roofline": op_roofline,
}


def read_all(record, specs) -> Dict[str, Dict[str, Any]]:
    out = {}
    for spec in specs:
        value = READERS[spec["reader"]["kind"]](record, spec["reader"])
        if value is not None and math.isfinite(value):
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out
