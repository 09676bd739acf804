"""Runtime telemetry: span tracing, device metrics, profiler windows.

The static-analysis stack (engines 1–9) gates what a program *should*
cost before a run; this package watches the run itself:

- :mod:`trlx_tpu.telemetry.tracer` — low-overhead span tracer on one
  monotonic clock; the phase loop's single timing source (``with
  telemetry.span("phase/collect"): ...``), with per-name p50/p95 stats
  and a Perfetto/chrome-tracing JSONL exporter.
- :mod:`trlx_tpu.telemetry.device_metrics` — ``device.memory_stats()``
  sampling (live/peak HBM, transfer counters) logged next to the static
  engine-7 predictions so static-vs-measured gaps become a printed
  attribution.
- :mod:`trlx_tpu.telemetry.profiler` — programmatic ``jax.profiler``
  windows: ``train.profile_phase: N`` dumps one xplane trace for
  exactly phase N.
- :mod:`trlx_tpu.telemetry.health` — run-health monitoring: streaming
  training-dynamics detectors (kl-spike, entropy-collapse,
  ratio-explosion, grad-spike, reward-saturation, nan-precursor) over
  the per-update stats rows, enabled by ``train.health``.
- :mod:`trlx_tpu.telemetry.flight_recorder` — crash forensics: a
  bounded ring of phase records dumped as one JSON file on uncaught
  exceptions / detector policy / ``train.flight_dump_phase``;
  ``python -m trlx_tpu.telemetry --inspect <dump>`` renders the
  triage view.
- :mod:`trlx_tpu.telemetry.metrics` — typed rank-0 metrics registry
  (counters, gauges with sample rings, histograms) absorbing the
  ad-hoc stats dicts (``engine/*``, ``async/*``, ``mem/*``,
  ``serve/*``) into one snapshot-able namespace;
  ``telemetry.get_metrics()``.
- :mod:`trlx_tpu.telemetry.attribution` — measured MFU / HBM-BW
  utilization per traced program per phase window (engine-7 statics ÷
  span walls), async bubble breakdown, phase goodput — bench prints
  the table every round.
- :mod:`trlx_tpu.telemetry.run_ledger` — per-run manifests appended to
  a ledger JSONL; ``python -m trlx_tpu.telemetry --compare`` renders a
  movers diff between any two runs, ``--watch`` tails a live run's
  phase rows.

Engine 10 (``python -m trlx_tpu.analysis --perf-audit``) gates the
span durations against the ``perf_budgets`` section of
``analysis/budgets.json``. See docs/observability.md for the span
taxonomy and workflows.

The module-level :func:`span` / :func:`get_tracer` API routes through
one process-global tracer, enabled by default on the main process only
(rank-0 gating, like ``Logger``); ``TRLX_TELEMETRY=0/1`` overrides.
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from typing import Optional

from trlx_tpu.telemetry.tracer import (  # noqa: F401
    DEFAULT_RING_SIZE,
    NULL_SPAN,
    Span,
    Tracer,
    chrome_counter_events,
    chrome_trace_events,
    chrome_trace_from_jsonl,
    env_ring_size,
    export_chrome_jsonl,
    monotonic,
    quantile,
)
from trlx_tpu.telemetry.metrics import (  # noqa: F401  (after tracer: shares its clock)
    NULL_INSTRUMENT,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    configure_metrics,
    flatten_snapshot,
    get_metrics,
    scoped_metrics,
    split_metric_label,
)

__all__ = [
    "DEFAULT_RING_SIZE",
    "NULL_SPAN",
    "Span",
    "Tracer",
    "chrome_counter_events",
    "chrome_trace_events",
    "chrome_trace_from_jsonl",
    "configure",
    "configure_from_dict",
    "configure_metrics",
    "env_ring_size",
    "export_chrome_jsonl",
    "get_metrics",
    "get_tracer",
    "monotonic",
    "now",
    "quantile",
    "scoped_metrics",
    "scoped_tracer",
    "span",
    "warn_on_span_drops",
    "watch_compiles",
]

_tracer: Optional[Tracer] = None


def _default_enabled() -> bool:
    env = os.environ.get("TRLX_TELEMETRY", "").lower()
    if env in ("0", "false", "off"):
        return False
    if env in ("1", "true", "on"):
        return True
    try:
        # rank-0 gating (multi-host pods trace on the main process only);
        # lazy so importing telemetry never forces jax initialization
        from trlx_tpu.parallel.distributed import is_main_process

        return is_main_process()
    except Exception:
        return True


def get_tracer() -> Tracer:
    """The process-global tracer (created on first use; ring capacity
    from ``TRLX_TELEMETRY_RING`` when set)."""
    global _tracer
    if _tracer is None:
        _tracer = Tracer(
            enabled=_default_enabled(), max_records=env_ring_size()
        )
    return _tracer


def span(name: str, force: bool = False, **attrs):
    """Open a span on the global tracer (see :meth:`Tracer.span`)."""
    return get_tracer().span(name, force=force, **attrs)


def now() -> float:
    """The shared monotonic clock, in seconds."""
    return monotonic()


@contextmanager
def scoped_tracer(tracer: Optional[Tracer] = None):
    """Temporarily install ``tracer`` (default: a fresh enabled one) as
    the process-global tracer; the previous tracer — records, enabled
    flag, everything — is restored on exit. Harnesses that drive
    instrumented code (the perf audit) use this so their measurement
    neither wipes nor leaks into the caller's span history."""
    global _tracer
    prev = get_tracer()
    installed = tracer if tracer is not None else Tracer(enabled=True)
    _tracer = installed
    try:
        yield installed
    finally:
        _tracer = prev


_drops_warned = False


def warn_on_span_drops(tracer: Optional[Tracer] = None) -> int:
    """Return the tracer's ``dropped`` count, warning ONCE on stderr
    when it is nonzero. Silent ring evictions skew every per-name p50
    (the oldest — often slowest, compile-bearing — spans vanish first),
    so any consumer aggregating span stats for a report should surface
    this; the serving CLI (``inference/__main__.py``) calls it."""
    global _drops_warned
    t = tracer if tracer is not None else get_tracer()
    dropped = int(t.dropped)
    if dropped and not _drops_warned:
        import sys

        print(
            f"warning: span ring dropped {dropped} spans (oldest "
            "evicted) — per-name p50/p95 stats cover a truncated "
            "window; raise the ring with "
            "telemetry.configure(max_records=...)",
            file=sys.stderr,
        )
        _drops_warned = True
    return dropped


def configure(
    enabled: Optional[bool] = None, max_records: Optional[int] = None
) -> Tracer:
    """Adjust the global tracer; returns it. ``max_records`` resizes
    the ring (newest records kept; forced evictions count as dropped)."""
    tracer = get_tracer()
    if enabled is not None:
        tracer.enabled = bool(enabled)
    if max_records is not None:
        tracer.set_max_records(max_records)
    return tracer


def configure_from_dict(d) -> Tracer:
    """Apply the ``train.telemetry`` config section (and return the
    global tracer). Every trainer and server is built through here, so
    this is also where the process starts watching its compiles
    (:func:`watch_compiles`). One knob today — ``ring_size``, the span-ring
    capacity (per-request serving spans multiply span volume; an
    evicting ring truncates every trace the ``--trace-report`` analyzer
    reads). Unknown keys refuse loudly, like every other config section.
    Precedence: an explicit ``TRLX_TELEMETRY_RING`` env var wins over
    the config — the operator at the terminal outranks the YAML."""
    watch_compiles()
    d = dict(d or {})
    known = {"ring_size"}
    unknown = set(d) - known
    if unknown:
        raise ValueError(
            f"Unknown train.telemetry keys: {sorted(unknown)} "
            f"(known: {sorted(known)})"
        )
    ring = d.get("ring_size")
    if ring is not None:
        # validate BEFORE precedence: a bad YAML value must refuse on
        # every machine, not only the ones without an env override
        ring = int(ring)
        if ring < 1:
            raise ValueError(
                f"train.telemetry.ring_size={ring} must be >= 1"
            )
        # a VALID env override wins; a malformed one (which
        # env_ring_size already ignores) must not ALSO block the
        # config — validity decides precedence, not mere presence
        raw = os.environ.get("TRLX_TELEMETRY_RING")
        try:
            env_valid = raw is not None and int(raw) > 0
        except ValueError:
            env_valid = False
        if not env_valid:
            return configure(max_records=ring)
    return get_tracer()


# ------------------------- which step recompiled ------------------------- #

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "jit/cache_hits",
    "/jax/compilation_cache/cache_misses": "jit/cache_misses",
}
_watching_compiles = False


def watch_compiles() -> None:
    """Listen to ``jax.monitoring`` (installed once per process; jax keeps
    listeners for the life of the process): every backend compile
    advances the counters ``jit/compiles`` and ``jit/compile_s`` and is
    recorded as a span ``jit/compile`` stamped ``[now - duration, now]``
    under the span open on the compiling thread, so a trace says inside
    which step a compile fell; persistent-cache hits and misses advance
    ``jit/cache_hits`` / ``jit/cache_misses``. A retrieval from the
    persistent cache is a "compile" of its retrieval time, as jax
    reports it. Events land in whatever tracer and registry are global
    when they fire."""
    global _watching_compiles
    if _watching_compiles:
        return
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_compile)
    monitoring.register_event_listener(_on_cache_event)
    _watching_compiles = True


def _on_compile(event: str, duration: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    registry = get_metrics()
    registry.counter("jit/compiles").inc()
    registry.counter("jit/compile_s").inc(duration)
    tracer = get_tracer()
    if not tracer.enabled:
        return
    from trlx_tpu.telemetry.request_trace import _stamp

    end = monotonic()
    thread = threading.current_thread()
    stamped = _stamp(
        "jit/compile", end - duration, end, thread.ident or 0, thread.name, {}
    )
    inside = tracer.current()
    if inside is not None:
        stamped.depth = inside.depth + 1
    tracer.record(stamped, parent=None if inside is None else inside.index)


def _on_cache_event(event: str, **_) -> None:
    counter = _CACHE_EVENT_COUNTERS.get(event)
    if counter is not None:
        get_metrics().counter(counter).inc()
