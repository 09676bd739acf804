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
- :mod:`trlx_tpu.telemetry.attribution` — the chip's peaks as
  ``chip_smoke.py`` reads them (bf16 FLOP/s, HBM bytes/s by device kind)
  and its device gate.
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

import gc
import os
import threading
import time
from contextlib import contextmanager
from typing import Optional, Sequence

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
    "HostMark",
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
    "touch_host_counters",
    "warn_on_span_drops",
    "watch_compiles",
    "watch_host",
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
    (:func:`watch_compiles`) and its collector (:func:`watch_host`). One
    knob today — ``ring_size``, the span-ring
    capacity (per-request serving spans multiply span volume; an
    evicting ring truncates every trace the ``--trace-report`` analyzer
    reads). Unknown keys refuse loudly, like every other config section.
    Precedence: an explicit ``TRLX_TELEMETRY_RING`` env var wins over
    the config — the operator at the terminal outranks the YAML."""
    watch_compiles()
    watch_host()
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
#: what building a program takes of the host, in jax's three steps
#: (tracing, lowering, compiling or fetching from the persistent cache)
BUILD_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    COMPILE_EVENT,
)
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


def _record_stamped(name: str, start: float, end: float, attrs: dict) -> None:
    """A span whose time has passed, stamped ``[start, end]`` under the
    span open on the calling thread (no profiler annotation)."""
    tracer = get_tracer()
    if not tracer.enabled:
        return
    from trlx_tpu.telemetry.request_trace import _stamp

    thread = threading.current_thread()
    stamped = _stamp(name, start, end, thread.ident or 0, thread.name, attrs)
    inside = tracer.current()
    if inside is not None:
        stamped.depth = inside.depth + 1
    tracer.record(stamped, parent=None if inside is None else inside.index)


def _on_compile(event: str, duration: float, **_) -> None:
    if event in BUILD_EVENTS:
        _host.build_s += duration
    if event != COMPILE_EVENT:
        return
    registry = get_metrics()
    registry.counter("jit/compiles").inc()
    registry.counter("jit/compile_s").inc(duration)
    end = monotonic()
    _record_stamped("jit/compile", end - duration, end, {})


def _on_cache_event(event: str, **_) -> None:
    counter = _CACHE_EVENT_COUNTERS.get(event)
    if counter is not None:
        get_metrics().counter(counter).inc()


# ------------------------- what paused the host -------------------------- #

#: a collection younger than the oldest generation is a span of its own
#: only where it lasted this long (ms); a full collection always is one
YOUNG_GC_SPAN_MS = 1.0

_GC_MS = ("host/gc_ms[gen=0]", "host/gc_ms[gen=1]", "host/gc_ms[gen=2]")
_GC_PAUSES = (
    "host/gc_pauses[gen=0]", "host/gc_pauses[gen=1]", "host/gc_pauses[gen=2]",
)
#: the counters that read 0.0, not absent, where nothing happened
HOST_COUNTERS = (
    "host/gc_ms", "host/gc_pauses", *_GC_MS, *_GC_PAUSES,
    "host/stalls", "host/stall_ms",
)


class _HostTotals:
    """Process-wide totals the loops take differences of, kept here and
    not in the registry (which a caller may ``clear()``): the collector's
    summed and longest pause (ms), and the seconds spent building
    programs (``BUILD_EVENTS``: a traced program that a warm cache serves
    still costs its tracing and lowering, which ``jit/compile_s`` does not
    hold; nested traces count twice, so this is an upper estimate)."""

    __slots__ = ("gc_ms", "gc_max_ms", "build_s", "gc_started", "gc_span")

    def __init__(self):
        self.gc_ms = 0.0
        self.gc_max_ms = 0.0
        self.build_s = 0.0
        self.gc_started = 0.0
        self.gc_span = None  # the open host/gc span of a full collection


_host = _HostTotals()


def watch_host() -> None:
    """Hook the garbage collector (``gc.callbacks``, once per process):
    every collection advances the counters ``host/gc_ms`` (its wall, ms)
    and ``host/gc_pauses`` with their ``[gen=0|1|2]`` twins and sets the
    gauge ``host/gc_max_ms``, the longest pause since the process
    started, whatever the tracer's state. A full collection (generation
    2) is a real span ``host/gc`` from the collector's ``start`` to its
    ``stop``: it nests under the span open on the collecting thread and
    carries the profiler annotation, so in a device trace the pause has
    a name on the device's clock. A younger one is counted, and stamped
    after the fact where it lasted :data:`YOUNG_GC_SPAN_MS` or more.

    A collection on any thread holds the interpreter lock and pauses
    every other: the counters are the process's, and a loop charges a
    phase or an iteration by their difference, not by span parentage.
    The collector runs one collection at a time, callbacks included, so
    the hook's state needs no lock; but a collection starts wherever its
    thread stands, also inside a locked section of the tracer or the
    registry, which is why their locks are reentrant. Nothing here
    changes when or what the collector collects."""
    if _on_gc in gc.callbacks:
        return
    get_tracer(), get_metrics()  # built here, never inside a collection
    gc.callbacks.append(_on_gc)


def _on_gc(phase: str, info: dict) -> None:
    # an exception in a collector callback is printed and lost: nothing
    # leaves this function, and the counters come before the span
    try:
        if phase == "start":
            if info["generation"] == 2:
                _host.gc_span = _open_gc_span()
            _host.gc_started = monotonic()
            return
        end = monotonic()
        ms = (end - _host.gc_started) * 1000.0
        gen = info["generation"]
        _host.gc_ms += ms
        if ms > _host.gc_max_ms:
            _host.gc_max_ms = ms
        registry = get_metrics()
        registry.counter("host/gc_ms").inc(ms)
        registry.counter("host/gc_pauses").inc()
        registry.counter(_GC_MS[gen]).inc(ms)
        registry.counter(_GC_PAUSES[gen]).inc()
        longest = registry.gauge("host/gc_max_ms")
        if longest.value != _host.gc_max_ms:  # a new longest, or a cleared registry
            longest.set(_host.gc_max_ms)
        span, _host.gc_span = _host.gc_span, None
        if span is not None:
            span.set(collected=info["collected"])
            span.__exit__(None, None, None)
        elif gen < 2 and ms >= YOUNG_GC_SPAN_MS:
            _record_stamped(
                "host/gc", _host.gc_started, end,
                {"generation": gen, "collected": info["collected"]},
            )
    except Exception:
        pass


def _open_gc_span():
    """An entered ``host/gc`` span, or None where the tracer refuses."""
    try:
        span = get_tracer().span("host/gc", generation=2)
        span.__enter__()
        return span
    except Exception:
        return None


def touch_host_counters() -> None:
    """Make the host's counters stand in the registry at what they read
    (0.0 where nothing happened since it was cleared) and the gauge at
    the longest pause: the loops call this once a phase and once a
    harvested group, so a window in which no collection fell and nothing
    stalled reports zeros and not absences."""
    registry = get_metrics()
    for name in HOST_COUNTERS:
        registry.counter(name)
    registry.gauge("host/gc_max_ms").set(_host.gc_max_ms)


class HostMark:
    """Where the host stood when a phase or an iteration began: the
    clock, the calling thread's CPU time, the collector's pauses and the
    time spent building programs so far, and the tracer's summed wall under each of
    ``names`` (and of ``wall``, the spans that together are the whole).
    :meth:`fill` writes what has passed since :meth:`take` into a timing
    row (``telemetry/health.py::TimingSeries``). Nothing is allocated
    between the two but floats."""

    __slots__ = ("names", "wall", "_all", "spans", "t", "cpu", "gc_ms", "build_s")

    def __init__(self, names: Sequence[str], wall: Sequence[str] = ()):
        self.names = tuple(names)
        self.wall = tuple(wall)
        self._all = self.names + self.wall
        self.spans = [0.0] * len(self._all)
        self.t = self.cpu = self.gc_ms = self.build_s = 0.0

    def take(self) -> None:
        totals = get_tracer().totals
        spans = self.spans
        for i, name in enumerate(self._all):
            spans[i] = totals.get(name, 0.0)
        self.gc_ms = _host.gc_ms
        self.build_s = _host.build_s
        self.cpu = time.thread_time()
        self.t = monotonic()

    def fill(self, row, offset: int = 0) -> float:
        """The walls under ``names`` since :meth:`take` into
        ``row.values`` from ``offset`` on, and ``row.gc_ms``,
        ``row.compile_ms`` and ``row.cpu_share`` (this thread's CPU time
        over the clock's wall). Returns the whole's wall, ms: the summed
        walls under ``wall``, or the clock's where none was named."""
        totals = get_tracer().totals
        spans, values = self.spans, row.values
        n = len(self.names)
        for i, name in enumerate(self.names):
            values[offset + i] = totals.get(name, 0.0) - spans[i]
        row.gc_ms = _host.gc_ms - self.gc_ms
        row.compile_ms = (_host.build_s - self.build_s) * 1000.0
        clock = monotonic() - self.t
        row.cpu_share = (time.thread_time() - self.cpu) / clock if clock > 0 else 0.0
        if not self.wall:
            return clock * 1000.0
        whole = 0.0
        for i, name in enumerate(self.wall):
            whole += totals.get(name, 0.0) - spans[n + i]
        return whole
