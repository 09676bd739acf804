"""What every driver shares: finding a cell's files by name, the device
gate, the compile counter, host spans, the profiler window and the result
line.

Nothing here knows a cell, a configuration, a model family or a metric by
name: those are files (``workloads/``, ``traffic/``, ``configs/``,
``reference/``, ``layer_metrics/``) that ``BENCHMARK.json`` and the
configuration files name.
"""

from __future__ import annotations

import contextlib
import functools
import glob
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

from benchmark.trace_reduce import HOST_SPAN_PREFIX as SPAN_PREFIX

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    """The machine does not hold the chips the cell asks for."""


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def _module_at(path: str):
    name = "benchmark_family_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_family(config_file: Dict[str, Any], root: str = HERE):
    """The model family of a configuration: the module at its ``reference``
    path (relative to the checkout, which is the parent of ``root``),
    loaded by file path so that a copy of the tree under another root
    brings its own families. The module exports ``forward(params, cfg,
    ids, mask)`` (the plain float32 reference) as the composition of its
    two halves ``trunk(params, cfg, ids, mask)`` and ``head(params, cfg,
    hidden)``, which the checks call, ``shape(cfg)`` (the sizes
    ``arithmetic.py`` reckons with) and, where the family has one,
    ``check_config(cfg)``, which raises on a configuration the program
    cannot build as published."""
    path = os.path.realpath(os.path.join(os.path.dirname(root), config_file["reference"]))
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"configuration {config_file.get('name')!r} names the reference {path}, which is not there")
    return _module_at(path)


def load_cell(name: str, root: str = HERE) -> Dict[str, Any]:
    """A cell with its configuration and traffic mix resolved by name, the
    ``root`` they were found under, and the configuration's ``family``
    (:func:`load_family`), which has checked the configuration."""
    def read(kind, key):
        path = os.path.join(root, kind, f"{key}.json")
        if not os.path.exists(path):
            raise FileNotFoundError(f"no {kind} file for {key!r}: {path}")
        with open(path) as f:
            return json.load(f)

    cell = read("workloads", name)
    cell["config_file"] = read("configs", cell["config"])
    cell["traffic_file"] = read("traffic", cell["traffic"])
    cell["root"] = root
    cell["family"] = load_family(cell["config_file"], root)
    if hasattr(cell["family"], "check_config"):
        cell["family"].check_config(cell["config_file"])
    return cell


def load_layer_metrics(cell_name: str, root: str = HERE) -> List[Dict[str, Any]]:
    """The per-layer metrics read in this cell: the ``per_layer`` entries
    of the ``BENCHMARK.json`` beside ``root`` whose ``workloads`` list the
    cell, each with the ``reader`` of its file
    ``layer_metrics/<name>.json``. The manifest is the one place that
    says which cell reads which metric, its unit and what it moves; the
    file says how it is read. An entry names its cells: one without
    ``workloads`` is refused."""
    with open(os.path.join(os.path.dirname(root), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    out = []
    for entry in manifest["per_layer"]:
        if "workloads" not in entry:
            raise KeyError(f"the per-layer metric {entry['name']!r} lists no `workloads` in BENCHMARK.json")
        if cell_name not in entry["workloads"]:
            continue
        path = os.path.join(root, "layer_metrics", f"{entry['name']}.json")
        if not os.path.exists(path):
            raise FileNotFoundError(f"no reader file for the per-layer metric {entry['name']!r}: {path}")
        with open(path) as f:
            out.append(dict(entry, reader=json.load(f)["reader"]))
    return out


def arch_of(config_file: Dict[str, Any]) -> Dict[str, Any]:
    """``model.model_arch`` for the program: the published keys the
    program's config class knows, plus the run's dtypes."""
    run = config_file["run"]
    arch = {k: config_file[k] for k in run["arch_keys"]}
    arch["kv_cache_dtype"] = run["kv_cache_dtype"]
    return arch


def kv_dtype_of(config_file: Dict[str, Any], capacity: int) -> str:
    """The cache dtype the program resolves for this capacity (``auto``
    picks by it)."""
    from trlx_tpu.models.gpt2 import resolve_kv_cache_dtype

    return resolve_kv_cache_dtype(config_file["run"]["kv_cache_dtype"], capacity)


# ------------------------------ the device ------------------------------ #


def require_chips(chips: int, allow_cpu: bool = False) -> Dict[str, Any]:
    """The device as jax reports it, or :class:`NoAccelerator`. A
    measurement never falls back to the CPU; ``allow_cpu`` is the tests'
    rehearsal at a tiny size, whose result says ``platform: cpu``."""
    import jax

    from benchmark.arithmetic import load_peaks

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        if not allow_cpu:
            raise NoAccelerator(
                f"jax found platform {dev.platform!r} ({dev.device_kind}); "
                "the benchmark measures on a TPU only"
            )
        peaks = {"bf16_flops_per_s": float("nan"), "hbm_bytes_per_s": float("nan")}
    else:
        peaks = load_peaks(dev.device_kind)
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, jax found {len(devices)}")
    return {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices), "peaks": peaks,
    }


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps
    no allocator statistics, which is the CPU rehearsal only)."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def place_compile_cache() -> str:
    """JAX's persistent compile cache where ``JAX_COMPILATION_CACHE_DIR``
    says, else the program's fixed ``<checkout>/.jax_cache``; every
    program is kept, however fast it compiled, so a warm run compiles
    nothing."""
    import jax

    from trlx_tpu.utils.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class CompileCounter:
    """Counts backend compiles (a persistent-cache hit counts its
    retrieval) and their seconds, from jax's monitoring events."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def install(self) -> "CompileCounter":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def mark(self):
        return (self.count, self.seconds)


def registry_scalars(before: Optional[Dict[str, Dict[str, float]]] = None) -> Dict[str, Dict[str, float]]:
    """The counters and gauges of the program's metrics registry, by name;
    counters less what they read in ``before`` (an earlier call's result),
    which leaves a window's own increments. A gauge is its last value."""
    from trlx_tpu import telemetry

    snap = telemetry.get_metrics().snapshot()
    start = (before or {}).get("counters", {})
    return {
        "counters": {k: v - start.get(k, 0.0) for k, v in snap["counters"].items()},
        "gauges": dict(snap["gauges"]),
    }


# ------------------------------ host spans ------------------------------ #


class Spans:
    """The harness's own spans around its calls into the program: kept in
    memory as (name, start, end) on ``time.perf_counter`` and, while a
    profiler window is open, written into the trace as ``bench/<name>`` so
    device gaps can be labelled on the profiler's clock."""

    def __init__(self):
        self.records: List[tuple] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def durations_ms(self, name: str) -> List[float]:
        return [(e - s) * 1e3 for n, s, e in self.records if n == name]

    def clear(self) -> None:
        self.records.clear()


class ProfilerWindow:
    """One ``jax.profiler`` trace written under ``<checkout>/.bench_trace``
    (recreated each run); :meth:`stop` returns the ``.xplane.pb`` path."""

    def __init__(self, tag: str):
        self.dir = os.path.join(REPO, ".bench_trace", tag)

    def start(self) -> None:
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        # the device and the harness's TraceMe spans; not every Python call
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self) -> Optional[str]:
        import jax

        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
        return max(found, key=os.path.getmtime) if found else None


# ------------------------------ the result ------------------------------ #


class CheckLog:
    """The sub-checks of one run's ``correct``: each printed on a line of
    its own as it is made, so that a ``false`` in a log says which
    comparison failed, and kept, the number compared beside its limit, for
    the end of the result line and of the error stream (what a record of a
    refused run keeps)."""

    def __init__(self):
        self.entries: Dict[str, Dict[str, Any]] = {}

    def line(self, name: str, value, limit: str, ok: bool) -> bool:
        self.entries[name] = {"value": value, "limit": limit, "ok": bool(ok)}
        self.echo(sys.stdout, [name])
        return bool(ok)

    def echo(self, stream, names=None) -> None:
        """The named checks (all of them where none is named), a line each."""
        for name in self.entries if names is None else names:
            c = self.entries[name]
            print(f"check {name}: value={c['value']!r} tolerance={c['limit']} ok={c['ok']}",
                  file=stream, flush=True)


def median(values: List[float]) -> Optional[float]:
    import statistics

    return statistics.median(values) if values else None


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None,
                checks: Optional[Dict[str, Dict[str, Any]]] = None) -> str:
    """The result, ``checks`` (each number compared beside its limit) last."""
    out: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks or {}
    return json.dumps(out)
