"""What every driver shares: finding a cell's files by name, the device
gate, the compile counter, host spans, the profiler window and the result
line.

Nothing here knows a cell, a configuration or a metric by name: those are
files (``workloads/``, ``traffic/``, ``configs/``, ``layer_metrics/``)
that ``BENCHMARK.json`` names.
"""

from __future__ import annotations

import contextlib
import glob
import json
import os
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


def load_cell(name: str, root: str = HERE) -> Dict[str, Any]:
    """A cell with its configuration and traffic mix resolved by name."""
    def read(kind, key):
        path = os.path.join(root, kind, f"{key}.json")
        if not os.path.exists(path):
            raise FileNotFoundError(f"no {kind} file for {key!r}: {path}")
        with open(path) as f:
            return json.load(f)

    cell = read("workloads", name)
    cell["config_file"] = read("configs", cell["config"])
    cell["traffic_file"] = read("traffic", cell["traffic"])
    return cell


def load_layer_metrics(cell_name: str, root: str = HERE) -> List[Dict[str, Any]]:
    """The per-layer metric files that are read in this cell (a file with
    no ``workloads`` key is read in every cell)."""
    out = []
    for path in sorted(glob.glob(os.path.join(root, "layer_metrics", "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if "workloads" not in spec or cell_name in spec["workloads"]:
            out.append(spec)
    return out


def arch_of(config_file: Dict[str, Any]) -> Dict[str, Any]:
    """``model.model_arch`` for the program: the published keys the
    program's config class knows, plus the run's dtypes."""
    run = config_file["run"]
    arch = {k: config_file[k] for k in run["arch_keys"]}
    arch["kv_cache_dtype"] = run["kv_cache_dtype"]
    if config_file["model_type"] == "gpt_neox":
        # the program builds the MLP 4 x hidden wide and has no key for it
        if config_file["intermediate_size"] != 4 * config_file["hidden_size"]:
            raise ValueError(
                "the program's NeoXConfig assumes intermediate_size = 4 x "
                "hidden_size; this configuration publishes another"
            )
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


def check_line(name: str, value: float, tolerance: str, ok: bool) -> bool:
    """One sub-check of ``correct`` on a line of its own, so that a
    ``false`` in a log says which comparison failed."""
    print(f"check {name}: value={value!r} tolerance={tolerance} ok={bool(ok)}", flush=True)
    return bool(ok)


def median(values: List[float]) -> Optional[float]:
    import statistics

    return statistics.median(values) if values else None


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    out: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    return json.dumps(out)
