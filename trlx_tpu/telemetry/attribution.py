"""The chip's published peaks, and the device gate of ``chip_smoke.py``.

Device peaks are the published per-chip specs, the table ``chip_smoke.py``
reads (``benchmark/peaks.json`` is the benchmark's own copy; ROADMAP.md
names the pair as a debt). A ``device_kind`` that is not in it (the CPU
included) is an error, never a default: a utilization priced off an
assumed peak is not a device number.

The utilization table that lived here (MFU and HBM use by traced
program, priced off engine-7 statics and span walls, with the async
schedule's bubbles and a phase goodput) went with its last caller,
``bench.py``: utilization is the benchmark's to report, from the device
trace (``benchmark/readers.py``: ``phase_mfu``, ``module_roofline``,
``op_roofline``).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

# Published bf16 peak per chip by device_kind (dense, no sparsity).
BF16_PEAK_TFLOPS = {
    "TPU v3": 123.0,
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,  # v5e
    "TPU v5": 459.0,  # v5p
    "TPU v6 lite": 918.0,  # v6e (Trillium)
}

# Published HBM bandwidth per chip (GB/s).
HBM_PEAK_GBPS = {
    "TPU v3": 900.0,
    "TPU v4": 1228.0,
    "TPU v5 lite": 819.0,  # v5e
    "TPU v5": 2765.0,  # v5p
    "TPU v6 lite": 1640.0,  # v6e
}


def device_peaks(device_kind: str) -> Tuple[float, float]:
    """(peak bf16 TFLOP/s, peak HBM GB/s) for a ``device_kind`` string as
    jax reports it. Raises for a device missing from the table."""
    if device_kind not in BF16_PEAK_TFLOPS:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(BF16_PEAK_TFLOPS)}); utilization is only defined "
            "against a published spec — add the device to "
            "trlx_tpu/telemetry/attribution.py with its source"
        )
    return BF16_PEAK_TFLOPS[device_kind], HBM_PEAK_GBPS[device_kind]


def require_tpu() -> Dict[str, Any]:
    """The device gate of the program's own measurement path
    (``chip_smoke.py``): jax must find a TPU whose ``device_kind`` has
    published peaks, or this raises — a measurement path never falls back
    to the CPU. Returns the device as jax reports it."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"no TPU: jax found platform {dev.platform!r} "
            f"({dev.device_kind}); this path reports device numbers and "
            "runs on the chip only"
        )
    device_peaks(dev.device_kind)
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
