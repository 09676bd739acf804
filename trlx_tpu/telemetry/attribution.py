"""Goodput & utilization attribution: statics ÷ span times.

The measurement layer already holds both halves of "where did the time
go": engine 7 (``analysis/resource_audit.py``) counts each traced
program's exact matmul FLOPs and boundary bytes *statically*, and the
span tracer measures what every phase region actually took. Nothing
joined them inside the program: whole-phase utilization came from outside
(today ``benchmark/``'s ``phase_mfu``), with no per-program breakdown and
no accounting of the async schedule's bubbles. This module is the join:

- :func:`attribute` — for each (traced program, span) pair in a work
  map, ``measured utilization = static work × fires ÷ (span wall ×
  device peak)``: measured MFU against the chip's published bf16 peak
  and HBM-BW utilization against its published bandwidth, where the
  byte side is the program's boundary traffic floor (sharded input
  bytes + output bytes — the program must at least read its inputs and
  write its outputs; fused internals are uncounted, so the utilization
  is a lower bound).
- :func:`bubble_breakdown` — the async schedule's idle attribution
  (learner drain, version-lag guard hold, admission bookkeeping,
  learner idle) as per-phase milliseconds and fractions of the phase
  wall — the LlamaRL-style table that justifies (or indicts) an async
  design choice.
- :func:`phase_goodput` — trained samples per second of *total* phase
  wall (collect + train + eval + checkpoint spans), the end-to-end
  number utilization percentages tend to flatter.

Device peaks are the published per-chip specs — the table ``chip_smoke.py``
and the attribution rows read (``benchmark/peaks.json`` is the
benchmark's own copy; ROADMAP.md names the pair as a debt). A ``device_kind``
that is not in it (the CPU included) is an error, never a default: a
utilization priced off an assumed peak is not a device number.

Everything here is host-side arithmetic over dicts the caller already
holds; nothing traces, compiles, or touches devices except
:func:`require_tpu` (the device gate: it asks jax what it found) and
:func:`trainer_program_resources`, which re-traces (tracing only, no
compilation — the engine-7 pattern) a LIVE trainer's programs at the real workload shape.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

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


# ------------------------------- work maps -------------------------------- #


@dataclass(frozen=True)
class WorkItem:
    """One (traced program, phase window) join. ``span`` is the WINDOW
    whose wall the program's work is charged against — a phase-level
    span containing the sync points, because per-call dispatch spans
    measure host dispatch, not device occupancy (an async jit call
    returns in microseconds while the device grinds). The fire count
    comes from ``count_span`` (a per-call span's count) or
    ``count_key`` (a stats counter, for programs with no per-call span
    like the engine's per-token decode_step); with neither, the window
    span's own count. Rows sharing a window therefore decompose it:
    each row is "what utilization did THIS program's static work
    achieve over the window", and their sum is the window's total."""

    program: str
    span: str
    count_span: str = ""
    count_key: str = ""


#: the fixed-sampler PPO phase: the compiled sampler fires once per
#: chunk (collect/decode spans count them) over the collect window;
#: streamed epoch-1 steps + the residual epochs 2..E (one
#: ``train/residual`` span of single-step dispatches, priced as the
#: fused scan over as many steps) charge the train window — under phase overlap their device work
#: partially hides inside collect, and the train window holds the
#: drain that waits for it (a conservative split, documented).
PPO_FIXED_WORK: Tuple[WorkItem, ...] = (
    WorkItem("ppo.rollout", "phase/collect", count_span="collect/decode"),
    WorkItem(
        "ppo.train_step", "phase/train", count_span="train/epoch1_dispatch"
    ),
    WorkItem("ppo.train_phase", "phase/train", count_span="train/residual"),
)

#: the continuous engine's three jitted programs decompose the collect
#: window; decode_step has no per-call span (hundreds of fires per
#: phase inside the drive loop), so its count is the
#: ``engine/decode_steps`` stat.
PPO_ENGINE_WORK: Tuple[WorkItem, ...] = (
    WorkItem(
        "ppo.engine_prefill", "phase/collect", count_span="collect/prefill"
    ),
    WorkItem(
        "ppo.engine_decode_step", "phase/collect",
        count_key="engine/decode_steps",
    ),
    WorkItem(
        "ppo.engine_refill", "phase/collect",
        count_span="collect/slot_recycle",
    ),
    WorkItem(
        "ppo.train_step", "phase/train", count_span="train/epoch1_dispatch"
    ),
    WorkItem("ppo.train_phase", "phase/train", count_span="train/residual"),
)


def default_work(engine: str = "fixed", kind: str = "ppo") -> Tuple[WorkItem, ...]:
    items = PPO_ENGINE_WORK if engine == "continuous" else PPO_FIXED_WORK
    if kind == "ppo":
        return items
    return tuple(
        WorkItem(
            f"{kind}.{w.program.split('.', 1)[1]}",
            w.span,
            w.count_span,
            w.count_key,
        )
        for w in items
    )


# ------------------------------ attribution ------------------------------- #


@dataclass
class AttributionRow:
    """Measured utilization of one traced program over one span window."""

    program: str
    span: str
    calls: float                    # program executions in the window
    wall_ms: float                  # span total wall covering them
    gflops_per_call: float          # engine-7 static FLOPs / 1e9
    mbytes_per_call: float          # static boundary bytes / 1e6
    achieved_tflops_per_dev: float
    achieved_gbps_per_dev: float
    mfu: float
    hbm_util: float

    def to_dict(self) -> Dict[str, Any]:
        return {
            "program": self.program,
            "span": self.span,
            "calls": self.calls,
            "wall_ms": round(self.wall_ms, 1),
            "gflops_per_call": round(self.gflops_per_call, 3),
            "mbytes_per_call": round(self.mbytes_per_call, 3),
            "achieved_tflops_per_dev": round(self.achieved_tflops_per_dev, 4),
            "achieved_gbps_per_dev": round(self.achieved_gbps_per_dev, 2),
            "mfu": round(self.mfu, 4),
            "hbm_util": round(self.hbm_util, 4),
        }


def _static_bytes(res: Dict[str, Any]) -> float:
    """The program's boundary-traffic floor: sharded input bytes +
    output bytes. ``peak_hbm_bytes`` is residency, not traffic — a
    program can re-read a resident buffer many times — so the floor is
    the only static number honestly chargeable per execution."""
    return float(res.get("input_bytes", 0)) + float(res.get("output_bytes", 0))


def attribute(
    resources: Dict[str, Dict[str, Any]],
    span_stats: Dict[str, Dict[str, float]],
    device_kind: str,
    n_devices: int = 1,
    work: Optional[Sequence[WorkItem]] = None,
    counts: Optional[Dict[str, float]] = None,
) -> List[AttributionRow]:
    """Join static program costs with measured span walls.

    :param resources: engine-7 numbers per subject
        (``ProgramResources.to_dict()`` shape — ``flops``,
        ``input_bytes``, ``output_bytes``).
    :param span_stats: :meth:`Tracer.stats` over the measured window.
    :param counts: flat stats/metrics dict for ``count_key`` joins
        (``engine/decode_steps`` etc.).
    :returns: one row per work item whose program AND span were both
        observed; items missing either side are skipped (a fixed-path
        run simply has no engine rows).

    FLOP statics count whole-program work; under data parallelism each
    device executes ``1/n_devices`` of it, so per-device FLOP rates
    divide by ``n_devices``. The byte side does NOT: engine 7 already
    applied per-device sharding divisors to input bytes (replicated
    inputs count in full on every device, which is correct per-device
    traffic), so dividing again would understate HBM utilization by up
    to ``n_devices``×.
    """
    peak_tf, peak_bw = device_peaks(device_kind)
    rows: List[AttributionRow] = []
    for item in work or PPO_FIXED_WORK:
        res = resources.get(item.program)
        span = span_stats.get(item.span)
        if not res or not span:
            continue
        if item.count_key:
            calls = float((counts or {}).get(item.count_key, 0.0))
        elif item.count_span:
            calls = float(
                (span_stats.get(item.count_span) or {}).get("count", 0.0)
            )
        else:
            calls = float(span.get("count", 0.0))
        wall_ms = float(span.get("total_ms", 0.0))
        if calls <= 0 or wall_ms <= 0:
            continue
        flops = float(res.get("flops", 0))
        nbytes = _static_bytes(res)
        wall_s = wall_ms / 1000.0
        achieved_tf = flops * calls / wall_s / n_devices / 1e12
        achieved_bw = nbytes * calls / wall_s / 1e9  # bytes are per-device
        rows.append(
            AttributionRow(
                program=item.program,
                span=item.span,
                calls=calls,
                wall_ms=wall_ms,
                gflops_per_call=flops / 1e9,
                mbytes_per_call=nbytes / 1e6,
                achieved_tflops_per_dev=achieved_tf,
                achieved_gbps_per_dev=achieved_bw,
                mfu=achieved_tf / peak_tf,
                hbm_util=achieved_bw / peak_bw,
            )
        )
    return rows


# ----------------------------- bubbles/goodput ---------------------------- #

#: phase-wall spans: everything the loop spends a phase on
PHASE_SPANS = ("phase/collect", "phase/train", "phase/eval", "phase/checkpoint")


def phase_wall_ms(
    span_stats: Dict[str, Dict[str, float]], phases: int = 1
) -> float:
    """Per-phase wall: the phase-level spans' total over the measured
    window divided by the phase count."""
    total = sum(
        float(span_stats[name]["total_ms"])
        for name in PHASE_SPANS
        if name in span_stats
    )
    return total / max(1, phases)


def bubble_breakdown(
    span_stats: Dict[str, Dict[str, float]],
    stats: Optional[Dict[str, float]] = None,
    phases: int = 1,
) -> Dict[str, float]:
    """The async schedule's idle attribution, per phase (ms + fraction
    of the phase wall):

    - ``bubble/drain_ms`` — learner waiting for the last rollout chunks
      after the epoch-1 dispatch window closed (``train/drain`` span);
    - ``bubble/guard_hold_ms`` — row-ready minibatches held behind the
      bounded-staleness version-lag guard (``async/guard_hold_ms``);
    - ``bubble/learner_idle_ms`` — drain + guard hold, the learner's
      total idle (``async/learner_idle_ms`` when the async path
      reported it, else the drain alone);
    - ``bubble/admit_ms`` — the engine's host-side admission
      bookkeeping (``collect/admit`` span), the slot-refill stall.

    ``stats`` is a flat per-phase stats row (the trainer's
    ``_last_overlap_stats`` / a metrics-gauge dict). Absent sources
    yield no key — a fixed-sampler sync run reports only its drain."""
    out: Dict[str, float] = {}
    wall = phase_wall_ms(span_stats, phases)
    out["phase_wall_ms"] = wall

    def put(key: str, ms: float) -> None:
        out[f"bubble/{key}_ms"] = ms
        if wall > 0:
            out[f"bubble/{key}_frac"] = ms / wall

    if "train/drain" in span_stats:
        put("drain", float(span_stats["train/drain"]["total_ms"]) / max(1, phases))
    if "collect/admit" in span_stats:
        put("admit", float(span_stats["collect/admit"]["total_ms"]) / max(1, phases))
    stats = stats or {}
    if "async/guard_hold_ms" in stats:
        put("guard_hold", float(stats["async/guard_hold_ms"]))
    if "async/learner_idle_ms" in stats:
        put("learner_idle", float(stats["async/learner_idle_ms"]))
    elif "bubble/drain_ms" in out:
        put("learner_idle", out["bubble/drain_ms"])
    return out


def phase_goodput(
    span_stats: Dict[str, Dict[str, float]],
    samples_per_phase: int,
    phases: int = 1,
) -> Dict[str, float]:
    """Trained samples per second of total phase wall — the end-to-end
    goodput the per-program utilizations decompose. Charged against
    EVERY phase-level span (eval and checkpoint time are real wall the
    run spent not training)."""
    wall = phase_wall_ms(span_stats, phases)
    out = {"phase_wall_ms": wall}
    if wall > 0:
        out["goodput_samples_per_sec"] = samples_per_phase / (wall / 1000.0)
    return out


# ------------------------------ live tracing ------------------------------ #


def trainer_program_resources(
    trainer,
    kind: str = "ppo",
    chunk_size: Optional[int] = None,
    residual_len: Optional[int] = None,
) -> Dict[str, Dict[str, Any]]:
    """Engine-7 statics for a LIVE trainer's phase programs at the real
    workload shape (tracing only — no compilation): the train step, the
    compiled sampler at the orchestrator's chunk shape, and (when the
    trainer has one) the residual fused train_phase at
    ``residual_len`` stacked minibatches. The continuous engine's
    programs, when built, are traced through the analysis harness.

    Returns ``{subject: ProgramResources.to_dict()}`` — the
    :func:`attribute` input. Each program is individually guarded: a
    shape drift in one trace drops that row, never the table."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.analysis import harness
    from trlx_tpu.analysis.resource_audit import analyze_closed_jaxpr
    from trlx_tpu.parallel.mesh import batch_sharding

    out: Dict[str, Dict[str, Any]] = {}
    axis_sizes = {k: int(v) for k, v in trainer.mesh.shape.items()}
    state_sds = harness._sds(trainer.state)

    try:
        mb = (
            harness._ilql_minibatch_sds(trainer)
            if kind == "ilql"
            else harness._ppo_minibatch_sds(trainer)
        )
        closed = jax.make_jaxpr(trainer._train_step_jit)(state_sds, mb)
        divisors = harness.flat_sharding_divisors(
            (state_sds, mb),
            (trainer.state_shardings, batch_sharding(trainer.mesh)),
        )
        out[f"{kind}.train_step"] = analyze_closed_jaxpr(
            closed, f"{kind}.train_step", axis_sizes, divisors
        ).to_dict()
    except Exception:
        pass

    try:
        B = int(chunk_size or trainer.config.train.batch_size)
        Q = trainer.query_length
        prompt = jax.ShapeDtypeStruct((B, Q), jnp.int32)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        params_sds = harness._sds(trainer.state.params)
        closed = jax.make_jaxpr(trainer._sample_jit)(
            params_sds, prompt, prompt, key
        )
        divisors = harness.flat_sharding_divisors(
            (params_sds, prompt, prompt, key),
            (
                trainer.state_shardings.params,
                batch_sharding(trainer.mesh),
                batch_sharding(trainer.mesh),
                None,
            ),
        )
        out[f"{kind}.rollout"] = analyze_closed_jaxpr(
            closed, f"{kind}.rollout", axis_sizes, divisors
        ).to_dict()
    except Exception:
        pass

    if residual_len and residual_len > 0:
        try:
            from trlx_tpu.parallel.mesh import stacked_batch_sharding

            stacked = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    (int(residual_len),) + x.shape, x.dtype
                ),
                mb,
            )
            closed = jax.make_jaxpr(trainer._train_phase_jit)(
                state_sds, stacked
            )
            divisors = harness.flat_sharding_divisors(
                (state_sds, stacked),
                (
                    trainer.state_shardings,
                    stacked_batch_sharding(trainer.mesh),
                ),
            )
            out[f"{kind}.train_phase"] = analyze_closed_jaxpr(
                closed, f"{kind}.train_phase", axis_sizes, divisors
            ).to_dict()
        except Exception:
            pass

    if getattr(trainer, "_rollout_engine_obj", None) is not None:
        try:
            mesh_shape = {k: int(v) for k, v in trainer.mesh.shape.items()}
            for traced in harness._trace_engine_programs(
                trainer, kind, mesh_shape
            ):
                from trlx_tpu.analysis.resource_audit import (
                    analyze_traced_program,
                )

                out[traced.subject] = analyze_traced_program(traced).to_dict()
        except Exception:
            pass
    return out


# -------------------------------- rendering ------------------------------- #


def format_attribution(
    rows: Sequence[AttributionRow],
    bubbles: Optional[Dict[str, float]] = None,
    goodput: Optional[Dict[str, float]] = None,
) -> str:
    """The per-run "where did the time go" table (bench prints this to
    stderr; the JSON payload carries the same rows machine-readably)."""
    lines = ["utilization attribution (engine-7 statics ÷ span wall):"]
    header = (
        f"  {'program':24} {'window':22} {'calls':>7} {'wall ms':>10} "
        f"{'TFLOP/s':>9} {'MFU':>7} {'GB/s':>8} {'HBM%':>6}"
    )
    lines.append(header)
    for r in rows:
        # significant digits, not fixed decimals: tiny shapes produce
        # MFUs like 4e-5 that fixed-point would render as 0
        lines.append(
            f"  {r.program:24} {r.span:22} {r.calls:>7.0f} "
            f"{r.wall_ms:>10.1f} {r.achieved_tflops_per_dev:>9.3g} "
            f"{r.mfu:>7.3g} {r.achieved_gbps_per_dev:>8.3g} "
            f"{100 * r.hbm_util:>6.3g}"
        )
    if not rows:
        lines.append("  (no program/span pairs observed)")
    if bubbles:
        lines.append("async bubble breakdown (per phase):")
        wall = bubbles.get("phase_wall_ms", 0.0)
        lines.append(f"  phase wall            {wall:>10.1f} ms")
        for key in sorted(bubbles):
            if not key.startswith("bubble/") or not key.endswith("_ms"):
                continue
            name = key[len("bubble/"):-len("_ms")]
            frac = bubbles.get(f"bubble/{name}_frac")
            pct = f" ({100 * frac:.1f}% of phase)" if frac is not None else ""
            lines.append(f"  {name:20} {bubbles[key]:>12.1f} ms{pct}")
    if goodput and "goodput_samples_per_sec" in goodput:
        lines.append(
            f"goodput: {goodput['goodput_samples_per_sec']:.2f} trained "
            f"samples/s over {goodput['phase_wall_ms']:.1f} ms phase wall"
        )
    return "\n".join(lines)
