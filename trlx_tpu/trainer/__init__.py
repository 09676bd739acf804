"""Trainer layer (reference layer 5, ``trlx/model/``).

``BaseRLTrainer`` re-designs ``BaseRLModel`` + ``AccelerateRLModel``
(``trlx/model/__init__.py:17-144``, ``accelerate_base_model.py:29-325``):
same responsibilities — own the model/optimizer/schedule, ``learn()`` /
``evaluate()`` / ``save()`` / ``load()``, log/eval/save cadence — but state
is an explicit pytree updated by jitted steps on a device mesh, not a
mutable module wrapped by Accelerate.
"""

from __future__ import annotations

import sys
from abc import ABC, abstractmethod
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax
import numpy as np

from trlx_tpu.data.configs import TRLConfig

_TRAINERS: Dict[str, type] = {}


def register_trainer(name=None):
    """Decorator registering a trainer class (reference
    `trlx/model/__init__.py:14-36` ``register_model``)."""

    def register_class(cls, key: str):
        _TRAINERS[key] = cls
        setattr(sys.modules[__name__], key, cls)
        return cls

    if isinstance(name, type):
        return register_class(name, name.__name__.lower())

    def wrap(cls):
        return register_class(cls, (name or cls.__name__).lower())

    return wrap


def get_trainer(name: str) -> type:
    key = name.lower()
    if key not in _TRAINERS:
        import trlx_tpu.trainer.grpo_trainer  # noqa: F401
        import trlx_tpu.trainer.ilql_trainer  # noqa: F401
        import trlx_tpu.trainer.ppo_trainer  # noqa: F401
        import trlx_tpu.trainer.seq2seq_ppo_trainer  # noqa: F401
    if key in _TRAINERS:
        return _TRAINERS[key]
    raise ValueError(f"Unknown trainer: {name!r}. Registered: {sorted(_TRAINERS)}")


class BaseRLTrainer(ABC):
    def __init__(
        self,
        config: TRLConfig,
        reward_fn: Optional[Callable] = None,
        metric_fn: Optional[Callable] = None,
        tokenizer=None,
        logit_mask=None,
    ):
        self.config = config
        self.reward_fn = reward_fn
        self.metric_fn = metric_fn
        self.tokenizer = tokenizer
        self.logit_mask = logit_mask
        self.orch = None  # back-reference installed by the orchestrator
        self.eval_pipeline = None
        from trlx_tpu import telemetry

        # span-ring capacity (train.telemetry.ring_size,
        # docs/observability.md): sized before any phase emits spans
        telemetry.configure_from_dict(
            getattr(config.train, "telemetry", None)
        )
        self._setup_health()

    def _setup_health(self) -> None:
        """Run-health monitoring (telemetry/health.py): parse
        ``train.health``, and — when enabled, on the main process only
        (a per-host abort decision would desynchronize the collective
        schedule, the host-branch hazard) — build the detector monitor
        and the crash-forensics flight recorder. ``_health_enabled``
        additionally gates the fused device-side health scalars in the
        jitted steps, so it is set before any program is built."""
        from trlx_tpu.telemetry.health import HealthConfig

        health_dict = dict(self.config.train.health or {})
        async_dict = dict(getattr(self.config.train, "async_rl", None) or {})
        if async_dict.get("enabled") and health_dict.get("enabled"):
            # async actor–learner circuit-breaker: the staleness-breach
            # detector's threshold IS the configured staleness window
            # unless the user tuned it explicitly — a guard bug (not
            # ordinary operation) is the only way to cross it
            detectors = dict(health_dict.get("detectors") or {})
            if "staleness-breach" not in detectors:
                detectors["staleness-breach"] = {
                    "threshold": float(
                        async_dict.get("staleness_window", 1)
                    )
                }
                health_dict["detectors"] = detectors
        self.health_config = HealthConfig.from_dict(health_dict)
        self._health_enabled = bool(self.health_config.enabled)
        self._health_ev = True  # GRPO opts out (placeholder returns slot)
        self.health_monitor = None
        self.flight_recorder = None
        self._phase_timing = None  # (TimingSeries, HostMark) once a phase is marked
        self._phase_log = None  # run_dir live --watch feed (run_ledger.py)
        if not self._health_enabled:
            return
        from trlx_tpu.parallel.distributed import is_main_process

        if not is_main_process():
            return
        from trlx_tpu.telemetry.flight_recorder import FlightRecorder
        from trlx_tpu.telemetry.health import (
            HealthMonitor,
            config_fingerprint,
        )

        config_dict = self.config.to_dict()
        fingerprint = config_fingerprint(config_dict)
        self.health_monitor = HealthMonitor(self.health_config, fingerprint)
        self.flight_recorder = FlightRecorder(
            capacity=self.health_config.flight_capacity,
            directory=self.health_config.dump_dir,
            fingerprint=fingerprint,
            config=config_dict,
        )
        # live phase-row mirror for `--watch` (run_ledger.py): rides the
        # flight recorder's phase records, so it shares its gating
        # (health.enabled + rank 0)
        run_dir = getattr(self.config.train, "run_dir", None)
        if run_dir:
            from trlx_tpu.telemetry.run_ledger import PhaseLogWriter

            self._phase_log = PhaseLogWriter(run_dir)

    def observe_health(
        self,
        row: Dict[str, Any],
        step: Optional[int] = None,
        phase: Optional[int] = None,
    ) -> None:
        """Feed one already-fetched stats row to the detector engine.

        Called wherever rows cross to host anyway (the streamed phase
        epilogue, the fused pass, log steps on the stepwise path, ILQL
        chunks, the orchestrator's collect stats) — the monitor never
        forces a device transfer; ``jax.Array`` leaves are skipped and
        observed later from the row they are fetched into. Each trip
        lands in the span stream and the Logger; ``error`` trips apply
        the ``health.on_error`` policy (warn | dump | abort)."""
        monitor = self.health_monitor
        if monitor is None:
            return
        events = monitor.observe(row, step=step, phase=phase)
        if events:
            self._sink_health_events(events, row, phase)

    def _sink_health_events(
        self, events, row: Dict[str, Any], phase: Optional[int]
    ) -> None:
        """Where a detector's trips go: the span stream, the Logger (or
        stderr without one) and, for ``error`` trips, the
        ``health.on_error`` policy."""
        from trlx_tpu.telemetry.health import announce

        monitor = self.health_monitor
        logger = getattr(self, "logger", None)
        for ev in events:
            announce(ev, logger)
        errors = [ev for ev in events if ev.severity == "error"]
        policy = self.health_config.on_error
        if not errors or policy == "warn":
            return
        recorder = self.flight_recorder
        if recorder is not None:
            # land the OFFENDING row + its events in the ring before
            # dumping, so the forensics file's final phase record and
            # its last-good diff show the anomaly itself — the phase
            # epilogue's own record has not run yet at this point.
            # Guarded: under the record-and-continue `dump` policy a
            # failing forensics write (full disk, unserializable config)
            # must never kill an otherwise-continuable run
            try:
                recorder.record_phase(
                    phase,
                    step=errors[0].step,
                    stats_row=row,
                    events=events,
                    detector_state=monitor.state_summary(),
                )
                for ev in errors:
                    path = recorder.dump(
                        "detector:" + ev.detector, once=True
                    )
                    if path:
                        print(f"health: flight record dumped to {path}",
                              file=sys.stderr)
            except Exception as dump_err:
                print(
                    f"health: flight dump FAILED "
                    f"({type(dump_err).__name__}: {dump_err})",
                    file=sys.stderr,
                )
        if policy == "abort":
            from trlx_tpu.telemetry.health import HealthAbort

            first = errors[0]
            raise HealthAbort(
                f"health.on_error=abort: detector {first.detector!r} "
                f"tripped at step {first.step} ({first.message}); "
                f"flight record(s): {self.flight_recorder.dumped if self.flight_recorder else 'disabled'}"
            )

    # the parts of a phase by span (docs/observability.md, "Host pauses"):
    # what ``host-stall`` names when a phase's wall grows; the second row
    # is the continuous engine's
    PHASE_SPANS = ("phase/begin", "phase/collect", "phase/train")
    PHASE_PARTS = (
        "phase/begin", "collect/wait", "collect/detokenize", "collect/score",
        "collect/land", "train/drain", "train/residual",
    )
    ENGINE_PHASE_PARTS = (
        "collect/admit", "collect/prefill", "collect/slot_recycle",
        "engine/fetch",
    )

    def mark_phase_timing(self) -> None:
        """A phase begins: note where the host stands (the clock, this
        thread's CPU time, the collector's and the compiler's totals, the
        tracer's walls by span) for :meth:`observe_phase_timing`. Nothing
        is built with ``train.health`` off or ``host-stall`` disabled."""
        monitor = self.health_monitor
        if monitor is None:
            return
        timing = self._phase_timing
        if timing is None:
            from trlx_tpu import telemetry

            parts = self.PHASE_PARTS
            if getattr(self, "rollout_engine", "fixed") == "continuous":
                parts = parts + self.ENGINE_PHASE_PARTS
            series = monitor.timing_series("time/phase_ms", parts)
            if series is None:
                return
            timing = self._phase_timing = (
                series, telemetry.HostMark(parts, wall=self.PHASE_SPANS)
            )
        timing[1].take()

    def observe_phase_timing(self, phase: Optional[int]) -> Dict[str, float]:
        """A phase has ended: its timing row (``time/phase_ms`` = the
        walls of ``phase/begin``, ``phase/collect`` and ``phase/train``,
        its parts by span, ``time/gc_ms``, ``time/compile_ms``,
        ``time/cpu_share``) to the ``host-stall`` detector, through the
        sinks of every detector. Returns the row ({} where none was
        built: health off, no mark taken, or a tracer that records
        nothing)."""
        timing = self._phase_timing
        if timing is None or not timing[1].t:
            return {}
        series, mark = timing
        wall_ms = mark.fill(series)
        mark.t = 0.0  # one row a mark
        if wall_ms <= 0.0:
            return {}
        row = series.row(wall_ms)
        event = self.health_monitor.observe_timing(series, wall_ms, phase=phase)
        if event is not None:
            self._sink_health_events([event], row, phase)
        return row

    def observe_health_rows(
        self,
        rows: Dict[str, Any],
        step0: Optional[int] = None,
        phase: Optional[int] = None,
        phase_row: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, Any]:
        """Feed a fetched stacked-stats tree (each value an [n_updates]
        host array) to the detectors row by row, then ``phase_row`` —
        series that are constant across the phase's rows (the rollout
        KL) — exactly ONCE. Repeating a phase-constant value per row
        would collapse its EWMA variance and burn warmup/cooldown in
        row units, hair-triggering the z-score rules on ordinary
        phase-to-phase movement. Returns the last row (+ phase_row)
        for the flight record."""
        last: Dict[str, Any] = {}
        if self.health_monitor is None or not rows:
            return last
        n_rows = len(rows[next(iter(rows))])
        for r in range(n_rows):
            last = {key: float(v[r]) for key, v in rows.items()}
            self.observe_health(
                last,
                step=None if step0 is None else step0 + r + 1,
                phase=phase,
            )
        if phase_row:
            self.observe_health(phase_row, phase=phase)
            last = {**last, **phase_row}
        return last

    def emit_health_event(
        self,
        detector: str,
        severity: str,
        message: str,
        series: str = "resilience",
        value: float = 1.0,
        step: Optional[int] = None,
        phase: Optional[int] = None,
    ) -> None:
        """Record one host-originated health event (engine fallback and
        other graceful degradations, docs/resilience.md) through the
        same sinks a detector trip uses: the monitor's event log, a
        zero-length marker span, and the Logger's ``health_event`` JSON
        line. Unlike :meth:`observe_health` this never applies the
        ``health.on_error`` policy — degradations are the alternative
        to aborting, not a trigger for it."""
        from trlx_tpu.telemetry.health import HealthEvent, announce

        monitor = self.health_monitor
        ev = HealthEvent(
            detector=detector,
            severity=severity,
            series=series,
            value=float(value),
            step=int(step) if step is not None else -1,
            phase=phase,
            message=message,
            fingerprint=monitor.fingerprint if monitor is not None else "",
        )
        if monitor is not None:
            monitor.events.append(ev)
            monitor.event_counts[detector] = (
                monitor.event_counts.get(detector, 0) + 1
            )
        announce(ev, getattr(self, "logger", None))

    def maybe_drain(
        self, phase: Optional[int] = None, step: Optional[int] = None
    ) -> None:
        """Phase-boundary resilience hook (docs/resilience.md): the
        ``slow_step`` / ``preempt`` fault-injection sites, then — when a
        guarded SIGTERM/SIGINT arrived since the last boundary — the
        graceful drain: write an emergency atomic checkpoint (the same
        save path as the cadence checkpoint, retried on transient I/O),
        dump the flight recorder, and raise
        :class:`~trlx_tpu.resilience.preemption.PreemptionDrain` for
        the supervisor / a distinct exit code. Costs one flag read per
        phase when no guard is installed."""
        from trlx_tpu.resilience import chaos, preemption

        chaos.check("slow_step", phase=phase, step=step)
        chaos.check("preempt", phase=phase, step=step)
        if not preemption.drain_requested():
            return
        from trlx_tpu.utils.checkpoint import wait_for_checkpoints

        directory = self.config.train.checkpoint_dir
        print(
            f"resilience: draining at phase boundary (step {step}) — "
            f"writing emergency checkpoint to {directory!r}",
            file=sys.stderr,
        )
        self.save()
        wait_for_checkpoints()  # the drain's whole point is durability
        recorder = self.flight_recorder
        if recorder is not None:
            try:
                path = recorder.dump("preemption", once=True)
                if path:
                    print(
                        f"health: flight record dumped to {path}",
                        file=sys.stderr,
                    )
            except Exception:
                pass  # forensics must never block the drain
        raise preemption.PreemptionDrain(
            f"preempted ({preemption.received_signal()}): drained at "
            f"step {step} with an emergency checkpoint in {directory!r}",
            step=step,
            checkpoint_dir=directory,
        )

    def record_flight_phase(
        self,
        phase: Optional[int],
        step: Optional[int] = None,
        stats_row: Optional[Dict[str, Any]] = None,
        kl_seq: Optional[List[float]] = None,
    ) -> None:
        """Append one phase record to the flight ring (no-op when health
        is off) and honor the on-demand ``train.flight_dump_phase``."""
        recorder = self.flight_recorder
        if recorder is None:
            return
        monitor = self.health_monitor
        rec = recorder.record_phase(
            phase,
            step=step,
            stats_row=stats_row,
            kl_seq=kl_seq,
            events=monitor.recent_events(phase) if monitor else (),
            detector_state=monitor.state_summary() if monitor else None,
        )
        if self._phase_log is not None:
            # the live --watch feed: the same record, minus the
            # detector EWMA state (bulky and meaningless line-by-line)
            self._phase_log.append(
                {k: v for k, v in rec.items() if k != "detectors"}
            )
        want = self.config.train.flight_dump_phase
        if want is not None and phase == want:
            path = recorder.dump(f"flight_dump_phase:{phase}", once=True)
            if path:
                print(f"health: flight record dumped to {path}",
                      file=sys.stderr)

    def flight_dump_on_exception(self, error: BaseException) -> None:
        """learn()-epilogue hook: write the crash forensics file for an
        uncaught exception (at most once per recorder; a HealthAbort
        whose detector already dumped is not dumped again)."""
        recorder = self.flight_recorder
        if recorder is None:
            return
        try:
            monitor = self.health_monitor
            if monitor is not None and monitor.events:
                # fold events the crash preempted out of a phase record
                # (e.g. check_anomalies raising mid-epilogue) into the
                # NEWEST record — never a fresh stats-less one, which
                # would displace the real final phase from the
                # --inspect last-good diff; the recorder dedupes, so
                # repeats are safe
                recorder.note_events(
                    monitor.events,
                    detector_state=monitor.state_summary(),
                )
            path = recorder.dump_on_exception(error)
        except Exception:
            return  # forensics must never mask the real failure
        if path:
            print(f"health: flight record dumped to {path}", file=sys.stderr)

    def append_run_ledger(
        self,
        status: str = "ok",
        error: Optional[BaseException] = None,
        payload: Optional[Dict[str, Any]] = None,
    ) -> None:
        """learn()-epilogue hook (docs/observability.md "Run ledger"):
        append this run's :class:`RunManifest` — config fingerprint,
        platform, git sha, span stats, metrics snapshot, health-event
        counts, final stats — to the ledger JSONL, and write
        ``<run_dir>/manifest.json`` when ``train.run_dir`` is set.
        Active only when ``train.run_dir`` or ``$TRLX_RUN_LEDGER`` is
        configured; best-effort (a full disk must never mask the run's
        real outcome)."""
        import os

        run_dir = getattr(self.config.train, "run_dir", None)
        ledger_env = os.environ.get("TRLX_RUN_LEDGER")
        if not run_dir and not ledger_env:
            return
        try:
            from trlx_tpu.parallel.distributed import is_main_process

            if not is_main_process():
                return
            import json

            from trlx_tpu.telemetry.run_ledger import (
                append_manifest,
                build_manifest,
                numeric_payload,
            )

            body = dict(payload or {})
            body["status"] = status
            if error is not None:
                body["error"] = f"{type(error).__name__}: {error}"
            body.update(
                numeric_payload(getattr(self, "_final_stats", None) or {})
            )
            monitor = self.health_monitor
            manifest = build_manifest(
                kind=f"train/{type(self).__name__}",
                config=self.config.to_dict(),
                payload=body,
                health_events=(
                    dict(monitor.event_counts) if monitor is not None else {}
                ),
            )
            ledger = ledger_env or (
                os.path.join(run_dir, "ledger.jsonl") if run_dir else None
            )
            if ledger:
                append_manifest(manifest, ledger)
            if run_dir:
                os.makedirs(run_dir, exist_ok=True)
                with open(
                    os.path.join(run_dir, "manifest.json"),
                    "w",
                    encoding="utf-8",
                ) as fh:
                    json.dump(manifest, fh, default=float)
        except Exception as e:
            print(
                f"run_ledger: manifest append failed "
                f"({type(e).__name__}: {e})",
                file=sys.stderr,
            )

    def add_eval_pipeline(self, pipeline) -> None:
        """Eval prompts source (reference `accelerate_base_model.py:148-150`)."""
        self.eval_pipeline = pipeline

    def intervals(self, step: int) -> Dict[str, bool]:
        """Log/eval/save cadence (reference `trlx/model/__init__.py:135-144`)."""
        t = self.config.train
        return {
            "do_log": step % t.log_interval == 0,
            "do_eval": step % t.eval_interval == 0,
            "do_save": step > 0 and step % t.checkpoint_interval == 0,
        }

    def _decode_cache_sharding(self):
        """KV-cache sharding for the compiled samplers: with an ``sp`` mesh
        axis > 1 the cache's *capacity* axis (causal) or the cross-KV's
        encoder-length axis (seq2seq) shards over sp, so long-context
        rollouts hold 1/sp of the cache per device (the training-side
        counterpart is ring attention, `ops/ring_attention.py`)."""
        from jax.sharding import NamedSharding, PartitionSpec

        from trlx_tpu.parallel.mesh import BATCH_AXES

        if dict(self.mesh.shape).get("sp", 1) <= 1:
            return None
        return NamedSharding(self.mesh, PartitionSpec(BATCH_AXES, "sp"))

    def setup_ep_axis(self, mesh, family) -> None:
        """Validate + install expert parallelism for this trainer's model.

        An ``ep`` mesh axis is only meaningful for families with experts
        (``ModelFamily.supports_ep``: gpt2_moe, olmoe); for any other family the
        axis would silently replicate all compute, so reject it loudly. For
        MoE families, install the mesh as the module-level ep context
        (`models/gpt2_moe.py::set_ep_mesh`) — call this *after* parameter
        init (so init traces the dense path with no token-divisibility
        constraints) and *before* building jitted programs. One active MoE
        trainer per process: a second MoE trainer re-points the context.
        """
        ep = dict(mesh.shape).get("ep", 1)
        if ep > 1 and not getattr(family, "supports_ep", False):
            raise NotImplementedError(
                f"ep mesh axis requires an MoE family (supports_ep); "
                f"{family.name!r} has no experts to shard — the axis would "
                "silently replicate all compute"
            )
        if getattr(family, "supports_ep", False):
            from trlx_tpu.models import gpt2_moe

            gpt2_moe.set_ep_mesh(mesh)

    def check_anomalies(self, stats: Dict[str, Any], step: int) -> None:
        """Abort with a clear error when fetched loss stats go non-finite
        (``train.detect_anomalies``; beyond the reference — SURVEY §5.3
        records no failure detection). ``stats`` values may be scalars or
        stacked per-update rows; only host-side (already-fetched) values are
        examined, so the check costs no device round-trip."""
        if not self.config.train.detect_anomalies:
            return
        for key, v in stats.items():
            if not key.startswith("losses/"):
                continue
            arr = np.asarray(v, dtype=np.float64)
            finite = np.isfinite(arr)
            if not finite.all():
                if arr.ndim == 0:
                    at, value = step, float(arr)
                else:
                    # stacked per-update rows: `step` is the count *before*
                    # the fused pass, row r is update step + r + 1
                    first_bad = int(np.argmin(finite.ravel()))
                    at = step + first_bad + 1
                    value = float(arr.ravel()[first_bad])
                mesh_spec = ",".join(
                    f"{k}={v}" for k, v in dict(self.mesh.shape).items()
                    if v != 1
                )
                raise RuntimeError(
                    f"non-finite {key} ({value}) detected at step {at} — "
                    "training diverged. Localize the first NaN-minting "
                    "equation with `python -m trlx_tpu.analysis --sanitize "
                    f"<trainer> --mesh {mesh_spec or 'dp=1'}` "
                    "(docs/static_analysis.md), inspect the learning rate / "
                    "reward scale, or resume from the last checkpoint in "
                    f"{self.config.train.checkpoint_dir!r}."
                )

    @abstractmethod
    def learn(self) -> None: ...

    @abstractmethod
    def sample(self, prompt_ids, prompt_mask):
        """Run the trainer's compiled sampler on a prompt batch."""
        ...

    @abstractmethod
    def save(self, directory: Optional[str] = None) -> None: ...

    @abstractmethod
    def load(self, directory: str) -> None: ...

    # --- host-state resume contract ------------------------------------ #

    def host_state_dict(self) -> Dict[str, Any]:
        """Mutable *host* state that must survive kill/resume but lives
        outside the device pytree: every subclass folds its own entries
        on top of this dict and the result rides the checkpoint
        ``metadata`` pickle. The checkpoint/resume auditor (engine 15,
        ``python -m trlx_tpu.analysis --resume-audit``) statically
        requires each phase-loop-mutated attribute to be reachable from
        here, reconstructed from config, or allowlisted ephemeral — add
        new mutable schedule state to this dict, not just to save().

        The base contribution is the health-detector engine: its EWMA
        baselines and cooldowns decide post-resume alerting (see
        HealthMonitor.state_dict)."""
        state: Dict[str, Any] = {}
        if self.health_monitor is not None:
            state["health_monitor"] = self.health_monitor.state_dict()
        return state

    def load_host_state_dict(self, state: Dict[str, Any]) -> None:
        """Inverse of :meth:`host_state_dict`; tolerates missing keys so
        checkpoints written before a given piece of state existed still
        restore (the schema lock in analysis/budgets.json makes any
        *removal* loud instead)."""
        monitor_state = state.get("health_monitor")
        if monitor_state is not None and self.health_monitor is not None:
            self.health_monitor.load_state_dict(monitor_state)

    # --- shared host-side text boundary -------------------------------- #

    def apply_tokenizer_gen_defaults(self, gen_kwargs: Dict[str, Any]) -> None:
        """Default eos/pad from the tokenizer when the config didn't set them
        (reference wires tokenizer ids into generate kwargs,
        `accelerate_ppo_model.py:50-54`). pad falls back to eos when the
        tokenizer has none; a pad id of 0 is preserved (is-not-None check)."""
        if self.tokenizer is None:
            return
        gen_kwargs.setdefault("eos_token_id", self.tokenizer.eos_token_id)
        gen_kwargs.setdefault(
            "pad_token_id",
            self.tokenizer.pad_token_id
            if self.tokenizer.pad_token_id is not None
            else self.tokenizer.eos_token_id,
        )

    def decode_responses(self, tokens, response_mask) -> List[str]:
        """Detokenize responses, truncated at their mask (host boundary).

        Both arrays come back in ONE transfer event — one blocking
        device->host fetch per boundary, not one per array (SURVEY §7.3).
        Two spans split the boundary: ``collect/wait`` is the host
        blocked on the device (near zero once the host, not the chip,
        sets the collect phase's pace), ``collect/detokenize`` the host
        loop after it."""
        from trlx_tpu import telemetry

        with telemetry.span("collect/wait"):
            tokens, response_mask = jax.device_get((tokens, response_mask))
        with telemetry.span("collect/detokenize"):
            lengths = response_mask.sum(axis=1)
            out = []
            for row, n in zip(tokens, lengths):
                ids = row[: int(n)].tolist()
                if self.tokenizer is not None:
                    out.append(
                        self.tokenizer.decode(ids, skip_special_tokens=True)
                    )
                else:
                    out.append(" ".join(map(str, ids)))
        return out

    def decode_queries(self, q_ids, q_mask) -> List[str]:
        q_ids, q_mask = jax.device_get((q_ids, q_mask))
        out = []
        for row, m in zip(q_ids, q_mask):
            ids = row[np.asarray(m, bool)].tolist()
            if self.tokenizer is not None:
                out.append(self.tokenizer.decode(ids, skip_special_tokens=True))
            else:
                out.append(" ".join(map(str, ids)))
        return out

    def evaluate(self) -> Dict[str, Any]:
        """Sample eval prompts, score, and build a sample table (reference
        `accelerate_base_model.py:152-222`). Uses full fixed-size pad-filled
        batches so the compiled sampler is reused."""
        if self.eval_pipeline is None:
            return {}
        from trlx_tpu import telemetry

        with telemetry.span("phase/eval"):
            return self._evaluate_body()

    def _evaluate_body(self) -> Dict[str, Any]:
        from trlx_tpu.utils import Clock

        clock = Clock()
        all_queries, all_texts, all_gt = [], [], []
        # dispatch every eval chunk's sampler first (independent programs),
        # then pull all outputs in ONE transfer event — one blocking fetch
        # for the whole eval instead of one per chunk
        chunks = []
        for batch, meta in self.eval_pipeline.create_loader(
            self.eval_batch_size, shuffle=False, drop_last=False
        ):
            out = self.sample(batch.input_ids, batch.attention_mask)
            # keep only what eval consumes — retaining full SampleOutputs
            # would pin every chunk's logprobs/values on device at once
            chunks.append((batch, meta, (out.tokens, out.response_mask)))
        fetched = jax.device_get([arrs for _, _, arrs in chunks])
        for (batch, meta, _), (tokens, response_mask) in zip(chunks, fetched):
            n_real = meta["n_real"]
            texts = self.decode_responses(tokens, response_mask)[:n_real]
            if meta["prompts_text"][0] is not None:
                queries = meta["prompts_text"][:n_real]
            else:
                queries = self.decode_queries(batch.input_ids, batch.attention_mask)[
                    :n_real
                ]
            all_queries += queries
            all_texts += texts
            if meta["response_gt"] is not None:
                all_gt += meta["response_gt"][:n_real]
        generate_time = clock.tick() / 1000.0

        stats: Dict[str, Any] = {"time/generate": generate_time}
        columns = ["query", "response"]
        table = [list(t) for t in zip(all_queries, all_texts)]
        if self.reward_fn is not None:
            scores = np.asarray(
                self.reward_fn(
                    samples=all_texts,
                    queries=all_queries,
                    response_gt=all_gt if all_gt else None,
                ),
                dtype=np.float32,
            )
            stats["reward/mean"] = float(scores.mean())
            stats["reward/std"] = float(scores.std())
            columns.append("reward")
            table = [row + [float(s)] for row, s in zip(table, scores)]
        if self.metric_fn is not None:
            metric_clock = Clock()
            metrics = self.metric_fn(all_texts)
            for k, v in metrics.items():
                v = np.asarray(v, dtype=np.float32)
                stats[f"metrics/{k}"] = float(v.mean())
            # reference logs metric_time (`accelerate_base_model.py:202-204`)
            stats["time/metric"] = metric_clock.tick() / 1000.0
        self._last_samples = (columns, table)
        return stats

    @property
    def eval_batch_size(self) -> int:
        return getattr(self.config.method, "chunk_size", None) or self.config.train.batch_size
