"""``--health-smoke``: planted-anomaly self-check for the health layer.

The ``--plant-nan`` / ``--plant-slowdown`` pattern, applied to the
run-health detectors (telemetry/health.py): a monitoring layer that
cannot detect a planted anomaly is vacuous exactly when it breaks. The
smoke runs the REAL streamed phase loop twice over one trainer:

1. **clean phases** — the detectors must stay silent (zero events);
2. **planted phases** — the policy's embedding table is scaled by a
   large factor, which sharpens every logit distribution (entropy
   collapses toward 0) and snaps the policy far from the frozen KL
   reference (rollout KL spikes). The ``kl-spike`` and
   ``entropy-collapse`` detectors must both trip on the next phase's
   real fetched stats — no synthetic series are injected anywhere.

The planted run drives the full failure path: the ``on_error: dump``
policy writes a flight-recorder forensics file, which the smoke then
parses and renders through the same ``--inspect`` code path operators
use. PASS requires all four: clean-quiet, both detectors tripped, a
dump on disk, and the dump inspectable.
"""

from __future__ import annotations

import os
import tempfile
from typing import Any, Dict, Optional

#: detectors the planted anomaly must trip for the smoke to pass
REQUIRED_TRIPS = ("kl-spike", "entropy-collapse")


def smoke_config_dict(dump_dir: str) -> Dict[str, Any]:
    """Harness-shape PPO config with health armed: 3 chunks per phase,
    2 ppo_epochs (6 update rows per phase), dump-on-error policy.

    ``warmup: 3``: the kl-spike series (``policy/mean_rollout_kl``) is
    phase-level — observed ONCE per phase — so its z-score rule needs
    ``warmup`` clean *phases* to arm; the per-row series (entropy,
    ratios) warm far faster. The smoke's clean window runs
    ``warmup + 1`` phases so every armed detector has a baseline."""
    from trlx_tpu.analysis import harness

    cfg = harness.tiny_config_dict("ppo")
    cfg["method"].update(num_rollouts=24, chunk_size=8, ppo_epochs=2)
    cfg["train"]["health"] = {
        "enabled": True,
        "on_error": "dump",
        "dump_dir": dump_dir,
        "warmup": 3,
        # the smoke is about the learning's dynamics; whether this CPU
        # stalled in a clean phase is not its question
        "disable": ["host-stall"],
    }
    return cfg


def _poison_embeddings(trainer, factor: float) -> None:
    """Scale the policy's token-embedding table in place on device.

    With a tied LM head, scaling the embedding scales every logit
    ~linearly: softmax sharpens (entropy -> 0) and the sampled policy
    leaps away from the frozen reference (rollout KL explodes) — a
    *real* divergence planted in the params, exercising sampler, ref
    scoring, and update stats end to end."""
    import jax

    from trlx_tpu.trainer.common import TrainState

    params = dict(trainer.state.params)
    backbone = dict(params[trainer.backbone_key])
    backbone["wte"] = jax.tree_util.tree_map(
        lambda x: (x * factor).astype(x.dtype), backbone["wte"]
    )
    params[trainer.backbone_key] = backbone
    trainer.state = TrainState(
        params=jax.device_put(params, trainer.param_shardings),
        opt_state=trainer.state.opt_state,
        step=trainer.state.step,
    )


def run_health_smoke(
    dump_dir: Optional[str] = None,
    clean_phases: int = 4,
    planted_phases: int = 2,
    poison_factor: float = 30.0,
) -> Dict[str, Any]:
    """Run the self-check; returns a JSON-able summary with ``passed``.

    Forces nothing on the caller's global tracer (scoped, like the perf
    audit) and writes dumps under ``dump_dir`` (a temp dir when unset —
    CI passes an artifact directory)."""
    import numpy as np

    from trlx_tpu import telemetry
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.orchestrator.ppo_orchestrator import PPOOrchestrator
    from trlx_tpu.pipeline.prompt_pipeline import PromptPipeline
    from trlx_tpu.telemetry.flight_recorder import inspect_dump, load_dump
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    dump_dir = dump_dir or tempfile.mkdtemp(prefix="health-smoke-")
    config = TRLConfig.from_dict(smoke_config_dict(dump_dir))
    trainer = PPOTrainer(config)

    def reward_fn(samples, queries, response_gt=None):
        return [(len(s) % 5) / 2.0 - 1.0 for s in samples]

    rng = np.random.default_rng(0)
    prompts = [
        [int(x) for x in rng.integers(1, 28, size=4)] for _ in range(64)
    ]
    pipeline = PromptPipeline(prompts, config.train.seq_length)
    orch = PPOOrchestrator(
        trainer, pipeline, reward_fn=reward_fn,
        chunk_size=config.method.chunk_size,
    )

    def one_phase(seed: int) -> None:
        trainer.buffer.clear_history()
        trainer.begin_streamed_phase(seed=seed)
        orch.make_experience(config.method.num_rollouts, 0)
        trainer.finish_streamed_phase()

    monitor = trainer.health_monitor
    try:
        with telemetry.scoped_tracer():
            for i in range(clean_phases):
                one_phase(seed=i)
            clean_events = [ev.to_dict() for ev in monitor.events]

            _poison_embeddings(trainer, poison_factor)
            for i in range(planted_phases):
                one_phase(seed=100 + i)
    finally:
        orch.close(reraise=False)

    tripped = dict(sorted(monitor.event_counts.items()))
    dumps = list(trainer.flight_recorder.dumped)
    inspect_ok = False
    inspect_error = ""
    rendered = ""
    if dumps:
        try:
            payload = load_dump(dumps[-1])
            rendered = inspect_dump(payload)
            inspect_ok = bool(rendered)
        except Exception as e:
            inspect_error = f"{type(e).__name__}: {e}"

    missing = [d for d in REQUIRED_TRIPS if d not in tripped]
    passed = (
        not clean_events and not missing and bool(dumps) and inspect_ok
    )
    return {
        "passed": passed,
        "clean_phases": clean_phases,
        "clean_events": clean_events,
        "planted_phases": planted_phases,
        "tripped": tripped,
        "missing_required": missing,
        "dump": dumps[-1] if dumps else None,
        "dumps": dumps,
        "inspect_ok": inspect_ok,
        "inspect_error": inspect_error,
        "inspect_preview": rendered.splitlines()[:8],
        "dump_dir": dump_dir,
    }


def format_smoke_text(summary: Dict[str, Any]) -> str:
    lines = []
    n_clean = len(summary["clean_events"])
    lines.append(
        f"clean run ({summary['clean_phases']} phases): "
        f"{n_clean} events {'OK' if n_clean == 0 else '— MUST be quiet'}"
    )
    trips = ", ".join(
        f"{d} x{n}" for d, n in summary["tripped"].items()
    ) or "none"
    lines.append(
        f"planted run ({summary['planted_phases']} phases): {trips}"
    )
    if summary["missing_required"]:
        lines.append(
            "MISSING required trips: "
            + ", ".join(summary["missing_required"])
        )
    dump = summary["dump"]
    if dump:
        status = "parseable" if summary["inspect_ok"] else (
            f"INSPECT FAILED: {summary['inspect_error']}"
        )
        lines.append(f"flight dump: {os.path.basename(dump)} ({status})")
    else:
        lines.append("flight dump: MISSING (on_error=dump did not fire)")
    lines.append("health-smoke: " + ("PASS" if summary["passed"] else "FAIL"))
    return "\n".join(lines)
