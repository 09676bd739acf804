"""A/B: asynchronous actor–learner PPO vs the serial same-plan phase.

One full PPO phase per timed region, both variants on the continuous
rollout engine (the actors) and the SAME
:class:`~trlx_tpu.pipeline.ppo_buffer.StreamPlan`:

- **async**: ``train.async_rl: {enabled, staleness_window: 1}`` — the
  learner consumes epoch-1 minibatches as their rows land and pushes
  refreshed weights to the engine MID-generation under the bounded-
  staleness window (docs/async_pipeline.md);
- **serial**: the identical plan, every update dispatched after
  collection completes (``overlap=False`` — the pre-async phase
  structure and the ``staleness_window: 0`` degenerate mode's
  execution order).

Methodology per ab_phase_overlap.py: compile warmup, variants
interleaved across rounds, best-of-N, one forcing fetch per timed
region. Before timing, the script runs the async self-check
(`trlx_tpu.analysis.async_smoke`): the ``staleness_window=0`` phase
must be BITWISE-identical to the serial same-plan phase, and a planted
dead actor (``engine.admit`` chaos) must surface an ``actor-dead``
health event and recover via the resilience supervisor with no hang —
an A/B whose two arms could diverge semantically, or whose failure
path hangs, measures nothing.

Prints one JSON line and RECORDS it into ``AB_ASYNC_RL.json`` (repo
root, `utils/ab_record.py`): the latest dated record per (metric,
device_kind) — the first hardware run lands the TPU throughput delta
in a committed artifact automatically.

Measured delta: CPU runs verify parity + plumbing only — host and
device contend for one core, so the learner work the async schedule
hides inside decode is not actually hidden on CPU (same story as
ab_phase_overlap.py, whose CPU record is 0.98x). Measured on this
image (1-core CPU, tiny shape, 2026-08-04): async 1023.8 ms vs serial
1027.5 ms per phase (1.00x — the expected wash) with 4/4 epoch-1
updates consumed during collection, 3 in-flight weight pushes,
staleness p50 1.0 bounded by the window of 1, and both smoke scenarios
green. The headline number is the first hardware round: collect MFU
0.157 means the learner idles most of every serial phase — the async
schedule's upper bound is hiding all of epoch-1 plus the drain inside
that window. See AB_ASYNC_RL.json for the latest dated record per
(metric, device_kind).

A CPU run of this script is a plumbing check (the model auto-shrinks) and
records nothing under a device's name.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("WANDB_DISABLED", "1")

import jax
import numpy as np

from bench_collect_audit import (
    bench_config, bench_reward_fn as reward_fn, force,
)


def make_workload(async_rl):
    """Bench-shape continuous-engine workload; chunk 16 << rollouts 128
    gives the async learner real landing boundaries. CPU shrinks the
    model/phase (the CPU tier proves parity + plumbing, not the
    delta)."""
    from trlx_tpu.utils.loading import (
        get_orchestrator, get_pipeline, get_trainer,
    )

    config = bench_config()
    config.train.rollout = {"engine": "continuous"}
    if async_rl:
        config.train.async_rl = dict(async_rl)
    if jax.default_backend() == "cpu":
        config.update(
            model={"model_arch": {
                "vocab_size": 512, "n_positions": 128, "n_embd": 64,
                "n_layer": 2, "n_head": 2, "kv_cache_dtype": "bfloat16",
            }},
            method={
                "num_rollouts": 64,
                "gen_kwargs": dict(
                    config.method.gen_kwargs,
                    max_new_tokens=8, min_new_tokens=8,
                    eos_token_id=510, pad_token_id=511,
                ),
            },
        )
        config.train.rollout = {
            "engine": "continuous", "slots": 16, "admit_width": 16,
            "harvest_width": 16,
        }
    rng = np.random.default_rng(0)
    vocab = config.model.model_arch["vocab_size"]
    prompts = [
        list(rng.integers(1, vocab - 8, size=rng.integers(4, 33)))
        for _ in range(512)
    ]
    trainer = get_trainer(config.train.trainer)(
        config, reward_fn=reward_fn
    )
    pipeline = get_pipeline(config.train.pipeline)(
        prompts, config.train.seq_length
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_fn, chunk_size=16
    )
    return config, trainer, pipeline, orch


def main():
    # self-check first: bitwise W=0 parity + dead-actor recovery (an
    # A/B over semantically-divergent arms measures nothing)
    from trlx_tpu.analysis.async_smoke import run_async_smoke

    smoke = run_async_smoke()
    smoke_flags = {
        "parity_w0_bitwise": bool(
            smoke["scenarios"]["staleness0_parity"].get("passed")
        ),
        "dead_actor_recovered": bool(
            smoke["scenarios"]["dead_actor_recovery"].get("passed")
        ),
    }
    if not smoke["passed"]:
        print(json.dumps({"error": "async smoke failed", **smoke_flags,
                          "scenarios": smoke["scenarios"]}, default=str))
        return 1

    config, trainer, pipeline, orch = make_workload(
        {"enabled": True, "staleness_window": 1}
    )
    num_rollouts = config.method.num_rollouts
    seed_counter = [0]

    def run_phase(overlap):
        seed_counter[0] += 1
        trainer.buffer.clear_history()
        # overlap=None → the async schedule (guard + in-flight pushes);
        # overlap=False → the serial same-plan baseline (the explicit
        # escape begin_streamed_phase honors even under async config)
        trainer.begin_streamed_phase(seed=seed_counter[0], overlap=overlap)
        orch.make_experience(num_rollouts, 0)
        trainer.finish_streamed_phase()
        force(jax.tree_util.tree_leaves(trainer.state.params)[0])

    variants = {
        "async": lambda: run_phase(None),
        "serial": lambda: run_phase(False),
    }
    for fn in variants.values():  # compile warmup
        fn()
    for fn in variants.values():  # absorb donated-buffer relayout retrace
        fn()

    best = {k: float("inf") for k in variants}
    async_stats = {}
    order = list(variants)
    for rnd in range(4):
        for k in order if rnd % 2 == 0 else reversed(order):
            t0 = time.perf_counter()
            variants[k]()
            best[k] = min(best[k], (time.perf_counter() - t0) * 1000)
            if k == "async":
                async_stats = {
                    key: round(v, 3)
                    for key, v in trainer._last_overlap_stats.items()
                    if key.startswith("async/")
                    or key == "exp/overlap_streamed_updates"
                }

    shape = (
        "ppo_async_phase_ms_B128_Q64_R48_gpt2s_chunk16"
        if jax.default_backend() != "cpu"
        else "ppo_async_phase_ms_cpu_tiny_chunk16"
    )
    record = {
        "metric": shape,
        **{f"{k}_ms": round(v, 1) for k, v in best.items()},
        "async_speedup_vs_serial": round(best["serial"] / best["async"], 3),
        **async_stats,
        **smoke_flags,
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(record))
    from trlx_tpu.utils.ab_record import record_latest

    record_latest(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "AB_ASYNC_RL.json"),
        record,
    )
    # run-ledger history next to the latest-per-key artifact, so any
    # two async A/B rounds diff via `telemetry --compare`
    from trlx_tpu.telemetry.run_ledger import append_ab_manifest

    append_ab_manifest("ab_async_rl", record)
    return 0


if __name__ == "__main__":
    sys.exit(main())
