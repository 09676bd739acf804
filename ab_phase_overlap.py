"""A/B: streamed collect→train phase overlap vs the serial schedule.

One full PPO phase per timed region — collect 128 rollouts (8 chunks of
16, so the streamed dispatcher has real landing boundaries to overlap
across) plus every update of the phase (8 epoch-1 minibatch steps + the
fused epochs-2..4 residual scan). Both variants execute the SAME
:class:`~trlx_tpu.pipeline.ppo_buffer.StreamPlan` — same minibatch
slices, same order, bitwise-identical results
(tests/test_phase_overlap.py) — and differ only in dispatch:

- **overlapped**: epoch-1 minibatch k dispatches the moment its
  arrival-aligned block of rollouts has landed, while later chunks are
  still decoding against the frozen behavior snapshot
  (docs/async_pipeline.md);
- **serial**: the identical schedule, every update dispatched after
  collection completes — the pre-overlap phase structure.

Methodology per ab_overlap.py / bench_longctx.py: compile warmup first,
fresh sampler rng per call (inputs always distinct), variants interleaved
across rounds, best-of-N, each timed region ended on a device->host value
transfer.

Prints one JSON line with per-variant best ms, the overlap speedup, and
the trainer's own per-phase attribution (`exp/overlap_saved_ms` etc.) —
and RECORDS the same data (plus device kind and date) into
``AB_PHASE_OVERLAP.json`` at the repo root, so every measurement
self-records: the first hardware run lands the TPU delta in a committed
artifact automatically instead of waiting for someone to paste it into
this docstring.

Measured delta: CPU runs of this script verify parity + plumbing only —
a CPU "device" has no idle window for the overlap to fill (host and
device contend for the same single core), so the expected CPU result is
a wash. Measured on this image (1-core CPU, tiny shape, 2026-08-03):
overlapped 1406.7 ms vs serial 1384.8 ms per phase (0.98x, i.e. noise),
with 4/4 epoch-1 updates dispatched during collection and a 0.1 ms
post-collect drain — the schedule overlaps; the hardware doesn't. See
AB_PHASE_OVERLAP.json for the latest dated record per (metric, device
kind) — the artifact keeps one row per shape+backend, not a log.

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


def make_workload():
    """Bench-shape workload with chunk_size 16 << num_rollouts 128: eight
    landing boundaries per phase for the streamed dispatcher to overlap
    across. On a CPU backend the model/phase shrink (gpt2-small decode is
    hours on CPU) — the CPU run verifies parity + plumbing; the headline
    delta is a TPU measurement."""
    from trlx_tpu.utils.loading import (
        get_orchestrator, get_pipeline, get_trainer,
    )

    config = bench_config()
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
    config, trainer, pipeline, orch = make_workload()
    num_rollouts = config.method.num_rollouts
    seed_counter = [0]

    def run_phase(overlap):
        seed_counter[0] += 1
        trainer.buffer.clear_history()
        trainer.begin_streamed_phase(seed=seed_counter[0], overlap=overlap)
        orch.make_experience(num_rollouts, 0)
        trainer.finish_streamed_phase()
        # forcing fetch: a real program output of the last update
        force(jax.tree_util.tree_leaves(trainer.state.params)[0])

    variants = {
        "overlapped": lambda: run_phase(True),
        "serial": lambda: run_phase(False),
    }
    for fn in variants.values():  # compile warmup
        fn()
    for fn in variants.values():  # absorb donated-buffer relayout retrace
        fn()

    best = {k: float("inf") for k in variants}
    overlap_stats = {}
    order = list(variants)
    for rnd in range(4):
        for k in order if rnd % 2 == 0 else reversed(order):
            t0 = time.perf_counter()
            variants[k]()
            best[k] = min(best[k], (time.perf_counter() - t0) * 1000)
            if k == "overlapped":
                overlap_stats = {
                    key: round(v, 2)
                    for key, v in trainer._last_overlap_stats.items()
                }

    shape = (
        "ppo_phase_ms_B128_Q64_R48_gpt2s_chunk16"
        if jax.default_backend() != "cpu"
        else "ppo_phase_ms_cpu_tiny_chunk16"
    )
    record = {
        "metric": shape,
        **{f"{k}_ms": round(v, 1) for k, v in best.items()},
        "overlap_speedup_vs_serial": round(
            best["serial"] / best["overlapped"], 3
        ),
        **overlap_stats,
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(record))
    # self-recording measurement (repo discipline: results live in
    # committed artifacts, not docstring TODOs): keep the latest record
    # per (metric, device_kind), dated — shared helper, also used by
    # ab_int8_kv.py
    from trlx_tpu.utils.ab_record import record_latest

    record_latest(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "AB_PHASE_OVERLAP.json"),
        record,
    )
    # run-ledger history next to the latest-per-key artifact
    from trlx_tpu.telemetry.run_ledger import append_ab_manifest

    append_ab_manifest("ab_phase_overlap", record)


if __name__ == "__main__":
    main()
