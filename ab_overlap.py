"""A/B: overlapped collect orchestration vs the reference's serial order.

The orchestrator overlaps the host boundary three ways (VERDICT r3 #1;
`orchestrator/ppo_orchestrator.py::_dispatch_chunk`):

1. the frozen-ref forward is dispatched right behind the sampler, so it
   executes on device DURING the token fetch + host scoring;
2. the sampler outputs start their device->host copy at dispatch time
   (``copy_to_host_async``), overlapping the transfer with the ref exec;
3. the rollout KL stays a device scalar (fetching it per chunk would add
   a blocking transfer per chunk).

The serial variant reproduces the reference's sequence
(`ppo_orchestrator.py:74-151`): generate -> fetch -> decode -> score ->
THEN the ref/recompute forwards -> rewards. Same compiled programs, same
shapes — only the dispatch order differs.

A third variant splits the phase into 2 chunks of 64 (the pipelining the
orchestrator does when num_rollouts > chunk_size): chunking hides the
per-chunk host tail behind the next chunk's decode, and pays one more
blocking fetch per extra chunk. Which side wins on this machine is not
measured; the variant is here so the single-fetch default can be judged
by a run, not an assumption.

Methodology per bench_longctx.py: compile warmup first, fresh sampler rng
per call (inputs always distinct), variants interleaved across rounds,
best-of-N, one fetch ending each timed region.

Prints one JSON line with per-variant best ms and the speedup.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("WANDB_DISABLED", "1")

import jax
import jax.numpy as jnp
import numpy as np

from bench_collect_audit import (
    bench_reward_fn as reward_fn, force, make_bench_workload,
)
from trlx_tpu.utils.loading import get_orchestrator


def main():
    config, trainer, pipeline, orch = make_bench_workload()
    orch_chunked = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_fn, chunk_size=64
    )
    loader = iter(pipeline.create_loader(128, shuffle=True, seed=1))

    def overlapped():
        trainer.buffer.clear_history()
        orch.make_experience(config.method.num_rollouts, 0)
        force(trainer.buffer._chunks[-1].rewards)

    def chunked():
        trainer.buffer.clear_history()
        orch_chunked.make_experience(config.method.num_rollouts, 0)
        force(trainer.buffer._chunks[-1].rewards)

    def serial():
        """Reference dispatch order: nothing queued behind the sampler."""
        nonlocal loader
        trainer.buffer.clear_history()
        try:
            batch, meta = next(loader)
        except StopIteration:
            loader = iter(pipeline.create_loader(128, shuffle=True, seed=2))
            batch, meta = next(loader)
        so = trainer.sample(batch.input_ids, batch.attention_mask)
        toks, mask = jax.device_get((so.tokens, so.response_mask))
        texts = trainer.decode_responses(toks, mask)
        scores = np.asarray(reward_fn(texts, None), dtype=np.float32)
        ref = trainer.score_ref(
            batch.input_ids, batch.attention_mask, so.tokens, so.response_mask
        )
        rewards = trainer.compute_rewards(
            so.logprobs, ref, so.response_mask, scores
        )
        force(rewards)

    variants = {"overlapped": overlapped, "serial": serial, "chunked": chunked}
    for fn in variants.values():  # compile warmup
        fn()

    best = {k: float("inf") for k in variants}
    order = list(variants)
    for rnd in range(4):
        for k in order if rnd % 2 == 0 else reversed(order):
            t0 = time.perf_counter()
            variants[k]()
            best[k] = min(best[k], (time.perf_counter() - t0) * 1000)

    print(json.dumps({
        "metric": "collect_phase_ms_B128_Q64_R48_gpt2s",
        **{f"{k}_ms": round(v, 1) for k, v in best.items()},
        "overlap_speedup_vs_serial": round(best["serial"] / best["overlapped"], 3),
        "chunked_vs_single_fetch": round(best["chunked"] / best["overlapped"], 3),
        "device_kind": jax.devices()[0].device_kind,
    }))


if __name__ == "__main__":
    main()
