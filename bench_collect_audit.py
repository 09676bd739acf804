"""One-off audit: where does the collect phase's time actually go?

Breaks one bench-shape PPO phase (B=128, Q=64, R=48, gpt2-small bf16,
int8 KV cache) into serialized components, each ended on a device->host
value fetch. Methodology per bench_longctx.py: fresh rng
per timed call (the sampler splits its key per invocation, so inputs are
always distinct), compile warmup first, best-of-N over interleaved rounds.

Prints a JSON dict of milliseconds.
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

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.utils.loading import get_orchestrator, get_pipeline, get_trainer


def bench_config():
    return TRLConfig.from_dict(
        {
            "model": {
                "model_type": "gpt2",
                "model_arch": {
                    "vocab_size": 50257,
                    "n_positions": 1024,
                    "n_embd": 768,
                    "n_layer": 12,
                    "n_head": 12,
                    "kv_cache_dtype": "int8",
                },
            },
            "train": {
                "seq_length": 64,
                "batch_size": 16,
                "epochs": 3,
                "total_steps": 10000,
                "eval_interval": 100000,
                "checkpoint_interval": 1000000,
                "mesh": {"dp": -1, "fsdp": 1, "tp": 1},
                "dtype": "bfloat16",
            },
            "method": {
                "name": "PPOConfig",
                "num_rollouts": 128,
                "chunk_size": 128,
                "ppo_epochs": 4,
                "init_kl_coef": 0.05,
                "scale_reward": "running",
                "gen_kwargs": {
                    "max_new_tokens": 48,
                    "min_new_tokens": 48,
                    "top_k": 0,
                    "do_sample": True,
                    "eos_token_id": 50256,
                    "pad_token_id": 50256,
                },
            },
        }
    )


def force(x):
    """Real value fetch — the only thing that forces execution here."""
    return float(jnp.ravel(x)[0])


def bench_reward_fn(samples, queries, response_gt=None):
    """The bench workload's cheap host reward (one definition for the
    audit + every ab_* script — a drifted copy would silently measure a
    different workload)."""
    return [len(set(s)) / max(len(s), 1) for s in samples]


def make_bench_workload(chunk_size=None):
    """(trainer, pipeline, orchestrator) at the bench shape — shared setup
    for the A/B scripts."""
    from trlx_tpu.utils.loading import (
        get_orchestrator, get_pipeline, get_trainer,
    )

    config = bench_config()
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(100, 40000, size=rng.integers(4, 33)))
               for _ in range(512)]
    trainer = get_trainer(config.train.trainer)(
        config, reward_fn=bench_reward_fn
    )
    pipeline = get_pipeline(config.train.pipeline)(
        prompts, config.train.seq_length
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=bench_reward_fn,
        chunk_size=chunk_size or config.method.chunk_size,
    )
    return config, trainer, pipeline, orch


def main():
    config = bench_config()
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(100, 40000, size=rng.integers(4, 33)))
               for _ in range(512)]

    def reward_fn(samples, queries, response_gt=None):
        return [len(set(s)) / max(len(s), 1) for s in samples]

    trainer = get_trainer(config.train.trainer)(config, reward_fn=reward_fn)
    pipeline = get_pipeline(config.train.pipeline)(
        prompts, config.train.seq_length
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_fn,
        chunk_size=config.method.chunk_size,
    )

    # ---- warmup: compile sampler, ref, rewards, train phase ----
    for _ in range(2):
        trainer.buffer.clear_history()
        orch.make_experience(config.method.num_rollouts, 0)
        trainer.train_on_buffer()
        force(jax.tree_util.tree_leaves(trainer.state.params)[0])

    out = {}

    # ---- host round-trip: fetch of an already-materialized scalar ----
    z = jnp.zeros(())
    force(z)
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        force(z)
        ts.append((time.perf_counter() - t0) * 1000)
    out["roundtrip_ms"] = round(min(ts), 1)

    batch, meta = next(orch._loader)

    def timed(fn, n=3):
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            best = min(best, (time.perf_counter() - t0) * 1000)
        return round(best, 1)

    # ---- sampler alone (exec + roundtrip) ----
    def run_sample():
        so = trainer.sample(batch.input_ids, batch.attention_mask)
        force(so.tokens)
        return so

    out["sample_ms"] = timed(run_sample)

    # ---- sampler + ref forward chained ----
    def run_sample_ref():
        so = trainer.sample(batch.input_ids, batch.attention_mask)
        ref = trainer.score_ref(
            batch.input_ids, batch.attention_mask, so.tokens, so.response_mask
        )
        force(ref)

    out["sample_ref_ms"] = timed(run_sample_ref)

    # ---- ref alone (on fixed tokens; approx = sample_ref - sample) ----
    so = trainer.sample(batch.input_ids, batch.attention_mask)
    jax.device_get(so.tokens)

    # ---- host tail: decode + reward + numpy scaling (no device work:
    #      decode_responses' device_get is a no-op on numpy arrays) ----
    toks, mask = jax.device_get((so.tokens, so.response_mask))

    def host_tail():
        texts = trainer.decode_responses(toks, mask)
        scores = np.asarray(reward_fn(texts, None), dtype=np.float32)
        return scores

    out["host_decode_reward_ms"] = timed(host_tail)

    # ---- full make_experience (forced by its own internal fetch +
    #      forcing the pushed rewards at the end) ----
    def run_collect():
        trainer.buffer.clear_history()
        orch.make_experience(config.method.num_rollouts, 0)
        force(trainer.buffer._chunks[-1].rewards)

    out["collect_ms"] = timed(run_collect)

    # ---- train phase alone (buffer already filled by last collect) ----
    def run_train():
        trainer.train_on_buffer()
        force(jax.tree_util.tree_leaves(trainer.state.params)[0])

    out["train_ms"] = timed(run_train)

    # ---- full phase, as bench.py sequences it ----
    def run_phase():
        trainer.buffer.clear_history()
        orch.make_experience(config.method.num_rollouts, 0)
        trainer.train_on_buffer()
        force(jax.tree_util.tree_leaves(trainer.state.params)[0])

    out["phase_ms"] = timed(run_phase)

    out["device_kind"] = jax.devices()[0].device_kind
    print(json.dumps(out))


if __name__ == "__main__":
    main()
