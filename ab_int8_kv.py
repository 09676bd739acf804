"""A/B: int8 vs bf16 rollout KV cache, on both rollout engines (TPU).

Methodology per the repo's measurement discipline: per measurement, queue
K sampler dispatches on DISTINCT inputs, end the window on ONE summed
fetch, and interleave variants across rounds (A/B by alternation on one
machine, never against recorded numbers).

Four variants: {bf16, int8} × {fixed sampler, continuous engine}. The
int8 lever now routes through BOTH cache layouts — the linear buffers
(``models/gpt2.py::kv_buffers``) and the paged/block cache the
continuous engine decodes over (``inference/kv_cache.py``: quantize on
write through the block table, dequantize the gathered logical view).

Self-recording (the AB_PHASE_OVERLAP.json pattern): every run updates
``AB_INT8_KV.json`` at the repo root with the latest record per
(metric, device kind) — the first hardware run lands the TPU delta in a
committed artifact automatically. On a CPU backend the model shrinks
(gpt2-small decode is minutes/call on CPU): a CPU run here is a plumbing
and parity check and records nothing under a device's name; the headline
delta is a TPU measurement.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("WANDB_DISABLED", "1")

import numpy as np


def build_trainer(kv_dtype, engine):
    import jax

    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.utils.loading import get_trainer

    on_cpu = jax.default_backend() == "cpu"
    arch = (
        {"vocab_size": 512, "n_positions": 128, "n_embd": 64,
         "n_layer": 2, "n_head": 2}
        if on_cpu
        else {"vocab_size": 50257, "n_positions": 1024, "n_embd": 768,
              "n_layer": 12, "n_head": 12}
    )
    # engine geometry must fit the measured batch width B: slots default
    # to chunk_size (128), whose default harvest_width (32) exceeds the
    # CPU shrink's 16-row batches — drive() would floor the target to 0
    # and the engine variants would never decode a token
    rollout = (
        {"engine": engine, "slots": 16, "admit_width": 8,
         "harvest_width": 8, "block_size": 8}
        if on_cpu
        else {"engine": engine, "admit_width": 32, "harvest_width": 32}
    )
    config = TRLConfig.from_dict(
        {
            "model": {
                "model_type": "gpt2",
                "model_arch": dict(arch, kv_cache_dtype=kv_dtype),
            },
            "train": {
                "seq_length": 64, "batch_size": 16, "epochs": 1,
                "total_steps": 10000, "eval_interval": 100000,
                "checkpoint_interval": 1000000,
                "mesh": {"dp": -1, "fsdp": 1, "tp": 1}, "dtype": "bfloat16",
                "rollout": rollout,
            },
            "method": {
                "name": "PPOConfig", "num_rollouts": 128, "chunk_size": 128,
                "ppo_epochs": 4,
                "gen_kwargs": {
                    "max_new_tokens": 8 if on_cpu else 48,
                    "min_new_tokens": 8 if on_cpu else 48,
                    "top_k": 0,
                    "do_sample": True,
                    "eos_token_id": 511 if on_cpu else 50256,
                    "pad_token_id": 511 if on_cpu else 50256,
                },
            },
        }
    )
    return get_trainer(config.train.trainer)(
        config, reward_fn=lambda **kw: [0.0]
    )


def main():
    import jax
    import jax.numpy as jnp

    on_cpu = jax.default_backend() == "cpu"
    B, Q = (16, 64) if on_cpu else (128, 64)
    K = 2 if on_cpu else 10
    rounds_n = 2 if on_cpu else 6
    rng = np.random.default_rng(0)
    vocab_hi = 500 if on_cpu else 40000

    def fresh_batches(n):
        return [
            (
                jnp.asarray(rng.integers(100, vocab_hi, (B, Q)), jnp.int32),
                jnp.ones((B, Q), jnp.int32),
            )
            for _ in range(n)
        ]

    trainers = {
        "bf16": build_trainer("bfloat16", "fixed"),
        "int8": build_trainer("int8", "fixed"),
        "bf16_engine": build_trainer("bfloat16", "continuous"),
        "int8_engine": build_trainer("int8", "continuous"),
    }

    def measure_fixed(trainer, batches):
        t0 = time.time()
        acc = jnp.zeros((), jnp.int32)
        for ids, mask in batches:
            out = trainer.sample(ids, mask)
            acc = acc + out.tokens.sum()
        _ = int(acc)  # single forcing fetch
        return time.time() - t0

    def measure_engine(trainer, batches):
        """Continuous engine: same prompt volume through the slot loop
        (admission/decode/harvest included — this IS the engine's cost
        model, unlike the fixed path where scoring overlaps)."""
        engine = trainer.rollout_engine_obj
        t0 = time.time()
        total = 0
        for ids, mask in batches:
            trainer.reset_rollout_phase()
            engine.start_phase(
                trainer.rollout_params(), trainer.rollout_phase_key()
            )
            n = ids.shape[0]
            engine.submit(np.asarray(ids), np.asarray(mask))
            target = (n // engine.harvest_width) * engine.harvest_width
            if target < n:
                raise RuntimeError(
                    f"engine harvest_width {engine.harvest_width} does "
                    f"not fit the {n}-row batch — the measurement would "
                    "drop rows (or decode nothing at all)"
                )
            for group in engine.drive(target):
                total += int(np.asarray(group["tokens"]).shape[0])
        if total != len(batches) * n:
            raise RuntimeError("engine completed fewer rows than submitted")
        return time.time() - t0

    def measure(name, batches):
        trainer = trainers[name]
        if name.endswith("_engine"):
            return measure_engine(trainer, batches)
        return measure_fixed(trainer, batches)

    # warm every compiled program (distinct signatures)
    for name in trainers:
        measure(name, fresh_batches(1))

    rounds = {name: [] for name in trainers}
    order = list(trainers)
    for r in range(rounds_n):
        for name in order if r % 2 == 0 else reversed(order):
            rounds[name].append(measure(name, fresh_batches(K)))
    for name, ts in rounds.items():
        per_call = [t / K for t in ts]
        print(
            f"{name}: per-call mean {np.mean(per_call)*1e3:.1f} ms  "
            f"median {np.median(per_call)*1e3:.1f} ms  "
            f"all {[round(x*1e3, 1) for x in per_call]}"
        )

    # the RECORDED per-call ms uses the same definition as the printed
    # lines, so artifact and console agree. Engine variants additionally
    # pay per-step done-flag fetches — that is part of the engine's real
    # cost model, deliberately included.
    med = {
        name: float(np.median(ts)) / K
        for name, ts in rounds.items()
    }
    record = {
        "metric": (
            "int8_kv_sampler_ms_B128_Q64_R48_gpt2s"
            if not on_cpu else "int8_kv_sampler_ms_cpu_tiny"
        ),
        **{f"{name}_ms": round(v * 1000, 1) for name, v in med.items()},
        "int8_speedup_fixed": round(med["bf16"] / med["int8"], 3),
        "int8_speedup_engine": round(
            med["bf16_engine"] / med["int8_engine"], 3
        ),
        "engine_vs_fixed_bf16": round(med["bf16"] / med["bf16_engine"], 3),
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(record))

    from trlx_tpu.utils.ab_record import record_latest

    record_latest(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "AB_INT8_KV.json"),
        record,
    )
    # run-ledger history next to the latest-per-key artifact
    from trlx_tpu.telemetry.run_ledger import append_ab_manifest

    append_ab_manifest("ab_int8_kv", record)


if __name__ == "__main__":
    main()
