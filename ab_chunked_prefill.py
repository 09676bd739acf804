"""A/B: chunked vs monolithic admission prefill, sharing off and on.

The PR's perf claim (docs/inference.md "Chunked prefill"): the engine's
monolithic ``[A, Q]`` prefill pays full prompt-capacity attention FLOPs
for every admitted row; the chunked program (``rollout.prefill_chunk``)
scans block-aligned prompt-column chunks under a ``lax.cond`` that skips
what no admitted row needs — leading pad columns of left-padded prompts,
and blocks served read-only from the shared-prefix pool — so prefill
compute scales with real prompt length, and prefix sharing becomes a
prefill-FLOP win (the docs/serving.md caveat, closed).

Methodology per the repo's measurement discipline: all four variants
run the SAME serving-style pump loop (plan-just-in-time admission,
harvest at fixed width), variants interleave across rounds (wall-clock
swings with machine load — A/B by alternation, never against recorded
numbers), and the CPU tier auto-shrinks the model: the CPU record
verifies bitwise parity + plumbing; the headline delta is a TPU
measurement (pending — this script self-records it on first hardware
run).

Four variants: {monolithic, chunked} x {sharing off, sharing on}.
Sharing-off batches use mixed-length left-padded prompts (the chunk
skip is the all-pad leading columns); sharing-on batches use
full-length prompts with a common leading half (the skip is the
pool-covered shared blocks — left-padded prompts share iff they pad
identically, docs/serving.md parity caveat).

Self-recording: updates ``AB_CHUNKED_PREFILL.json`` (latest record per
metric + device kind, ``utils/ab_record.py``) and appends a run-ledger
manifest (``telemetry/run_ledger.py``).

A CPU run of this script is a plumbing check (the model auto-shrinks) and
records nothing under a device's name.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("WANDB_DISABLED", "1")

import numpy as np


def build_trainer():
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
    Q = 32 if on_cpu else 64
    R = 8 if on_cpu else 48
    rollout = (
        {"engine": "continuous", "slots": 16, "admit_width": 8,
         "harvest_width": 8, "block_size": 8}
        if on_cpu
        else {"engine": "continuous", "admit_width": 32,
              "harvest_width": 32, "block_size": 16}
    )
    config = TRLConfig.from_dict(
        {
            "model": {"model_type": "gpt2", "model_arch": arch},
            "train": {
                "seq_length": Q, "batch_size": 16, "epochs": 1,
                "total_steps": 10000, "eval_interval": 100000,
                "checkpoint_interval": 1000000,
                "mesh": {"dp": -1, "fsdp": 1, "tp": 1},
                "dtype": "bfloat16",
                "rollout": rollout,
            },
            "method": {
                "name": "PPOConfig", "num_rollouts": 128,
                "chunk_size": 128, "ppo_epochs": 4,
                "gen_kwargs": {
                    "max_new_tokens": R,
                    "min_new_tokens": R,
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


def build_engines(trainer, prefill_chunk, pool_blocks):
    base = trainer.rollout_engine_obj
    return type(base)(
        apply_fn=base._apply_fn,
        init_cache_fn=base._init_cache_fn,
        gen_config=base.gen_config,
        query_length=base.Q,
        vocab_size=base.vocab_size,
        num_slots=base.num_slots,
        admit_width=base.admit_width,
        harvest_width=base.harvest_width,
        block_size=base.block_size,
        mesh=base.mesh,
        param_shardings=base._param_shardings,
        cache_sharding=base._cache_sharding,
        with_values=base.with_values,
        prefix_pool_blocks=pool_blocks,
        prefill_chunk=prefill_chunk,
    )


def make_prompts(rng, n, Q, vocab_hi, shared_prefix):
    """[n, Q] ids/mask. ``shared_prefix`` None: mixed-length left-padded
    rows (the pad-skip workload); else: full-length rows with a common
    leading half (the pool-skip workload — equal lengths so left-padded
    rows pad identically and the trie shares)."""
    ids = rng.integers(100, vocab_hi, (n, Q)).astype(np.int32)
    mask = np.ones((n, Q), np.int32)
    if shared_prefix is None:
        for i in range(n):
            real = int(rng.integers(4, Q + 1))
            mask[i, : Q - real] = 0
            ids[i, : Q - real] = 0
        # submit length-sorted: admit groups become length-homogeneous
        # (what a length-bucketing serving scheduler produces), so short
        # groups actually skip their leading all-pad chunks — the chunk
        # skip is a GROUP-max decision, and per-row RNG makes admission
        # order irrelevant to every row's bits (the engine contract)
        order = np.argsort(mask.sum(axis=1))
        ids, mask = ids[order], mask[order]
    else:
        ids[:, : len(shared_prefix)] = shared_prefix
    return ids, mask


def serve_rows(engine, ids, mask, pool=None):
    """Serving-style pump loop: plan-just-in-time admission in
    admit_width waves (a later wave's plan sees the earlier wave's
    published blocks as ready — the server's flow), pump to completion.
    Returns {row: tokens} host arrays. Pool refcounts are deliberately
    not released (the run ends; the pool is sized to never fill)."""
    N, fed = ids.shape[0], 0
    published_by_row = {}

    def on_admitted(rows):
        if pool is None:
            return
        for row in rows:
            blocks = published_by_row.pop(row, None)
            if blocks:
                pool.mark_ready(blocks)

    engine._admit_listener = on_admitted
    got = {}
    while len(got) < N:
        free = engine.free_capacity
        if fed < N and free > 0:
            take = min(free, engine.admit_width, N - fed)
            batch = slice(fed, fed + take)
            shared_maps = publish_maps = None
            if pool is not None:
                plans = [
                    pool.plan_admission(ids[i], mask[i])
                    for i in range(fed, fed + take)
                ]
                shared_maps = np.stack([p.shared_map for p in plans])
                publish_maps = np.stack([p.publish_map for p in plans])
            rows = engine.submit(
                ids[batch], mask[batch],
                shared_maps=shared_maps, publish_maps=publish_maps,
            )
            if pool is not None:
                for row, plan in zip(rows, plans):
                    if plan.published:
                        published_by_row[row] = plan.published
            fed += take
        for group in engine.pump():
            toks = np.asarray(group["tokens"])
            for j, r in enumerate(group["rows"]):
                got[r] = toks[j]
    return got


def main():
    import jax

    from trlx_tpu.serving.prefix_cache import PrefixBlockPool

    on_cpu = jax.default_backend() == "cpu"
    trainer = build_trainer()
    base = trainer.rollout_engine_obj
    Q = base.Q
    W = 8 if on_cpu else 16
    pool_blocks = 64
    vocab_hi = 500 if on_cpu else 40000
    N = 32 if on_cpu else 128
    rounds_n = 2 if on_cpu else 6

    engines = {
        "mono": build_engines(trainer, 0, 0),
        "chunked": build_engines(trainer, W, 0),
        "mono_shared": build_engines(trainer, 0, pool_blocks),
        "chunked_shared": build_engines(trainer, W, pool_blocks),
    }
    print(
        f"chunk width {engines['chunked'].prefill_chunk} "
        f"({engines['chunked'].n_prefill_chunks} chunks), "
        f"block {base.block_size}, Q={Q}",
        file=sys.stderr,
    )

    rng = np.random.default_rng(0)

    def measure(name, seed):
        engine = engines[name]
        shared = name.endswith("_shared")
        prng = np.random.default_rng(seed)
        prefix = (
            prng.integers(100, vocab_hi, Q // 2).astype(np.int32)
            if shared
            else None
        )
        ids, mask = make_prompts(prng, N, Q, vocab_hi, prefix)
        pool = (
            PrefixBlockPool(pool_blocks, engine.block_size, engine.n_blocks)
            if shared
            else None
        )
        trainer.rng = jax.random.PRNGKey(seed)
        trainer.reset_rollout_phase()
        engine.start_phase(
            trainer.rollout_params(), trainer.rollout_phase_key()
        )
        t0 = time.time()
        got = serve_rows(engine, ids, mask, pool)
        wall = time.time() - t0
        return wall, got, engine.stats

    # warm every compiled program, and pin CPU-tier bitwise parity on
    # the warming round (same seed per pair => same prompts + phase key)
    warm = {name: measure(name, 1234) for name in engines}
    for a, b in (("mono", "chunked"), ("mono_shared", "chunked_shared")):
        rows_a, rows_b = warm[a][1], warm[b][1]
        assert set(rows_a) == set(rows_b)
        for r in rows_a:
            np.testing.assert_array_equal(rows_a[r], rows_b[r])
    print("parity: chunked == monolithic tokens, sharing off AND on",
          file=sys.stderr)

    rounds = {name: [] for name in engines}
    order = list(engines)
    stats = {}
    for r in range(rounds_n):
        for name in order if r % 2 == 0 else reversed(order):
            wall, _, st = measure(name, 7 + r)
            rounds[name].append(wall)
            stats[name] = st
    med = {n: float(np.median(ts)) for n, ts in rounds.items()}
    for name, ts in rounds.items():
        print(
            f"{name}: median {med[name]*1e3:.1f} ms  "
            f"all {[round(x*1e3, 1) for x in ts]}",
            file=sys.stderr,
        )

    st_c, st_cs = stats["chunked"], stats["chunked_shared"]
    record = {
        "metric": (
            "chunked_prefill_serve_ms_cpu_tiny"
            if on_cpu
            else "chunked_prefill_serve_ms_B128_Q64_R48_gpt2s"
        ),
        **{f"{n}_ms": round(v * 1000, 1) for n, v in med.items()},
        "chunked_speedup": round(med["mono"] / med["chunked"], 3),
        "chunked_speedup_shared": round(
            med["mono_shared"] / med["chunked_shared"], 3
        ),
        "prefill_cols_skipped": int(st_c.prefill_cols_skipped),
        "prefill_flops_saved": float(st_c.prefill_flops_saved),
        "prefill_cols_skipped_shared": int(st_cs.prefill_cols_skipped),
        "prefill_flops_saved_shared": float(st_cs.prefill_flops_saved),
        "prefix_hit_rate_shared": round(st_cs.prefix_hit_rate, 4),
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(record))

    from trlx_tpu.utils.ab_record import record_latest

    record_latest(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "AB_CHUNKED_PREFILL.json"),
        record,
    )
    from trlx_tpu.telemetry.run_ledger import append_ab_manifest

    append_ab_manifest("ab_chunked_prefill", record)


if __name__ == "__main__":
    main()
