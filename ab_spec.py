"""A/B: trie-drafted speculative decoding in the continuous engine,
sharing off and on.

Supersedes the old stage-1 projection (acceptance probe + break-even
table): the drafted ``verify_step`` is now implemented
(docs/inference.md "Speculative decoding"), so this measures the real
thing. Four variants run the SAME serving-style pump loop:
{spec off, spec on} x {sharing off, sharing on}. Spec-off decodes one
token per jitted step; spec-on proposes up to ``max_draft`` host-drafted
tokens per slot (n-gram self-lookup; with sharing on, the shared-prefix
trie's ready chains as a global corpus) and verifies them in one batched
pass — accepted tokens are bitwise the tokens the one-token loop would
have sampled (the per-row RNG contract), which the warming round pins.

Methodology per the repo's measurement discipline: variants interleave
across rounds (wall-clock swings with machine load — A/B by alternation,
never against recorded numbers), and the CPU tier auto-shrinks the
model. The CPU record verifies bitwise parity + a nonzero accept-rate
with tokens-per-verify > 1; the headline wall delta is a TPU
measurement (direction 5a — this script self-records it on first
hardware run). Low temperature makes the workload draftable: near-greedy
decode on cyclic prompts falls into loops the n-gram drafter locks onto,
which is the regime speculation targets (templated/repetitive spans).

Self-recording: updates ``AB_SPEC.json`` (latest record per metric +
device kind, ``utils/ab_record.py``) and appends a run-ledger manifest
(``telemetry/run_ledger.py``).

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

MAX_DRAFT = 4


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
    R = 16 if on_cpu else 48
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
                    # near-greedy: random-init decode loops, so the
                    # drafter has something to accept (see docstring)
                    "temperature": 0.05,
                    "per_row_rng": True,
                    "eos_token_id": 511 if on_cpu else 50256,
                    "pad_token_id": 511 if on_cpu else 50256,
                },
            },
        }
    )
    return get_trainer(config.train.trainer)(
        config, reward_fn=lambda **kw: [0.0]
    )


def build_engines(trainer, spec_draft, pool_blocks):
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
        spec_max_draft=spec_draft,
    )


def make_prompts(rng, n, Q, shared_prefix):
    """[n, Q] ids/mask: cyclic two-token motifs (every suffix recurs, so
    the n-gram drafter has a match the moment decode starts looping).
    ``shared_prefix`` overwrites the leading half so the trie publishes
    common chains (the sharing-on workload); prompt identity between a
    spec/no-spec pair comes from the shared seed."""
    ids = np.zeros((n, Q), np.int32)
    for i in range(n):
        a = 3 + int(rng.integers(0, 4))
        b = 9 + int(rng.integers(0, 4))
        ids[i] = np.tile([a, b], (Q + 1) // 2)[:Q]
    mask = np.ones((n, Q), np.int32)
    if shared_prefix is not None:
        ids[:, : len(shared_prefix)] = shared_prefix
    return ids, mask


def serve_rows(engine, ids, mask, pool=None):
    """Serving-style pump loop: plan-just-in-time admission in
    admit_width waves, pump to completion. Returns {row: tokens} host
    arrays. Pool refcounts are deliberately not released (the run ends;
    the pool is sized to never fill)."""
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
    from trlx_tpu.serving.spec_drafter import NGramDrafter, TrieDrafter

    on_cpu = jax.default_backend() == "cpu"
    trainer = build_trainer()
    base = trainer.rollout_engine_obj
    Q = base.Q
    pool_blocks = 64
    N = 32 if on_cpu else 128
    rounds_n = 2 if on_cpu else 6

    engines = {
        "base": build_engines(trainer, 0, 0),
        "spec": build_engines(trainer, MAX_DRAFT, 0),
        "base_shared": build_engines(trainer, 0, pool_blocks),
        "spec_shared": build_engines(trainer, MAX_DRAFT, pool_blocks),
    }
    print(
        f"max_draft {engines['spec'].spec_max_draft}, "
        f"block {base.block_size}, Q={Q}, R={base.R}",
        file=sys.stderr,
    )

    def measure(name, seed):
        engine = engines[name]
        shared = name.endswith("_shared")
        prng = np.random.default_rng(seed)
        prefix = (
            prng.integers(100, 500 if on_cpu else 40000, Q // 2)
            .astype(np.int32)
            if shared
            else None
        )
        ids, mask = make_prompts(prng, N, Q, prefix)
        pool = (
            PrefixBlockPool(pool_blocks, engine.block_size, engine.n_blocks)
            if shared
            else None
        )
        if engine.spec_max_draft:
            # fresh drafter per run: histories must not leak across
            # rounds (row ids restart each phase)
            engine.spec_drafter = (
                TrieDrafter(pool=pool, max_draft=engine.spec_max_draft)
                if pool is not None
                else NGramDrafter(max_draft=engine.spec_max_draft)
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
    # the warming round (same seed per pair => same prompts + phase key;
    # accepted tokens must be the tokens the one-token loop sampled)
    warm = {name: measure(name, 1234) for name in engines}
    for a, b in (("base", "spec"), ("base_shared", "spec_shared")):
        rows_a, rows_b = warm[a][1], warm[b][1]
        assert set(rows_a) == set(rows_b)
        for r in rows_a:
            np.testing.assert_array_equal(rows_a[r], rows_b[r])
    print("parity: spec == one-token-loop tokens, sharing off AND on",
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

    st_s, st_ss = stats["spec"], stats["spec_shared"]
    record = {
        "metric": (
            "spec_decode_serve_ms_cpu_tiny"
            if on_cpu
            else "spec_decode_serve_ms_B128_Q64_R48_gpt2s"
        ),
        **{f"{n}_ms": round(v * 1000, 1) for n, v in med.items()},
        "spec_speedup": round(med["base"] / med["spec"], 3),
        "spec_speedup_shared": round(
            med["base_shared"] / med["spec_shared"], 3
        ),
        "max_draft": MAX_DRAFT,
        "accept_rate": round(st_s.spec_accept_rate, 4),
        "tokens_per_verify": round(st_s.spec_tokens_per_step, 4),
        "accept_rate_shared": round(st_ss.spec_accept_rate, 4),
        "tokens_per_verify_shared": round(st_ss.spec_tokens_per_step, 4),
        "verify_steps": int(st_s.spec_steps),
        "verify_steps_shared": int(st_ss.spec_steps),
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(record))

    assert record["accept_rate"] > 0, "CPU round must accept something"
    assert record["tokens_per_verify"] > 1, (
        "verify must average more than one committed token per step"
    )

    from trlx_tpu.utils.ab_record import record_latest

    record_latest(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "AB_SPEC.json"),
        record,
    )
    from trlx_tpu.telemetry.run_ledger import append_ab_manifest

    append_ab_manifest("ab_spec", record)


if __name__ == "__main__":
    main()
