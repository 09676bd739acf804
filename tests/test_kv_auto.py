"""`kv_cache_dtype: "auto"` + the int8 long-context guardrail: the int8
read is measured on the chip up to capacity 512 (`ppo-gpt2m-longgen`) and
not beyond — no config may silently take an unmeasured read. "auto"
resolves per cache capacity; an explicit "int8" past the measured capacity
warns loudly."""

import os
import sys
import warnings

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_resolve_auto_by_capacity():
    from trlx_tpu.ops.kv_cache import (
        INT8_KV_MAX_CAPACITY, resolve_kv_cache_dtype,
    )

    assert resolve_kv_cache_dtype("auto", 112) == "int8"
    assert resolve_kv_cache_dtype("auto", INT8_KV_MAX_CAPACITY) == "int8"
    assert resolve_kv_cache_dtype("auto", INT8_KV_MAX_CAPACITY + 1) == "bfloat16"
    assert resolve_kv_cache_dtype("auto", 2048) == "bfloat16"


def test_explicit_int8_past_crossover_warns():
    from trlx_tpu.ops.kv_cache import resolve_kv_cache_dtype

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert resolve_kv_cache_dtype("int8", 2048) == "int8"  # honored
    said = [str(w.message) for w in caught]
    assert any(
        "measured only up to capacity 512" in m and "ppo-gpt2m-longgen" in m
        and "paged engine's int8 read still gathers" in m
        for m in said
    )
    assert not any("LONGCTX" in m or "0.4.36" in m for m in said)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        resolve_kv_cache_dtype("int8", 112)
        resolve_kv_cache_dtype("bfloat16", 2048)
    assert not caught


@pytest.mark.parametrize("family", ["gpt2", "gptj", "gpt_neox", "gpt_neo"])
def test_auto_buffers_per_family(family):
    """Every causal family accepts "auto" and allocates the resolved layout
    through the shared kv_buffers path."""
    from trlx_tpu.models.registry import get_model_family

    fam = get_model_family(family)
    tiny = {
        "gpt2": dict(vocab_size=32, n_positions=4096, n_embd=16, n_layer=2,
                     n_head=2),
        "gptj": dict(vocab_size=32, n_positions=4096, n_embd=16, n_layer=2,
                     n_head=2, rotary_dim=4),
        "gpt_neox": dict(vocab_size=32, max_position_embeddings=4096,
                         hidden_size=16, num_hidden_layers=2,
                         num_attention_heads=2),
        "gpt_neo": dict(vocab_size=32, max_position_embeddings=4096,
                        hidden_size=16, num_layers=2, num_heads=2,
                        attention_types=[[["global", "local"], 1]],
                        window_size=8),
    }[family]
    arch = fam.config_cls.from_dict({**tiny, "kv_cache_dtype": "auto"})
    short = fam.init_cache(arch, batch_size=2, capacity=64)
    long = fam.init_cache(arch, batch_size=2, capacity=2048)
    assert "k_scale" in short[0], family  # int8 layout below the crossover
    assert "k_scale" not in long[0], family  # bf16 beyond it


def test_pp_stage_cache_resolves_auto():
    from trlx_tpu.models.gpt2 import GPT2Config
    from trlx_tpu.models.pp_runner import pp_init_cache

    arch = GPT2Config.from_dict(
        dict(vocab_size=32, n_positions=4096, n_embd=16, n_layer=2, n_head=2,
             kv_cache_dtype="auto")
    )
    assert "k_scale" in pp_init_cache(arch, 2, 64)
    assert "k_scale" not in pp_init_cache(arch, 2, 2048)


def test_sampler_runs_with_auto(tmp_path):
    """End-to-end: a tiny PPO sampler under kv_cache_dtype "auto" decodes
    and trains normally (the resolved int8 layout at rollout capacity)."""
    os.environ["WANDB_DISABLED"] = "1"
    import numpy as np

    import trlx_tpu
    from trlx_tpu.data.configs import TRLConfig

    config = TRLConfig.from_dict(
        {
            "model": {
                "model_type": "gpt2",
                "model_arch": {
                    "vocab_size": 32, "n_positions": 32, "n_embd": 16,
                    "n_layer": 2, "n_head": 2, "kv_cache_dtype": "auto",
                },
            },
            "train": {
                "seq_length": 8, "batch_size": 8, "epochs": 1,
                "total_steps": 2, "eval_interval": 1000,
                "checkpoint_interval": 100000,
                "mesh": {"dp": -1, "fsdp": 1, "tp": 1}, "dtype": "float32",
            },
            "method": {
                "name": "PPOConfig", "num_rollouts": 16, "chunk_size": 16,
                "ppo_epochs": 1,
                "gen_kwargs": {"max_new_tokens": 4, "min_new_tokens": 4,
                               "do_sample": True, "eos_token_id": 30,
                               "pad_token_id": 31},
            },
        }
    )
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, 28, size=3)) for _ in range(16)]
    trainer = trlx_tpu.train(
        reward_fn=lambda samples, queries, response_gt=None: [
            float(len(set(s))) for s in samples
        ],
        prompts=prompts,
        config=config,
    )
    assert int(trainer.state.step) >= 2
