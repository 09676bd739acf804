"""The fixed sampler's decode loop carries its cache layer-major: one array
a kind for all layers (``ops/kv_cache.py::decode_kv_layout`` of a tuple),
which each layer writes in place and reads its slice of
(``layer_cache`` / ``with_layer_cache``, ``ops/attention.py::_decode_read``).

The reference is the loop the sampler carried before (PERF.md §6, PR 50): a
tuple of per-layer folded dicts, each read by the same function with no
layer index. It is rebuilt here by handing the sampler that layout, so both
programs run the same arithmetic and have to agree bit for bit on any
machine."""

import functools

import numpy as np
import pytest

B, Q, R = 4, 8, 6


def _family(name, kv_cache_dtype):
    """(config, backbone class, init_cache) of a family at a tiny width."""
    common = dict(dtype="bfloat16", param_dtype="float32", kv_cache_dtype=kv_cache_dtype)
    if name == "gpt2":
        from trlx_tpu.models.gpt2 import GPT2Config, GPT2Model, init_cache

        cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=32, n_layer=3, n_head=4, **common)
        return cfg, GPT2Model, init_cache
    if name == "neox":
        from trlx_tpu.models.neox import NeoXConfig, NeoXModel, init_neox_cache

        cfg = NeoXConfig(vocab_size=64, max_position_embeddings=32, hidden_size=32,
                         num_hidden_layers=3, num_attention_heads=4, **common)
        return cfg, NeoXModel, init_neox_cache
    if name == "gptj":
        from trlx_tpu.models.gptj import GPTJConfig, GPTJModel, init_gptj_cache

        cfg = GPTJConfig(vocab_size=64, n_positions=32, n_embd=32, n_layer=2, n_head=4,
                         rotary_dim=4, **common)
        return cfg, GPTJModel, init_gptj_cache
    if name == "gpt_neo":
        from trlx_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel, init_gpt_neo_cache

        cfg = GPTNeoConfig(vocab_size=64, max_position_embeddings=32, hidden_size=32,
                           num_layers=2, num_heads=4, window_size=4,
                           attention_layers=("global", "local"), **common)
        return cfg, GPTNeoModel, init_gpt_neo_cache
    from trlx_tpu.models.olmoe import OlmoeConfig, OlmoeModel, init_olmoe_cache

    cfg = OlmoeConfig(vocab_size=64, max_position_embeddings=32, hidden_size=32,
                      num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
                      intermediate_size=16, num_experts=4, num_experts_per_tok=2, **common)
    return cfg, OlmoeModel, init_olmoe_cache


def _policy(name, kv_cache_dtype):
    """(apply_fn, init_cache_fn, params, number of layers) of a tiny policy
    with a value head."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.heads import CausalLMWithValueHead
    from trlx_tpu.models.registry import num_layers_of

    cfg, backbone, init_cache = _family(name, kv_cache_dtype)
    model = CausalLMWithValueHead(cfg, backbone_cls=backbone)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, Q), jnp.int32))["params"]

    def apply_fn(params, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None, last_only=False):
        return model.apply(
            {"params": params}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache, cache_index=cache_index,
            last_only=last_only,
        )

    return apply_fn, functools.partial(init_cache, cfg), params, num_layers_of(cfg)


def _prompts():
    import jax
    import jax.numpy as jnp

    ids = jax.random.randint(jax.random.PRNGKey(1), (B, Q), 0, 60)
    mask = jnp.ones((B, Q), jnp.int32).at[0, :3].set(0).at[2, :5].set(0)
    return ids, mask


def _per_layer(cache):
    """The layout the loop carried before: each layer folded on its own."""
    from trlx_tpu.ops.kv_cache import decode_kv_layout

    if isinstance(cache, dict):
        return decode_kv_layout(cache)
    return tuple(decode_kv_layout(layer) for layer in cache)


CASES = [
    (family, kv, sampled)
    for family in ("gpt2", "neox")
    for kv in ("int8", "bfloat16")
    for sampled in (False, True)
] + [("gptj", "int8", True), ("gpt_neo", "bfloat16", True), ("olmoe", "int8", False)]


@pytest.mark.parametrize(
    "family,kv,sampled", CASES,
    ids=[f"{f}-{kv}-{'sampled' if s else 'greedy'}" for f, kv, s in CASES],
)
def test_the_carry_samples_what_the_per_layer_loop_sampled(monkeypatch, family, kv, sampled):
    import jax

    from trlx_tpu.ops import sampling
    from trlx_tpu.telemetry import get_metrics

    apply_fn, init_cache_fn, params, n_layer = _policy(family, kv)
    gen = sampling.GenerationConfig(
        max_new_tokens=R, do_sample=sampled, top_k=0, eos_token_id=63, pad_token_id=63,
    )
    ids, mask = _prompts()
    args = (params, ids, mask, jax.random.PRNGKey(2))
    kinds = 4 if kv == "int8" else 2
    gauge = get_metrics().gauge("sampler/carry_buffers")

    got = jax.jit(sampling.make_sampler(apply_fn, init_cache_fn, gen, Q))(*args)
    assert gauge.value == kinds
    monkeypatch.setattr(sampling, "decode_kv_layout", _per_layer)
    want = jax.jit(sampling.make_sampler(apply_fn, init_cache_fn, gen, Q))(*args)
    assert gauge.value == kinds * n_layer

    assert np.asarray(got.response_mask).sum() > 0
    for name in ("tokens", "response_mask", "logprobs", "values"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)), err_msg=name
        )


@pytest.mark.parametrize("kv", ["int8", "bfloat16"])
@pytest.mark.parametrize("family", ["gpt2", "neox"])
def test_the_carry_that_leaves_a_step_is_the_layers_stacked(family, kv):
    """Prefill, then three decode steps through the model on the carry and
    on the per-layer folded tuple: logits and values equal at every step,
    and the carry equals the per-layer caches stacked, key for key, bit
    for bit; it leaves the model a plain dict of arrays, as it came."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.ops.kv_cache import decode_kv_layout

    apply_fn, init_cache_fn, params, n_layer = _policy(family, kv)
    ids, mask = _prompts()
    cap = Q + R
    cache_mask = jnp.concatenate([mask, jnp.ones((B, R), jnp.int32)], axis=1)
    positions = jnp.clip(jnp.cumsum(mask, axis=-1) - 1, 0, None)
    filled = jax.jit(functools.partial(apply_fn, cache_index=0))(
        params, ids, cache_mask, positions, init_cache_fn(B, cap)
    )["cache"]
    carry, layers = decode_kv_layout(filled), _per_layer(filled)
    assert isinstance(carry, dict) and len(layers) == n_layer
    step = jax.jit(apply_fn)
    n_real = jnp.sum(mask, axis=-1)
    for t in range(3):
        token = jnp.full((B, 1), 7 + t, jnp.int32)
        step_mask = cache_mask * (jnp.arange(cap)[None, :] <= Q + t)
        a = step(params, token, step_mask, (n_real + t)[:, None], carry, jnp.int32(Q + t))
        b = step(params, token, step_mask, (n_real + t)[:, None], layers, jnp.int32(Q + t))
        carry, layers = a["cache"], b["cache"]
        for name in ("logits", "values"):
            np.testing.assert_array_equal(
                np.asarray(a[name], np.float32), np.asarray(b[name], np.float32), err_msg=name
            )
        assert sorted(carry) == sorted(layers[0])
        for name, stacked in carry.items():
            assert stacked.shape == (n_layer,) + layers[0][name].shape
            np.testing.assert_array_equal(
                np.asarray(stacked, np.float32),
                np.stack([np.asarray(layer[name], np.float32) for layer in layers]),
                err_msg=f"{name} after step {t}",
            )


def test_a_batch_sharded_tuple_cache_pins_its_carry_behind_the_layers():
    """``cache_sharding`` is a layer's (``[B, C, H, Dh]``: here the batch
    over four devices); the carry leads with the layers, so the sampler
    pins it one axis further in, and samples the unpinned one's tokens."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from trlx_tpu.ops import sampling

    apply_fn, init_cache_fn, params, _ = _policy("gpt2", "int8")
    gen = sampling.GenerationConfig(
        max_new_tokens=R, do_sample=True, top_k=0, eos_token_id=63, pad_token_id=63,
    )
    ids, mask = _prompts()
    args = (params, ids, mask, jax.random.PRNGKey(2))
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    pinned = sampling.make_sampler(
        apply_fn, init_cache_fn, gen, Q,
        cache_sharding=NamedSharding(mesh, PartitionSpec("dp")),
    )
    got, want = jax.jit(pinned)(*args), jax.jit(sampling.make_sampler(apply_fn, init_cache_fn, gen, Q))(*args)
    for name in ("tokens", "response_mask"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)), err_msg=name
        )
    # a program partitioned over four devices sums in another order
    for name in ("logprobs", "values"):
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)), atol=1e-5, err_msg=name
        )
