"""Sampler correctness: the compiled prefill+scan decode must agree with a
naive full-forward loop, and its emitted logprobs/values must exactly match
the training-time recompute slice (the PPO on/off-policy alignment the whole
method depends on)."""

import functools

import numpy as np
import pytest


@pytest.fixture(scope="module")
def tiny_policy():
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.gpt2 import GPT2Config
    from trlx_tpu.models.heads import CausalLMWithValueHead

    config = GPT2Config(
        vocab_size=97, n_positions=64, n_embd=32, n_layer=2, n_head=2, dtype="float32"
    )
    model = CausalLMWithValueHead(config)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    return config, model, params


def _make_sampler(config, model, Q, R, do_sample):
    from trlx_tpu.models.gpt2 import init_cache
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler

    gen = GenerationConfig(
        max_new_tokens=R,
        do_sample=do_sample,
        eos_token_id=96,
        pad_token_id=0,
        top_k=0,
    )

    def apply_fn(params, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None):
        return model.apply(
            {"params": params}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache, cache_index=cache_index,
        )

    return make_sampler(
        apply_fn, functools.partial(init_cache, config), gen, Q
    )


@pytest.mark.slow  # compile-heavy e2e: nightly tier (tier-1 870 s budget)
def test_greedy_matches_naive_loop(tiny_policy):
    import jax
    import jax.numpy as jnp

    config, model, params = tiny_policy
    Q, R, B = 7, 5, 3
    rng = np.random.default_rng(0)

    # left-padded prompts of varying length
    lens = [7, 4, 2]
    ids = np.zeros((B, Q), np.int32)
    mask = np.zeros((B, Q), np.int32)
    for i, L in enumerate(lens):
        ids[i, Q - L :] = rng.integers(1, 96, size=L)
        mask[i, Q - L :] = 1

    sampler = _make_sampler(config, model, Q, R, do_sample=False)
    out = sampler(params, jnp.asarray(ids), jnp.asarray(mask), jax.random.PRNGKey(1))

    # naive loop: full forward over growing sequence, argmax
    for b in range(B):
        seq = [int(x) for x in ids[b][mask[b].astype(bool)]]
        for t in range(R):
            full = jnp.asarray([seq])
            res = model.apply({"params": params}, full)
            nxt = int(jnp.argmax(res["logits"][0, -1]))
            expected_value = float(res["values"][0, -1])
            assert int(np.asarray(out.tokens)[b, t]) == nxt, (b, t)
            np.testing.assert_allclose(
                float(np.asarray(out.values)[b, t]), expected_value, atol=1e-4
            )
            seq.append(nxt)


def test_rollout_logprobs_match_training_recompute(tiny_policy):
    """Behavior logprobs/values emitted during decode == response-slice
    recompute on [query; response], the exact computation the PPO train step
    performs. Any drift here silently corrupts importance ratios."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.parallel.collectives import logprobs_from_logits

    config, model, params = tiny_policy
    Q, R, B = 6, 4, 4
    rng = np.random.default_rng(1)
    lens = [6, 5, 3, 1]
    ids = np.zeros((B, Q), np.int32)
    mask = np.zeros((B, Q), np.int32)
    for i, L in enumerate(lens):
        ids[i, Q - L :] = rng.integers(1, 96, size=L)
        mask[i, Q - L :] = 1

    sampler = _make_sampler(config, model, Q, R, do_sample=True)
    out = sampler(params, jnp.asarray(ids), jnp.asarray(mask), jax.random.PRNGKey(7))

    full_ids = jnp.concatenate([jnp.asarray(ids), out.tokens], axis=1)
    full_mask = jnp.concatenate([jnp.asarray(mask), out.response_mask], axis=1)
    res = model.apply({"params": params}, full_ids, attention_mask=full_mask)
    logits = res["logits"][:, Q - 1 : -1]
    recomputed_lp = logprobs_from_logits(logits, out.tokens)
    recomputed_v = res["values"][:, Q - 1 : -1]

    m = np.asarray(out.response_mask).astype(bool)
    np.testing.assert_allclose(
        np.asarray(out.logprobs)[m], np.asarray(recomputed_lp)[m], atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(out.values)[m], np.asarray(recomputed_v)[m], atol=1e-4
    )


def test_eos_finishes_sequences(tiny_policy):
    """After eos is sampled, tokens become pad and the mask zeroes out."""
    import jax
    import jax.numpy as jnp

    config, model, params = tiny_policy
    Q, R, B = 4, 6, 2
    ids = np.ones((B, Q), np.int32)
    mask = np.ones((B, Q), np.int32)

    from trlx_tpu.models.gpt2 import init_cache
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler

    # eos = the argmax token of an arbitrary step: force immediate finish by
    # making every token eos
    gen = GenerationConfig(
        max_new_tokens=R, do_sample=False, eos_token_id=-1, pad_token_id=0
    )

    def apply_fn(params, input_ids, **kw):
        return model.apply({"params": params}, input_ids, **kw)

    # run greedy once to find the first generated token, then rebuild with
    # that token as eos
    sampler = make_sampler(apply_fn, functools.partial(init_cache, config), gen, Q)
    out = sampler(params, jnp.asarray(ids), jnp.asarray(mask), jax.random.PRNGKey(0))
    first = int(np.asarray(out.tokens)[0, 0])

    gen2 = GenerationConfig(
        max_new_tokens=R, do_sample=False, eos_token_id=first, pad_token_id=0
    )
    sampler2 = make_sampler(apply_fn, functools.partial(init_cache, config), gen2, Q)
    out2 = sampler2(params, jnp.asarray(ids), jnp.asarray(mask), jax.random.PRNGKey(0))
    toks = np.asarray(out2.tokens)
    rmask = np.asarray(out2.response_mask)
    assert toks[0, 0] == first
    assert rmask[0, 0] == 1  # eos token itself is real
    assert (toks[0, 1:] == 0).all()  # pad after finish
    assert (rmask[0, 1:] == 0).all()


def _eos_biased_apply(model, eos_id, bias=8.0):
    """apply_fn wrapper that adds a large constant to the eos logit, so an
    unsuppressed sampler would finish nearly every sequence at step 0."""
    import jax.numpy as jnp

    def apply_fn(params, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None):
        out = dict(model.apply(
            {"params": params}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache, cache_index=cache_index,
        ))
        out["logits"] = out["logits"].at[..., eos_id].add(bias)
        return out

    return apply_fn


def test_min_new_tokens_suppresses_eos(tiny_policy):
    """With a heavily eos-biased model, min_new_tokens=k must keep every
    sequence alive through step k-1 and let eos through right after (HF
    MinLengthLogitsProcessor semantics)."""
    import functools

    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.gpt2 import init_cache
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler

    config, model, params = tiny_policy
    Q, R, B = 4, 6, 8
    gen = GenerationConfig(
        max_new_tokens=R, min_new_tokens=3, do_sample=True,
        eos_token_id=96, pad_token_id=0, top_k=0,
    )
    sampler = jax.jit(make_sampler(
        _eos_biased_apply(model, 96), functools.partial(init_cache, config),
        gen, Q,
    ))
    ids = jnp.ones((B, Q), jnp.int32)
    mask = jnp.ones((B, Q), jnp.int32)
    saw_eos_after = False
    for seed in range(4):
        toks = np.asarray(
            sampler(params, ids, mask, jax.random.PRNGKey(seed)).tokens
        )
        assert not (toks[:, :3] == 96).any()
        saw_eos_after |= bool((toks[:, 3:] == 96).any())
    # the bias makes eos overwhelmingly likely once suppression lifts —
    # proves suppression was load-bearing, not vacuous
    assert saw_eos_after


def test_min_length_counts_real_prompt_tokens(tiny_policy):
    """min_length is total (real prompt + generated) per sequence: a 1-token
    prompt with min_length=4 gets 3 suppressed steps; a 3-token prompt only
    1 (HF causal semantics, reference randomwalks `min_length: 2`)."""
    import functools

    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.gpt2 import init_cache
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler

    config, model, params = tiny_policy
    Q, R = 4, 6
    gen = GenerationConfig(
        max_new_tokens=R, min_length=4, do_sample=True,
        eos_token_id=96, pad_token_id=0, top_k=0,
    )
    sampler = jax.jit(make_sampler(
        _eos_biased_apply(model, 96), functools.partial(init_cache, config),
        gen, Q,
    ))
    ids = np.zeros((2, Q), np.int32)
    mask = np.zeros((2, Q), np.int32)
    ids[0, -1] = 5; mask[0, -1] = 1          # 1 real token
    ids[1, -3:] = [5, 6, 7]; mask[1, -3:] = 1  # 3 real tokens
    for seed in range(4):
        toks = np.asarray(
            sampler(params, jnp.asarray(ids), jnp.asarray(mask),
                    jax.random.PRNGKey(seed)).tokens
        )
        assert not (toks[0, :3] == 96).any()  # needs 3 generated
        assert not (toks[1, :1] == 96).any()  # needs 1 generated
        # row 1 is eos-biased and unsuppressed from step 1 on
        assert (toks[1, 1:] == 96).any()


def test_min_suppression_noop_without_eos(tiny_policy):
    """eos_token_id=None/-1 (a supported 'disabled' sentinel) must not mask
    the whole vocab when min_new_tokens is set."""
    import jax.numpy as jnp

    from trlx_tpu.ops.sampling import GenerationConfig, suppress_eos_before_min

    logits = jnp.zeros((2, 8))
    for eos in (None, -1):
        cfg = GenerationConfig(min_new_tokens=3, eos_token_id=eos)
        out = suppress_eos_before_min(logits, jnp.asarray(0), cfg, jnp.asarray(3))
        assert bool(jnp.isfinite(out).all())


def test_gen_config_accepts_reference_style_kwargs():
    """Reference YAMLs write `max_length` and float `top_k: 0.0`
    (configs/ppo_config.yml, ppo_gptj.yml) — from_dict must map/coerce
    instead of silently dropping."""
    from trlx_tpu.ops.sampling import GenerationConfig

    gc = GenerationConfig.from_dict(
        {"max_length": 48, "min_length": 48, "top_k": 0.0, "top_p": 1.0,
         "do_sample": True}
    )
    assert gc.max_new_tokens == 48
    assert gc.min_length == 48
    assert gc.top_k == 0 and isinstance(gc.top_k, int)
    # explicit max_new_tokens wins over max_length
    gc = GenerationConfig.from_dict({"max_length": 48, "max_new_tokens": 12})
    assert gc.max_new_tokens == 12


def test_max_length_caps_total_length_per_sequence(tiny_policy):
    """HF max_length counts prompt + generated for causal LMs: a 6-token
    prompt with max_length=8 gets 2 real response tokens, a 2-token prompt
    gets 6 (budget-limited), the rest is pad/mask-0."""
    import functools

    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.gpt2 import init_cache
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler

    config, model, params = tiny_policy
    Q, R = 6, 6
    gen = GenerationConfig(
        max_new_tokens=R, max_length=8, do_sample=True,
        eos_token_id=96, pad_token_id=0, top_k=0,
    )

    def apply_fn(params, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None):
        return model.apply(
            {"params": params}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache, cache_index=cache_index,
        )

    sampler = jax.jit(make_sampler(
        apply_fn, functools.partial(init_cache, config), gen, Q
    ))
    ids = np.zeros((2, Q), np.int32)
    mask = np.zeros((2, Q), np.int32)
    ids[0, -6:] = np.arange(1, 7); mask[0, -6:] = 1   # 6 real tokens
    ids[1, -2:] = [3, 4]; mask[1, -2:] = 1            # 2 real tokens
    out = sampler(params, jnp.asarray(ids), jnp.asarray(mask),
                  jax.random.PRNGKey(0))
    lens = np.asarray(out.response_mask).sum(axis=1)
    assert lens[0] <= 2, lens  # 6 + 2 = 8
    assert lens[1] <= 6, lens  # budget-limited (2 + 6 = 8)


def test_filter_logits_top_p_nucleus():
    """top-p keeps the smallest prefix of tokens (by prob) whose cumulative
    mass reaches p, always >= 1 token (HF TopPLogitsWarper semantics)."""
    import jax.numpy as jnp

    from trlx_tpu.ops.sampling import GenerationConfig, filter_logits

    # probs ~ [0.6439, 0.2369, 0.0871, 0.0321] for logits [3,2,1,0]
    logits = jnp.asarray([[3.0, 2.0, 1.0, 0.0]])
    out = np.asarray(
        filter_logits(logits, GenerationConfig(top_p=0.7, top_k=0))
    )[0]
    # 0.6439 < 0.7 -> token0 kept; adding token1 exceeds -> token1 kept
    # (cum - probs < p rule keeps the boundary token), rest masked
    assert np.isfinite(out[0]) and np.isfinite(out[1])
    assert np.isneginf(out[2]) and np.isneginf(out[3])

    # p smaller than the top prob still keeps >= 1 token
    out = np.asarray(
        filter_logits(logits, GenerationConfig(top_p=0.1, top_k=0))
    )[0]
    assert np.isfinite(out[0]) and np.isneginf(out[1:]).all()


def test_filter_logits_temperature_and_top_k():
    import jax.numpy as jnp

    from trlx_tpu.ops.sampling import GenerationConfig, filter_logits

    logits = jnp.asarray([[4.0, 3.0, 2.0, 1.0]])
    out = np.asarray(
        filter_logits(logits, GenerationConfig(temperature=2.0, top_k=0))
    )[0]
    np.testing.assert_allclose(out, [2.0, 1.5, 1.0, 0.5])
    out = np.asarray(filter_logits(logits, GenerationConfig(top_k=2)))[0]
    assert np.isfinite(out[:2]).all() and (out[2:] < -1e8).all()


def test_int8_kv_cache_matches_bf16_closely(tiny_policy):
    """The int8 rollout cache (absmax-per-token/head quantization,
    `ops/kv_cache.py::quantize_kv`) must produce decode logprobs close to
    the exact cache: same sampler, same rng, cache dtype the only delta.
    Quantization noise bounds the drift; the importance ratios in the PPO
    update absorb this (behavior logprobs stay self-consistent either
    way)."""
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.gpt2 import init_cache
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler

    config, model, params = tiny_policy
    q_config = dataclasses.replace(config, kv_cache_dtype="int8")
    Q, R, B = 6, 5, 4
    rng = np.random.default_rng(3)
    ids = np.zeros((B, Q), np.int32)
    mask = np.zeros((B, Q), np.int32)
    for i, L in enumerate([6, 5, 3, 2]):
        ids[i, Q - L :] = rng.integers(1, 96, size=L)
        mask[i, Q - L :] = 1

    def apply_fn(params, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None):
        return model.apply(
            {"params": params}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache, cache_index=cache_index,
        )

    gen = GenerationConfig(
        max_new_tokens=R, do_sample=False, eos_token_id=96, pad_token_id=0,
        top_k=0,
    )
    outs = {}
    for name, cfg in [("bf16", config), ("int8", q_config)]:
        sampler = make_sampler(
            apply_fn, functools.partial(init_cache, cfg), gen, Q
        )
        outs[name] = sampler(
            params, jnp.asarray(ids), jnp.asarray(mask), jax.random.PRNGKey(1)
        )
    # int8 cache buffers really are int8
    cache = init_cache(q_config, B, Q + R)
    assert cache[0]["k"].dtype == jnp.int8 and "k_scale" in cache[0]
    # greedy tokens agree and behavior logprobs drift only by quantization
    np.testing.assert_array_equal(
        np.asarray(outs["bf16"].tokens), np.asarray(outs["int8"].tokens)
    )
    m = np.asarray(outs["bf16"].response_mask).astype(bool)
    np.testing.assert_allclose(
        np.asarray(outs["bf16"].logprobs)[m],
        np.asarray(outs["int8"].logprobs)[m],
        atol=0.05,
    )


def test_int8_cache_extends_to_all_causal_families():
    """`kv_cache_dtype="int8"` plumbs through every causal family's cache
    initializer (the write path is shared: `ops/kv_cache.py`);
    unknown values fail loudly."""
    import jax.numpy as jnp
    import pytest as _pytest

    from trlx_tpu.models.gpt_neo import GPTNeoConfig, init_gpt_neo_cache
    from trlx_tpu.models.gptj import GPTJConfig, init_gptj_cache
    from trlx_tpu.models.neox import NeoXConfig, init_neox_cache

    cases = [
        (init_gptj_cache, GPTJConfig(
            vocab_size=32, n_positions=16, n_embd=32, n_layer=2, n_head=2,
            rotary_dim=8, kv_cache_dtype="int8")),
        (init_gpt_neo_cache, GPTNeoConfig(
            vocab_size=32, max_position_embeddings=16, hidden_size=32,
            num_layers=2, num_heads=2, kv_cache_dtype="int8")),
        (init_neox_cache, NeoXConfig(
            vocab_size=32, max_position_embeddings=16, hidden_size=32,
            num_hidden_layers=2, num_attention_heads=2,
            kv_cache_dtype="int8")),
    ]
    for init, cfg in cases:
        cache = init(cfg, 4, 8)
        assert cache[0]["k"].dtype == jnp.int8, type(cfg).__name__
        assert cache[0]["k_scale"].shape == (4, 8, 2, 1), type(cfg).__name__
    from dataclasses import replace

    with _pytest.raises(ValueError, match="kv_cache_dtype"):
        init_gptj_cache(replace(cases[0][1], kv_cache_dtype="fp8"), 4, 8)


def _make_counting_sampler(config, model, Q, R, eos=96, max_length=0,
                           steps=None):
    """Sampler whose decode forwards (one token a call) are counted into
    ``steps[0]`` through a host callback, when a list is given."""
    import jax

    from trlx_tpu.models.gpt2 import init_cache
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler

    gen = GenerationConfig(
        max_new_tokens=R,
        do_sample=True,
        eos_token_id=eos,
        pad_token_id=0,
        top_k=0,
        max_length=max_length,
    )

    def count():
        steps[0] += 1

    def apply_fn(params, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None):
        if steps is not None and input_ids.shape[1] == 1:
            jax.debug.callback(count)
        return model.apply(
            {"params": params}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache, cache_index=cache_index,
        )

    return make_sampler(
        apply_fn, functools.partial(init_cache, config), gen, Q
    )


def test_early_exit_decode_bitwise_matches_full_run(tiny_policy, monkeypatch):
    """The decode loop stops once every row has finished, and its outputs
    are BITWISE what the full R-step run gives — tokens, masks, behavior
    logprobs, and values (finished rows emit constants; the outputs are
    pre-filled with them). max_length forces every row to finish early
    DETERMINISTICALLY (row i after max_length - n_real_i tokens), so the
    exit is guaranteed on the line. The full run is the same loop with the
    all-finished term taken out of its predicate."""
    import jax
    import jax.numpy as jnp

    config, model, params = tiny_policy
    Q, R, B = 4, 8, 4
    rng = np.random.default_rng(2)
    ids = np.zeros((B, Q), np.int32)
    mask = np.zeros((B, Q), np.int32)
    for i, L in enumerate([4, 3, 2, 1]):
        ids[i, Q - L:] = rng.integers(1, 96, size=L)
        mask[i, Q - L:] = 1

    # max_length=6: rows finish at t = 6 - n_real - 1 = [1, 2, 3, 4];
    # all finished from t=5 on -> the loop runs steps 0..4 and stops
    full_steps, steps = [0], [0]
    full = jax.jit(_make_counting_sampler(
        config, model, Q, R, max_length=6, steps=full_steps))
    early = jax.jit(_make_counting_sampler(
        config, model, Q, R, max_length=6, steps=steps))
    key = jax.random.PRNGKey(0)
    with monkeypatch.context() as m:
        real = jax.lax.while_loop
        # traced here, under the patch: carry[0] is the step counter t
        m.setattr(
            jax.lax, "while_loop",
            lambda cond, body, init: real(lambda c: c[0] < R, body, init),
        )
        full(params, jnp.asarray(ids), jnp.asarray(mask), key)
    for seed in range(2):
        key = jax.random.PRNGKey(seed)
        full_steps[0] = steps[0] = 0
        a = full(params, jnp.asarray(ids), jnp.asarray(mask), key)
        b = early(params, jnp.asarray(ids), jnp.asarray(mask), key)
        jax.block_until_ready((a, b))
        jax.effects_barrier()
        for name in ("tokens", "response_mask", "logprobs", "values"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, name)),
                np.asarray(getattr(b, name)),
                err_msg=f"{name} (seed {seed})",
            )
        lengths = np.asarray(a.response_mask).sum(axis=1)
        # max_length caps row i at 6 - n_real_i live tokens (a
        # sampled eos may finish a row even earlier)
        assert (lengths <= np.array([2, 3, 4, 5])).all(), lengths
        # the tail past t=5 is all-finished: never run, pre-filled
        assert (np.asarray(b.tokens)[:, 5:] == 0).all()
        assert (np.asarray(b.response_mask)[:, 5:] == 0).all()
        # the loop stops: the step that finishes the last row is the last
        # one run (its forward still runs), the full run takes all R
        assert full_steps[0] == R, full_steps
        assert steps[0] == lengths.max() <= 5, (steps, lengths)


def test_finished_rows_emit_deterministic_zeros(tiny_policy):
    """Post-finish slots emit logprob 0.0 and value 0.0 (mask is 0 there;
    training consumes neither) — the invariant that makes the early exit
    exact and keeps masked slots independent of post-eos
    logits."""
    import jax
    import jax.numpy as jnp

    config, model, params = tiny_policy
    Q, R, B = 4, 8, 8
    sampler = jax.jit(_make_counting_sampler(config, model, Q, R, eos=3))
    ids = jnp.asarray(
        np.random.default_rng(0).integers(1, 96, size=(B, Q)), jnp.int32
    )
    mask = jnp.ones((B, Q), jnp.int32)
    out = sampler(params, ids, mask, jax.random.PRNGKey(1))
    m = np.asarray(out.response_mask).astype(bool)
    assert not m.all(), "need at least one finished row for the assertion"
    assert (np.asarray(out.logprobs)[~m] == 0.0).all()
    assert (np.asarray(out.values)[~m] == 0.0).all()
    assert (np.asarray(out.tokens)[~m] == 0).all()  # pad_token_id
