"""The fixed sampler promises its decode read what no layer can see: the
prefill fills ``[0, Q)``, step ``t`` writes ``Q + t`` and nothing past it
holds anything (``ops/kv_cache.py::written_to_index``), so the read takes
the narrowest of a few static widths that holds the written part
(``decode_read_widths``, ``ops/attention.py::_decode_read``).

The reference is the same sampler with the promise taken off: every step
reads the whole capacity, as every step did before. The dropped positions
carry exactly-zero weights there, so the two differ by float32 summation
order alone."""

import functools

import numpy as np
import pytest

# a prompt window and an answer whose capacity takes two widths (128, 160):
# steps 0..27 read 128 positions, steps 28..59 all 160
B, Q, R = 4, 100, 60


def _policy(family, kv_cache_dtype, dtype="float32"):
    """(apply_fn, init_cache_fn, params) of a tiny policy with a value head."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.heads import CausalLMWithValueHead

    common = dict(dtype=dtype, param_dtype="float32", kv_cache_dtype=kv_cache_dtype)
    if family == "gpt2":
        from trlx_tpu.models.gpt2 import GPT2Config, GPT2Model, init_cache

        cfg = GPT2Config(vocab_size=64, n_positions=192, n_embd=32, n_layer=2, n_head=4, **common)
        backbone = GPT2Model
    else:
        from trlx_tpu.models.neox import NeoXConfig, NeoXModel, init_neox_cache as init_cache

        cfg = NeoXConfig(vocab_size=64, max_position_embeddings=192, hidden_size=32,
                         num_hidden_layers=2, num_attention_heads=4, **common)
        backbone = NeoXModel
    model = CausalLMWithValueHead(cfg, backbone_cls=backbone)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    def apply_fn(params, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None, last_only=False):
        return model.apply(
            {"params": params}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache, cache_index=cache_index,
            last_only=last_only,
        )

    return apply_fn, functools.partial(init_cache, cfg), params


def _prompts():
    """Left-padded prompts: rows 0 and 2 hold 70 and 5 real tokens."""
    import jax
    import jax.numpy as jnp

    ids = jax.random.randint(jax.random.PRNGKey(1), (B, Q), 0, 60)
    mask = jnp.ones((B, Q), jnp.int32).at[0, :30].set(0).at[2, :95].set(0)
    return ids, mask


def _read_width_counts(widths):
    from trlx_tpu.telemetry import get_metrics

    return {
        w: get_metrics().counter("attention/decode_read_width{width=%d}" % w).value
        for w in widths
    }


def _without_promise(monkeypatch):
    from trlx_tpu.ops import sampling

    monkeypatch.setattr(sampling, "written_to_index", lambda cache, first_index: cache)


CASES = [
    (family, kv, sampled, carry)
    for family in ("gpt2", "neox")
    for kv in ("int8", "bfloat16")
    for sampled in (False, True)
    for carry in (True, False)
]


@pytest.mark.parametrize(
    "family,kv,sampled,carry", CASES,
    ids=[f"{f}-{kv}-{'sampled' if s else 'greedy'}-{'carry' if c else 'layers'}"
         for f, kv, s, c in CASES],
)
def test_the_promised_sampler_samples_what_the_whole_read_sampled(monkeypatch, family, kv, sampled, carry):
    """Greedy and sampled, the layer-major carry and a tuple of folded
    layers (what layers too large to stage are carried as): the same tokens
    and masks, log-probs and values to float32 summation order; the
    counters say which widths were traced."""
    import jax

    from trlx_tpu.ops import kv_cache, sampling
    from trlx_tpu.telemetry import get_metrics

    if not carry:
        monkeypatch.setattr(kv_cache, "STAGED_LAYER_BYTES", 0)
    apply_fn, init_cache_fn, params = _policy(family, kv)
    gen = sampling.GenerationConfig(
        max_new_tokens=R, do_sample=sampled, top_k=0, eos_token_id=63, pad_token_id=63,
    )
    ids, mask = _prompts()
    args = (params, ids, mask, jax.random.PRNGKey(2))
    share = get_metrics().gauge("sampler/read_share")
    leaves = get_metrics().gauge("sampler/carry_buffers")

    before = _read_width_counts((128, 160))
    got = jax.jit(sampling.make_sampler(apply_fn, init_cache_fn, gen, Q))(*args)
    # one traced read site a layer and width
    assert _read_width_counts((128, 160)) == {w: n + 2 for w, n in before.items()}
    assert share.value == pytest.approx((28 * 128 + 32 * 160) / (60 * 160))
    # the loop carries arrays alone: the promise is no leaf of it
    assert leaves.value == (4 if kv == "int8" else 2) * (1 if carry else 2)

    _without_promise(monkeypatch)
    want = jax.jit(sampling.make_sampler(apply_fn, init_cache_fn, gen, Q))(*args)
    assert _read_width_counts((128, 160)) == {128: before[128] + 2, 160: before[160] + 4}

    assert np.asarray(got.response_mask).sum() > B * 30  # past the boundary
    for name in ("tokens", "response_mask"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)), err_msg=name
        )
    for name in ("logprobs", "values"):
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
            atol=2e-5, err_msg=name,
        )


def test_the_promised_sampler_in_bfloat16_stays_within_rounding(monkeypatch):
    """The compute dtype of the PPO cells: weights are rounded to bfloat16
    where they meet V in both reads, so one differing float32 sum can move
    a value by a bfloat16 step; log-probs agree to that."""
    import jax

    from trlx_tpu.ops import sampling

    apply_fn, init_cache_fn, params = _policy("gpt2", "int8", dtype="bfloat16")
    gen = sampling.GenerationConfig(
        max_new_tokens=R, do_sample=False, eos_token_id=63, pad_token_id=63, min_new_tokens=R,
    )
    ids, mask = _prompts()
    args = (params, ids, mask, jax.random.PRNGKey(2))
    got = jax.jit(sampling.make_sampler(apply_fn, init_cache_fn, gen, Q))(*args)
    _without_promise(monkeypatch)
    want = jax.jit(sampling.make_sampler(apply_fn, init_cache_fn, gen, Q))(*args)
    np.testing.assert_array_equal(np.asarray(got.response_mask), np.asarray(want.response_mask))
    same = np.asarray(got.tokens) == np.asarray(want.tokens)
    # greedy over 64 near-level logits of a seeded model: a tie may turn
    assert same.mean() > 0.9
    first = np.where(same.all(axis=0), R, 0).argmin() if not same.all() else R
    np.testing.assert_allclose(
        np.asarray(got.logprobs)[:, :first], np.asarray(want.logprobs)[:, :first], atol=3e-2
    )


def test_early_exit_of_the_promised_sampler_is_bitwise_the_full_run(monkeypatch):
    """The loop still stops once every row has finished (``max_length``
    finishes a row of ``n`` real tokens after ``110 - n`` of its own: here
    after 40 to 50, past the first width's 28 steps and short of ``R``),
    and what it returns is bit for bit what all ``R`` steps return: the
    same loop with the all-finished term taken out of its predicate."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.ops import sampling

    apply_fn, init_cache_fn, params = _policy("gpt2", "int8")
    gen = sampling.GenerationConfig(
        max_new_tokens=R, do_sample=True, top_k=0, eos_token_id=63, pad_token_id=63,
        max_length=110,
    )
    ids, _ = _prompts()
    real_tokens = jnp.asarray([70, 65, 60, 62])
    mask = (jnp.arange(Q)[None, :] >= Q - real_tokens[:, None]).astype(jnp.int32)
    args = (params, ids, mask, jax.random.PRNGKey(3))
    steps = [0]

    def counting(params, *a, **kw):
        jax.debug.callback(lambda: steps.__setitem__(0, steps[0] + 1))
        return apply_fn(params, *a, **kw)

    early = jax.jit(sampling.make_sampler(counting, init_cache_fn, gen, Q))(*args)
    jax.block_until_ready(early)
    jax.effects_barrier()
    lengths = np.asarray(early.response_mask).sum(axis=1)
    # the prefill and one forward a step run: the last row's last token is
    # the last step
    assert 28 < steps[0] - 1 == lengths.max() <= 50 < R, (steps, lengths)
    real = jax.lax.while_loop
    monkeypatch.setattr(
        jax.lax, "while_loop", lambda cond, body, init: real(lambda c: c[0] < R, body, init)
    )
    full = jax.jit(sampling.make_sampler(apply_fn, init_cache_fn, gen, Q))(*args)
    for name in ("tokens", "response_mask", "logprobs", "values"):
        np.testing.assert_array_equal(
            np.asarray(getattr(early, name)), np.asarray(getattr(full, name)), err_msg=name
        )


@pytest.mark.parametrize(
    "query,new,kv,share,widths",
    [(64, 448, "auto", 0.6786, (128, 256, 384, 512)),
     (512, 48, "bfloat16", 1.0, (560,))],
    ids=["longgen", "tldr"],
)
def test_read_share_at_the_ppo_cells_shapes(query, new, kv, share, widths):
    """``sampler/read_share``, the mean over the steps of (the width a step
    reads / the capacity), is host arithmetic set where the sampler is
    traced: 0.6786 at ``ppo-gpt2m-longgen``'s window and answer, 1.0 at
    ``ppo-gpt2m-tldr``'s, where the rule gives the capacity alone. Traced
    on a two-layer model of those lengths; nothing runs."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.gpt2 import GPT2Config, init_cache
    from trlx_tpu.models.heads import CausalLMWithValueHead
    from trlx_tpu.ops import sampling
    from trlx_tpu.ops.kv_cache import decode_read_widths
    from trlx_tpu.telemetry import get_metrics

    assert decode_read_widths(query + new, query) == widths
    cfg = GPT2Config(vocab_size=64, n_positions=1024, n_embd=32, n_layer=2, n_head=4,
                     dtype="bfloat16", kv_cache_dtype=kv)
    model = CausalLMWithValueHead(cfg)

    def apply_fn(params, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None, last_only=False):
        return model.apply(
            {"params": params}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache, cache_index=cache_index,
            last_only=last_only,
        )

    gen = sampling.GenerationConfig(
        max_new_tokens=new, min_new_tokens=new, do_sample=True, top_k=0,
        eos_token_id=63, pad_token_id=63,
    )
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    )
    prompts = jax.ShapeDtypeStruct((8, query), jnp.int32)
    before = _read_width_counts(widths)
    jax.eval_shape(
        sampling.make_sampler(apply_fn, functools.partial(init_cache, cfg), gen, query),
        params, prompts, prompts, jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    assert get_metrics().gauge("sampler/read_share").value == pytest.approx(share, abs=1e-4)
    assert _read_width_counts(widths) == {w: n + cfg.n_layer for w, n in before.items()}


def test_a_capacity_sharded_cache_is_promised_nothing():
    """A cache whose capacity axis is sharded stays in the ``kv_buffers``
    layout and decodes through the generic read: no promise, no narrowed
    read, a share of 1."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from trlx_tpu.ops import sampling
    from trlx_tpu.telemetry import get_metrics

    apply_fn, init_cache_fn, params = _policy("gpt2", "bfloat16")
    gen = sampling.GenerationConfig(max_new_tokens=R, eos_token_id=63, pad_token_id=63)
    ids, mask = _prompts()
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "sp"))
    before = _read_width_counts((128, 160))
    sampler = sampling.make_sampler(
        apply_fn, init_cache_fn, gen, Q, cache_sharding=NamedSharding(mesh, P("dp", "sp"))
    )
    jax.jit(sampler).lower(params, ids, mask, jax.random.PRNGKey(0))
    assert _read_width_counts((128, 160)) == before
    assert get_metrics().gauge("sampler/read_share").value == 1.0
