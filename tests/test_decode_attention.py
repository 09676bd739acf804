"""The fixed sampler's decode read (``ops/attention.py::decode_attention`` on
a cache in ``decode_kv_layout``) and the paged engine's (the pools read in
the order they are stored) against the generic read — ``dense_write_read`` / ``paged_write_read`` and
``dot_product_attention`` on the dequantised updated buffer — and the
dispatch between the three, counted at trace time."""

import numpy as np
import pytest

B, H = 3, 4

# (compute dtype, cache dtype, atol): float32 agrees to 1e-6 with either
# cache (int8 values and their bf16 scales are exact in float32); in bf16
# the fused read rounds where the generic one does but sums in another
# order (bf16 cache), or skips the rounding of the dequantised buffer (int8)
PRECISIONS = [
    ("float32", "float32", 1e-6),
    ("float32", "int8", 1e-6),
    ("bfloat16", "bfloat16", 2e-2),
    ("bfloat16", "int8", 3e-2),
]
# head size (gpt2's, pythia's) x capacity (not a tile multiple, a multiple)
SHAPES = [(64, 560), (128, 560), (64, 512), (128, 512)]


def _counts():
    from trlx_tpu.telemetry import get_metrics

    reg = get_metrics()
    return {
        path: reg.counter("attention/decode_path{path=%s}" % path).value
        for path in ("fused", "paged", "generic")
    }


def _filled_cache(rng, C, Dh, dtype, cache_dtype, filled):
    """One layer's ``kv_buffers``-layout cache holding ``filled`` random
    positions, written through ``dense_write_read`` (so int8 holds what the
    program would have quantised)."""
    import jax.numpy as jnp

    from trlx_tpu.ops.kv_cache import dense_write_read, kv_buffers

    kv = "int8" if cache_dtype == "int8" else "bfloat16"
    cache = kv_buffers(1, B, C, H, Dh, dtype, kv)[0]
    k = jnp.asarray(rng.standard_normal((B, filled, H, Dh)), dtype)
    v = jnp.asarray(rng.standard_normal((B, filled, H, Dh)), dtype)
    return dense_write_read(cache, k, v, 0, jnp.dtype(dtype))[2]


LAYERS, LAYER = 3, 1


def _carry(cache):
    """The fixed sampler's carry (``decode_kv_layout`` of a tuple: one array
    a kind, layer-major) with ``cache`` as layer ``LAYER`` of ``LAYERS`` and
    every other layer holding other values, as the model hands it to that
    layer (``layer_cache``)."""
    import jax

    from trlx_tpu.ops.kv_cache import decode_kv_layout, layer_cache

    layers = tuple(
        cache if l == LAYER
        else jax.tree_util.tree_map(lambda a: (a + 1 + l).astype(a.dtype), cache)
        for l in range(LAYERS)
    )
    return layer_cache(decode_kv_layout(layers), LAYER)


def _bias(C, index, pad, shared):
    """Additive [B|1, 1, 1, C]: causal at ``index`` and, per row, ``pad``
    masked positions on the left (row 0 of a per-row bias has none)."""
    from trlx_tpu.ops.attention import causal_bias, combine_biases, padding_bias

    causal = causal_bias(1, C, offset=index)
    if shared:
        valid = (np.arange(C) >= pad)[None, :]
    else:
        valid = np.arange(C)[None, :] >= (np.arange(B) * pad)[:, None]
    return combine_biases(causal, padding_bias(valid.astype(np.int32)))


@pytest.mark.parametrize("shared_bias", [False, True], ids=["bias_B", "bias_1"])
@pytest.mark.parametrize("where", ["first", "mid", "last"])
@pytest.mark.parametrize("Dh,C", SHAPES)
@pytest.mark.parametrize("dtype,cache_dtype,atol", PRECISIONS)
def test_fused_read_matches_generic(dtype, cache_dtype, atol, Dh, C, where,
                                    shared_bias):
    import jax.numpy as jnp

    from trlx_tpu.ops.attention import decode_attention
    from trlx_tpu.ops.kv_cache import decode_kv_layout

    rng = np.random.default_rng(C + Dh)
    index = {"first": 0, "mid": C // 2, "last": C - 1}[where]
    cache = _filled_cache(rng, C, Dh, dtype, cache_dtype, filled=index)
    q, k_new, v_new = (
        jnp.asarray(rng.standard_normal((B, 1, H, Dh)), dtype) for _ in range(3)
    )
    # left padding: at `first` the new position is the only one in view, so
    # nothing is masked; elsewhere up to a third of the filled part is
    bias = _bias(C, index, pad=index // 3, shared=shared_bias)

    before = _counts()
    ref, ref_kv = decode_attention(q, k_new, v_new, cache, index, bias)
    carry = _carry(cache)
    out, new_kv = decode_attention(q, k_new, v_new, carry, index, bias)
    after = _counts()
    assert after["generic"] == before["generic"] + 1
    assert after["fused"] == before["fused"] + 1

    assert out.shape == ref.shape and out.dtype == ref.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=atol
    )
    # the write: the carry comes back whole, this layer's buffers the same
    # as the generic read's in the other layout, bit for bit, and every
    # other layer as it was
    want = decode_kv_layout(ref_kv)
    assert sorted(new_kv) == sorted(want)
    for name in want:
        assert new_kv[name].dtype == want[name].dtype, name
        assert new_kv[name].shape == carry[name].shape, name
        np.testing.assert_array_equal(
            np.asarray(new_kv[name][LAYER], np.float32),
            np.asarray(want[name], np.float32), err_msg=name,
        )
        others = [l for l in range(LAYERS) if l != LAYER]
        np.testing.assert_array_equal(
            np.asarray(new_kv[name], np.float32)[others],
            np.asarray(carry[name], np.float32)[others], err_msg=name,
        )


@pytest.mark.parametrize("dtype,cache_dtype", [p[:2] for p in PRECISIONS])
def test_a_layers_own_buffers_read_as_the_carry_does(dtype, cache_dtype):
    """One layer's folded dict (the pp stage scan's call: the carry with no
    leading axis) takes the same read: output and written buffers equal the
    carry's, bit for bit."""
    import jax.numpy as jnp

    from trlx_tpu.ops.attention import decode_attention
    from trlx_tpu.ops.kv_cache import decode_kv_layout

    rng = np.random.default_rng(3)
    C, Dh, index = 40, 64, 17
    cache = _filled_cache(rng, C, Dh, dtype, cache_dtype, filled=index)
    q, k_new, v_new = (
        jnp.asarray(rng.standard_normal((B, 1, H, Dh)), dtype) for _ in range(3)
    )
    bias = _bias(C, index, pad=5, shared=False)
    before = _counts()
    out, new_kv = decode_attention(q, k_new, v_new, _carry(cache), index, bias)
    own, own_kv = decode_attention(
        q, k_new, v_new, decode_kv_layout(cache), index, bias
    )
    assert _counts()["fused"] == before["fused"] + 2
    np.testing.assert_array_equal(
        np.asarray(own, np.float32), np.asarray(out, np.float32)
    )
    assert sorted(own_kv) == sorted(new_kv)
    for name in own_kv:
        np.testing.assert_array_equal(
            np.asarray(own_kv[name], np.float32),
            np.asarray(new_kv[name][LAYER], np.float32), err_msg=name,
        )


def test_fully_masked_row_matches_generic():
    """A row with every position masked (an idle row's bias) softmaxes to
    uniform weights in both reads — no NaN, same output."""
    import jax.numpy as jnp

    from trlx_tpu.ops.attention import NEG_INF, decode_attention

    rng = np.random.default_rng(0)
    C, Dh, index = 40, 64, 17
    cache = _filled_cache(rng, C, Dh, "float32", "float32", filled=index)
    q, k_new, v_new = (
        jnp.asarray(rng.standard_normal((B, 1, H, Dh)), jnp.float32)
        for _ in range(3)
    )
    bias = np.array(_bias(C, index, pad=3, shared=False))
    bias[1] = NEG_INF
    bias = jnp.asarray(bias)
    ref, _ = decode_attention(q, k_new, v_new, cache, index, bias)
    out, _ = decode_attention(q, k_new, v_new, _carry(cache), index, bias)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


# --------- the read's width under the sampler's promise --------------- #

# a call's first index and capacity: longgen's (four widths, 128..512)
PROMISED, CAPACITY = 64, 512
WIDTHS = (128, 256, 384, 512)
# both sides of every boundary, the first call and the last position
INDICES = sorted(
    {PROMISED, CAPACITY - 1} | {w + d for w in WIDTHS for d in (-2, -1, 0) if w + d < CAPACITY}
)


def _promised(cache_kv, first_index=PROMISED):
    """``cache_kv`` (the carry as a layer is handed it, or one layer's own
    dict) under the sampler's promise."""
    from trlx_tpu.ops.kv_cache import written_to_index

    return written_to_index((cache_kv,), first_index)[0]


@pytest.mark.parametrize(
    "capacity,first,widths",
    [(512, 64, (128, 256, 384, 512)), (560, 512, (560,)),
     (2560, 512, (1024, 1536, 2048, 2560)), (128, 0, (128,)),
     (512, 128, (256, 384, 512)), (1024, 0, (256, 512, 768, 1024)),
     (160, 100, (128, 160)), (512, None, (512,))],
    ids=["longgen", "tldr", "long-answer", "one-tile", "first-on-a-tile",
         "from-zero", "two", "no-promise"],
)
def test_the_widths_follow_from_the_capacity_and_the_first_index(capacity, first, widths):
    """Derived, no knob: the multiples of 128 inside ``(first, capacity)``,
    then the capacity; beyond four, every ``ceil(n / 4)``-th counted back
    from the capacity. Every index a call may come at has a width."""
    from trlx_tpu.ops.kv_cache import decode_read_widths

    got = decode_read_widths(capacity, first)
    assert got == widths
    assert got[-1] == capacity and len(got) <= 4 and list(got) == sorted(set(got))
    assert first is None or got[0] > first


@pytest.mark.parametrize("index", INDICES)
@pytest.mark.parametrize("own", [False, True], ids=["carry", "own_dict"])
@pytest.mark.parametrize("dtype,cache_dtype,atol", PRECISIONS)
def test_promised_read_matches_the_whole_read(dtype, cache_dtype, atol, own, index):
    """Under ``written_to_index`` the read takes the narrowest width that
    holds ``cache_index`` (left-padded rows, both sides of every boundary):
    the output is the whole-capacity read's to float32 summation order, the
    written buffers are bit for bit the same, and what comes back carries
    no promise."""
    import jax.numpy as jnp

    from trlx_tpu.ops.attention import decode_attention
    from trlx_tpu.ops.kv_cache import decode_kv_layout, decode_read_widths
    from trlx_tpu.telemetry import get_metrics

    assert decode_read_widths(CAPACITY, PROMISED) == WIDTHS
    rng = np.random.default_rng(index)
    Dh = 16
    cache = _filled_cache(rng, CAPACITY, Dh, dtype, cache_dtype, filled=index)
    cache_kv = decode_kv_layout(cache) if own else _carry(cache)
    q, k_new, v_new = (
        jnp.asarray(rng.standard_normal((B, 1, H, Dh)), dtype) for _ in range(3)
    )
    bias = _bias(CAPACITY, index, pad=PROMISED // 4, shared=False)
    read = lambda w: get_metrics().counter("attention/decode_read_width{width=%d}" % w).value  # noqa: E731
    before = {w: read(w) for w in WIDTHS}
    whole, whole_kv = decode_attention(q, k_new, v_new, cache_kv, index, bias)
    assert {w: read(w) - before[w] for w in WIDTHS} == {128: 0, 256: 0, 384: 0, 512: 1}
    out, new_kv = decode_attention(q, k_new, v_new, _promised(cache_kv), index, bias)
    # one traced read site a width
    assert {w: read(w) - before[w] for w in WIDTHS} == {128: 1, 256: 1, 384: 1, 512: 2}

    assert out.shape == whole.shape and out.dtype == whole.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(whole, np.float32), atol=atol
    )
    assert sorted(new_kv) == sorted(whole_kv)
    for name in whole_kv:
        np.testing.assert_array_equal(
            np.asarray(new_kv[name], np.float32),
            np.asarray(whole_kv[name], np.float32), err_msg=name,
        )


def test_promised_read_of_a_fully_masked_row_is_finite():
    """A row with every position masked softmaxes to uniform weights over
    the width it reads: finite, and every other row the whole read's."""
    import jax.numpy as jnp

    from trlx_tpu.ops.attention import NEG_INF, decode_attention

    rng = np.random.default_rng(0)
    Dh, index = 16, 200
    cache = _filled_cache(rng, CAPACITY, Dh, "float32", "float32", filled=index)
    q, k_new, v_new = (
        jnp.asarray(rng.standard_normal((B, 1, H, Dh)), jnp.float32) for _ in range(3)
    )
    bias = np.array(_bias(CAPACITY, index, pad=3, shared=False))
    bias[1] = NEG_INF
    bias = jnp.asarray(bias)
    whole, _ = decode_attention(q, k_new, v_new, _carry(cache), index, bias)
    out, _ = decode_attention(q, k_new, v_new, _promised(_carry(cache)), index, bias)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out)[[0, 2]], np.asarray(whole)[[0, 2]], atol=1e-6)


# the jaxpr of the read without a promise at the commit before the promise
# existed (PR 53's tree): printed, hashed
PARENT_READ_JAXPR = {"carry": "a6826f3ea1c77c05", "own_dict": "f79d13217aee52f9"}


@pytest.mark.parametrize("own", [False, True], ids=["carry", "own_dict"])
def test_no_promise_reads_the_whole_capacity_as_before(own):
    """A caller that promises nothing (the pp stage scan, a layer's own
    dict handed over by anyone) traces the read it traced before: the
    parent's jaxpr, no ``cond``. So does a promise under which the rule
    gives one width (tldr's 512 of 560)."""
    import hashlib

    import jax
    import jax.numpy as jnp

    from trlx_tpu.ops.attention import decode_attention
    from trlx_tpu.ops.kv_cache import cache_kind, decode_kv_layout, kv_buffers

    C, Dh = 560, 16
    cache = kv_buffers(1, B, C, H, Dh, jnp.bfloat16, "int8")[0]
    cache_kv = decode_kv_layout(cache) if own else _carry(cache)
    x = jnp.zeros((B, 1, H, Dh), jnp.bfloat16)
    bias = jnp.zeros((B, 1, 1, C), jnp.float32)

    def traced(cache_kv):
        static = {k: a for k, a in cache_kv.items() if not hasattr(a, "shape")}
        arrays = {k: a for k, a in cache_kv.items() if k not in static}
        return str(jax.make_jaxpr(
            lambda arrays, index: decode_attention(x, x, x, {**arrays, **static}, index, bias)
        )(arrays, jnp.int32(520)))

    assert cache_kind(cache_kv).written_to_index is None
    plain = traced(cache_kv)
    assert "cond" not in plain
    key = "own_dict" if own else "carry"
    assert hashlib.sha256(plain.encode()).hexdigest()[:16] == PARENT_READ_JAXPR[key]
    promised = _promised(cache_kv, first_index=512)
    assert cache_kind(promised).written_to_index == 512
    assert traced(promised) == plain
    assert "cond" in traced(_promised(cache_kv, first_index=64))


def test_decode_kv_layout_shapes():
    """A tuple of layers becomes the carry, one array a kind with the
    layers leading; one layer's dict and the pp sampler's layer-major dict
    fold as they are: heads into the minor axis, int8 scales
    capacity-minor."""
    import jax.numpy as jnp

    from trlx_tpu.ops.kv_cache import decode_kv_layout, kv_buffers

    layers = kv_buffers(2, B, 24, H, 8, jnp.bfloat16, "int8")
    carry = decode_kv_layout(layers)
    assert sorted(carry) == ["k", "k_scale", "v", "v_scale"]
    assert carry["k"].shape == carry["v"].shape == (2, B, 24, H * 8)
    assert carry["k"].dtype == jnp.int8
    assert carry["k_scale"].shape == carry["v_scale"].shape == (2, B, H, 24)
    own = decode_kv_layout(layers[0])
    assert own["k"].shape == (B, 24, H * 8) and own["v_scale"].shape == (B, H, 24)
    stacked = {"k": jnp.zeros((5, B, 24, H, 8)), "v": jnp.zeros((5, B, 24, H, 8))}
    assert decode_kv_layout(stacked)["v"].shape == (5, B, 24, H * 8)


@pytest.mark.parametrize(
    "batch,capacity,kv,one_array",
    [(64, 512, "int8", True), (32, 560, "bfloat16", True), (48, 560, "bfloat16", True),
     (56, 560, "bfloat16", False), (64, 560, "bfloat16", False)],
    ids=["longgen-33.5MB", "36.7MB", "55.1MB", "64.2MB", "tldr-73.4MB"],
)
def test_layers_the_compiler_would_stage_fold_into_one_array(batch, capacity, kv, one_array):
    """gpt2-medium's layers (16 heads of 64): where a layer's buffer is
    small enough for the chip's compiler to stage and write back whole
    (``staged_by_the_compiler``: the sizes are the ones compiled for a
    described v5e, PERF.md §6 PR 50) a tuple of layers folds into the
    layer-major carry; larger layers stay a tuple of folded dicts, the
    layout they were carried in before. Shapes only: nothing is allocated."""
    import warnings

    import jax
    import jax.numpy as jnp

    from trlx_tpu.ops.kv_cache import decode_kv_layout, kv_buffers, staged_by_the_compiler

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # int8 beyond its measured capacity
        layers = jax.eval_shape(lambda: kv_buffers(3, batch, capacity, 16, 64, jnp.bfloat16, kv))
    assert staged_by_the_compiler(layers[0]) == one_array
    folded = jax.eval_shape(decode_kv_layout, layers)
    if one_array:
        assert isinstance(folded, dict) and folded["k"].shape == (3, batch, capacity, 1024)
    else:
        assert isinstance(folded, tuple) and len(folded) == 3
        assert folded[0]["k"].shape == folded[2]["v"].shape == (batch, capacity, 1024)


def _bypass_case(kind):
    """(q_len, cache, cache_index, bias) for one call the generic read
    keeps: the paged engine's int8 pool or a window of positions into its
    pool, a learned per-head bias, two positions a call."""
    import jax.numpy as jnp

    from trlx_tpu.ops.kv_cache import kv_buffers
    from trlx_tpu.ops.attention import causal_bias

    C, Dh = 16, 8
    if kind.startswith("paged"):
        from trlx_tpu.ops.kv_cache import init_paged_cache

        # an int8 pool is read dequantised, a window of two positions (the
        # verify step) through the logical view: both stay generic
        kv = "int8" if kind == "paged_int8" else "bfloat16"
        q_len = 2 if kind == "paged_q_len_2" else 1
        cache = init_paged_cache(1, B, C, H, Dh, jnp.float32, kv, block_size=4)[0]
        at = jnp.full((B,), 5, jnp.int32)
        return q_len, cache, at, causal_bias(q_len, C, offset=at)
    cache = kv_buffers(1, B, C, H, Dh, jnp.float32, "bfloat16")[0]
    if kind == "per_head_bias":
        bias = causal_bias(1, C, offset=5) + jnp.arange(H, dtype=jnp.float32)[
            None, :, None, None]
        return 1, cache, 5, bias
    return 2, cache, 5, causal_bias(2, C, offset=5)


@pytest.mark.parametrize(
    "kind", ["paged_int8", "paged_q_len_2", "per_head_bias", "q_len_2"]
)
def test_bypass_takes_the_generic_read(kind):
    import jax.numpy as jnp

    from trlx_tpu.ops.attention import decode_attention

    q_len, cache, index, bias = _bypass_case(kind)
    rng = np.random.default_rng(1)
    q, k_new, v_new = (
        jnp.asarray(rng.standard_normal((B, q_len, H, 8)), jnp.float32)
        for _ in range(3)
    )
    before = _counts()
    out, new_kv = decode_attention(
        q, k_new, v_new, cache, index, bias,
        learned_bias=kind == "per_head_bias",
    )
    after = _counts()
    assert after["generic"] == before["generic"] + 1
    assert after["fused"] == before["fused"]
    assert after["paged"] == before["paged"]
    assert out.shape == q.shape and new_kv["k"].shape == cache["k"].shape


@pytest.mark.parametrize("kind", ["per_head_bias", "q_len_2"])
def test_decode_layout_refuses_what_it_cannot_read(kind):
    """A cache in the decode layout is never rerouted: a call it cannot
    serve is an error, not a slower answer."""
    import jax.numpy as jnp

    from trlx_tpu.ops.attention import decode_attention

    q_len, cache, index, bias = _bypass_case(kind)
    x = jnp.zeros((B, q_len, H, 8), jnp.float32)
    with pytest.raises(ValueError, match="decode_kv_layout"):
        decode_attention(
            x, x, x, _carry(cache), index, bias,
            learned_bias=kind == "per_head_bias",
        )


def test_sampler_traces_the_fused_read_and_sp_keeps_generic():
    """One traced call site a layer: a plain sampler's decode step takes
    the fused read (its prefill the generic one); a cache sharded over the
    capacity axis decodes through the generic read."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from trlx_tpu.models.gpt2 import GPT2Config, GPT2Model, init_cache
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler

    cfg = GPT2Config(vocab_size=50, n_positions=32, n_embd=16, n_layer=2,
                     n_head=2, dtype="float32")
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    gen = GenerationConfig(max_new_tokens=4, eos_token_id=49, pad_token_id=0)

    def apply_fn(params, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None):
        return model.apply(
            {"params": params}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache, cache_index=cache_index,
        )

    ids = jnp.ones((2, 4), jnp.int32)
    args = (params, ids, jnp.ones_like(ids), jax.random.PRNGKey(0))
    build = functools.partial(
        make_sampler, apply_fn, functools.partial(init_cache, cfg), gen, 4,
        with_values=False,
    )
    before = _counts()
    jax.jit(build()).lower(*args)
    mid = _counts()
    assert mid["fused"] - before["fused"] == cfg.n_layer  # the decode step
    assert mid["generic"] - before["generic"] == cfg.n_layer  # the prefill

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "sp"))
    sharded = build(cache_sharding=NamedSharding(mesh, P("dp", "sp")))
    jax.jit(sharded).lower(*args)
    after = _counts()
    assert after["fused"] == mid["fused"]
    assert after["generic"] - mid["generic"] == 2 * cfg.n_layer
    # no sampler program reads a paged pool (the PPO cells run this one)
    assert after["paged"] == before["paged"]


# ----------------- the paged engine's read, as stored ------------------ #


def _paged_case(cache_dtype, rotated, dropped, vector_index, dtype="float32"):
    """One layer's paged pool (``C`` 24 in blocks of 4) filled to
    position 10 through ``paged_write_read``, its tables rotated a slot or left
    identity, and one more position a slot: at a per-slot depth, or at one
    scalar depth; ``dropped`` parks slot 1 at the discard sentinel."""
    import jax.numpy as jnp

    from trlx_tpu.ops.kv_cache import (
        init_paged_cache, paged_write_read, rotate_block_table,
    )
    from trlx_tpu.ops.attention import causal_bias, combine_biases, padding_bias

    C, Dh, filled = 24, 8, 10
    rng = np.random.default_rng(7)
    cache = init_paged_cache(1, B, C, H, Dh, dtype, cache_dtype, block_size=4)[0]
    if rotated:
        tables = cache["block_tables"]
        for b in range(B):
            tables = tables.at[b].set(rotate_block_table(tables[b], 2 * b + 1))
        cache = dict(cache, block_tables=tables)
    k, v = (
        jnp.asarray(rng.standard_normal((B, filled, H, Dh)), dtype) for _ in range(2)
    )
    cache = paged_write_read(
        cache, k, v, jnp.zeros((B,), jnp.int32), jnp.dtype(dtype)
    )[2]
    if vector_index:
        depth = np.asarray([filled, filled - 3, filled - 1])
        index = jnp.asarray(np.where(dropped, [filled, C, filled - 1], depth), jnp.int32)
    else:
        depth = np.full((B,), filled)
        index = jnp.asarray(C if dropped else filled, jnp.int32)
    valid = np.arange(C)[None, :] >= np.arange(B)[:, None]  # left padding a row
    bias = combine_biases(
        causal_bias(1, C, offset=jnp.asarray(depth, jnp.int32)),
        padding_bias(valid.astype(np.int32)),
    )
    q, k_new, v_new = (
        jnp.asarray(rng.standard_normal((B, 1, H, Dh)), dtype) for _ in range(3)
    )
    return cache, q, k_new, v_new, index, bias


@pytest.mark.parametrize("vector_index", [True, False], ids=["index_B", "index_scalar"])
@pytest.mark.parametrize("dropped", [False, True], ids=["all_live", "some_dropped"])
@pytest.mark.parametrize("rotated", [False, True], ids=["identity", "rotated"])
@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8"])
def test_paged_one_token_read_matches_the_logical_view(
    cache_dtype, rotated, dropped, vector_index
):
    """One new position a slot into a paged pool: a floating pool is read
    as stored (``path=paged``), an int8 pool through the dequantised logical
    view (``generic``). Either way the stored pools are, bit for bit, what
    the logical-view form stores; the pools returned as stored, gathered
    into logical order, ARE the logical view; and the attention output
    agrees with the logical-view read to float32 rounding (the same terms,
    summed in the slot's physical order)."""
    import jax.numpy as jnp

    from trlx_tpu.ops.kv_cache import (
        _gather_logical,
        logical_view_index,
        paged_write_read,
        reads_as_stored,
    )
    from trlx_tpu.ops.attention import decode_attention, dot_product_attention

    cache, q, k_new, v_new, index, bias = _paged_case(
        cache_dtype, rotated, dropped, vector_index
    )
    k_view, v_view, want_kv = paged_write_read(cache, k_new, v_new, index, q.dtype)
    want = dot_product_attention(q, k_view, v_view, bias)

    before = _counts()
    out, new_kv = decode_attention(q, k_new, v_new, cache, index, bias)
    after = _counts()
    path = "generic" if cache_dtype == "int8" else "paged"
    assert reads_as_stored(cache, k_new, index) == (path == "paged")
    assert {p: after[p] - before[p] for p in after} == {
        p: float(p == path) for p in after
    }

    assert sorted(new_kv) == sorted(want_kv)
    for name in want_kv:
        assert new_kv[name].dtype == want_kv[name].dtype, name
        np.testing.assert_array_equal(
            np.asarray(new_kv[name], np.float32),
            np.asarray(want_kv[name], np.float32), err_msg=name,
        )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)
    if path == "generic":
        with pytest.raises(ValueError, match="as_stored"):
            paged_write_read(cache, k_new, v_new, index, q.dtype, as_stored=True)
        return
    k_st, v_st, _ = paged_write_read(
        cache, k_new, v_new, index, q.dtype, as_stored=True
    )
    view = logical_view_index(cache["block_tables"], k_st.shape[1])
    np.testing.assert_array_equal(
        np.asarray(_gather_logical(k_st, view)), np.asarray(k_view)
    )
    np.testing.assert_array_equal(
        np.asarray(_gather_logical(v_st, view)), np.asarray(v_view)
    )


def test_paged_read_in_bfloat16_matches_the_logical_view():
    """The compute dtype the cells serve in: products and sums in float32
    on both sides, the output rounded once, so the two orders of one sum
    agree to a bfloat16 ulp of the output."""
    import jax.numpy as jnp

    from trlx_tpu.ops.kv_cache import paged_write_read
    from trlx_tpu.ops.attention import decode_attention, dot_product_attention

    cache, q, k_new, v_new, index, bias = _paged_case(
        "bfloat16", True, True, True, dtype="bfloat16"
    )
    k_view, v_view, _ = paged_write_read(cache, k_new, v_new, index, q.dtype)
    want = dot_product_attention(q, k_view, v_view, bias)
    out, _ = decode_attention(q, k_new, v_new, cache, index, bias)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=2e-2
    )


def test_stored_order_bias_follows_the_tables():
    """Column ``p`` of the re-indexed bias is the bias of the logical
    position whose row the slot keeps at ``p``; a shared ``[1, ...]`` bias
    is spread over the slots first."""
    import jax.numpy as jnp

    from trlx_tpu.ops.kv_cache import logical_view_index, stored_order_bias

    tables = jnp.asarray([[2, 0, 3, 1], [0, 1, 2, 3], [1, 2, 3, 0]], jnp.int32)
    C = 12
    view = np.asarray(logical_view_index(tables, C))  # physical of each logical
    bias = np.arange(3 * 2 * C, dtype=np.float32).reshape(3, 2, 1, C)
    got = np.asarray(stored_order_bias(tables, jnp.asarray(bias)))
    for b in range(3):
        np.testing.assert_array_equal(got[b][..., view[b]], bias[b])
    shared = np.asarray(stored_order_bias(tables, jnp.asarray(bias[:1, :1])))
    assert shared.shape == (3, 1, 1, C)
    np.testing.assert_array_equal(shared[0][..., view[0]], bias[0, :1])
