"""Pallas flash attention vs the XLA einsum path (interpret mode on CPU).

The kernel must be bit-comparable (f32 rounding) to
``dot_product_attention`` for every bias/causal/padding combination the
models use: GPT-family training (causal + key padding), sampler prefill
(causal over a capacity buffer), T5 cross-attention (padding only), and
T5-style per-head biases. Gradients are checked through the custom VJP
against JAX autodiff of the reference path.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.ops.attention import (
    causal_bias,
    combine_biases,
    dot_product_attention,
    padding_bias,
)
from trlx_tpu.ops.flash_attention import (
    LONG_BLOCK,
    LONG_SEQ,
    ROW_CHUNK,
    _row_chunk,
    fitted_block,
    flash_attention,
    operand_layout,
)

RNG = np.random.default_rng(0)


def rand(*shape):
    return jnp.asarray(RNG.normal(size=shape), jnp.float32)


def ref_loss(q, k, v, bias):
    return (dot_product_attention(q, k, v, bias) ** 2).sum()


def flash_loss(q, k, v, bias, **kw):
    return (
        flash_attention(q, k, v, bias, block_q=16, block_k=16, interpret=True, **kw)
        ** 2
    ).sum()


class TestFlashForward:
    def test_causal_with_padding_mask(self):
        B, T, H, D = 2, 48, 4, 32
        q, k, v = rand(B, T, H, D), rand(B, T, H, D), rand(B, T, H, D)
        mask = jnp.asarray(
            RNG.integers(0, 2, size=(B, T)) | (np.arange(T)[None] < 4), jnp.int32
        )
        ref = dot_product_attention(
            q, k, v, combine_biases(causal_bias(T, T), padding_bias(mask))
        )
        out = flash_attention(
            q, k, v, padding_bias(mask), causal=True,
            block_q=16, block_k=16, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)

    def test_unequal_q_k_with_tile_padding(self):
        # prompt-prefill shape: Q < K, neither a tile multiple
        B, Q, K, H, D = 1, 21, 37, 4, 32
        q, k, v = rand(B, Q, H, D), rand(B, K, H, D), rand(B, K, H, D)
        ref = dot_product_attention(q, k, v, causal_bias(Q, K))
        out = flash_attention(
            q, k, v, None, causal=True, block_q=16, block_k=16, interpret=True
        )
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)

    def test_per_head_bias_non_causal(self):
        # T5 cross-attention style: [1, H, Q, K] additive bias
        B, Q, K, H, D = 1, 24, 40, 4, 32
        q, k, v = rand(B, Q, H, D), rand(B, K, H, D), rand(B, K, H, D)
        bias = rand(1, H, Q, K)
        ref = dot_product_attention(q, k, v, bias)
        out = flash_attention(q, k, v, bias, block_q=16, block_k=16, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)

    def test_batched_padding_only(self):
        B, T, H, D = 2, 32, 2, 16
        q, k, v = rand(B, T, H, D), rand(B, T, H, D), rand(B, T, H, D)
        mask = jnp.asarray(
            (np.arange(T)[None] < np.array([[17], [32]])), jnp.int32
        ).reshape(B, T)
        ref = dot_product_attention(q, k, v, padding_bias(mask))
        out = flash_attention(
            q, k, v, padding_bias(mask), block_q=16, block_k=16, interpret=True
        )
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


class TestFlashBackward:
    def test_grads_causal_padding(self):
        B, T, H, D = 2, 48, 4, 32
        q, k, v = rand(B, T, H, D), rand(B, T, H, D), rand(B, T, H, D)
        mask = jnp.asarray(
            RNG.integers(0, 2, size=(B, T)) | (np.arange(T)[None] < 4), jnp.int32
        )
        full = combine_biases(causal_bias(T, T), padding_bias(mask))
        gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v, full)
        gf = jax.grad(
            lambda q, k, v: flash_loss(q, k, v, padding_bias(mask), causal=True),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(gr, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)

    def test_grads_unequal_with_padding(self):
        B, Q, K, H, D = 1, 21, 37, 4, 32
        q, k, v = rand(B, Q, H, D), rand(B, K, H, D), rand(B, K, H, D)
        cb = causal_bias(Q, K)
        gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v, cb)
        gf = jax.grad(
            lambda q, k, v: flash_loss(q, k, v, None, causal=True),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(gr, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)

    def test_grads_per_head_bias(self):
        B, Q, K, H, D = 1, 24, 40, 4, 32
        q, k, v = rand(B, Q, H, D), rand(B, K, H, D), rand(B, K, H, D)
        bias = rand(1, H, Q, K)
        gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v, bias)
        gf = jax.grad(
            lambda q, k, v: flash_loss(q, k, v, bias), argnums=(0, 1, 2)
        )(q, k, v)
        for a, b in zip(gr, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)

    def test_bias_grad_is_zero_by_contract(self):
        # The VJP deliberately returns zero for bias (learned biases must use
        # the XLA path — dot_product_attention(learned_bias=True)).
        B, T, H, D = 1, 16, 2, 16
        q, k, v = rand(B, T, H, D), rand(B, T, H, D), rand(B, T, H, D)
        bias = rand(1, 1, T, T)
        db = jax.grad(lambda b: flash_loss(q, k, v, b))(bias)
        assert float(jnp.abs(db).max()) == 0.0


def _assert_output_and_grads_match(xla, flash, loss, q, k, v):
    np.testing.assert_allclose(
        np.asarray(xla(q, k, v)), np.asarray(flash(q, k, v)), atol=2e-5
    )
    gr = jax.grad(lambda *a: loss(xla(*a)), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(lambda *a: loss(flash(*a)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf, strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_tiles_are_fitted_to_the_length():
    # one tile over the whole axis (in sublanes of 16) under LONG_SEQ: the
    # cells' updates (T 512, 560) and everything a direct caller sends
    assert [fitted_block(t) for t in (1, 8, 21, 48, 512, 560, 563, 640, 1000)] == [
        8, 8, 32, 48, 512, 560, 576, 640, 1008,
    ]
    assert [fitted_block(t) for t in (LONG_SEQ, 1500, 4096)] == [LONG_BLOCK] * 3
    # the rows a loop iteration of the one-tile kernels takes: the whole tile
    # while it is small, else the tile over the fewest iterations that keep a
    # chunk under the cap, in bf16 sublanes: fitted to the length, no divisor
    # of it (as divisors T 560 ran chunks of 112 rows and T 592 of 16)
    assert [_row_chunk(t) for t in (8, 32, 128, 320, 336, 512, 560, 576, 592, 640, 768, 1008)] == [
        8, 32, 128, 320, 176, 256, 288, 288, 304, 320, 256, 256,
    ]
    assert 512 % _row_chunk(512) == 0  # longgen's T: the program it was
    for t in sorted(set(map(fitted_block, range(1, LONG_SEQ)))):
        c = _row_chunk(t)
        n = -(-t // c)
        assert c <= ROW_CHUNK and n == -(-t // ROW_CHUNK)  # the fewest iterations
        if n > 1:
            # whole sublanes, and the last chunk, aligned to the tile's end,
            # revisits less than a chunk, none of it before the chunk before
            assert c % 16 == 0 and 0 <= n * c - t < c and (n - 1) * c <= t
        # no other multiple of 16 does it in as few iterations of fewer rows
        assert c == t or all(n * d < t for d in range(16, c, 16))


def test_operands_fold_where_whole_lanes_of_heads_divide_the_head_count():
    # (heads a grid step, folded to [B, T, H * Dh]?) from the call's own H, Dh
    assert [tuple(operand_layout(H, D)[2:]) for H, D in (
        (16, 64), (4, 128), (16, 256), (4, 32), (3, 64), (4, 80), (2, 16),
    )] == [
        (2, True), (1, True), (1, True), (4, True), (1, False), (1, False),
        (1, False),
    ]


@pytest.mark.parametrize(
    "T,H,D",
    [
        (512, 2, 64), (560, 2, 64), (640, 2, 64),
        (512, 2, 128), (560, 2, 128), (640, 2, 128),
        (560, 16, 64),  # gpt2-medium's heads: eight blocks of two
        (512, 4, 128),  # one head a block
        (560, 3, 64),   # an odd count of 64-wide heads: heads-major
        (512, 4, 80),   # heads of 80 fill no whole lanes: heads-major
        (100, 4, 64),   # a length that pads to the tile
        (100, 3, 64),
    ],
)
def test_fitted_tiles_match_xla_at_the_update_lengths(T, H, D):
    """The uncached causal call of an update at the tiles the rule picks
    (no ``block_q`` / ``block_k``), in both operand layouts (folded: two
    heads of 64 or one of 128 a grid step; heads-major where the heads do
    not fill whole lanes): output and dq, dk, dv against the XLA
    path under a left-padding ``[B, 1, 1, T]`` bias. Row 0 is half padding,
    row 1 has none. A padding position's own output is uniform weights over
    the keys its row visits, on both paths and over the same keys while the
    axis is one tile, so the forward is compared on those rows too. The
    loss is over the real positions, as a trainer's is: no real position
    reads a padding one, so its cotangent is exactly zero, and the kernels'
    backward is only good for that (it recomputes the weights from a
    logsumexp in which -1e9 has absorbed log n, so an all-padding row's
    are 1 and not 1/n)."""
    B = 2
    q, k, v = rand(B, T, H, D), rand(B, T, H, D), rand(B, T, H, D)
    pads = np.array([[T // 2], [0]])
    mask = jnp.asarray(np.arange(T)[None] >= pads, jnp.float32)
    bias = padding_bias(mask)
    assert bias.shape == (B, 1, 1, T)

    def loss(out):
        return ((out * mask[:, :, None, None]) ** 2).sum()

    def flash(q, k, v):
        return flash_attention(q, k, v, bias, causal=True, interpret=True)

    def xla(q, k, v):
        return dot_product_attention(q, k, v, bias, causal=True)

    _assert_output_and_grads_match(xla, flash, loss, q, k, v)


@pytest.mark.parametrize(
    "Q,K,H,D,bias_shape,causal",
    [
        (352, 352, 2, 32, (1, 2, 352, 352), False),  # per-head, per-row bias: sliced by chunk
        (352, 200, 2, 32, (2, 1, 352, 200), True),   # Q < K, neither a lane multiple
        (384, 384, 2, 32, None, True),               # no bias at all
        (352, 40, 2, 32, (2, 1, 1, 40), False),      # cross-attention style: padding only
        # folded operands, two heads of 64 a grid step
        (352, 352, 4, 64, (1, 4, 352, 352), False),  # a step reads its two heads' bias planes
        (352, 352, 4, 64, (1, 1, 352, 352), True),   # [1, 1, Q, K]
        (352, 200, 4, 64, (2, 1, 1, 200), False),    # [B, 1, 1, K]
        (384, 384, 4, 64, None, False),
        (384, 384, 4, 128, None, True),              # one head of 128 a step
        (352, 352, 4, 128, (2, 1, 1, 352), True),
        # heads-major: the same kernels, a head a step
        (352, 352, 3, 64, (1, 3, 352, 352), True),
        (384, 384, 4, 80, (1, 1, 384, 384), False),
        (352, 352, 4, 80, None, True),
        # the chunk does not divide the tile (336 = 176 + 176 - 16, 400 = 208
        # + 208 - 16, 650 -> 656 = 3 x 224 - 16): the last chunk is aligned to
        # the tile's end and revisits 16 rows of the one before it, which have
        # to count once in dk and dv and keep their own o, lse and dq
        (336, 336, 4, 64, (2, 1, 1, 336), True),     # folded, two heads a step: an update's call
        (336, 336, 3, 64, None, True),               # heads-major
        (336, 336, 2, 32, (1, 2, 336, 336), False),  # per-head, per-row bias: sliced by chunk
        (400, 200, 4, 64, (2, 1, 400, 200), True),   # Q != K
        (650, 650, 2, 128, None, True),              # three chunks, a tile padded past T
    ],
    ids=[
        "per-row-bias", "unequal-causal", "no-bias", "short-keys",
        "folded-per-head-bias", "folded-row-bias-causal", "folded-key-bias",
        "folded-no-bias", "folded-128-no-bias-causal", "folded-128-key-bias",
        "odd-heads-per-head-bias", "heads-of-80-row-bias", "heads-of-80-causal",
        "overlap-folded-causal", "overlap-heads-major", "overlap-per-row-bias",
        "overlap-unequal", "overlap-three-chunks",
    ],
)
def test_one_tile_row_loop_matches_xla(request, Q, K, H, D, bias_shape, causal):
    """The one-tile kernels where their row loop runs more than once (over
    ``ROW_CHUNK`` query rows) for every kind of bias the BlockSpecs
    broadcast, in both operand layouts, with chunks that divide the tile
    and chunks whose last overlaps the one before: output and gradients
    against the XLA path (a row counted twice shows in dk and dv, a row
    written by the wrong chunk in dq)."""
    B = 2
    tile = fitted_block(Q)
    assert tile > _row_chunk(tile)
    overlaps = tile % _row_chunk(tile) != 0
    assert overlaps == request.node.callspec.id.startswith("overlap")
    q, k, v = rand(B, Q, H, D), rand(B, K, H, D), rand(B, K, H, D)
    bias = None if bias_shape is None else rand(*bias_shape)

    def flash(q, k, v):
        return flash_attention(q, k, v, bias, causal=causal, interpret=True)

    def xla(q, k, v):
        full = combine_biases(causal_bias(Q, K) if causal else None, bias)
        return dot_product_attention(q, k, v, full)

    _assert_output_and_grads_match(
        xla, flash, lambda out: (out ** 2).sum(), q, k, v
    )


@pytest.mark.parametrize(
    "H,D,bias_shape",
    [(4, 64, (1, 1, 1, LONG_SEQ)), (2, 128, None), (3, 64, (1, 3, 1, LONG_SEQ))],
    ids=["two-heads-a-step", "one-head-a-step", "heads-major"],
)
def test_tiled_kernels_match_xla_from_long_seq(H, D, bias_shape):
    """From ``LONG_SEQ`` the tiled kernels (``LONG_BLOCK`` x ``LONG_BLOCK``,
    the running softmax carried between key tiles, dQ and dK/dV in two
    kernels), causal, in each operand layout."""
    B, T = 1, LONG_SEQ
    q, k, v = rand(B, T, H, D), rand(B, T, H, D), rand(B, T, H, D)
    bias = None if bias_shape is None else rand(*bias_shape)

    def flash(q, k, v):
        return flash_attention(q, k, v, bias, causal=True, interpret=True)

    def xla(q, k, v):
        return dot_product_attention(q, k, v, bias, causal=True)

    _assert_output_and_grads_match(
        xla, flash, lambda out: (out ** 2).sum(), q, k, v
    )


def _flash_site(monkeypatch, H, D, T=512):
    """Trace (no compile) one call site of ``dot_product_attention`` as a
    TPU process would route it: the kernels' path for a causal T 512."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = jax.ShapeDtypeStruct((1, T, H, D), jnp.bfloat16)
    jax.eval_shape(
        lambda q, k, v: dot_product_attention(q, k, v, causal=True), x, x, x
    )


def _benchmark_reader(metric):
    """How the benchmark reads a per-layer metric: gauges are read by name."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "layer_metrics", f"{metric}.json",
    )
    with open(path) as f:
        return json.load(f)["reader"]


def test_folded_share_gauge_counts_the_flash_sites(monkeypatch):
    """``attention/flash_folded_share``: 1.0 while every traced flash site
    took the folded operands, under 1 once a site fell back to heads-major,
    and never set where no call takes the kernels."""
    from trlx_tpu.telemetry import MetricsRegistry, scoped_metrics

    def gauges(registry):
        return registry.snapshot()["gauges"]

    with scoped_metrics(MetricsRegistry()) as registry:
        q = rand(1, 16, 2, 64)
        dot_product_attention(q, q, q, causal=True)  # the XLA path
        assert "attention/flash_folded_share" not in gauges(registry)
        _flash_site(monkeypatch, 16, 64)
        _flash_site(monkeypatch, 16, 128)
        assert gauges(registry)["attention/flash_folded_share"] == 1.0
        _flash_site(monkeypatch, 3, 64)
        assert gauges(registry)["attention/flash_folded_share"] == pytest.approx(2 / 3)
        counters = registry.snapshot()["counters"]
        assert counters["attention/flash_operands{layout=folded}"] == 2
        assert counters["attention/flash_operands{layout=heads_major}"] == 1
        assert counters["attention/path{path=flash}"] == 3
    assert _benchmark_reader("attn_folded_share") == {
        "kind": "counter", "name": "attention/flash_folded_share",
    }


def test_row_chunk_gauge_holds_the_fewest_rows_of_the_one_tile_sites(monkeypatch):
    """``attention/flash_row_chunk``: the fewest query rows a loop iteration
    takes among the one-tile sites traced in the process (tldr's T 560: 288,
    longgen's T 512: 256), untouched by a site of the tiled kernels and
    never set where no call takes the one-tile kernels."""
    from trlx_tpu.telemetry import MetricsRegistry, scoped_metrics

    def gauge(registry):
        return registry.snapshot()["gauges"].get("attention/flash_row_chunk")

    with scoped_metrics(MetricsRegistry()) as registry:
        q = rand(1, 16, 2, 64)
        dot_product_attention(q, q, q, causal=True)  # the XLA path
        _flash_site(monkeypatch, 16, 64, T=LONG_SEQ)  # the tiled kernels
        assert gauge(registry) is None
        _flash_site(monkeypatch, 16, 64, T=640)
        assert gauge(registry) == 320
        _flash_site(monkeypatch, 16, 64, T=560)
        assert gauge(registry) == 288 == _row_chunk(560)
        _flash_site(monkeypatch, 16, 64, T=592)
        assert gauge(registry) == 288  # 304 rows there: the fewest stays
        _flash_site(monkeypatch, 3, 64)
        assert gauge(registry) == 256 == _row_chunk(512)
    assert _benchmark_reader("attn_row_chunk") == {
        "kind": "counter", "name": "attention/flash_row_chunk",
    }


class TestBlockHelpers:
    """flash_block_fwd/bwd — the ring-attention inner kernels — must match
    the XLA block math (including the external/combined-lse backward)."""

    def _setup(self):
        import jax.numpy as jnp

        B, Tq, Tk, H, D = 2, 24, 40, 2, 16
        q = rand(B, Tq, H, D)
        k = rand(B, Tk, H, D)
        v = rand(B, Tk, H, D)
        bias = jnp.asarray(
            np.where(RNG.random((B, 1, Tq, Tk)) < 0.15, -1e9, 0.0), jnp.float32
        )
        return q, k, v, bias

    @staticmethod
    def _xla_block_fwd(q, k, v, bias):
        import jax.numpy as jnp

        scale = 1.0 / (q.shape[-1] ** 0.5)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale + bias
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bkhd->bhqd", p / l, v)
        return o, (m + jnp.log(l))[..., 0]

    def test_block_fwd_matches_xla(self):
        from trlx_tpu.ops.flash_attention import flash_block_fwd

        q, k, v, bias = self._setup()
        o_ref, lse_ref = self._xla_block_fwd(q, k, v, bias)
        o, lse = flash_block_fwd(q, k, v, bias, block_q=16, block_k=16,
                                 interpret=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref), atol=2e-5)

    def test_block_bwd_matches_xla_with_external_lse(self):
        import jax.numpy as jnp

        from trlx_tpu.ops.flash_attention import flash_block_bwd

        q, k, v, bias = self._setup()
        o, lse = self._xla_block_fwd(q, k, v, bias)
        # shift lse as if combined with another block (external weights < 1)
        lse_ext = lse + 0.3
        do = jnp.asarray(RNG.normal(size=o.shape), jnp.float32)
        delta = jnp.sum(do * o, axis=-1)

        scale = 1.0 / (q.shape[-1] ** 0.5)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale + bias
        p = jnp.exp(s - lse_ext[..., None])
        dv_ref = jnp.einsum("bhqk,bhqd->bkhd", p, do)
        dp = jnp.einsum("bhqd,bkhd->bhqk", do, v)
        ds = p * (dp - delta[..., None]) * scale
        dq_ref = jnp.einsum("bhqk,bkhd->bqhd", ds, k)
        dk_ref = jnp.einsum("bhqk,bqhd->bkhd", ds, q)

        dq, dk, dv = flash_block_bwd(
            q, k, v, bias, o, lse_ext, do, block_q=16, block_k=16,
            interpret=True,
        )
        np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_ref), atol=2e-4)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_ref), atol=2e-4)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_ref), atol=2e-4)


class TestRouting:
    def test_learned_bias_grad_flows_on_xla_path(self):
        # dot_product_attention(learned_bias=True) must produce real bias
        # gradients on every backend.
        B, T, H, D = 1, 16, 2, 16
        q, k, v = rand(B, T, H, D), rand(B, T, H, D), rand(B, T, H, D)
        bias = rand(1, H, T, T)
        db = jax.grad(
            lambda b: (
                dot_product_attention(q, k, v, b, learned_bias=True) ** 2
            ).sum()
        )(bias)
        assert float(jnp.abs(db).max()) > 0.0

    def test_causal_flag_matches_bias_on_xla_path(self):
        B, T, H, D = 2, 24, 2, 16
        q, k, v = rand(B, T, H, D), rand(B, T, H, D), rand(B, T, H, D)
        a = dot_product_attention(q, k, v, causal_bias(T, T))
        b = dot_product_attention(q, k, v, None, causal=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_flash_decode_shape_matches_xla():
    """Q=1 (single-query decode over a cache) compiles and matches the XLA
    path. Routing stays XLA for decode — measured at the HBM roofline
    already (ROADMAP "measured, rejected") — but the kernel handling the
    shape correctly is locked in for any future fusion use."""
    q, k, v = rand(2, 1, 3, 16), rand(2, 64, 3, 16), rand(2, 64, 3, 16)
    mask = jnp.asarray((RNG.random((2, 64)) > 0.2).astype(np.int32))
    bias = padding_bias(mask)
    ref = dot_product_attention(q, k, v, bias)
    out = flash_attention(q, k, v, bias, block_q=1, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)
