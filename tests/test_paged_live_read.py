"""The decode step's read of a paged pool by its live chunks
(`ops/paged_live_read.py`, `ops/kv_cache.py::live_chunks`,
`ops/attention.py::decode_attention` `path=paged`).

The kernel (interpret mode here) against `dot_product_attention` over the
whole pool: a position outside a live chunk weighs exactly 0 in the whole
read's float32 softmax, so the two differ by the order of float32 sums.
Shapes are small (interpret mode is slow) and keep what the kernel needs:
bfloat16 pools, heads of 128, blocks of 16. The kernel is taken through
`_live_chunks_read` directly, so that grouped heads are held to the same
numbers though `decode_attention` sends only thick positions there.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu import telemetry
from trlx_tpu.ops import attention
from trlx_tpu.ops.attention import NEG_INF, decode_attention, dot_product_attention
from trlx_tpu.ops.kv_cache import (
    identity_block_tables,
    live_chunk_positions,
    live_chunks,
    paged_write_read,
    reads_live_chunks,
    rotate_block_table,
    stored_order_bias,
)

BLOCK, DH = 16, 128


def span_mask(C, spans):
    """``[B, C]`` 0/1: row ``b`` valid on ``spans[b]`` (lo, hi), hi excluded."""
    mask = np.zeros((len(spans), C), np.int32)
    for b, (lo, hi) in enumerate(spans):
        mask[b, lo:hi] = 1
    return mask


def call(C, H, H_kv, spans, index, turns=None, fill=None, seed=0):
    """One decode call: pools of seeded values (``fill``: a function of the
    stored-order validity ``[B, C]`` that spoils the pools first), the
    logical mask of ``spans`` with the new position's column valid, tables
    rotated by ``turns`` blocks a slot. Returns the jitted call's output,
    the whole read of the pools it left, and the cache."""
    B = len(spans)
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, 1, H, DH)), jnp.bfloat16)
    new = jnp.asarray(rng.normal(size=(2, B, 1, H_kv, DH)), jnp.bfloat16)
    pools = rng.normal(size=(2, B, C, H_kv, DH)).astype(np.float32)
    tables = identity_block_tables(B, C // BLOCK)
    if turns is not None:
        tables = jnp.stack([rotate_block_table(t, n) for t, n in zip(tables, turns)])
    index = jnp.asarray(index, jnp.int32)
    mask = span_mask(C, spans)
    mask[np.arange(B)[index < C], np.asarray(index)[index < C]] = 1
    bias = attention.padding_bias(jnp.asarray(mask))
    stored = np.asarray(stored_order_bias(tables, bias))[:, 0, 0] > NEG_INF / 2
    if fill is not None:
        pools = fill(pools, stored)
    cache = {
        "k": jnp.asarray(pools[0], jnp.bfloat16),
        "v": jnp.asarray(pools[1], jnp.bfloat16),
        "block_tables": tables,
    }

    @jax.jit
    def read(q, k_new, v_new, cache, index, bias):
        """``decode_attention``'s ``paged`` branch with the read by live
        chunks taken whatever the pool's shape (the dispatch sends only
        thick positions there: `test_which_pools_are_read_by_live_chunks`)."""
        k, v, new_kv = paged_write_read(cache, k_new, v_new, index, q.dtype, as_stored=True)
        stored = stored_order_bias(cache["block_tables"], bias)
        return attention._live_chunks_read(q, k, v, stored, cache, index, None), new_kv

    out, new_kv = read(q, new[0], new[1], cache, index, bias)
    clean = lambda a: jnp.where(jnp.isnan(a), 0, a)  # noqa: E731
    whole = dot_product_attention(
        q, clean(new_kv["k"]), clean(new_kv["v"]), stored_order_bias(tables, bias)
    )
    return np.asarray(out, np.float32), np.asarray(whole, np.float32), cache, index, bias


def outside_live_chunks(chunk):
    def fill(pools, stored):
        """NaN wherever no live position shares the chunk: an unwritten
        column the read must never fetch."""
        B, C = stored.shape
        live = stored.reshape(B, C // chunk, chunk).any(-1)
        pools[:, ~np.repeat(live, chunk, axis=1)] = np.nan
        return pools

    return fill


CASES = {
    # pythia's and OLMoE's heads at a small batch and capacity
    "equal-heads-16": dict(C=256, H=16, H_kv=16, spans=[(100, 200), (0, 40)], index=[200, 40]),
    # zaya's: 8 query heads over 2 KV heads
    "grouped-8-over-2": dict(C=256, H=8, H_kv=2, spans=[(100, 200), (0, 40)], index=[200, 40]),
    # a recycled slot's table: live positions are no prefix of the pool
    "rotated-tables": dict(
        C=384, H=8, H_kv=2, spans=[(96, 130), (96, 300), (0, 5)], index=[130, 300, 5],
        turns=[5, 17, 1],
    ),
    # a left-padded prompt whose padding chunks were never written
    "left-padded-unwritten": dict(
        C=384, H=4, H_kv=4, spans=[(250, 256), (200, 256)], index=[256, 300],
        fill=outside_live_chunks(128),
    ),
    "one-live-position": dict(C=256, H=4, H_kv=2, spans=[(0, 0), (255, 255)], index=[77, 255]),
    "every-chunk-live": dict(C=256, H=4, H_kv=4, spans=[(0, 255), (3, 250)], index=[255, 250]),
}


@pytest.mark.parametrize("name", list(CASES))
def test_the_live_chunks_read_is_the_whole_read_in_another_order(name):
    out, whole, cache, index, bias = call(**CASES[name])
    assert np.isfinite(out).all()
    # bfloat16 outputs of float32 sums in another order: a last bit
    np.testing.assert_allclose(out, whole, atol=2e-2, rtol=2e-2)
    assert np.abs(out - whole).mean() < 1e-3


def test_a_slot_at_the_sentinel_is_never_fetched_and_reads_zeros():
    """`cache_index == capacity` is the engine's row nobody reads: no live
    chunk whatever mask it carries, zeros out, and its pool (NaN here)
    untouched; the live slot beside it is the whole read's."""
    C = 256

    def fill(pools, stored):
        pools[:, 1] = np.nan
        return pools

    out, whole, cache, index, bias = call(
        C=C, H=4, H_kv=2, spans=[(10, 60), (0, 200)], index=[60, C], fill=fill
    )
    live = live_chunks(
        stored_order_bias(cache["block_tables"], bias), index,
        live_chunk_positions(cache), NEG_INF / 2,
    )
    assert live.counts.tolist() == [1, 0]
    assert live.n_slots.tolist() == [1] and live.slots.tolist()[0] == 0
    np.testing.assert_array_equal(out[1], 0.0)
    np.testing.assert_allclose(out[0], whole[0], atol=2e-2, rtol=2e-2)


def test_a_masked_position_inside_a_live_chunk_weighs_exactly_zero():
    """Values of 1e4 under the mask inside the one live chunk: any weight at
    all would show; the result is the whole read's to a last bit."""

    def fill(pools, stored):
        pools[1][~stored] = 1e4
        return pools

    out, whole, *_ = call(C=128, H=4, H_kv=4, spans=[(40, 50), (0, 3)], index=[50, 3], fill=fill)
    assert np.abs(out).max() < 10
    np.testing.assert_allclose(out, whole, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("H_kv,read", [(16, "live_chunks"), (2, "whole")], ids=["thick", "thin"])
def test_decode_attention_sends_a_thick_position_to_the_kernel_and_counts_it(H_kv, read):
    """The `paged` branch counts every call as before and, beside it, which
    read the call took: 16 KV heads of 128 (4 KiB a position) the kernel, 2
    (zaya's, nemotron's: 512 B) XLA's whole read; both are the same numbers."""
    B, C = 2, 256
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, 1, 16, DH)), jnp.bfloat16)
    new = jnp.asarray(rng.normal(size=(B, 1, H_kv, DH)), jnp.bfloat16)
    pool = jnp.asarray(rng.normal(size=(B, C, H_kv, DH)), jnp.bfloat16)
    cache = {"k": pool, "v": pool, "block_tables": identity_block_tables(B, C // BLOCK)}
    index = jnp.asarray([200, 17], jnp.int32)
    bias = attention.causal_bias(1, C, offset=index)
    telemetry.get_metrics().clear()

    def decode(*call):  # a function of this case's: jax keeps traces by function
        return decode_attention(*call)

    out, new_kv = jax.jit(decode)(q, new, new, cache, index, bias)
    counters = telemetry.get_metrics().snapshot()["counters"]
    assert counters.get("attention/decode_path{path=paged}") == 1
    assert counters.get("attention/paged_read{read=%s}" % read) == 1
    assert sum(v for k, v in counters.items() if k.startswith("attention/paged_read")) == 1
    whole = dot_product_attention(q, new_kv["k"], new_kv["v"], bias)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(whole, np.float32), atol=2e-2, rtol=2e-2
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_chunk_lists_are_numpys(seed):
    rng = np.random.default_rng(seed)
    B, C, chunk = 6, 640, 128
    mask = rng.random((B, C)) < rng.choice([0.0, 0.002, 0.5], size=(B, 1))
    index = rng.choice([C, 17], size=B)
    bias = jnp.where(jnp.asarray(mask)[:, None, None, :], 0.0, NEG_INF)
    live = live_chunks(bias, jnp.asarray(index, jnp.int32), chunk, NEG_INF / 2)
    want = mask.reshape(B, C // chunk, chunk).any(-1) & (index < C)[:, None]
    assert live.counts.tolist() == want.sum(1).tolist()
    for b in range(B):
        n = int(want[b].sum())
        assert live.chunks[b, :n].tolist() == np.flatnonzero(want[b]).tolist()
    slots = np.flatnonzero(want.any(1))
    assert live.n_slots.tolist() == [len(slots)]
    assert live.slots[: len(slots)].tolist() == slots.tolist()
    assert float(live.share) == pytest.approx(want.mean())
    assert live.chunks.dtype == live.counts.dtype == live.slots.dtype == jnp.int32


@pytest.mark.parametrize(
    "pool,head,dtype,extra,takes",
    [
        ((32, 640, 16, 128), 128, jnp.bfloat16, {}, True),  # pythia, OLMoE
        ((32, 640, 8, 128), 128, jnp.bfloat16, {}, False),  # granite: 2 KiB a position
        ((32, 1024, 2, 128), 128, jnp.bfloat16, {}, False),  # zaya, nemotron: 512 B
        ((128, 1024, 4, 128), 256, jnp.bfloat16, {}, False),  # qwen3-next: a head in two lane rows
        ((8, 64, 2, 64), 64, jnp.bfloat16, {}, False),  # gpt2: half a lane row
        ((8, 640, 16, 128), 128, jnp.float32, {}, False),
        ((8, 640, 16, 128), 128, jnp.int8, {"k_scale": (8, 640, 16, 1)}, False),
        ((8, 640, 16, 128), 128, jnp.bfloat16, {"shared_tables": (8, 40)}, False),
    ],
    ids=["heads-16", "heads-8", "heads-2", "lane-rows", "narrow-head", "float32", "int8", "shared-prefix"],
)
def test_which_pools_are_read_by_live_chunks(pool, head, dtype, extra, takes):
    sds = jax.ShapeDtypeStruct
    layer = {
        "k": sds(pool, dtype), "v": sds(pool, dtype),
        "block_tables": sds((pool[0], pool[1] // BLOCK), jnp.int32),
        **{k: sds(shape, jnp.bfloat16) for k, shape in extra.items()},
    }
    assert reads_live_chunks(layer, head) is takes
    latent = {"k": sds((8, 640, 1, 640), jnp.bfloat16), "block_tables": layer["block_tables"]}
    assert not reads_live_chunks(latent, 640)


# ------------------------------ the engine ------------------------------ #


def olmoe_config():
    from trlx_tpu.data.configs import TRLConfig

    return TRLConfig.from_dict({
        # OLMoE's attention at its cell's widths (16 heads of 128) over a toy
        # expert layer, whose statistics ride the same poll as the chunk share
        "model": {"model_type": "olmoe", "model_arch": dict(
            vocab_size=64, max_position_embeddings=512, hidden_size=2048, num_hidden_layers=1,
            num_attention_heads=16, num_key_value_heads=16, intermediate_size=32, num_experts=4,
            num_experts_per_tok=2, norm_topk_prob=False, rms_norm_eps=1e-5, rope_theta=10000.0)},
        "train": {
            "seq_length": 240, "batch_size": 4, "epochs": 1, "total_steps": 1,
            "eval_interval": 1000, "checkpoint_interval": 100000,
            "mesh": {"dp": 1, "fsdp": 1, "tp": 1}, "dtype": "bfloat16",
            "rollout": {"slots": 4, "admit_width": 2, "harvest_width": 2, "block_size": BLOCK},
        },
        "method": {
            "name": "PPOConfig", "num_rollouts": 4, "chunk_size": 4, "ppo_epochs": 1,
            "gen_kwargs": {"max_new_tokens": 16, "do_sample": False,
                           "eos_token_id": 62, "pad_token_id": 63},
        },
    })


def served(monkeypatch, whole: bool):
    """Greedy tokens and log-probabilities of three requests through a
    server of four slots in groups of two: the third request's group is
    filled with a placeholder that finishes at once and waits for it, and
    the first group's slots, harvested, idle beside them; prompts are
    left-padded to 240 of a capacity of 256, so a live row's first chunk of
    128 is all padding. ``whole``: the read taken by calling the whole
    read's function where the program would take the kernel."""
    from trlx_tpu.inference.server import InferenceServer

    import trlx_tpu.parallel as parallel

    telemetry.get_metrics().clear()
    with monkeypatch.context() as patch:
        # one device of the test process's eight, as a serve cell's chip: a
        # program on several keeps the whole read
        make_mesh = parallel.make_mesh
        patch.setattr(parallel, "make_mesh", lambda config: make_mesh(config, jax.devices()[:1]))
        if whole:
            patch.setattr(
                attention, "_live_chunks_read",
                lambda q, k, v, bias, cache_kv, cache_index, scale:
                    dot_product_attention(q, k, v, bias, scale=scale),
            )
        server = InferenceServer(olmoe_config(), seed=5)
        rng = np.random.default_rng(3)
        rids = server.submit([list(rng.integers(1, 60, n)) for n in (5, 40, 9)])
        results = server.wait(rids)
    registry = telemetry.get_metrics().snapshot()
    return (
        [results[r]["tokens"] for r in rids],
        [np.asarray(results[r]["logprobs"], np.float32) for r in rids],
        registry,
    )


def test_a_mixed_batch_streams_what_the_whole_read_streams(monkeypatch):
    tokens, logprobs, registry = served(monkeypatch, whole=False)
    w_tokens, w_logprobs, w_registry = served(monkeypatch, whole=True)
    assert tokens == w_tokens
    for got, want in zip(logprobs, w_logprobs):
        np.testing.assert_allclose(got, want, atol=2e-2)
    # the one layer's site took the kernel, and the branch counts as before
    for counters in (registry["counters"], w_registry["counters"]):
        assert counters["attention/paged_read{read=live_chunks}"] == 1
        assert counters["attention/decode_path{path=paged}"] == 1
    # capacity 256 in chunks of 128: a live slot reads the second of its two
    # chunks, a finished or idle slot none
    share = registry["gauges"]["attention/paged_chunks_read_share"]
    assert 0.0 < share < 0.5
    assert 0.0 < registry["gauges"]["moe/experts_touched"] <= 4.0
