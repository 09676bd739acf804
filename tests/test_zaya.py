"""The zaya family (models/zaya.py) and what it forced below it: the CCA mix
and its one-row step from a tail (ops/cca.py), a layer's cache that is keys
by position and rows by slot at once (ops/kv_cache.py), an expert layer that
takes the caller's routing with a skip among its choices (ops/moe.py), and
the engine's handling of such layers.

Everything is compared on logits (never sampled tokens) with the plain
float32 reference ``benchmark/reference/zaya.py``, which computes the
convolutions as shifted sums over the whole sequence and every expert on
every token: it shares no code with ops/cca.py, ops/ssm.py or ops/moe.py.
Tolerances: the program in float32 against the float32 reference differs by
summation order alone, 1e-5 of the logits' standard deviation (the cached
paths too: the same sums over a window of tail and column); recorded
log-probabilities 2e-5 nats, as the granite tests hold theirs.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import zaya as ref
from trlx_tpu.models.zaya import ZayaConfig, ZayaModel, init_zaya_cache
from trlx_tpu.ops import cca, moe
from trlx_tpu.ops.kv_cache import (
    DENSE,
    PAGED,
    STATE,
    CacheKind,
    cache_kind,
    identity_block_tables,
    kv_buffers,
    rotate_block_table,
    split_tail,
    state_buffers,
)

ROPE = {"hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000, "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000, "rope_type": "default"},
        "rope_type": "default"}
ARCH = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, cca_time0=2, cca_time1=2, moe_intermediate_size=32, num_experts=4,
    num_experts_per_tok=1, router_hidden_size=16, partial_rotary_factor=0.5, rope_parameters=ROPE,
    rms_norm_eps=1e-5, dtype="float32", param_dtype="float32",
)
TAIL = ("tail_c0", "tail_v", "tail_z")


def reference_cfg(**over):
    return dict(ARCH, layer_types=["hybrid"] * over.get("num_hidden_layers", ARCH["num_hidden_layers"]), **over)


@functools.lru_cache(maxsize=None)
def model_and_params(**over):
    cfg = ZayaConfig.from_dict(dict(ARCH, **over))
    model = ZayaModel(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    # move the ones and zeros (scales, biases, the temperature, the merges) off their defaults
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    leaves = [a + 0.1 * jax.random.normal(k, a.shape) for a, k in zip(leaves, keys)]
    return cfg, model, jax.tree_util.tree_unflatten(tree, leaves)


def left_padded(lens, T, seed=0, vocab=95):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, vocab, (len(lens), T)), jnp.int32)
    mask = jnp.asarray(np.stack([np.r_[np.zeros(T - n), np.ones(n)] for n in lens]), jnp.int32)
    return ids, mask


def positions_of(mask):
    return jnp.clip(jnp.cumsum(mask, axis=-1) - 1, 0, None)


def rel_err(got, want, where):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    where = np.asarray(where).astype(bool)
    return np.abs(got - want)[where].max() / want[where].std()


def paged(cache, tables):
    return tuple(dict(c, block_tables=tables) for c in cache)


# ------------------------------ the model ------------------------------ #


def test_uncached_forward_matches_the_reference_on_left_padded_rows():
    cfg, model, params = model_and_params()
    ids, mask = left_padded([21, 13, 5], 21)
    out = model.apply({"params": params}, ids, attention_mask=mask)
    want = ref.forward(params, reference_cfg(), ids, mask)
    assert rel_err(out["logits"], want, mask) < 1e-5
    stats = out["moe_stats"]
    assert set(stats) == {"experts_touched", "max_load", "rows_routed", "skip_share"}
    assert float(stats["experts_touched"]) <= 4 and 0 <= float(stats["skip_share"]) < 1


@pytest.mark.parametrize("chunks", [1, 2, 4], ids=["whole", "two-chunks", "four-chunks"])
def test_prefill_then_decode_through_the_paged_cache_matches_the_full_forward(chunks):
    cfg, model, params = model_and_params()
    T, Q, cap = 21, 16, 24
    ids, mask = left_padded([21, 13, 6], T, seed=1)
    want = ref.forward(params, reference_cfg(), ids, mask)
    tables = identity_block_tables(3, cap // 4)
    tables = tables.at[1].set(rotate_block_table(tables[1], 2))
    cache = paged(init_zaya_cache(cfg, 3, cap), tables)
    assert all(cache_kind(c).layout == PAGED and cache_kind(c).tail == TAIL for c in cache)
    pos = positions_of(mask)
    grow = lambda m: jnp.concatenate([m, jnp.zeros((3, cap - m.shape[1]), jnp.int32)], axis=1)
    W = Q // chunks
    for c in range(chunks):  # a later chunk carries on from the tail the one before left
        cols = slice(c * W, (c + 1) * W)
        out = model.apply({"params": params}, ids[:, cols], attention_mask=grow(mask[:, :Q]),
                          position_ids=pos[:, cols], cache=cache, cache_index=c * W)
        cache = out["cache"]
        assert rel_err(out["logits"], want[:, cols], mask[:, cols]) < 1e-5
    for t in range(Q, T):  # per-row targets, as the engine's decode step gives them
        out = model.apply({"params": params}, ids[:, t : t + 1], attention_mask=grow(mask[:, : t + 1]),
                          position_ids=pos[:, t : t + 1], cache=cache, cache_index=jnp.full((3,), t, jnp.int32))
        cache = out["cache"]
        assert rel_err(out["logits"][:, 0], want[:, t], mask[:, t]) < 1e-5


def test_a_parked_row_keeps_its_tail_and_a_recycled_slot_forgets_its_predecessors():
    """The engine's two conventions as the model reads them from the cache
    mask: a row whose ``cache_index`` is past the mask's width (idle or
    finished) leaves its tail bit for bit; a row with no valid column before
    the call starts from zeros whatever the slot held."""
    cfg, model, params = model_and_params()
    cap = 12
    ids, mask = left_padded([8, 5], 8, seed=2)
    grow = lambda m: jnp.concatenate([m, jnp.zeros((2, cap - m.shape[1]), jnp.int32)], axis=1)
    clean = paged(init_zaya_cache(cfg, 2, cap), identity_block_tables(2, cap // 4))
    dirty = tuple({k: (jnp.ones_like(v) * 3 if k in TAIL else v) for k, v in c.items()} for c in clean)
    a = model.apply({"params": params}, ids, attention_mask=grow(mask), cache=dirty, cache_index=0)
    b = model.apply({"params": params}, ids, attention_mask=grow(mask), cache=clean, cache_index=0)
    np.testing.assert_array_equal(np.asarray(a["logits"]), np.asarray(b["logits"]))
    step_mask = grow(jnp.concatenate([mask, jnp.ones((2, 1), jnp.int32)], axis=1))
    out = model.apply({"params": params}, ids[:, :1], attention_mask=step_mask, cache=a["cache"],
                      cache_index=jnp.asarray([8, cap], jnp.int32))
    for before, after in zip(a["cache"], out["cache"]):
        for k in TAIL:
            np.testing.assert_array_equal(np.asarray(before[k][1]), np.asarray(after[k][1]))
            assert not np.array_equal(np.asarray(before[k][0]), np.asarray(after[k][0]))
        for k in ("k", "v"):  # the parked row's write dropped
            np.testing.assert_array_equal(np.asarray(before[k][1]), np.asarray(after[k][1]))


def test_the_router_carry_reaches_the_next_layer_and_is_zeros_at_layer_zero():
    """Layer 1's carry scale moves layer 1's routing and the logits; layer
    0's multiplies zeros and moves nothing; and the reference, which threads
    the carry by hand, agrees either way."""
    cfg, model, params = model_and_params()
    ids, mask = left_padded([10, 7], 10, seed=3)

    def with_scale(layer, value):
        p = jax.tree_util.tree_map(lambda a: a, params)
        p[f"h_{layer}"]["mlp"]["router"]["carry_scale"] = jnp.full((16,), value)
        return p

    base = model.apply({"params": params}, ids, attention_mask=mask)["logits"]
    zero = model.apply({"params": with_scale(0, 5.0)}, ids, attention_mask=mask)["logits"]
    np.testing.assert_array_equal(np.asarray(zero), np.asarray(base))
    moved = with_scale(1, 5.0)
    got = model.apply({"params": moved}, ids, attention_mask=mask)["logits"]
    assert rel_err(got, base, mask) > 1e-3
    assert rel_err(got, ref.forward(moved, reference_cfg(), ids, mask), mask) < 1e-5
    # what a block hands on is r, before its norm: one block's carry by hand
    one = model_and_params(num_hidden_layers=1)
    _, state = one[1].apply({"params": one[2]}, ids, attention_mask=mask, mutable=["intermediates"])
    assert state["intermediates"]["h_0"]["mlp"]["router_choice"][0].shape == (20,)


def test_the_skip_contributes_exactly_zero_and_is_counted():
    """A balancing bias that sends every token to index ``num_experts``:
    the expert sublayer adds its merge's bias alone, whatever the experts
    hold, and ``skip_share`` is 1; sent to expert 0 instead, the output
    moves with expert 0's weights and with no other's."""
    cfg, model, params = model_and_params(num_hidden_layers=1)
    ids, mask = left_padded([9, 6], 9, seed=4)

    def biased(to, scale_expert=None):
        p = jax.tree_util.tree_map(lambda a: a, params)
        p["h_0"]["mlp"]["router"]["balance_bias"] = jnp.zeros((5,)).at[to].set(10.0)
        if scale_expert is not None:
            p["h_0"]["mlp"]["w_down"] = p["h_0"]["mlp"]["w_down"].at[scale_expert].multiply(3.0)
        return p

    skipped = model.apply({"params": biased(4)}, ids, attention_mask=mask)
    assert float(skipped["moe_stats"]["skip_share"]) == 1.0 and float(skipped["moe_stats"]["experts_touched"]) == 0.0
    for e in range(4):
        again = model.apply({"params": biased(4, scale_expert=e)}, ids, attention_mask=mask)
        np.testing.assert_array_equal(np.asarray(again["hidden"]), np.asarray(skipped["hidden"]))
    assert rel_err(skipped["logits"], ref.forward(biased(4), reference_cfg(num_hidden_layers=1), ids, mask), mask) < 1e-5
    first = model.apply({"params": biased(0)}, ids, attention_mask=mask)
    assert float(first["moe_stats"]["skip_share"]) == 0.0 and float(first["moe_stats"]["experts_touched"]) == 1.0
    assert rel_err(model.apply({"params": biased(0, 0)}, ids, attention_mask=mask)["hidden"], first["hidden"], mask) > 1e-3
    same = model.apply({"params": biased(0, 2)}, ids, attention_mask=mask)["hidden"]
    np.testing.assert_array_equal(np.asarray(same), np.asarray(first["hidden"]))
    # the bias moves the choice and not the weight: p is the softmax's own value
    routing_stats = moe.routing_stats(
        moe.Routing(jnp.zeros((3, 5)), jnp.full((3, 5), 0.2), jnp.full((3, 1), 0.2), jnp.asarray([[4], [0], [4]])),
        5, 0, 4, skip=4)
    assert float(routing_stats["skip_share"]) == pytest.approx(2 / 3) and "rows_here_share" not in routing_stats


def test_what_the_family_does_not_build_is_refused_by_name():
    for over, said in [
        ({"layer_types": ["hybrid", "hybrid_sliding", "hybrid"]}, "hybrid_sliding"),
        ({"sliding_window": 4096}, "sliding_window"),
        ({"attention_bias": True}, "attention_bias"),
        ({"lm_head_bias": True}, "lm_head_bias"),
        ({"tie_word_embeddings": False}, "tie_word_embeddings"),
        ({"num_experts_per_tok": 2}, "num_experts_per_tok"),
        ({"hidden_act": "gelu"}, "hidden_act"),
        ({"kv_cache_dtype": "int8"}, "kv_cache_dtype"),
        ({"state_dtype": "bfloat16"}, "state_dtype"),
        ({"num_key_value_heads": 1, "num_attention_heads": 4}, "even num_key_value_heads"),
        ({"rope_parameters": {"hybrid": {"rope_type": "yarn", "rope_theta": 1.0}}}, "rope_type"),
    ]:
        with pytest.raises(ValueError, match=said):
            ZayaConfig.from_dict(dict(ARCH, **over))
    assert ZayaConfig.from_dict(ARCH).rope_theta == 5e6 and ZayaConfig.from_dict(ARCH).rotary_dim == 8
    cfg, model, params = model_and_params()
    ids = jnp.zeros((2, 2), jnp.int32)
    with pytest.raises(ValueError, match="verify"):
        model.apply({"params": params}, ids, attention_mask=jnp.ones((2, 8), jnp.int32),
                    cache=init_zaya_cache(cfg, 2, 8), cache_index=jnp.zeros((2, 2), jnp.int32))
    for hook in ({"start_layer": 1}, {"hidden_override": jnp.zeros((2, 2, 64))}, {"capture_hidden_at": 1}):
        with pytest.raises(ValueError, match="router carry"):
            model.apply({"params": params}, ids, **hook)
    from trlx_tpu.models import gpt2_moe

    gpt2_moe.set_ep_mesh(jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("ep",)))
    try:
        with pytest.raises(ValueError, match="ep mesh is not built for zaya"):
            model.apply({"params": params}, ids)
    finally:
        gpt2_moe.reset()
    h = jnp.zeros((2, 3, 8))
    routing = moe.route(h.reshape(-1, 8), jnp.zeros((8, 4)), 1)
    w = jnp.zeros((4, 8, 8))
    with pytest.raises(ValueError, match="caller's own routing .* is not built on an ep mesh"):
        moe.expert_layer(h, None, w, w, w, routing=routing,
                         mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("ep",)))
    with pytest.raises(ValueError, match="not both or neither"):
        moe.expert_layer(h, jnp.zeros((8, 4)), w, w, w, k=1, routing=routing)


def test_registry_builds_the_family_and_its_cache():
    from trlx_tpu.models.registry import get_model_family

    family = get_model_family("zaya")
    cfg = family.config_cls.from_dict(ARCH)
    cache = family.init_cache(cfg, 2, 8)
    assert len(cache) == 3 and cache[0]["k"].shape == (2, 8, 2, 16)  # sized by KV heads
    assert cache[0]["tail_z"].shape == (2, 1, 96) and cache[0]["tail_c0"].shape == (2, 1, 96)
    assert cache[0]["tail_v"].shape == (2, 1, 16) and cache[0]["tail_z"].dtype == jnp.float32
    with pytest.raises(ValueError, match="no checkpoint converter"):
        family.load_checkpoint("somewhere")


def test_cache_kind_on_a_cca_layer_a_state_layer_and_a_plain_paged_layer():
    cfg = model_and_params()[0]
    layer = init_zaya_cache(cfg, 2, 8)[0]
    assert cache_kind(layer) == CacheKind(DENSE, False, False, False, TAIL)
    tables = identity_block_tables(2, 2)
    kind = cache_kind(dict(layer, block_tables=tables, slot_ids=jnp.zeros((2,), jnp.int32)))
    assert (kind.layout, kind.rows, kind.tail) == (PAGED, True, TAIL)
    kv, tail = split_tail(dict(layer, block_tables=tables))
    assert set(kv) == {"k", "v", "block_tables"} and tuple(sorted(tail)) == TAIL
    state = state_buffers(2, 4, 8, 16, 4, 32)
    assert cache_kind(state) == CacheKind(STATE, False, False, False, ("conv_tail", "ssm_state"))
    plain = dict(kv_buffers(1, 2, 8, 2, 16, jnp.bfloat16)[0], block_tables=tables)
    assert cache_kind(plain) == CacheKind(PAGED, False, False, False, ())
    assert split_tail(plain) == (plain, {})


# ----------------------------- ops/cca.py ------------------------------- #


def mix_inputs(B=2, T=12, n_q=4, n_kv=2, Dh=8, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    H = n_q + n_kv
    return dict(
        q_lat=jax.random.normal(k[0], (B, T, n_q * Dh)), k_lat=jax.random.normal(k[1], (B, T, n_kv * Dh)),
        v_lat=jax.random.normal(k[2], (B, T, n_kv * Dh)),
    ), dict(
        conv0_weight=jax.random.normal(k[3], (2, H * Dh)), conv0_bias=jax.random.normal(k[4], (H * Dh,)),
        conv1_weight=jax.random.normal(k[5], (H, 2, Dh, Dh)) * 0.3, conv1_bias=jax.random.normal(k[6], (H, Dh)),
        k_temp=1 + 0.1 * jax.random.normal(k[7], (n_kv,)), n_q=n_q, n_kv=n_kv, head_dim=Dh, dtype=jnp.float32,
    )


def zero_tail(B, H=6, Dh=8, half=1):
    return {"tail_z": jnp.zeros((B, 1, H * Dh)), "tail_c0": jnp.zeros((B, 1, H * Dh)), "tail_v": jnp.zeros((B, 1, half * Dh))}


def test_two_calls_that_carry_the_tail_equal_one():
    lat, kw = mix_inputs()
    whole = cca.cca_mix(**lat, **kw)
    assert whole[3] is None  # no tail handed in, none handed back
    cut = lambda lo, hi: {k: v[:, lo:hi] for k, v in lat.items()}
    first = cca.cca_mix(**cut(0, 7), **kw, tail=zero_tail(2))
    second = cca.cca_mix(**cut(7, 12), **kw, tail=first[3])
    for got_a, got_b, want in zip(first[:3], second[:3], whole[:3]):
        np.testing.assert_allclose(np.asarray(jnp.concatenate([got_a, got_b], 1)), np.asarray(want), rtol=1e-5, atol=1e-5)
    # and a column at a time: the one-row step from the tail
    tail, cols = zero_tail(2), []
    for t in range(12):
        q, k, v, tail = cca.cca_mix(**cut(t, t + 1), **kw, tail=tail)
        cols.append(q)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(cols, 1)), np.asarray(whole[0]), rtol=1e-5, atol=1e-5)
    # the value's second half reads the column before; the first its own
    np.testing.assert_array_equal(np.asarray(whole[2][:, 1:, 1]), np.asarray(lat["v_lat"][:, :-1, 8:]))
    np.testing.assert_array_equal(np.asarray(whole[2][:, :, 0]), np.asarray(lat["v_lat"][:, :, :8]))
    assert not np.asarray(whole[2][:, 0, 1]).any()


def test_a_masked_column_leaves_pool_and_tail_untouched():
    lat, kw = mix_inputs(T=6)
    tail = {k: jax.random.normal(jax.random.PRNGKey(i), v.shape) for i, (k, v) in enumerate(zero_tail(2).items())}
    mask = jnp.asarray([[0] * 6, [0, 0, 1, 1, 1, 1]], jnp.float32)
    masked = {k: v * mask[..., None] for k, v in lat.items()}  # a masked column's latents are zero
    _, _, _, new = cca.cca_mix(**masked, **kw, mask=mask, tail=tail)
    for k in tail:  # an all-pad row: bit for bit; the other's moved
        np.testing.assert_array_equal(np.asarray(new[k][0]), np.asarray(tail[k][0]))
        assert not np.array_equal(np.asarray(new[k][1]), np.asarray(tail[k][1]))
    np.testing.assert_array_equal(np.asarray(new["tail_z"][1, 0]),
                                  np.asarray(jnp.concatenate([lat["q_lat"], lat["k_lat"]], -1)[1, -1]))
    # a fresh row reads zeros for its tail: equal to handing in none
    fresh = cca.cca_mix(**masked, **kw, mask=mask, fresh=jnp.asarray([True, True]), tail=tail)
    clean = cca.cca_mix(**masked, **kw, mask=mask)
    np.testing.assert_array_equal(np.asarray(fresh[0][1, 2:]), np.asarray(clean[0][1, 2:]))
    # through the model: a masked step (a parked slot) writes no key and moves no tail
    cfg, model, params = model_and_params()
    cache = paged(init_zaya_cache(cfg, 2, 8), identity_block_tables(2, 2))
    out = model.apply({"params": params}, jnp.ones((2, 1), jnp.int32), attention_mask=jnp.ones((2, 8), jnp.int32),
                      cache=cache, cache_index=jnp.asarray([8, 8], jnp.int32))
    for was, now in zip(cache, out["cache"]):
        for k in was:
            np.testing.assert_array_equal(np.asarray(now[k]), np.asarray(was[k]), err_msg=k)


def test_the_grouped_convolution_is_the_shifted_sum_of_per_head_products():
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    x, w = jax.random.normal(k[0], (2, 9, 3 * 8)), jax.random.normal(k[1], (3, 3, 8, 8))
    b, tail = jax.random.normal(k[2], (3, 8)), jax.random.normal(k[3], (2, 2, 24))
    out, new_tail = cca.grouped_causal_conv(x, w, b, tail, jnp.ones((2, 9)), jnp.float32)
    padded = jnp.concatenate([tail, x], 1).reshape(2, 11, 3, 8)
    want = sum(jnp.einsum("bthd,hde->bthe", padded[:, j : j + 9], w[:, j]) for j in range(3)) + b
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(new_tail), np.asarray(x[:, -2:]))


def test_expert_layer_given_router_w_and_given_the_routing_route_makes_of_it_agree():
    D, F, E, k = 16, 8, 8, 2
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    h = jax.random.normal(keys[0], (2, 5, D))
    router = jax.random.normal(keys[1], (D, E))
    w_gate, w_up = jax.random.normal(keys[2], (E, D, F)), jax.random.normal(keys[3], (E, D, F))
    w_down = jax.random.normal(keys[4], (E, F, D))
    for held, first in ((E, 0), (4, 2)):
        sl = slice(first, first + held)
        args = (w_gate[sl], w_up[sl], w_down[sl])
        y_w, routing_w = moe.expert_layer(h, router, *args, k=k, norm_topk=True, dtype=jnp.float32, first_expert=first)
        given = moe.route(h.reshape(-1, D), router, k, norm_topk=True)
        y_r, routing_r = moe.expert_layer(h, None, *args, dtype=jnp.float32, first_expert=first, routing=given)
        np.testing.assert_array_equal(np.asarray(y_w), np.asarray(y_r))
        a, b = moe.routing_stats(routing_w, E, first, held), moe.routing_stats(routing_r, E, first, held)
        assert set(a) == set(b) and all(float(a[n]) == float(b[n]) for n in a)


def test_a_ppo_shaped_train_step_gives_finite_gradients():
    """Top-1 keeps ``p`` in the product: the router's matrices take a
    gradient through the chosen score, the chosen experts through their
    rows, the convolutions and the merges through the stream."""
    from trlx_tpu.models.heads import CausalLMWithValueHead
    from trlx_tpu.ops.moe import moe_loss_summary

    cfg = model_and_params()[0]
    model = CausalLMWithValueHead(cfg, backbone_cls=ZayaModel)
    ids, mask = left_padded([14, 9], 14, seed=6)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    params = dict(params, transformer=model_and_params()[2])
    Q = 8
    old = jax.random.normal(jax.random.PRNGKey(3), (2, 14 - Q)) * 0.1 - 4.0

    def loss(p):
        (logits, values), state = model.apply({"params": p}, ids, mask, Q, method=model.response_forward,
                                              mutable=["moe_losses"])
        lp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1), ids[:, Q:, None], -1)[..., 0]
        ratio = jnp.exp(lp - old)
        aux = moe_loss_summary(state["moe_losses"])
        return jnp.mean(-jnp.minimum(ratio, jnp.clip(ratio, 0.8, 1.2))) + jnp.mean(values ** 2) + 0.01 * aux["aux_loss"]

    value, grads = jax.jit(jax.value_and_grad(loss))(params)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert np.isfinite(float(value)) and all(np.isfinite(np.asarray(g)).all() for _, g in flat)
    named = {jax.tree_util.keystr(path): float(jnp.abs(g).sum()) for path, g in flat}
    for part in ("router']['out", "router']['down", "router']['carry_scale", "conv1_weight", "conv0_weight",
                 "k_temp", "w_down", "merge_2']['branch_scale", "wte"):
        assert any(part in name and total > 0 for name, total in named.items()), part


# ------------------------------ the engine ------------------------------ #

Q, R, EOS = 16, 6, 95


@functools.lru_cache(maxsize=None)
def engine(prefill_chunk=0, chunks_per_pump=0):
    from trlx_tpu.inference.engine import ContinuousBatchingEngine
    from trlx_tpu.models.heads import CausalLMWithValueHead
    from trlx_tpu.ops.sampling import GenerationConfig

    cfg = model_and_params()[0]
    model = CausalLMWithValueHead(cfg, backbone_cls=ZayaModel)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    params = dict(params, transformer=model_and_params()[2])

    def apply_fn(p, input_ids, attention_mask=None, position_ids=None, cache=None,
                 cache_index=None, last_only=False):
        return model.apply({"params": p}, input_ids, attention_mask=attention_mask,
                           position_ids=position_ids, cache=cache, cache_index=cache_index,
                           last_only=last_only)

    gen = GenerationConfig(max_new_tokens=R, min_new_tokens=1, eos_token_id=EOS,
                           pad_token_id=EOS, do_sample=True)
    eng = ContinuousBatchingEngine(
        apply_fn=apply_fn, init_cache_fn=functools.partial(init_zaya_cache, cfg),
        gen_config=gen, query_length=Q, vocab_size=cfg.vocab_size, num_slots=4, admit_width=2,
        harvest_width=2, block_size=4, prefill_chunk=prefill_chunk,
        prefill_chunks_per_pump=chunks_per_pump,
    )
    return eng, params


def drive(eng, params, ids, mask, pump):
    eng.start_phase(params, jax.random.PRNGKey(5))
    got = {}

    def land(group):
        arrs = {k: np.asarray(group[k]) for k in ("tokens", "response_mask", "logprobs")}
        for j, r in enumerate(group["rows"]):
            got[r] = {k: v[j] for k, v in arrs.items()}

    if not pump:
        eng.submit(ids, mask)
        for group in eng.drive(len(ids)):
            land(group)
        return got
    fed = 0
    while len(got) < len(ids):  # the serving pump: one step in flight (PR 44)
        free = eng.free_capacity
        if fed < len(ids) and free > 0:
            take = min(free, eng.admit_width, len(ids) - fed)
            eng.submit(ids[fed : fed + take], mask[fed : fed + take])
            fed += take
        for group in eng.pump():
            land(group)
    return got


@pytest.mark.parametrize("chunk,pump", [(0, False), (4, False), (4, True)],
                         ids=["whole", "chunked", "chunk-a-pump"])
def test_engine_logprobs_match_the_uncached_forward_on_the_tokens_it_drew(chunk, pump):
    """Ten requests through four slots: every slot is recycled, after
    requests of other lengths (the longest first), with whole and chunked
    admission and with the step in flight. The recorded log-probability of
    every drawn token is the reference's on [prompt; drawn tokens]."""
    eng, params = engine(chunk, 1 if pump else 0)
    lens = [16, 15, 3, 9, 2, 12, 5, 16, 4, 7]
    ids, mask = left_padded(lens, Q, seed=4)
    ids, mask = np.asarray(ids), np.asarray(mask)
    got = drive(eng, params, ids, mask, pump)
    assert sorted(got) == list(range(len(lens)))
    for r, row in got.items():
        full_ids = jnp.asarray(np.r_[ids[r], row["tokens"]])[None]
        full_mask = jnp.asarray(np.r_[mask[r], row["response_mask"]])[None]
        logits = ref.forward(params["transformer"], reference_cfg(), full_ids, full_mask)[0]
        lp = jax.nn.log_softmax(logits[Q - 1 : -1], axis=-1)
        want = np.take_along_axis(np.asarray(lp), row["tokens"][:, None], axis=1)[:, 0]
        live = row["response_mask"].astype(bool)
        np.testing.assert_allclose(row["logprobs"][live], want[live], rtol=0, atol=2e-5)
    if chunk:
        assert eng.stats.prefill_cols_skipped > 0  # all-pad chunks were not computed


@pytest.mark.parametrize("program", ["prefill", "prefill_chunk"])
def test_an_admission_leaves_every_other_slots_keys_and_tails_as_they_were(program):
    """Every layer's pool is handed to the forward whole and written where
    the group's rows lie; its tail's rows are taken and set back by slot.
    Slots 0 and 1 (a running group, two steps in) and the idle slot 2 read
    bit for bit what they read before slot 3 and a dummy are admitted, and
    slot 3 holds the keys and the tail of its prompt alone."""
    eng, params = engine(4, 1)
    cfg, _, backbone = model_and_params()
    state = eng.init_state()
    ids0, mask0 = left_padded([9, 16], Q, seed=1)
    key = jax.random.PRNGKey(5)
    state = eng.prefill_jit(params, state, jnp.asarray([0, 1], jnp.int32), ids0, mask0,
                            jnp.asarray([7, 8], jnp.int32), jnp.asarray([1, 3], jnp.int32), key)
    for _ in range(2):
        state = eng.decode_step_jit(params, state)[0]
    before = jax.device_get(jax.tree_util.tree_map(jnp.array, state))

    slot_ids = jnp.asarray([3, eng.num_slots], jnp.int32)
    turns = jnp.asarray([2, 4], jnp.int32)
    ids, mask = left_padded([13, 6], Q, seed=2)
    rows = jnp.arange(2, dtype=jnp.int32)
    if program == "prefill":
        state = eng.prefill_jit(params, state, slot_ids, ids, mask, rows, turns, key)
    else:
        for c in range(Q // 4):
            state = eng.prefill_chunk_jit(params, state, slot_ids, ids, mask, rows, turns, key,
                                          jnp.asarray(c, jnp.int32))
    after = jax.device_get(state)

    others = [0, 1, 2]
    for was, now in zip(before.cache, after.cache):
        assert set(was) == {"k", "v", "block_tables", *TAIL}
        for k in was:
            np.testing.assert_array_equal(np.asarray(now[k])[others], np.asarray(was[k])[others], err_msg=k)
    for f in dataclasses.fields(before):
        if f.name != "cache":
            np.testing.assert_array_equal(np.asarray(getattr(after, f.name))[others],
                                          np.asarray(getattr(before, f.name))[others], err_msg=f.name)
    # slot 3 against the same prompt through a dense cache of one row
    dense = init_zaya_cache(cfg, 1, eng.capacity)
    cache_mask = jnp.concatenate([mask[:1], jnp.zeros((1, R), mask.dtype)], axis=1)
    want = ZayaModel(cfg).apply(
        {"params": backbone}, ids[:1], attention_mask=cache_mask,
        position_ids=positions_of(mask[:1]), cache=dense, cache_index=0,
    )["cache"]
    nb, bs = eng.n_blocks, eng.block_size
    table = (np.arange(nb) + 2) % nb
    real = np.flatnonzero(np.asarray(mask[0]))
    phys = table[real // bs] * bs + real % bs
    for now, ref_layer in zip(after.cache, want):
        np.testing.assert_array_equal(np.asarray(now["block_tables"])[3], table)
        for k in TAIL:
            np.testing.assert_allclose(np.asarray(now[k])[3], np.asarray(ref_layer[k])[0], rtol=0, atol=2e-5, err_msg=k)
        for k in ("k", "v"):
            np.testing.assert_allclose(np.asarray(now[k])[3, phys], np.asarray(ref_layer[k])[0, real],
                                       rtol=0, atol=2e-5, err_msg=k)


def test_engine_and_fixed_sampler_refuse_what_a_tail_cannot_give():
    from trlx_tpu import telemetry
    from trlx_tpu.inference.engine import ContinuousBatchingEngine
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler

    cfg = model_and_params()[0]
    init = functools.partial(init_zaya_cache, cfg)
    common = dict(apply_fn=lambda *a, **k: None, init_cache_fn=init, gen_config=GenerationConfig(max_new_tokens=4),
                  query_length=8, vocab_size=96, num_slots=2)
    with pytest.raises(ValueError, match="prefix_pool_blocks.*tail beside its keys"):
        ContinuousBatchingEngine(**common, prefix_pool_blocks=2)
    with pytest.raises(ValueError, match="verify_step"):
        ContinuousBatchingEngine(**common, spec_max_draft=2)
    eng = ContinuousBatchingEngine(**common)
    state = jax.eval_shape(eng._make_state)
    assert all("block_tables" in c and cache_kind(c).tail == TAIL for c in state.cache)
    gauges = telemetry.get_metrics().snapshot()["gauges"]
    assert gauges["cache/tail_gb"] == pytest.approx(3 * 2 * (96 + 96 + 16) * 4 / 1e9)
    assert gauges["cache/kv_gb"] == pytest.approx(3 * 2 * 2 * 12 * 2 * 16 * 4 / 1e9)
    assert gauges["cache/state_gb"] == 0.0
    sampler = make_sampler(lambda *a, **k: None, init, GenerationConfig(max_new_tokens=4), 8, with_values=False)
    with pytest.raises(ValueError, match="a tail beside its keys .zaya. samples through rollout.engine: continuous"):
        sampler(None, jnp.zeros((2, 8), jnp.int32), jnp.ones((2, 8), jnp.int32), jax.random.PRNGKey(0))


def test_which_paths_the_engines_programs_traced():
    """Counted per traced call site: the decode step reads every layer's
    pool as stored (``paged``) and steps its mix from the tail; an admission
    program addresses its group's rows inside the whole pool
    (``paged_rows``), none left under ``generic``; the device scopes that
    docs/observability.md names are in the lowered programs."""
    from trlx_tpu import telemetry

    eng, params = engine.__wrapped__(4, 1)  # its own: a program traced before counts nothing again
    cfg = model_and_params()[0]
    L = cfg.num_hidden_layers
    with telemetry.scoped_metrics() as reg:
        state = jax.eval_shape(eng._make_state)
        abstract = jax.eval_shape(lambda: params)
        step = eng.decode_step_jit.lower(abstract, state)
        after_step = dict(reg.snapshot()["counters"])
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        chunk = eng.prefill_chunk_jit.lower(abstract, state, i32(2), i32(2, Q), i32(2, Q), i32(2), i32(2),
                                            jax.ShapeDtypeStruct((2,), jnp.uint32), i32())
        after_chunk = reg.snapshot()["counters"]
    step_text, chunk_text = step.as_text(debug_info=True), chunk.as_text(debug_info=True)
    for scope in ("cca_proj", "cca_mix", "cca_attn", "cca_out", "moe_router", "moe_dispatch", "moe_experts", "moe_combine"):
        assert scope in step_text and scope in chunk_text, scope
    assert after_step["cca/path{path=step}"] == L and "cca/path{path=prefill}" not in after_step
    assert after_step["attention/decode_path{path=paged}"] == L
    assert after_chunk["cca/path{path=prefill}"] == L and after_chunk["cca/path{path=step}"] == L
    assert after_chunk["attention/decode_path{path=paged_rows}"] == L
    assert "attention/decode_path{path=generic}" not in after_chunk
