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
log-probabilities 2e-5 nats, as the granite tests hold theirs. Programs, engine
and the tests every family is held to come from ``tests/family_harness.py``.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import zaya as ref
from family_harness import (  # noqa: F401  (the contract tests run here, on FAMILY)
    Q,
    R,
    Family,
    admit_beside_a_running_group,
    engine,
    grow,
    left_padded,
    model_and_params,
    paged,
    positions_of,
    programs,
    refused,
    rel_err,
    slot_3_rows,
    test_engine_logprobs_match_the_uncached_forward_on_the_tokens_it_drew,
    test_registry_builds_the_family_and_its_cache_by_kind,
    test_uncached_forward_matches_the_reference_on_left_padded_rows,
    test_what_the_family_does_not_build_is_refused_by_name,
    test_which_paths_the_engines_programs_traced,
    under_a_value_head,
)
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
TOL = 1e-5


def reference_cfg(cfg=None, **over):
    return dict(ARCH, layer_types=["hybrid"] * over.get("num_hidden_layers", ARCH["num_hidden_layers"]), **over)


def check_forward(cfg, params, out):
    stats = out["moe_stats"]
    assert set(stats) == {"experts_touched", "max_load", "rows_routed", "skip_share"}
    assert float(stats["experts_touched"]) <= 4 and 0 <= float(stats["skip_share"]) < 1


def refuse_more(cfg, model, params):
    from trlx_tpu.models import gpt2_moe

    assert ZayaConfig.from_dict(ARCH).rope_theta == 5e6 and ZayaConfig.from_dict(ARCH).rotary_dim == 8
    ids = jnp.zeros((2, 2), jnp.int32)
    apply = functools.partial(model.apply, {"params": params}, ids)
    refused("verify", apply, attention_mask=jnp.ones((2, 8), jnp.int32),
            cache=init_zaya_cache(cfg, 2, 8), cache_index=jnp.zeros((2, 2), jnp.int32))
    for hook in ({"start_layer": 1}, {"hidden_override": jnp.zeros((2, 2, 64))}, {"capture_hidden_at": 1}):
        refused("router carry", apply, **hook)
    gpt2_moe.set_ep_mesh(jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("ep",)))
    try:
        refused("ep mesh is not built for zaya", apply)
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


def check_registry(family, cfg, cache):
    assert len(cache) == 3 and cache[0]["k"].shape == (2, 8, 2, 16)  # sized by KV heads
    assert cache[0]["tail_z"].shape == (2, 1, 96) and cache[0]["tail_c0"].shape == (2, 1, 96)
    assert cache[0]["tail_v"].shape == (2, 1, 16) and cache[0]["tail_z"].dtype == jnp.float32
    with pytest.raises(ValueError, match="no checkpoint converter"):
        family.load_checkpoint("somewhere")


def check_paths(t):
    """The decode step reads every layer's pool as stored (``paged``) and
    steps its mix from the tail; an admission program addresses its
    group's rows inside the whole pool (``paged_rows``)."""
    L = t.cfg.num_hidden_layers
    for scope in ("cca_proj", "cca_mix", "cca_attn", "cca_out", "moe_router", "moe_dispatch", "moe_experts", "moe_combine"):
        assert scope in t.step_text and scope in t.chunk_text, scope
    assert t.after_step["cca/path{path=step}"] == L and "cca/path{path=prefill}" not in t.after_step
    assert t.after_step["attention/decode_path{path=paged}"] == L
    assert t.counters["cca/path{path=prefill}"] == L and t.counters["cca/path{path=step}"] == L
    assert t.counters["attention/decode_path{path=paged_rows}"] == L


FAMILY = Family(
    name="zaya", config_cls=ZayaConfig, model_cls=ZayaModel, reference=ref, arch=ARCH,
    reference_cfg=reference_cfg, init_cache=init_zaya_cache, tol=TOL, logprob_tol=2e-5,
    cache_layouts=(DENSE,) * 3,
    refusals={"zaya": [
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
    ]},
    engine_cases={"whole": (0, False, {}), "chunked": (4, False, {}), "chunk-a-pump": (4, True, {})},
    check_forward=check_forward, check_paths=check_paths, check_registry=check_registry, refuse_more=refuse_more,
)


# ------------------------------ the model ------------------------------ #


@pytest.mark.parametrize("chunks", [1, 2, 4], ids=["whole", "two-chunks", "four-chunks"])
def test_prefill_then_decode_through_the_paged_cache_matches_the_full_forward(chunks):
    cfg, model, params = model_and_params(FAMILY)
    T, cap = 21, 24
    ids, mask = left_padded([21, 13, 6], T, seed=1)
    _, cached, reference = programs(FAMILY)
    want = reference(params, ids, mask)
    cache = paged(FAMILY, cfg, 3, cap, rotate=1)
    assert all(cache_kind(c).layout == PAGED and cache_kind(c).tail == TAIL for c in cache)
    pos = positions_of(mask)
    W = Q // chunks
    for c in range(chunks):  # a later chunk carries on from the tail the one before left
        cols = slice(c * W, (c + 1) * W)
        out = cached(params, ids[:, cols], grow(mask[:, :Q], cap), cache, 0 if chunks == 1 else jnp.asarray(c * W),
                     pos[:, cols])
        cache = out["cache"]
        assert rel_err(out["logits"], want[:, cols], mask[:, cols]) < TOL
    for t in range(Q, T):  # per-row targets, as the engine's decode step gives them
        out = cached(params, ids[:, t : t + 1], grow(mask[:, : t + 1], cap), cache, jnp.full((3,), t, jnp.int32),
                     pos[:, t : t + 1])
        cache = out["cache"]
        assert rel_err(out["logits"][:, 0], want[:, t], mask[:, t]) < TOL


def test_a_parked_row_keeps_its_tail_and_a_recycled_slot_forgets_its_predecessors():
    """The engine's two conventions as the model reads them from the cache
    mask: a row whose ``cache_index`` is past the mask's width (idle or
    finished) leaves its tail bit for bit; a row with no valid column before
    the call starts from zeros whatever the slot held."""
    cfg, model, params = model_and_params(FAMILY)
    cap = 12
    ids, mask = left_padded([8, 5], 8, seed=2)
    clean = paged(FAMILY, cfg, 2, cap)
    dirty = tuple({k: (jnp.ones_like(v) * 3 if k in TAIL else v) for k, v in c.items()} for c in clean)
    cached = programs(FAMILY)[1]
    a = cached(params, ids, grow(mask, cap), dirty, 0)
    b = cached(params, ids, grow(mask, cap), clean, 0)
    np.testing.assert_array_equal(np.asarray(a["logits"]), np.asarray(b["logits"]))
    step_mask = grow(jnp.concatenate([mask, jnp.ones((2, 1), jnp.int32)], axis=1), cap)
    out = cached(params, ids[:, :1], step_mask, a["cache"], jnp.asarray([8, cap], jnp.int32))
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
    cfg, model, params = model_and_params(FAMILY)
    ids, mask = left_padded([10, 7], 10, seed=3)
    forward, _, reference = programs(FAMILY)

    def with_scale(layer, value):
        p = jax.tree_util.tree_map(lambda a: a, params)
        p[f"h_{layer}"]["mlp"]["router"]["carry_scale"] = jnp.full((16,), value)
        return p

    base = forward(params, ids, mask)["logits"]
    zero = forward(with_scale(0, 5.0), ids, mask)["logits"]
    np.testing.assert_array_equal(np.asarray(zero), np.asarray(base))
    moved = with_scale(1, 5.0)
    got = forward(moved, ids, mask)["logits"]
    assert rel_err(got, base, mask) > 1e-3
    assert rel_err(got, reference(moved, ids, mask), mask) < TOL
    # what a block hands on is r, before its norm: one block's carry by hand
    _, one, one_params = model_and_params(FAMILY, num_hidden_layers=1)
    _, state = jax.jit(lambda p: one.apply({"params": p}, ids, attention_mask=mask, mutable=["intermediates"]))(one_params)
    assert state["intermediates"]["h_0"]["mlp"]["router_choice"][0].shape == (20,)


def test_the_skip_contributes_exactly_zero_and_is_counted():
    """A balancing bias that sends every token to index ``num_experts``:
    the expert sublayer adds its merge's bias alone, whatever the experts
    hold, and ``skip_share`` is 1; sent to expert 0 instead, the output
    moves with expert 0's weights and with no other's."""
    cfg, model, params = model_and_params(FAMILY, num_hidden_layers=1)
    ids, mask = left_padded([9, 6], 9, seed=4)
    forward, _, reference = programs(FAMILY, num_hidden_layers=1)

    def biased(to, scale_expert=None):
        p = jax.tree_util.tree_map(lambda a: a, params)
        p["h_0"]["mlp"]["router"]["balance_bias"] = jnp.zeros((5,)).at[to].set(10.0)
        if scale_expert is not None:
            p["h_0"]["mlp"]["w_down"] = p["h_0"]["mlp"]["w_down"].at[scale_expert].multiply(3.0)
        return p

    skipped = forward(biased(4), ids, mask)
    assert float(skipped["moe_stats"]["skip_share"]) == 1.0 and float(skipped["moe_stats"]["experts_touched"]) == 0.0
    for e in range(4):
        again = forward(biased(4, scale_expert=e), ids, mask)
        np.testing.assert_array_equal(np.asarray(again["hidden"]), np.asarray(skipped["hidden"]))
    assert rel_err(skipped["logits"], reference(biased(4), ids, mask), mask) < TOL
    first = forward(biased(0), ids, mask)
    assert float(first["moe_stats"]["skip_share"]) == 0.0 and float(first["moe_stats"]["experts_touched"]) == 1.0
    assert rel_err(forward(biased(0, 0), ids, mask)["hidden"], first["hidden"], mask) > 1e-3
    same = forward(biased(0, 2), ids, mask)["hidden"]
    np.testing.assert_array_equal(np.asarray(same), np.asarray(first["hidden"]))
    # the bias moves the choice and not the weight: p is the softmax's own value
    routing_stats = moe.routing_stats(
        moe.Routing(jnp.zeros((3, 5)), jnp.full((3, 5), 0.2), jnp.full((3, 1), 0.2), jnp.asarray([[4], [0], [4]])),
        5, 0, 4, skip=4)
    assert float(routing_stats["skip_share"]) == pytest.approx(2 / 3) and "rows_here_share" not in routing_stats


def test_cache_kind_on_a_cca_layer_a_state_layer_and_a_plain_paged_layer():
    cfg = model_and_params(FAMILY)[0]
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
    mix = jax.jit(lambda columns, tail=None: cca.cca_mix(**columns, **kw, tail=tail))  # one program a width
    whole = mix(lat)
    assert whole[3] is None  # no tail handed in, none handed back
    cut = lambda lo, hi: {k: v[:, lo:hi] for k, v in lat.items()}
    first = mix(cut(0, 7), zero_tail(2))
    second = mix(cut(7, 12), first[3])
    for got_a, got_b, want in zip(first[:3], second[:3], whole[:3]):
        np.testing.assert_allclose(np.asarray(jnp.concatenate([got_a, got_b], 1)), np.asarray(want), rtol=1e-5, atol=1e-5)
    # and a column at a time: the one-row step from the tail
    tail, cols = zero_tail(2), []
    for t in range(12):
        q, k, v, tail = mix(cut(t, t + 1), tail)
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
    cfg, model, params = model_and_params(FAMILY)
    cache = paged(FAMILY, cfg, 2, 8)
    out = programs(FAMILY)[1](params, jnp.ones((2, 1), jnp.int32), jnp.ones((2, 8), jnp.int32), cache,
                              jnp.asarray([8, 8], jnp.int32))
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
    from trlx_tpu.ops.moe import moe_loss_summary

    model, params = under_a_value_head(FAMILY)
    ids, mask = left_padded([14, 9], 14, seed=6)
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

@pytest.mark.parametrize("program", ["prefill", "prefill_chunk"])
def test_an_admission_leaves_every_other_slots_keys_and_tails_as_they_were(program):
    """Every layer's pool is handed to the forward whole and written where
    the group's rows lie; its tail's rows are taken and set back by slot.
    Slots 0 and 1 (a running group, two steps in) and the idle slot 2 read
    bit for bit what they read before slot 3 and a dummy are admitted, and
    slot 3 holds the keys and the tail of its prompt alone."""
    eng, params = engine(FAMILY, 4, 1)
    cfg, _, backbone = model_and_params(FAMILY)
    before, after, ids, mask = admit_beside_a_running_group(eng, params, program)
    assert all(set(was) == {"k", "v", "block_tables", *TAIL} for was in before.cache)
    # slot 3 against the same prompt through a dense cache of one row
    dense = init_zaya_cache(cfg, 1, eng.capacity)
    cache_mask = jnp.concatenate([mask[:1], jnp.zeros((1, R), mask.dtype)], axis=1)
    want = programs(FAMILY)[1](backbone, ids[:1], cache_mask, dense, 0, positions_of(mask[:1]))["cache"]
    table, real, phys = slot_3_rows(eng, mask)
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

    cfg = model_and_params(FAMILY)[0]
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
