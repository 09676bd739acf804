"""The deepseek_v3 family (models/deepseek_v3.py) and what it forced below
it: a latent cache of one row a position and no values (ops/kv_cache.py),
its two reads, published and absorbed (ops/attention.py), YaRN frequencies
(ops/rotary.py), a sigmoid group-limited router handed to the expert layer
as a finished routing over a share of the experts (ops/moe.py), and the
engine's handling of such a cache.

Everything is compared on logits (never sampled tokens) with the plain
float32 reference ``benchmark/reference/deepseek_v3.py``, which knows the
published (decompressed) form alone, rotates pairs by hand, limits groups
with sorts and computes every held expert on every token: it shares no code
with ops/. Tolerances: the program in float32 against the float32 reference
differs by summation order alone, 1e-5 of the logits' standard deviation
(the cached paths too, absorbed or not); recorded log-probabilities 2e-5
nats, as the other families' tests hold theirs. Programs, engine and the tests
every family is held to come from ``tests/family_harness.py``.
"""

import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import deepseek_v3 as ref
from family_harness import (  # noqa: F401  (the contract tests run here, on FAMILY; tests/test_pool_layout.py takes EOS, Q, R from here)
    EOS,
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
)
from trlx_tpu.models.deepseek_v3 import (
    DeepseekV3Config,
    DeepseekV3Model,
    DeepseekV3SparseMLP,
    init_deepseek_v3_cache,
)
from trlx_tpu.ops import moe
from trlx_tpu.ops.attention import Latent, decode_attention, latent_attention
from trlx_tpu.ops.kv_cache import (
    DENSE,
    PAGED,
    CacheKind,
    cache_kind,
    identity_block_tables,
    kv_buffers,
    latent_buffers,
    paged_write_read,
    rotate_block_table,
    writes_whole_blocks,
)
from trlx_tpu.ops.rotary import rotary_angles, yarn_frequencies, yarn_score_scale

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn"}
ARCH = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
    v_head_dim=12, intermediate_size=96, moe_intermediate_size=32, n_routed_experts=16,
    n_shared_experts=1, num_experts_per_tok=4, n_group=4, topk_group=2, routed_scaling_factor=2.5,
    rope_scaling=YARN, rope_theta=10000.0, rms_norm_eps=1e-6, dtype="float32", param_dtype="float32",
)
WIDTH = 16 + 8  # a cached row: [c_kv | k_r]
TOL = 1e-5


def check_forward(cfg, params, out):
    assert float(jnp.abs(params["h_1"]["mlp"]["router_bias"]).min()) > 0  # a selection bias off zero
    stats = out["moe_stats"]
    assert set(stats) == {"experts_touched", "max_load", "rows_routed"}  # every expert held: no share to report
    assert float(stats["experts_touched"]) <= 16 and float(stats["rows_routed"]) == 2 * 3 * 21 * 4


def refuse_more(cfg, model, params):
    from trlx_tpu.models import gpt2_moe

    assert cfg.latent_width == WIDTH and cfg.num_router_experts == 16
    ids = jnp.zeros((2, 2), jnp.int32)
    apply = functools.partial(model.apply, {"params": params}, ids)
    refused("verify", apply, attention_mask=jnp.ones((2, 8), jnp.int32),
            cache=paged(FAMILY, cfg, 2, 8), cache_index=jnp.zeros((2, 2), jnp.int32))
    for hook in ({"start_layer": 1}, {"hidden_override": jnp.zeros((2, 2, 64))}, {"capture_hidden_at": 1}):
        refused("hydra branch .* is not built for deepseek_v3", apply, **hook)
    # a latent cache is read through a paged pool, and only a latent cache takes `latent`
    refused("a latent cache .* is read through a paged pool", apply, attention_mask=jnp.ones((2, 8), jnp.int32),
            cache=init_deepseek_v3_cache(cfg, 2, 8), cache_index=0)
    cache, latent, q, row = latent_case()
    plain = dict(kv_buffers(1, 3, 24, 1, 24, jnp.float32)[0], block_tables=cache["block_tables"])
    bias = jnp.zeros((3, 1, 1, 24))
    with pytest.raises(ValueError, match="a cache of keys and values without"):
        decode_attention(q, row, row, plain, jnp.zeros((3,), jnp.int32), bias, latent=latent)
    with pytest.raises(ValueError, match="takes v=None"):
        paged_write_read(cache, row, row, jnp.zeros((3,), jnp.int32), jnp.float32)
    with pytest.raises(ValueError, match="not built for a latent cache"):
        latent_buffers(1, 2, 8, 24, jnp.float32, "int8")
    gpt2_moe.set_ep_mesh(jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("ep",)))
    try:
        refused("ep mesh is not built for deepseek_v3", apply)
    finally:
        gpt2_moe.reset()


def check_registry(family, cfg, cache):
    from trlx_tpu.trainer import BaseRLTrainer

    assert len(cache) == 3 and set(cache[0]) == {"k"} and cache[0]["k"].shape == (2, 8, 1, WIDTH)
    # nothing trains its router (no loss is sown), so a trainer refuses an ep axis for it by name
    assert not family.supports_ep
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("ep",))
    with pytest.raises(NotImplementedError, match="'deepseek_v3' has no experts to shard"):
        BaseRLTrainer.setup_ep_axis(None, mesh, family)
    with pytest.raises(ValueError, match="no checkpoint converter"):
        family.load_checkpoint("somewhere")


def check_paths(t):
    """The decode step reads every layer's latent pool as stored
    (``paged``: absorbed) after one write by position; an admission program
    addresses its group's rows inside the whole pool (``paged_rows``:
    decompressed) after a write by block; each scope where it belongs."""
    L = t.cfg.num_hidden_layers
    for scope in ("mla_q", "mla_kv_down", "moe_group_router", "moe_shared", "moe_dispatch", "moe_experts", "moe_combine"):
        assert scope in t.step_text and scope in t.chunk_text, scope
    assert "mla_absorbed_read" in t.step_text and "mla_decompress" not in t.step_text
    assert "mla_decompress" in t.chunk_text and "mla_absorbed_read" not in t.chunk_text
    assert t.after_step["attention/decode_path{path=paged}"] == L
    assert t.after_step["kv_cache/write_path{path=positions}"] == L
    assert t.counters["attention/decode_path{path=paged_rows}"] == L
    assert t.counters["kv_cache/write_path{path=blocks}"] == L
    assert t.eng._block_write_share == {"prefill": 1.0, "prefill_chunk": 1.0}


FAMILY = Family(
    name="deepseek_v3", config_cls=DeepseekV3Config, model_cls=DeepseekV3Model, reference=ref, arch=ARCH,
    reference_cfg=lambda cfg, **over: dict(ARCH, **over), init_cache=init_deepseek_v3_cache, tol=TOL, logprob_tol=2e-5,
    cache_layouts=(DENSE,) * 3,
    refusals={"deepseek_v3": [
        ({"num_nextn_predict_layers": 1}, "multi-token prediction"),
        ({"scoring_func": "softmax"}, "scoring_func"),
        ({"topk_method": "greedy"}, "topk_method"),
        ({"norm_topk_prob": False}, "norm_topk_prob"),
        ({"q_lora_rank": None}, "q_lora_rank"),
        ({"moe_layer_freq": 2}, "moe_layer_freq"),
        ({"attention_bias": True}, "attention_bias"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"hidden_act": "gelu"}, "hidden_act"),
        ({"num_key_value_heads": 2}, "num_key_value_heads"),
        ({"kv_cache_dtype": "int8"}, "kv_cache_dtype='int8' for a latent row"),
        ({"rope_scaling": {"type": "linear", "factor": 2}}, "rope_scaling of type 'linear'"),
        ({"first_local_expert": 1}, "not among the router's 16"),
        ({"first_k_dense_replace": 4}, "first_k_dense_replace"),
    ]},
    engine_cases={"whole": (0, False, {}), "chunked": (4, False, {}), "chunk-a-pump": (4, True, {})},
    check_forward=check_forward, check_paths=check_paths, check_registry=check_registry, refuse_more=refuse_more,
)


# ------------------------------ the model ------------------------------ #


@pytest.mark.parametrize("chunks", [1, 2, 4], ids=["whole", "two-chunks", "four-chunks"])
def test_prefill_then_decode_through_the_paged_latent_pool_matches_the_full_forward(chunks):
    cfg, model, params = model_and_params(FAMILY)
    T, cap = 21, 24
    ids, mask = left_padded([21, 13, 6], T, seed=1)
    _, cached, reference = programs(FAMILY)
    want = reference(params, ids, mask)
    cache = paged(FAMILY, cfg, 3, cap, rotate=1)
    assert all(set(c) == {"k", "block_tables"} and c["k"].shape == (3, cap, 1, WIDTH) for c in cache)
    pos = positions_of(mask)
    W = Q // chunks
    logits = []
    for c in range(chunks):  # the admission: the published form over the view
        cols = slice(c * W, (c + 1) * W)
        out = cached(params, ids[:, cols], grow(mask[:, :Q], cap), cache, 0 if chunks == 1 else jnp.asarray(c * W),
                     pos[:, cols])
        cache = out["cache"]
        logits.append(out["logits"])
    for t in range(Q, T):  # the decode step: absorbed, over the pool as stored
        out = cached(params, ids[:, t : t + 1], grow(mask[:, : t + 1], cap), cache, jnp.full((3,), t, jnp.int32),
                     pos[:, t : t + 1])
        cache = out["cache"]
        logits.append(out["logits"])
    assert rel_err(jnp.concatenate(logits, axis=1), want, mask) < TOL
    assert all(set(c) == {"k", "block_tables"} for c in cache)


def latent_case(B=3, C=24, H=4, c=16, nope=8, rope=8, Dv=12, seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    tables = identity_block_tables(B, C // 4).at[2].set(rotate_block_table(identity_block_tables(1, C // 4)[0], 3))
    cache = dict(latent_buffers(1, B, C, c + rope, jnp.float32)[0], block_tables=tables)
    filled = jax.random.normal(keys[0], (B, 20, 1, c + rope))
    _, _, cache = paged_write_read(cache, filled, None, 0, jnp.float32)
    latent = Latent(jax.random.normal(keys[1], (c, H, nope + Dv)), nope)
    q = jax.random.normal(keys[2], (B, 1, H, nope + rope))
    row = jax.random.normal(keys[3], (B, 1, 1, c + rope))
    return cache, latent, q, row


def test_absorbed_and_published_forms_agree_on_the_same_cache():
    cache, latent, q, row = latent_case()
    at = jnp.asarray([20, 7, 13], jnp.int32)
    mask = (jnp.arange(24)[None, :] <= at[:, None])
    bias = jnp.where(mask, 0.0, -1e9)[:, None, None, :]
    from trlx_tpu import telemetry

    with telemetry.scoped_metrics() as reg:
        absorbed, new_kv = decode_attention(q, row, None, cache, at, bias, scale=0.2, latent=latent)
        counted = {k: v for k, v in reg.snapshot()["counters"].items() if not k.startswith(("jit/", "host/"))}
        assert counted == {
            "attention/decode_path{path=paged}": 1.0,
            "attention/paged_read{read=whole}": 1.0,  # a latent pool keeps the absorbed read
            "kv_cache/write_path{path=positions}": 1.0,
        }
    # the same rows in logical order, decompressed and attended head by head
    view, none, _ = paged_write_read(cache, row, None, at, jnp.float32)
    assert none is None and view.shape == (3, 24, 1, 24)
    published = latent_attention(q, view, bias, latent, scale=0.2)
    assert absorbed.shape == published.shape == (3, 1, 4, 12)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(published), rtol=2e-5, atol=2e-5)
    # and by hand for one head of one slot
    b, h = 1, 2
    rows = np.asarray(view[b, : 8, 0])
    kv = rows[:, :16] @ np.asarray(latent.w_ukv[:, h])
    k = np.concatenate([kv[:, :8], rows[:, 16:]], axis=1)
    p = np.exp(0.2 * k @ np.asarray(q[b, 0, h]))
    np.testing.assert_allclose(np.asarray(absorbed[b, 0, h]), (p / p.sum()) @ kv[:, 8:], rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(new_kv["block_tables"]), np.asarray(cache["block_tables"]))


def test_a_whole_forward_decompresses_no_more_than_its_own_columns():
    """A call of ``T`` columns from a Python ``cache_index`` sees
    ``cache_index + T`` positions whatever its bias's width: the view and
    the decompression are that wide, and the result is the wide one's."""
    cache, latent, _, _ = latent_case()
    q = jax.random.normal(jax.random.PRNGKey(9), (3, 8, 4, 16))
    rows = jax.random.normal(jax.random.PRNGKey(8), (3, 8, 1, 24))
    causal = jnp.where(jnp.arange(24)[None, :] <= jnp.arange(8)[:, None], 0.0, -1e9)[None, None]
    group = dict(cache, slot_ids=jnp.arange(3, dtype=jnp.int32))
    narrow = jax.make_jaxpr(lambda: decode_attention(q, rows, None, group, 0, causal, scale=0.2, latent=latent))()
    assert "f32[3,8,4,20]" in str(narrow) and "f32[3,24,4,20]" not in str(narrow)  # 8 rows decompressed, not 24
    got, _ = decode_attention(q, rows, None, group, 0, causal, scale=0.2, latent=latent)
    wide, _ = decode_attention(q, rows, None, group, jnp.asarray(0), causal, scale=0.2, latent=latent)
    np.testing.assert_allclose(np.asarray(got), np.asarray(wide), rtol=1e-5, atol=1e-5)


def test_yarn_angles_and_the_score_scale_by_hand():
    # the published group at a rotary width of 64: lo 10, hi 23 (c(32) = 10.47, c(1) = 22.51)
    turn = lambda b: 64 * math.log(4096 / (2 * math.pi * b)) / (2 * math.log(10000.0))
    assert (math.floor(turn(32)), math.ceil(turn(1))) == (10, 23)
    f = yarn_frequencies(64, 10000.0, YARN)
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)  # fast pairs keep their frequency
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-6)  # slow pairs are stretched 40 times
    np.testing.assert_allclose(f[11], plain[11] * (1 - 1 / 13 + 1 / 13 / 40), rtol=1e-6)  # 0.925
    np.testing.assert_allclose(f[17], plain[17] * (1 - 7 / 13 + 7 / 13 / 40), rtol=1e-6)
    np.testing.assert_allclose(ref.yarn_frequencies(dict(ARCH, qk_rope_head_dim=64, rope_scaling=YARN)), f, rtol=1e-6)
    sin, cos = rotary_angles(jnp.asarray([[0, 1, 1000]]), 64, 10000.0, YARN)
    np.testing.assert_allclose(np.asarray(sin[0, 2]), np.sin(1000 * f), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cos[0, 1]), np.cos(f), rtol=1e-6)  # mscale / mscale_all_dim = 1
    m = 0.1 * math.log(40) + 1
    assert m == pytest.approx(1.36889, abs=1e-5) and yarn_score_scale(YARN) == pytest.approx(m * m)
    cfg = DeepseekV3Config.from_dict(dict(ARCH, qk_nope_head_dim=128, qk_rope_head_dim=64, rope_scaling=YARN))
    assert cfg.score_scale == pytest.approx(192 ** -0.5 * 1.873853, rel=1e-6) == pytest.approx(0.135233, rel=1e-5)
    assert ref.score_scale(dict(qk_nope_head_dim=128, qk_rope_head_dim=64, rope_scaling=YARN)) == pytest.approx(cfg.score_scale)
    # without the group: the plain frequencies and 1 / sqrt
    plain_sin, _ = rotary_angles(jnp.asarray([[3]]), 64, 10000.0)
    np.testing.assert_allclose(np.asarray(plain_sin[0, 0]), np.sin(3 * plain), rtol=1e-5, atol=1e-6)
    assert yarn_score_scale(None) == 1.0
    assert DeepseekV3Config.from_dict(dict(ARCH, rope_scaling=None)).score_scale == pytest.approx(0.25)
    # at the model tests' rotary width of 8 the same group leaves two pairs, bends one and stretches one (lo 1, hi 3)
    toy = yarn_frequencies(8, 10000.0, YARN) / 10000.0 ** (-np.arange(4) / 4)
    np.testing.assert_allclose(toy, [1.0, 1.0, 0.5 + 0.5 / 40, 1 / 40], rtol=1e-6)
    with pytest.raises(ValueError, match="rope_scaling of type 'linear' is not built"):
        rotary_angles(jnp.zeros((1, 1), jnp.int32), 8, 10000.0, {"type": "linear", "factor": 2})


def numpy_group_limited(scores, bias, k, n_group, keep, scale):
    """The choice as a plain loop: a token at a time."""
    N, E = scores.shape
    size = E // n_group
    experts, weights = [], []
    for n in range(N):
        biased = scores[n] + bias
        group_score = [np.sort(biased[g * size : (g + 1) * size])[-2:].sum() for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: -group_score[g])[:keep]
        allowed = [e for e in range(E) if e // size in kept]
        chosen = sorted(allowed, key=lambda e: -biased[e])[:k]
        w = scores[n, chosen]
        experts.append(chosen)
        weights.append(scale * w / (w.sum() + 1e-20))
    return np.asarray(experts), np.asarray(weights)


def test_the_group_limited_choice_against_a_plain_loop():
    D, E, G, keep, k = 12, 32, 8, 3, 6
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    h = jax.random.normal(keys[0], (40, D))
    w = jax.random.normal(keys[1], (D, E))
    bias = 0.3 * jax.random.normal(keys[2], (E,))
    routing = moe.route_group_limited(h, w, bias, k, n_group=G, topk_group=keep, scale=2.5)
    scores = 1 / (1 + np.exp(-np.asarray(h, np.float64) @ np.asarray(w, np.float64)))
    np.testing.assert_allclose(np.asarray(routing.probs), scores, rtol=1e-5, atol=1e-6)
    experts, weights = numpy_group_limited(scores, np.asarray(bias, np.float64), k, G, keep, 2.5)
    order = np.argsort(np.asarray(routing.experts), axis=1)
    got_experts = np.take_along_axis(np.asarray(routing.experts), order, axis=1)
    got_weights = np.take_along_axis(np.asarray(routing.weights), order, axis=1)
    want_order = np.argsort(experts, axis=1)
    np.testing.assert_array_equal(got_experts, np.take_along_axis(experts, want_order, axis=1))
    np.testing.assert_allclose(got_weights, np.take_along_axis(weights, want_order, axis=1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(routing.weights).sum(-1), 2.5, rtol=1e-5)
    # the limit binds: some token's best expert lies in a group that was dropped, and is not chosen
    best = np.argmax(scores + np.asarray(bias), axis=1)
    dropped = [n for n in range(40) if best[n] not in experts[n]]
    assert dropped and all(best[n] not in got_experts[n] for n in dropped)
    # the bias moves the choice and not the weight: weights are the unbiased scores renormalised
    n = dropped[0]
    np.testing.assert_allclose(got_weights[n], 2.5 * scores[n, got_experts[n]] / scores[n, got_experts[n]].sum(), rtol=1e-5)
    # the reference's own router (sorts, no top_k) makes the same choice
    cfg = dict(n_group=G, topk_group=keep, num_experts_per_tok=k, routed_scaling_factor=2.5)
    dense = np.asarray(ref.router_weights(h, {"router": w, "router_bias": bias}, cfg))
    assert ((dense > 0).sum(-1) == k).all()
    np.testing.assert_allclose(np.take_along_axis(dense, got_experts, axis=1), got_weights, rtol=1e-5)
    with pytest.raises(ValueError, match="do not divide into"):
        moe.route_group_limited(h, w, bias, k, n_group=5, topk_group=2)


def test_the_sixteen_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """One chip of an expert-parallel group holds ``n_routed_experts`` of the
    router's ``num_router_experts`` and returns its own experts' part of the
    sum plus the shared expert. Over all 16 shares, the shared expert (which
    every chip computes alike) counted once, that is the whole layer."""
    cfg, _, params = model_and_params(FAMILY)
    layer = params["h_1"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, 64))
    whole_cfg = cfg
    shared = jnp.asarray(ref.swiglu(x, layer["shared"]))
    whole, stats = DeepseekV3SparseMLP(whole_cfg).apply({"params": layer["mlp"]}, x, shared)
    want = ref.held_experts(x, layer["mlp"], ref.router_weights(x, layer["mlp"], ARCH)) + shared
    assert rel_err(whole, want, np.ones((2, 9))) < 1e-5
    total, here = jnp.zeros_like(whole), []
    for first in range(16):
        cut = DeepseekV3Config.from_dict(dict(ARCH, n_routed_experts=1, num_router_experts=16, first_local_expert=first))
        mine = dict(layer["mlp"], **{k: layer["mlp"][k][first : first + 1] for k in ("w_gate", "w_up", "w_down")})
        part, stats = DeepseekV3SparseMLP(cut).apply({"params": mine}, x, shared)
        total = total + (part - shared)
        here.append(float(stats["rows_here_share"]))
        assert float(stats["experts_touched"]) <= 1
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole), rtol=1e-5, atol=1e-5)
    assert sum(here) == pytest.approx(1.0)  # every routed copy is some share's
    # and through the reference, which is handed the same share: the model on a cut configuration
    cut = dict(ARCH, n_routed_experts=4, num_router_experts=16, first_local_expert=8)
    held = jax.tree_util.tree_map(lambda a: a, params)
    for i in (1, 2):
        held[f"h_{i}"]["mlp"] = dict(held[f"h_{i}"]["mlp"], **{
            k: held[f"h_{i}"]["mlp"][k][8:12] for k in ("w_gate", "w_up", "w_down")})
    ids, mask = left_padded([12, 7], 12, seed=2)
    share = DeepseekV3Model(DeepseekV3Config.from_dict(cut))
    out = jax.jit(lambda p: share.apply({"params": p}, ids, attention_mask=mask))(held)
    assert rel_err(out["logits"], jax.jit(lambda p: ref.forward(p, cut, ids, mask))(held), mask) < TOL
    assert 0 < float(out["moe_stats"]["rows_here_share"]) < 1 and float(out["moe_stats"]["experts_touched"]) <= 4


def test_a_yarn_group_that_scales_sin_and_cos_is_refused_by_name():
    """The published group has ``mscale == mscale_all_dim``, so sin and cos
    carry a factor of 1; a group where the two differ (or that leaves
    ``mscale_all_dim`` out) would scale them, which nothing here builds."""
    ids = jnp.zeros((1, 2), jnp.int32)
    rotary_angles(ids, 8, 10000.0, YARN)
    for over in ({"mscale_all_dim": 0}, {"mscale": 0.5}, {"mscale_all_dim": 0.707}):
        with pytest.raises(ValueError, match="mscale=.* != mscale_all_dim=.* is not built"):
            rotary_angles(ids, 8, 10000.0, dict(YARN, **over))
    bad = dict(ARCH, rope_scaling=dict(YARN, mscale_all_dim=0.707))
    model = DeepseekV3Model(DeepseekV3Config.from_dict(bad))
    refused("mscale_all_dim=0.707", model.init, jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    # a factor of 1 and under stretches nothing and scales nothing, whatever the two say
    rotary_angles(ids, 8, 10000.0, dict(YARN, factor=1, mscale_all_dim=0))


def test_cache_kind_on_a_latent_layer():
    layer = latent_buffers(1, 2, 8, WIDTH, jnp.float32)[0]
    with pytest.raises(KeyError):  # dense: no rank of `k` without a table says more than a plain layer's
        cache_kind({})
    tables = identity_block_tables(2, 2)
    assert cache_kind(dict(layer, block_tables=tables)) == CacheKind(PAGED, False, False, latent=True)
    group = dict(layer, block_tables=tables, slot_ids=jnp.zeros((2,), jnp.int32))
    assert cache_kind(group) == CacheKind(PAGED, False, False, rows=True, latent=True)
    assert not cache_kind(dict(kv_buffers(1, 2, 8, 1, WIDTH, jnp.float32)[0], block_tables=tables)).latent
    # one head: a whole forward's columns are whole blocks like any pool's
    rows = jax.ShapeDtypeStruct((2, 8, 1, WIDTH), jnp.float32)
    assert writes_whole_blocks(group, rows, 0) and not writes_whole_blocks(group, rows, 2)


# ------------------------------ the engine ------------------------------ #

@pytest.mark.parametrize("program", ["prefill", "prefill_chunk"])
def test_an_admission_leaves_every_other_slots_rows_as_they_were(program):
    """Every layer's latent pool is handed to the forward whole and written
    where the group's rows lie. Slots 0 and 1 (a running group, two steps
    in) and the idle slot 2 read bit for bit what they read before slot 3
    and a dummy are admitted, and slot 3 holds the rows of its prompt alone."""
    eng, params = engine(FAMILY, 4, 1)
    cfg, _, backbone = model_and_params(FAMILY)
    before, after, ids, mask = admit_beside_a_running_group(eng, params, program)
    assert all(set(was) == {"k", "block_tables"} for was in before.cache)
    # slot 3 against the same prompt through a paged pool of one row with a plain table
    table = identity_block_tables(1, eng.n_blocks)
    alone = tuple(dict(c, block_tables=table) for c in init_deepseek_v3_cache(cfg, 1, eng.capacity))
    cache_mask = jnp.concatenate([mask[:1], jnp.zeros((1, R), mask.dtype)], axis=1)
    want = programs(FAMILY)[1](backbone, ids[:1], cache_mask, alone, 0, positions_of(mask[:1]))["cache"]
    table, real, phys = slot_3_rows(eng, mask)
    for now, ref_layer in zip(after.cache, want):
        np.testing.assert_array_equal(np.asarray(now["block_tables"])[3], table)
        # the engine holds a row padded to whole lanes (ops/kv_cache.py::hold_pool): a row's own columns, then zeros
        assert now["k"].shape[-1] == 128
        np.testing.assert_allclose(np.asarray(now["k"])[3, phys, :, :WIDTH], np.asarray(ref_layer["k"])[0, real],
                                   rtol=0, atol=2e-5)
        assert not np.asarray(now["k"])[..., WIDTH:].any()


def test_engine_and_fixed_sampler_refuse_what_a_latent_row_cannot_give():
    from trlx_tpu import telemetry
    from trlx_tpu.inference.engine import ContinuousBatchingEngine
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler
    from trlx_tpu.parallel.mesh import make_mesh

    cfg = model_and_params(FAMILY)[0]
    init = functools.partial(init_deepseek_v3_cache, cfg)
    common = dict(apply_fn=lambda *a, **k: None, init_cache_fn=init, gen_config=GenerationConfig(max_new_tokens=4),
                  query_length=8, vocab_size=96, num_slots=2)
    with pytest.raises(ValueError, match="prefix_pool_blocks.*not built for a latent cache"):
        ContinuousBatchingEngine(**common, prefix_pool_blocks=2)
    with pytest.raises(ValueError, match="verify_step.*not built for a latent cache"):
        ContinuousBatchingEngine(**common, spec_max_draft=2)
    for axis in ("tp", "ep", "pp"):
        mesh = make_mesh({"dp": 1, "fsdp": 1, "tp": 1, axis: 2}, devices=jax.devices()[:2])
        with pytest.raises(ValueError, match=f"a {axis} mesh is not built for a latent cache"):
            ContinuousBatchingEngine(**common, mesh=mesh)
    eng = ContinuousBatchingEngine(**common)
    state = jax.eval_shape(eng._make_state)
    assert all(set(c) == {"k", "block_tables"} and cache_kind(c).latent for c in state.cache)
    gauges = telemetry.get_metrics().snapshot()["gauges"]
    assert gauges["cache/latent_gb"] == pytest.approx(3 * 2 * 12 * WIDTH * 4 / 1e9)
    assert gauges["cache/kv_gb"] == gauges["cache/state_gb"] == gauges["cache/tail_gb"] == 0.0
    sampler = make_sampler(lambda *a, **k: None, init, GenerationConfig(max_new_tokens=4), 8, with_values=False)
    with pytest.raises(ValueError, match="a latent cache .deepseek_v3. or a tail beside its keys .zaya. samples through"):
        sampler(None, jnp.zeros((2, 8), jnp.int32), jnp.ones((2, 8), jnp.int32), jax.random.PRNGKey(0))
