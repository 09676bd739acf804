"""The ling family (models/ling.py: Ling-3.0-flash) and what it forced below
it: the delta rule with a decay that is a vector a head (ops/delta.py:
``kda_chunk`` in row blocks, ``kda_step``, ``kda_mix``), a cache whose layers
that hold keys are latent layers among state layers
(ops/kv_cache.py::hybrid_cache with ``latent_width``), a latent sublayer with a
full-rank query and plain rotary, a share of 512-wide group-limited routing,
and the engine's handling of a sequence that keeps a matrix state and a latent
row.

Everything is compared on logits (never sampled tokens) with the plain float32
reference ``benchmark/reference/ling.py``, which runs the rule a position at a
time, the latent attention decompressed and every held expert on every token:
it shares no code with ops/delta.py, ops/attention.py or ops/moe.py. Programs,
engine and the tests every family is held to come from
``tests/family_harness.py``.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import ling as ref
from family_harness import (  # noqa: F401  (the contract tests run here, on FAMILY)
    Family,
    engine,
    grow,
    left_padded,
    model_and_params,
    paged,
    positions_of,
    programs,
    refused,
    rel_err,
    test_a_parked_row_keeps_its_state_and_a_fresh_row_forgets_the_slot,
    test_engine_logprobs_match_the_uncached_forward_on_the_tokens_it_drew,
    test_registry_builds_the_family_and_its_cache_by_kind,
    test_uncached_forward_matches_the_reference_on_left_padded_rows,
    test_what_the_family_does_not_build_is_refused_by_name,
    test_which_paths_the_engines_programs_traced,
)
from trlx_tpu.models.deepseek_v3 import DeepseekV3MLP, DeepseekV3SparseMLP
from trlx_tpu.models.ling import KDA, LATENT, LingConfig, LingLatentAttention, LingModel, init_ling_cache
from trlx_tpu.ops import delta
from trlx_tpu.ops.kv_cache import (
    DENSE,
    PAGED,
    STATE,
    cache_kind,
    held_row_width,
    hold_pool,
    hybrid_cache,
    identity_block_tables,
)

ARCH = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=7, first_k_dense_replace=1, layer_group_size=6,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16, kv_lora_rank=24, qk_nope_head_dim=16,
    qk_rope_head_dim=8, rotary_dim=8, v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32, num_experts=4, num_router_experts=16, first_local_expert=4,
    num_experts_per_tok=4, n_group=4, topk_group=2,
    dtype="float32", param_dtype="float32",
)
TOL = 3e-5  # float32 arithmetic on both sides, 24 positions through seven blocks: rounding alone reads 7e-6


def reference_cfg(cfg: LingConfig, **over):
    return dict(
        ARCH, rms_norm_eps=cfg.rms_norm_eps, kda_lower_bound=cfg.kda_lower_bound,
        short_conv_kernel_size=cfg.short_conv_kernel_size, rope_theta=cfg.rope_theta,
        routed_scaling_factor=cfg.routed_scaling_factor, **over,
    )


def check_forward(cfg, params, out):
    assert cfg.layer_types == (KDA,) * 5 + (LATENT, KDA)
    assert float(jnp.abs(params["h_1"]["mlp"]["router_bias"]).max()) > 0  # the selection bias is not zero
    assert set(params["h_0"]["mlp"]) == {"gate_proj", "up_proj", "down_proj"} and "shared" not in params["h_0"]
    stats = out["moe_stats"]
    assert set(stats) == {"experts_touched", "max_load", "rows_routed", "rows_here_share"}
    assert float(stats["experts_touched"]) <= 4 and 0 < float(stats["rows_here_share"]) < 1


def refuse_more(cfg, model, params):
    from trlx_tpu.models import gpt2_moe
    from trlx_tpu.parallel.mesh import make_mesh, traced_on

    # the limit lists as published: zeros for the blocks the cut keeps, a clamp past them
    LingConfig.from_dict(dict(ARCH, expert_swiglu_limit_list=[0] * 7 + [4] * 3))
    ids = jnp.zeros((2, 2), jnp.int32)
    apply = functools.partial(model.apply, {"params": params}, ids)
    refused("verify", apply, attention_mask=jnp.ones((2, 8), jnp.int32),
            cache=init_ling_cache(cfg, 2, 8), cache_index=jnp.zeros((2, 2), jnp.int32))
    for hook in ({"start_layer": 1}, {"hidden_override": jnp.zeros((2, 2, 64))}, {"capture_hidden_at": 1}):
        refused("hydra branch .* is not built for ling", apply, **hook)
    # a latent layer's cache that is not paged (ops/attention.py::decode_attention)
    refused("paged", apply, attention_mask=jnp.ones((2, 8), jnp.int32), cache=init_ling_cache(cfg, 2, 8), cache_index=0)
    gpt2_moe.set_ep_mesh(jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("ep",)))
    try:
        refused("a ep mesh is not built for ling", apply)
    finally:
        gpt2_moe.reset()
    for axis in ("tp", "ep", "pp"):
        mesh = make_mesh({"dp": 1, "fsdp": 1, "tp": 1, axis: 2}, devices=jax.devices()[:2])
        refused(f"a {axis} mesh is not built for ling", traced_on(mesh, apply))
    dp = make_mesh({"dp": 2, "fsdp": 1, "tp": 1}, devices=jax.devices()[:2])
    jax.eval_shape(traced_on(dp, apply))  # data axes shard nothing of the model


def check_registry(family, cfg, cache):
    from trlx_tpu.models.registry import get_model_family
    from trlx_tpu.trainer import BaseRLTrainer

    assert get_model_family("bailing_hybrid") is family  # the published model_type
    assert [cache_kind(c).latent for c in cache] == [False] * 5 + [True, False]
    assert set(cache[5]) == {"k"} and cache[5]["k"].shape == (2, 8, 1, 32)  # one row [c | k_r] a position
    # [B, heads, key size, value size] and the tail over [q | k | v]
    assert cache[0]["ssm_state"].shape == (2, 4, 16, 16) and cache[0]["conv_tail"].shape == (2, 3, 192)
    assert cache[0]["ssm_state"].dtype == cache[0]["conv_tail"].dtype == jnp.float32
    assert not family.supports_ep and family.stored_width_leaves == ("conv_weight",)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("ep",))
    with pytest.raises(NotImplementedError, match="'ling' has no experts to shard"):
        BaseRLTrainer.setup_ep_axis(None, mesh, family)
    with pytest.raises(ValueError, match="no checkpoint converter"):
        family.load_checkpoint("somewhere")
    # the published keys alone derive the pattern: every sixth block latent, two leading dense blocks
    whole = family.config_cls()
    assert whole.layer_types.count(LATENT) == 7 and whole.layer_types[5] == whole.layer_types[41] == LATENT
    assert (whole.conv_channels, whole.latent_width, whole.num_router_experts) == (12288, 576, 512)
    assert (whole.qk_head_dim, whole.kda_lower_bound) == (192, -5.0)
    assert delta.kda_sub_chunk(whole.kda_lower_bound) == 16


def check_paths(t):
    """The decode step reads its one latent layer as stored (``paged``,
    absorbed) and steps its six state layers; an admission program runs the
    chunked form in row blocks and addresses its group's rows inside the
    whole pool (``paged_rows``)."""
    for scope in ("kda_in_proj", "kda_conv", "kda_gate", "kda_out", "mla_q", "mla_kv_down",
                  "moe_group_router", "moe_shared", "moe_experts"):
        assert scope in t.step_text and scope in t.chunk_text, scope
    assert "kda_step" in t.step_text and "kda_chunk" not in t.step_text
    assert "kda_chunk" in t.chunk_text and "kda_step" not in t.chunk_text
    assert "mla_absorbed_read" in t.step_text and "mla_decompress" in t.chunk_text
    n_state = t.cfg.layer_types.count(KDA)
    assert t.after_step["kda/path{path=step}"] == n_state and "kda/path{path=chunk}" not in t.after_step
    assert t.after_step["attention/decode_path{path=paged}"] == 1
    assert t.counters["kda/path{path=chunk}"] == n_state
    assert t.counters["attention/decode_path{path=paged_rows}"] == 1
    # the row block the traced chunked form took: the bound's 16 of a chunk of 64
    assert t.gauges["kda/sub_chunk"] == 16
    assert "gdn/path{path=step}" not in t.after_step and "gdn/path{path=chunk}" not in t.counters


FAMILY = Family(
    name="ling", config_cls=LingConfig, model_cls=LingModel, reference=ref, arch=ARCH,
    reference_cfg=reference_cfg, init_cache=init_ling_cache, tol=TOL, logprob_tol=3e-5,
    cache_layouts=[STATE] * 5 + [DENSE, STATE],
    refusals={"ling": [
        ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
        ({"q_lora_rank": 1536}, "q_lora_rank=1536"),
        ({"use_mla_nope": True}, "use_mla_nope"),
        ({"expert_swiglu_limit_list": [0, 0, 0, 0, 0, 0, 4]}, "expert_swiglu_limit_list with a non-zero limit at blocks \\[6\\]"),
        ({"share_expert_swiglu_limit_list": [0, 5] + [0] * 5}, "share_expert_swiglu_limit_list"),
        ({"num_kv_heads_for_linear_attn": 2}, "num_kv_heads_for_linear_attn"),
        ({"kda_safe_gate": False}, "kda_safe_gate"),
        ({"use_kda_lora": True}, "use_kda_lora"),
        ({"no_kda_lora": False}, "no_kda_lora"),
        ({"linear_silu": False}, "linear_silu"),
        ({"group_norm_size": 4}, "group_norm_size"),
        ({"gated_attention_proj_granularity_type": "element_wise"}, "granularity"),
        ({"use_qk_norm": False}, "use_qk_norm"),
        ({"value_norm": True}, "value_norm"),
        ({"use_nGPT": True}, "use_nGPT"),
        ({"scale_router_input": True}, "scale_router_input"),
        ({"score_function": "softmax"}, "score_function"),
        ({"norm_topk_prob": False}, "norm_topk_prob"),
        ({"moe_router_enable_expert_bias": False}, "moe_router_enable_expert_bias"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"kv_cache_dtype": "int8"}, "kv_cache_dtype='int8' for a latent row beside state layers"),
        ({"state_dtype": "bfloat16"}, "state_dtype"),
        ({"num_key_value_heads": 2}, "num_key_value_heads"),
        ({"rotary_dim": 16}, "rotary_dim"),
        ({"kda_lower_bound": 0.0}, "kda_lower_bound"),
        ({"num_experts": 14}, "not among the router's 16"),
    ]},
    engine_cases={"whole": (0, False, {}), "chunked": (4, False, {}), "chunk-a-pump": (4, True, {})},
    check_forward=check_forward, check_paths=check_paths, check_registry=check_registry, refuse_more=refuse_more,
)


# ------------------------------ the model ------------------------------ #


@pytest.mark.parametrize("what", ["gate", "solve"])
def test_bfloat16_where_float32_is_stated_fails_the_tolerance(what, monkeypatch):
    """The 3e-5 the comparisons are held to (float32 arithmetic on both
    sides, 24 positions through seven blocks: rounding alone reads 7e-6) is
    tight enough that the gate or the solve computed in bfloat16 fails it by
    more than ten times; the state's type is held by
    ``test_a_long_carry_holds_the_state_to_float32``."""
    cfg, model, params = model_and_params(FAMILY)
    ids, mask = left_padded([21, 13, 5], 21)
    want = programs(FAMILY)[2](params, ids, mask)
    bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if what == "solve":
        inverse = delta.unit_lower_inverse
        monkeypatch.setattr(delta, "unit_lower_inverse", lambda A: bf16(inverse(bf16(A))))
    else:
        chunked = delta.kda_chunk
        monkeypatch.setattr(delta, "kda_chunk", lambda q, k, v, g, *rest: chunked(q, k, v, bf16(g), *rest))
    out = jax.jit(lambda p: model.apply({"params": p}, ids, attention_mask=mask))(params)  # traced under the patch
    assert rel_err(out["logits"], want, mask) > 10 * TOL


@pytest.mark.parametrize("chunk", [0, 4], ids=["whole", "chunked"])
def test_admission_then_decode_through_states_and_a_paged_latent_pool_matches_the_full_forward(chunk):
    """An admission of 16 columns (whole, or in chunks of 4 that carry the
    state and the tail from call to call) then five steps, the latent layer
    through a paged pool whose second row's blocks are rotated and whose
    rows are held padded (``hold_pool``), the KDA layers through their
    state: logits against the reference's full forward."""
    cfg, model, params = model_and_params(FAMILY)
    T, Q, cap = 21, 16, 24
    ids, mask = left_padded([21, 13, 6], T, seed=1)
    _, cached, reference = programs(FAMILY)
    want = reference(params, ids, mask)
    cache = paged(FAMILY, cfg, 3, cap, rotate=1, hold=True)
    assert [cache_kind(c).layout for c in cache] == [STATE] * 5 + [PAGED, STATE]
    assert cache_kind(cache[5]).latent and cache[5]["k"].shape == (3, cap, 1, 128)  # 32 -> whole lanes
    positions = positions_of(mask)
    for lo in range(0, Q, chunk or Q):
        hi = lo + (chunk or Q)
        out = cached(params, ids[:, lo:hi], grow(mask[:, :Q], cap), cache, jnp.asarray(lo) if chunk else 0,
                     positions[:, lo:hi])
        cache = out["cache"]
        assert rel_err(out["logits"], want[:, lo:hi], mask[:, lo:hi]) < TOL
    for t in range(Q, T):
        out = cached(params, ids[:, t : t + 1], grow(mask[:, : t + 1], cap), cache, jnp.full((3,), t, jnp.int32),
                     positions[:, t : t + 1])
        cache = out["cache"]
        assert rel_err(out["logits"][:, 0], want[:, t], mask[:, t]) < TOL
    assert cache[5]["k"].shape[-1] == 128 and "v" not in cache[5]


def test_hybrid_cache_with_a_latent_layer_among_state_layers():
    """``cache_kind`` answers layer by layer: the state kind for a KDA
    layer, ``.latent`` for the layer that holds keys; the holder pads the
    latent rows to whole lanes and leaves the states as they are; block
    tables go to the latent layer alone."""
    sizes = dict(dtype="bfloat16", kv_cache_dtype="bfloat16",
                 state=dict(n_head=2, head_dim=4, d_state=6, conv_width=4, conv_channels=16))
    cache = hybrid_cache([KDA, LATENT, KDA], 2, 8, keys=(LATENT,), latent_width=576, **sizes)
    assert [set(c) for c in cache] == [{"ssm_state", "conv_tail"}, {"k"}, {"ssm_state", "conv_tail"}]
    assert cache[1]["k"].shape == (2, 8, 1, 576) and cache[1]["k"].dtype == jnp.bfloat16
    kinds = [cache_kind(c) for c in cache]
    assert [k.layout for k in kinds] == [STATE, DENSE, STATE] and [k.latent for k in kinds] == [False, True, False]
    assert kinds[0].tail == ("conv_tail", "ssm_state") and kinds[1].tail == ()
    assert [held_row_width(c) for c in cache] == [0, 640, 0]
    held = [hold_pool(c) for c in cache]
    assert held[1]["k"].shape == (2, 8, 1, 640) and held[0] is cache[0] and held[2] is cache[2]
    paged = dict(held[1], block_tables=identity_block_tables(2, 2))
    assert cache_kind(paged).layout == PAGED and cache_kind(paged).latent
    # without a latent width the same call gives keys and values, as qwen3-next's does
    kv = hybrid_cache([KDA, LATENT], 2, 8, keys=(LATENT,), n_kv_head=2, head_dim=4, **sizes)
    assert set(kv[1]) == {"k", "v"} and not cache_kind(kv[1]).latent
    with pytest.raises(ValueError, match="int8"):
        hybrid_cache([KDA, LATENT], 2, 8, keys=(LATENT,), latent_width=576, **dict(sizes, kv_cache_dtype="int8"))
    # a caller says which of the two it holds: neither (a forgotten size), half of one, or both are refused
    for given in ({}, {"n_kv_head": 2}, {"head_dim": 4}, {"latent_width": 576, "n_kv_head": 2, "head_dim": 4}):
        with pytest.raises(ValueError, match="either latent_width"):
            hybrid_cache([KDA, LATENT], 2, 8, keys=(LATENT,), **given, **sizes)


# ---------------------------- ops/delta.py ------------------------------ #


# the rule's forms as one program a shape (tests/family_harness.py says why); the mixer tests patch `delta` and stay eager
kda_chunk = jax.jit(delta.kda_chunk, static_argnames=("chunk", "sub_chunk"))
kda_step = jax.jit(delta.kda_step)
gated_delta_chunk = jax.jit(delta.gated_delta_chunk, static_argnames=("chunk",))
gated_delta_step = jax.jit(delta.gated_delta_step)


def rule_inputs(B=2, T=24, H=3, Dk=8, Dv=16, seed=0, dtype=jnp.float32, floor=None):
    """``g`` a vector a head in (-5, 0), spread over the whole range; with
    ``floor`` every channel of every column at that value."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(k[3], (B, T, H, Dk)))
    return dict(
        q=(delta.l2_normalise(jax.random.normal(k[0], (B, T, H, Dk))) * Dk**-0.5).astype(dtype),
        k=delta.l2_normalise(jax.random.normal(k[1], (B, T, H, Dk))).astype(dtype),
        v=jax.random.normal(k[2], (B, T, H, Dv)).astype(dtype),
        g=g if floor is None else jnp.full_like(g, floor),
        beta=jax.nn.sigmoid(jax.random.normal(k[4], (B, T, H))),
    )


def recurrence(q, k, v, g, beta, state):
    """The rule as its equations, a column at a time, in float64 on the host."""
    q, k, v, g, beta = (np.asarray(x.astype(jnp.float32), np.float64) for x in (q, k, v, g, beta))
    S, out = np.asarray(state, np.float64), []
    for t in range(q.shape[1]):
        S = S * np.exp(g[:, t])[..., None]  # Diag(exp g) S
        held = np.einsum("bhkv,bhk->bhv", S, k[:, t])
        S = S + k[:, t][..., None] * (beta[:, t][..., None] * (v[:, t] - held))[..., None, :]
        out.append(np.einsum("bhkv,bhk->bhv", S, q[:, t]))
    return np.stack(out, axis=1), S


def steps(a, state, lo=0, hi=None):
    outs = []
    for t in range(lo, a["q"].shape[1] if hi is None else hi):
        o, state = kda_step(*(a[n][:, t] for n in ("q", "k", "v", "g", "beta")), state)
        outs.append(o)
    return jnp.stack(outs, axis=1), state


@pytest.mark.parametrize("chunk,sub", [(4, 2), (8, 8), (16, 4), (64, 16)], ids=lambda c: f"{c}")
def test_the_chunked_form_the_step_and_the_recurrence_agree_on_mixed_gates(chunk, sub):
    """One sequence of 24 columns from a state that is not zero, the gate
    spread over (-5, 0): across chunk edges, across row blocks inside a
    chunk, inside one padded chunk (64 in blocks of 16)."""
    a = rule_inputs()
    state = jax.random.normal(jax.random.PRNGKey(9), (2, 3, 8, 16))
    want_o, want_s = recurrence(**a, state=state)
    o, s = kda_chunk(**a, state=state, chunk=chunk, sub_chunk=sub)
    np.testing.assert_allclose(np.asarray(o), want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=2e-5, atol=2e-5)
    o, s = steps(a, state)
    np.testing.assert_allclose(np.asarray(o), want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("T", [64, 100], ids=lambda t: f"T{t}")
def test_every_column_at_the_floor_stays_in_float32(T):
    """-5 a channel for 64 columns and more: the cumulative log-decay of a
    chunk reaches -320, whose exponential float32 cannot hold either way;
    the row blocks keep every factor within exp(+-40). The case the
    sub-chunks exist for: one reference a chunk of 64 overflows."""
    a = rule_inputs(T=T, floor=-5.0)
    state = jax.random.normal(jax.random.PRNGKey(9), (2, 3, 8, 16))
    want_o, want_s = recurrence(**a, state=state)
    o, s = kda_chunk(**a, state=state, chunk=64, sub_chunk=delta.kda_sub_chunk(-5.0))
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)).all()
    np.testing.assert_allclose(np.asarray(o), want_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=1e-5, atol=1e-6)
    whole_o, _ = kda_chunk(**a, state=state, chunk=64, sub_chunk=64)  # one reference a chunk
    assert not np.isfinite(np.asarray(whole_o)).all()


def test_the_row_block_follows_the_bound():
    assert [delta.kda_sub_chunk(b) for b in (-5.0, -2.5, -1.0, -10.0, -40.0, -100.0)] == [16, 32, 64, 8, 2, 1]
    assert delta.kda_sub_chunk(-5.0, chunk=8) == 8  # never wider than the chunk
    with pytest.raises(ValueError, match="bounds nothing"):
        delta.kda_sub_chunk(0.0)
    a = rule_inputs(T=8)
    with pytest.raises(ValueError, match="power of two"):
        kda_chunk(**a, state=jnp.zeros((2, 3, 8, 16)), chunk=8, sub_chunk=3)


def test_a_gate_constant_over_a_heads_channels_is_the_scalar_rule():
    """``kda_chunk`` with ``g`` one number a head against
    ``gated_delta_chunk`` (qwen3-next's, untouched), and the steps alike."""
    a = rule_inputs(T=48)
    state = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 8, 16))
    scalar = a["g"][..., 0]
    same = dict(a, g=jnp.broadcast_to(scalar[..., None], a["g"].shape))
    o1, s1 = kda_chunk(**same, state=state, chunk=16, sub_chunk=4)
    o2, s2 = gated_delta_chunk(a["q"], a["k"], a["v"], scalar, a["beta"], state, chunk=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5, atol=1e-5)
    o3, s3 = kda_step(*(same[n][:, 0] for n in ("q", "k", "v", "g", "beta")), state)
    o4, s4 = gated_delta_step(a["q"][:, 0], a["k"][:, 0], a["v"][:, 0], scalar[:, 0], a["beta"][:, 0], state)
    np.testing.assert_allclose(np.asarray(o3), np.asarray(o4), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(s3), np.asarray(s4), rtol=1e-6, atol=1e-6)


def test_two_calls_that_carry_the_state_equal_one_and_steps_go_on_from_a_chunk():
    a = rule_inputs(T=16)
    zero = jnp.zeros((2, 3, 8, 16))
    whole_o, whole_s = kda_chunk(**a, state=zero, chunk=4, sub_chunk=2)
    cut = lambda lo, hi: {n: v[:, lo:hi] for n, v in a.items()}
    o1, s1 = kda_chunk(**cut(0, 10), state=zero, chunk=4, sub_chunk=2)  # a call that ends inside a chunk
    o2, s2 = kda_chunk(**cut(10, 16), state=s1, chunk=4, sub_chunk=2)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)), np.asarray(whole_o), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(whole_s), rtol=1e-5, atol=1e-5)
    o3, s3 = steps(a, s1, 10)  # a prefill, then decode steps, against the full sequence
    np.testing.assert_allclose(np.asarray(o3), np.asarray(whole_o[:, 10:]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s3), np.asarray(whole_s), rtol=1e-5, atol=1e-5)


def mixer_inputs(T, keys, mask, H=4, D=16):
    width = 3 * H * D
    qkv = jax.random.normal(keys[3], (2, T, width)) * mask[:, :T, None]
    return qkv, jax.random.normal(keys[4], (2, T, H * D)), jax.random.normal(keys[5], (2, T, H))


def test_a_masked_column_leaves_the_state_bit_for_bit():
    """``beta = 0`` and ``g = 0`` at a masked column (the mixer multiplies
    both with the mask): an all-pad row's state comes back bit for bit from
    the chunked form and from the step, and through the mixer its tail too."""
    a = rule_inputs(T=8)
    state = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 8, 16))
    mask = jnp.asarray([[0] * 8, [0, 0, 0, 1, 1, 1, 1, 1]], jnp.float32)
    masked = dict(a, g=a["g"] * mask[..., None, None], beta=a["beta"] * mask[..., None])
    _, s = kda_chunk(**masked, state=state, chunk=4, sub_chunk=2)
    np.testing.assert_array_equal(np.asarray(s[0]), np.asarray(state[0]))
    assert not np.allclose(np.asarray(s[1]), np.asarray(state[1]))
    _, s1 = kda_step(*(masked[n][:, 0] for n in ("q", "k", "v", "g", "beta")), state)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(state))  # column 0 is masked in both rows
    keys = jax.random.split(jax.random.PRNGKey(4), 6)
    H, D = 4, 16
    layer = {"ssm_state": jax.random.normal(keys[0], (2, H, D, D)),
             "conv_tail": jax.random.normal(keys[1], (2, 3, 3 * H * D))}
    common = dict(conv_weight=jax.random.normal(keys[2], (4, 3 * H * D)), dt_bias=jnp.zeros((H * D,)),
                  A_log=jnp.zeros((H,)), n_heads=H, key_dim=D, value_dim=D, lower_bound=-5.0, chunk=4)
    for T in (8, 1):  # a chunk, a step
        qkv, g_raw, b_raw = mixer_inputs(T, keys, mask)
        _, new = delta.kda_mix(qkv, g_raw, b_raw, mask=mask[:, :T], cache_layer=layer, **common)
        for k in layer:
            np.testing.assert_array_equal(np.asarray(new[k][0]), np.asarray(layer[k][0]), err_msg=k)
    # a fresh row starts from zeros whatever the slot held
    _, fresh = delta.kda_mix(qkv, g_raw, b_raw, mask=mask[:, :1], fresh=jnp.asarray([True, False]),
                             cache_layer=layer, **common)
    np.testing.assert_array_equal(np.asarray(fresh["ssm_state"][0]), 0.0)
    np.testing.assert_array_equal(np.asarray(fresh["ssm_state"][1]), np.asarray(layer["ssm_state"][1]))


def test_the_mixers_gate_is_bounded_and_a_vector_a_head():
    """``g = lower_bound * sigmoid(exp(A_log[h]) (g_raw + dt_bias))``: in
    (-5, 0) whatever the projection says, and a head's channels differ."""
    keys = jax.random.split(jax.random.PRNGKey(6), 6)
    H, D = 4, 16
    seen = {}
    step = delta.kda_step

    def spy(q, k, v, g, beta, state):
        seen["g"] = g
        return step(q, k, v, g, beta, state)

    layer = {"ssm_state": jnp.zeros((2, H, D, D)), "conv_tail": jnp.zeros((2, 3, 3 * H * D))}
    qkv, g_raw, b_raw = mixer_inputs(1, keys, jnp.ones((2, 1)))
    delta.kda_step = spy
    try:
        delta.kda_mix(qkv, 50.0 * g_raw, b_raw, conv_weight=jnp.ones((4, 3 * H * D)), dt_bias=jnp.zeros((H * D,)),
                      A_log=jnp.log(jnp.asarray([0.5, 1.0, 2.0, 4.0])), n_heads=H, key_dim=D, value_dim=D,
                      lower_bound=-5.0, cache_layer=layer)
    finally:
        delta.kda_step = step
    g = np.asarray(seen["g"])
    assert g.shape == (2, H, D) and g.min() >= -5.0 and g.max() <= 0.0
    assert g.min() < -4.99 and g.max() > -0.01  # the bound is reached from both sides
    assert np.ptp(g, axis=-1).min() > 1.0  # not one number a head


def long_carry(state_dtype, seed, T=320, T0=256):
    """An admission of ``T0`` columns then ``T - T0`` decode steps of one
    row at the cell's kind of decay (a channel's log-decay near ``-5 dt``,
    ``dt`` log-uniform in [0.001, 0.1]), operands in bfloat16 as the program
    hands them over, the state kept in ``state_dtype`` between calls,
    against the recurrence in float64: relative rms errors of (the decoded
    outputs, the final state)."""
    from trlx_tpu.models.granite_hybrid import DT_RANGE

    H, Dk, Dv = 8, 8, 16
    a = rule_inputs(B=1, T=T, H=H, Dk=Dk, Dv=Dv, seed=seed, dtype=jnp.bfloat16)
    k = jax.random.split(jax.random.PRNGKey(100 + seed), 2)
    lo, hi = np.log(DT_RANGE)
    dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(k[0], (H, Dk))) * jnp.exp(0.3 * jax.random.normal(k[1], (1, T, H, Dk)))
    a["g"] = -5.0 * jnp.minimum(dt, 0.999)
    zero = jnp.zeros((1, H, Dk, Dv))
    want_o, want_s = recurrence(**a, state=zero)
    cut = {n: v[:, :T0] for n, v in a.items()}
    _, state = kda_chunk(**cut, state=zero, chunk=64, sub_chunk=16)
    step, got = kda_step, []
    for t in range(T0, T):
        o, state = step(*(a[n][:, t] for n in ("q", "k", "v", "g", "beta")), state.astype(state_dtype))
        got.append(np.asarray(o, np.float64))
    rel = lambda g, w: float(np.sqrt(((g - w) ** 2).mean() / (w**2).mean()))
    return rel(np.stack(got, axis=1), want_o[:, T0:]), rel(np.asarray(state, np.float64), want_s)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_long_carry_holds_the_state_to_float32(seed):
    """What the benchmark's comparison cannot see on the chip (PERF.md §7
    (20)) is held here: the state a cache allocates is float32, and over a
    carry of 320 positions it stays within limits that the same ops with
    the state rounded to bfloat16 between calls do not keep."""
    cfg = model_and_params(FAMILY)[0]
    allocated = init_ling_cache(cfg, 1, 8)[0]["ssm_state"].dtype
    assert allocated == jnp.float32
    o_err, s_err = long_carry(allocated, seed)
    o_low, s_low = long_carry(jnp.bfloat16, seed)
    assert o_err < 3e-3 and s_err < 3e-3, (o_err, s_err)
    assert s_low > 1.5 * s_err and s_low > 3e-3, (s_low, s_err)


def test_the_uncached_chunked_form_is_differentiable():
    a = rule_inputs(T=8)
    loss = lambda v: kda_chunk(a["q"], a["k"], v, a["g"], a["beta"], jnp.zeros((2, 3, 8, 16)), 4, 2)[0].sum()
    g = jax.grad(loss)(a["v"])
    assert np.isfinite(np.asarray(g)).all() and float(jnp.abs(g).sum()) > 0


# --------------------- the latent sublayer, by hand ---------------------- #


def test_the_latent_sublayer_with_a_full_rank_query_and_plain_rotary_by_hand():
    """Two heads over one latent row a position, 8 of a head's 24 rotated in
    pairs at theta 6e6 with no scaling: three positions worked in float64
    from the layer's own weights, in the published (decompressed) form."""
    cfg = LingConfig.from_dict(dict(ARCH, hidden_size=32, num_attention_heads=2, num_key_value_heads=2))
    layer = LingLatentAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, 32))
    positions = jnp.asarray([[0, 1, 2]])
    params = layer.init(jax.random.PRNGKey(1), x, None, positions, causal=True)["params"]
    assert set(params) == {"q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj", "o_proj"}  # no low-rank query pair
    params = jax.tree_util.tree_map(lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(2), a.shape), params)
    got = layer.apply({"params": params}, x, None, positions, causal=True)[0]
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    x64 = np.asarray(x[0], np.float64)
    H, C, nope, rope, Dv = 2, 24, 16, 8, 16

    def rotate(v):  # [T, rope]
        out = v.copy()
        for t in range(3):
            for j in range(rope // 2):
                angle = t * 6e6 ** (-2 * j / rope)
                a, b = v[t, 2 * j], v[t, 2 * j + 1]
                out[t, 2 * j], out[t, 2 * j + 1] = a * np.cos(angle) - b * np.sin(angle), a * np.sin(angle) + b * np.cos(angle)
        return out

    q = (x64 @ p["q_proj"]["kernel"]).reshape(3, H, nope + rope)
    down = x64 @ p["kv_a_proj"]["kernel"]
    c = down[:, :C] / np.sqrt((down[:, :C] ** 2).mean(-1, keepdims=True) + 1e-6) * p["kv_a_norm"]["scale"]
    k_r = rotate(down[:, C:])
    kv = (c @ p["kv_b_proj"]["kernel"]).reshape(3, H, nope + Dv)
    heads = []
    for h in range(H):
        qh = np.concatenate([q[:, h, :nope], rotate(q[:, h, nope:])], -1)
        kh = np.concatenate([kv[:, h, :nope], k_r], -1)
        scores = qh @ kh.T * (nope + rope) ** -0.5
        scores[np.triu_indices(3, 1)] = -np.inf
        weights = np.exp(scores - scores.max(-1, keepdims=True))
        heads.append((weights / weights.sum(-1, keepdims=True)) @ kv[:, h, nope:])
    want = np.concatenate(heads, -1) @ p["o_proj"]["kernel"]
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=2e-4, atol=2e-5)


# --------------------- the expert layer's shares ------------------------ #


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Eight chips that hold 2 experts each of the router's 16 (a chip a
    router group, as the cut holds one group of eight): their routed parts
    and the shared expert, counted once, give what the reference computes
    for the whole layer (all 16 held) through the family's own modules."""
    over = dict(num_hidden_layers=2, n_group=8, topk_group=4, num_experts=2, first_local_expert=0)
    cfg0, model0, params0 = model_and_params(FAMILY, **over)
    ids, mask = left_padded([9, 4], 9, seed=3)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    all_experts = {n: 0.1 * jax.random.normal(k, (16,) + params0["h_1"]["mlp"][n].shape[1:])
                   for n, k in zip(("w_gate", "w_up", "w_down"), keys)}
    rc = reference_cfg(cfg0, **over)

    def with_experts(first, held):
        tree = jax.tree_util.tree_map(lambda a: a, params0)
        for n in all_experts:
            tree["h_1"]["mlp"][n] = all_experts[n][first : first + held]
        return tree

    # the input of block 1's feed-forward and its three terms, by the reference
    def moe_terms(tree, first, held):
        cf = dict(rc, num_experts=held, first_local_expert=first)
        with jax.default_matmul_precision("highest"):
            x = ref.f32(tree["wte"]["embedding"][ids])
            for i in (0, 1):
                blk = tree[f"h_{i}"]
                x = x + ref.kda(ref.rms_norm(x, blk["ln_1"]["scale"], 1e-6), blk["kda"], cf, mask)
                h = ref.rms_norm(x, blk["ln_2"]["scale"], 1e-6)
                if i == 0:
                    x = x + ref.swiglu(h, blk["mlp"])
            w = ref.router_weights(h, blk["mlp"], cf)
            return h, ref.held_experts(h, blk["mlp"], w[..., first : first + held]), ref.swiglu(h, blk["shared"])

    h, whole_routed, shared = jax.jit(moe_terms, static_argnums=(1, 2))(with_experts(0, 16), 0, 16)
    parts = []
    for first in range(0, 16, 2):
        cfg = LingConfig.from_dict(dict(ARCH, **dict(over, first_local_expert=first)))
        tree = with_experts(first, 2)
        @jax.jit
        def this_share(tree, cfg=cfg, first=first):  # the family's own modules and the reference, one program a share
            term = DeepseekV3MLP(cfg, cfg.moe_shared_expert_intermediate_size, f32_out=True).apply(
                {"params": tree["h_1"]["shared"]}, h)
            with_shared, stats = DeepseekV3SparseMLP(cfg).apply({"params": tree["h_1"]["mlp"]}, h, term)
            without, _ = DeepseekV3SparseMLP(cfg).apply({"params": tree["h_1"]["mlp"]}, h, None)
            got = LingModel(cfg).apply({"params": tree}, ids, attention_mask=mask)["hidden"]
            return term, with_shared, without, stats, got, ref.trunk(tree, dict(rc, first_local_expert=first), ids, mask)

        term, with_shared, without, stats, got, want = this_share(tree)
        np.testing.assert_allclose(np.asarray(term), np.asarray(shared), rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(with_shared - without), np.asarray(shared), rtol=1e-4, atol=1e-5)
        parts.append(without)
        assert 0 <= float(stats["rows_here_share"]) < 1 and float(stats["experts_touched"]) <= 2
        # and the model's own forward with this share is the reference's with the same share
        assert rel_err(got, want, mask) < TOL
    total = sum(parts) + shared  # the shared expert counted once
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole_routed + shared), rtol=2e-4, atol=2e-5)
    # no share is the whole: the absent experts' terms are left out
    assert float(jnp.abs(parts[0] - whole_routed).max()) > 1e-3


# ------------------------------ the engine ------------------------------ #


def test_engine_and_fixed_sampler_refuse_what_a_state_and_a_latent_row_cannot_give():
    from trlx_tpu import telemetry
    from trlx_tpu.inference.engine import ContinuousBatchingEngine
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler
    from trlx_tpu.parallel.mesh import make_mesh

    cfg = model_and_params(FAMILY)[0]
    init = functools.partial(init_ling_cache, cfg)
    common = dict(apply_fn=lambda *a, **k: None, init_cache_fn=init,
                  gen_config=GenerationConfig(max_new_tokens=4), query_length=8, vocab_size=96, num_slots=2)
    with pytest.raises(ValueError, match="prefix_pool_blocks.*state layers"):
        ContinuousBatchingEngine(**common, prefix_pool_blocks=2)
    with pytest.raises(ValueError, match="verify_step.*state layers"):
        ContinuousBatchingEngine(**common, spec_max_draft=2)
    pp = make_mesh({"dp": 1, "fsdp": 1, "tp": 1, "pp": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="a pp mesh is not built for a model with state layers"):
        ContinuousBatchingEngine(**common, mesh=pp)
    for axis in ("tp", "ep"):
        mesh = make_mesh({"dp": 1, "fsdp": 1, "tp": 1, axis: 2}, devices=jax.devices()[:2])
        with pytest.raises(ValueError, match=f"a {axis} mesh is not built for a latent cache"):
            ContinuousBatchingEngine(**common, mesh=mesh)
    eng = ContinuousBatchingEngine(**common)
    state = jax.eval_shape(eng._make_state)
    # block tables for the latent layer alone, whose rows the engine holds padded to whole lanes
    assert ["block_tables" in c for c in state.cache] == [False] * 5 + [True, False]
    assert state.cache[5]["k"].shape == (2, 12, 1, 128) and "v" not in state.cache[5]
    assert state.cache[0]["ssm_state"].shape == (2, 4, 16, 16)
    gauges = telemetry.get_metrics().snapshot()["gauges"]
    # both kinds of by-sequence memory are counted, each under its own gauge (logical bytes)
    assert gauges["cache/state_gb"] == pytest.approx(6 * 2 * (4 * 16 * 16 + 3 * 192) * 4 / 1e9)
    assert gauges["cache/latent_gb"] == pytest.approx(2 * 12 * 32 * 4 / 1e9)
    assert gauges["cache/kv_gb"] == 0 and gauges["cache/tail_gb"] == 0
    sampler = make_sampler(lambda *a, **k: None, init, GenerationConfig(max_new_tokens=4), 8, with_values=False)
    with pytest.raises(ValueError, match="rollout.engine: continuous"):
        sampler(None, jnp.zeros((2, 8), jnp.int32), jnp.ones((2, 8), jnp.int32), jax.random.PRNGKey(0))


def test_the_engines_state_holds_the_latent_pool_pinned_beside_the_states():
    """``cache/latent_pinned_share`` is read off the state as it lies: the
    one latent pool among six state layers, its rows whole lanes."""
    from trlx_tpu import telemetry

    eng, params = engine.__wrapped__(FAMILY, 4, 1)
    eng.start_phase(params, jax.random.PRNGKey(5))
    gauges = telemetry.get_metrics().snapshot()["gauges"]
    assert gauges["cache/latent_pinned_share"] == 1.0
    assert gauges["cache/state_gb"] > 0 and gauges["cache/latent_gb"] > 0
