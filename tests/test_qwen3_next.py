"""The qwen3_next family (models/qwen3_next.py) and what it forced below it:
the gated delta rule (ops/delta.py: the chunked form, the step, the gated
norm of the other order), a state kind of cache whose caller says which
layers hold keys (ops/kv_cache.py::hybrid_cache), a gated attention layer
with head norms and a partial rotary, a gated shared expert beside a share of
512-wide routing, and the engine's handling of layers that keep a state.

Everything is compared on logits (never sampled tokens) with the plain
float32 reference ``benchmark/reference/qwen3_next.py``, which runs the rule
a position at a time and every held expert on every token: it shares no code
with ops/delta.py or ops/moe.py. Programs, engine and the tests every family is
held to come from ``tests/family_harness.py``.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import qwen3_next as ref
from family_harness import (  # noqa: F401  (the contract tests run here, on FAMILY)
    Family,
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
from trlx_tpu.models.qwen3_next import (
    FULL,
    LINEAR,
    Qwen3NextAttention,
    Qwen3NextConfig,
    Qwen3NextModel,
    Qwen3NextSharedExpert,
    Qwen3NextSparseMLP,
    init_qwen3_next_cache,
)
from trlx_tpu.ops import delta
from trlx_tpu.ops.kv_cache import DENSE, PAGED, STATE, cache_kind, hybrid_cache, state_buffers

ARCH = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=4, full_attention_interval=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, partial_rotary_factor=0.25,
    rope_theta=10000000, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=16, linear_conv_kernel_dim=4, linear_chunk_size=8,
    moe_intermediate_size=32, shared_expert_intermediate_size=48, num_experts=4,
    num_router_experts=16, first_local_expert=4, num_experts_per_tok=4,
    dtype="float32", param_dtype="float32",
)
TOL = 2e-5


def reference_cfg(cfg: Qwen3NextConfig, **over):
    return dict(ARCH, rms_norm_eps=cfg.rms_norm_eps, norm_topk_prob=cfg.norm_topk_prob, **over)


def check_forward(cfg, params, out):
    assert cfg.layer_types == (LINEAR, LINEAR, LINEAR, FULL)
    assert float(jnp.abs(params["h_0"]["ln_1"]["scale"]).max()) > 0  # the offsets are not zero
    stats = out["moe_stats"]
    assert set(stats) == {"experts_touched", "max_load", "rows_routed", "rows_here_share"}
    assert float(stats["experts_touched"]) <= 4 and 0 < float(stats["rows_here_share"]) < 1


def refuse_more(cfg, model, params):
    from trlx_tpu.models import gpt2_moe
    from trlx_tpu.parallel.mesh import make_mesh, traced_on

    ids = jnp.zeros((2, 2), jnp.int32)
    apply = functools.partial(model.apply, {"params": params}, ids)
    refused("verify", apply, attention_mask=jnp.ones((2, 8), jnp.int32),
            cache=init_qwen3_next_cache(cfg, 2, 8), cache_index=jnp.zeros((2, 2), jnp.int32))
    for hook in ({"start_layer": 1}, {"hidden_override": jnp.zeros((2, 2, 64))}, {"capture_hidden_at": 1}):
        refused("hydra branch .* is not built for qwen3_next", apply, **hook)
    gpt2_moe.set_ep_mesh(jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("ep",)))
    try:
        refused("a ep mesh is not built for qwen3_next", apply)
    finally:
        gpt2_moe.reset()
    for axis in ("tp", "ep", "pp"):
        mesh = make_mesh({"dp": 1, "fsdp": 1, "tp": 1, axis: 2}, devices=jax.devices()[:2])
        refused(f"a {axis} mesh is not built for qwen3_next", traced_on(mesh, apply))
    dp = make_mesh({"dp": 2, "fsdp": 1, "tp": 1}, devices=jax.devices()[:2])
    jax.eval_shape(traced_on(dp, apply))  # data axes shard nothing of the model


def check_registry(family, cfg, cache):
    from trlx_tpu.trainer import BaseRLTrainer

    assert cache[3]["k"].shape == (2, 8, 2, 16)  # sized by KV heads of head_dim
    # [B, value heads, key size, value size] and the tail over [q | k | v]
    assert cache[0]["ssm_state"].shape == (2, 4, 8, 16) and cache[0]["conv_tail"].shape == (2, 3, 96)
    assert cache[0]["ssm_state"].dtype == cache[0]["conv_tail"].dtype == jnp.float32
    assert not family.supports_ep and family.stored_width_leaves == ("conv_weight",)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("ep",))
    with pytest.raises(NotImplementedError, match="'qwen3_next' has no experts to shard"):
        BaseRLTrainer.setup_ep_axis(None, mesh, family)
    with pytest.raises(ValueError, match="no checkpoint converter"):
        family.load_checkpoint("somewhere")
    # the published keys alone derive the pattern: every fourth layer full
    whole = family.config_cls()
    assert whole.layer_types.count(FULL) == 12 and whole.layer_types[3] == whole.layer_types[47] == FULL
    assert (whole.conv_channels, whole.rotary_dim, whole.num_router_experts) == (8192, 64, 512)


def check_engine(eng, over):
    """At the published head of 256 the engine holds the pool in lane rows
    (``ops/kv_cache.py::hold_pool``): recycled slots' rotated tables, the
    block write, the gathered view and the read as stored all go through
    the held shape."""
    if over:
        (pool,) = [c["k"] for c in jax.eval_shape(eng._make_state).cache if "k" in c]
        assert pool.shape[2:] == (4, 128)


def check_paths(t):
    """The decode step reads its one KV layer as stored (``paged``) and
    steps its three state layers; an admission program runs the chunked
    form and addresses its group's rows inside the whole pool
    (``paged_rows``)."""
    for scope in ("gdn_in_proj", "gdn_conv", "gdn_out", "attn_gate", "moe_shared", "moe_experts"):
        assert scope in t.step_text and scope in t.chunk_text, scope
    assert "gdn_step" in t.step_text and "gdn_chunk" not in t.step_text
    assert "gdn_chunk" in t.chunk_text and "gdn_step" not in t.chunk_text
    n_state = t.cfg.layer_types.count(LINEAR)
    assert t.after_step["gdn/path{path=step}"] == n_state and "gdn/path{path=chunk}" not in t.after_step
    assert t.after_step["attention/decode_path{path=paged}"] == 1
    assert t.counters["gdn/path{path=chunk}"] == n_state
    assert t.counters["attention/decode_path{path=paged_rows}"] == 1
    assert "ssm/path{path=step}" not in t.after_step and "ssm/path{path=scan}" not in t.counters


FAMILY = Family(
    name="qwen3_next", config_cls=Qwen3NextConfig, model_cls=Qwen3NextModel, reference=ref, arch=ARCH,
    reference_cfg=reference_cfg, init_cache=init_qwen3_next_cache, tol=TOL, logprob_tol=3e-5,
    cache_layouts=(STATE, STATE, STATE, DENSE),
    refusals={"qwen3_next": [
        ({"rope_scaling": {"type": "linear", "factor": 2}}, "rope_scaling"),
        ({"mlp_only_layers": [1]}, "mlp_only_layers"),
        ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
        ({"use_sliding_window": True}, "use_sliding_window"),
        ({"attention_bias": True}, "attention_bias"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"hidden_act": "gelu"}, "hidden_act"),
        ({"kv_cache_dtype": "int8"}, "kv_cache_dtype='int8' beside state layers"),
        ({"state_dtype": "bfloat16"}, "state_dtype"),
        ({"num_experts": 14}, "not among the router's 16"),
        ({"linear_num_value_heads": 3}, "linear_num_value_heads"),
        ({"num_key_value_heads": 3}, "num_key_value_heads"),
        ({"layer_types": ["attention"] * 4}, "layer_types"),
    ]},
    engine_cases={"whole": (0, False, {}), "chunked": (4, False, {}), "chunk-a-pump": (4, True, {}),
                  "chunk-a-pump-heads-of-256": (4, True, {"head_dim": 256})},
    check_forward=check_forward, check_engine=check_engine, check_paths=check_paths,
    check_registry=check_registry, refuse_more=refuse_more,
)


# ------------------------------ the model ------------------------------ #


@pytest.mark.parametrize("head_dim", [16, 256], ids=["heads_of_16", "heads_of_256_held_in_lane_rows"])
@pytest.mark.parametrize("chunk", [0, 4], ids=["whole", "chunked"])
def test_admission_then_decode_through_state_and_paged_pool_matches_the_full_forward(chunk, head_dim):
    """An admission of 16 columns (whole, or in chunks of 4 that carry the
    state and the tail from call to call) then five steps, the full layer
    through a paged pool whose second row's blocks are rotated, the linear
    layers through their state: logits against the reference's full
    forward. The pool is as its holder keeps it (``hold_pool``): at the
    published head of 256, each head as two lane rows."""
    cfg, model, params = model_and_params(FAMILY, head_dim=head_dim)
    T, Q, cap = 21, 16, 24
    ids, mask = left_padded([21, 13, 6], T, seed=1)
    _, cached, reference = programs(FAMILY, head_dim=head_dim)
    want = reference(params, ids, mask)
    cache = paged(FAMILY, cfg, 3, cap, rotate=1, hold=True)
    assert [cache_kind(c).layout for c in cache] == [STATE, STATE, STATE, PAGED]
    assert cache[3]["k"].shape == ((3, cap, 4, 128) if head_dim == 256 else (3, cap, 2, 16))
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


def test_hybrid_cache_takes_the_kinds_that_hold_keys_from_its_caller():
    sizes = dict(n_kv_head=2, head_dim=4, dtype="float32", kv_cache_dtype="bfloat16",
                 state=dict(n_head=2, head_dim=4, d_state=6, conv_width=4, conv_channels=16))
    cache = hybrid_cache([LINEAR, FULL, LINEAR], 2, 8, keys=(FULL,), **sizes)
    assert [set(c) for c in cache] == [{"ssm_state", "conv_tail"}, {"k", "v"}, {"ssm_state", "conv_tail"}]
    assert cache[0]["ssm_state"].shape == (2, 2, 4, 6)
    # granite's cache: the default names "attention" and its layers come out as they did
    granite = hybrid_cache(["mamba", "attention", "mamba"], 2, 8, **sizes)
    assert [set(c) for c in granite] == [set(c) for c in cache]
    for a, b in zip(granite, cache):
        assert {k: (v.shape, v.dtype) for k, v in a.items()} == {k: (v.shape, v.dtype) for k, v in b.items()}
    # without the caller's word a `full_attention` entry would be a state
    assert all("ssm_state" in c for c in hybrid_cache([LINEAR, FULL], 2, 8, **sizes))
    with pytest.raises(ValueError, match="int8"):
        hybrid_cache([LINEAR, FULL], 2, 8, keys=(FULL,), **dict(sizes, kv_cache_dtype="int8"))
    hybrid_cache([FULL, FULL], 2, 8, keys=(FULL,), **dict(sizes, kv_cache_dtype="int8"))  # no state: int8 keys


# ---------------------------- ops/delta.py ------------------------------ #


# the rule's forms as one program a shape (tests/family_harness.py says why)
gated_delta_chunk = jax.jit(delta.gated_delta_chunk, static_argnames=("chunk",))
gated_delta_step = jax.jit(delta.gated_delta_step)
unit_lower_inverse = jax.jit(delta.unit_lower_inverse)


def rule_inputs(B=2, T=24, H=3, Dk=8, Dv=16, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        q=(delta.l2_normalise(jax.random.normal(k[0], (B, T, H, Dk))) * Dk**-0.5).astype(dtype),
        k=delta.l2_normalise(jax.random.normal(k[1], (B, T, H, Dk))).astype(dtype),
        v=jax.random.normal(k[2], (B, T, H, Dv)).astype(dtype),
        g=-0.3 * jax.nn.softplus(jax.random.normal(k[3], (B, T, H))),
        beta=jax.nn.sigmoid(jax.random.normal(k[4], (B, T, H))),
    )


def recurrence(q, k, v, g, beta, state):
    """The rule as its equations, a column at a time, in float64 on the host."""
    q, k, v, g, beta = (np.asarray(x.astype(jnp.float32), np.float64) for x in (q, k, v, g, beta))
    S, out = np.asarray(state, np.float64), []
    for t in range(q.shape[1]):
        S = S * np.exp(g[:, t])[..., None, None]
        held = np.einsum("bhkv,bhk->bhv", S, k[:, t])
        S = S + k[:, t][..., None] * (beta[:, t][..., None] * (v[:, t] - held))[..., None, :]
        out.append(np.einsum("bhkv,bhk->bhv", S, q[:, t]))
    return np.stack(out, axis=1), S


def steps(a, state, lo=0, hi=None):
    outs = []
    for t in range(lo, a["q"].shape[1] if hi is None else hi):
        o, state = gated_delta_step(*(a[n][:, t] for n in ("q", "k", "v", "g", "beta")), state)
        outs.append(o)
    return jnp.stack(outs, axis=1), state


@pytest.mark.parametrize("chunk", [4, 8, 16, 64], ids=lambda c: f"chunk{c}")
def test_the_chunked_form_the_step_and_the_recurrence_agree(chunk):
    """One sequence of 24 columns from a state that is not zero: across
    chunk edges (chunks of 4, 8 and 16), inside one padded chunk (64)."""
    a = rule_inputs()
    state = jax.random.normal(jax.random.PRNGKey(9), (2, 3, 8, 16))
    want_o, want_s = recurrence(**a, state=state)
    o, s = gated_delta_chunk(**a, state=state, chunk=chunk)
    np.testing.assert_allclose(np.asarray(o), want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=2e-5, atol=2e-5)
    o, s = steps(a, state)
    np.testing.assert_allclose(np.asarray(o), want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=2e-5, atol=2e-5)


def test_two_calls_that_carry_the_state_equal_one_and_steps_go_on_from_a_chunk():
    a = rule_inputs(T=16)
    zero = jnp.zeros((2, 3, 8, 16))
    whole_o, whole_s = gated_delta_chunk(**a, state=zero, chunk=4)
    cut = lambda lo, hi: {n: v[:, lo:hi] for n, v in a.items()}
    o1, s1 = gated_delta_chunk(**cut(0, 10), state=zero, chunk=4)  # a call that ends inside a chunk
    o2, s2 = gated_delta_chunk(**cut(10, 16), state=s1, chunk=4)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)), np.asarray(whole_o), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(whole_s), rtol=1e-5, atol=1e-5)
    o3, s3 = steps(a, s1, 10)  # an admission, then decode steps
    np.testing.assert_allclose(np.asarray(o3), np.asarray(whole_o[:, 10:]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s3), np.asarray(whole_s), rtol=1e-5, atol=1e-5)


def test_the_unit_triangular_solve_against_a_plain_inverse():
    """Also where keys repeat: ``A`` near its largest (every ``k_i . k_j``
    one, ``beta`` one), where the Neumann factors grow like binomials."""
    lower = jnp.tril(jnp.ones((64, 64)), -1)
    for A in (0.3 * jax.random.normal(jax.random.PRNGKey(0), (2, 3, 64, 64)) * lower, lower[None]):
        got = unit_lower_inverse(A)
        want = np.linalg.inv(np.eye(64) + np.asarray(A, np.float64))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4 * np.abs(want).max())
    with pytest.raises(ValueError, match="power of two"):
        unit_lower_inverse(jnp.zeros((6, 6)))


def test_a_masked_column_leaves_the_state_bit_for_bit():
    """``beta = 0`` and ``g = 0`` at a masked column (the mixer multiplies
    both with the mask): an all-pad row's state comes back bit for bit from
    the chunked form and from the step, and through the mixer its tail too."""
    a = rule_inputs(T=8)
    state = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 8, 16))
    mask = jnp.asarray([[0] * 8, [0, 0, 0, 1, 1, 1, 1, 1]], jnp.float32)
    masked = dict(a, g=a["g"] * mask[..., None], beta=a["beta"] * mask[..., None])
    _, s = gated_delta_chunk(**masked, state=state, chunk=4)
    np.testing.assert_array_equal(np.asarray(s[0]), np.asarray(state[0]))
    assert not np.allclose(np.asarray(s[1]), np.asarray(state[1]))
    _, s1 = gated_delta_step(*(masked[n][:, 0] for n in ("q", "k", "v", "g", "beta")), state)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(state))  # column 0 is masked in both rows
    keys = jax.random.split(jax.random.PRNGKey(4), 6)
    Hk, Hv, Dk, Dv = 2, 4, 8, 16
    width = 2 * Hk * Dk + Hv * Dv
    layer = {"ssm_state": jax.random.normal(keys[0], (2, Hv, Dk, Dv)),
             "conv_tail": jax.random.normal(keys[1], (2, 3, width))}
    common = dict(conv_weight=jax.random.normal(keys[2], (4, width)), dt_bias=jnp.zeros((Hv,)),
                  A_log=jnp.zeros((Hv,)), n_key_heads=Hk, n_value_heads=Hv, key_dim=Dk, value_dim=Dv, chunk=4)
    for T in (8, 1):  # a chunk, a step
        qkv = jax.random.normal(keys[3], (2, T, width)) * mask[:, :T, None]
        b, a_raw = jax.random.normal(keys[4], (2, T, Hv)), jax.random.normal(keys[5], (2, T, Hv))
        _, new = delta.gated_delta_mix(qkv, b, a_raw, mask=mask[:, :T], cache_layer=layer, **common)
        for k in layer:
            np.testing.assert_array_equal(np.asarray(new[k][0]), np.asarray(layer[k][0]), err_msg=k)
    # a fresh row starts from zeros whatever the slot held
    _, fresh = delta.gated_delta_mix(qkv, b, a_raw, mask=mask[:, :1], fresh=jnp.asarray([True, False]),
                                     cache_layer=layer, **common)
    np.testing.assert_array_equal(np.asarray(fresh["ssm_state"][0]), 0.0)
    np.testing.assert_array_equal(np.asarray(fresh["ssm_state"][1]), np.asarray(layer["ssm_state"][1]))


def long_carry(state_dtype, seed, T=320, T0=256):
    """An admission of ``T0`` columns then ``T - T0`` decode steps of one
    row at the cell's kind of decay (``dt`` log-uniform in [0.001, 0.1] a
    head, ``A`` uniform in (0, 16)), operands in bfloat16 as the program
    hands them over, the state kept in ``state_dtype`` between calls,
    against the recurrence in float64: relative rms errors of (the decoded
    outputs, the final state)."""
    from trlx_tpu.models.granite_hybrid import DT_RANGE

    H, Dk, Dv = 8, 8, 16
    a = rule_inputs(B=1, T=T, H=H, Dk=Dk, Dv=Dv, seed=seed, dtype=jnp.bfloat16)
    k = jax.random.split(jax.random.PRNGKey(100 + seed), 3)
    lo, hi = np.log(DT_RANGE)
    dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(k[0], (H,))) * jnp.exp(0.3 * jax.random.normal(k[1], (1, T, H)))
    a["g"] = -jax.random.uniform(k[2], (H,), minval=0.5, maxval=16.0) * dt
    zero = jnp.zeros((1, H, Dk, Dv))
    want_o, want_s = recurrence(**a, state=zero)
    cut = {n: v[:, :T0] for n, v in a.items()}
    _, state = gated_delta_chunk(**cut, state=zero, chunk=64)
    step, got = gated_delta_step, []
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
    allocated = state_buffers(1, 8, 8, 16, 4, 8)["ssm_state"].dtype
    assert allocated == jnp.float32
    o_err, s_err = long_carry(allocated, seed)
    o_low, s_low = long_carry(jnp.bfloat16, seed)
    assert o_err < 3e-3 and s_err < 3e-3, (o_err, s_err)
    assert s_low > 1.5 * s_err and s_low > 3e-3, (s_low, s_err)


def test_the_gated_norm_is_the_norm_first_and_the_gate_after():
    from trlx_tpu.ops import ssm

    k = jax.random.split(jax.random.PRNGKey(5), 3)
    y, z, w = jax.random.normal(k[0], (2, 3, 16)), jax.random.normal(k[1], (2, 3, 16)), jax.random.normal(k[2], (16,))
    y64, z64, w64 = (np.asarray(x, np.float64) for x in (y, z, w))
    want = y64 / np.sqrt((y64**2).mean(-1, keepdims=True) + 1e-6) * w64 * (z64 / (1 + np.exp(-z64)))
    np.testing.assert_allclose(np.asarray(delta.rms_norm_gated(y, z, w, 1e-6)), want, rtol=1e-5, atol=1e-6)
    assert not np.allclose(np.asarray(ssm.gated_rms_norm(y, z, w, 1e-6)), want, rtol=1e-2, atol=1e-3)


def test_the_uncached_chunked_form_is_differentiable():
    a = rule_inputs(T=8)
    loss = lambda v: gated_delta_chunk(a["q"], a["k"], v, a["g"], a["beta"], jnp.zeros((2, 3, 8, 16)), 4)[0].sum()
    g = jax.grad(loss)(a["v"])
    assert np.isfinite(np.asarray(g)).all() and float(jnp.abs(g).sum()) > 0


# ------------------- the gated attention, by hand ------------------------ #


def test_the_attention_gate_the_head_norms_and_the_partial_rotary_by_hand():
    """One query head over one KV head of 256, 64 of them rotated in halves
    at theta 1e7: three positions worked in float64 from the layer's own
    weights."""
    cfg = Qwen3NextConfig.from_dict(dict(
        ARCH, hidden_size=32, num_attention_heads=1, num_key_value_heads=1, head_dim=256))
    assert cfg.rotary_dim == 64
    layer = Qwen3NextAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, 32))
    positions = jnp.asarray([[0, 1, 2]])
    params = layer.init(jax.random.PRNGKey(1), x, None, positions, causal=True)["params"]
    params = jax.tree_util.tree_map(lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(2), a.shape), params)
    got, _ = layer.apply({"params": params}, x, None, positions, causal=True)
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    x64 = np.asarray(x[0], np.float64)
    q_gate = x64 @ p["q_proj"]["kernel"]
    q, gate = q_gate[:, :256], q_gate[:, 256:]
    norm = lambda v, w: v / np.sqrt((v**2).mean(-1, keepdims=True) + 1e-6) * (1 + w)
    q, k = norm(q, p["q_norm"]["scale"]), norm(x64 @ p["k_proj"]["kernel"], p["k_norm"]["scale"])
    v = x64 @ p["v_proj"]["kernel"]

    def rotate(v):
        out = v.copy()
        for t in range(3):
            for j in range(32):
                angle = t * 1e7 ** (-2 * j / 64)
                a, b = v[t, j], v[t, j + 32]
                out[t, j], out[t, j + 32] = a * np.cos(angle) - b * np.sin(angle), b * np.cos(angle) + a * np.sin(angle)
        return out

    q, k = rotate(q), rotate(k)
    np.testing.assert_array_equal(q[:, 64:], norm(q_gate[:, :256], p["q_norm"]["scale"])[:, 64:])  # 192 unrotated
    scores = q @ k.T / 16.0  # 256^-1/2
    scores[np.triu_indices(3, 1)] = -np.inf
    weights = np.exp(scores - scores.max(-1, keepdims=True))
    o = (weights / weights.sum(-1, keepdims=True)) @ v
    want = (o / (1 + np.exp(-gate))) @ p["o_proj"]["kernel"]
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=2e-4, atol=2e-5)


# --------------------- the expert layer's shares ------------------------ #


def test_the_four_shares_and_the_gated_shared_expert_once_add_up_to_the_uncut_layer():
    """Four chips that hold 4 experts each of the router's 16: their routed
    parts and the gated shared expert, counted once, give what the reference
    computes for the whole layer (all 16 held) - through the model's own
    block, the shared term computed by the family."""
    cfg_whole = dict(ARCH, num_hidden_layers=1, full_attention_interval=4)
    cfg0, model0, params0 = model_and_params(FAMILY, num_hidden_layers=1, first_local_expert=0)
    ids, mask = left_padded([9, 4], 9, seed=3)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    all_experts = {n: 0.1 * jax.random.normal(k, (16,) + params0["h_0"]["mlp"][n].shape[1:])
                   for n, k in zip(("w_gate", "w_up", "w_down"), keys)}
    rc = reference_cfg(cfg0, num_hidden_layers=1)

    def with_experts(first, held):
        tree = jax.tree_util.tree_map(lambda a: a, params0)
        for n in all_experts:
            tree["h_0"]["mlp"][n] = all_experts[n][first : first + held]
        return tree

    # what a block adds beyond its input and mixer: read it off the layer's input to the final norm
    def moe_out(tree, cfg_dict, first, held):
        cf = dict(cfg_dict, num_experts=held, first_local_expert=first)
        blk = tree["h_0"]
        with jax.default_matmul_precision("highest"):
            x = ref.f32(tree["wte"]["embedding"][ids])
            x = x + ref.gated_delta_net(ref.rms_norm(x, blk["ln_1"]["scale"], 1e-6), blk["linear_attn"], cf, mask)
            h = ref.rms_norm(x, blk["ln_2"]["scale"], 1e-6)
            w = ref.router_weights(h, blk["mlp"]["router"], cf["num_experts_per_tok"])
            return h, ref.held_experts(h, blk["mlp"], w[..., first : first + held]), ref.shared_expert(h, blk["shared"])

    h, whole_routed, shared = jax.jit(lambda tree: moe_out(tree, rc, 0, 16))(with_experts(0, 16))
    parts = []
    for first in (0, 4, 8, 12):
        cfg = Qwen3NextConfig.from_dict(dict(cfg_whole, first_local_expert=first))
        tree = with_experts(first, 4)
        @jax.jit
        def this_share(tree, cfg=cfg, first=first):  # the family's own modules and the reference, one program a share
            term = Qwen3NextSharedExpert(cfg).apply({"params": tree["h_0"]["shared"]}, h)
            with_shared, stats = Qwen3NextSparseMLP(cfg).apply({"params": tree["h_0"]["mlp"]}, h, term)
            without, _ = Qwen3NextSparseMLP(cfg).apply({"params": tree["h_0"]["mlp"]}, h, None)
            got = Qwen3NextModel(cfg).apply({"params": tree}, ids, attention_mask=mask)["hidden"]
            return term, with_shared, without, stats, got, ref.trunk(tree, dict(rc, first_local_expert=first), ids, mask)

        term, with_shared, without, stats, got, want = this_share(tree)
        np.testing.assert_allclose(np.asarray(term), np.asarray(shared), rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(with_shared - without), np.asarray(shared), rtol=1e-4, atol=1e-5)
        parts.append(without)
        assert 0 < float(stats["rows_here_share"]) < 1 and float(stats["experts_touched"]) <= 4
        # and the model's own forward with this share is the reference's with the same share
        assert rel_err(got, want, mask) < TOL
    total = sum(parts) + shared  # the shared expert counted once
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole_routed + shared), rtol=2e-4, atol=2e-5)
    # no share is the whole: the absent experts' terms are left out
    assert float(jnp.abs(parts[0] - whole_routed).max()) > 1e-3


# ------------------------------ the engine ------------------------------ #


def test_engine_and_fixed_sampler_refuse_what_a_state_layer_cannot_give():
    from trlx_tpu import telemetry
    from trlx_tpu.inference.engine import ContinuousBatchingEngine
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler
    from trlx_tpu.parallel.mesh import make_mesh

    cfg = model_and_params(FAMILY)[0]
    init = functools.partial(init_qwen3_next_cache, cfg)
    common = dict(apply_fn=lambda *a, **k: None, init_cache_fn=init,
                  gen_config=GenerationConfig(max_new_tokens=4), query_length=8, vocab_size=96, num_slots=2)
    with pytest.raises(ValueError, match="prefix_pool_blocks.*state layers"):
        ContinuousBatchingEngine(**common, prefix_pool_blocks=2)
    with pytest.raises(ValueError, match="verify_step.*state layers"):
        ContinuousBatchingEngine(**common, spec_max_draft=2)
    pp = make_mesh({"dp": 1, "fsdp": 1, "tp": 1, "pp": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="a pp mesh is not built for a model with state layers"):
        ContinuousBatchingEngine(**common, mesh=pp)
    eng = ContinuousBatchingEngine(**common)
    state = jax.eval_shape(eng._make_state)
    assert ["block_tables" in c for c in state.cache] == [False, False, False, True]
    gauges = telemetry.get_metrics().snapshot()["gauges"]
    # `cache/state_gb` counts a matrix state as it counts a state-space one
    assert gauges["cache/state_gb"] == pytest.approx(3 * 2 * (4 * 8 * 16 + 3 * 96) * 4 / 1e9)
    assert gauges["cache/kv_gb"] == pytest.approx(2 * 2 * 12 * 2 * 16 * 4 / 1e9)
    sampler = make_sampler(lambda *a, **k: None, init, GenerationConfig(max_new_tokens=4), 8, with_values=False)
    with pytest.raises(ValueError, match="rollout.engine: continuous"):
        sampler(None, jnp.zeros((2, 8), jnp.int32), jnp.ones((2, 8), jnp.int32), jax.random.PRNGKey(0))
