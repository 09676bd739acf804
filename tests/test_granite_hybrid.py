"""The granitemoehybrid family (models/granite_hybrid.py) and what it forced
below it: the state-space ops (ops/ssm.py), the state kind of cache
(ops/kv_cache.py), grouped KV heads and a score scale in the attention
reads, an expert layer told which experts it holds, and the engine's
handling of layers that keep a state.

Everything is compared on logits (never sampled tokens) with the plain
float32 reference ``benchmark/reference/granitemoehybrid.py``, which runs
the recurrence a position at a time and every held expert on every token:
it shares no code with ops/ssm.py or ops/moe.py. Programs, engine and the
tests every family is held to come from ``tests/family_harness.py``.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import granitemoehybrid as ref
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
    test_a_parked_row_keeps_its_state_and_a_fresh_row_forgets_the_slot,
    test_engine_logprobs_match_the_uncached_forward_on_the_tokens_it_drew,
    test_registry_builds_the_family_and_its_cache_by_kind,
    test_uncached_forward_matches_the_reference_on_left_padded_rows,
    test_what_the_family_does_not_build_is_refused_by_name,
    test_which_paths_the_engines_programs_traced,
)
from trlx_tpu.models.granite_hybrid import (
    GraniteMoeHybridConfig,
    GraniteMoeHybridModel,
    init_granite_hybrid_cache,
)
from trlx_tpu.ops import moe, ssm
from trlx_tpu.ops.attention import decode_attention, dot_product_attention
from trlx_tpu.ops.kv_cache import (
    PAGED,
    STATE,
    cache_kind,
    decode_kv_layout,
    hybrid_cache,
    identity_block_tables,
    kv_buffers,
)

ARCH = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=4,
    layer_types=["mamba", "attention", "mamba", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=32,
    shared_intermediate_size=48, num_local_experts=4, num_router_experts=8,
    first_local_expert=0, num_experts_per_tok=2, mamba_n_heads=16, mamba_d_head=8,
    mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=8,
    dtype="float32", param_dtype="float32",
)
TOL = 1e-5


def reference_cfg(cfg: GraniteMoeHybridConfig, **over):
    keys = ("rms_norm_eps", "residual_multiplier", "embedding_multiplier",
            "attention_multiplier", "logits_scaling")
    return dict(ARCH, **{k: getattr(cfg, k) for k in keys}, **over)


def check_forward(cfg, params, out):
    stats = out["moe_stats"]
    assert set(stats) == {"experts_touched", "max_load", "rows_routed", "rows_here_share"}
    assert float(stats["experts_touched"]) <= 4 and 0 < float(stats["rows_here_share"]) < 1


def refuse_more(cfg, model, params):
    with pytest.raises(ValueError, match="int8"):
        hybrid_cache(["mamba", "attention"], 2, 8, n_kv_head=2, head_dim=4, dtype="float32",
                     kv_cache_dtype="int8", state=dict(n_head=2, head_dim=4, d_state=4,
                                                        conv_width=4, conv_channels=16))
    refused("verify", model.apply, {"params": params}, jnp.zeros((2, 2), jnp.int32),
            attention_mask=jnp.ones((2, 8), jnp.int32),
            cache=init_granite_hybrid_cache(cfg, 2, 8), cache_index=jnp.zeros((2, 2), jnp.int32))


def check_registry(family, cfg, cache):
    assert cache[1]["k"].shape == (2, 8, 2, 16)  # sized by KV heads
    assert cache[0]["ssm_state"].shape == (2, 16, 8, 16) and cache[0]["conv_tail"].shape == (2, 3, 160)
    assert cache[0]["ssm_state"].dtype == jnp.float32


def check_paths(t):
    """The decode step reads its one KV layer as stored (``paged``) and
    steps its three state layers; an admission program scans them and
    addresses its group's rows inside the whole pool (``paged_rows``)."""
    for scope in ("ssm_in_proj", "ssm_conv", "ssm_step", "ssm_out", "moe_shared", "moe_experts"):
        assert scope in t.step_text, scope
    assert "ssm_scan" in t.chunk_text and "ssm_scan" not in t.step_text and "ssm_step" not in t.chunk_text
    n_state = t.cfg.layer_types.count("mamba")
    assert t.after_step["ssm/path{path=step}"] == n_state and "ssm/path{path=scan}" not in t.after_step
    assert t.after_step["attention/decode_path{path=paged}"] == 1
    assert t.counters["ssm/path{path=scan}"] == n_state
    assert t.counters["attention/decode_path{path=paged_rows}"] == 1


FAMILY = Family(
    name="granitemoehybrid", config_cls=GraniteMoeHybridConfig, model_cls=GraniteMoeHybridModel, reference=ref,
    arch=ARCH, reference_cfg=reference_cfg, init_cache=init_granite_hybrid_cache, tol=TOL, logprob_tol=2e-5,
    cache_layouts=("state", "dense", "state", "state"),
    refusals={"granitemoehybrid": [
        ({"rope_scaling": {"type": "linear"}}, "rope_scaling"),
        ({"position_embedding_type": "rope"}, "position_embedding_type"),
        ({"attention_bias": True}, "attention_bias"),
        ({"mamba_n_groups": 2}, "mamba_n_groups"),
        ({"tie_word_embeddings": False}, "tie_word_embeddings"),
        ({"kv_cache_dtype": "int8"}, "kv_cache_dtype"),
        ({"state_dtype": "int8"}, "state_dtype"),
        ({"num_local_experts": 9}, "router"),
    ]},
    engine_cases={"whole": (0, False, {}), "chunked": (4, False, {}), "chunk-a-pump": (4, True, {})},
    check_forward=check_forward, check_paths=check_paths, check_registry=check_registry, refuse_more=refuse_more,
)


# ------------------------------ the model ------------------------------ #


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_prefill_then_decode_through_the_cache_matches_the_full_forward(layout):
    cfg, model, params = model_and_params(FAMILY)
    T, cap = 21, 24
    ids, mask = left_padded([21, 13, 6], T, seed=1)
    _, cached, reference = programs(FAMILY)
    want = reference(params, ids, mask)
    cache = init_granite_hybrid_cache(cfg, 3, cap)
    if layout == "paged":
        cache = paged(FAMILY, cfg, 3, cap, rotate=1)
        assert [cache_kind(c).layout for c in cache] == [STATE, PAGED, STATE, STATE]
    positions = positions_of(mask)  # handed over as the engine hands them, whatever the family makes of them
    out = cached(params, ids[:, :Q], grow(mask[:, :Q], cap), cache, 0, positions[:, :Q])
    assert rel_err(out["logits"], want[:, :Q], mask[:, :Q]) < TOL
    cache = out["cache"]
    for t in range(Q, T):
        # the paged pool takes per-row targets, as the engine's decode step gives them
        at = jnp.full((3,), t, jnp.int32) if layout == "paged" else jnp.asarray(t)
        out = cached(params, ids[:, t : t + 1], grow(mask[:, : t + 1], cap), cache, at, positions[:, t : t + 1])
        cache = out["cache"]
        assert rel_err(out["logits"][:, 0], want[:, t], mask[:, t]) < TOL


# ---------------------------- ops/ssm.py -------------------------------- #


# the scan and the step as one program a shape (tests/family_harness.py says why)
ssd_scan = jax.jit(ssm.ssd_scan, static_argnames=("chunk",))
ssd_step = jax.jit(ssm.ssd_step)


def scan_inputs(B=2, T=24, H=4, P=8, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(k[0], (B, T, H, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (B, T, H))),
        A=-jnp.exp(jax.random.normal(k[2], (H,))),
        B=jax.random.normal(k[3], (B, T, N)),
        C=jax.random.normal(k[4], (B, T, N)),
        D=jax.random.normal(k[5], (H,)),
    )


def sequential(x, dt, A, B, C, D, mask, state):
    """The recurrence a column at a time through :func:`ssm.ssd_step`."""
    ys = []
    for t in range(x.shape[1]):
        y, state = ssd_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], D, mask[:, t], state)
        ys.append(y)
    return jnp.stack(ys, axis=1), state


@pytest.mark.parametrize("chunk", [4, 8, 24, 64], ids=lambda c: f"chunk{c}")
def test_chunked_scan_matches_the_sequential_recurrence(chunk):
    a = scan_inputs()
    mask = jnp.ones((2, 24))
    state = jax.random.normal(jax.random.PRNGKey(9), (2, 4, 8, 16))
    want_y, want_s = sequential(**a, mask=mask, state=state)
    y, s = ssd_scan(**a, mask=mask, state=state, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), rtol=2e-4, atol=2e-4)


def test_two_calls_that_carry_the_state_equal_one():
    a = scan_inputs(T=16)
    mask = jnp.ones((2, 16))
    zero = jnp.zeros((2, 4, 8, 16))
    whole_y, whole_s = ssd_scan(**a, mask=mask, state=zero, chunk=4)
    cut = lambda lo, hi: {k: (v[:, lo:hi] if v.ndim > 1 else v) for k, v in a.items()}
    y1, s1 = ssd_scan(**cut(0, 8), mask=mask[:, :8], state=zero, chunk=4)
    y2, s2 = ssd_scan(**cut(8, 16), mask=mask[:, 8:], state=s1, chunk=4)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(whole_y), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(whole_s), rtol=1e-5, atol=1e-5)


def test_a_masked_column_leaves_state_and_tail_untouched():
    a = scan_inputs(T=8)
    state = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 8, 16))
    mask = jnp.asarray([[0] * 8, [0, 0, 0, 1, 1, 1, 1, 1]], jnp.float32)
    _, s = ssd_scan(**a, mask=mask, state=state, chunk=4)
    np.testing.assert_array_equal(np.asarray(s[0]), np.asarray(state[0]))  # an all-pad row: bit for bit
    assert not np.allclose(np.asarray(s[1]), np.asarray(state[1]))
    step = {k: (v[:, 0] if v.ndim > 1 else v) for k, v in a.items()}
    _, s1 = ssd_step(**step, mask=jnp.asarray([0.0, 1.0]), state=state)
    np.testing.assert_array_equal(np.asarray(s1[0]), np.asarray(state[0]))
    # the convolution's tail: kept where the call holds no valid column,
    # the last K - 1 inputs (pads as zeros) where it does
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 6))
    tail = jax.random.normal(jax.random.PRNGKey(5), (2, 3, 6))
    w = jax.random.normal(jax.random.PRNGKey(6), (4, 6))
    out, new_tail = ssm.causal_conv(x, w, None, tail, mask)
    np.testing.assert_array_equal(np.asarray(new_tail[0]), np.asarray(tail[0]))
    np.testing.assert_array_equal(np.asarray(new_tail[1]), np.asarray(x[1, 5:]))
    want = sum(w[k] * jnp.concatenate([tail[1], x[1] * mask[1][:, None]])[k : k + 8] for k in range(4))
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(want), rtol=1e-6, atol=1e-6)


def long_carry(state_dtype, seed, T=320, T0=256):
    """A prefill of ``T0`` columns then ``T - T0`` decode steps of one row at
    the cell's kind of decay (``dt`` log-uniform in [0.001, 0.1] a head, ``A =
    -(1..H)``), operands in bfloat16 as the program hands them over, the
    state kept in ``state_dtype`` between calls, against the recurrence in
    float64: relative rms errors of (the decoded outputs, the final state)."""
    from trlx_tpu.models.granite_hybrid import DT_RANGE

    H, P, N = 8, 8, 16
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    lo, hi = np.log(DT_RANGE)
    dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(k[1], (H,))) * jnp.exp(0.3 * jax.random.normal(k[5], (1, T, H)))
    bf = lambda key, shape: jax.random.normal(key, shape).astype(jnp.bfloat16)
    a = dict(x=bf(k[0], (1, T, H, P)), dt=dt, A=-jnp.arange(1, H + 1, dtype=jnp.float32),
             B=bf(k[3], (1, T, N)), C=bf(k[4], (1, T, N)), D=jnp.ones((H,)))
    x, B, C = (np.asarray(a[n].astype(jnp.float32), np.float64) for n in ("x", "B", "C"))
    d, A = np.asarray(dt, np.float64), np.asarray(a["A"], np.float64)
    S, want = np.zeros((1, H, P, N)), []
    for t in range(T):
        S = S * np.exp(d[:, t] * A)[..., None, None] + (d[:, t][..., None] * x[:, t])[..., None] * B[:, t][:, None, None, :]
        want.append((S * C[:, t][:, None, None, :]).sum(-1) + x[:, t])
    cut = lambda lo, hi: {n: (v[:, lo:hi] if v.ndim > 1 else v) for n, v in a.items()}
    _, state = ssd_scan(**cut(0, T0), mask=jnp.ones((1, T0)), state=jnp.zeros((1, H, P, N)), chunk=64)
    step, got = ssd_step, []
    for t in range(T0, T):
        now = {n: (v[:, t] if v.ndim > 1 else v) for n, v in a.items()}
        y, state = step(**now, mask=jnp.ones((1,)), state=state.astype(state_dtype))
        got.append(np.asarray(y, np.float64))
    rel = lambda g, w: float(np.sqrt(((g - w) ** 2).mean() / (w**2).mean()))
    return rel(np.stack(got), np.stack(want[T0:])), rel(np.asarray(state, np.float64), S)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_long_carry_holds_the_state_to_float32(seed):
    """What the benchmark's comparison cannot see on the chip (PERF.md §7
    (20)) is held here: the state a cache allocates is float32, and over a
    carry of 320 positions it stays within limits that the same ops with
    the state rounded to bfloat16 between calls do not keep (float32 reads
    1.0e-4..1.7e-4 and 1e-4..6e-4; bfloat16 7e-4..1.3e-3 and 3.7e-3..7.7e-3)."""
    from trlx_tpu.ops.kv_cache import state_buffers

    allocated = state_buffers(1, 8, 8, 16, 4, 8)["ssm_state"].dtype
    assert allocated == jnp.float32
    y_err, s_err = long_carry(allocated, seed)
    assert y_err < 4e-4 and s_err < 1.5e-3, (y_err, s_err)
    y_low, s_low = long_carry(jnp.bfloat16, seed)
    assert y_low > 5e-4 and s_low > 2.5e-3, (y_low, s_low)


def test_call_columns_reads_validity_and_freshness_from_the_cache_mask():
    mask = jnp.asarray([[0, 0, 1, 1, 1, 1, 0, 0], [0, 0, 0, 0, 0, 1, 1, 0], [1] * 8], jnp.int32)
    cols, fresh = ssm.call_columns(mask, 4, 3, 2)  # a chunk of two columns from 4
    np.testing.assert_array_equal(np.asarray(cols), [[1, 1], [0, 1], [1, 1]])
    np.testing.assert_array_equal(np.asarray(fresh), [False, True, False])
    cols, fresh = ssm.call_columns(mask, jnp.asarray([5, 8, 0]), 3, 1)  # per-row; 8 is past the width
    np.testing.assert_array_equal(np.asarray(cols), [[1], [0], [1]])
    np.testing.assert_array_equal(np.asarray(fresh), [False, False, True])


def test_the_uncached_scan_is_differentiable():
    a = scan_inputs(T=8)
    loss = lambda x: ssd_scan(x, a["dt"], a["A"], a["B"], a["C"], a["D"], jnp.ones((2, 8)),
                                  jnp.zeros((2, 4, 8, 16)), 4)[0].sum()
    g = jax.grad(loss)(a["x"])
    assert np.isfinite(np.asarray(g)).all() and float(jnp.abs(g).sum()) > 0


# ------------------- grouped heads and a score scale -------------------- #


def plain_attention(q, k, v, bias, scale):
    G = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale + bias
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)


@pytest.mark.parametrize("layout", ["dense", "folded", "paged"])
@pytest.mark.parametrize("scale", [None, 0.05], ids=["rsqrt", "scale0.05"])
def test_decode_attention_at_four_query_heads_a_kv_head(layout, scale):
    B, C, H, H_kv, Dh, at = 2, 8, 8, 2, 4, 5
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(k[0], (B, 1, H, Dh))
    k_new, v_new = jax.random.normal(k[1], (B, 1, H_kv, Dh)), jax.random.normal(k[2], (B, 1, H_kv, Dh))
    past_k, past_v = jax.random.normal(k[3], (B, C, H_kv, Dh)), jax.random.normal(k[4], (B, C, H_kv, Dh))
    valid = (jnp.arange(C) <= at)[None, None, None, :]
    bias = jnp.where(valid, 0.0, -1e9) * jnp.ones((B, 1, 1, 1))
    full_k = past_k.at[:, at].set(k_new[:, 0])
    full_v = past_v.at[:, at].set(v_new[:, 0])
    want = plain_attention(q, full_k, full_v, bias, Dh**-0.5 if scale is None else scale)
    cache = dict(kv_buffers(1, B, C, H_kv, Dh, jnp.float32)[0], k=past_k, v=past_v)
    index = at
    if layout == "folded":  # no family with grouped heads reaches the fixed sampler's read
        with pytest.raises(ValueError, match="takes equal heads; got 8 query over 2 KV"):
            decode_attention(q, k_new, v_new, decode_kv_layout(cache), index, bias, scale=scale)
        return
    if layout == "paged":
        tables = identity_block_tables(B, C // 2).at[1].set(jnp.asarray([2, 3, 0, 1]))
        # slot 1's logical block j lives in physical block tables[1, j]
        phys = lambda a: a.at[1].set(jnp.zeros_like(a[1]).reshape(4, 2, H_kv, Dh).at[tables[1]].set(
            a[1].reshape(4, 2, H_kv, Dh)).reshape(C, H_kv, Dh))
        cache = dict(cache, k=phys(past_k), v=phys(past_v), block_tables=tables)
        index = jnp.full((B,), at, jnp.int32)
    out, new_kv = decode_attention(q, k_new, v_new, cache, index, bias, scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert new_kv["k"].shape == cache["k"].shape  # H_kv heads wide, as allocated


def test_grouped_prefill_attention_and_per_head_bias():
    B, T, H, H_kv, Dh = 2, 6, 4, 2, 8
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(k[0], (B, T, H, Dh))
    kk, v = jax.random.normal(k[1], (B, T, H_kv, Dh)), jax.random.normal(k[2], (B, T, H_kv, Dh))
    bias = jax.random.normal(k[3], (B, H, T, T))
    got = dot_product_attention(q, kk, v, bias, scale=0.3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain_attention(q, kk, v, bias, 0.3)),
                               rtol=2e-5, atol=2e-5)
    # equal heads and the default scale: the path that was there
    same = dot_product_attention(q, jnp.repeat(kk, 2, 2), jnp.repeat(v, 2, 2), bias)
    np.testing.assert_allclose(np.asarray(same), np.asarray(plain_attention(q, kk, v, bias, Dh**-0.5)),
                               rtol=2e-5, atol=2e-5)


def test_olmoe_still_refuses_grouped_kv_heads_and_says_why():
    from trlx_tpu.models.olmoe import OlmoeConfig

    with pytest.raises(ValueError, match="QK-norm"):
        OlmoeConfig(num_attention_heads=8, num_key_value_heads=2)


# --------------------- the expert layer's shares ------------------------ #


def test_the_shares_add_up_to_the_uncut_layer():
    """Two chips that hold 4 experts each of the router's 8: their routed
    parts and the shared MLP, counted once, give what the reference
    computes for the whole layer (all 8 held)."""
    D, F, E, k = 16, 8, 8, 3
    keys = jax.random.split(jax.random.PRNGKey(2), 6)
    h = jax.random.normal(keys[0], (2, 5, D))
    router = jax.random.normal(keys[1], (D, E))
    w_gate, w_up = jax.random.normal(keys[2], (E, D, F)), jax.random.normal(keys[3], (E, D, F))
    w_down = jax.random.normal(keys[4], (E, F, D))
    shared = jax.random.normal(keys[5], (2, 5, D))
    halves = []
    for first in (0, 4):
        sl = slice(first, first + 4)
        y, routing = moe.expert_layer(h, router, w_gate[sl], w_up[sl], w_down[sl], k=k, norm_topk=True,
                                      dtype=jnp.float32, first_expert=first)
        halves.append(y)
        stats = moe.routing_stats(routing, E, first, 4)
        counts = np.bincount(np.asarray(routing.experts).reshape(-1), minlength=E)[sl]
        assert float(stats["experts_touched"]) == (counts > 0).sum()
        assert float(stats["max_load"]) == pytest.approx(counts.max() / 30)
        assert float(stats["rows_here_share"]) == pytest.approx(counts.sum() / 30)
    with_shared, _ = moe.expert_layer(h, router, w_gate[:4], w_up[:4], w_down[:4], k=k, norm_topk=True,
                                      dtype=jnp.float32, shared=shared)
    np.testing.assert_allclose(np.asarray(with_shared), np.asarray(halves[0] + shared), rtol=1e-5, atol=1e-5)
    weights = ref.router_weights(h, router, k)
    with jax.default_matmul_precision("highest"):
        whole = ref.held_experts(h, {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}, weights)
    np.testing.assert_allclose(np.asarray(halves[0] + halves[1]), np.asarray(whole), rtol=2e-4, atol=2e-4)
    # all experts held: three figures, as before this layer knew of shares
    all_held = moe.routing_stats(routing, E)
    assert set(all_held) == {"experts_touched", "max_load", "rows_routed"}
    with pytest.raises(ValueError, match="not among the router"):
        moe.expert_layer(h, router, w_gate[:4], w_up[:4], w_down[:4], k=k, dtype=jnp.float32, first_expert=5)
    ep_mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("ep",))
    with pytest.raises(ValueError, match="shared term beside the experts is not built on an ep mesh"):
        moe.expert_layer(h, router, w_gate, w_up, w_down, k=k, dtype=jnp.float32, mesh=ep_mesh, shared=shared)


def test_the_model_halves_compose_to_the_uncut_model_layer():
    """The same through the model: one block with experts 0-3, one with 4-7
    (same other weights), against the reference holding all 8."""
    over = dict(num_hidden_layers=1, layer_types=("mamba",))
    cfg_a, model_a, params = model_and_params(FAMILY, **over)
    cfg_b = GraniteMoeHybridConfig.from_dict(dict(ARCH, **over, first_local_expert=4))
    ids, mask = left_padded([9, 4], 9, seed=3)
    other = jax.tree_util.tree_map(lambda a: a, params)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    for name, key in zip(("w_gate", "w_up", "w_down"), keys):
        other["h_0"]["mlp"][name] = 0.1 * jax.random.normal(key, params["h_0"]["mlp"][name].shape)
    whole = jax.tree_util.tree_map(lambda a: a, params)
    for name in ("w_gate", "w_up", "w_down"):
        whole["h_0"]["mlp"][name] = jnp.concatenate([params["h_0"]["mlp"][name], other["h_0"]["mlp"][name]])
    rc = reference_cfg(cfg_a, num_hidden_layers=1, layer_types=["mamba"])
    trunk = lambda cfg: jax.jit(lambda p: ref.trunk(p, cfg, ids, mask))
    hidden = lambda model: jax.jit(lambda p: model.apply({"params": p}, ids, attention_mask=mask)["hidden"])
    ref_whole = trunk(dict(rc, num_local_experts=8))(whole)
    ref_a = trunk(rc)(params)
    ref_b = trunk(dict(rc, first_local_expert=4))(other)
    got_a = hidden(model_a)(params)
    got_b = hidden(GraniteMoeHybridModel(cfg_b))(other)
    assert rel_err(got_a, ref_a, mask) < TOL and rel_err(got_b, ref_b, mask) < TOL
    # the halves differ, and neither is the whole: the absent experts' terms are left out
    assert rel_err(ref_a, ref_whole, mask) > 1e-3 and rel_err(ref_b, ref_whole, mask) > 1e-3


# ------------------------------ the engine ------------------------------ #

@pytest.mark.parametrize("program", ["prefill", "prefill_chunk"])
def test_an_admission_leaves_every_other_slots_keys_and_state_as_they_were(program):
    """Grouped KV heads beside state layers: the attention layer's pool is
    handed to the forward whole and written where the group's rows lie, the
    state layers' rows are taken and set back; either way slots 0 and 1 (a
    running group, two steps in) and the idle slot 2 read bit for bit what
    they read before slot 3 and a dummy are admitted, and slot 3 holds the
    keys and the state of its prompt alone."""
    eng, params = engine(FAMILY, 4, 1)
    cfg, _, backbone = model_and_params(FAMILY)
    _, after, ids, mask = admit_beside_a_running_group(eng, params, program)
    # slot 3 against the same prompt through a dense cache of one row
    dense = init_granite_hybrid_cache(cfg, 1, eng.capacity)
    cache_mask = jnp.concatenate([mask[:1], jnp.zeros((1, R), mask.dtype)], axis=1)
    want = programs(FAMILY)[1](backbone, ids[:1], cache_mask, dense, 0, positions_of(mask[:1]))["cache"]
    table, real, phys = slot_3_rows(eng, mask)
    for now, ref_layer in zip(after.cache, want):
        if cache_kind(now).layout == STATE:
            for k in ref_layer:
                np.testing.assert_allclose(np.asarray(now[k])[3], np.asarray(ref_layer[k])[0], rtol=0, atol=2e-5, err_msg=k)
        else:
            np.testing.assert_array_equal(np.asarray(now["block_tables"])[3], table)
            for k in ("k", "v"):
                np.testing.assert_allclose(np.asarray(now[k])[3, phys], np.asarray(ref_layer[k])[0, real],
                                           rtol=0, atol=2e-5, err_msg=k)


def test_engine_refuses_what_a_state_layer_cannot_give():
    from trlx_tpu.inference.engine import ContinuousBatchingEngine
    from trlx_tpu.ops.sampling import GenerationConfig

    cfg = model_and_params(FAMILY)[0]
    common = dict(
        apply_fn=lambda *a, **k: None, init_cache_fn=functools.partial(init_granite_hybrid_cache, cfg),
        gen_config=GenerationConfig(max_new_tokens=4), query_length=8, vocab_size=96, num_slots=2,
    )
    with pytest.raises(ValueError, match="prefix_pool_blocks"):
        ContinuousBatchingEngine(**common, prefix_pool_blocks=2)
    with pytest.raises(ValueError, match="verify_step"):
        ContinuousBatchingEngine(**common, spec_max_draft=2)
    eng = ContinuousBatchingEngine(**common)
    state = jax.eval_shape(eng._make_state)
    assert ["block_tables" in c for c in state.cache] == [False, True, False, False]
    from trlx_tpu import telemetry

    gauges = telemetry.get_metrics().snapshot()["gauges"]
    assert gauges["cache/state_gb"] == pytest.approx(3 * 2 * (16 * 8 * 16 + 3 * 160) * 4 / 1e9)
    assert gauges["cache/kv_gb"] == pytest.approx(2 * 2 * 12 * 2 * 16 * 4 / 1e9)


def test_the_fixed_sampler_refuses_state_layers_by_name():
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler

    cfg = model_and_params(FAMILY)[0]
    sampler = make_sampler(
        lambda *a, **k: None, functools.partial(init_granite_hybrid_cache, cfg),
        GenerationConfig(max_new_tokens=4), 8, with_values=False,
    )
    with pytest.raises(ValueError, match="rollout.engine: continuous"):
        sampler(None, jnp.zeros((2, 8), jnp.int32), jnp.ones((2, 8), jnp.int32), jax.random.PRNGKey(0))


def test_the_fused_read_takes_a_scale_over_an_int8_cache_as_the_generic_read_does():
    from trlx_tpu.ops.kv_cache import dense_write_read

    B, C, H, Dh, at = 2, 8, 4, 8, 5
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(k[0], (B, 1, H, Dh))
    k_new, v_new = jax.random.normal(k[1], (B, 1, H, Dh)), jax.random.normal(k[2], (B, 1, H, Dh))
    cache = kv_buffers(1, B, C, H, Dh, jnp.float32, "int8")[0]
    _, _, cache = dense_write_read(cache, jax.random.normal(k[3], (B, at, H, Dh)),
                                   jax.random.normal(k[4], (B, at, H, Dh)), 0, jnp.float32)
    bias = jnp.where((jnp.arange(C) <= at)[None, None, None, :], 0.0, -1e9) * jnp.ones((B, 1, 1, 1))
    want, _ = decode_attention(q, k_new, v_new, cache, at, bias, scale=0.2)
    got, new_kv = decode_attention(q, k_new, v_new, decode_kv_layout(cache), at, bias, scale=0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
    assert new_kv["k"].dtype == jnp.int8 and new_kv["k_scale"].shape == (B, H, C)
