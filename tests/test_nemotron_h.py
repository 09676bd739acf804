"""``model_type: nemotron_h`` (``models/nemotron_h.py``): a layer that is one
sublayer (a Mamba-2 mixer with groups of ``B``/``C``, a grouped-KV NoPE
attention, or a routed MLP whose plain experts work in a latent), against
``benchmark/reference/nemotron_h.py`` on logits: the whole forward; whole
and chunked admission then decode steps through the states and a paged pool;
the paged engine with every slot recycled. Toy widths, seeded weights,
float32 on both sides unless a test says otherwise. Programs, engine and the
tests every family is held to come from ``tests/family_harness.py``."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import nemotron_h as ref
from family_harness import (  # noqa: F401  (the contract tests run here, on FAMILY)
    Q,
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
from trlx_tpu.models.nemotron_h import (
    ATTENTION,
    EXPERTS,
    MAMBA,
    NemotronHConfig,
    NemotronHLatentMoE,
    NemotronHModel,
    NemotronHSharedMLP,
    init_nemotron_h_cache,
)
from trlx_tpu.ops import moe, ssm
from trlx_tpu.ops.kv_cache import PAGED, STATE, cache_kind

ARCH = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=7, hybrid_override_pattern="MEME*EM",
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, mamba_num_heads=8, mamba_head_dim=16,
    ssm_state_size=16, n_groups=4, chunk_size=8, n_routed_experts=4, num_router_experts=16, first_local_expert=4,
    num_experts_per_tok=6, moe_latent_size=32, moe_intermediate_size=48, moe_shared_expert_intermediate_size=80,
    dtype="float32", param_dtype="float32",
)
TOL = 1e-5  # float32 arithmetic on both sides, 21 positions through seven layers: rounding alone reads under 2e-6


def reference_cfg(cfg: NemotronHConfig, **over):
    return dict(
        ARCH, layer_norm_epsilon=cfg.layer_norm_epsilon, conv_kernel=cfg.conv_kernel,
        routed_scaling_factor=cfg.routed_scaling_factor, **over,
    )


def check_forward(cfg, params, out):
    assert cfg.layer_types == (MAMBA, EXPERTS, MAMBA, EXPERTS, ATTENTION, EXPERTS, MAMBA)
    assert float(jnp.abs(params["h_1"]["mlp"]["router_bias"]).max()) > 0  # the selection bias is not zero
    # a layer is one sublayer under one norm: a mixer has no MLP, an expert layer no mixer
    assert set(params["h_0"]) == {"ln_1", "mamba"} and set(params["h_4"]) == {"ln_1", "attn"}
    assert set(params["h_1"]) == {"ln_1", "mlp", "shared"}
    assert set(params["h_1"]["mlp"]) == {"router", "router_bias", "latent_down", "latent_up", "w_up", "w_down"}
    assert params["h_1"]["mlp"]["w_up"].shape == (4, 32, 48)  # the held experts, in the latent; no gate
    assert params["h_0"]["mamba"]["conv_weight"].shape == (4, 8 * 16 + 2 * 4 * 16)  # [x | B of 4 groups | C of 4]
    stats = out["moe_stats"]
    assert set(stats) == {"experts_touched", "max_load", "rows_routed", "rows_here_share"}
    assert float(stats["experts_touched"]) <= 4 and 0 < float(stats["rows_here_share"]) < 1
    assert float(stats["rows_routed"]) == 3 * 3 * 21 * 6  # three expert layers, 6 copies a token


def refuse_more(cfg, model, params):
    from trlx_tpu.models.registry import get_model_family
    from trlx_tpu.parallel.mesh import make_mesh, traced_on

    grouped = NemotronHModel(NemotronHConfig.from_dict(dict(ARCH, n_group=4, topk_group=1)))
    refused("16 experts do not divide into 4 groups of which 1 hold the 6",  # the router's own refusal
            grouped.init, jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    ids = jnp.zeros((2, 4), jnp.int32)
    apply = functools.partial(model.apply, {"params": params}, ids)
    refused("hydra branch .* not built for nemotron_h", apply, start_layer=2)
    refused("speculative verify step.* not built for nemotron_h", apply,
            cache=paged(FAMILY, cfg, 2, 8), cache_index=jnp.zeros((2, 4), jnp.int32))
    refused("the cache has 7 entries; nemotron_h keeps one for each of its 4", apply,
            cache=(paged(FAMILY, cfg, 2, 8)[0],) * 7, cache_index=0)
    for axis in ("tp", "ep", "pp"):
        mesh = make_mesh({"dp": 1, "fsdp": 1, "tp": 1, axis: 2}, devices=jax.devices()[:2])
        refused(f"a {axis} mesh is not built for nemotron_h", traced_on(mesh, apply))
    with pytest.raises(ValueError, match="no checkpoint converter is built for nemotron_h"):
        get_model_family("nemotron_h").load_checkpoint("/nowhere")


def check_registry(fam, cfg, cache):
    from trlx_tpu.models.registry import hidden_size_of, num_layers_of

    assert not fam.supports_ep and fam.stored_width_leaves == ("conv_weight",)
    assert (hidden_size_of(cfg), num_layers_of(cfg)) == (64, 7)
    assert cache[0]["ssm_state"].dtype == jnp.float32 and cache[2]["k"].dtype == jnp.float32
    published = NemotronHConfig()  # the defaults are the published model
    assert published.layer_types.count(MAMBA) == 40 and published.layer_types.count(EXPERTS) == 40
    assert published.layer_types.count(ATTENTION) == 8 and len(published.cache_layer_types) == 48
    assert published.conv_channels == 8192 + 2 * 8 * 128 and published.num_router_experts == 512


def check_paths(t):
    """The decode step steps its three state layers and reads its one pool
    as stored (``paged``); an admission program runs the chunked scan and
    addresses its group's rows inside the whole pool (``paged_rows``);
    every expert layer takes the plain form; the gauges say what the
    programs were built with."""
    for scope in ("ssm_in_proj", "ssm_conv", "ssm_out", "moe_group_router", "moe_latent_down", "moe_dispatch",
                  "moe_experts", "moe_combine", "moe_latent_up", "moe_shared"):
        assert scope in t.step_text and scope in t.chunk_text, scope
    assert "ssm_step" in t.step_text and "ssm_scan" not in t.step_text
    assert "ssm_scan" in t.chunk_text and "ssm_step" not in t.chunk_text
    n_state, n_routed = t.cfg.layer_types.count(MAMBA), t.cfg.layer_types.count(EXPERTS)
    assert t.after_step["ssm/path{path=step}"] == n_state and "ssm/path{path=scan}" not in t.after_step
    assert t.after_step["moe/expert_form{form=plain}"] == n_routed and "moe/expert_form{form=gated}" not in t.after_step
    assert t.after_step["attention/decode_path{path=paged}"] == 1
    assert t.counters["ssm/path{path=scan}"] == n_state and t.counters["moe/expert_form{form=plain}"] == 2 * n_routed
    assert t.counters["attention/decode_path{path=paged_rows}"] == 1
    assert t.gauges["ssm/groups"] == 4 and t.gauges["moe/latent_width"] == 32


FAMILY = Family(
    name="nemotron_h", config_cls=NemotronHConfig, model_cls=NemotronHModel, reference=ref, arch=ARCH,
    reference_cfg=reference_cfg, init_cache=init_nemotron_h_cache, tol=TOL, logprob_tol=TOL,
    cache_layouts=(STATE, STATE, "dense", STATE),  # an expert layer keeps nothing: four entries for seven layers
    refusals={"nemotron_h": [
        ({"hybrid_override_pattern": "MEME*-M"}, r"hybrid_override_pattern's \['-'\].*is not built for nemotron_h"),
        ({"hybrid_override_pattern": "ME*"}, "names 3 layers, num_hidden_layers=7"),
        ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers=1 .*multi-token-prediction"),
        *(({key: True}, "attention_bias / use_bias / mamba_proj_bias / mlp_bias is not built")
          for key in ("attention_bias", "use_bias", "mamba_proj_bias", "mlp_bias")),
        ({"tie_word_embeddings": True}, "tie_word_embeddings=True is not built"),
        ({"residual_in_fp32": True}, "residual_in_fp32=True is not built"),
        ({"sliding_window": 128}, "sliding_window=128 is not built"),
        ({"norm_topk_prob": False}, "norm_topk_prob=False"),
        ({"n_shared_experts": 2}, "n_shared_experts=2"),
        ({"mamba_hidden_act": "gelu"}, "mamba_hidden_act='gelu'"),
        ({"mlp_hidden_act": "swiglu"}, "mlp_hidden_act='swiglu'"),
        ({"kv_cache_dtype": "int8"}, "kv_cache_dtype='int8' beside state layers"),
        ({"state_dtype": "bfloat16"}, "state_dtype='bfloat16'"),
        ({"n_groups": 3}, "does not divide into n_groups=3"),
        ({"mamba_num_heads": 4}, "mamba_num_heads \\* mamba_head_dim != expand \\* hidden_size"),
        ({"first_local_expert": 14}, "experts 14 .. 18 are not among the router's 16"),
    ]},
    engine_cases={"whole": (0, False, {}), "chunk-a-pump": (4, True, {})},
    check_forward=check_forward, check_paths=check_paths, check_registry=check_registry, refuse_more=refuse_more,
)


# ------------------------------ the model ------------------------------ #


@pytest.mark.parametrize("what", ["state", "dt", "router", "groups"])
def test_bfloat16_where_float32_is_stated_fails_the_tolerance(what, monkeypatch):
    """``TOL`` is tight enough that the state, ``dt`` or the router's scores
    rounded to bfloat16, or every head reading group 0's ``B`` and ``C``,
    fails it forty times over and more (``dt`` 4e-4, the state 8e-4, the
    router 1e-3, the groups 0.3: the convolution's taps are the family's
    own, so the state term is a third of a logit's deviation)."""
    cfg, model, params = model_and_params(FAMILY)
    ids, mask = left_padded([21, 13, 5], 21)
    want = programs(FAMILY)[2](params, ids, mask)
    bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    scan = ssm.ssd_scan
    if what == "state":
        def carried_in_bf16(x, dt, A, B, C, D, m, state, chunk):
            ys, S = [], state
            for lo in range(0, x.shape[1], chunk):  # the state a chunk leaves, rounded before the next reads it
                sl = slice(lo, lo + chunk)
                y, S = scan(x[:, sl], dt[:, sl], A, B[:, sl], C[:, sl], D, m[:, sl], bf16(S), chunk)
                ys.append(y)
            return jnp.concatenate(ys, axis=1), S
        monkeypatch.setattr(ssm, "ssd_scan", carried_in_bf16)
    elif what == "dt":
        monkeypatch.setattr(ssm, "ssd_scan", lambda x, dt, *rest: scan(x, bf16(dt), *rest))
    elif what == "groups":
        first = lambda a: jnp.broadcast_to(a[:, :, :1], a.shape)
        monkeypatch.setattr(ssm, "ssd_scan", lambda x, dt, A, B, C, *rest: scan(x, dt, A, first(B), first(C), *rest))
    else:
        route = moe.route_group_limited

        def in_bf16(h, router_w, bias, k, **kw):
            r = route(h, router_w, bias, k, **kw)
            scores = bf16(r.probs)
            chosen = jnp.take_along_axis(scores, r.experts, axis=-1)
            return r._replace(weights=kw["scale"] * chosen / (chosen.sum(-1, keepdims=True) + 1e-20))
        monkeypatch.setattr(moe, "route_group_limited", in_bf16)
    out = jax.jit(lambda p: model.apply({"params": p}, ids, attention_mask=mask))(params)  # traced under the patch
    assert rel_err(out["logits"], want, mask) > 40 * TOL


@pytest.mark.parametrize("chunk", [0, 4], ids=["whole", "chunked"])
def test_admission_then_decode_through_states_and_a_paged_pool_matches_the_full_forward(chunk):
    """An admission of 16 columns (whole, or in chunks of 4 that carry the
    state and the tail from call to call) then five steps, the attention
    layer through a paged pool, the mixers through their grouped state:
    logits against the reference's full forward."""
    cfg, model, params = model_and_params(FAMILY)
    T, Q, cap = 21, 16, 24
    ids, mask = left_padded([21, 13, 6], T, seed=1)
    _, cached, reference = programs(FAMILY)
    want = reference(params, ids, mask)
    cache = paged(FAMILY, cfg, 3, cap, rotate=1)
    # the cache is over the layers that keep something: three mixers and the attention layer, in their order
    assert cfg.cache_layer_types == (MAMBA, MAMBA, ATTENTION, MAMBA)
    assert [cache_kind(c).layout for c in cache] == [STATE, STATE, PAGED, STATE]
    assert cache[0]["ssm_state"].shape == (3, 8, 16, 16) and cache[0]["conv_tail"].shape == (3, 3, 256)
    assert cache[2]["k"].shape == (3, cap, 2, 16)
    positions = positions_of(mask)  # handed over as the engine hands them, whatever the family makes of them
    for lo in range(0, Q, chunk or Q):
        hi = lo + (chunk or Q)
        out = cached(params, ids[:, lo:hi], grow(mask[:, :Q], cap), cache, jnp.asarray(lo) if chunk else 0,
                     positions[:, lo:hi])
        cache = out["cache"]
        assert len(cache) == 4
        assert rel_err(out["logits"], want[:, lo:hi], mask[:, lo:hi]) < TOL
    for t in range(Q, T):
        out = cached(params, ids[:, t : t + 1], grow(mask[:, : t + 1], cap), cache, jnp.full((3,), t, jnp.int32),
                     positions[:, t : t + 1])
        cache = out["cache"]
        assert rel_err(out["logits"][:, 0], want[:, t], mask[:, t]) < TOL


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Four chips that hold 4 experts each of the router's 16: their routed
    parts, each taken up through ``W_up``, and the shared expert, counted
    once, give what the reference computes for the whole layer (all 16
    held); and the model's forward with a share is the reference's with it."""
    over = dict(num_hidden_layers=2, hybrid_override_pattern="ME")
    cfg0, model0, params0 = model_and_params(FAMILY, **over)
    ids, mask = left_padded([9, 4], 9, seed=3)
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    all_experts = {n: 0.1 * jax.random.normal(k, (16,) + params0["h_1"]["mlp"][n].shape[1:])
                   for n, k in zip(("w_up", "w_down"), keys)}
    rc = reference_cfg(cfg0, **over)

    def with_experts(first, held):
        tree = jax.tree_util.tree_map(lambda a: a, params0)
        for n in all_experts:
            tree["h_1"]["mlp"][n] = all_experts[n][first : first + held]
        return tree

    whole = with_experts(0, 16)
    with jax.default_matmul_precision("highest"):
        x = ref.f32(whole["wte"]["embedding"][ids])
        x = x + ref.mamba(ref.rms_norm(x, whole["h_0"]["ln_1"]["scale"], 1e-5), whole["h_0"]["mamba"], rc, mask)
        u = ref.rms_norm(x, whole["h_1"]["ln_1"]["scale"], 1e-5)
        want = ref.routed_mlp(u, whole["h_1"], dict(rc, n_routed_experts=16, first_local_expert=0))
        shared = ref.relu2(u @ whole["h_1"]["shared"]["up_proj"]["kernel"]) @ whole["h_1"]["shared"]["down_proj"]["kernel"]
    parts = []
    for first in range(0, 16, 4):
        cfg = NemotronHConfig.from_dict(dict(ARCH, **dict(over, first_local_expert=first)))
        tree = with_experts(first, 4)
        @jax.jit
        def this_share(tree, cfg=cfg, first=first):  # the family's own modules and the reference, one program a share
            routed, stats = NemotronHLatentMoE(cfg).apply({"params": tree["h_1"]["mlp"]}, u)
            term = NemotronHSharedMLP(cfg).apply({"params": tree["h_1"]["shared"]}, u)
            got = NemotronHModel(cfg).apply({"params": tree}, ids, attention_mask=mask)["hidden"]
            return routed, stats, term, got, ref.trunk(tree, dict(rc, first_local_expert=first), ids, mask)

        routed, stats, term, got, want_trunk = this_share(tree)
        assert routed.dtype == jnp.float32 and routed.shape == u.shape  # back at the model's width
        assert 0 <= float(stats["rows_here_share"]) < 1 and float(stats["experts_touched"]) <= 4
        parts.append(routed)
        np.testing.assert_allclose(np.asarray(term), np.asarray(shared), rtol=2e-5, atol=2e-6)
        assert rel_err(got, want_trunk, mask) < TOL
    total = sum(parts) + shared  # the shared expert counted once
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-4, atol=2e-5)
    # no share is the whole: the absent experts' terms are left out
    assert float(jnp.abs(parts[0] + shared - want).max()) > 1e-3


# ------------------------------ the engine ------------------------------ #


def test_engine_and_fixed_sampler_refuse_what_a_state_cannot_give_and_the_cache_is_over_mixer_layers():
    from trlx_tpu import telemetry
    from trlx_tpu.inference.engine import ContinuousBatchingEngine
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler
    from trlx_tpu.parallel.mesh import make_mesh

    cfg = model_and_params(FAMILY)[0]
    init = functools.partial(init_nemotron_h_cache, cfg)
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
    # four entries for seven layers; block tables for the attention layer alone
    assert ["block_tables" in c for c in state.cache] == [False, False, True, False]
    assert state.cache[2]["k"].shape == (2, 12, 2, 16) and state.cache[0]["ssm_state"].shape == (2, 8, 16, 16)
    gauges = telemetry.get_metrics().snapshot()["gauges"]
    assert gauges["cache/state_gb"] == pytest.approx(3 * 2 * (8 * 16 * 16 + 3 * 256) * 4 / 1e9)
    assert gauges["cache/kv_gb"] == pytest.approx(2 * 2 * 12 * 2 * 16 * 4 / 1e9)
    sampler = make_sampler(lambda *a, **k: None, init, GenerationConfig(max_new_tokens=4), 8, with_values=False)
    with pytest.raises(ValueError, match="rollout.engine: continuous"):
        sampler(None, jnp.zeros((2, 8), jnp.int32), jnp.ones((2, 8), jnp.int32), jax.random.PRNGKey(0))
