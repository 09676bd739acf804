"""``model_type: nemotron_h`` (``models/nemotron_h.py``): a layer that is one
sublayer (a Mamba-2 mixer with groups of ``B``/``C``, a grouped-KV NoPE
attention, or a routed MLP whose plain experts work in a latent), against
``benchmark/reference/nemotron_h.py`` on logits: the whole forward; whole
and chunked admission then decode steps through the states and a paged pool;
the paged engine with every slot recycled. Toy widths, seeded weights,
float32 on both sides unless a test says otherwise."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import nemotron_h as ref
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
from trlx_tpu.ops.kv_cache import PAGED, STATE, cache_kind, identity_block_tables, rotate_block_table

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


@functools.lru_cache(maxsize=None)
def model_and_params(**over):
    cfg = NemotronHConfig.from_dict(dict(ARCH, **over))
    model = NemotronHModel(cfg)
    params = jax.jit(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"])(jax.random.PRNGKey(0))
    # move the ones- and zeros-initialised vectors (norm scales, D, the selection bias) off their defaults
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    leaves = [a + 0.1 * jax.random.normal(k, a.shape) for a, k in zip(leaves, keys)]
    return cfg, model, jax.tree_util.tree_unflatten(tree, leaves)


@functools.lru_cache(maxsize=None)
def jitted(**over):
    """``(forward, cached, reference)`` of ``model_and_params(**over)``, each
    one jitted program a shape (an eager flax apply costs tens of seconds)."""
    cfg, model, _ = model_and_params(**over)
    forward = jax.jit(lambda p, ids, mask: model.apply({"params": p}, ids, attention_mask=mask))
    cached = jax.jit(lambda p, ids, mask, cache, at: model.apply(
        {"params": p}, ids, attention_mask=mask, cache=cache, cache_index=at))
    reference = jax.jit(lambda p, ids, mask: ref.forward(p, reference_cfg(cfg, **over), ids, mask))
    return forward, cached, reference


def left_padded(lens, T, seed=0, vocab=95):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, vocab, (len(lens), T)), jnp.int32)
    mask = jnp.asarray(np.stack([np.r_[np.zeros(T - n), np.ones(n)] for n in lens]), jnp.int32)
    return ids, mask


def rel_err(got, want, where):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    where = np.asarray(where).astype(bool)
    return np.abs(got - want)[where].max() / want[where].std()


# ------------------------------ the model ------------------------------ #


def test_uncached_forward_matches_the_reference_on_left_padded_rows():
    cfg, model, params = model_and_params()
    assert cfg.layer_types == (MAMBA, EXPERTS, MAMBA, EXPERTS, ATTENTION, EXPERTS, MAMBA)
    assert float(jnp.abs(params["h_1"]["mlp"]["router_bias"]).max()) > 0  # the selection bias is not zero
    # a layer is one sublayer under one norm: a mixer has no MLP, an expert layer no mixer
    assert set(params["h_0"]) == {"ln_1", "mamba"} and set(params["h_4"]) == {"ln_1", "attn"}
    assert set(params["h_1"]) == {"ln_1", "mlp", "shared"}
    assert set(params["h_1"]["mlp"]) == {"router", "router_bias", "latent_down", "latent_up", "w_up", "w_down"}
    assert params["h_1"]["mlp"]["w_up"].shape == (4, 32, 48)  # the held experts, in the latent; no gate
    assert params["h_0"]["mamba"]["conv_weight"].shape == (4, 8 * 16 + 2 * 4 * 16)  # [x | B of 4 groups | C of 4]
    ids, mask = left_padded([21, 13, 5], 21)
    forward, _, reference = jitted()
    out = forward(params, ids, mask)
    assert rel_err(out["logits"], reference(params, ids, mask), mask) < TOL
    stats = out["moe_stats"]
    assert set(stats) == {"experts_touched", "max_load", "rows_routed", "rows_here_share"}
    assert float(stats["experts_touched"]) <= 4 and 0 < float(stats["rows_here_share"]) < 1
    assert float(stats["rows_routed"]) == 3 * 3 * 21 * 6  # three expert layers, 6 copies a token


@pytest.mark.parametrize("what", ["state", "dt", "router", "groups"])
def test_bfloat16_where_float32_is_stated_fails_the_tolerance(what, monkeypatch):
    """``TOL`` is tight enough that the state, ``dt`` or the router's scores
    rounded to bfloat16, or every head reading group 0's ``B`` and ``C``,
    fails it forty times over and more (``dt`` 4e-4, the state 8e-4, the
    router 1e-3, the groups 0.3: the convolution's taps are the family's
    own, so the state term is a third of a logit's deviation)."""
    cfg, model, params = model_and_params()
    ids, mask = left_padded([21, 13, 5], 21)
    want = jitted()[2](params, ids, mask)
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


def paged(cfg, rows, cap, rotate=None):
    """The family's cache with its one pool paged (the second row's blocks
    rotated): a state and a tail for a mixer, keys for the attention layer,
    nothing for an expert layer."""
    tables = identity_block_tables(rows, cap // 4)
    if rotate is not None:
        tables = tables.at[rotate].set(rotate_block_table(tables[rotate], 2))
    return tuple(
        c if cache_kind(c).layout == STATE else dict(c, block_tables=tables)
        for c in init_nemotron_h_cache(cfg, rows, cap)
    )


@pytest.mark.parametrize("chunk", [0, 4], ids=["whole", "chunked"])
def test_admission_then_decode_through_states_and_a_paged_pool_matches_the_full_forward(chunk):
    """An admission of 16 columns (whole, or in chunks of 4 that carry the
    state and the tail from call to call) then five steps, the attention
    layer through a paged pool, the mixers through their grouped state:
    logits against the reference's full forward."""
    cfg, model, params = model_and_params()
    T, Q, cap = 21, 16, 24
    ids, mask = left_padded([21, 13, 6], T, seed=1)
    _, cached, reference = jitted()
    want = reference(params, ids, mask)
    cache = paged(cfg, 3, cap, rotate=1)
    # the cache is over the layers that keep something: three mixers and the attention layer, in their order
    assert cfg.cache_layer_types == (MAMBA, MAMBA, ATTENTION, MAMBA)
    assert [cache_kind(c).layout for c in cache] == [STATE, STATE, PAGED, STATE]
    assert cache[0]["ssm_state"].shape == (3, 8, 16, 16) and cache[0]["conv_tail"].shape == (3, 3, 256)
    assert cache[2]["k"].shape == (3, cap, 2, 16)
    grow = lambda m: jnp.concatenate([m, jnp.zeros((3, cap - m.shape[1]), jnp.int32)], axis=1)
    for lo in range(0, Q, chunk or Q):
        hi = lo + (chunk or Q)
        out = cached(params, ids[:, lo:hi], grow(mask[:, :Q]), cache, lo)
        cache = out["cache"]
        assert len(cache) == 4
        assert rel_err(out["logits"], want[:, lo:hi], mask[:, lo:hi]) < TOL
    for t in range(Q, T):
        out = cached(params, ids[:, t : t + 1], grow(mask[:, : t + 1]), cache, jnp.full((3,), t, jnp.int32))
        cache = out["cache"]
        assert rel_err(out["logits"][:, 0], want[:, t], mask[:, t]) < TOL


def test_a_parked_row_keeps_its_state_and_a_fresh_row_forgets_the_slot():
    """The engine's two conventions as the model reads them from the cache
    mask: a row whose ``cache_index`` is past the mask's width (idle or
    finished) leaves state and tail bit for bit; a row with no valid column
    before the call starts from zeros whatever the slot held."""
    cfg, model, params = model_and_params()
    cap = 12
    ids, mask = left_padded([8, 8], 8, seed=2)
    grow = lambda m: jnp.concatenate([m, jnp.zeros((2, cap - m.shape[1]), jnp.int32)], axis=1)
    clean = paged(cfg, 2, cap)
    dirty = tuple(
        {k: jnp.ones_like(v) * 3 for k, v in c.items()} if cache_kind(c).layout == STATE else c for c in clean
    )
    cached = jitted()[1]
    a = cached(params, ids, grow(mask), dirty, 0)
    b = cached(params, ids, grow(mask), clean, 0)
    np.testing.assert_array_equal(a["logits"], b["logits"])  # fresh rows: the slot's leftovers are not read
    parked = jnp.asarray([8, cap + 5], jnp.int32)  # row 1 is parked past the mask's width
    step = cached(params, ids[:, :1], grow(jnp.ones((2, 9), jnp.int32)), b["cache"], parked)
    for before, after in zip(b["cache"], step["cache"]):
        if cache_kind(before).layout == STATE:
            for key in before:
                np.testing.assert_array_equal(before[key][1], after[key][1])
                assert float(jnp.abs(before[key][0] - after[key][0]).max()) > 0


def test_what_the_family_does_not_build_is_refused_by_name():
    from trlx_tpu.models.registry import get_model_family
    from trlx_tpu.parallel.mesh import make_mesh, traced_on

    def refused(match, **over):
        with pytest.raises(ValueError, match=match):
            NemotronHConfig.from_dict(dict(ARCH, **over))

    refused(r"hybrid_override_pattern's \['-'\].*is not built for nemotron_h", hybrid_override_pattern="MEME*-M")
    refused("names 3 layers, num_hidden_layers=7", hybrid_override_pattern="ME*")
    refused("num_nextn_predict_layers=1 .*multi-token-prediction", num_nextn_predict_layers=1)
    for key in ("attention_bias", "use_bias", "mamba_proj_bias", "mlp_bias"):
        refused("attention_bias / use_bias / mamba_proj_bias / mlp_bias is not built", **{key: True})
    refused("tie_word_embeddings=True is not built", tie_word_embeddings=True)
    refused("residual_in_fp32=True is not built", residual_in_fp32=True)
    refused("sliding_window=128 is not built", sliding_window=128)
    refused("norm_topk_prob=False", norm_topk_prob=False)
    refused("n_shared_experts=2", n_shared_experts=2)
    refused("mamba_hidden_act='gelu'", mamba_hidden_act="gelu")
    refused("mlp_hidden_act='swiglu'", mlp_hidden_act="swiglu")
    refused("kv_cache_dtype='int8' beside state layers", kv_cache_dtype="int8")
    refused("state_dtype='bfloat16'", state_dtype="bfloat16")
    refused("does not divide into n_groups=3", n_groups=3)
    refused("mamba_num_heads \\* mamba_head_dim != expand \\* hidden_size", mamba_num_heads=4)
    refused("experts 14 .. 18 are not among the router's 16", first_local_expert=14)
    with pytest.raises(ValueError, match="16 experts do not divide into 4 groups of which 1 hold the 6"):
        cfg = NemotronHConfig.from_dict(dict(ARCH, n_group=4, topk_group=1))  # the router's own refusal
        NemotronHModel(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))

    cfg, model, params = model_and_params()
    ids = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(ValueError, match="hydra branch .* not built for nemotron_h"):
        model.apply({"params": params}, ids, start_layer=2)
    with pytest.raises(ValueError, match="speculative verify step.* not built for nemotron_h"):
        model.apply({"params": params}, ids, cache=paged(cfg, 2, 8), cache_index=jnp.zeros((2, 4), jnp.int32))
    with pytest.raises(ValueError, match="the cache has 7 entries; nemotron_h keeps one for each of its 4"):
        seven = paged(cfg, 2, 8)
        model.apply({"params": params}, ids, cache=(seven[0],) * 7, cache_index=0)
    for axis in ("tp", "ep", "pp"):
        mesh = make_mesh({"dp": 1, "fsdp": 1, "tp": 1, axis: 2}, devices=jax.devices()[:2])
        with pytest.raises(ValueError, match=f"a {axis} mesh is not built for nemotron_h"):
            traced_on(mesh, lambda p, i: model.apply({"params": p}, i)["logits"])(params, ids)
    with pytest.raises(ValueError, match="no checkpoint converter is built for nemotron_h"):
        get_model_family("nemotron_h").load_checkpoint("/nowhere")


def test_registry_builds_the_family_and_its_cache_by_kind():
    from trlx_tpu.models.registry import get_model_family, hidden_size_of, num_layers_of

    fam = get_model_family("nemotron_h")
    assert fam.config_cls is NemotronHConfig and fam.backbone_cls is NemotronHModel
    assert not fam.supports_ep and fam.stored_width_leaves == ("conv_weight",)
    cfg = fam.config_cls.from_dict(dict(ARCH, some_unknown_key=1))
    assert (hidden_size_of(cfg), num_layers_of(cfg)) == (64, 7)
    cache = fam.init_cache(cfg, 2, 8)
    # an expert layer keeps nothing: four entries for seven layers
    assert [cache_kind(c).layout for c in cache] == [STATE, STATE, "dense", STATE]
    assert cache[0]["ssm_state"].dtype == jnp.float32 and cache[2]["k"].dtype == jnp.float32
    published = NemotronHConfig()  # the defaults are the published model
    assert published.layer_types.count(MAMBA) == 40 and published.layer_types.count(EXPERTS) == 40
    assert published.layer_types.count(ATTENTION) == 8 and len(published.cache_layer_types) == 48
    assert published.conv_channels == 8192 + 2 * 8 * 128 and published.num_router_experts == 512


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Four chips that hold 4 experts each of the router's 16: their routed
    parts, each taken up through ``W_up``, and the shared expert, counted
    once, give what the reference computes for the whole layer (all 16
    held); and the model's forward with a share is the reference's with it."""
    over = dict(num_hidden_layers=2, hybrid_override_pattern="ME")
    cfg0, model0, params0 = model_and_params(**over)
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
        routed, stats = NemotronHLatentMoE(cfg).apply({"params": tree["h_1"]["mlp"]}, u)
        assert routed.dtype == jnp.float32 and routed.shape == u.shape  # back at the model's width
        assert 0 <= float(stats["rows_here_share"]) < 1 and float(stats["experts_touched"]) <= 4
        parts.append(routed)
        term = NemotronHSharedMLP(cfg).apply({"params": tree["h_1"]["shared"]}, u)
        np.testing.assert_allclose(np.asarray(term), np.asarray(shared), rtol=2e-5, atol=2e-6)
        got = jax.jit(lambda p: NemotronHModel(cfg).apply({"params": p}, ids, attention_mask=mask)["hidden"])(tree)
        want_trunk = jax.jit(lambda p: ref.trunk(p, dict(rc, first_local_expert=first), ids, mask))(tree)
        assert rel_err(got, want_trunk, mask) < TOL
    total = sum(parts) + shared  # the shared expert counted once
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-4, atol=2e-5)
    # no share is the whole: the absent experts' terms are left out
    assert float(jnp.abs(parts[0] + shared - want).max()) > 1e-3


# ------------------------------ the engine ------------------------------ #

Q, R, EOS = 16, 6, 95


@functools.lru_cache(maxsize=None)
def engine(prefill_chunk=0, chunks_per_pump=0):
    from trlx_tpu.inference.engine import ContinuousBatchingEngine
    from trlx_tpu.models.heads import CausalLMWithValueHead
    from trlx_tpu.ops.sampling import GenerationConfig

    cfg, _, _ = model_and_params()
    model = CausalLMWithValueHead(cfg, backbone_cls=NemotronHModel)
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
        apply_fn=apply_fn, init_cache_fn=functools.partial(init_nemotron_h_cache, cfg),
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
    while len(got) < len(ids):
        free = eng.free_capacity
        if fed < len(ids) and free > 0:
            take = min(free, eng.admit_width, len(ids) - fed)
            eng.submit(ids[fed : fed + take], mask[fed : fed + take])
            fed += take
        for group in eng.pump():
            land(group)
    return got


@pytest.mark.parametrize("chunk,pump", [(0, False), (4, True)], ids=["whole", "chunk-a-pump"])
def test_engine_logprobs_match_the_uncached_forward_on_the_tokens_it_drew(chunk, pump):
    """Ten requests through four slots: every slot is recycled, after
    requests of other lengths (the longest first), with whole and chunked
    admission: the states are zeroed at recycle and the pool's block tables
    rotated. The recorded log-probability of every drawn token is the
    reference's on [prompt; drawn tokens]."""
    eng, params = engine(chunk, 1 if pump else 0)
    cfg = model_and_params()[0]
    lens = [16, 15, 3, 9, 2, 12, 5, 16, 4, 7]
    ids, mask = left_padded(lens, Q, seed=4)
    ids, mask = np.asarray(ids), np.asarray(mask)
    got = drive(eng, params, ids, mask, pump)
    assert sorted(got) == list(range(len(lens)))
    forward = jax.jit(lambda p, i, m: ref.forward(p, reference_cfg(cfg), i, m))
    for r, row in got.items():
        full_ids = jnp.asarray(np.r_[ids[r], row["tokens"]])[None]
        full_mask = jnp.asarray(np.r_[mask[r], row["response_mask"]])[None]
        logits = forward(params["transformer"], full_ids, full_mask)[0]
        lp = jax.nn.log_softmax(logits[Q - 1 : -1], axis=-1)
        want = np.take_along_axis(np.asarray(lp), row["tokens"][:, None], axis=1)[:, 0]
        live = row["response_mask"].astype(bool)
        np.testing.assert_allclose(row["logprobs"][live], want[live], rtol=0, atol=TOL)
    if chunk:
        assert eng.stats.prefill_cols_skipped > 0  # all-pad chunks were not computed


def test_engine_and_fixed_sampler_refuse_what_a_state_cannot_give_and_the_cache_is_over_mixer_layers():
    from trlx_tpu import telemetry
    from trlx_tpu.inference.engine import ContinuousBatchingEngine
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler
    from trlx_tpu.parallel.mesh import make_mesh

    cfg = model_and_params()[0]
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


def test_which_paths_the_engines_programs_traced():
    """Counted per traced call site: the decode step steps its three state
    layers and reads its one pool as stored (``paged``); an admission
    program runs the chunked scan and addresses its group's rows inside the
    whole pool (``paged_rows``); every expert layer takes the plain form;
    the gauges say what the programs were built with; the device scopes
    docs/observability.md names are in the lowered programs."""
    from trlx_tpu import telemetry

    eng, params = engine.__wrapped__(4, 1)  # its own: a program traced before counts nothing again
    cfg = model_and_params()[0]
    with telemetry.scoped_metrics() as reg:
        state = jax.eval_shape(eng._make_state)
        abstract = jax.eval_shape(lambda: params)
        step = eng.decode_step_jit.lower(abstract, state)
        after_step = dict(reg.snapshot()["counters"])
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        chunk = eng.prefill_chunk_jit.lower(abstract, state, i32(2), i32(2, Q), i32(2, Q), i32(2), i32(2),
                                            jax.ShapeDtypeStruct((2,), jnp.uint32), i32())
        after_chunk = reg.snapshot()
    step_text, chunk_text = step.as_text(debug_info=True), chunk.as_text(debug_info=True)
    for scope in ("ssm_in_proj", "ssm_conv", "ssm_out", "moe_group_router", "moe_latent_down", "moe_dispatch",
                  "moe_experts", "moe_combine", "moe_latent_up", "moe_shared"):
        assert scope in step_text and scope in chunk_text, scope
    assert "ssm_step" in step_text and "ssm_scan" not in step_text
    assert "ssm_scan" in chunk_text and "ssm_step" not in chunk_text
    n_state, n_routed = cfg.layer_types.count(MAMBA), cfg.layer_types.count(EXPERTS)
    assert after_step["ssm/path{path=step}"] == n_state and "ssm/path{path=scan}" not in after_step
    assert after_step["moe/expert_form{form=plain}"] == n_routed and "moe/expert_form{form=gated}" not in after_step
    assert after_step["attention/decode_path{path=paged}"] == 1
    counters = after_chunk["counters"]
    assert counters["ssm/path{path=scan}"] == n_state and counters["moe/expert_form{form=plain}"] == 2 * n_routed
    assert counters["attention/decode_path{path=paged_rows}"] == 1
    assert "attention/decode_path{path=generic}" not in counters
    assert after_chunk["gauges"]["ssm/groups"] == 4 and after_chunk["gauges"]["moe/latent_width"] == 32
