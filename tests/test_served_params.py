"""What a server's engine reads (`utils.served_params`, `InferenceServer.served`).

A server's decode step is one program a token, so a float32 matrix cast
to the compute dtype inside it is converted again every token. The server
makes the copy once: every leaf of rank >= 2 wider than the compute dtype
whose path holds none of `ROLLOUT_CAST_EXCLUDE` nor of its family's
`stored_width_leaves` is stored at the compute dtype; vectors stay as
stored, and `InferenceServer.params` stays the tree the caller gave. That is exact only where every program
first uses such a leaf through a cast to the compute dtype, which the
jaxpr test below reads from each family's own code.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from trlx_tpu import telemetry
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.models.heads import CausalLMWithValueHead
from trlx_tpu.trainer.ppo_trainer import get_causal_arch
from trlx_tpu.utils import (
    ROLLOUT_CAST_EXCLUDE,
    cast_is_exact,
    compute_dtype_cast,
    served_params,
    tree_gb,
)

DP_MESH = {"dp": -1, "fsdp": 1, "tp": 1}
ZAYA_ROPE = {
    "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000, "rope_type": "default"},
    "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000, "rope_type": "default"},
    "rope_type": "default",
}
GPT2 = {"vocab_size": 32, "n_positions": 32, "n_embd": 32, "n_layer": 2, "n_head": 2}
ARCHS = {
    "gpt2": GPT2,
    "gptj": {"vocab_size": 32, "n_positions": 16, "n_embd": 32, "n_layer": 2, "n_head": 2,
             "rotary_dim": 8},
    "gpt_neo": {"vocab_size": 32, "max_position_embeddings": 16, "hidden_size": 32,
                "num_layers": 2, "num_heads": 2, "window_size": 3,
                "attention_layers": ["global", "local"]},
    "gpt_neox": {"vocab_size": 32, "max_position_embeddings": 32, "hidden_size": 32,
                 "num_hidden_layers": 2, "num_attention_heads": 2, "rotary_pct": 0.5},
    "gpt2_moe": dict(GPT2, n_experts=2, moe_every=2, capacity_factor=4.0),
    "olmoe": dict(vocab_size=96, max_position_embeddings=64, hidden_size=64,
                  num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
                  intermediate_size=32, num_experts=8, num_experts_per_tok=2,
                  norm_topk_prob=False, rms_norm_eps=1e-5, rope_theta=10000.0),
    "granitemoehybrid": dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=4,
        layer_types=["mamba", "attention", "mamba", "mamba"], num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=32, shared_intermediate_size=48,
        num_local_experts=4, num_router_experts=8, first_local_expert=0,
        num_experts_per_tok=2, mamba_n_heads=16, mamba_d_head=8, mamba_d_state=16,
        mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=8),
    "zaya": dict(vocab_size=96, hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, cca_time0=2, cca_time1=2,
                 moe_intermediate_size=32, num_experts=4, num_experts_per_tok=1,
                 router_hidden_size=16, partial_rotary_factor=0.5,
                 rope_parameters=ZAYA_ROPE, rms_norm_eps=1e-5),
    "qwen3_next": dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=4, full_attention_interval=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=16,
        linear_chunk_size=8, moe_intermediate_size=32, shared_expert_intermediate_size=48,
        num_experts=4, num_router_experts=8, first_local_expert=0, num_experts_per_tok=2),
    "ling": dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=7, first_k_dense_replace=1, layer_group_size=6,
        num_attention_heads=4, num_key_value_heads=4, head_dim=16, kv_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, rotary_dim=8, v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=32, num_experts=4, num_router_experts=8, first_local_expert=0,
        num_experts_per_tok=2, n_group=4, topk_group=2),
    "nemotron_h": dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=4, hybrid_override_pattern="ME*E",
        num_attention_heads=4, num_key_value_heads=2, head_dim=16, mamba_num_heads=8, mamba_head_dim=16,
        ssm_state_size=16, n_groups=2, chunk_size=8, n_routed_experts=4, num_router_experts=8,
        first_local_expert=0, num_experts_per_tok=3, moe_latent_size=32, moe_intermediate_size=48,
        moe_shared_expert_intermediate_size=80),
}


def trl_config(model_type, mesh=None, dtype="bfloat16", param_dtype="float32"):
    return TRLConfig.from_dict({
        "model": {"model_type": model_type, "model_arch": ARCHS[model_type]},
        "train": {
            "seq_length": 8, "batch_size": 8, "epochs": 1, "total_steps": 1,
            "eval_interval": 1000, "checkpoint_interval": 100000,
            "mesh": dict(mesh or DP_MESH), "dtype": dtype, "param_dtype": param_dtype,
            "rollout": {"slots": 8, "admit_width": 8, "harvest_width": 8, "block_size": 4},
        },
        "method": {
            "name": "PPOConfig", "num_rollouts": 8, "chunk_size": 8, "ppo_epochs": 1,
            "gen_kwargs": {"max_new_tokens": 6, "min_new_tokens": 6, "do_sample": True,
                           "eos_token_id": 30, "pad_token_id": 31},
        },
    })


def build_model(config):
    family, model_config, _ = get_causal_arch(config)
    return family, model_config, CausalLMWithValueHead(
        model_config, backbone_cls=family.backbone_cls
    )


def perturbed_params(config, seed=0):
    """Seeded weights with float32 noise on every leaf: no norm scale is
    one, no bias zero, nothing bf16 holds exactly."""
    _, _, model = build_model(config)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [a + 0.1 * jax.random.normal(k, a.shape, a.dtype) for a, k in zip(leaves, keys)]
    )


def prompts(n=6, seed=3):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, 30, int(rng.integers(2, 9)))) for _ in range(n)]


def serve(config, params, monkeypatch, rule=None):
    """Tokens as streamed, and the harvested tokens and log-probabilities,
    of a server built on ``params``; ``rule`` stands in for the server's."""
    from trlx_tpu.inference.server import InferenceServer

    with monkeypatch.context() as patch:
        if rule is not None:
            patch.setattr("trlx_tpu.utils.served_params", rule)
        server = InferenceServer(config, params=params, seed=5)
    rids = server.submit(prompts(), stream=True)
    streamed = [list(server.stream(r)) for r in rids]
    results = server.wait(rids)
    assert streamed == [results[r]["tokens"] for r in rids]
    return server, streamed, [np.asarray(results[r]["logprobs"], np.float32) for r in rids]


# -------------------------- (a) exactness ------------------------------- #


@pytest.mark.parametrize("model_type", ["gpt_neox", "gpt2"])
def test_served_copy_streams_what_the_float32_tree_streams(model_type, monkeypatch):
    config = trl_config(model_type)
    params = perturbed_params(config)
    server, tokens, logprobs = serve(config, params, monkeypatch)
    dtypes = {leaf.ndim >= 2: leaf.dtype
              for leaf in jax.tree_util.tree_leaves(server.served["transformer"])}
    assert dtypes == {True: jnp.bfloat16, False: jnp.float32}
    # what the caller gave is what `params` still is
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree_util.tree_leaves(server.params))

    # an engine handed the float32 tree as it is
    masters, m_tokens, m_logprobs = serve(config, params, monkeypatch, rule=lambda tree, dtype, keep: tree)
    assert masters.served is masters.params
    assert tokens == m_tokens
    for got, want in zip(logprobs, m_logprobs):
        np.testing.assert_array_equal(got, want)

    # the trainers' rule in its place rounds the norm vectors, which the
    # programs apply in float32: another result on weights like these
    def every_float_leaf(tree, dtype, keep):
        return jax.jit(lambda t: compute_dtype_cast(t, dtype))(tree)

    _, r_tokens, r_logprobs = serve(config, params, monkeypatch, rule=every_float_leaf)
    assert r_tokens != tokens or any(
        not np.array_equal(a, b) for a, b in zip(r_logprobs, logprobs)
    )


# ------------------- (b) what the programs do with a leaf ---------------- #

# data movement: a cast commutes with each of these
MOVES = {"gather", "reshape", "transpose", "squeeze", "slice", "dynamic_slice",
         "broadcast_in_dim", "copy"}


def bodies(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            if isinstance(v, jcore.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jcore.Jaxpr):
                yield v


def consumers(jaxpr, var, found):
    """Adds to ``found`` every ``(primitive, new_dtype)`` that consumes
    ``var`` or a moved copy of it, through ``pjit`` / ``custom_jvp`` /
    ``remat`` bodies; returns the positions of ``jaxpr.outvars`` through
    which it leaves this body."""
    leaves = [k for k, v in enumerate(jaxpr.outvars) if v is var]
    for eqn in jaxpr.eqns:
        where = [i for i, v in enumerate(eqn.invars) if v is var]
        if not where:
            continue
        name = eqn.primitive.name
        inner = list(bodies(eqn))
        if inner:
            for body in inner:
                shift = len(eqn.invars) - len(body.invars)
                for i in where:
                    if i < shift or len(body.outvars) != len(eqn.outvars):
                        found.add((name + " (operand not followed)", None))
                        continue
                    for k in consumers(body, body.invars[i - shift], found):
                        leaves += consumers(jaxpr, eqn.outvars[k], found)
        elif name in MOVES and where == [0]:
            for out in eqn.outvars:
                leaves += consumers(jaxpr, out, found)
        else:
            found.add((name, eqn.params.get("new_dtype")))
    return leaves


@functools.lru_cache(maxsize=None)
def leaf_consumers(model_type, columns):
    """``{path: (leaf, cast by the rule, kept by the family's own names,
    consumers)}`` over a cached call of ``columns`` columns through the
    module a server applies (backbone and value head), at bf16 arithmetic on
    float32 weights."""
    config = trl_config(model_type)
    family, model_config, model = build_model(config)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    )
    keep = family.stored_width_leaves
    B, C = 2, 16
    from trlx_tpu.ops.kv_cache import cache_kind, identity_block_tables

    # a latent layer is read through block tables only (ops/attention.py::decode_attention)
    cache = jax.eval_shape(lambda: tuple(
        dict(layer, block_tables=identity_block_tables(B, C // 4)) if cache_kind(layer).latent else layer
        for layer in family.init_cache(model_config, B, C)
    ))

    def call(p, ids, mask, positions, cache, index):
        return model.apply({"params": p}, ids, attention_mask=mask, position_ids=positions,
                           cache=cache, cache_index=index)

    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    closed = jax.make_jaxpr(call)(
        params, ints(B, columns), ints(B, C), ints(B, columns), cache, ints()
    )
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    out = {}
    for (path, leaf), var in zip(flat, closed.jaxpr.invars):
        found = set()
        if consumers(closed.jaxpr, var, found):
            found.add(("returned", None))
        name = "/".join(str(p.key) for p in path)
        out[name] = (leaf, cast_is_exact(path, leaf, config.train.dtype, keep),
                     [ex for ex in (*ROLLOUT_CAST_EXCLUDE, *keep) if ex in name], found)
    return out


THROUGH_THE_CAST = {("convert_element_type", jnp.dtype("bfloat16"))}


@pytest.mark.parametrize("model_type", sorted(ARCHS))
def test_every_cast_leaf_is_first_used_through_the_cast(model_type):
    n_cast = 0
    for columns in (1, 8):  # a decode step, an admission forward
        for path, (leaf, cast, names, found) in leaf_consumers(model_type, columns).items():
            if cast:
                # nothing may see its float32 value
                assert leaf.ndim >= 2 and found <= THROUGH_THE_CAST, (path, found)
                n_cast += 1
            elif leaf.ndim >= 2:
                # a matrix is kept only by name
                assert names, path
    assert n_cast


def test_each_excluded_name_is_a_leaf_some_program_uses_at_its_width():
    """No name sits in a list without a program of its family (of some
    family, for the shared list) that consumes a leaf of that name other
    than through the cast."""
    from trlx_tpu.models.registry import get_model_family

    earned = {}
    for model_type in ("gpt2_moe", "granitemoehybrid", "zaya", "qwen3_next", "ling", "nemotron_h"):
        earned[model_type] = set()
        for path, (leaf, cast, names, found) in leaf_consumers(model_type, 8).items():
            if found - THROUGH_THE_CAST:
                earned[model_type] |= set(names)
        own = set(get_model_family(model_type).stored_width_leaves)
        assert earned[model_type] - set(ROLLOUT_CAST_EXCLUDE) == own
    assert set.union(*earned.values()) >= set(ROLLOUT_CAST_EXCLUDE)


# ------------------- (c) nothing to do, nothing done --------------------- #


def shapes_of(config, cast=None):
    _, _, model = build_model(config)
    init = lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return jax.eval_shape(init if cast is None else lambda: cast(init()))


@pytest.mark.parametrize("case", ["stored_at_the_compute_dtype", "float32_compute",
                                  "cast_by_a_trainer"])
def test_a_tree_with_no_wider_matrix_comes_back_as_it_is(case):
    """Decided on the host: the leaves here are shapes, so any dispatch on
    them would raise."""
    if case == "stored_at_the_compute_dtype":
        tree, dtype = shapes_of(trl_config("olmoe", param_dtype="bfloat16")), "bfloat16"
    elif case == "float32_compute":
        tree, dtype = shapes_of(trl_config("gpt2", dtype="float32")), "float32"
    else:
        # router and fc2 stay float32 matrices there, and stay excluded
        tree = shapes_of(trl_config("gpt2_moe"), lambda t: compute_dtype_cast(t, "bfloat16"))
        dtype = "bfloat16"
        assert any(leaf.dtype == jnp.float32 and leaf.ndim >= 2
                   for leaf in jax.tree_util.tree_leaves(tree))
    assert served_params(tree, dtype) is tree
    assert not any(cast_is_exact(path, leaf, dtype)
                   for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0])


def test_the_copy_is_sharded_like_the_masters_and_the_masters_live_on():
    """On the audit mesh (dp x fsdp x tp over the 8 host devices) the
    kernels are split; the caller's tree is neither donated nor deleted."""
    from trlx_tpu.analysis import harness
    from trlx_tpu.inference.server import InferenceServer

    config = trl_config("gpt_neox", mesh=harness.audit_mesh_config())
    params = perturbed_params(config)
    server = InferenceServer(config, params=params, seed=0)
    copies = jax.tree_util.tree_leaves(server.served)
    wanted = jax.tree_util.tree_leaves(server.param_shardings)
    assert [c.sharding for c in copies] == wanted
    assert any(not s.is_fully_replicated for s in wanted)
    for master, copy in zip(jax.tree_util.tree_leaves(params), copies):
        assert not master.is_deleted() and master.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(master.astype(copy.dtype), np.float32), np.asarray(copy, np.float32)
        )


# --------------------------- the counters -------------------------------- #


def test_param_gb_follows_what_the_engine_was_handed():
    from trlx_tpu.inference.server import InferenceServer

    config = trl_config("gpt_neox")
    params = perturbed_params(config)
    registry = telemetry.get_metrics()
    registry.clear()  # the counter is the process's, over every server built
    server = InferenceServer(config, params=params, seed=0)
    engine = server.engine
    given, served = tree_gb(server.params), tree_gb(server.served)
    assert given == tree_gb(params)
    matrices = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params) if leaf.ndim >= 2
                   ) - params["v_head"]["fc2"]["kernel"].size
    assert given - served == pytest.approx(2 * matrices / 1e9)
    assert engine.stats.param_gb == pytest.approx(served)
    assert engine.stats.to_dict()["engine/param_gb"] == pytest.approx(served, abs=1e-4)
    metrics = server.metrics()
    assert metrics["param_gb_as_given"] == pytest.approx(given)
    assert metrics["param_gb_served"] == pytest.approx(served)
    assert metrics["serve/param_leaves_cast"] == sum(
        leaf.dtype == jnp.bfloat16 for leaf in jax.tree_util.tree_leaves(server.served)) > 0

    # the driver clears the registry after warm-up: the done poll publishes again
    registry.clear()
    server.generate(prompts(2))
    assert registry.snapshot()["gauges"]["engine/param_gb"] == pytest.approx(served)

    # a tree of another width, applied at the loop's safe point and not before
    engine.push_weights(server.params)
    assert engine.stats.param_gb == pytest.approx(served)
    server.generate(prompts(2, seed=4))
    assert engine.stats.weight_pushes == 1
    assert engine.stats.param_gb == pytest.approx(given)
    assert registry.snapshot()["gauges"]["engine/param_gb"] == pytest.approx(given)
    assert server.metrics()["param_gb_served"] == pytest.approx(given)
