"""OLMoE (models/olmoe.py, ops/moe.py) at a tiny size on the CPU: d 64, 4
heads of 16, 2 layers, 8 experts of which 2 a token, expert width 32.

The program is held to ``benchmark/reference/olmoe.py`` (plain float32,
every expert on every token) on logits and on gradients, to the installed
``transformers`` implementation through the checkpoint converter, to itself
through the cache (fixed sampler: fused read; continuous engine: paged) and
across ``ep`` meshes, and run end to end through both trainers and the
serving tier.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import olmoe as reference  # noqa: E402
from family_harness import (  # noqa: E402, F401  (the contract test runs here, on FAMILY)
    Family,
    test_what_the_family_does_not_build_is_refused_by_name,
)
from trlx_tpu.models.olmoe import OlmoeConfig, OlmoeModel  # noqa: E402
from trlx_tpu.ops import moe  # noqa: E402
from trlx_tpu.telemetry.health import without_timing  # noqa: E402

ARCH = dict(
    vocab_size=96, max_position_embeddings=64, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=32, num_experts=8,
    num_experts_per_tok=2, norm_topk_prob=False, rms_norm_eps=1e-5, rope_theta=10000.0,
)
MASK = np.array([[0] * 4 + [1] * 8, [1] * 12, [0] * 7 + [1] * 5], np.int32)
IDS = np.random.default_rng(1).integers(0, 95, MASK.shape).astype(np.int32)
FAMILY = Family(
    name="olmoe", config_cls=OlmoeConfig, model_cls=OlmoeModel, arch=ARCH,
    refusals={key: [({key: value}, key)] for key, value in [
        ("num_key_value_heads", 2), ("clip_qkv", 8.0), ("rope_scaling", {"type": "linear", "factor": 2}),
        ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ]},
)


def build(dtype="float32", seed=0, expert_scale=15, **arch):
    cfg = OlmoeConfig.from_dict(dict(ARCH, dtype=dtype, param_dtype="float32", **arch))
    model = OlmoeModel(cfg)

    def seeded(key, noise):
        params = model.init(key, IDS, MASK)["params"]
        # norm scales away from 1, routing away from uniform and the experts'
        # output as large as the residual stream, so that an error in any shows
        keys = iter(jax.random.split(noise, 64))
        return jax.tree_util.tree_map(
            lambda a: a * (1 + 0.1 * jax.random.normal(next(keys), a.shape)) if a.ndim == 1
            else a * (3, 3, expert_scale)[a.ndim - 1],
            params,
        )

    return model, jax.jit(seeded)(jax.random.PRNGKey(seed), jax.random.PRNGKey(seed + 1))


def logits_of(model, params, ids=IDS, mask=MASK):
    """One jitted program a call (tests/family_harness.py says why); a test
    that patches an op gets its trace after the patch."""
    return jax.jit(lambda p: model.apply({"params": p}, ids, mask)["logits"])(params)


def reference_logits(params, cfg=ARCH, ids=IDS, mask=MASK):
    return jax.jit(lambda p: reference.forward(p, cfg, ids, mask))(params)


def peak_err(got, want, where=None):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if where is not None:
        got, want = got[where], want[where]
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


REAL = MASK.astype(bool)


@pytest.fixture(scope="module")
def f32():
    return build()


# 1 ------------------------------------------------------------------------- #


def test_logits_match_reference_on_left_padded_rows(f32):
    model, params = f32
    assert peak_err(logits_of(model, params), reference_logits(params), REAL) <= 1e-4


def test_norm_topk_prob_matches_reference():
    model, params = build(norm_topk_prob=True)
    want = reference_logits(params, dict(ARCH, norm_topk_prob=True))
    assert peak_err(logits_of(model, params), want, REAL) <= 1e-4


# 2 ------------------------------------------------------------------------- #


def ppo_like_loss(logits, aux):
    """The log-probability of a fixed token at the real positions, plus
    the balance penalty at the published coefficient."""
    logp = jax.nn.log_softmax(logits)[..., 3]
    return (logp * MASK).sum() + 0.01 * aux


@pytest.fixture(scope="module")
def grads(f32):
    model, params = f32

    def through_program(p):
        out, state = model.apply({"params": p}, IDS, MASK, mutable=["moe_losses"])
        return ppo_like_loss(out["logits"], moe.moe_loss_summary(state["moe_losses"])["aux_loss"])

    def through_reference(p):
        return ppo_like_loss(*reference.forward_with_aux(p, ARCH, IDS, MASK))

    flat = lambda g: {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(g)}
    return flat(jax.jit(jax.grad(through_program))(params)), flat(jax.jit(jax.grad(through_reference))(params))


LEAVES = sorted(
    jax.tree_util.keystr(k) for k, _ in jax.tree_util.tree_leaves_with_path(
        jax.eval_shape(lambda: OlmoeModel(OlmoeConfig.from_dict(ARCH)).init(
            jax.random.PRNGKey(0), IDS, MASK)["params"])
    )
)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_matches_reference(grads, leaf):
    got, want = grads
    assert peak_err(got[leaf], want[leaf]) <= 1e-3


def test_unrouted_expert_has_exactly_zero_gradient():
    """Work follows the routed rows in the backward pass too: an expert no
    token chose gets a gradient of exactly zero, not a small one."""
    D, F, E = 16, 8, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    h = jnp.abs(jax.random.normal(keys[0], (2, 9, D)))  # positive: a negative column never wins
    router = jnp.abs(jax.random.normal(keys[1], (D, E))).at[:, 7].set(-1.0)
    weights = [jax.random.normal(k, s) for k, s in zip(keys[2:], [(E, D, F), (E, D, F), (E, F, D)])]

    def loss(w_gate, w_up, w_down):
        y, routing = moe.expert_layer(h, router, w_gate, w_up, w_down, k=2, dtype=jnp.float32)
        assert routing.experts.shape == (18, 2)
        return jnp.sum(y ** 2)

    for g in jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*weights):
        g = np.asarray(g)
        assert not g[7].any() and g[:7].any()


# 3 and 8: through the cache, the trainers and the server ------------------ #


def trl_config(kind="ppo", rollout=None, dtype="float32", **train):
    from trlx_tpu.analysis import harness
    from trlx_tpu.data.configs import TRLConfig

    cfg = harness.tiny_config_dict(kind, mesh={"dp": -1, "fsdp": 1, "tp": 1})
    cfg["model"] = {"model_type": "olmoe", "model_arch": dict(ARCH, vocab_size=32)}
    cfg["train"].update(dtype=dtype, **train)
    if rollout:
        cfg["train"]["rollout"] = rollout
    if kind == "ppo":
        cfg["method"]["gen_kwargs"]["min_new_tokens"] = 1
    return TRLConfig.from_dict(cfg)


def prompts(n, q, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, 30, (n, q)).astype(np.int32)
    mask = np.ones((n, q), np.int32)
    for i in range(n):
        real = int(rng.integers(3, q + 1))
        mask[i, : q - real], ids[i, : q - real] = 0, 31
    return ids, mask


def recorded_vs_full_forward(trainer, q_ids, q_mask, r_ids, r_mask, logprobs):
    """The log-probabilities recorded while decoding through the cache
    against one full forward over [query; response]."""
    params = jax.device_get(trainer.state.params)
    ids = np.concatenate([q_ids, r_ids], 1)
    mask = np.concatenate([q_mask, r_mask], 1)
    logits = trainer.model.apply({"params": params}, ids, mask)["logits"]
    Q = q_ids.shape[1]
    full = jax.nn.log_softmax(logits[:, Q - 1 : -1].astype(jnp.float32))
    at = np.take_along_axis(np.asarray(full), np.asarray(r_ids)[..., None], -1)[..., 0]
    live = np.asarray(r_mask).astype(bool)
    assert live.any()
    return float(np.abs(at - np.asarray(logprobs))[live].max())


def test_fixed_sampler_decodes_through_the_fused_read():
    from trlx_tpu import telemetry
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    trainer = PPOTrainer(trl_config(rollout={"engine": "fixed"}))
    ids, mask = prompts(8, trainer.query_length)
    telemetry.get_metrics().clear()
    out = trainer.sample(jnp.asarray(ids), jnp.asarray(mask))
    counters = telemetry.get_metrics().snapshot()["counters"]
    fused = sum(v for k, v in counters.items() if k.startswith("attention/decode_path") and "fused" in k)
    assert fused >= ARCH["num_hidden_layers"]
    err = recorded_vs_full_forward(
        trainer, ids, mask, np.asarray(out.tokens), np.asarray(out.response_mask), out.logprobs)
    assert err <= 1e-4


def test_continuous_engine_decodes_through_the_paged_cache():
    from trlx_tpu import telemetry
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    rollout = {"engine": "continuous", "slots": 8, "admit_width": 8, "harvest_width": 8,
               "block_size": 4, "per_row_rng": True}
    trainer = PPOTrainer(trl_config(rollout=rollout))
    ids, mask = prompts(16, trainer.query_length, seed=3)
    telemetry.get_metrics().clear()
    trainer.reset_rollout_phase()
    engine = trainer.rollout_engine_obj
    engine.start_phase(trainer.rollout_params(), trainer.rollout_phase_key())
    engine.submit(ids, mask)
    rows = {}
    for group in engine.drive(16):
        arrs = {k: np.asarray(group[k]) for k in ("tokens", "response_mask", "logprobs")}
        for j, r in enumerate(group["rows"]):
            rows[r] = {k: v[j] for k, v in arrs.items()}
    assert set(rows) == set(range(16))
    stack = lambda k: np.stack([rows[r][k] for r in range(16)])
    err = recorded_vs_full_forward(
        trainer, ids, mask, stack("tokens"), stack("response_mask"), stack("logprobs"))
    assert err <= 1e-4
    # the step's routing statistics came home with the done flags
    snap = telemetry.get_metrics().snapshot()
    assert 1 <= snap["gauges"]["moe/experts_touched"] <= ARCH["num_experts"]
    assert 1 / ARCH["num_experts"] <= snap["gauges"]["moe/max_load"] <= 1
    assert snap["counters"]["moe/rows_routed"] > 0


@pytest.fixture
def logged(monkeypatch):
    from trlx_tpu.utils.logging import Logger

    seen = []
    monkeypatch.setattr(Logger, "log", lambda self, stats, step=None: seen.append(dict(stats)))
    return seen


def assert_trained(trainer, logged, steps):
    assert int(trainer.state.step) == steps
    leaves = jax.device_get(jax.tree_util.tree_leaves(trainer.state.params))
    assert all(bool(np.isfinite(np.asarray(l)).all()) for l in leaves)
    rows = [s for s in logged if "losses/moe_aux" in s]
    assert rows and all("moe/max_load" in s for s in rows)
    assert all(np.isfinite(np.asarray(s["losses/total_loss"])).all() for s in rows)
    # uniform routing gives aux = k; collapse gives E
    assert all(1.0 <= float(np.mean(s["losses/moe_aux"])) <= ARCH["num_experts"] for s in rows)


@pytest.mark.parametrize("engine", ["fixed", "continuous"])
def test_two_ppo_phases_through_train(engine, logged, tmp_path):
    os.environ["WANDB_DISABLED"] = "1"
    import trlx_tpu

    rollout = {"engine": engine}
    if engine == "continuous":
        rollout.update(slots=8, admit_width=8, harvest_width=8, block_size=4)
    # a checkpoint directory of its own: the default `ckpts/<step>` is
    # shared with every other worker's tests that end on the same step
    config = trl_config(rollout=rollout, total_steps=2, epochs=2, health={"enabled": True},
                        checkpoint_dir=str(tmp_path))
    rng = np.random.default_rng(0)
    trainer = trlx_tpu.train(
        reward_fn=lambda samples, queries, response_gt=None: [float(len(s)) for s in samples],
        prompts=[list(rng.integers(1, 30, size=5)) for _ in range(8)], config=config,
    )
    assert_trained(trainer, logged, 2)
    assert not getattr(trainer, "health_events", [])


def test_one_ilql_step_through_train(logged, tmp_path):
    os.environ["WANDB_DISABLED"] = "1"
    import trlx_tpu

    config = trl_config("ilql", total_steps=1, checkpoint_dir=str(tmp_path))
    rng = np.random.default_rng(0)
    samples = [(list(rng.integers(1, 30, size=6)), 1) for _ in range(8)]
    trainer = trlx_tpu.train(
        dataset=(samples, [float(r) for r in rng.random(8)]), config=config,
        eval_prompts=[[1]] * 8,
    )
    assert_trained(trainer, logged, 1)


def test_inference_server_answers_eight_requests():
    from trlx_tpu.inference.server import InferenceServer
    from trlx_tpu.models.heads import CausalLMWithValueHead
    from trlx_tpu.trainer.ppo_trainer import get_causal_arch

    config = trl_config(rollout={"slots": 8, "admit_width": 8, "harvest_width": 8, "block_size": 4})
    family, model_config, _ = get_causal_arch(config)
    assert family.name == "olmoe" and family.supports_ep
    model = CausalLMWithValueHead(model_config, backbone_cls=family.backbone_cls)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    server = InferenceServer(config, params=params, seed=0)
    rng = np.random.default_rng(2)
    rids = server.submit([list(rng.integers(1, 30, int(rng.integers(2, 8)))) for _ in range(8)])
    results = server.wait(rids)
    assert set(results) == set(rids)
    assert all(1 <= r["length"] == len(r["tokens"]) for r in results.values())
    assert without_timing(server.health_events) == []


# 4 ------------------------------------------------------------------------- #


def test_every_token_on_one_expert_is_not_dropped(f32):
    model, params = f32
    rigged = jax.tree_util.tree_map(lambda a: a, params)
    for i in range(ARCH["num_hidden_layers"]):
        r = jnp.zeros_like(params[f"h_{i}"]["mlp"]["router"])
        # expert 2 wins everywhere, expert 5 is everyone's second
        rigged[f"h_{i}"]["mlp"]["router"] = r.at[:, 2].set(4.0).at[:, 5].set(2.0)
    # h W_r has the sign of sum(h): make the choice independent of it
    out = jax.jit(lambda p: model.apply({"params": p}, IDS, MASK))(rigged)
    assert peak_err(out["logits"], reference_logits(rigged), REAL) <= 1e-4
    assert float(out["moe_stats"]["experts_touched"]) <= 4


@pytest.mark.parametrize("seed", range(5))
def test_group_sizes_sum_to_every_copy(seed):
    rng = np.random.default_rng(seed)
    N, k, E = int(rng.integers(1, 200)), int(rng.integers(1, 5)), 8
    # skewed: most copies go to two experts
    p = rng.dirichlet(np.full(E, 0.2))
    experts = np.stack([rng.choice(E, size=k, replace=False, p=p) for _ in range(N)]).astype(np.int32)
    first = int(rng.integers(0, E))
    order, inverse, sizes = moe.sort_by_expert(jnp.asarray(experts), E, first)
    assert int(sizes.sum()) == N * k
    assert sorted(np.asarray(order)) == list(range(N * k))
    np.testing.assert_array_equal(np.asarray(order)[np.asarray(inverse)], np.arange(N * k))
    flat = (experts.reshape(-1) - first) % E
    assert (np.diff(flat[np.asarray(order)]) >= 0).all()
    np.testing.assert_array_equal(np.asarray(sizes), np.bincount(flat, minlength=E))


# 5 ------------------------------------------------------------------------- #


def test_bf16_compute_stays_inside_the_benchmark_tolerance():
    from benchmark import checks

    tol = checks.tolerance_for("bfloat16", "bfloat16")
    # at the scale of an initialisation: with experts as large as the
    # residual stream a tiny top-2-of-8 router flips on bf16 near-ties
    model, params = build(dtype="bfloat16", expert_scale=3)
    got = logits_of(model, params)
    want = np.asarray(reference_logits(params))
    rms, mx = checks.error_stats(got, want, float(want[REAL].std()), REAL)
    assert rms <= tol["logits_rms_rel"] and mx <= tol["logits_max_rel"]
    assert rms > 1e-4  # and bf16 is not float32: the comparison sees it


def test_a_bf16_router_softmax_fails_the_float32_tolerance(f32, monkeypatch):
    model, params = f32

    def bf16_route(h, router_w, k, norm_topk=False):
        logits = (h.astype(jnp.bfloat16) @ router_w.astype(jnp.bfloat16))
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, k)
        return moe.Routing(*(a.astype(jnp.float32) for a in (logits, probs, weights)),
                           experts.astype(jnp.int32))

    monkeypatch.setattr(moe, "route", bf16_route)
    assert peak_err(logits_of(model, params), reference_logits(params), REAL) > 1e-4


# 6 ------------------------------------------------------------------------- #


@pytest.mark.parametrize("ep", [2, 4])
def test_experts_over_ep_match_one_rank(f32, ep):
    from trlx_tpu.models import gpt2_moe
    from trlx_tpu.parallel.mesh import make_mesh

    model, params = f32
    ids = np.tile(IDS, (4, 1))[:8]
    mask = np.tile(MASK, (4, 1))[:8]

    def loss(p):
        out, state = model.apply({"params": p}, ids, mask, mutable=["moe_losses"])
        aux = moe.moe_loss_summary(state["moe_losses"])["aux_loss"]
        return jnp.mean(out["logits"] ** 2) + 0.01 * aux, out["logits"]

    (l1, logits1), g1 = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    mesh = make_mesh({"dp": 8 // ep // 2, "fsdp": 2, "tp": 1, "ep": ep})
    gpt2_moe.set_ep_mesh(mesh)
    try:
        (l2, logits2), g2 = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
        # a decode step's row count need not divide the data shards
        one = logits_of(model, params, ids[:3, :1], None)
    finally:
        gpt2_moe.set_ep_mesh(None)
    np.testing.assert_allclose(np.asarray(logits2), np.asarray(logits1), atol=1e-4, rtol=1e-4)
    f1, _ = jax.flatten_util.ravel_pytree(g1)
    f2, _ = jax.flatten_util.ravel_pytree(g2)
    np.testing.assert_allclose(np.asarray(f2), np.asarray(f1), atol=1e-5, rtol=1e-3)
    np.testing.assert_allclose(
        np.asarray(one), np.asarray(logits_of(model, params, ids[:3, :1], None)), atol=1e-4, rtol=1e-4)


def test_partition_rules_put_experts_on_ep_and_attention_on_tp(f32):
    from jax.sharding import PartitionSpec as P

    from trlx_tpu.models.olmoe import OLMOE_PARTITION_RULES
    from trlx_tpu.parallel.mesh import make_mesh
    from trlx_tpu.parallel.partition import make_partition_specs

    _, params = f32
    mesh = make_mesh({"dp": 2, "fsdp": 1, "tp": 2, "ep": 2})
    specs = make_partition_specs(params, mesh, OLMOE_PARTITION_RULES, min_shard_size=1)
    blk = specs["h_0"]
    assert blk["mlp"]["w_gate"] == blk["mlp"]["w_up"] == blk["mlp"]["w_down"] == P("ep")
    assert blk["mlp"]["router"] == P()
    assert blk["attn"]["q_proj"]["kernel"] == P(None, "tp")
    assert blk["attn"]["o_proj"]["kernel"] == P("tp")


# 7 ------------------------------------------------------------------------- #


def test_transformers_checkpoint_loads_to_equal_logits(tmp_path):
    import torch
    from transformers import OlmoeConfig as HFConfig, OlmoeForCausalLM

    from trlx_tpu.models.conversion import load_olmoe_checkpoint

    torch.manual_seed(0)
    hf_config = HFConfig(**{**ARCH, "vocab_size": 211}, attention_dropout=0.0,
                         tie_word_embeddings=False, pad_token_id=0)
    hf = OlmoeForCausalLM(hf_config).eval()
    with torch.no_grad():  # norm scales away from their initial 1
        for name, p in hf.named_parameters():
            if "norm" in name:
                p.mul_(1 + 0.1 * torch.randn_like(p))
    hf.save_pretrained(str(tmp_path))
    config, params = load_olmoe_checkpoint(str(tmp_path))
    assert config.num_experts == 8 and config.num_experts_per_tok == 2
    config = OlmoeConfig(**{**config.__dict__, "dtype": "float32"})
    ids = np.random.default_rng(2).integers(0, 211, size=(2, 11))
    with torch.no_grad():
        want = hf(input_ids=torch.tensor(ids)).logits.numpy()
    ids = jnp.asarray(ids)
    assert peak_err(logits_of(OlmoeModel(config), params, ids, None), want) <= 1e-4
    # and the reference's equations are the ones transformers computes
    ref = reference_logits(params, dict(ARCH, vocab_size=211), ids, jnp.ones_like(ids))
    assert peak_err(ref, want) <= 1e-4


def test_pipeline_parallel_refuses_the_family():
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    config = trl_config()
    config.train.mesh = {"dp": -1, "fsdp": 1, "tp": 1, "pp": 2}
    with pytest.raises((NotImplementedError, ValueError), match="(?i)moe|olmoe|pp"):
        PPOTrainer(config)


def test_device_scopes_are_in_the_compiled_program(f32):
    """The four scopes a device trace is read by (docs/observability.md)
    are op metadata of the compiled program."""
    model, params = f32
    text = jax.jit(lambda p: model.apply({"params": p}, IDS, MASK)["logits"]).lower(params).compile().as_text()
    for scope in ("moe_router", "moe_dispatch", "moe_experts", "moe_combine"):
        assert f"/{scope}/" in text, scope
