"""A latent pool is held in rows of whole lanes, so that it lies as its
programs compute on it (`ops/kv_cache.py::held_row_width`, `hold_pool`;
`inference/engine.py::_make_state`).

The runtime lays a bf16 pool ``[B, C, 1, 576]`` position-minor and every
program that writes rows copied it in and out; ``[B, C, 1, 640]`` it lays
row-minor itself (tests/test_tpu_compile.py; PERF.md section 6, PR 57). So
the engine pads a latent row to whole lanes where it builds its state, a
write pads the rows it is handed, and the reads take a row's own columns.
Everything but the copies is read here: the padding changes no result, stays
zero through every program, and no other kind of cache is touched.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from test_deepseek_v3 import ARCH, EOS, Q, R, WIDTH, latent_case
from test_served_params import DP_MESH, perturbed_params, serve, trl_config

from trlx_tpu import telemetry
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.inference.engine import ContinuousBatchingEngine
from trlx_tpu.models.heads import CausalLMWithValueHead
from trlx_tpu.ops import kv_cache as kc
from trlx_tpu.ops.attention import decode_attention
from trlx_tpu.ops.sampling import GenerationConfig
from trlx_tpu.parallel import make_mesh, make_partition_specs
from trlx_tpu.trainer.ppo_trainer import get_causal_arch

DEEPSEEK = {k: v for k, v in ARCH.items() if k not in ("dtype", "param_dtype")}  # the config's: bfloat16 over float32


def config_of(model_type):
    if model_type != "deepseek_v3":
        return trl_config(model_type)
    base = trl_config("gpt2").to_dict()
    base["model"] = {"model_type": "deepseek_v3", "model_arch": DEEPSEEK}
    base["method"]["gen_kwargs"].update(eos_token_id=EOS, pad_token_id=EOS)
    return TRLConfig.from_dict(base)


def build_engine(model_type, on_mesh):
    """An engine of the family at toy widths, as a server builds it (a dp
    mesh over the 8 devices, the family's parameter shardings) or with no
    mesh at all, and seeded parameters placed for it."""
    config = config_of(model_type)
    family, model_config, _ = get_causal_arch(config)
    model = CausalLMWithValueHead(model_config, backbone_cls=family.backbone_cls)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    placed = {}
    if on_mesh:
        mesh = make_mesh(DP_MESH)
        specs = make_partition_specs(params, mesh, family.partition_rules)
        shardings = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P))
        params = jax.device_put(params, shardings)
        placed = dict(mesh=mesh, param_shardings=shardings)

    def apply_fn(p, input_ids, attention_mask=None, position_ids=None, cache=None, cache_index=None, last_only=False):
        return model.apply({"params": p}, input_ids, attention_mask=attention_mask, position_ids=position_ids,
                           cache=cache, cache_index=cache_index, last_only=last_only)

    gen = GenerationConfig(max_new_tokens=R, min_new_tokens=1, eos_token_id=EOS, pad_token_id=EOS, do_sample=True)
    return ContinuousBatchingEngine(
        apply_fn=apply_fn, init_cache_fn=functools.partial(family.init_cache, model_config), gen_config=gen,
        query_length=Q, vocab_size=model_config.vocab_size, num_slots=8, admit_width=8, harvest_width=8,
        block_size=4, prefill_chunk=4, prefill_chunks_per_pump=1, **placed,
    ), params


def latent(width, **extra):
    return dict(kc.latent_buffers(1, 4, 16, width, jnp.bfloat16)[0], **extra)


@pytest.mark.parametrize(
    "layer,held",
    [
        (lambda: latent(576), 640),
        (lambda: latent(24, block_tables=jnp.zeros((4, 4), jnp.int32)), 128),
        (lambda: latent(24, block_tables=jnp.zeros((2, 4), jnp.int32), slot_ids=jnp.zeros((2,), jnp.int32)), 128),
        (lambda: latent(640), None),  # whole lanes already: the runtime lays it row-minor itself
        (lambda: kc.kv_buffers(1, 4, 16, 2, 64, jnp.bfloat16)[0], None),
        (lambda: kc.kv_buffers(1, 4, 16, 1, 576, jnp.bfloat16)[0], None),  # one head and a value pool: keys, not a latent
        (lambda: dict(kc.kv_buffers(1, 4, 16, 2, 16, jnp.bfloat16)[0], **kc.tail_buffers(4, {"z": (2, 16)})), None),
        (lambda: kc.state_buffers(4, 2, 4, 8, 3, 40), None),
    ],
    ids=["published_row", "paged", "a_groups_call", "row_of_whole_lanes", "keys_and_values", "one_kv_head",
         "keys_with_a_tail", "state"],
)
def test_the_rule_pads_a_latent_row_that_fills_no_lane_and_nothing_else(layer, held):
    layer = layer()
    out = kc.hold_pool(layer)
    if held is None:
        assert out is layer
        assert kc.held_row_width(layer) == (layer["k"].shape[-1] if "k" in layer else 0)
        return
    assert kc.held_row_width(layer) == held and kc.held_row_width(out) == held
    assert set(out) == set(layer) and out["k"].shape == layer["k"].shape[:-1] + (held,)
    assert out["k"].dtype == layer["k"].dtype and kc.cache_kind(out) == kc.cache_kind(layer)
    assert all(out[k] is layer[k] for k in layer if k != "k")
    assert kc.hold_pool(out) is out


@pytest.mark.parametrize("at", ["one_row_a_slot", "a_groups_columns"])
def test_a_wider_pool_is_written_with_zeros_behind_a_row_and_read_as_the_narrow_one(at):
    """The same rows through a latent pool as the model allocates it and
    through the pool its holder keeps (rows padded to whole lanes): a write
    leaves zeros behind every row it wrote, and the absorbed read of the
    pool as stored (a decode step) and the published read of a group's
    gathered view (an admission) return bit for bit what they return over
    the narrow pool."""
    cache, lat, q, row = latent_case()
    wide = kc.hold_pool(cache)
    assert wide["k"].shape == (3, 24, 1, 128) and not np.asarray(wide["k"])[..., WIDTH:].any()
    np.testing.assert_array_equal(np.asarray(wide["k"])[..., :WIDTH], np.asarray(cache["k"]))
    if at == "one_row_a_slot":
        index = jnp.asarray([20, 7, 13], jnp.int32)
        bias = jnp.where(jnp.arange(24)[None, :] <= index[:, None], 0.0, -1e9)[:, None, None, :]
        call = lambda c: decode_attention(q, row, None, c, index, bias, scale=0.2, latent=lat)
    else:
        q = jax.random.normal(jax.random.PRNGKey(9), (3, 8, 4, 16))
        row = jax.random.normal(jax.random.PRNGKey(8), (3, 8, 1, WIDTH))
        bias = jnp.where(jnp.arange(24)[None, :] <= 8 + jnp.arange(8)[:, None], 0.0, -1e9)[None, None]
        call = lambda c: decode_attention(
            q, row, None, dict(c, slot_ids=jnp.arange(3, dtype=jnp.int32)), 8, bias, scale=0.2, latent=lat
        )
    want, narrow_kv = call(cache)
    got, wide_kv = call(wide)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert wide_kv["k"].shape == (3, 24, 1, 128) and not np.asarray(wide_kv["k"])[..., WIDTH:].any()
    np.testing.assert_array_equal(np.asarray(wide_kv["k"])[..., :WIDTH], np.asarray(narrow_kv["k"]))
    assert not np.array_equal(np.asarray(narrow_kv["k"]), np.asarray(cache["k"]))  # the call wrote something


def pools_of(state):
    return [np.asarray(layer["k"]) for layer in state.cache]


@pytest.mark.parametrize("on_mesh", [True, False], ids=["dp_mesh", "no_mesh"])
def test_a_latent_engine_holds_its_pools_in_rows_of_whole_lanes_through_every_program(on_mesh):
    """``init_state``, a whole admission, a chunk, a decode step, a
    ``refill`` and a ``release``: the state's pools are 128 wide where the
    model's rows are 24, every program hands them back so, what lies behind
    a row stays zero, and the gauge reads 1.0. The model is still asked for
    rows of 24 and ``cache/latent_gb`` counts those."""
    with telemetry.scoped_metrics() as reg:
        eng, params = build_engine("deepseek_v3", on_mesh)
        abstract = jax.eval_shape(eng._make_state)
        state = eng.init_state()
        gauges = reg.snapshot()["gauges"]
    L = len(state.cache)
    assert eng._pads_a_pool and gauges["cache/latent_pinned_share"] == 1.0
    assert gauges["cache/latent_gb"] == pytest.approx(L * 8 * eng.capacity * WIDTH * 2 / 1e9)
    assert [layer["k"].shape for layer in abstract.cache] == [(8, eng.capacity, 1, 128)] * L
    assert [layer["k"].shape for layer in eng._init_cache_fn(8, eng.capacity)] == [(8, eng.capacity, 1, WIDTH)] * L

    def held(state):
        pools = pools_of(state)
        assert [p.shape for p in pools] == [(8, eng.capacity, 1, 128)] * L
        assert not any(p[..., WIDTH:].any() for p in pools)
        return pools

    assert not any(p.any() for p in held(state))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, 90, (8, Q)), jnp.int32)
    mask = jnp.ones((8, Q), jnp.int32)
    slots, zeros, key = jnp.arange(8, dtype=jnp.int32), jnp.zeros((8,), jnp.int32), jax.random.PRNGKey(5)
    state = eng.prefill_jit(params, state, slots, ids, mask, slots, zeros, key)
    written = held(state)
    assert all(p[..., :WIDTH].any() for p in written)
    state = eng.prefill_chunk_jit(params, state, slots, ids, mask, slots, zeros, key, jnp.asarray(1, jnp.int32))
    for was, now in zip(written, held(state)):  # chunk 1 of the same prompt under the same table: the same rows
        np.testing.assert_array_equal(now, was)
    state = eng.decode_step_jit(params, state)[0]
    for was, now in zip(written, held(state)):  # one row a slot more, at position Q
        assert now[:, Q, 0, :WIDTH].any() and not was[:, Q].any()
        np.testing.assert_array_equal(now[:, :Q], was[:, :Q])
    state, _ = eng.refill_jit(state, slots)
    held(state)
    state = eng.release_jit(state, slots)
    held(state)


def rule_off(patch):
    patch.setattr("trlx_tpu.inference.engine.hold_pool", lambda layer: layer)


def test_a_server_on_padded_pools_draws_what_it_draws_on_the_models_own(monkeypatch):
    """The padding is where bytes lie, not what they are: the same requests
    through a server whose engine holds its latent pools by the rule, and
    through one built with the rule switched off, stream the same tokens
    with the same log-probabilities; the gauge says which is which."""
    config = config_of("deepseek_v3")
    params = perturbed_params(config)
    with telemetry.scoped_metrics() as reg:
        server, tokens, logprobs = serve(config, params, monkeypatch)
        share = reg.snapshot()["gauges"]["cache/latent_pinned_share"]
    assert share == 1.0 and {layer["k"].shape[-1] for layer in server.engine._state.cache} == {128}
    with monkeypatch.context() as patch, telemetry.scoped_metrics() as reg:
        rule_off(patch)
        plain, p_tokens, p_logprobs = serve(config, params, monkeypatch)
        share = reg.snapshot()["gauges"]["cache/latent_pinned_share"]
    assert share == 0.0 and {layer["k"].shape[-1] for layer in plain.engine._state.cache} == {WIDTH}
    assert tokens == p_tokens and any(len(t) > 1 for t in tokens)
    for got, want in zip(logprobs, p_logprobs):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("model_type", ["gpt_neox", "olmoe", "zaya", "granitemoehybrid"])
def test_every_other_kind_of_cache_is_held_as_allocated_and_lowers_to_the_programs_it_had(model_type, monkeypatch):
    """Keys and values (pythia, OLMoE), keys with a tail (zaya), state
    layers beside keys (granite): the state's cache is what the model's
    ``init_cache`` makes, array for array, no gauge of a pinned share
    exists, and ``decode_step``, ``prefill`` and ``prefill_chunk`` lower to
    the text they lower to with the rule taken out."""
    def lowered(eng, params):
        state = jax.eval_shape(eng._make_state)
        abstract = jax.eval_shape(lambda: params)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        admit = (abstract, state, i32(8), i32(8, Q), i32(8, Q), i32(8), i32(8), jax.ShapeDtypeStruct((2,), jnp.uint32))
        return [
            eng.decode_step_jit.lower(abstract, state).as_text(),
            eng.prefill_jit.lower(*admit).as_text(),
            eng.prefill_chunk_jit.lower(*admit, i32()).as_text(),
        ]

    with telemetry.scoped_metrics() as reg:
        eng, params = build_engine(model_type, True)
        state = eng.init_state()
        assert "cache/latent_pinned_share" not in reg.snapshot()["gauges"]
    assert not eng._pads_a_pool
    for held, made in zip(state.cache, eng._init_cache_fn(8, eng.capacity)):
        assert kc.hold_pool(made) is made
        assert {k: (v.shape, v.dtype) for k, v in held.items() if k != "block_tables"} == {
            k: (v.shape, v.dtype) for k, v in made.items()
        }
    texts = lowered(eng, params)
    rule_off(monkeypatch)
    assert lowered(*build_engine(model_type, True)) == texts
