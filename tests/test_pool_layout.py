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

The same rule's other case (PR 63): a pool of keys and values whose head is
several whole lane rows (qwen3-next's 256) is held with each head as those
rows, ``[slots, capacity, H * Dh // 128, 128]``, so that the admission's view
of the pool by blocks is a bitcast. The writes take the call's rows to that
shape, the gathered view comes back in the call's heads and the read of the
pool as stored takes a head's lane rows where they lie: every call returns
what it returns over the pool as the model allocates it.
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
    if model_type == "qwen3_next_heads_of_256":
        base = trl_config("qwen3_next").to_dict()
        base["model"]["model_arch"] = dict(base["model"]["model_arch"], head_dim=256)
        return TRLConfig.from_dict(base)
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
        (lambda: kc.kv_buffers(1, 4, 16, 16, 128, jnp.bfloat16)[0], None),  # a head of one lane row: pythia's, OLMoE's
        (lambda: kc.kv_buffers(1, 4, 16, 2, 192, jnp.bfloat16)[0], None),  # no whole number of lane rows
        (lambda: kc.kv_buffers(1, 4, 16, 2, 256, jnp.bfloat16, "int8")[0], None),  # a scale is a head's
        (lambda: kc.decode_kv_layout(kc.kv_buffers(1, 4, 16, 2, 256, jnp.bfloat16)[0]), None),  # the sampler's folded rows
    ],
    ids=["published_row", "paged", "a_groups_call", "row_of_whole_lanes", "keys_and_values", "one_kv_head",
         "keys_with_a_tail", "state", "heads_of_one_lane_row", "heads_of_a_lane_row_and_a_half", "int8_heads_of_256",
         "folded_rows"],
)
def test_the_rule_pads_a_latent_row_that_fills_no_lane_and_nothing_else(layer, held):
    layer = layer()
    out = kc.hold_pool(layer)
    if held is None:
        assert out is layer and kc.lane_rows(layer) == 1
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


HEADS = pytest.mark.parametrize(
    "H,Dh", [(16, 128), (2, 128), (2, 256), (1, 512)],
    ids=["pythia_16x128", "zaya_2x128", "qwen3next_2x256", "one_head_of_512"],
)
S_, CAP, BS = 4, 16, 4  # slots, positions a slot, positions a block


def kv_case(H, Dh, dtype=jnp.float32, **extra):
    """A paged layer of ``H`` KV heads of ``Dh`` as the model allocates it,
    filled, every slot's table rotated by another number of blocks, and the
    same layer as its holder keeps it."""
    keys = jax.random.split(jax.random.PRNGKey(H * Dh), 2)
    tables = kc.identity_block_tables(S_, CAP // BS)
    tables = jnp.stack([kc.rotate_block_table(tables[b], b) for b in range(S_)])
    layer = dict(
        k=jax.random.normal(keys[0], (S_, CAP, H, Dh), dtype), v=jax.random.normal(keys[1], (S_, CAP, H, Dh), dtype),
        block_tables=tables, **extra,
    )
    return layer, kc.hold_pool(layer)


def as_allocated(pool, H, Dh):
    return np.asarray(pool).reshape(pool.shape[:2] + (H, Dh))


@HEADS
def test_the_rule_holds_a_head_of_several_lane_rows_as_those_rows(H, Dh):
    """``[S, C, H, Dh]`` is held ``[S, C, H * Dh // 128, 128]`` where a head
    is more than one whole lane row, the same bytes in the same order, and
    as it is where a head is one lane row; the held pool is held as it is,
    and only the held shape makes the view by blocks a bitcast."""
    layer, held = kv_case(H, Dh, jnp.bfloat16)
    J = Dh // 128
    assert kc.lane_rows(layer) == J and kc.held_row_width(layer) == 128 and kc.block_view_is_bitcast(held)
    assert kc.block_view_is_bitcast(layer) == (J == 1)
    if J == 1:
        assert held is layer
        return
    assert held["k"].shape == held["v"].shape == (S_, CAP, H * J, 128) and held["k"].dtype == jnp.bfloat16
    assert kc.cache_kind(held) == kc.cache_kind(layer) and held["block_tables"] is layer["block_tables"]
    for name in ("k", "v"):
        np.testing.assert_array_equal(as_allocated(held[name], H, Dh), np.asarray(layer[name]))
        # row kv * J + j of a position is columns [128 j, 128 (j + 1)) of head kv
        kv, j = H - 1, 1
        np.testing.assert_array_equal(
            np.asarray(held[name])[:, :, kv * J + j], np.asarray(layer[name])[:, :, kv, 128 * j : 128 * (j + 1)]
        )
    assert kc.hold_pool(held) is held and kc.lane_rows(held) == 1
    with pytest.raises(ValueError, match="do not fill a pool"):
        kc._scatter_rows(held["k"], jnp.zeros((S_, 1), jnp.int32), jnp.zeros((S_, 1, H, Dh // 2)))


CALLS = {
    # name: (columns, cache_index, a group's call, the decode path, the write path)
    "a_whole_admission": (8, 0, True, "paged_rows", "blocks"),
    "a_chunk_from_a_traced_block": (4, "chunk 2", True, "paged_rows", "blocks"),
    "columns_that_are_no_whole_blocks": (3, 5, True, "paged_rows", "positions"),
    "one_row_a_slot_read_as_stored": (1, "a slot's own", False, "paged", "positions"),
    "a_verify_steps_columns": (3, "a column's own", False, "generic", "positions"),
}


@HEADS
@pytest.mark.parametrize("call", list(CALLS))
def test_a_pool_held_in_lane_rows_is_written_and_read_as_the_pool_the_model_allocates(H, Dh, call):
    """Every cached call of the engine's programs (an admission written by
    block from a Python 0 and from a traced block, one written by position,
    the decode step's read of the pool as stored, the verify step's
    per-column targets read through the logical view), each slot's table
    rotated: over the pool as held the call returns the output it returns
    over the pool as allocated (bit for bit where the view is gathered; to
    float32 round-off where the pool is read as stored, a head's channels
    summed in its lane rows and the parts added) and leaves the same bytes
    in the pools. The paths are the ones named."""
    T, index, rows, path, write = CALLS[call]
    G = 4
    layer, held = kv_case(H, Dh)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    A = 3 if rows else S_
    q = jax.random.normal(keys[0], (A, T, H * G, Dh))
    k_new, v_new = (jax.random.normal(key, (A, T, H, Dh)) for key in keys[1:])
    first = {"chunk 2": 8, "a slot's own": jnp.asarray([9, 0, 15, 4], jnp.int32),
             "a column's own": jnp.asarray([[4, 5, CAP], [0, 1, 2], [CAP, CAP, CAP], [13, 14, 15]], jnp.int32)}.get(index, index)
    base = first[:, 0] if jnp.ndim(first) == 2 else first
    bias = jnp.where(
        jnp.arange(CAP)[None, None, :] <= jnp.reshape(base, (-1, 1, 1)) + jnp.arange(T)[None, :, None], 0.0, -1e9
    )[:, None]
    bias = jnp.broadcast_to(bias, (A, 1, T, CAP))

    def run(cache):
        if rows:
            cache = dict(cache, block_tables=cache["block_tables"][jnp.asarray([2, 0, 3])],
                         slot_ids=jnp.asarray([2, 0, S_], jnp.int32))  # the last row a dummy: it writes nowhere
        if index == "chunk 2":
            step = lambda c: decode_attention(q, k_new, v_new, kc.starting_at_block((cache,), c)[0], c * BS, bias)
            return jax.jit(step)(jnp.asarray(2, jnp.int32))
        return decode_attention(q, k_new, v_new, cache, first, bias)

    with telemetry.scoped_metrics() as reg:
        want, kv = run(layer)
        got, held_kv = run(held)
        counters = reg.snapshot()["counters"]
    assert counters["attention/decode_path{path=%s}" % path] == 2
    assert counters["kv_cache/write_path{path=%s}" % write] == 2
    assert held_kv["k"].shape == held["k"].shape and set(held_kv) == set(kv)
    for name in ("k", "v"):
        np.testing.assert_array_equal(as_allocated(held_kv[name], H, Dh), np.asarray(kv[name]))
        assert not np.array_equal(np.asarray(kv[name]), np.asarray(layer[name]))  # the call wrote something
    if path == "paged" and Dh > 128:
        assert not np.array_equal(np.asarray(got), np.asarray(want))  # another order of the same sums
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@HEADS
def test_a_shared_prefix_is_published_and_read_back_through_a_pool_held_in_lane_rows(H, Dh):
    """The shared-prefix pool beside a held layer is sized from the held
    rows (``inference/engine.py::_make_state``), so a donor's columns are
    published in lane rows and a reader's overlay is merged with its view:
    a donor that publishes its first block and a reader mapped to it, in
    one call, see what they see over the pool as allocated."""
    layer, held = kv_case(H, Dh)
    n_blocks = CAP // BS
    publish = kc.empty_share_tables(S_, n_blocks).at[0, 0].set(1)
    shared = kc.empty_share_tables(S_, n_blocks).at[1, 0].set(1)

    def with_pool(layer):
        pool = kc.init_shared_pool(2, BS, layer["k"].shape[2], layer["k"].shape[3], layer["k"].dtype)
        return dict(layer, **pool, shared_tables=shared, publish_tables=publish)

    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    k_new, v_new = (jax.random.normal(key, (S_, 8, H, Dh)) for key in keys)
    want = kc.paged_write_read(with_pool(layer), k_new, v_new, 0, jnp.float32)
    got = kc.paged_write_read(with_pool(held), k_new, v_new, 0, jnp.float32)
    for a, b in zip(got[:2], want[:2]):
        assert a.shape == (S_, CAP, H, Dh)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(got[0])[1, :BS], np.asarray(k_new)[0, :BS])  # the reader sees the donor's block
    for name in ("k", "v", "shared_k", "shared_v"):
        assert got[2][name].shape[-1] == min(Dh, 128)
        np.testing.assert_array_equal(
            np.asarray(got[2][name]).reshape(want[2][name].shape), np.asarray(want[2][name])
        )


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


def lowered(eng, params):
    """The text of the engine's three model programs, lowered on shapes."""
    state = jax.eval_shape(eng._make_state)
    abstract = jax.eval_shape(lambda: params)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    admit = (abstract, state, i32(8), i32(8, Q), i32(8, Q), i32(8), i32(8), jax.ShapeDtypeStruct((2,), jnp.uint32))
    return [
        eng.decode_step_jit.lower(abstract, state).as_text(),
        eng.prefill_jit.lower(*admit).as_text(),
        eng.prefill_chunk_jit.lower(*admit, i32()).as_text(),
    ]


def lane_rows_off(patch):
    """The rule as it was before a head was split: a latent row still padded."""
    patch.setattr(kc, "lane_rows", lambda layer: 1)


def test_an_engine_holds_heads_of_256_in_lane_rows_through_every_program(monkeypatch):
    """qwen3-next at toy widths with its published head of 256 (2 KV heads
    under 4): the state's one pool of keys and values is ``[8, capacity, 4,
    128]`` where the model allocates ``[8, capacity, 2, 256]``, every
    program hands it back so and writes it, ``cache/kv_gb`` counts the
    same bytes, and ``cache/block_write_bitcast_share`` reads 1.0; with the
    rule's new half off the pool is the model's and the gauge reads 0.0."""
    with telemetry.scoped_metrics() as reg:
        eng, params = build_engine("qwen3_next_heads_of_256", True)
        state = eng.init_state()
        gauges = reg.snapshot()["gauges"]
    assert eng._pads_a_pool and gauges["cache/block_write_bitcast_share"] == 1.0
    assert "cache/latent_pinned_share" not in gauges
    made = eng._init_cache_fn(8, eng.capacity)
    assert [c["k"].shape for c in made if "k" in c] == [(8, eng.capacity, 2, 256)]
    assert gauges["cache/kv_gb"] == pytest.approx(2 * 8 * eng.capacity * 2 * 256 * 2 / 1e9)

    def held(state):
        (layer,) = [c for c in state.cache if "k" in c]
        assert layer["k"].shape == layer["v"].shape == (8, eng.capacity, 4, 128)
        return np.asarray(layer["k"], np.float32), np.asarray(layer["v"], np.float32)

    assert not any(p.any() for p in held(state))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, 90, (8, Q)), jnp.int32)
    mask = jnp.ones((8, Q), jnp.int32)
    slots, zeros, key = jnp.arange(8, dtype=jnp.int32), jnp.zeros((8,), jnp.int32), jax.random.PRNGKey(5)
    state = eng.prefill_jit(params, state, slots, ids, mask, slots, zeros, key)
    written = held(state)
    assert all(p[:, :Q].any(axis=-1).all() and not p[:, Q:].any() for p in written)  # every lane row of every column
    state = eng.prefill_chunk_jit(params, state, slots, ids, mask, slots, zeros, key, jnp.asarray(1, jnp.int32))
    chunked = held(state)
    for was, now in zip(written, chunked):  # chunk 1 (columns 4-7, from the state layers' rows as the prompt left them)
        np.testing.assert_array_equal(now[:, :4], was[:, :4])
        np.testing.assert_array_equal(now[:, 8:], was[:, 8:])
        assert now[:, 4:8].any(axis=-1).all()
    state = eng.decode_step_jit(params, state)[0]
    for was, now in zip(chunked, held(state)):  # one row a slot more, at position Q
        assert now[:, Q].any(axis=-1).all() and not was[:, Q].any()
        np.testing.assert_array_equal(now[:, :Q], was[:, :Q])
    held(eng.release_jit(eng.refill_jit(state, slots)[0], slots))

    with monkeypatch.context() as patch, telemetry.scoped_metrics() as reg:
        lane_rows_off(patch)
        plain, _ = build_engine("qwen3_next_heads_of_256", True)
        shapes = [c["k"].shape for c in jax.eval_shape(plain._make_state).cache if "k" in c]
        plain.init_state()
        assert reg.snapshot()["gauges"]["cache/block_write_bitcast_share"] == 0.0
    assert not plain._pads_a_pool and shapes == [(8, eng.capacity, 2, 256)]


def test_a_server_on_heads_held_in_lane_rows_draws_what_it_draws_on_the_models_own(monkeypatch):
    """Where the bytes lie, not what they are: the same requests through a
    server whose engine holds heads of 256 in lane rows and through one that
    holds them as the model allocates stream the same tokens, with
    log-probabilities equal to what bfloat16 activations leave of another
    order of a head's sums in the decode step's read."""
    config = config_of("qwen3_next_heads_of_256")
    params = perturbed_params(config)
    with telemetry.scoped_metrics() as reg:
        server, tokens, logprobs = serve(config, params, monkeypatch)
        assert reg.snapshot()["gauges"]["cache/block_write_bitcast_share"] == 1.0
    assert {c["k"].shape[2:] for c in server.engine._state.cache if "k" in c} == {(4, 128)}
    with monkeypatch.context() as patch:
        lane_rows_off(patch)
        plain, p_tokens, p_logprobs = serve(config, params, monkeypatch)
    assert {c["k"].shape[2:] for c in plain.engine._state.cache if "k" in c} == {(2, 256)}
    assert tokens == p_tokens and any(len(t) > 1 for t in tokens)
    for got, want in zip(logprobs, p_logprobs):
        np.testing.assert_allclose(got, want, rtol=0, atol=0.05)


@pytest.mark.parametrize("model_type", ["deepseek_v3", "ling"])
def test_a_latent_family_lowers_to_the_programs_it_had_before_a_head_was_split(model_type, monkeypatch):
    """One engine each of the two families whose pools are latent rows
    (deepseek-v3's cell, ling's) at toy sizes: a latent row is padded as it
    was and no head is split, so ``decode_step``, ``prefill`` and
    ``prefill_chunk`` lower to the text they lower to with the split of a
    head taken out of the rule, character for character; no layer keeps keys
    and values, so the block-view gauge is not emitted. (The four families
    of keys and values, pythia's, OLMoE's, granite's and zaya's, are held to
    the stronger test below: their programs are the text they are with the
    whole rule taken out.)"""
    def lowered_and_gauges():
        with telemetry.scoped_metrics() as reg:
            eng, params = build_engine(model_type, True)
            eng.init_state()
            gauges = reg.snapshot()["gauges"]
        return lowered(eng, params), gauges

    texts, gauges = lowered_and_gauges()
    assert "cache/block_write_bitcast_share" not in gauges and gauges["cache/latent_pinned_share"] == 1.0
    lane_rows_off(monkeypatch)
    assert lowered_and_gauges()[0] == texts


@pytest.mark.parametrize("model_type", ["gpt_neox", "olmoe", "zaya", "granitemoehybrid"])
def test_every_other_kind_of_cache_is_held_as_allocated_and_lowers_to_the_programs_it_had(model_type, monkeypatch):
    """Keys and values (pythia, OLMoE), keys with a tail (zaya), state
    layers beside keys (granite): the state's cache is what the model's
    ``init_cache`` makes, array for array, no gauge of a pinned share
    exists, and ``decode_step``, ``prefill`` and ``prefill_chunk`` lower to
    the text they lower to with the rule taken out."""
    with telemetry.scoped_metrics() as reg:
        eng, params = build_engine(model_type, True)
        state = eng.init_state()
        gauges = reg.snapshot()["gauges"]
    # heads of one lane row or less: the view by blocks is a bitcast as they are allocated
    assert "cache/latent_pinned_share" not in gauges and gauges["cache/block_write_bitcast_share"] == 1.0
    assert not eng._pads_a_pool
    for held, made in zip(state.cache, eng._init_cache_fn(8, eng.capacity)):
        assert kc.hold_pool(made) is made
        assert {k: (v.shape, v.dtype) for k, v in held.items() if k != "block_tables"} == {
            k: (v.shape, v.dtype) for k, v in made.items()
        }
    texts = lowered(eng, params)
    rule_off(monkeypatch)
    assert lowered(*build_engine(model_type, True)) == texts
