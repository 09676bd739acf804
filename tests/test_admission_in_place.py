"""An admission forward addresses the paged pool by (slot, block table): it
is handed the pool whole with its group's tables and slot ids, scatters the
call's columns where they lie and gathers its group's view alone
(``ops/kv_cache.py::paged_write_read``, ``cache_kind(...).rows``; the
engine's ``group_cache`` / ``land_group_cache``).

Pinned here, over floating and int8 pools, with and without a shared
prefix, equal and grouped KV heads, rotated tables, a dummy row in the
group, for the whole forward and every chunk of a chunked one:

- the call computes what the slice-call-merge it replaced computed, bit
  for bit (attention output, every pool, the shared pool);
- the admitted slots' pools hold at each physical position the K/V of its
  logical position; every other slot's rows, tables and state are what they
  were; a dummy row (``slot_ids == num_slots``) writes nothing anywhere;
- the lowered programs hold no slice of the group, no merge back.

(The state kind's rows, which keep their take and set, are held in
``tests/test_granite_hybrid.py``.)
"""

import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.ops import kv_cache as kc
from trlx_tpu.ops.attention import decode_attention, padding_bias


# --------------------- the call, against slice-call-merge ------------------ #

N_SLOTS, CAP, DH, BS = 5, 24, 8, 4
QLEN = 16  # prompt columns; the rest of the capacity is the decode region
N_BLOCKS = CAP // BS
POOL_BLOCKS = 3


def _pool(kv, shared, h_kv, seed=0):
    """One layer's pool with every slot's region already holding values
    (so a write that strays is seen), rotated tables a slot."""
    rng = np.random.default_rng(seed)
    layer = kc.init_paged_cache(1, N_SLOTS, CAP, h_kv, DH, jnp.float32, kv, block_size=BS)[0]

    def fill(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        return jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype)

    layer = {k: fill(v) if k != "block_tables" else v for k, v in layer.items()}
    layer["block_tables"] = jnp.stack(
        [kc.rotate_block_table(layer["block_tables"][b], b) for b in range(N_SLOTS)]
    )
    if shared:
        pool = kc.init_shared_pool(POOL_BLOCKS, BS, h_kv, DH, jnp.float32, kv)
        layer.update({k: fill(v) for k, v in pool.items()})
        layer["shared_tables"] = kc.empty_share_tables(N_SLOTS, N_BLOCKS)
        layer["publish_tables"] = kc.empty_share_tables(N_SLOTS, N_BLOCKS)
    return layer


def _group(shared):
    """Slots 3 and 0 and a dummy, fresh tables rotated by 2, 5 and 1; with
    sharing, row 0 reads its first block from pool block 1 and row 1
    publishes its first block to pool block 2."""
    slot_ids = jnp.asarray([3, 0, N_SLOTS], jnp.int32)
    turns = jnp.asarray([2, 5, 1], jnp.int32)
    tables = (jnp.arange(N_BLOCKS, dtype=jnp.int32)[None, :] + turns[:, None]) % N_BLOCKS
    maps = {}
    if shared:
        sh = np.full((3, N_BLOCKS), -1, np.int32)
        pub = np.full((3, N_BLOCKS), -1, np.int32)
        sh[0, 0], pub[1, 0] = 1, 2
        maps = {"shared_tables": jnp.asarray(sh), "publish_tables": jnp.asarray(pub)}
    return slot_ids, tables, maps


def _slice_call_merge(layer, slot_ids, tables, maps, call):
    """What an admission program did before PR 42: take the group's rows of
    everything kept a slot, call on the slice, set the slice back."""
    by_slot = [k for k in layer if k not in kc.SHARED_POOL_KEYS]
    sl = {k: jnp.take(layer[k], slot_ids, axis=0) for k in by_slot}
    sl.update({k: layer[k] for k in layer if k in kc.SHARED_POOL_KEYS})
    sl.update(maps, block_tables=tables)
    out, new = call(sl)
    merged = {
        k: new[k] if k in kc.SHARED_POOL_KEYS
        else layer[k].at[slot_ids].set(new[k].astype(layer[k].dtype), mode="drop")
        for k in layer
    }
    return out, merged


def _land(layer, slot_ids, new):
    """The engine's ``land_group_cache`` for one paged layer."""
    by_slot = ("block_tables",) + kc.SHARE_TABLE_KEYS
    return {
        k: layer[k].at[slot_ids].set(new[k], mode="drop") if k in by_slot else new[k]
        for k in layer
    }


# the whole forward (view = capacity) and every chunk of a chunked one
# (view = the prompt columns), as (first column, columns, view width)
CALLS = {"whole": (0, QLEN, CAP), **{f"chunk{c}": (c * 4, 4, QLEN) for c in range(QLEN // 4)}}


@pytest.mark.parametrize("call", sorted(CALLS))
@pytest.mark.parametrize("heads", ["equal", "grouped"])
@pytest.mark.parametrize("shared", [False, True], ids=["private", "shared_prefix"])
@pytest.mark.parametrize("kv", ["bfloat16", "int8"])
def test_a_groups_call_is_the_slice_call_merge_it_replaced(kv, shared, heads, call):
    h_q, h_kv = (4, 4) if heads == "equal" else (4, 2)
    first, T, view = CALLS[call]
    layer = _pool(kv, shared, h_kv)
    slot_ids, tables, maps = _group(shared)
    A = slot_ids.shape[0]
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((A, T, h_q, DH)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((A, T, h_kv, DH)), jnp.float32) for _ in range(2))
    mask = np.zeros((A, view), np.int32)
    mask[:, 3:QLEN] = 1  # left padding of three columns
    bias = padding_bias(jnp.asarray(mask))

    def attend(cache):
        return decode_attention(q, k, v, cache, first, bias, causal=True)

    want_out, want = _slice_call_merge(layer, slot_ids, tables, maps, attend)
    rows = dict(layer, **maps, block_tables=tables, slot_ids=slot_ids)
    assert kc.cache_kind(rows) == kc.CacheKind(kc.PAGED, kv == "int8", shared, True)
    out, new = attend(rows)
    # the written cache is the same kind, key for key and shape for shape
    assert kc.cache_kind(new) == kc.cache_kind(rows)
    assert jax.tree_util.tree_map(jnp.shape, new) == jax.tree_util.tree_map(jnp.shape, rows)
    got = _land(layer, slot_ids, new)

    real = np.asarray(slot_ids) < N_SLOTS
    np.testing.assert_array_equal(np.asarray(out)[real], np.asarray(want_out)[real])
    for key in layer:
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want[key]), err_msg=key)
    # every other slot's rows as they were; the dummy's write went nowhere
    others = [b for b in range(N_SLOTS) if b not in np.asarray(slot_ids)]
    for key in layer:
        if key not in kc.SHARED_POOL_KEYS:
            np.testing.assert_array_equal(
                np.asarray(got[key])[others], np.asarray(layer[key])[others], err_msg=key
            )
    # the admitted slots hold at each physical position its logical position's K
    phys = np.asarray(kc.physical_positions(tables, np.broadcast_to(first + np.arange(T), (A, T)), CAP))
    stored = kc.quantize_kv(k) if kv == "int8" else (k,)
    for i in np.flatnonzero(real):
        for t in range(T):
            privately = not (shared and maps["shared_tables"][i, (first + t) // BS] >= 0)
            held = np.asarray(got["k"])[int(slot_ids[i]), phys[i, t]]
            if privately:
                np.testing.assert_array_equal(held, np.asarray(stored[0])[i, t])
            else:  # a shared column's private write drops: the pool serves it
                np.testing.assert_array_equal(held, np.asarray(layer["k"])[int(slot_ids[i]), phys[i, t]])


def test_a_groups_call_never_reads_the_pool_as_stored():
    """One position a row into a floating pool at full width is the decode
    step's read when the call spans every slot; a group's call shows the
    same shapes but for its rows, and must gather its view."""
    layer = _pool("bfloat16", False, 2)
    slot_ids, tables, _ = _group(False)
    rows = dict(layer, block_tables=tables, slot_ids=slot_ids)
    k = jnp.ones((3, 1, 2, DH), jnp.float32)
    at = jnp.zeros((3,), jnp.int32)
    assert kc.reads_as_stored(layer, jnp.ones((N_SLOTS, 1, 2, DH)), jnp.zeros((N_SLOTS,), jnp.int32))
    assert not kc.reads_as_stored(rows, k, at)
    with pytest.raises(ValueError, match="as_stored"):
        kc.paged_write_read(rows, k, k, at, jnp.float32, as_stored=True)


# ------------------------------ the engine -------------------------------- #

Q, R, VOCAB, EOS, W = 16, 8, 64, 63, 4


@functools.lru_cache(maxsize=None)
def _model(kv):
    from trlx_tpu.models.gpt2 import GPT2Config
    from trlx_tpu.models.heads import CausalLMWithValueHead

    cfg = GPT2Config(
        vocab_size=VOCAB, n_positions=64, n_embd=32, n_layer=2, n_head=2,
        dtype="float32", kv_cache_dtype=kv,
    )
    model = CausalLMWithValueHead(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


@functools.lru_cache(maxsize=None)
def _engine(kv, pool_blocks):
    from trlx_tpu.inference.engine import ContinuousBatchingEngine
    from trlx_tpu.models.gpt2 import init_cache
    from trlx_tpu.ops.sampling import GenerationConfig

    cfg, model, _ = _model(kv)

    def apply_fn(p, input_ids, attention_mask=None, position_ids=None, cache=None,
                 cache_index=None, last_only=False):
        return model.apply(
            {"params": p}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache, cache_index=cache_index,
            last_only=last_only,
        )

    gen = GenerationConfig(
        max_new_tokens=R, min_new_tokens=1, eos_token_id=EOS, pad_token_id=EOS, do_sample=True,
    )
    return ContinuousBatchingEngine(
        apply_fn=apply_fn, init_cache_fn=functools.partial(init_cache, cfg), gen_config=gen,
        query_length=Q, vocab_size=VOCAB, num_slots=4, admit_width=2, harvest_width=2,
        block_size=4, prefix_pool_blocks=pool_blocks, prefill_chunk=W,
    )


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    ids = np.full((len(lens), Q), EOS, np.int32)
    mask = np.zeros((len(lens), Q), np.int32)
    for i, n in enumerate(lens):
        ids[i, Q - n:] = rng.integers(1, 60, n)
        mask[i, Q - n:] = 1
    return jnp.asarray(ids), jnp.asarray(mask)


def _admit(eng, state, params, program, slot_ids, ids, mask, turns, maps):
    """One admission of a group through ``program``; every chunk index
    where it goes chunk by chunk."""
    key = jax.random.PRNGKey(3)
    rows = jnp.arange(len(slot_ids), dtype=jnp.int32)
    if program == "prefill":
        return eng.prefill_jit(params, state, slot_ids, ids, mask, rows, turns, key, *maps)
    for c in range(Q // W):
        state = eng.prefill_chunk_jit(
            params, state, slot_ids, ids, mask, rows, turns, key, jnp.asarray(c, jnp.int32), *maps
        )
    return state


def _copy(state):
    return jax.tree_util.tree_map(jnp.array, state)


def _fields(state):
    return {f.name: getattr(state, f.name) for f in dataclasses.fields(state) if f.name != "cache"}


@pytest.mark.parametrize("program", ["prefill", "prefill_chunk"])
@pytest.mark.parametrize("pool_blocks", [0, 3], ids=["private", "shared_prefix"])
@pytest.mark.parametrize("kv", ["bfloat16", "int8"])
def test_an_admission_writes_its_slots_and_nothing_else(kv, pool_blocks, program):
    from trlx_tpu.models.gpt2 import init_cache

    cfg, model, params = _model(kv)
    eng = _engine(kv, pool_blocks)
    n_slots, nb, bs, cap = eng.num_slots, eng.n_blocks, eng.block_size, eng.capacity
    no_maps = [jnp.full((2, nb), -1, jnp.int32)] * 2 if pool_blocks else []

    # slots 0 and 1 hold a running group (tables rotated), two steps in
    state = eng.init_state()
    ids0, mask0 = _prompts(0, [9, 16])
    state = eng.prefill_jit(
        params, state, jnp.asarray([0, 1], jnp.int32), ids0, mask0,
        jnp.asarray([7, 8], jnp.int32), jnp.asarray([1, 3], jnp.int32), jax.random.PRNGKey(5), *no_maps,
    )
    for _ in range(2):
        state = eng.decode_step_jit(params, state)[0]
    before = jax.device_get(_copy(state))

    # the admission under test: slot 3 and a dummy, slot 3's table rotated by 2;
    # with sharing, slot 3 publishes its first block to pool block 1
    slot_ids = jnp.asarray([3, n_slots], jnp.int32)
    turns = jnp.asarray([2, 4], jnp.int32)
    ids, mask = _prompts(1, [13, 6])
    maps = []
    if pool_blocks:
        pub = np.full((2, nb), -1, np.int32)
        pub[0, 0] = 1
        maps = [jnp.full((2, nb), -1, jnp.int32), jnp.asarray(pub)]
    after = jax.device_get(_admit(eng, state, params, program, slot_ids, ids, mask, turns, maps))

    # (b) every other slot's rows, tables and state are bit-identical; (c)
    # the dummy wrote nothing: slot 3 alone changed, anywhere
    others = [0, 1, 2]
    for layer_before, layer_after in zip(before.cache, after.cache):
        for key, was in layer_before.items():
            if key in kc.SHARED_POOL_KEYS:
                continue
            np.testing.assert_array_equal(np.asarray(layer_after[key])[others], np.asarray(was)[others], err_msg=key)
    for name, was in _fields(before).items():
        np.testing.assert_array_equal(np.asarray(_fields(after)[name])[others], np.asarray(was)[others], err_msg=name)

    # (a) the dense reference: the same prompt through the dense cache
    dense = init_cache(cfg, 1, cap)
    positions = jnp.clip(jnp.cumsum(mask[:1], axis=-1) - 1, 0, None)
    cache_mask = jnp.concatenate([mask[:1], jnp.zeros((1, R), mask.dtype)], axis=1)
    ref = model.apply(
        {"params": params}, ids[:1], attention_mask=cache_mask, position_ids=positions,
        cache=dense, cache_index=0,
    )
    table = (np.arange(nb) + 2) % nb
    np.testing.assert_array_equal(np.asarray(after.cache[0]["block_tables"])[3], table)
    real = np.flatnonzero(np.asarray(mask[0]))  # a skipped all-pad chunk writes nothing
    phys = table[real // bs] * bs + real % bs

    def values(layer, rows, at):
        """K and V as attention reads them (an int8 pool dequantised)."""
        out = {}
        for key in ("k", "v"):
            x = np.asarray(layer[key], np.float32)[rows, at]
            if kv == "int8":
                x = x * np.asarray(layer[key + "_scale"], np.float32)[rows, at]
            out[key] = x
        return out

    # float32 sums of another batch shape differ in their last bits (the
    # repo's established 2e-6); an int8 value may then round one step apart
    for layer_after, layer_ref in zip(after.cache, ref["cache"]):
        got, want = values(layer_after, 3, phys), values(layer_ref, 0, real)
        for key in got:
            step = np.abs(want[key]).max() / 127 if kv == "int8" else 0.0
            np.testing.assert_allclose(got[key], want[key], rtol=0, atol=2e-6 + step, err_msg=key)
    np.testing.assert_allclose(
        np.asarray(after.logits_last)[3], np.asarray(ref["logits"], np.float32)[0, -1], rtol=0, atol=2e-5
    )
    assert bool(after.active[3]) and int(after.t[3]) == 0 and int(after.n_real[3]) == 13
    np.testing.assert_array_equal(np.asarray(after.query_ids)[3], np.asarray(ids)[0])
    if pool_blocks:
        # the published block holds the K of slot 3's first logical block
        for layer_after in after.cache:
            np.testing.assert_array_equal(
                np.asarray(layer_after["shared_k"])[bs:2 * bs],
                np.asarray(layer_after["k"])[3, table[0] * bs:(table[0] + 1) * bs],
            )
            np.testing.assert_array_equal(np.asarray(layer_after["publish_tables"])[3], np.asarray(maps[1])[0])


@pytest.mark.parametrize("kv", ["bfloat16", "int8"])
def test_a_group_of_dummies_changes_nothing(kv):
    """``compile_admission_programs`` runs every program on a group whose
    slot ids are all out of bounds: the state is what it was, bit for bit."""
    _, _, params = _model(kv)
    eng = _engine(kv, 0)
    state = eng.init_state()
    ids0, mask0 = _prompts(0, [9, 16])
    state = eng.prefill_jit(
        params, state, jnp.asarray([2, 1], jnp.int32), ids0, mask0,
        jnp.asarray([0, 1], jnp.int32), jnp.asarray([1, 3], jnp.int32), jax.random.PRNGKey(5),
    )
    before = jax.device_get(_copy(state))
    dummies = jnp.full((2,), eng.num_slots, jnp.int32)
    ids, mask = _prompts(1, [13, 6])
    for program in ("prefill", "prefill_chunk"):
        state = _admit(eng, state, params, program, dummies, ids, mask, jnp.asarray([2, 4], jnp.int32), [])
    after = jax.device_get(state)
    for was, now in zip(jax.tree_util.tree_leaves(before), jax.tree_util.tree_leaves(after)):
        np.testing.assert_array_equal(np.asarray(now), np.asarray(was))


# --------------------------- the lowered programs -------------------------- #


def _tensor(shape, dtype):
    return "x".join(map(str, shape)) + "x" + {"float32": "f32", "int8": "i8", "bfloat16": "bf16", "int32": "i32"}[str(dtype)]


@pytest.mark.parametrize("program", ["prefill", "prefill_chunk"])
@pytest.mark.parametrize("kv", ["bfloat16", "int8"])
def test_no_slice_of_the_group_and_no_merge_back_in_the_lowered_text(kv, program):
    """StableHLO of a small paged engine's admission programs: the one
    scatter a pool takes updates of the call's columns, never of a slot's
    whole region ``[A, capacity, H, Dh]`` (the merge), and the one gather a
    pool returns is the view ``[A, view_len, H, Dh]`` (the whole forward's
    is the capacity wide: one a pool and no more, where the slice of the
    group was a second), with no slice of a pool. A floating pool takes
    the columns a block a window: ``A x T // bs`` index pairs into the pool
    viewed by blocks ``[B, n_blocks, bs * H, Dh]`` (a reshape, no copy);
    an int8 pool a position a window, ``A x T`` pairs ``[A, T, H, Dh]``."""
    _, _, params = _model(kv)
    eng = _engine(kv, 0)
    sds = lambda tree: jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)  # noqa: E731
    state = jax.eval_shape(eng._make_state)
    A = eng.admit_width
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    args = [sds(params), state, i32(A), i32(A, Q), i32(A, Q), i32(A), i32(A), jax.ShapeDtypeStruct((2,), jnp.uint32)]
    if program == "prefill":
        text, T, view = eng.prefill_jit.lower(*args).as_text(), Q, eng.capacity
    else:
        text, T, view = eng.prefill_chunk_jit.lower(*args, i32()).as_text(), W, Q
    layer = state.cache[0]
    B, cap, H, Dh = layer["k"].shape
    bs = eng.block_size
    n_pools = len(state.cache) * sum(1 for k in layer if k != "block_tables")
    pools = {_tensor(v.shape, v.dtype) for k, v in layer.items() if k != "block_tables"}
    regions = {_tensor((A, cap) + v.shape[2:], v.dtype) for k, v in layer.items() if k != "block_tables"}
    views = {_tensor((A, view) + v.shape[2:], v.dtype) for k, v in layer.items() if k != "block_tables"}
    if kv == "bfloat16":
        written = {_tensor((B, cap // bs, bs * H, Dh), layer["k"].dtype)}
        columns = {_tensor((A, T // bs, bs * H, Dh), layer["k"].dtype)}
        pairs = _tensor((A, T // bs, 2), "int32")
    else:
        written = pools
        columns = {_tensor((A, T) + v.shape[2:], v.dtype) for k, v in layer.items() if k != "block_tables"}
        pairs = _tensor((A, T, 2), "int32")

    scatters = re.findall(
        r'"stablehlo\.scatter".*?\}\) : \(tensor<(\w+)>, tensor<(\w+)>, tensor<(\w+)>\)', text, re.S
    )
    into_pools = [(idx, upd) for operand, idx, upd in scatters if operand in written]
    assert len(into_pools) == n_pools and {upd for _, upd in into_pools} <= columns
    assert {idx for idx, _ in into_pools} == {pairs}
    assert not [upd for _, _, upd in scatters if upd in regions - columns]
    # the block view is a reshape of the pool and back: no copy, no transpose of one
    assert not re.findall(r"stablehlo\.transpose[^\n]*tensor<(?:%s)>" % "|".join(pools | written), text)
    gathers = re.findall(r'"stablehlo\.gather"[^\n]*: \(tensor<(\w+)>, [^\n]*-> tensor<(\w+)>', text)
    from_pools = [res for operand, res in gathers if operand in pools]
    assert len(from_pools) == n_pools and set(from_pools) <= views
    assert not [res for operand, res in gathers if operand in regions]
    sliced = re.findall(r"stablehlo\.(?:dynamic_)?slice[^\n]*: \(tensor<(\w+)>[^\n]*-> tensor<(\w+)>", text)
    assert not [res for operand, res in sliced if operand in pools]
