"""An admission forward writes whole blocks into the paged pool
(``ops/kv_cache.py::writes_whole_blocks``, ``_scatter_blocks``): a call
whose ``T`` columns are whole blocks from a block's first column goes into
the pool a block a window, ``A x T // bs`` index pairs, not a position a
window, ``A x T``. A reorder of writes, not a change of values.

Pinned here, at the serving cells' head shapes (16 x 128 and 2 x 128) and
widths (a chunk of 128 columns, a prompt of 512, blocks of 16):

- the pools and the gathered view after a block write are the by-position
  write's, bit for bit: tables rotated so that a call's blocks wrap the
  pool's end, a traced chunk index for every chunk, dummy rows in the group
  that change nothing, the last slot included;
- the promise a traced index needs (``starting_at_block``) marks a group's
  layers of keys alone, rides through a layer with a tail beside its keys
  and leaves a state layer as it is;
- the predicate: what is unaligned, undeclared, per column, per slot, int8
  or shared by prefix keeps the by-position write; a declared call whose
  width is not whole blocks is refused by name;
- the engine's own admission programs, whole and chunk by chunk, on a
  dense family, a hybrid cache with a state layer and a cache with a tail
  beside its keys, leave the state bit for bit what an engine whose every
  write goes by position leaves;
- the engine observes the predicate's own answer a dispatched forward
  (``engine/prefill_block_write_share``) and every traced write counts its
  path (``kv_cache/write_path{path=blocks|positions}``).

(The lowered admission programs' one scatter a pool and its index count:
``tests/test_admission_in_place.py``; what the chip's compiler makes of it
at the cells' sizes: ``tests/test_tpu_compile.py``.)
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu import telemetry
from trlx_tpu.ops import kv_cache as kc

N_SLOTS, CAP, DH, BS, Q = 5, 640, 128, 16, 512
N_BLOCKS = CAP // BS


def _counts():
    counters = telemetry.get_metrics().snapshot()["counters"]
    return {p: counters.get("kv_cache/write_path{path=%s}" % p, 0) for p in ("blocks", "positions")}


def _paths(fn):
    """``fn()``'s result and the write paths its traced sites counted."""
    before = _counts()
    out = fn()
    after = _counts()
    return out, {p: after[p] - before[p] for p in after}


def _pool(h_kv, seed=0, n_slots=N_SLOTS, cap=CAP):
    """One layer's floating pool, every slot's region already holding
    values (a write that strays is seen)."""
    rng = np.random.default_rng(seed)
    layer = kc.init_paged_cache(1, n_slots, cap, h_kv, DH, jnp.bfloat16, block_size=BS)[0]
    return {
        k: v if k == "block_tables" else jnp.asarray(rng.uniform(0.5, 1.5, v.shape), v.dtype)
        for k, v in layer.items()
    }


def _group(dummies):
    """Slots 3, 0 and 1 (the last slot, 4, is nobody's) and ``dummies``
    dummy rows; the first row's table is rotated so that its first chunk's
    blocks wrap the pool's end (logical blocks 0.. sit at N_BLOCKS - 3..)."""
    slot_ids = np.asarray([3, 0, 1] + [N_SLOTS] * dummies, np.int32)
    turns = np.asarray([N_BLOCKS - 3, 7, 0] + [N_BLOCKS - 1, 5][:dummies], np.int32)
    tables = (np.arange(N_BLOCKS, dtype=np.int32)[None, :] + turns[:, None]) % N_BLOCKS
    return jnp.asarray(slot_ids), jnp.asarray(tables)


def _new(A, T, h_kv, seed):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((A, T, h_kv, DH)), jnp.bfloat16) for _ in range(2))


# (columns, the call's first columns): a chunk at every traced index, the whole prompt
CALLS = {"chunk": (128, [c * 128 for c in range(Q // 128)]), "whole": (Q, [0])}


@pytest.mark.parametrize("dummies", [0, 2], ids=["no_dummy", "two_dummies"])
@pytest.mark.parametrize("call", sorted(CALLS))
@pytest.mark.parametrize("h_kv", [16, 2], ids=["16x128", "2x128"])
def test_a_block_write_holds_the_by_position_writes_bits(h_kv, call, dummies):
    T, firsts = CALLS[call]
    layer = _pool(h_kv)
    slot_ids, tables = _group(dummies)
    A = slot_ids.shape[0]
    rows = dict(layer, block_tables=tables, slot_ids=slot_ids)

    @jax.jit
    def by_position(rows, k, v, first):  # a traced index nobody vouched for
        return kc.paged_write_read(rows, k, v, first, jnp.bfloat16, view_len=Q)

    @jax.jit
    def by_block(rows, k, v, first):
        (declared,) = kc.starting_at_block((rows,), first // BS)
        return kc.paged_write_read(declared, k, v, first, jnp.bfloat16, view_len=Q)

    want_rows, got_rows = rows, rows
    for i, first in enumerate(firsts):
        k, v = _new(A, T, h_kv, seed=10 + i)
        at = jnp.asarray(first, jnp.int32)
        (want_k, want_v, want_rows), took = _paths(lambda: by_position(want_rows, k, v, at))
        assert took in ({"blocks": 0, "positions": 1}, {"blocks": 0, "positions": 0})  # (0: traced already)
        (got_k, got_v, got_new), took = _paths(lambda: by_block(got_rows, k, v, at))
        assert took in ({"blocks": 1, "positions": 0}, {"blocks": 0, "positions": 0})
        assert "first_block" not in got_new  # a promise is about one call
        got_rows = got_new
        # the view the call attends over, and every pool, bit for bit
        np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
        np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
        for key in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(got_rows[key]), np.asarray(want_rows[key]), err_msg=key)
        # the columns lie where the table says: logical position p of row 0
        # at block table[p // bs], and its first chunk wraps the pool's end
        table = np.asarray(tables[0])
        pos = first + np.arange(T)
        phys = table[pos // BS] * BS + pos % BS
        np.testing.assert_array_equal(np.asarray(got_rows["k"])[3, phys], np.asarray(k)[0])
    assert np.asarray(tables[0])[:4].tolist() == [N_BLOCKS - 3, N_BLOCKS - 2, N_BLOCKS - 1, 0]
    # nobody's slots as they were, the last one included: a dummy row
    # (slot_ids == num_slots) drops, it is never clamped into slot 4
    for slot in (2, 4):
        for key in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(got_rows[key])[slot], np.asarray(layer[key])[slot])
    if call == "whole":
        # the Python 0 of a whole forward shows its alignment itself
        k, v = _new(A, T, h_kv, seed=10)
        (_, _, static), took = _paths(lambda: kc.paged_write_read(rows, k, v, 0, jnp.bfloat16))
        assert took == {"blocks": 1, "positions": 0}
        for key in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(static[key]), np.asarray(got_rows[key]), err_msg=key)


def test_a_call_over_every_slot_writes_whole_blocks_too():
    """No group: the call's rows are the pool's (a prefill over all slots
    at a static 0). A block out of range drops like a position there."""
    layer = _pool(2)
    layer["block_tables"] = jnp.stack([kc.rotate_block_table(layer["block_tables"][b], 3 * b) for b in range(N_SLOTS)])
    k, v = _new(N_SLOTS, 32, 2, seed=4)
    assert kc.writes_whole_blocks(layer, k, 16)
    _, _, got = kc.paged_write_read(layer, k, v, 16, jnp.bfloat16)
    _, _, want = jax.jit(lambda at: kc.paged_write_read(layer, k, v, at, jnp.bfloat16))(jnp.int32(16))
    for key in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want[key]), err_msg=key)
    # the discard sentinel, a block at a time: a call at capacity writes nothing
    _, _, dropped = kc.paged_write_read(layer, k, v, CAP, jnp.bfloat16)
    # and one that starts in range and runs past the end keeps its blocks in range
    _, _, tail = kc.paged_write_read(layer, k, v, CAP - 16, jnp.bfloat16)
    _, _, tail_want = jax.jit(lambda at: kc.paged_write_read(layer, k, v, at, jnp.bfloat16))(jnp.int32(CAP - 16))
    for key in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(dropped[key]), np.asarray(layer[key]), err_msg=key)
        np.testing.assert_array_equal(np.asarray(tail[key]), np.asarray(tail_want[key]), err_msg=key)


def test_the_promise_marks_a_groups_layers_of_keys_alone():
    """A hybrid cache: a group's layer of keys, a state layer, a group's
    layer of keys with a tail beside them, and a layer of keys that is no
    group's. ``starting_at_block`` marks the two group layers; the key
    changes no layer's kind, reaches the write through ``split_tail`` and,
    a promise about one call, does not come back with the written layer."""
    slot_ids, tables = _group(1)
    A = slot_ids.shape[0]
    keys = dict(_pool(2), block_tables=tables, slot_ids=slot_ids)
    state = kc.state_buffers(A, n_head=4, head_dim=8, d_state=16, conv_width=4, conv_channels=32)
    tailed = dict(keys, **kc.tail_buffers(A, {"z": (1, 24)}))
    every_slot = _pool(2)
    cache = (keys, state, tailed, every_slot)
    declared = kc.starting_at_block(cache, 8)
    assert [sorted(set(d) - set(c)) for c, d in zip(cache, declared)] == [["first_block"], [], ["first_block"], []]
    assert declared[1] is state and declared[3] is every_slot
    assert [kc.cache_kind(d) for d in declared] == [kc.cache_kind(c) for c in cache]
    assert kc.cache_kind(declared[2]).tail == ("tail_z",)

    kv, tail = kc.split_tail(declared[2])
    assert "first_block" in kv and set(tail) == {"tail_z"}
    k, v = _new(A, 128, 2, seed=2)
    traced = jax.jit(lambda kv, c: kc.paged_write_read(kv, k, v, c * 128, jnp.bfloat16, view_len=Q))
    (_, _, new), took = _paths(lambda: traced(kv, jnp.int32(1)))
    assert took == {"blocks": 1, "positions": 0}
    assert set(new) == set(kv) - {"first_block"}
    # what the model hands back: the written keys with the stepped tail
    assert kc.cache_kind(dict(new, **tail)) == kc.cache_kind(declared[2])


def _int8(layer):
    return dict(layer, k_scale=jnp.zeros(layer["k"].shape[:3] + (1,), jnp.bfloat16),
                v_scale=jnp.zeros(layer["k"].shape[:3] + (1,), jnp.bfloat16))


def _shared(layer):
    n = layer["block_tables"].shape[0]
    return dict(layer, **kc.init_shared_pool(3, BS, 2, DH, jnp.bfloat16),
                shared_tables=kc.empty_share_tables(n, N_BLOCKS), publish_tables=kc.empty_share_tables(n, N_BLOCKS))


TRACED = jax.ShapeDtypeStruct((), jnp.int32)
A_ROWS = 4  # _group(1)

# (what the call shows) -> whether it writes whole blocks
PREDICATE = {
    "a_python_zero": (lambda l: l, 128, 0, True),
    "a_python_block_boundary": (lambda l: l, 128, 384, True),
    "a_python_index_inside_a_block": (lambda l: l, 128, 8, False),
    "a_traced_scalar_nobody_vouched_for": (lambda l: l, 128, TRACED, False),
    "a_traced_scalar_declared": (lambda l: kc.starting_at_block((l,), 8)[0], 128, TRACED, True),
    "columns_that_are_no_whole_blocks": (lambda l: l, 24, 0, False),
    "one_column": (lambda l: l, 1, 0, False),
    "the_verify_steps_matrix": (lambda l: kc.starting_at_block((l,), 0)[0], 128,
                                jax.ShapeDtypeStruct((A_ROWS, 128), jnp.int32), False),
    "the_decode_steps_vector": (lambda l: l, 128, jax.ShapeDtypeStruct((A_ROWS,), jnp.int32), False),
    "an_int8_pool": (lambda l: kc.starting_at_block((_int8(l),), 0)[0], 128, 0, False),
    "a_shared_prefix_group": (lambda l: kc.starting_at_block((_shared(l),), 0)[0], 128, 0, False),
}


@pytest.mark.parametrize("case", sorted(PREDICATE))
def test_the_predicate_reads_what_the_call_shows(case):
    make, T, index, want = PREDICATE[case]
    slot_ids, tables = _group(1)
    layer = make(dict(_pool(2), block_tables=tables, slot_ids=slot_ids))
    k = jax.ShapeDtypeStruct((A_ROWS, T, 2, DH), jnp.bfloat16)
    assert kc.writes_whole_blocks(layer, k, index) is want
    # a dense layer is no pool at all
    assert kc.writes_whole_blocks({"k": layer["k"], "v": layer["v"]}, k, 0) is False


def test_a_declared_call_of_another_width_is_refused_by_name():
    slot_ids, tables = _group(1)
    (layer,) = kc.starting_at_block((dict(_pool(2), block_tables=tables, slot_ids=slot_ids),), 1)
    k, v = _new(A_ROWS, 24, 2, seed=0)
    with pytest.raises(ValueError, match="starting_at_block.*24 columns.*block size 16"):
        kc.writes_whole_blocks(layer, k, jnp.int32(16))
    with pytest.raises(ValueError, match="starting_at_block"):
        jax.jit(lambda at: kc.paged_write_read(layer, k, v, at, jnp.bfloat16))(jnp.int32(16))


@pytest.mark.parametrize("case", [
    "a_traced_scalar_nobody_vouched_for", "a_python_index_inside_a_block", "an_int8_pool", "a_shared_prefix_group",
    "the_verify_steps_matrix",
])
def test_what_keeps_the_by_position_write_lowers_to_it(case):
    """Each such call counts ``path=positions`` and its lowered text is the
    text of the by-position write alone: one scatter a buffer of ``A x T``
    (row, position) pairs into the pool as it is stored, and no view of the
    pool by blocks."""
    make, T, index, _ = PREDICATE[case]
    slot_ids, tables = _group(1)
    layer = make(dict(_pool(2), block_tables=tables, slot_ids=slot_ids))
    if case == "an_int8_pool":
        layer["k"], layer["v"] = (layer[n].astype(jnp.int8) for n in ("k", "v"))
    k, v = _new(A_ROWS, T, 2, seed=1)

    def write(layer, k, v, index):
        return kc.paged_write_read(layer, k, v, index, jnp.bfloat16, view_len=Q)[2]

    if isinstance(index, int):
        lowered, took = _paths(lambda: jax.jit(lambda l, k, v: write(l, k, v, index)).lower(layer, k, v))
    else:
        lowered, took = _paths(lambda: jax.jit(write).lower(layer, k, v, index))
    assert took == {"blocks": 0, "positions": 1}
    text = lowered.as_text()
    scatters = re.findall(
        r'"stablehlo\.scatter".*?\}\) : \(tensor<(\w+)>, tensor<(\w+)>, tensor<(\w+)>\)', text, re.S
    )
    into_pools = [(idx, upd) for operand, idx, upd in scatters if operand.startswith(f"{N_SLOTS}x{CAP}x2x")]
    assert len(into_pools) == (4 if case == "an_int8_pool" else 2)
    assert {idx for idx, _ in into_pools} == {f"{A_ROWS}x{T}x2xi32"}
    assert f"{N_SLOTS}x{N_BLOCKS}x{BS * 2}x{DH}x" not in text


# ------------------------------ the engine -------------------------------- #

EQ, ER, VOCAB, EOS = 16, 8, 64, 63


@functools.lru_cache(maxsize=None)
def _model(kv):
    from trlx_tpu.models.gpt2 import GPT2Config
    from trlx_tpu.models.heads import CausalLMWithValueHead

    cfg = GPT2Config(vocab_size=VOCAB, n_positions=64, n_embd=32, n_layer=2, n_head=2,
                     dtype="float32", kv_cache_dtype=kv)
    model = CausalLMWithValueHead(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def _engine(kv, block_size, prefill_chunk, pool_blocks=0):
    from trlx_tpu.inference.engine import ContinuousBatchingEngine
    from trlx_tpu.models.gpt2 import init_cache
    from trlx_tpu.ops.sampling import GenerationConfig

    cfg, model, _ = _model(kv)

    def apply_fn(p, input_ids, attention_mask=None, position_ids=None, cache=None,
                 cache_index=None, last_only=False):
        return model.apply(
            {"params": p}, input_ids, attention_mask=attention_mask, position_ids=position_ids,
            cache=cache, cache_index=cache_index, last_only=last_only,
        )

    gen = GenerationConfig(max_new_tokens=ER, min_new_tokens=1, eos_token_id=EOS, pad_token_id=EOS, do_sample=True)
    return ContinuousBatchingEngine(
        apply_fn=apply_fn, init_cache_fn=functools.partial(init_cache, cfg), gen_config=gen,
        query_length=EQ, vocab_size=VOCAB, num_slots=4, admit_width=2, harvest_width=2,
        block_size=block_size, prefix_pool_blocks=pool_blocks, prefill_chunk=prefill_chunk,
    )


# (kv, requested block size, requested chunk, shared-prefix blocks) -> the share a program's forwards observe
ENGINES = {
    "whole_blocks": (("bfloat16", 4, 4, 0), {"prefill": 1.0, "prefill_chunk": 1.0}),
    # a user's chunk of 2 columns under blocks of 4: the chunks by position, the whole prompt by block
    "a_chunk_inside_a_block": (("bfloat16", 4, 2, 0), {"prefill": 1.0, "prefill_chunk": 0.0}),
    # blocks of 12 tile the capacity of 24 and neither the prompt nor a chunk
    "blocks_that_tile_nothing": (("bfloat16", 12, 4, 0), {"prefill": 0.0, "prefill_chunk": 0.0}),
    "an_int8_pool": (("int8", 4, 4, 0), {"prefill": 0.0, "prefill_chunk": 0.0}),
    "a_shared_prefix_pool": (("bfloat16", 4, 4, 3), {"prefill": 0.0, "prefill_chunk": 0.0}),
}


@pytest.mark.parametrize("case", sorted(ENGINES))
def test_the_engine_observes_what_its_programs_trace(case):
    """``_block_write_share`` is the predicate's answer on the engine's own
    shapes, a program: each program's traced write sites count the same
    path, ``n_layer`` of them."""
    (kv, bs, chunk, pool_blocks), want = ENGINES[case]
    eng = _engine(kv, bs, chunk, pool_blocks)
    assert eng._block_write_share == want
    _, _, params = _model(kv)
    sds = lambda tree: jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)  # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    A, nb = eng.admit_width, eng.n_blocks
    maps = [i32(A, nb), i32(A, nb)] if pool_blocks else []
    head = [sds(params), jax.eval_shape(eng._make_state), i32(A), i32(A, EQ), i32(A, EQ)]
    seeds = [i32(A), i32(A), jax.ShapeDtypeStruct((2,), jnp.uint32)]
    programs = {
        "prefill": (eng.prefill_jit, head + seeds + maps),
        "prefill_chunk": (eng.prefill_chunk_jit, head + seeds + [i32()] + maps),
    }
    for name, (program, args) in programs.items():
        _, took = _paths(lambda: program.lower(*args))
        by_block = want[name] == 1.0
        assert took == {"blocks": 2 * by_block, "positions": 2 * (not by_block)}, name
    # the decode step's one position a slot stays by position
    _, took = _paths(lambda: eng.decode_step_jit.lower(sds(params), jax.eval_shape(eng._make_state)))
    assert took == {"blocks": 0, "positions": 2}


def _family_engine(family):
    """A fresh toy engine of ``family`` (programs untraced) and its
    parameters: this file's gpt2, or the hybrid's (a state layer beside a
    layer of keys) and zaya's (a tail beside every layer's keys) from their
    own test files. Q 16; a chunk of 4 columns, one a pump."""
    if family == "gpt2":
        return _engine("bfloat16", 4, 4), _model("bfloat16")[2]
    import family_harness
    import test_granite_hybrid
    import test_zaya

    record = {"granite": test_granite_hybrid, "zaya": test_zaya}[family].FAMILY
    return family_harness.engine.__wrapped__(record, 4, 1)


@pytest.mark.parametrize("program", ["prefill", "prefill_chunk"])
@pytest.mark.parametrize("family", ["gpt2", "granite", "zaya"])
def test_an_admission_by_block_leaves_the_state_the_by_position_write_leaves(family, program, monkeypatch):
    """The engine's own programs, whole and chunk by chunk, on a dense
    family, a hybrid cache with a state layer and a cache with a tail
    beside its keys: a running group two steps in, then slot 3 and a dummy
    admitted with rotated tables. The state after (every pool, table, state
    row, tail and slot field) is bit for bit the state of an engine whose
    every write goes by position."""
    def admitted(eng, params):
        key = jax.random.PRNGKey(5)
        rng = np.random.default_rng(1)

        def prompts(lens):
            ids = jnp.asarray(rng.integers(1, 60, (len(lens), EQ)), jnp.int32)
            mask = jnp.asarray(np.stack([np.r_[np.zeros(EQ - n), np.ones(n)] for n in lens]), jnp.int32)
            return ids, mask

        state = eng.init_state()
        ids0, mask0 = prompts([9, 16])
        state = eng.prefill_jit(params, state, jnp.asarray([0, 1], jnp.int32), ids0, mask0,
                                jnp.asarray([7, 8], jnp.int32), jnp.asarray([1, 3], jnp.int32), key)
        for _ in range(2):
            state = eng.decode_step_jit(params, state)[0]
        slot_ids = jnp.asarray([3, eng.num_slots], jnp.int32)
        turns = jnp.asarray([eng.n_blocks - 1, 4], jnp.int32)  # slot 3's first block is the pool's last
        ids, mask = prompts([13, 6])
        rows = jnp.arange(2, dtype=jnp.int32)
        if program == "prefill":
            state = eng.prefill_jit(params, state, slot_ids, ids, mask, rows, turns, key)
        else:
            for c in range(EQ // eng.prefill_chunk):
                state = eng.prefill_chunk_jit(params, state, slot_ids, ids, mask, rows, turns, key,
                                              jnp.asarray(c, jnp.int32))
        return jax.device_get(eng.decode_step_jit(params, state)[0])

    eng, params = _family_engine(family)
    assert eng._block_write_share["prefill"] == eng._block_write_share["prefill_chunk"] == 1.0
    got, took = _paths(lambda: admitted(eng, params))
    assert took["blocks"] > 0
    with monkeypatch.context() as patch:
        patch.setattr(kc, "_first_whole_block", lambda *call: None)
        by_position, params = _family_engine(family)
        assert set(by_position._block_write_share.values()) == {0.0}
        want, took = _paths(lambda: admitted(by_position, params))
        assert took["blocks"] == 0
    for now, was in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(now), np.asarray(was))


@pytest.mark.parametrize("kv,share", [("bfloat16", 1.0), ("int8", 0.0)])
def test_a_server_on_pythias_toy_shapes_reports_the_share(kv, share):
    """Through ``InferenceServer``: a chunk a pump of ``Q // 4`` columns and
    the whole forward for the groups that skip least, every forward
    observed once; a floating pool reads 1.0, an int8 pool 0.0."""
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.inference.server import InferenceServer
    from trlx_tpu.models.heads import CausalLMWithValueHead
    from trlx_tpu.trainer.ppo_trainer import get_causal_arch

    config = TRLConfig.from_dict({
        "model": {"model_type": "gpt_neox", "model_arch": {
            "vocab_size": 32, "max_position_embeddings": 32, "hidden_size": 32, "num_hidden_layers": 2,
            "num_attention_heads": 2, "rotary_pct": 0.25, "kv_cache_dtype": kv}},
        "train": {
            "seq_length": 16, "batch_size": 4, "epochs": 1, "total_steps": 1, "eval_interval": 1000,
            "checkpoint_interval": 100000, "mesh": {"dp": -1, "fsdp": 1, "tp": 1}, "dtype": "float32",
            "rollout": {"slots": 8, "admit_width": 8, "harvest_width": 8, "block_size": 4},
        },
        "method": {
            "name": "PPOConfig", "num_rollouts": 8, "chunk_size": 8, "ppo_epochs": 1,
            "gen_kwargs": {"max_new_tokens": 8, "min_new_tokens": 4, "do_sample": True,
                           "eos_token_id": 30, "pad_token_id": 31},
        },
    })
    family, model_config, _ = get_causal_arch(config)
    model = CausalLMWithValueHead(model_config, backbone_cls=family.backbone_cls)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    server = InferenceServer(config, params=params, seed=5)
    engine = server.engine
    assert (engine.Q, engine.block_size, engine.prefill_chunk) == (16, 4, 4)
    registry = telemetry.get_metrics()
    registry.clear()
    rng = np.random.default_rng(3)
    # short prompts (a chunk or two) and long ones (the whole forward)
    lens = [2, 3, 4, 3, 2, 4, 3, 2, 15, 16, 14, 16, 15, 13, 16, 14]
    rids = server.submit([list(rng.integers(1, 30, n)) for n in lens])
    server.wait(rids)
    seen = registry.snapshot()["histograms"]["engine/prefill_block_write_share"]
    forwards = engine.stats.prefill_chunks + engine.stats.prefill_whole
    assert engine.stats.prefill_chunks >= 1 and engine.stats.prefill_whole >= 1
    assert (seen["count"], seen["mean"], seen["min"]) == (forwards, share, share)
