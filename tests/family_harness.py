"""What every model family's tests share (README.md § tests): how a toy
model, its seeded weights, its programs and its engine are built, and the six
tests every family is held to. Not collected itself: ``tests/test_<family>.py``
defines a :class:`Family` record named ``FAMILY`` and imports the contract
tests it is held to, so a family's cases run in its own file (one xdist worker
under ``--dist loadfile``) and its programs compile once.

Every program here is a ``jax.jit`` compiled once a shape. An eager flax
``apply`` or an eager reference ``forward`` dispatches thousands of tiny
operations, each a compile of its own the first time, and under five busy
neighbour workers reads four times its solo time (ISSUE 68: the eager family
files were 37% of tier-1). No family test runs a whole model or a reference
outside ``jax.jit``; a test that patches an op traces a fresh program after
patching, never a cached one.
"""

import dataclasses
import functools
from typing import Any, Callable, Mapping, Optional, Sequence, Tuple

import numpy as np
import pytest

import jax
import jax.numpy as jnp

Q, R, EOS = 16, 6, 95  # the engines' prompt width, response budget and end token


@dataclasses.dataclass(frozen=True, eq=False)
class Family:
    """One family as its tests build it. ``eq=False``: a record is its own
    cache key."""

    name: str  # as models/registry.py knows it
    config_cls: type
    model_cls: type
    arch: Mapping[str, Any]  # the toy widths, float32 on both sides
    # what the contract tests a family imports read of it:
    reference: Any = None  # the ``benchmark.reference`` module: ``forward(params, cfg, ids, mask)``
    reference_cfg: Optional[Callable[..., dict]] = None  # (cfg, **over) -> what the reference is handed
    init_cache: Optional[Callable] = None  # (cfg, rows, capacity) -> the family's cache
    tol: Optional[float] = None  # logits, in standard deviations of the reference's
    logprob_tol: float = 2e-5  # recorded log-probabilities, nats
    # {case id: [(config override, what the refusal says)]}: one case each
    refusals: Mapping[str, Sequence[Tuple[dict, str]]] = dataclasses.field(default_factory=dict)
    # {case id: (prefill_chunk, pump, config override)}
    engine_cases: Mapping[str, Tuple[int, bool, dict]] = dataclasses.field(default_factory=dict)
    cache_layouts: Sequence[str] = ()  # of ``registry.init_cache``, layer by layer
    # what a family asserts of its own inside a contract test
    check_forward: Optional[Callable] = None  # (cfg, params, out)
    check_engine: Optional[Callable] = None  # (eng, over)
    check_paths: Optional[Callable] = None  # (traced: Traced)
    check_registry: Optional[Callable] = None  # (registered, cfg, cache)
    refuse_more: Optional[Callable] = None  # (cfg, model, params): beyond the configuration's


def left_padded(lens, T, seed=0, vocab=95):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, vocab, (len(lens), T)), jnp.int32)
    mask = jnp.asarray(np.stack([np.r_[np.zeros(T - n), np.ones(n)] for n in lens]), jnp.int32)
    return ids, mask


def positions_of(mask):
    return jnp.clip(jnp.cumsum(mask, axis=-1) - 1, 0, None)


def rel_err(got, want, where):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    where = np.asarray(where).astype(bool)
    return np.abs(got - want)[where].max() / want[where].std()


def grow(mask, cap):
    """``mask`` as a cache of ``cap`` positions reads it: zeros past its width."""
    return jnp.concatenate([mask, jnp.zeros((mask.shape[0], cap - mask.shape[1]), jnp.int32)], axis=1)


def refused(match, fn, *args, **kwargs):
    """``fn`` refuses by name while it is traced: nothing is computed."""
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(functools.partial(fn, *args, **kwargs))


@functools.lru_cache(maxsize=None)
def model_and_params(family, **over):
    cfg = family.config_cls.from_dict(dict(family.arch, **over))
    model = family.model_cls(cfg)

    def seeded(key, noise):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        # move the ones- and zeros-initialised vectors (norm scales, biases, D) off their defaults
        leaves, tree = jax.tree_util.tree_flatten(params)
        keys = jax.random.split(noise, len(leaves))
        leaves = [a + 0.1 * jax.random.normal(k, a.shape) for a, k in zip(leaves, keys)]
        return jax.tree_util.tree_unflatten(tree, leaves)

    return cfg, model, jax.jit(seeded)(jax.random.PRNGKey(0), jax.random.PRNGKey(1))


@functools.lru_cache(maxsize=None)
def programs(family, **over):
    """``(forward, cached, reference)`` of ``model_and_params(family,
    **over)``. ``cached(params, ids, mask, cache, at, positions=None)``: a
    Python ``at`` is part of the program (a whole admission from 0, as the
    engine's ``prefill`` traces it); an array is an operand (a chunk's
    offset, a step's per-row targets)."""
    cfg, model, _ = model_and_params(family, **over)
    reference_cfg = family.reference_cfg(cfg, **over)

    def apply_cached(p, ids, mask, cache, at, positions=None):
        return model.apply({"params": p}, ids, attention_mask=mask, position_ids=positions,
                           cache=cache, cache_index=at)

    at_operand, at_static = jax.jit(apply_cached), jax.jit(apply_cached, static_argnums=4)

    def cached(p, ids, mask, cache, at, positions=None):
        return (at_static if isinstance(at, int) else at_operand)(p, ids, mask, cache, at, positions)

    forward = jax.jit(lambda p, ids, mask: model.apply({"params": p}, ids, attention_mask=mask))
    reference = jax.jit(lambda p, ids, mask: family.reference.forward(p, reference_cfg, ids, mask))
    return forward, cached, reference


def paged(family, cfg, rows, cap, rotate=None, hold=False):
    """The family's cache with every layer that holds keys paged in blocks
    of 4 (row ``rotate``'s blocks rotated by two, as a recycled slot's are;
    ``hold``: padded to whole lanes, as the engine holds a pool)."""
    from trlx_tpu.ops.kv_cache import STATE, cache_kind, hold_pool, identity_block_tables, rotate_block_table

    tables = identity_block_tables(rows, cap // 4)
    if rotate is not None:
        tables = tables.at[rotate].set(rotate_block_table(tables[rotate], 2))
    return tuple(
        c if cache_kind(c).layout == STATE else dict(hold_pool(c) if hold else c, block_tables=tables)
        for c in family.init_cache(cfg, rows, cap)
    )


@functools.lru_cache(maxsize=None)
def under_a_value_head(family, **over):
    """The family's toy model under a value head and its parameters: the
    head's seeded as ``init`` seeds them, the backbone's those of
    ``model_and_params`` (whose second initialisation the compiler drops:
    nothing of it is returned)."""
    from trlx_tpu.models.heads import CausalLMWithValueHead

    cfg, _, backbone = model_and_params(family, **over)
    model = CausalLMWithValueHead(cfg, backbone_cls=family.model_cls)

    def heads(key):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        return {name: sub for name, sub in params.items() if name != "transformer"}

    return model, dict(jax.jit(heads)(jax.random.PRNGKey(0)), transformer=backbone)


@functools.lru_cache(maxsize=None)
def engine(family, prefill_chunk=0, chunks_per_pump=0, **over):
    """A four-slot continuous engine over ``under_a_value_head``'s model,
    and its parameters."""
    from trlx_tpu.inference.engine import ContinuousBatchingEngine
    from trlx_tpu.ops.sampling import GenerationConfig

    cfg = model_and_params(family, **over)[0]
    model, params = under_a_value_head(family, **over)

    def apply_fn(p, input_ids, attention_mask=None, position_ids=None, cache=None,
                 cache_index=None, last_only=False):
        return model.apply({"params": p}, input_ids, attention_mask=attention_mask,
                           position_ids=position_ids, cache=cache, cache_index=cache_index,
                           last_only=last_only)

    gen = GenerationConfig(max_new_tokens=R, min_new_tokens=1, eos_token_id=EOS,
                           pad_token_id=EOS, do_sample=True)
    eng = ContinuousBatchingEngine(
        apply_fn=apply_fn, init_cache_fn=functools.partial(family.init_cache, cfg),
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
    while len(got) < len(ids):  # the serving pump: one step in flight
        free = eng.free_capacity
        if fed < len(ids) and free > 0:
            take = min(free, eng.admit_width, len(ids) - fed)
            eng.submit(ids[fed : fed + take], mask[fed : fed + take])
            fed += take
        for group in eng.pump():
            land(group)
    return got


def admit_beside_a_running_group(eng, params, program):
    """Slots 0 and 1 admitted and two steps in, then slot 3 and a dummy
    admitted through ``program`` (``prefill`` or ``prefill_chunk``): the
    engine's state before and after on the host, and the admitted ids and
    mask. Asserts that every field of slots 0, 1 and the idle 2 reads bit
    for bit what it read before."""
    state = eng.init_state()
    ids0, mask0 = left_padded([9, 16], Q, seed=1)
    key = jax.random.PRNGKey(5)
    state = eng.prefill_jit(params, state, jnp.asarray([0, 1], jnp.int32), ids0, mask0,
                            jnp.asarray([7, 8], jnp.int32), jnp.asarray([1, 3], jnp.int32), key)
    for _ in range(2):
        state = eng.decode_step_jit(params, state)[0]
    before = jax.device_get(jax.tree_util.tree_map(jnp.array, state))

    slot_ids = jnp.asarray([3, eng.num_slots], jnp.int32)
    turns = jnp.asarray([2, 4], jnp.int32)
    ids, mask = left_padded([13, 6], Q, seed=2)
    rows = jnp.arange(2, dtype=jnp.int32)
    if program == "prefill":
        state = eng.prefill_jit(params, state, slot_ids, ids, mask, rows, turns, key)
    else:
        for c in range(Q // 4):
            state = eng.prefill_chunk_jit(params, state, slot_ids, ids, mask, rows, turns, key,
                                          jnp.asarray(c, jnp.int32))
    after = jax.device_get(state)

    others = [0, 1, 2]
    for was, now in zip(before.cache, after.cache):
        for k in was:
            np.testing.assert_array_equal(np.asarray(now[k])[others], np.asarray(was[k])[others], err_msg=k)
    for f in dataclasses.fields(before):
        if f.name != "cache":
            np.testing.assert_array_equal(np.asarray(getattr(after, f.name))[others],
                                          np.asarray(getattr(before, f.name))[others], err_msg=f.name)
    return before, after, ids, mask


def slot_3_rows(eng, mask):
    """Where slot 3's real columns lie in its pool after its second turn:
    ``(its block table, the real columns, their physical rows)``."""
    nb, bs = eng.n_blocks, eng.block_size
    table = (np.arange(nb) + 2) % nb
    real = np.flatnonzero(np.asarray(mask[0]))
    return table, real, table[real // bs] * bs + real % bs


@dataclasses.dataclass(frozen=True)
class Traced:
    """The decode step and one admission chunk of an engine of the
    family's own, lowered: their text with scopes, the path counters after
    each was traced, the gauges after both."""

    eng: Any
    cfg: Any
    step_text: str
    chunk_text: str
    after_step: Mapping[str, float]
    counters: Mapping[str, float]  # after the chunk too
    gauges: Mapping[str, float]


# ------------------------------ the contract ------------------------------ #
# Each takes ``family`` (tests/conftest.py: the importing module's FAMILY).


def test_uncached_forward_matches_the_reference_on_left_padded_rows(family):
    cfg, _, params = model_and_params(family)
    ids, mask = left_padded([21, 13, 5], 21)
    forward, _, reference = programs(family)
    out = forward(params, ids, mask)
    assert rel_err(out["logits"], reference(params, ids, mask), mask) < family.tol
    family.check_forward(cfg, params, out)


def test_a_parked_row_keeps_its_state_and_a_fresh_row_forgets_the_slot(family):
    """The engine's two conventions as the model reads them from the cache
    mask: a row whose ``cache_index`` is past the mask's width (idle or
    finished) leaves state and tail bit for bit; a row with no valid column
    before the call starts from zeros whatever the slot held (a recycled
    slot)."""
    from trlx_tpu.ops.kv_cache import STATE, cache_kind

    cfg, _, params = model_and_params(family)
    rows, cap = 3, 24  # the admission tests' shapes: their programs, compiled once
    ids, mask = left_padded([Q] * rows, Q, seed=2)
    clean = paged(family, cfg, rows, cap, hold=True)
    dirty = tuple(
        {k: jnp.ones_like(v) * 3 for k, v in c.items()} if cache_kind(c).layout == STATE else c
        for c in clean
    )
    cached = programs(family)[1]
    a = cached(params, ids, grow(mask, cap), dirty, 0, positions_of(mask))
    b = cached(params, ids, grow(mask, cap), clean, 0, positions_of(mask))
    np.testing.assert_array_equal(np.asarray(a["logits"]), np.asarray(b["logits"]))
    parked = jnp.asarray([Q, cap, Q], jnp.int32)  # row 1 is parked past the mask's width
    out = cached(params, ids[:, :1], grow(jnp.ones((rows, Q + 1), jnp.int32), cap), a["cache"], parked,
                 jnp.full((rows, 1), Q, jnp.int32))
    for before, after in zip(a["cache"], out["cache"]):
        if cache_kind(before).layout == STATE:
            for k in before:
                np.testing.assert_array_equal(np.asarray(before[k][1]), np.asarray(after[k][1]))
                assert not np.array_equal(np.asarray(before[k][0]), np.asarray(after[k][0]))


def test_what_the_family_does_not_build_is_refused_by_name(family, refusals):
    for over, said in refusals:
        with pytest.raises(ValueError, match=said):
            family.config_cls.from_dict(dict(family.arch, **over))
    if family.refuse_more is not None:
        family.refuse_more(*model_and_params(family))


def test_registry_builds_the_family_and_its_cache_by_kind(family):
    from trlx_tpu.models.registry import get_model_family
    from trlx_tpu.ops.kv_cache import cache_kind

    registered = get_model_family(family.name)
    assert registered.config_cls is family.config_cls and registered.backbone_cls is family.model_cls
    cfg = registered.config_cls.from_dict(dict(family.arch, some_unknown_key=1))
    cache = registered.init_cache(cfg, 2, 8)
    assert [cache_kind(c).layout for c in cache] == list(family.cache_layouts)
    family.check_registry(registered, cfg, cache)


def test_engine_logprobs_match_the_uncached_forward_on_the_tokens_it_drew(family, engine_case):
    """Ten requests through four slots: every slot is recycled (states and
    tails zeroed, block tables rotated), after requests of other lengths
    (the longest first), with whole and chunked admission and with the step
    in flight. The recorded log-probability of every drawn token is the
    reference's on [prompt; drawn tokens]."""
    chunk, pump, over = engine_case
    eng, params = engine(family, chunk, 1 if pump else 0, **over)
    if family.check_engine is not None:
        family.check_engine(eng, over)
    lens = [16, 15, 3, 9, 2, 12, 5, 16, 4, 7]
    ids, mask = left_padded(lens, Q, seed=4)
    ids, mask = np.asarray(ids), np.asarray(mask)
    got = drive(eng, params, ids, mask, pump)
    assert sorted(got) == list(range(len(lens)))
    reference = programs(family, **over)[2]
    for r, row in got.items():
        full_ids = jnp.asarray(np.r_[ids[r], row["tokens"]])[None]
        full_mask = jnp.asarray(np.r_[mask[r], row["response_mask"]])[None]
        logits = reference(params["transformer"], full_ids, full_mask)[0]
        lp = jax.nn.log_softmax(logits[Q - 1 : -1], axis=-1)
        want = np.take_along_axis(np.asarray(lp), row["tokens"][:, None], axis=1)[:, 0]
        live = row["response_mask"].astype(bool)
        np.testing.assert_allclose(row["logprobs"][live], want[live], rtol=0, atol=family.logprob_tol)
    if chunk:
        assert eng.stats.prefill_cols_skipped > 0  # all-pad chunks were not computed


def test_which_paths_the_engines_programs_traced(family):
    """Counted per traced call site, on an engine of the test's own (a
    program traced before counts nothing again): which read and which
    mixer form the decode step and an admission chunk took, none left
    under ``generic``, and the device scopes docs/observability.md names in
    the lowered programs. What a family's layers take is its own
    ``check_paths``."""
    from trlx_tpu import telemetry

    eng, params = engine.__wrapped__(family, 4, 1)
    with telemetry.scoped_metrics() as reg:
        state = jax.eval_shape(eng._make_state)
        abstract = jax.eval_shape(lambda: params)
        step = eng.decode_step_jit.lower(abstract, state)
        after_step = dict(reg.snapshot()["counters"])
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        chunk = eng.prefill_chunk_jit.lower(abstract, state, i32(2), i32(2, Q), i32(2, Q), i32(2), i32(2),
                                            jax.ShapeDtypeStruct((2,), jnp.uint32), i32())
        after_chunk = reg.snapshot()
    assert "attention/decode_path{path=generic}" not in after_chunk["counters"]
    family.check_paths(Traced(
        eng, model_and_params(family)[0], step.as_text(debug_info=True), chunk.as_text(debug_info=True),
        after_step, after_chunk["counters"], after_chunk["gauges"],
    ))
