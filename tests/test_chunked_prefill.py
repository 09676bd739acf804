"""Chunked admission prefill (rollout.prefill_chunk): parity + FLOPs.

The acceptance pins (ISSUE 15 / docs/inference.md "Chunked prefill"):

- chunked <-> monolithic prefill BITWISE parity on tokens/masks (and
  logprobs/values at the engine's established resolution — exact on the
  float32 CPU tier here; the bf16 caveat applies to real-mesh runs and
  is pinned at bf16 tolerance on the fsdp×tp nightly variant), with
  prefix sharing OFF and ON;
- the all-skipped-segment edge: an admit group whose rows are ALL
  shorter than one chunk runs ONLY the final chunk (the prefill mirror
  of the segmented-decode all-finished-tail tests);
- the serving pump's chunk budget interleaves decode with a burst's
  admission without changing any row's bits;
- a chunked admission is a host loop over ONE program, ``prefill_chunk``,
  whoever asks and whatever the budget (ISSUE 52);
- engine-7's exact FLOP count for a group's ``Q // W`` chunk forwards is
  STRICTLY below the monolithic prefill at the same shape.

Engines here are built directly over a tiny float32 model (no trainer
build — the parity surface is the engine's jitted programs, and the
trainer integration is covered by test_inference_engine.py through the
shared construction path).
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.inference import RolloutEngineConfig
from trlx_tpu.inference.engine import ContinuousBatchingEngine
from trlx_tpu.ops.kv_cache import choose_prefill_chunk
from trlx_tpu.ops.sampling import GenerationConfig


# ------------------------------- units --------------------------------- #


def test_choose_prefill_chunk():
    # block-aligned divisor of Q preferred
    assert choose_prefill_chunk(64, 16, 16) == 16
    assert choose_prefill_chunk(64, 20, 16) == 16  # rounded down to divisor
    assert choose_prefill_chunk(8, 4, 2) == 4
    # no block-aligned divisor (bs does not divide Q): largest plain one
    assert choose_prefill_chunk(8, 4, 14) == 4
    # clamped to Q
    assert choose_prefill_chunk(8, 64, 2) == 8
    # disabled
    assert choose_prefill_chunk(64, 0, 16) == 0
    assert choose_prefill_chunk(64, -1, 16) == 0


def test_rollout_config_chunk_validation():
    cfg = RolloutEngineConfig.from_dict(
        {"engine": "continuous", "prefill_chunk": 16,
         "prefill_chunks_per_pump": 2}
    )
    assert cfg.prefill_chunk == 16 and cfg.prefill_chunks_per_pump == 2
    with pytest.raises(ValueError, match="prefill_chunk"):
        RolloutEngineConfig.from_dict({"prefill_chunk": -1})
    with pytest.raises(ValueError, match="prefill_chunks_per_pump"):
        RolloutEngineConfig.from_dict({"prefill_chunks_per_pump": -1})
    with pytest.raises(ValueError, match="needs chunked"):
        RolloutEngineConfig.from_dict({"prefill_chunks_per_pump": 1})
    with pytest.raises(ValueError, match="needs chunked"):
        ContinuousBatchingEngine(
            apply_fn=lambda *a, **k: None,
            init_cache_fn=lambda *a, **k: (),
            gen_config=GenerationConfig(max_new_tokens=4),
            query_length=8,
            vocab_size=16,
            num_slots=2,
            prefill_chunks_per_pump=1,
        )


# --------------------------- shared fixtures ---------------------------- #

Q, R, VOCAB, EOS = 16, 8, 64, 63


@functools.lru_cache(maxsize=None)
def _model_and_params():
    from trlx_tpu.models.gpt2 import GPT2Config
    from trlx_tpu.models.heads import CausalLMWithValueHead

    cfg = GPT2Config(
        vocab_size=VOCAB, n_positions=64, n_embd=32, n_layer=2,
        n_head=2, dtype="float32",
    )
    model = CausalLMWithValueHead(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, model, params


@functools.lru_cache(maxsize=None)
def _engine(prefill_chunk=0, pool_blocks=0, chunks_per_pump=0):
    from trlx_tpu.models.gpt2 import init_cache

    cfg, model, _ = _model_and_params()

    def apply_fn(p, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None, last_only=False):
        return model.apply(
            {"params": p}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache,
            cache_index=cache_index, last_only=last_only,
        )

    gen = GenerationConfig(
        max_new_tokens=R, min_new_tokens=1, eos_token_id=EOS,
        pad_token_id=EOS, do_sample=True,
    )
    return ContinuousBatchingEngine(
        apply_fn=apply_fn,
        init_cache_fn=functools.partial(init_cache, cfg),
        gen_config=gen,
        query_length=Q,
        vocab_size=VOCAB,
        num_slots=4,
        admit_width=2,
        harvest_width=2,
        block_size=4,
        prefix_pool_blocks=pool_blocks,
        prefill_chunk=prefill_chunk,
        prefill_chunks_per_pump=chunks_per_pump,
    )


def _params():
    return _model_and_params()[2]


def _mixed_prompts(n, seed=0, lo=2, hi=None, sort=True):
    """Left-padded mixed-length prompts; sorted by length so admit
    groups become length-homogeneous and leading-pad chunks actually
    skip (a group-max decision — per-row RNG makes submission order
    irrelevant to every row's bits, the engine's invariance contract)."""
    rng = np.random.default_rng(seed)
    hi = Q if hi is None else hi
    ids = np.full((n, Q), EOS, np.int32)
    mask = np.zeros((n, Q), np.int32)
    for i in range(n):
        real = int(rng.integers(lo, hi + 1))
        ids[i, Q - real:] = rng.integers(1, 60, real)
        mask[i, Q - real:] = 1
    if sort:
        order = np.argsort(mask.sum(axis=1))
        ids, mask = ids[order], mask[order]
    return ids, mask


def _sharing_case(pool_blocks):
    """(ids, mask, a fresh host pool or None): mixed lengths for a private
    engine, full-length prompts with a common leading half for a sharing one."""
    from trlx_tpu.serving.prefix_cache import PrefixBlockPool

    if not pool_blocks:
        return (*_mixed_prompts(8, seed=3), None)
    rng = np.random.default_rng(11)
    ids = rng.integers(1, 60, (8, Q)).astype(np.int32)
    ids[:, : Q // 2] = ids[0, : Q // 2]
    return ids, np.ones((8, Q), np.int32), PrefixBlockPool(pool_blocks, 4, (Q + R) // 4)


def _drive_rows(engine, ids, mask, key, pool=None, pump=False, params=None):
    """Run a prompt set through the engine; returns {row: fields}.
    ``pool`` plans prefix sharing just-in-time per admission wave (the
    serving flow — a later wave reads the earlier wave's published
    blocks once ready); ``pump`` uses the serving pump loop instead of
    drive() (exercises the per-pump chunk budget path)."""
    N = ids.shape[0]
    engine.start_phase(_params() if params is None else params, key)
    published_by_row = {}

    def on_admitted(rows):
        for row in rows:
            blocks = published_by_row.pop(row, None)
            if blocks:
                pool.mark_ready(blocks)

    engine._admit_listener = on_admitted if pool is not None else None
    got = {}

    def land(group):
        arrs = {
            k: np.asarray(group[k])
            for k in ("tokens", "response_mask", "logprobs", "values")
        }
        for j, r in enumerate(group["rows"]):
            assert r not in got
            got[r] = {k: v[j] for k, v in arrs.items()}

    if pool is None and not pump:
        engine.submit(ids, mask)
        for group in engine.drive(N):
            land(group)
        return got
    fed = 0
    while len(got) < N:
        free = engine.free_capacity
        if fed < N and free > 0:
            take = min(free, engine.admit_width, N - fed)
            shared_maps = publish_maps = None
            if pool is not None:
                plans = [
                    pool.plan_admission(ids[i], mask[i])
                    for i in range(fed, fed + take)
                ]
                shared_maps = np.stack([p.shared_map for p in plans])
                publish_maps = np.stack([p.publish_map for p in plans])
            rows = engine.submit(
                ids[fed:fed + take], mask[fed:fed + take],
                shared_maps=shared_maps, publish_maps=publish_maps,
            )
            if pool is not None:
                for row, plan in zip(rows, plans):
                    if plan.published:
                        published_by_row[row] = plan.published
            fed += take
        for group in engine.pump():
            land(group)
    return got


def _assert_rows_equal(a, b, exact_fp=True):
    assert set(a) == set(b)
    for r in a:
        np.testing.assert_array_equal(a[r]["tokens"], b[r]["tokens"])
        np.testing.assert_array_equal(
            a[r]["response_mask"], b[r]["response_mask"]
        )
        if exact_fp:
            # float32 CPU tier: tokens and masks above are bitwise. The
            # narrowed attention view and the chunked forward run the same
            # terms (masked columns' softmax weights underflow to exactly
            # 0) through reductions of another shape, so float32
            # log-probabilities and values agree to the last bits, not in
            # them: measured gap on jax 0.9.0 <= 4.8e-7 (1.9e-7 relative)
            np.testing.assert_allclose(
                a[r]["logprobs"], b[r]["logprobs"], rtol=0, atol=2e-6
            )
            np.testing.assert_allclose(
                a[r]["values"], b[r]["values"], rtol=0, atol=2e-6
            )
        else:
            np.testing.assert_allclose(
                a[r]["logprobs"], b[r]["logprobs"], rtol=0, atol=1e-2
            )
            np.testing.assert_allclose(
                a[r]["values"], b[r]["values"], rtol=0, atol=2e-2
            )


# ------------------------------- parity --------------------------------- #


def test_chunked_matches_monolithic_mixed_lengths():
    """The tentpole pin: chunked prefill samples the monolithic program's
    tokens bitwise (float32 log-probabilities and values to 2e-6, see
    ``_assert_rows_equal``) on mixed-length left-padded prompts — INCLUDING
    groups whose leading all-pad chunks were skipped (never computed:
    their cache positions stay zero and every read of them is masked)."""
    mono, chunked = _engine(0), _engine(4)
    ids, mask = _mixed_prompts(8, seed=3)
    key = jax.random.PRNGKey(7)
    want = _drive_rows(mono, ids, mask, key)
    got = _drive_rows(chunked, ids, mask, key)
    _assert_rows_equal(want, got)
    st = chunked.stats
    assert st.prefill_chunks > 0
    # length-sorted submission makes at least the shortest admit group
    # skip its leading pad chunks — the compute-skipping acceptance
    assert st.prefill_cols_skipped > 0
    assert st.prefill_flops_saved > 0


def test_chunked_sharing_matches_monolithic():
    """Prefix sharing ON: pool-covered shared blocks are gathered, never
    recomputed — and the result is still bitwise the monolithic+sharing
    engine's. Full-length prompts with a common leading half (left-padded
    prompts share iff they pad identically, docs/serving.md)."""
    mono_sh, chunked_sh = _engine(0, pool_blocks=16), _engine(4, pool_blocks=16)
    ids, mask, pool = _sharing_case(16)
    key = jax.random.PRNGKey(5)
    want = _drive_rows(mono_sh, ids, mask, key, pool=pool)
    got = _drive_rows(chunked_sh, ids, mask, key, pool=_sharing_case(16)[2])
    _assert_rows_equal(want, got)
    st = chunked_sh.stats
    assert st.prefix_hit_blocks > 0  # sharing actually happened
    # shared leading blocks were SKIPPED, not recomputed: the
    # docs/serving.md caveat ("sharing buys HBM traffic, not prefill
    # FLOPs") is closed — prefix_hit_rate is now also a FLOP number
    assert st.prefill_cols_skipped > 0
    assert st.prefill_flops_saved > 0


def test_all_rows_shorter_than_one_chunk():
    """The early-exit tail edge (the prefill mirror of the segmented
    decode's all-finished-tail pins): every row of every admit group
    fits inside the FINAL chunk, so every chunk before it skips — the
    group pays exactly one chunk forward (the final one), and the bits
    still match the monolithic program."""
    mono, chunked = _engine(0), _engine(4)
    ids, mask = _mixed_prompts(4, seed=9, lo=1, hi=3, sort=False)
    key = jax.random.PRNGKey(13)
    want = _drive_rows(mono, ids, mask, key)
    got = _drive_rows(chunked, ids, mask, key)
    _assert_rows_equal(want, got)
    st = chunked.stats
    n_groups = st.prefills
    n_before = chunked.n_prefill_chunks - 1
    assert st.prefill_chunks == n_groups  # ONLY the final chunks ran
    assert st.prefill_cols_skipped == (
        n_groups * n_before * chunked.prefill_chunk
    )


def test_pump_chunk_budget_interleaves_decode():
    """Sarathi-style stall-free admission: with a one-chunk-per-pump
    budget, an admission burst's prefill spreads across pump iterations
    with decode steps in between — strictly more decode dispatches than
    the inline admission path while rows are identical bitwise, and a
    mid-prefill weight push is deferred to the group boundary."""
    chunked, budgeted = _engine(4), _engine(4, chunks_per_pump=1)
    ids, mask = _mixed_prompts(8, seed=21, lo=Q, hi=Q)  # all full-length
    key = jax.random.PRNGKey(17)
    want = _drive_rows(chunked, ids, mask, key, pump=True)
    got = _drive_rows(budgeted, ids, mask, key, pump=True)
    _assert_rows_equal(want, got)
    assert budgeted.stats.prefill_chunks == chunked.stats.prefill_chunks
    # the budgeted loop needed MORE pump iterations (each a decode step
    # once slots are busy) to cover the same admissions
    assert budgeted.stats.decode_steps > chunked.stats.decode_steps

    # mid-prefill push deferral: stage a push while a group is in
    # flight; it must not apply until the group completes
    budgeted.start_phase(_params(), key)
    budgeted.submit(ids[:2], mask[:2])
    budgeted.pump()  # begins the admission, dispatches one chunk
    assert budgeted._inflight_admission is not None
    budgeted.push_weights(_params(), version=5)
    budgeted.pump()
    assert budgeted.param_version in (0, 5)
    if budgeted._inflight_admission is not None:
        assert budgeted.param_version == 0  # still deferred mid-group
    while budgeted._inflight_admission is not None:
        budgeted.pump()
    budgeted.pump()  # group boundary: the push applies
    assert budgeted.param_version == 5


def test_request_marks_carry_chunk_offsets():
    """Serving observability: a traced request harvested through the
    chunked path carries per-chunk-window dispatch offsets in its marks
    (the serve/prefill span attributes --trace-report reads)."""
    chunked = _engine(4)
    chunked.trace_requests = True
    try:
        ids, mask = _mixed_prompts(2, seed=4, lo=Q, hi=Q, sort=False)
        chunked.start_phase(_params(), jax.random.PRNGKey(3))
        rows = chunked.submit(ids, mask)
        for _ in chunked.drive(2):
            pass
        record = chunked.pop_request_record(rows[0])
        offs = record["marks"]["prefill_chunk_offsets"]
        assert len(offs) >= 1
        assert all(
            set(o) == {"col", "ms"} and o["ms"] >= 0.0 for o in offs
        )
        cols = [o["col"] for o in offs]
        assert cols == sorted(cols)
        assert cols[-1] == (chunked.n_prefill_chunks - 1) * chunked.prefill_chunk
    finally:
        chunked.trace_requests = False


# ------------------- the families the serving cells run ------------------ #

FAMILY_ARCH = {
    "gpt_neox": dict(
        vocab_size=VOCAB, max_position_embeddings=64, hidden_size=32,
        num_hidden_layers=2, num_attention_heads=2, rotary_pct=0.25,
    ),
    "olmoe": dict(
        vocab_size=VOCAB, max_position_embeddings=64, hidden_size=32,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        intermediate_size=16, num_experts=4, num_experts_per_tok=2,
        norm_topk_prob=False,
    ),
}


@functools.lru_cache(maxsize=None)
def _family_engines(name):
    """(params, monolithic, chunked, chunked with a pump budget of one)
    over a tiny float32 model of a registered family."""
    from trlx_tpu.models.heads import CausalLMWithValueHead
    from trlx_tpu.models.registry import get_model_family

    family = get_model_family(name)
    cfg = family.config_cls.from_dict(
        dict(FAMILY_ARCH[name], dtype="float32", param_dtype="float32")
    )
    model = CausalLMWithValueHead(cfg, backbone_cls=family.backbone_cls)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]

    def apply_fn(p, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None, last_only=False):
        return model.apply(
            {"params": p}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache,
            cache_index=cache_index, last_only=last_only,
        )

    gen = GenerationConfig(
        max_new_tokens=R, min_new_tokens=1, eos_token_id=EOS,
        pad_token_id=EOS, do_sample=True,
    )
    common = dict(
        apply_fn=apply_fn,
        init_cache_fn=functools.partial(family.init_cache, cfg),
        gen_config=gen, query_length=Q, vocab_size=VOCAB, num_slots=4,
        admit_width=2, harvest_width=2, block_size=4,
    )
    return (
        params,
        ContinuousBatchingEngine(**common),
        ContinuousBatchingEngine(**common, prefill_chunk=4),
        ContinuousBatchingEngine(
            **common, prefill_chunk=4, prefill_chunks_per_pump=1
        ),
        ContinuousBatchingEngine(
            **common, prefill_chunk=4, prefill_chunks_per_pump=1,
            prefill_min_skip_share=0.5,
        ),
    )


@pytest.mark.parametrize(
    "how", ["drive", "pump-budget-1", "pump-min-skip-half"]
)
@pytest.mark.parametrize("family", sorted(FAMILY_ARCH))
def test_chunked_matches_monolithic_by_family(family, how):
    """The serving cells' families (pythia is ``gpt_neox``: rotary
    positions, parallel residual; OLMoE: a routed expert layer whose
    grouped multiplication sees a chunk's rows, not the prompt's) through
    the chunked admission, whole in ``drive``, one forward a pump, and one
    forward a pump with the groups that can skip under half their chunks
    forwarded whole (what a server derives): the monolithic program's
    tokens and masks, bitwise."""
    params, mono, chunked, budgeted, mixed = _family_engines(family)
    ids, mask = _mixed_prompts(8, seed=5)
    key = jax.random.PRNGKey(23)
    want = _drive_rows(mono, ids, mask, key, params=params)
    engine = {"drive": chunked, "pump-budget-1": budgeted}.get(how, mixed)
    got = _drive_rows(
        engine, ids, mask, key, pump=how != "drive", params=params
    )
    _assert_rows_equal(want, got)
    stats = engine.stats
    assert stats.prefill_cols_skipped > 0
    if engine is mixed:
        # both kinds of admission ran, and a whole one runs no chunk
        assert 0 < stats.prefill_whole < stats.prefills
        assert stats.prefill_chunks >= stats.prefills - stats.prefill_whole
    else:
        assert stats.prefill_whole == 0
        assert stats.prefill_chunks > stats.prefills  # > the final chunks alone


# --------------------- one program, whatever the budget ------------------- #


def _count_dispatches(engine, monkeypatch):
    """Record the chunk index of every ``prefill_chunk`` dispatch and the
    plan of every admitted group; count ``prefill`` dispatches."""
    seen = {"chunks": [], "plans": [], "whole": 0}
    chunk_jit, whole_jit = engine.prefill_chunk_jit, engine.prefill_jit
    finalize = engine._finalize_admission

    def chunk(*args):
        seen["chunks"].append(int(args[8]))
        return chunk_jit(*args)

    def whole(*args):
        seen["whole"] += 1
        return whole_jit(*args)

    def finalized():
        seen["plans"].append(np.asarray(engine._inflight_admission["need"]))
        finalize()

    monkeypatch.setattr(engine, "prefill_chunk_jit", chunk)
    monkeypatch.setattr(engine, "prefill_jit", whole)
    monkeypatch.setattr(engine, "_finalize_admission", finalized)
    return seen


@pytest.mark.parametrize("pool_blocks", [0, 16], ids=["private", "shared_prefix"])
def test_an_unbudgeted_admission_is_one_dispatch_a_needed_chunk(pool_blocks, monkeypatch):
    """``drive()`` with a chunk set (and a pump without a budget, the way a
    sharing engine is fed here): every group goes through ``prefill_chunk``
    alone, once for each chunk its plan needs before the final one, in
    order, and once for the final chunk."""
    engine = _engine(4, pool_blocks=pool_blocks)
    ids, mask, pool = _sharing_case(pool_blocks)
    seen = _count_dispatches(engine, monkeypatch)
    _drive_rows(engine, ids, mask, jax.random.PRNGKey(7), pool=pool)
    last = engine.n_prefill_chunks - 1
    want = [
        c for plan in seen["plans"]
        for c in [*np.flatnonzero(plan[:last]), last]
    ]
    assert seen["chunks"] == want and seen["whole"] == 0
    assert len(seen["plans"]) == engine.stats.prefills == 4
    assert engine.stats.prefill_chunks == len(want)
    # some group skipped, some group needed more than its final chunk
    assert len(seen["plans"]) < len(want) < len(seen["plans"]) * (last + 1)


@pytest.mark.parametrize("pool_blocks", [0, 16], ids=["private", "shared_prefix"])
def test_a_budget_of_two_is_at_most_two_dispatches_a_pump(pool_blocks, monkeypatch):
    """``prefill_chunks_per_pump=2`` means two forwards a pump iteration
    and nothing else: each is a ``prefill_chunk`` dispatch, an iteration
    makes at most two (and some make two), and the rows are the monolithic
    program's bitwise."""
    mono = _engine(0, pool_blocks=pool_blocks)
    budgeted = _engine(4, pool_blocks=pool_blocks, chunks_per_pump=2)
    ids, mask, pool = _sharing_case(pool_blocks)
    key = jax.random.PRNGKey(17)
    want = _drive_rows(mono, ids, mask, key, pool=pool, pump=True)
    seen = _count_dispatches(budgeted, monkeypatch)
    pump, per_pump = budgeted.pump, []

    def counted():
        before = len(seen["chunks"])
        groups = pump()
        per_pump.append(len(seen["chunks"]) - before)
        return groups

    monkeypatch.setattr(budgeted, "pump", counted)
    pool = _sharing_case(pool_blocks)[2]
    got = _drive_rows(budgeted, ids, mask, key, pool=pool, pump=True)
    _assert_rows_equal(want, got)
    assert max(per_pump) == 2 and seen["whole"] == 0
    assert sum(per_pump) == budgeted.stats.prefill_chunks > budgeted.stats.prefills


def test_an_engine_has_one_chunk_program_and_a_head_one_switch():
    """What existed for the scan of chunks is gone: the engine's jitted
    programs are these six names, and the value-head model's call takes
    ``last_only`` and no other switch."""
    import inspect

    from trlx_tpu.models.heads import CausalLMWithValueHead

    assert {n for n in vars(_engine(4)) if n.endswith("_jit")} == {
        "prefill_jit", "prefill_chunk_jit", "decode_step_jit", "refill_jit",
        "release_jit", "verify_step_jit",
    }
    assert list(inspect.signature(CausalLMWithValueHead.__call__).parameters) == [
        "self", "input_ids", "attention_mask", "position_ids", "cache",
        "cache_index", "last_only",
    ]


# ------------------------- what a server derives -------------------------- #


def _server(rollout=None, seq_length=16):
    from trlx_tpu.analysis import harness
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.inference.server import InferenceServer

    cfg = harness.tiny_config_dict("ppo")  # dp 2 x fsdp 2 x tp 2
    cfg["train"]["seq_length"] = seq_length
    cfg["train"]["dtype"] = "float32"  # the tier where parity is bitwise
    cfg["train"]["rollout"] = dict(
        {"slots": 8, "admit_width": 4, "harvest_width": 4, "block_size": 4},
        **(rollout or {}),
    )
    cfg["method"]["gen_kwargs"].update(max_new_tokens=8, min_new_tokens=1)
    return InferenceServer(TRLConfig.from_dict(cfg), seed=3)


@pytest.fixture(scope="module")
def derived_server():
    return _server()


def _stream_all(server, prompts, every=3):
    """Submit ``prompts`` one every ``every`` iterations (0: all at
    once), streamed, and step the server dry. Returns (tokens each stream
    delivered, results, the largest number of prefill forwards, chunk or
    whole, one iteration dispatched)."""
    stats = server.engine.stats
    streams, rids, most = {}, [], 0
    todo = list(prompts)
    it = 0
    while todo or any(server.poll(r) is None for r in rids):
        while todo and (not every or it % every == 0):
            (rid,) = server.submit([todo.pop(0)], stream=True)
            rids.append(rid)
            streams[rid] = (server.stream(rid), [])
            if every:
                break
        before = stats.prefill_chunks + stats.prefill_whole
        server.step()
        most = max(most, stats.prefill_chunks + stats.prefill_whole - before)
        for stream, got in streams.values():
            got.extend(stream.drain())
        it += 1
    results = [server.pop_result(r) for r in rids]
    return [streams[r][1] for r in rids], results, most


def _server_prompts(n, q, seed):
    rng = np.random.default_rng(seed)
    return [
        list(rng.integers(1, 30, int(rng.integers(1, q + 1)))) for _ in range(n)
    ]


@pytest.mark.parametrize("rollout,chunk,per_pump,min_skip", [
    # nothing set: Q // 4 through choose_prefill_chunk, budget 1, a group
    # that needs 3 or 4 of its 4 forwards goes whole
    ({}, 4, 1, 0.5),
    # the user's width, with the budget it came with, every group chunked
    ({"prefill_chunk": 8}, 8, 0, 0),
    ({"prefill_chunk": 8, "prefill_chunks_per_pump": 2}, 8, 2, 0),
])
def test_server_chunk_rule(rollout, chunk, per_pump, min_skip, derived_server):
    server = derived_server if not rollout else _server(rollout)
    engine = server.engine
    assert engine.prefill_chunk == chunk
    assert engine.prefill_chunks_per_pump == per_pump
    assert engine.n_prefill_chunks == 16 // chunk
    assert engine.prefill_min_skip_share == min_skip


def test_server_streams_the_monolithic_programs_tokens(derived_server, monkeypatch):
    """No chunk option: the server streams, token for token and
    log-probability for log-probability, what one forced to the monolithic
    ``prefill`` streams for the same seed, with 14 requests of mixed
    lengths over 8 slots, so that groups are admitted, one forward an
    iteration (chunks, or the whole group where little can be skipped),
    into recycled slots while the others decode. (What the tap
    must not do there: credit the tokens a reserved slot's previous
    occupant still emits to the row waiting for the slot. Submitted at
    once, because a placeholder takes a row index, and with it a draw:
    when one is needed depends on when requests arrive.)"""
    real = ContinuousBatchingEngine.__init__

    def monolithic(self, **kw):
        kw.update(
            prefill_chunk=0, prefill_chunks_per_pump=0, prefill_min_skip_share=0
        )
        real(self, **kw)

    # groups of up to half length (chunked, leading chunks skipped), then
    # groups with a longer prompt (forwarded whole)
    prompts = _server_prompts(8, 8, seed=8) + _server_prompts(6, 16, seed=9)
    stats = derived_server.engine.stats
    skipped0, whole0 = stats.prefill_cols_skipped, stats.prefill_whole
    got_streams, got, _ = _stream_all(derived_server, prompts, every=0)
    assert stats.prefill_cols_skipped > skipped0
    assert stats.prefill_whole > whole0
    monkeypatch.setattr(ContinuousBatchingEngine, "__init__", monolithic)
    mono = _server()
    assert mono.engine.prefill_chunk == 0 and mono.engine.prefill_chunk_jit is None
    want_streams, want, _ = _stream_all(mono, prompts, every=0)
    for g, w in zip(got, want):
        assert g["tokens"] == w["tokens"] and g["length"] == w["length"]
        np.testing.assert_allclose(g["logprobs"], w["logprobs"], rtol=0, atol=2e-6)
    # a stream holds its request's tokens, then what the slot emits past
    # its budget until its harvest group fills (PERF.md section 7)
    for streams, results in ((got_streams, got), (want_streams, want)):
        for streamed, res in zip(streams, results):
            assert streamed[: res["length"]] == res["tokens"]


def test_a_pump_dispatches_at_most_one_chunk_forward(derived_server):
    """The stall a running stream feels is one forward and a decode step,
    whatever arrives, a new prompt every second iteration: a group of
    half-length prompts takes two chunk forwards, in two iterations; one
    that holds a full-length prompt can skip nothing and takes the one
    whole forward. (Which requests share a group follows from when slots
    come free, a step after their flags since the loop reads one step
    behind: a half-length prompt may ride in a whole group.)"""
    stats = derived_server.engine.stats
    chunks0, whole0, groups0 = (
        stats.prefill_chunks, stats.prefill_whole, stats.prefills
    )
    prompts = [list(range(1, 9)), list(range(1, 17))] * 3
    _, results, most = _stream_all(derived_server, prompts, every=2)
    assert most == 1 and all(r["length"] >= 1 for r in results)
    whole = stats.prefill_whole - whole0
    chunked = stats.prefills - groups0 - whole
    assert whole == 3 and chunked >= 1
    assert stats.prefill_chunks - chunks0 == 2 * chunked


def test_server_compiles_nothing_after_setup():
    """Construction builds every admission program; a warm-up whose
    prompts all fit the final chunk (so no non-final chunk ever ran) then
    leaves nothing for the first longer prompts to compile: backend
    compiles counted from jax's monitoring events as the benchmark's
    ``accounting.compiles_in_window`` counts them, and the engine's jitted
    programs by their cache sizes: they are all the programs it has."""
    from jax import monitoring

    compiles = []
    counting = [False]

    def on(event, duration, **_):
        if counting[0] and event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    monitoring.register_event_duration_secs_listener(on)
    server = _server()
    engine = server.engine
    # 5 short prompts: a partial harvest group, so placeholders too
    _stream_all(server, _server_prompts(5, 3, seed=2), every=1)
    assert engine.stats.prefill_chunks == engine.stats.prefills  # final chunks only
    assert engine.stats.released > 0
    programs = {
        name: getattr(engine, name)._cache_size()
        for name in ("prefill_jit", "prefill_chunk_jit", "release_jit",
                     "decode_step_jit", "refill_jit")
    }
    assert all(n == 1 for n in programs.values()), programs
    counting[0] = True
    try:
        # half-length prompts reach ``prefill_chunk``, full-length ones
        # the whole ``prefill``
        late = [list(range(1, 9)), list(range(1, 17))] * 3
        _, results, _ = _stream_all(server, late, every=1)
    finally:
        counting[0] = False
    assert all(r["length"] >= 1 for r in results)
    assert engine.stats.prefill_whole > 0
    assert (
        engine.stats.prefill_chunks
        > engine.stats.prefills - engine.stats.prefill_whole
    )
    assert compiles == []
    assert programs == {
        name: getattr(engine, name)._cache_size() for name in programs
    }
    # ``prefill_chunk`` forwards the final chunk too: no other program
    # exists that a later admission could reach
    assert {
        n for n, fn in vars(engine).items() if n.endswith("_jit") and fn is not None
    } == set(programs)


def test_skip_share_histogram_is_observed_once_an_admission(derived_server):
    """``engine/prefill_skip_share`` (the benchmark's
    ``serve_prefill_skip_share``): the group's skipped chunks over its
    chunks, once an admission, in the registry the server reports."""
    from trlx_tpu import telemetry

    with telemetry.scoped_metrics() as reg:
        derived_server._registry = reg
        try:
            before = derived_server.engine.stats.prefills
            # one group that fits the final chunk (3 of 4 skipped), then
            # one of full length (none skipped)
            derived_server.generate([[1, 2, 3]] * 4)
            derived_server.generate([list(range(1, 17))] * 4)
            summary = derived_server.metrics()["engine/prefill_skip_share"]
        finally:
            derived_server._registry = telemetry.get_metrics()
    assert summary["count"] == derived_server.engine.stats.prefills - before == 2
    assert summary["max"] == 0.75 and summary["min"] == 0.0
    assert summary["mean"] == pytest.approx(0.375)


# ---------------- the paths that keep the parent's programs --------------- #

# sha256[:16] of ``jitted.lower(args).as_text()`` (StableHLO, no source
# locations) at commit 270555e (PR 29; PERF.md section 6 lists the same
# digests): gpt2 2 x 32 in bf16, Q 16 + R 8, 4 slots, admit/harvest 2
PARENT_PROGRAMS = {
    # PR 51 (first asked as PR 50): its loop carries one array a kind for all layers, written in
    # place at the layer's index; 0ad7eebdd65972e5 before it
    "sampler": "f9ea1ab01e76431c",
    # PR 42: the forward addresses its group's rows inside the whole pool
    # (no slice of the group, no merge back); 09eb3fddfb0669cc before it.
    # PR 49: its 16 columns are whole blocks (of 4) from a Python 0, so they
    # go into the pool a block a window; e1d3e97bfe73739e before it
    "prefill": "d978d250b0578bbd",
    "decode_step": "d393924ac90fb367",
    "refill": "5e8422c7555df564",
}


@functools.lru_cache(maxsize=None)
def _default_path_digests():
    import hashlib

    from trlx_tpu.models.gpt2 import GPT2Config, init_cache
    from trlx_tpu.models.heads import CausalLMWithValueHead
    from trlx_tpu.ops.sampling import make_sampler

    digest = lambda text: hashlib.sha256(text.encode()).hexdigest()[:16]  # noqa: E731
    cfg = GPT2Config(
        vocab_size=VOCAB, n_positions=64, n_embd=32, n_layer=2, n_head=2,
        dtype="bfloat16", kv_cache_dtype="bfloat16",
    )
    model = CausalLMWithValueHead(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]

    def apply_fn(p, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None, **kw):
        return model.apply(
            {"params": p}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache,
            cache_index=cache_index, **kw,
        )

    gen = GenerationConfig(
        max_new_tokens=R, min_new_tokens=1, eos_token_id=EOS,
        pad_token_id=EOS, do_sample=True,
    )
    init_fn = functools.partial(init_cache, cfg)
    out = {}
    ids, mask = _mixed_prompts(4, seed=0)
    sampler = jax.jit(make_sampler(apply_fn, init_fn, gen, Q))
    out["sampler"] = digest(
        sampler.lower(
            params, jnp.asarray(ids), jnp.asarray(mask), jax.random.PRNGKey(1)
        ).as_text()
    )
    # the trainer's collect loop with default options: no chunk, drive()
    engine = ContinuousBatchingEngine(
        apply_fn=apply_fn, init_cache_fn=init_fn, gen_config=gen,
        query_length=Q, vocab_size=VOCAB, num_slots=4, admit_width=2,
        harvest_width=2, block_size=4,
    )
    for name in ("prefill", "decode_step", "refill"):
        fn = getattr(engine, name + "_jit")

        def recording(*a, _fn=fn, _name=name):
            if _name not in out:
                out[_name] = digest(_fn.lower(*a).as_text())
            return _fn(*a)

        setattr(engine, name + "_jit", recording)
    ids, mask = _mixed_prompts(8, seed=3)
    engine.start_phase(params, jax.random.PRNGKey(7))
    engine.submit(ids, mask)
    for _ in engine.drive(8):
        pass
    assert engine.prefill_chunk_jit is None and engine.stats.prefill_chunks == 0
    return out


@pytest.mark.parametrize("program", sorted(PARENT_PROGRAMS))
def test_default_rollout_paths_lower_the_parents_programs(program):
    """Chunked admission is the serving pump's: the trainer's collect loop
    on the continuous engine with default options still lowers the
    monolithic ``prefill``, and ``decode_step`` and ``refill`` with it, text
    for text what they were before PR 30 (``prefill`` as PR 42 left it),
    and the fixed sampler is as PR 51 left it. A change that means to alter one of these programs updates
    its digest here (``tools/program_hashes.py`` compares whole runs)."""
    assert _default_path_digests()[program] == PARENT_PROGRAMS[program]


def test_layers_too_large_to_stage_keep_the_sampler_they_had(monkeypatch):
    """Where a layer's buffer is too large for the compiler to stage
    (``ppo-gpt2m-tldr``'s 73 MB bf16 layers; here the limit is set to 0)
    the sampler's loop carries the per-layer tuple and lowers, text for
    text, the program it was before PR 51: such a cell runs the parent's
    operations (PERF.md §6, PR 50/51)."""
    from trlx_tpu.ops import kv_cache

    monkeypatch.setattr(kv_cache, "STAGED_LAYER_BYTES", 0)
    assert _default_path_digests.__wrapped__()["sampler"] == "0ad7eebdd65972e5"


# ------------------------------- FLOPs ---------------------------------- #


def test_chunked_flops_strictly_below_monolithic():
    """The engine-7 acceptance: a group's exact dot-FLOP count in chunks
    (``Q // W`` traces of ``prefill_chunk``, every chunk run) is strictly
    below the monolithic prefill at the same shape — the prompt-wide
    attention view alone guarantees it, before any chunk is skipped at
    runtime. Also pins the flops-saved gauge's per-chunk cost as a real
    traced number: that of the program that runs."""
    from trlx_tpu.analysis.resource_audit import count_flops

    mono, chunked = _engine(0), _engine(4)
    params_sds = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), _params()
    )
    state_sds = jax.eval_shape(mono._make_state)
    A = mono.admit_width
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    args = (
        params_sds, state_sds, i32(A), i32(A, Q), i32(A, Q), i32(A), i32(A),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    f_mono = count_flops(jax.make_jaxpr(mono.prefill_jit)(*args).jaxpr)
    f_chunk = count_flops(
        jax.make_jaxpr(chunked.prefill_chunk_jit)(*args, i32()).jaxpr
    )
    assert 0 < chunked.n_prefill_chunks * f_chunk < f_mono
    # the saved-FLOPs gauge prices one skipped chunk with the SAME
    # counter over the same traced program
    chunked.start_phase(_params(), jax.random.PRNGKey(1))
    assert chunked._chunk_flop_cost() == f_chunk


def test_budget_lockfile_pins_chunked_below_monolithic():
    """The committed resource lockfile (analysis/budgets.json) carries
    the chunk program's subject, and at the audit shape (a chunk of
    Q // 2) its two forwards sit strictly below the monolithic entry —
    for the trainer engine AND the sharing serving variant."""
    import json

    from trlx_tpu.analysis.resource_audit import default_budgets_path

    programs = json.load(open(default_budgets_path()))["programs"]
    for suffix in ("", "_shared"):
        mono = programs[f"ppo.engine_prefill{suffix}"]["flops"]
        chunk = programs[f"ppo.engine_prefill_chunk{suffix}"]["flops"]
        assert 0 < 2 * chunk < mono, suffix


def test_engine_serves_local_attention_gpt_neo():
    """Ride-along regression pin: ``gpt_neo.local_causal_bias`` now
    supports the engine's per-row [B] ``cache_index`` offsets (the
    vector-offset contract ``ops/attention.py::causal_bias`` already
    had). Previously ANY GPT-Neo config with a local layer crashed the
    continuous engine's decode_step at trace time — the latent gap the
    chunked-prefill family sweep exposed. Pins engine (monolithic AND
    chunked) against the fixed sampler bitwise on a global+local
    config."""
    from trlx_tpu.models.gpt_neo import (
        GPTNeoConfig,
        GPTNeoModel,
        init_gpt_neo_cache,
    )
    from trlx_tpu.models.heads import CausalLMWithValueHead
    from trlx_tpu.ops.sampling import make_row_keys, make_sampler

    cfg = GPTNeoConfig(
        vocab_size=VOCAB, max_position_embeddings=64, hidden_size=32,
        num_layers=2, num_heads=2, window_size=8,
        attention_layers=("global", "local"), dtype="float32",
    )
    model = CausalLMWithValueHead(cfg, backbone_cls=GPTNeoModel)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]

    def apply_fn(p, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None, last_only=False):
        return model.apply(
            {"params": p}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache,
            cache_index=cache_index, last_only=last_only,
        )

    gen = GenerationConfig(
        max_new_tokens=R, min_new_tokens=1, eos_token_id=EOS,
        pad_token_id=EOS, per_row_rng=True,
    )
    init_fn = functools.partial(init_gpt_neo_cache, cfg)
    common = dict(
        apply_fn=apply_fn, init_cache_fn=init_fn, gen_config=gen,
        query_length=Q, vocab_size=VOCAB, num_slots=4, admit_width=2,
        harvest_width=2, block_size=4,
    )
    engines = {
        "mono": ContinuousBatchingEngine(**common),
        "chunked": ContinuousBatchingEngine(**common, prefill_chunk=4),
    }
    sampler = jax.jit(make_sampler(apply_fn, init_fn, gen, Q))
    ids, mask = _mixed_prompts(4, seed=6, lo=3, sort=False)
    key = jax.random.PRNGKey(3)
    fixed = sampler(
        params, jnp.asarray(ids), jnp.asarray(mask),
        make_row_keys(key, jnp.arange(4)),
    )
    want_tokens = np.asarray(fixed.tokens)
    for engine in engines.values():
        engine.start_phase(params, key)
        engine.submit(ids, mask)
        got = {}
        for group in engine.drive(4):
            for j, r in enumerate(group["rows"]):
                got[r] = np.asarray(group["tokens"])[j]
        for r in range(4):
            np.testing.assert_array_equal(got[r], want_tokens[r])


# ---------------------------- mesh variants ------------------------------ #


@pytest.mark.slow
def test_chunked_parity_on_mixed_mesh():
    """Nightly: chunked <-> monolithic parity through the TRAINER's
    engine construction path on the mixed fsdp×tp mesh — tokens/masks
    bitwise, logprobs/values at the established bf16 resolution (the
    same caveat as every engine parity pin on tp-sharded meshes)."""
    from trlx_tpu.analysis import harness
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    def build(rollout):
        cfg = harness.tiny_config_dict(
            "ppo", mesh={"dp": 2, "fsdp": 2, "tp": 2}
        )
        cfg["method"]["num_rollouts"] = 16
        cfg["method"]["chunk_size"] = 8
        cfg["train"]["batch_size"] = 8
        cfg["train"]["rollout"] = rollout
        cfg["method"]["gen_kwargs"]["min_new_tokens"] = 1
        return PPOTrainer(TRLConfig.from_dict(cfg))

    base = {
        "engine": "continuous", "slots": 16, "admit_width": 8,
        "harvest_width": 8, "block_size": 4, "per_row_rng": True,
    }
    mono_t = build(dict(base))
    chunk_t = build(dict(base, prefill_chunk=4))
    assert chunk_t.rollout_engine_obj.prefill_chunk > 0
    qlen = mono_t.query_length
    rng = np.random.default_rng(2)
    ids = rng.integers(1, 30, (16, qlen)).astype(np.int32)
    mask = np.ones((16, qlen), np.int32)
    for i in range(16):
        real = int(rng.integers(2, qlen + 1))
        mask[i, : qlen - real] = 0
        ids[i, : qlen - real] = 31
    rowsets = []
    for tr in (mono_t, chunk_t):
        tr.rng = jax.random.PRNGKey(42)
        tr.reset_rollout_phase()
        engine = tr.rollout_engine_obj
        engine.start_phase(tr.rollout_params(), tr.rollout_phase_key())
        engine.submit(ids, mask)
        got = {}
        for group in engine.drive(16):
            arrs = {
                k: np.asarray(group[k])
                for k in ("tokens", "response_mask", "logprobs", "values")
            }
            for j, r in enumerate(group["rows"]):
                got[r] = {k: v[j] for k, v in arrs.items()}
        rowsets.append(got)
    _assert_rows_equal(rowsets[0], rowsets[1], exact_fp=False)
