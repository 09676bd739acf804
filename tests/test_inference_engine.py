"""Continuous-batching rollout engine (trlx_tpu/inference/): paged-cache
units, slot lifecycle, and the fixed-vs-continuous parity contract.

The engine's correctness story is per-row determinism: under per-row RNG
(``fold_in(phase_key, draw_index)`` base keys, ``fold_in(row_key, t)``
per step) a row's tokens/logprobs/values depend only on its prompt, its
draw position, and the params — never on batch composition, admission
order, or slot assignment. The parity tests pin that BITWISE between
``rollout.engine: continuous`` (slot-admission decode over the paged
cache, recycled slots with rotated block tables) and the fixed-batch
sampler, both per-call and through a full streamed PPO phase.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.analysis import harness
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.inference import RolloutEngineConfig
from trlx_tpu.telemetry.health import without_timing
from trlx_tpu.ops.kv_cache import (
    choose_block_size,
    dense_write_read,
    identity_block_tables,
    init_paged_cache,
    kv_buffers,
    logical_view_index,
    paged_write_read,
    physical_positions,
    rotate_block_table,
)


DP_MESH = {"dp": -1, "fsdp": 1, "tp": 1}
ENGINE_ROLLOUT = {
    "engine": "continuous", "slots": 16, "admit_width": 8,
    "harvest_width": 8, "block_size": 4, "per_row_rng": True,
}


# ------------------------------ units --------------------------------- #


def test_choose_block_size():
    assert choose_block_size(112, 16) == 16
    assert choose_block_size(14, 4) == 2  # 4 does not divide 14
    assert choose_block_size(13, 8) == 1  # prime capacity
    assert choose_block_size(8, 64) == 8  # clamped to capacity
    with pytest.raises(ValueError):
        choose_block_size(0, 4)


def test_rollout_config_validation():
    with pytest.raises(ValueError, match="engine"):
        RolloutEngineConfig.from_dict({"engine": "vllm"})
    with pytest.raises(ValueError, match="Unknown train.rollout"):
        RolloutEngineConfig.from_dict({"engin": "fixed"})
    cfg = RolloutEngineConfig.from_dict({"engine": "continuous"})
    assert cfg.rows_per_row_rng  # continuous implies per-row RNG
    assert not RolloutEngineConfig.from_dict({}).rows_per_row_rng


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_cache_matches_linear(kv_dtype):
    """The paged cache's logical view holds the exact bits of the linear
    cache per logical position — through rotated block tables, per-row
    write positions, and the int8 quantized layout."""
    B, cap, H, Dh, L = 2, 12, 2, 4, 1
    rng = np.random.default_rng(0)
    lin = kv_buffers(L, B, cap, H, Dh, "bfloat16", kv_dtype)[0]
    paged = init_paged_cache(L, B, cap, H, Dh, "bfloat16", kv_dtype,
                             block_size=4)[0]
    tables = paged["block_tables"]
    tables = tables.at[1].set(rotate_block_table(tables[1], 2))
    paged = dict(paged, block_tables=tables)

    k = jnp.asarray(rng.normal(size=(B, 3, H, Dh)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, 3, H, Dh)), jnp.bfloat16)
    kl, vl, lin = dense_write_read(lin, k, v, 0, jnp.bfloat16)
    kp, vp, paged = paged_write_read(
        paged, k, v, jnp.asarray([0, 0]), jnp.bfloat16
    )
    np.testing.assert_array_equal(np.asarray(kl, np.float32),
                                  np.asarray(kp, np.float32))
    np.testing.assert_array_equal(np.asarray(vl, np.float32),
                                  np.asarray(vp, np.float32))
    k2 = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.bfloat16)
    v2 = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.bfloat16)
    kl2, _, _ = dense_write_read(lin, k2, v2, 3, jnp.bfloat16)
    kp2, _, _ = paged_write_read(
        paged, k2, v2, jnp.asarray([3, 3]), jnp.bfloat16
    )
    np.testing.assert_array_equal(np.asarray(kl2, np.float32),
                                  np.asarray(kp2, np.float32))


def test_paged_oob_writes_drop():
    """Position >= capacity is the engine's discard sentinel: the write
    must vanish, not clip into the last block."""
    B, cap, H, Dh = 2, 8, 1, 2
    paged = init_paged_cache(1, B, cap, H, Dh, "bfloat16", "bfloat16",
                             block_size=4)[0]
    ones = jnp.ones((B, 1, H, Dh), jnp.bfloat16)
    _, _, out = paged_write_read(paged, ones, ones, jnp.asarray([cap, 0]),
                                 jnp.bfloat16)
    assert np.asarray(out["k"], np.float32)[0].sum() == 0  # dropped
    assert np.asarray(out["k"], np.float32)[1].sum() != 0  # written


def test_block_table_indirection():
    """physical_positions / logical_view_index invert each other under an
    arbitrary table permutation."""
    B, nb, bs = 1, 4, 3
    cap = nb * bs
    table = jnp.asarray([[2, 0, 3, 1]], jnp.int32)
    pos = jnp.arange(cap)[None, :]
    phys = np.asarray(physical_positions(table, pos, cap))[0]
    view = np.asarray(logical_view_index(table, cap))[0]
    np.testing.assert_array_equal(phys, view)  # same mapping both ways
    assert sorted(phys.tolist()) == list(range(cap))  # a permutation
    base = identity_block_tables(B, nb)
    np.testing.assert_array_equal(
        np.asarray(physical_positions(base, pos, cap))[0], np.arange(cap)
    )


# --------------------------- engine builders --------------------------- #


def _engine_config(mesh, rollout):
    cfg = harness.tiny_config_dict("ppo", mesh=dict(mesh))
    cfg["method"]["num_rollouts"] = 16
    cfg["method"]["chunk_size"] = 8
    cfg["train"]["batch_size"] = 8
    cfg["train"]["rollout"] = dict(rollout)
    cfg["method"]["gen_kwargs"]["min_new_tokens"] = 1
    return TRLConfig.from_dict(cfg)


def _build_trainer(mesh, rollout):
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    return PPOTrainer(_engine_config(mesh, rollout))


_CACHE = {}


def _cached_trainer(name, mesh, rollout):
    if name not in _CACHE:
        _CACHE[name] = _build_trainer(mesh, rollout)
    return _CACHE[name]


def _prompts(n, q, seed=0, min_len=None):
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, 30, (n, q)).astype(np.int32)
    mask = np.ones((n, q), np.int32)
    if min_len is not None:
        # left-padded mixed lengths: row i keeps min_len..q real tokens
        for i in range(n):
            real = int(rng.integers(min_len, q + 1))
            mask[i, : q - real] = 0
            ids[i, : q - real] = 31  # pad id
    return ids, mask


# ------------------------- slot lifecycle ------------------------------ #


@pytest.mark.slow
def test_slot_lifecycle_overflow_and_drain():
    """More prompts than slot-turns available at once: the queue backs
    up, slots recycle as rows finish (mixed real lengths + max_length
    make finish times differ deterministically), and the phase drains to
    exactly the target with every row accounted for once. Nightly tier
    (builds a second engine for the max_length config); the tier-1
    canary is the drain/overflow accounting inside
    test_engine_matches_fixed_sampler_rows."""
    trainer = _cached_trainer("cont_dp", DP_MESH, ENGINE_ROLLOUT)
    import dataclasses

    engine = trainer.rollout_engine_obj
    # cap total length so longer prompts finish earlier (deterministic
    # staggered recycling without relying on sampled eos)
    gen = dataclasses.replace(trainer.gen_config, max_length=11)
    engine = type(engine)(
        apply_fn=engine._apply_fn,
        init_cache_fn=engine._init_cache_fn,
        gen_config=gen,
        query_length=trainer.query_length,
        vocab_size=trainer.model_config.vocab_size,
        num_slots=16,
        admit_width=8,
        harvest_width=8,
        block_size=4,
        mesh=trainer.mesh,
        param_shardings=trainer.param_shardings,
        with_values=True,
    )
    N, Q = 40, trainer.query_length  # 40 rows through 16 slots
    ids, mask = _prompts(N, Q, seed=3, min_len=3)
    trainer.reset_rollout_phase()
    engine.start_phase(trainer.rollout_params(), trainer.rollout_phase_key())
    rows = engine.submit(ids, mask)
    assert rows == list(range(N))
    assert engine.pending == N

    seen = {}
    for group in engine.drive(N):
        toks = np.asarray(group["tokens"])
        m = np.asarray(group["response_mask"])
        for j, r in enumerate(group["rows"]):
            assert r not in seen, "row harvested twice"
            seen[r] = (toks[j], m[j])
    assert set(seen) == set(range(N))
    # drain: nothing left in flight, stats account for every row
    assert engine.pending == 0
    st = engine.stats
    assert st.admitted == N and st.completed == N and st.recycles == N
    assert 0 < st.slot_util <= 1.0
    # max_length=11 with real lengths 3..8: every row's token budget is
    # 11 - n_real, so responses have differing lengths — recycling
    # actually happened at different steps
    lengths = {int(m.sum()) for _, m in seen.values()}
    assert len(lengths) > 1
    # queue overflow path: submitting more than the pool size never
    # admitted more than num_slots at once
    assert st.prefills >= N // 8


def test_engine_starvation_refuses():
    trainer = _cached_trainer("cont_dp", DP_MESH, ENGINE_ROLLOUT)
    engine = trainer.rollout_engine_obj
    trainer.reset_rollout_phase()
    engine.start_phase(trainer.rollout_params(), trainer.rollout_phase_key())
    with pytest.raises(ValueError, match="pending"):
        list(engine.drive(8))  # nothing submitted
    ids, mask = _prompts(8, trainer.query_length)
    engine.submit(ids, mask)
    with pytest.raises(ValueError, match="multiple"):
        list(engine.drive(3))  # not a harvest multiple


# ------------------------------ parity --------------------------------- #


PARITY_MESHES = [
    pytest.param(DP_MESH, id="dp"),
    pytest.param(
        {"dp": 2, "fsdp": 2, "tp": 2}, id="fsdp_tp",
        marks=pytest.mark.slow,
    ),
    pytest.param(
        {"dp": -1, "fsdp": 1, "tp": 1, "sp": 2}, id="sp",
        marks=pytest.mark.slow,
    ),
]


def _trainer_pair(mesh, mesh_id):
    fixed = _cached_trainer(
        f"fixed_{mesh_id}", mesh, {"engine": "fixed", "per_row_rng": True}
    )
    cont = _cached_trainer(f"cont_{mesh_id}", mesh, ENGINE_ROLLOUT)
    return fixed, cont


@pytest.mark.parametrize("mesh", PARITY_MESHES)
def test_engine_matches_fixed_sampler_rows(mesh):
    """Per-call parity: the same prompt set decoded through slots (with
    recycling + rotated block tables) and through the fixed batch yields
    bitwise-identical per-row tokens/mask/logprobs/values."""
    mesh_id = "dp" if mesh == DP_MESH else ("sp" if "sp" in mesh else "mix")
    fixed, cont = _trainer_pair(mesh, mesh_id)
    N, Q = 24, fixed.query_length
    ids, mask = _prompts(N, Q, seed=11, min_len=4)

    # pin both trainers' rng: the phase key must be the SAME single
    # split regardless of what earlier tests consumed
    fixed.rng = jax.random.PRNGKey(42)
    cont.rng = jax.random.PRNGKey(42)
    fixed.reset_rollout_phase()
    outs = [
        fixed.sample(jnp.asarray(ids[s:s + 8]), jnp.asarray(mask[s:s + 8]))
        for s in range(0, N, 8)
    ]
    want = {
        "tokens": np.concatenate([np.asarray(o.tokens) for o in outs]),
        "mask": np.concatenate([np.asarray(o.response_mask) for o in outs]),
        "logprobs": np.concatenate([np.asarray(o.logprobs) for o in outs]),
        "values": np.concatenate([np.asarray(o.values) for o in outs]),
    }

    # identical init (same seed/arch) is a parity precondition
    for a, b in zip(jax.tree_util.tree_leaves(fixed.state.params),
                    jax.tree_util.tree_leaves(cont.state.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))

    cont.reset_rollout_phase()
    engine = cont.rollout_engine_obj
    engine.start_phase(cont.rollout_params(), cont.rollout_phase_key())
    engine.submit(ids, mask)
    got = {}
    for group in engine.drive(N):
        arrs = {k: np.asarray(group[k]) for k in
                ("tokens", "response_mask", "logprobs", "values",
                 "query_tokens")}
        for j, r in enumerate(group["rows"]):
            assert r not in got, "row harvested twice"
            got[r] = {k: v[j] for k, v in arrs.items()}
    assert set(got) == set(range(N))
    # slot-lifecycle canary (full version: the nightly
    # test_slot_lifecycle_overflow_and_drain): 24 rows through 16 slots
    # means the queue overflowed the pool and slots recycled; the phase
    # drains completely and the stats account for every row once
    assert engine.pending == 0
    st = engine.stats
    assert st.admitted == N and st.completed == N and st.recycles == N
    assert 0 < st.slot_util <= 1.0
    for r in range(N):
        np.testing.assert_array_equal(got[r]["query_tokens"], ids[r])
        np.testing.assert_array_equal(got[r]["tokens"], want["tokens"][r])
        np.testing.assert_array_equal(got[r]["response_mask"],
                                      want["mask"][r])
        # logprobs/values: per-row math, but the forward's bf16 matmuls
        # are lowered per BATCH shape — XLA may reassociate reductions
        # when the slot pool width differs from the fixed chunk width
        # (observed on the tp-sharded mixed mesh), so parity here is
        # bf16-resolution. TOKENS above are bitwise — token identity is
        # the engine contract (selection consumes identical per-row
        # keys; finished emissions are deterministic pads).
        np.testing.assert_allclose(
            got[r]["logprobs"], want["logprobs"][r], rtol=0, atol=1e-2
        )
        np.testing.assert_allclose(
            got[r]["values"], want["values"][r], rtol=0, atol=2e-2
        )


def _run_streamed_phase(trainer, prompts, seed=3):
    from trlx_tpu.orchestrator.ppo_orchestrator import PPOOrchestrator
    from trlx_tpu.pipeline.prompt_pipeline import PromptPipeline

    pipe = PromptPipeline(prompts, trainer.query_length)
    orch = PPOOrchestrator(
        trainer, pipe,
        reward_fn=lambda samples, queries, response_gt: [
            float(len(s)) for s in samples
        ],
        chunk_size=8,
    )
    trainer.begin_streamed_phase(seed=seed)
    orch.make_experience(trainer.config.method.num_rollouts, 0)
    n_up, rows, kl_seq = trainer.finish_streamed_phase()
    full = trainer.buffer.full
    fetched = jax.device_get(
        (full.query_tokens, full.response_tokens, full.response_mask,
         full.logprobs, full.values)
    )
    q, t, m, lp, v = (np.asarray(x) for x in fetched)
    by_query = {
        tuple(q[i].tolist()): (t[i], m[i], lp[i], v[i])
        for i in range(len(q))
    }
    orch.close()
    return n_up, by_query


@pytest.mark.slow
@pytest.mark.parametrize("mesh", PARITY_MESHES)
def test_full_streamed_phase_parity(mesh):
    """Acceptance pin: with rollout.engine continuous, a full streamed
    PPO phase (epoch-1 dispatch through the landing hook included)
    produces per-row token-identical rollouts to the fixed-batch sampler
    on the same prompt set.

    Nightly tier since PR 11 (it was the heaviest remaining tier-1
    call at 14.3 s; ROADMAP tier-1 budget note). The tier-1 canaries:
    test_engine_matches_fixed_sampler_rows[dp] pins per-row
    engine-vs-fixed token parity + the slot-lifecycle accounting, and
    tests/test_async_rl.py::test_async_staleness0_bitwise_parity_canary
    pins the full engine-collected streamed phase (landing hook,
    version-tagged store, epoch-1 dispatch, residual epochs) BITWISE
    against the serial same-plan run — a strict superset of the
    phase-integration surface this test exercises."""
    mesh_id = "dp" if mesh == DP_MESH else ("sp" if "sp" in mesh else "mix")
    fixed, cont = _trainer_pair(mesh, mesh_id)
    rng = np.random.default_rng(21)
    prompts = [list(rng.integers(1, 30, 8)) for _ in range(24)]

    fixed.rng = jax.random.PRNGKey(77)
    cont.rng = jax.random.PRNGKey(77)
    n_f, rows_f = _run_streamed_phase(fixed, prompts)
    n_c, rows_c = _run_streamed_phase(cont, prompts)
    assert n_f == n_c
    assert set(rows_f) == set(rows_c)
    for key in rows_f:
        (t_f, m_f, lp_f, v_f), (t_c, m_c, lp_c, v_c) = rows_f[key], rows_c[key]
        np.testing.assert_array_equal(t_f, t_c)
        np.testing.assert_array_equal(m_f, m_c)
        # batch-shape-dependent bf16 matmul lowering: logprobs/values
        # pin at bf16 resolution (see test_engine_matches_fixed_sampler_rows)
        np.testing.assert_allclose(lp_f, lp_c, rtol=0, atol=1e-2)
        np.testing.assert_allclose(v_f, v_c, rtol=0, atol=2e-2)


def test_per_row_rng_is_admission_order_invariant():
    """The root contract: a row's tokens depend on its draw index, not
    its chunk — one 16-wide call and two 8-wide calls agree row-by-row."""
    fixed, _ = _trainer_pair(DP_MESH, "dp")
    N, Q = 16, fixed.query_length
    ids, mask = _prompts(N, Q, seed=5, min_len=4)
    fixed.rng = jax.random.PRNGKey(9)
    fixed.reset_rollout_phase()
    whole = fixed.sample(jnp.asarray(ids), jnp.asarray(mask))
    # same phase key, chunked draw
    fixed.rng = jax.random.PRNGKey(9)
    fixed.reset_rollout_phase()
    halves = [
        fixed.sample(jnp.asarray(ids[s:s + 8]), jnp.asarray(mask[s:s + 8]))
        for s in range(0, N, 8)
    ]
    half_toks = np.concatenate([np.asarray(h.tokens) for h in halves])
    np.testing.assert_array_equal(np.asarray(whole.tokens), half_toks)


# ----------------- which read each engine program takes ---------------- #


@functools.lru_cache(maxsize=None)
def _lowered_engine_programs():
    """(decode_step, prefill) of the tier-1 engine, lowered once from
    shapes (a new ``jax.jit`` each: a cached trace would count nothing),
    and what one traced call site a layer added to
    ``attention/decode_path``."""
    from trlx_tpu.telemetry import get_metrics

    trainer = _cached_trainer("cont_dp", DP_MESH, ENGINE_ROLLOUT)
    engine = trainer.rollout_engine_obj
    sds = harness._sds
    params = sds(trainer.rollout_params())
    state = sds(engine.init_state())
    A, Q = engine.admit_width, engine.Q

    def counts():
        return {
            path: get_metrics().counter(
                "attention/decode_path{path=%s}" % path
            ).value
            for path in ("fused", "paged", "paged_rows", "generic")
        }

    def lowered(jitted, *args):
        before = counts()
        out = jax.jit(jitted.__wrapped__).lower(*args)
        after = counts()
        return out, {p: after[p] - before[p] for p in after}

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    decode = lowered(engine.decode_step_jit, params, state)
    prefill = lowered(
        engine.prefill_jit, params, state, i32(A), i32(A, Q), i32(A, Q),
        i32(A), i32(A), jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    n_layer = len(state.cache)
    return engine, n_layer, decode, prefill


def test_decode_step_reads_the_pool_as_stored_and_prefill_its_rows_of_it():
    """``attention/decode_path``: every layer of a traced ``decode_step``
    takes the paged read, every layer of the admission prefill addresses
    its group's rows inside the whole pool (``paged_rows``), and neither
    the fixed sampler's."""
    _, n_layer, (_, decode), (_, prefill) = _lowered_engine_programs()
    assert decode == {"fused": 0, "paged": n_layer, "paged_rows": 0, "generic": 0}
    assert prefill == {"fused": 0, "paged": 0, "paged_rows": n_layer, "generic": 0}


def test_decode_step_holds_no_copy_of_a_pool():
    """What made the serving decode step 43 ms on the v5e (PERF.md §6, PR
    28): a gather of each layer's whole pool into logical order, which the
    TPU compiler then converted to float32, whole, for a one-row query. The
    lowered ``decode_step`` holds no gather that permutes a pool (same
    shape in and out) and keeps its one scatter of rows a pool. The
    pattern does find a gather where there is one: the admission prefill
    attends over the logical view of its group's rows, gathered from the
    whole pool, K and V a layer (and since PR 42 takes no other:
    tests/test_admission_in_place.py)."""
    import re

    engine, n_layer, (decode, _), (prefill, _) = _lowered_engine_programs()
    layer = engine.init_state().cache[0]
    B, cap, H, Dh = layer["k"].shape
    dt = {"bfloat16": "bf16", "float32": "f32"}[str(layer["k"].dtype)]

    def pool_shapes(n_slots):
        """A pool of ``n_slots`` slots as the indexing lowers it."""
        return (f"{n_slots}x{cap}x{H}x{Dh}x{dt}", f"{n_slots * cap}x{H}x{Dh}x{dt}")

    def permuting_gathers(text, n_slots, n_rows=None):
        return [
            m for m in re.finditer(
                r'"stablehlo\.gather"[^\n]*: \(tensor<([0-9x]+\w+)>, [^\n]*'
                r"-> tensor<([0-9x]+\w+)>", text)
            if m.group(1) in pool_shapes(n_slots)
            and m.group(2) in pool_shapes(n_rows or n_slots)
        ]

    def pool_scatters(text, n_slots):
        # a scatter's type follows its update region
        return [
            m for m in re.finditer(
                r'"stablehlo\.scatter".*?\}\) : \(tensor<([0-9x]+\w+)>', text, re.S)
            if m.group(1) in pool_shapes(n_slots)
        ]

    decode_text = decode.as_text()
    assert not permuting_gathers(decode_text, B)
    assert len(pool_scatters(decode_text, B)) == 2 * n_layer
    assert len(permuting_gathers(prefill.as_text(), B, engine.admit_width)) == 2 * n_layer


# --------------------------- config refusals --------------------------- #


def test_continuous_refuses_grpo():
    cfg = harness.tiny_config_dict("grpo")
    cfg["train"]["rollout"] = {"engine": "continuous"}
    from trlx_tpu.trainer.grpo_trainer import GRPOTrainer

    with pytest.raises(NotImplementedError, match="grouped"):
        GRPOTrainer(TRLConfig.from_dict(cfg))


def test_continuous_refuses_seq2seq():
    cfg = harness.tiny_config_dict("seq2seq")
    cfg["train"]["rollout"] = {"engine": "continuous"}
    from trlx_tpu.trainer.seq2seq_ppo_trainer import Seq2SeqPPOTrainer

    with pytest.raises(NotImplementedError, match="continuous"):
        Seq2SeqPPOTrainer(TRLConfig.from_dict(cfg))


def test_continuous_refuses_ilql():
    cfg = harness.tiny_config_dict("ilql")
    cfg["train"]["rollout"] = {"engine": "continuous"}
    from trlx_tpu.trainer.ilql_trainer import ILQLTrainer

    with pytest.raises(NotImplementedError, match="ILQL"):
        ILQLTrainer(TRLConfig.from_dict(cfg))


# ------------------------------ server --------------------------------- #


@pytest.mark.slow
def test_inference_server_submit_poll(tmp_path):
    """Serving path: checkpoint round-trip, submit/poll/wait, overflow
    (more requests than slots), zero health events on a clean policy,
    and the too-long-prompt refusal. Nightly tier — every PR's CI runs
    the same path via `python -m trlx_tpu.inference --smoke`
    (serving-smoke job)."""
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer
    from trlx_tpu.utils.checkpoint import save_checkpoint

    cfg = harness.tiny_config_dict("ppo", mesh=DP_MESH)
    trainer = PPOTrainer(TRLConfig.from_dict(cfg))
    ckpt = str(tmp_path / "ckpt")
    save_checkpoint(ckpt, trainer.state, metadata={}, step=1)

    from trlx_tpu.inference.server import InferenceServer

    scfg = harness.tiny_config_dict("ppo", mesh=DP_MESH)
    scfg["train"]["rollout"] = {
        "slots": 8, "admit_width": 8, "harvest_width": 8, "block_size": 4,
    }
    server = InferenceServer(TRLConfig.from_dict(scfg), checkpoint_dir=ckpt)
    # served params are the checkpoint's params
    for a, b in zip(jax.tree_util.tree_leaves(server.params),
                    jax.tree_util.tree_leaves(trainer.state.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))

    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(1, 30, int(rng.integers(2, 8))))
               for _ in range(13)]  # > slots, not a harvest multiple
    rids = server.submit(prompts)
    assert server.poll(rids[0]) is None  # not driven yet
    results = server.wait(rids)
    assert set(results) == set(rids)
    for out in results.values():
        assert out["length"] >= 1
        assert len(out["tokens"]) == out["length"]
    assert without_timing(server.health_events) == []
    assert server.stats()["engine/completed"] >= len(rids)

    with pytest.raises(ValueError, match="seq_length"):
        server.submit([list(range(1, server.query_length + 5))])
    with pytest.raises(ValueError, match="empty"):
        server.submit([[]])
