"""Streamed collect→train phase overlap (docs/async_pipeline.md).

The contract under test: the overlapped schedule — epoch-1 minibatch
updates dispatched while rollout chunks are still decoding against the
frozen behavior snapshot — is BITWISE-identical to running the same
:class:`~trlx_tpu.pipeline.ppo_buffer.StreamPlan` serially (collect
everything, then update). Final params, the KL-coefficient sequence, and
every per-update stat must match exactly, on every mesh of the CPU
matrix including the mixed fsdp×tp mesh that historically NaN'd.

Also: unit tests for the streaming buffer (partial-chunk arrival,
minibatch-ready accounting, capacity overflow, group-contiguous rows)
and the up-front stream plan.
"""

import os

import numpy as np
import pytest

os.environ.setdefault("WANDB_DISABLED", "1")


# --------------------------- plan unit tests --------------------------- #


def test_stream_plan_shapes_and_permutations():
    from trlx_tpu.pipeline.ppo_buffer import make_stream_plan

    plan = make_stream_plan(total=32, batch_size=8, ppo_epochs=3, seed=5)
    assert plan.n_minibatches == 4
    assert plan.n_updates == 12
    assert plan.epoch1.shape == (4, 8)
    assert plan.residual.shape == (8, 8)
    # epoch-1 minibatch k IS arrival block k — the minibatch-ready
    # invariant (randomness comes from the shuffled prompt draw)
    for k in range(4):
        np.testing.assert_array_equal(
            plan.epoch1[k], np.arange(k * 8, (k + 1) * 8)
        )
    # every residual epoch is a full global permutation
    res = plan.residual.reshape(2, 32)
    for epoch_rows in res:
        assert sorted(epoch_rows) == list(range(32))
    # deterministic by seed; residual permutations vary with it
    again = make_stream_plan(total=32, batch_size=8, ppo_epochs=3, seed=5)
    np.testing.assert_array_equal(plan.epoch1, again.epoch1)
    np.testing.assert_array_equal(plan.residual, again.residual)
    other = make_stream_plan(total=32, batch_size=8, ppo_epochs=3, seed=6)
    assert not np.array_equal(plan.residual, other.residual)


def test_stream_plan_ready_accounting():
    from trlx_tpu.pipeline.ppo_buffer import make_stream_plan

    plan = make_stream_plan(total=24, batch_size=8, ppo_epochs=1, seed=0)
    assert plan.residual.size == 0
    assert plan.rows_needed(0) == 8
    assert plan.rows_needed(2) == 24
    assert not plan.ready(0, landed=7)
    assert plan.ready(0, landed=8)
    assert not plan.ready(2, landed=23)
    assert plan.ready(2, landed=24)
    # a non-dividing total schedules only the floor minibatches
    plan = make_stream_plan(total=20, batch_size=8, ppo_epochs=2, seed=0)
    assert plan.n_minibatches == 2 and plan.total == 16
    with pytest.raises(ValueError, match="at least one minibatch"):
        make_stream_plan(total=4, batch_size=8, ppo_epochs=1)


# ------------------------ streaming buffer units ------------------------ #


def _chunk(rows, Q=2, R=3, base=0):
    """A PPORolloutBatch whose every array encodes the GLOBAL row id, so
    gathers can be checked for row integrity."""
    import jax.numpy as jnp

    from trlx_tpu.data.ppo_types import PPORolloutBatch

    ids = np.arange(base, base + rows, dtype=np.int32)
    return PPORolloutBatch(
        query_tokens=jnp.asarray(np.tile(ids[:, None], (1, Q))),
        query_mask=jnp.ones((rows, Q), jnp.int32),
        response_tokens=jnp.asarray(np.tile(ids[:, None], (1, R))),
        response_mask=jnp.ones((rows, R), jnp.int32),
        logprobs=jnp.asarray(np.tile(ids[:, None], (1, R)), jnp.float32),
        values=jnp.asarray(np.tile(ids[:, None], (1, R)), jnp.float32) * 0.5,
        rewards=jnp.asarray(np.tile(ids[:, None], (1, R)), jnp.float32) * 2.0,
    )


def test_stream_buffer_partial_arrival_and_gather():
    from trlx_tpu.pipeline.ppo_buffer import PPORolloutBuffer

    buf = PPORolloutBuffer()
    buf.begin_stream(12)
    assert len(buf) == 0
    # uneven chunk sizes, in arrival order
    buf.push(_chunk(4, base=0))
    assert len(buf) == 4
    # rows that landed gather correctly mid-stream
    mb = buf.gather(np.asarray([2, 0, 3]))
    np.testing.assert_array_equal(
        np.asarray(mb.query_tokens)[:, 0], [2, 0, 3]
    )
    # rows that have NOT landed refuse loudly
    with pytest.raises(ValueError, match="landed"):
        buf.gather(np.asarray([5]))
    buf.push(_chunk(2, base=4))
    buf.push(_chunk(6, base=6))
    assert len(buf) == 12
    # full buffer is the identity layout (row i holds id i), bitwise
    full = buf.full
    np.testing.assert_array_equal(
        np.asarray(full.query_tokens)[:, 0], np.arange(12)
    )
    np.testing.assert_array_equal(
        np.asarray(full.rewards)[:, 0], np.arange(12) * 2.0
    )
    # stacked gather (fused residual input shape): [n, B] -> [n, B, ...]
    stacked = buf.gather(np.asarray([[0, 5], [11, 6]]))
    assert stacked.query_tokens.shape[:2] == (2, 2)
    np.testing.assert_array_equal(
        np.asarray(stacked.response_tokens)[:, :, 0], [[0, 5], [11, 6]]
    )


def test_stream_buffer_overflow_grows():
    from trlx_tpu.pipeline.ppo_buffer import PPORolloutBuffer

    buf = PPORolloutBuffer()
    buf.begin_stream(8)  # planned 8, but a non-dividing final chunk lands
    buf.push(_chunk(5, base=0))
    buf.push(_chunk(5, base=5))  # overshoots the planned capacity
    assert len(buf) == 10
    np.testing.assert_array_equal(
        np.asarray(buf.full.query_tokens)[:, 0], np.arange(10)
    )
    # a caller-fixed pass size caps the stacked pass below the
    # over-collected buffer's natural 10 // 2 = 5 minibatches, keeping
    # learn()'s step accounting honest on every path
    mbs = buf.stacked_minibatches(2, shuffle=False, n_minibatches=4)
    assert mbs.query_tokens.shape[0] == 4


def test_stream_buffer_state_transitions():
    from trlx_tpu.pipeline.ppo_buffer import PPORolloutBuffer

    buf = PPORolloutBuffer()
    buf.push(_chunk(4))
    with pytest.raises(ValueError, match="non-empty"):
        buf.begin_stream(8)
    buf.clear_history()
    buf.begin_stream(8)
    assert buf.streaming
    buf.push(_chunk(8))
    # landed == capacity: full returns the store itself (no copy slice)
    assert buf.full.batch_size == 8
    buf.clear_history()
    assert not buf.streaming and len(buf) == 0
    # chunk mode still works after a stream
    buf.push(_chunk(4))
    assert len(buf) == 4 and not buf.streaming


def test_stream_buffer_group_expanded_rows_stay_contiguous():
    """Grouped trainers (GRPO / group_size > 1) push chunks whose rows are
    G-contiguous same-prompt groups; the stream store must preserve that
    layout exactly (group whitening happened upstream, but downstream
    debugging relies on row order)."""
    import jax.numpy as jnp

    from trlx_tpu.data.ppo_types import PPORolloutBatch
    from trlx_tpu.pipeline.ppo_buffer import PPORolloutBuffer

    G, prompts = 3, 4
    rows = G * prompts
    group_ids = np.repeat(np.arange(prompts, dtype=np.int32), G)

    def grouped_chunk(sl):
        n = sl.stop - sl.start
        gid = group_ids[sl]
        return PPORolloutBatch(
            query_tokens=jnp.asarray(np.tile(gid[:, None], (1, 2))),
            query_mask=jnp.ones((n, 2), jnp.int32),
            response_tokens=jnp.zeros((n, 3), jnp.int32),
            response_mask=jnp.ones((n, 3), jnp.int32),
            logprobs=jnp.zeros((n, 3), jnp.float32),
            values=jnp.zeros((n, 3), jnp.float32),
            rewards=jnp.asarray(np.tile(gid[:, None], (1, 3)), jnp.float32),
        )

    buf = PPORolloutBuffer()
    buf.begin_stream(rows)
    buf.push(grouped_chunk(slice(0, 6)))   # two whole groups per chunk
    buf.push(grouped_chunk(slice(6, 12)))
    got = np.asarray(buf.full.query_tokens)[:, 0]
    np.testing.assert_array_equal(got, group_ids)


# ------------------- overlapped vs serial bitwise parity ----------------- #


def _parity_config(mesh):
    from trlx_tpu.data.configs import TRLConfig

    return TRLConfig.from_dict(
        {
            "model": {
                "model_type": "gpt2",
                "model_arch": {
                    "vocab_size": 12,
                    "n_positions": 16,
                    "n_embd": 32,
                    "n_layer": 2,
                    "n_head": 2,
                },
            },
            "train": {
                "seq_length": 2,
                "batch_size": 8,
                "epochs": 1,
                "total_steps": 8,
                "eval_interval": 1000,
                "checkpoint_interval": 10000,
                "mesh": dict(mesh),
                "dtype": "float32",
            },
            "method": {
                "name": "PPOConfig",
                "num_rollouts": 24,
                "chunk_size": 8,
                "ppo_epochs": 2,
                "init_kl_coef": 0.02,
                "target": 6.0,
                "gen_kwargs": {
                    "max_new_tokens": 6,
                    "do_sample": True,
                    "eos_token_id": 10,
                    "pad_token_id": 11,
                },
            },
        }
    )


def _reward_fn(samples, queries, response_gt=None):
    # deterministic pure function of the sampled text
    return [
        (sum(int(tok) for tok in s.split()) % 7) / 3.0 - 1.0 if s else -1.0
        for s in samples
    ]


def _run_phase(trainer, init_state, overlap):
    """One full streamed phase from a fixed initial state. The trainer is
    REUSED across calls (a second construction recompiles every program —
    pure overhead in the tier-1 budget): host state that a phase mutates
    (train state, rng, KL state, buffer, and the orchestrator's stateful
    prompt loader / running reward moments) is reset to identical values,
    so both calls consume bitwise-identical inputs."""
    from trlx_tpu.pipeline.prompt_pipeline import PromptPipeline
    from trlx_tpu.utils import set_seed
    from trlx_tpu.utils.loading import get_orchestrator

    import jax

    config = trainer.config
    trainer.state = jax.device_put(init_state, trainer.state_shardings)
    trainer.rng = set_seed(config.train.seed)
    trainer.kl_coef = float(config.method.init_kl_coef)
    trainer.mean_kl = 0.0
    trainer.buffer.clear_history()
    rng = np.random.default_rng(3)
    prompts = [[int(x) for x in rng.integers(1, 10, size=2)] for _ in range(64)]
    pipeline = PromptPipeline(prompts, config.train.seq_length)
    # fresh orchestrator per call: its infinite prompt loader and running
    # reward moments are phase state too
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=_reward_fn,
        chunk_size=config.method.chunk_size,
    )
    trainer.begin_streamed_phase(seed=11, overlap=overlap)
    # while the phase is active, every rollout consumes the frozen
    # behavior snapshot, not the mutating masters
    assert trainer.rollout_params() is trainer._behavior_params
    orch.make_experience(config.method.num_rollouts, 0)
    if overlap:
        # the arrival-block plan must have dispatched epoch-1 work
        # before collection finished
        assert trainer._stream.next_mb >= 1
    n_updates, rows, kl_seq = trainer.finish_streamed_phase()
    assert trainer._behavior_params is None and trainer._stream is None
    params = jax.device_get(trainer.state.params)
    return params, rows, kl_seq, n_updates


MESHES = [
    pytest.param({"dp": -1, "fsdp": 1, "tp": 1}, id="dp"),
    pytest.param(
        {"dp": -1, "fsdp": 2, "tp": 1}, id="fsdp", marks=pytest.mark.slow
    ),
    pytest.param(
        {"dp": -1, "fsdp": 1, "tp": 2}, id="tp", marks=pytest.mark.slow
    ),
    pytest.param(
        {"dp": 2, "fsdp": 2, "tp": 2}, id="fsdp_tp", marks=pytest.mark.slow
    ),
]


@pytest.mark.parametrize("mesh", MESHES)
def test_overlapped_matches_serial_bitwise(mesh):
    """Same plan, same seed: the overlapped dispatch schedule and the
    serial one must produce bit-identical final params, KL sequence, and
    per-update stats — the overlap is a dispatch reordering, nothing
    else. Covers the mixed fsdp×tp mesh that previously NaN'd via the
    buffer-concat SPMD bug (the streaming store must not reintroduce
    it)."""
    import jax

    from trlx_tpu.utils.loading import get_trainer

    config = _parity_config(mesh)
    trainer = get_trainer("PPOTrainer")(config, reward_fn=_reward_fn)
    init_state = jax.device_get(trainer.state)

    p_ov, r_ov, kl_ov, n_ov = _run_phase(trainer, init_state, overlap=True)
    p_se, r_se, kl_se, n_se = _run_phase(trainer, init_state, overlap=False)
    assert n_ov == n_se == 6  # 3 minibatches x 2 ppo epochs
    assert kl_ov == kl_se
    for a, b in zip(
        jax.tree_util.tree_leaves(p_ov),
        jax.tree_util.tree_leaves(p_se),
        strict=True,
    ):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all()
        np.testing.assert_array_equal(a, b)
    assert set(r_ov) == set(r_se)
    for key in r_ov:
        np.testing.assert_array_equal(r_ov[key], r_se[key], err_msg=key)


@pytest.mark.slow
def test_grpo_streamed_parity_group_expanded():
    """The streamed phase composes with grouped rollouts: the orchestrator
    expands each prompt into group_size contiguous rollouts, the stream
    plan's blocks stay arrival-aligned, and overlapped == serial holds
    bitwise."""
    import jax

    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.pipeline.prompt_pipeline import PromptPipeline
    from trlx_tpu.utils.loading import get_orchestrator, get_trainer

    def run(overlap):
        config = TRLConfig.from_dict(
            {
                "model": {
                    "model_type": "gpt2",
                    "model_arch": {
                        "vocab_size": 12, "n_positions": 16, "n_embd": 32,
                        "n_layer": 2, "n_head": 2,
                    },
                },
                "train": {
                    "seq_length": 2, "batch_size": 8, "epochs": 1,
                    "total_steps": 8, "eval_interval": 1000,
                    "checkpoint_interval": 10000,
                    "mesh": {"dp": -1, "fsdp": 1, "tp": 1},
                    "dtype": "float32",
                },
                "method": {
                    "name": "GRPOConfig", "group_size": 4, "vf_coef": 0.0,
                    "num_rollouts": 16, "chunk_size": 8, "ppo_epochs": 2,
                    "gen_kwargs": {
                        "max_new_tokens": 6, "do_sample": True,
                        "eos_token_id": 10, "pad_token_id": 11,
                    },
                },
            }
        )
        trainer = get_trainer("GRPOTrainer")(config, reward_fn=_reward_fn)
        rng = np.random.default_rng(9)
        prompts = [
            [int(x) for x in rng.integers(1, 10, size=2)] for _ in range(32)
        ]
        pipeline = PromptPipeline(prompts, config.train.seq_length)
        orch = get_orchestrator("PPOOrchestrator")(
            trainer, pipeline, reward_fn=_reward_fn, chunk_size=8
        )
        trainer.begin_streamed_phase(seed=2, overlap=overlap)
        orch.make_experience(config.method.num_rollouts, 0)
        _, rows, kl_seq = trainer.finish_streamed_phase()
        return jax.device_get(trainer.state.params), rows, kl_seq

    p_ov, r_ov, kl_ov = run(True)
    p_se, r_se, kl_se = run(False)
    assert kl_ov == kl_se
    for a, b in zip(
        jax.tree_util.tree_leaves(p_ov),
        jax.tree_util.tree_leaves(p_se),
        strict=True,
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for key in r_ov:
        np.testing.assert_array_equal(r_ov[key], r_se[key], err_msg=key)


@pytest.mark.slow
def test_health_on_matches_health_off_bitwise_dp():
    """train.health.enabled must not perturb training: the health build
    adds extra stats OUTPUTS to the jitted step (entropy at ent_coef=0,
    log-ratio extremes, explained variance, reward quantiles) but the
    loss/grad arithmetic is untouched — final params and the KL
    sequence of a full streamed phase pin bitwise against the
    health-off build from the same initial state, on the dp mesh.

    Nightly tier (two trainer builds, ~30 s of compile; ROADMAP tier-1
    budget note); the tier-1 canary is
    tests/test_health.py::test_health_on_step_parity_canary, which pins
    the same params-bitwise contract at the single-train-step level."""
    import jax

    from trlx_tpu.utils.loading import get_trainer

    mesh = {"dp": -1, "fsdp": 1, "tp": 1}
    config_off = _parity_config(mesh)
    trainer_off = get_trainer("PPOTrainer")(config_off, reward_fn=_reward_fn)
    init_state = jax.device_get(trainer_off.state)
    p_off, r_off, kl_off, n_off = _run_phase(
        trainer_off, init_state, overlap=True
    )
    assert not any(k.startswith("health/") for k in r_off)

    config_on = _parity_config(mesh)
    config_on.train.health = {"enabled": True}
    trainer_on = get_trainer("PPOTrainer")(config_on, reward_fn=_reward_fn)
    # same arch + same seed: identical init — but pin the states anyway
    # (the parity must hold from literally the same bytes)
    p_on, r_on, kl_on, n_on = _run_phase(trainer_on, init_state, overlap=True)

    assert n_on == n_off and kl_on == kl_off
    for a, b in zip(
        jax.tree_util.tree_leaves(p_on),
        jax.tree_util.tree_leaves(p_off),
        strict=True,
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # every shared stat row is bitwise-identical too; the health build
    # additionally carries the fused health scalars in the same rows.
    # (losses/entropy is the one deliberate stats difference: 0 with the
    # bonus off, the real measured entropy once health computes it —
    # training itself is pinned by the params/kl asserts above.)
    for key in r_off:
        if key == "losses/entropy":
            continue
        np.testing.assert_array_equal(r_on[key], r_off[key], err_msg=key)
    assert (np.asarray(r_on["losses/entropy"]) > 0).all()
    for key in (
        "health/entropy",
        "health/log_ratio_max",
        "health/log_ratio_min",
        "health/value_explained_var",
        "health/reward_std",
        "health/reward_q50",
    ):
        assert key in r_on, key
        assert np.isfinite(r_on[key]).all(), key
    # the detectors watched every update row of the phase and stayed
    # quiet on a healthy run
    monitor = trainer_on.health_monitor
    assert monitor is not None
    assert monitor.latest["health/entropy"] > 0.0
    assert monitor.events == []


# ----------------------- eligibility / fallbacks ----------------------- #


def test_stream_eligibility_rules():
    """_stream_eligible must refuse (falling back to the legacy paths)
    when: overlap disabled, no orchestrator, a mid-pass eval/checkpoint
    boundary, the total_steps cutoff, a profiler trace, or fewer rollouts
    than one minibatch. Pure host logic — no compile."""
    from trlx_tpu.utils.loading import get_trainer

    config = _parity_config({"dp": -1, "fsdp": 1, "tp": 1})
    # smallest constructible arch — this test never dispatches a program
    config.model.model_arch.update(n_embd=8, n_layer=1, n_head=1)
    trainer = get_trainer("PPOTrainer")(config, reward_fn=_reward_fn)
    # no orchestrator attached yet
    assert not trainer._stream_eligible(0)
    trainer.orch = object()
    # eligible pass: 3 mb x 2 epochs = 6 steps, no interior boundary
    assert trainer._stream_eligible(0)
    # total_steps cutoff strictly inside the pass
    assert not trainer._stream_eligible(4)
    # overlap disabled
    trainer.config.train.phase_overlap = False
    assert not trainer._stream_eligible(0)
    trainer.config.train.phase_overlap = True
    # interior eval boundary ON a minibatch boundary (pass = 3 mb x 2
    # epochs; boundaries at steps 2 and 4)
    trainer.config.train.eval_interval = 2
    assert not trainer._stream_eligible(0)
    # an interval multiple at a MID-minibatch step (3, 5) must NOT
    # disable streaming: no path can ever evaluate there anyway
    trainer.config.train.eval_interval = 3
    assert trainer._stream_eligible(0)
    trainer.config.train.eval_interval = 1000
    # interior checkpoint boundary (step 4)
    trainer.config.train.checkpoint_interval = 4
    assert not trainer._stream_eligible(0)
    trainer.config.train.checkpoint_interval = 10000
    # a profiler window never changes the schedule it measures
    trainer.config.train.profile_dir = "/tmp/never"
    assert trainer._stream_eligible(0)
    trainer.config.train.profile_dir = None
    # fewer rollouts than one minibatch
    trainer.config.method.num_rollouts = 4
    assert not trainer._stream_eligible(0)

    # error recovery: a failed collection must not wedge the trainer on
    # the stale plan — abort clears stream + snapshot + buffer, and a
    # fresh phase can begin
    trainer.config.method.num_rollouts = 24
    trainer.begin_streamed_phase(seed=0)
    with pytest.raises(RuntimeError, match="already active"):
        trainer.begin_streamed_phase(seed=1)
    trainer.abort_streamed_phase()
    assert trainer._stream is None and trainer._behavior_params is None
    assert len(trainer.buffer) == 0 and not trainer.buffer.streaming
    trainer.begin_streamed_phase(seed=1)
    trainer.abort_streamed_phase()


def test_background_rollout_writer_drains_and_surfaces_errors(tmp_path):
    from trlx_tpu.utils.async_writer import BackgroundJSONLWriter

    w = BackgroundJSONLWriter(maxsize=4)
    path = str(tmp_path / "rollouts.jsonl")
    for i in range(10):
        w.submit(path, [{"i": i, "s": "x" * 8}])
    w.flush()
    import json

    with open(path) as f:
        rows = [json.loads(line) for line in f]
    assert [r["i"] for r in rows] == list(range(10))
    # a failing path surfaces at flush, wrapped with context
    w.submit(str(tmp_path / "no_dir" / "x.jsonl"), [{"i": 0}])
    with pytest.raises(RuntimeError, match="background rollout writer"):
        w.flush()
    # reraise=False swallows for now (the orchestrator's finally path when
    # another exception is already propagating) — but the error stays
    # pending and surfaces at the next reraising flush/close, so a crash
    # can't permanently eat a disk failure
    w.submit(str(tmp_path / "no_dir" / "x.jsonl"), [{"i": 1}])
    w.flush(reraise=False)
    with pytest.raises(RuntimeError, match="background rollout writer"):
        w.close()
    w.close(reraise=False)


def test_rollout_writer_drain_on_exception_path_surfaces_at_close(tmp_path):
    # the orchestrator's `finally` drains with reraise=False when another
    # exception is already propagating; a write error hit during that
    # final drain must not be swallowed forever — it re-raises at close,
    # and a RAISING close still stops the writer thread (no leak)
    import json

    from trlx_tpu.utils.async_writer import BackgroundJSONLWriter

    w = BackgroundJSONLWriter(maxsize=4)
    good = str(tmp_path / "good.jsonl")
    w.submit(good, [{"i": 0}])
    w.submit(str(tmp_path / "no_dir" / "x.jsonl"), [{"i": 1}])
    w.flush(reraise=False)  # drain-on-exception: queue fully drained ...
    assert w.pending == 0  # ... and already empty when close runs
    with pytest.raises(RuntimeError, match="background rollout writer"):
        w.close()
    assert w._thread is None  # raising close still shut the thread down
    with open(good) as f:
        assert [json.loads(line)["i"] for line in f] == [0]
    with pytest.raises(RuntimeError, match="closed"):
        w.submit(good, [{"i": 2}])


def test_orchestrator_close_closes_rollout_writer(tmp_path):
    # PPOOrchestrator.close must surface a swallowed writer error at the
    # end of a run (api.train calls it after learn())
    from trlx_tpu.orchestrator import Orchestrator
    from trlx_tpu.orchestrator.ppo_orchestrator import PPOOrchestrator
    from trlx_tpu.utils.async_writer import BackgroundJSONLWriter

    orch = PPOOrchestrator.__new__(PPOOrchestrator)
    orch._rollout_writer = BackgroundJSONLWriter(maxsize=4)
    orch._rollout_writer.submit(str(tmp_path / "no_dir" / "x.jsonl"), [{}])
    orch._rollout_writer.flush(reraise=False)
    with pytest.raises(RuntimeError, match="background rollout writer"):
        orch.close()
    assert orch._rollout_writer is None
    orch.close()  # idempotent
    # the base class close is a safe no-op for writer-less orchestrators
    Orchestrator.close(orch)


def test_streamed_phase_is_the_fused_pass_and_compiles_no_train_phase(caplog):
    """The residual epochs go through ``_train_step_jit`` a dispatch a
    minibatch (PR 37): parameters and the ``[n_updates]`` statistics rows
    of a streamed phase equal the fused ``train_phase`` scan over the same
    plan, and the streamed phase compiles no ``jit_train_phase`` (compiles
    counted by name from jax's own compile log)."""
    import logging

    import jax

    from trlx_tpu.pipeline.ppo_buffer import make_stream_plan
    from trlx_tpu.utils.loading import get_trainer

    config = _parity_config({"dp": -1, "fsdp": 1, "tp": 1})
    trainer = get_trainer("PPOTrainer")(config, reward_fn=_reward_fn)
    init_state = jax.device_get(trainer.state)

    def compiled(name):
        return sum(
            rec.getMessage().startswith(f"Compiling {name} ")
            for rec in caplog.records
        )

    with jax.log_compiles(), caplog.at_level(logging.WARNING, logger="jax"):
        params, rows, _, n_updates = _run_phase(trainer, init_state, overlap=True)
        assert compiled("jit(train_step)") == 1
        assert compiled("jit(train_phase)") == 0
        assert trainer._train_phase_jit._cache_size() == 0

        # the same plan through the fused scan, from the same state and
        # the rollouts the phase left in its store
        plan = make_stream_plan(
            config.method.num_rollouts, config.train.batch_size,
            config.method.ppo_epochs, seed=11,
        )
        assert plan.residual.shape[0] == 3 and n_updates == 6
        mbs = trainer.buffer.gather(
            np.concatenate([plan.epoch1, plan.residual]),
            sharding=trainer._stacked_batch_sh,
        )
        fused_state, fused_rows = trainer._train_phase_jit(
            jax.device_put(init_state, trainer.state_shardings), mbs
        )
        assert compiled("jit(train_phase)") == 1

    for a, b in zip(
        jax.tree_util.tree_leaves(params),
        jax.tree_util.tree_leaves(jax.device_get(fused_state.params)),
        strict=True,
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    fused_rows = jax.device_get(fused_rows)
    assert set(rows) == set(fused_rows)
    for key in rows:
        assert rows[key].shape == (n_updates,), key
        np.testing.assert_array_equal(
            rows[key], np.asarray(fused_rows[key]), err_msg=key
        )


def test_the_second_update_of_a_run_traces_nothing():
    """A trainer's initial state has the types its train step returns, the
    step counter's mesh included: the second update of a run reuses the
    first one's trace (PR 37; left uncommitted, the counter made every run
    trace, lower, key and load the whole train program twice)."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.analysis.harness import _ppo_minibatch_sds
    from trlx_tpu.utils.loading import get_trainer

    config = _parity_config({"dp": -1, "fsdp": 1, "tp": 1})
    trainer = get_trainer("PPOTrainer")(config, reward_fn=_reward_fn)
    mb = jax.device_put(
        jax.tree_util.tree_map(
            lambda s: jnp.ones(s.shape, s.dtype), _ppo_minibatch_sds(trainer)
        ),
        trainer._batch_sh,
    )
    state, _ = trainer._train_step_jit(trainer.state, mb)
    trainer._train_step_jit(state, mb)
    assert trainer._train_step_jit._cache_size() == 1
