"""PPO experience collection.

Re-design of ``PPOOrchestrator.make_experience``
(``trlx/orchestrator/ppo_orchestrator.py:59-196``). The loop keeps the
reference's semantics — draw prompts, generate, decode, score with the user
reward fn ``(samples, queries, response_gt)``, scale/clip rewards, per-token
KL penalty vs the frozen reference model, push to the store — but the
device/host boundary is redrawn for TPU (SURVEY §7.3 "host/device boundary
in the rollout loop"):

- generation emits behavior logprobs *and* values in the same compiled
  program, so the reference's no-grad policy recompute (:126-131) is gone;
- only token ids cross to host (for detokenization + the user's Python
  reward fn); rewards go back as one [B] array;
- the per-token KL penalty + terminal score add (:163-167) is a tiny jitted
  op on device; rollouts are pushed as batched device pytrees, never as
  Python lists of CPU tensors (:169-187).
"""

from __future__ import annotations

import os
import sys
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

from trlx_tpu import telemetry
from trlx_tpu.orchestrator import Orchestrator, register_orchestrator
from trlx_tpu.data.ppo_types import PPORolloutBatch
from trlx_tpu.ops.ppo_math import PPOConfig
from trlx_tpu.parallel.collectives import RunningMoments
from trlx_tpu.parallel.distributed import is_main_process
from trlx_tpu.utils import Clock, infinite_loader, safe_mkdir


@register_orchestrator
class PPOOrchestrator(Orchestrator):
    """
    :param trainer: a :class:`PPOTrainer`.
    :param pipeline: prompt pipeline (queries + optional response_gt).
    :param reward_fn: ``(samples, queries, response_gt) -> [float]`` — the
        fork's reward interface (`ppo_orchestrator.py:53-57`,
        `ul2_RL/rl_ul2.py:71`).
    :param chunk_size: prompts per generation chunk.
    """

    def __init__(
        self,
        trainer,
        pipeline,
        reward_fn: Callable,
        chunk_size: int = 128,
    ):
        super().__init__(trainer, pipeline)
        self.reward_fn = reward_fn
        self.chunk_size = chunk_size
        # validate / bound the decode budget against the pipeline's real
        # prompt lengths (raises on guaranteed zero-length responses;
        # shrinks over-allocated max_new_tokens before anything compiles)
        if hasattr(trainer, "bind_prompt_budget"):
            trainer.bind_prompt_budget(pipeline)
        # chunk_size counts ROLLOUTS per chunk; a grouped trainer (GRPO, or
        # PPO with method.group_size > 1) turns each drawn prompt into
        # group_size rollouts, so the loader draws chunk_size / G prompts
        self.group_size = int(getattr(trainer, "group_size", 1) or 1)
        if chunk_size % self.group_size:
            raise ValueError(
                f"chunk_size={chunk_size} must be a multiple of "
                f"group_size={self.group_size} (each prompt yields "
                f"{self.group_size} rollouts)"
            )
        self._loader = infinite_loader(
            lambda seed: pipeline.create_loader(
                chunk_size // self.group_size, shuffle=True, seed=seed,
                drop_last=False,
            )
        )
        # prompt draws since construction: the infinite stream's position
        # is run-cumulative, so it is checkpointed (state_dict) and
        # fast-forwarded on resume — without it a resumed run replays
        # prompts from the beginning and diverges from the run it
        # continues (kill/resume parity, docs/resilience.md)
        self._draws = 0
        # running reward scaling state (`ppo_orchestrator.py:49-51`)
        self.running = RunningMoments()
        self.ref_mean = trainer.config.method.ref_mean
        self.ref_std = trainer.config.method.ref_std
        # back-reference, as the reference installs (`ppo_orchestrator.py:45`)
        trainer.orch = self
        # pid suffix: two jobs sharing a rollout_logging_dir that start in
        # the same second must still get distinct run directories
        self._run_id = f"{time.strftime('%Y%m%d-%H%M%S')}-{os.getpid()}"
        # rollout JSONL writes run on a background thread so host file
        # I/O never sits on the collect critical path; drained at every
        # phase end (and on exceptions) by make_experience
        self._rollout_writer = None
        if trainer.config.train.rollout_logging_dir and is_main_process():
            from trlx_tpu.utils.async_writer import BackgroundJSONLWriter

            self._rollout_writer = BackgroundJSONLWriter()
        # marker distinguishing ENGINE-layer failures (dead actor) from
        # learner/reward-path failures inside the continuous collect
        # loop — set by _engine_step, consumed by make_experience
        self._engine_error: Optional[BaseException] = None

    def _engine_step(self, fn, *args, **kwargs):
        """Run one engine call (start_phase/submit/drive-next), marking
        any failure as engine-originated so ``make_experience`` can tell
        a dead actor from a learner-side bug raised in the same loop."""
        try:
            return fn(*args, **kwargs)
        except StopIteration:
            raise
        except BaseException as e:
            self._engine_error = e
            raise

    def _draw(self):
        """One prompt-batch draw from the infinite stream (counted for
        checkpoint/resume)."""
        self._draws += 1
        return next(self._loader)

    def state_dict(self) -> Dict[str, Any]:
        """Host-side collection state that must survive a checkpoint
        round trip for a resumed run to continue the same trajectory:
        reward-scaling moments (`RunningMoments`), the reference stats,
        and the prompt-stream position."""
        return {
            "running": {
                "mean": self.running.mean,
                "std": self.running.std,
                "var": self.running.var,
                "count": self.running.count,
            },
            "ref_mean": self.ref_mean,
            "ref_std": self.ref_std,
            "prompt_draws": self._draws,
        }

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        running = state.get("running") or {}
        for key in ("mean", "std", "var", "count"):
            if key in running:
                setattr(self.running, key, float(running[key]))
        self.ref_mean = state.get("ref_mean", self.ref_mean)
        self.ref_std = state.get("ref_std", self.ref_std)
        # fast-forward the deterministic prompt stream to the saved
        # position (draws are host-side index shuffles — cheap)
        target = int(state.get("prompt_draws", 0))
        while self._draws < target:
            self._draw()

    def close(self, reraise: bool = True) -> None:
        """Stop the rollout writer, draining queued rows; a write error a
        phase-end drain-on-exception flush swallowed re-raises here (the
        writer would otherwise take the failure to the grave — rows
        silently missing from a 'successful' run)."""
        if self._rollout_writer is not None:
            writer, self._rollout_writer = self._rollout_writer, None
            writer.close(reraise=reraise)

    def _expand_groups(self, batch, meta):
        """Grouped-baseline support (GRPO): when the trainer declares
        ``group_size`` G > 1, repeat each prompt G times *within the chunk*
        so same-prompt rollouts are contiguous — the trainer's reward
        shaping normalizes scores within each group before anything is
        shuffled."""
        G = self.group_size
        if G <= 1:
            return batch, meta
        import jax.numpy as jnp

        batch = type(batch)(
            input_ids=jnp.repeat(batch.input_ids, G, axis=0),
            attention_mask=jnp.repeat(batch.attention_mask, G, axis=0),
        )
        meta = {
            k: ([x for x in v for _ in range(G)] if isinstance(v, list) else v)
            for k, v in meta.items()
        }
        if "n_real" in meta:
            meta["n_real"] = meta["n_real"] * G
        return batch, meta

    def score(self, samples, queries, response_gt):
        """User reward fn call (host Python; `ppo_orchestrator.py:53-57`)."""
        return self.reward_fn(
            samples=samples, queries=queries, response_gt=response_gt
        )

    def _scale_scores(self, scores: np.ndarray, method) -> np.ndarray:
        """Reward scaling + clip (`ppo_orchestrator.py:96-112`), shared by
        the fixed-batch and continuous collect paths. The reference seeds
        ref stats from the first rollout batch when unset (`:97-98`) and
        always advances the running moments."""
        if self.ref_mean is None:
            self.ref_mean, self.ref_std = (
                float(scores.mean()), float(scores.std())
            )
        self.running.update(scores)
        if method.scale_reward == "running":
            if self.running.std > 0:
                scores = scores / self.running.std
        elif method.scale_reward == "ref" and self.ref_std:
            scores = scores / self.ref_std
        elif method.scale_reward == "group":
            # whiten within each same-prompt group (beyond parity;
            # rows are group-contiguous via _expand_groups)
            from trlx_tpu.ops.ppo_math import group_whiten

            scores = group_whiten(scores, self.group_size)
        if method.cliprange_reward:
            scores = np.clip(
                scores, -method.cliprange_reward, method.cliprange_reward,
            )
        return scores

    def _log_rollouts(self, queries, texts, scores, iter_count: int) -> None:
        """Enqueue collected rollouts for ``train.rollout_logging_dir`` as
        JSON lines (query/response/raw score), rank-0 only — the writes
        happen on the background writer thread, never on the collect
        critical path; ``make_experience`` drains the queue at phase end
        (and on exceptions, so already-queued rows survive a crash). Each
        run writes under its own ``run_<timestamp>`` subdirectory so a
        resumed/re-run job reusing the directory never appends rows
        indistinguishable from an earlier run's."""
        if self._rollout_writer is None:
            return
        directory = os.path.join(
            self.trainer.config.train.rollout_logging_dir,
            f"run_{self._run_id}",
        )
        safe_mkdir(directory)
        path = os.path.join(directory, f"rollouts_{iter_count}.jsonl")
        self._rollout_writer.submit(
            path,
            [
                {"query": q, "response": s, "score": float(r)}
                for q, s, r in zip(queries, texts, scores)
            ],
        )

    def _dispatch_chunk(self):
        """Enqueue one chunk's device work (sampler + frozen-ref forward)
        without waiting on it. Dispatch is async; the results are consumed
        later, after the *previous* chunk's host-side scoring."""
        with telemetry.span("collect/prompt_draw"):
            batch, meta = self._draw()
        batch, meta = self._expand_groups(batch, meta)
        # forced span: its duration IS exp/dispatch_time's increment, so
        # the stat survives a disabled tracer (span measures, won't record)
        with telemetry.span("collect/dispatch", force=True) as sp:
            sample_out = self.trainer.sample(
                batch.input_ids, batch.attention_mask
            )
        dispatch_ms = sp.duration_ms
        # Frozen-reference forward queued right behind generation
        # (SURVEY §7.3 — "call out + re-insert scores without stalling
        # the TPU"): it runs on device while Python scores the batch.
        ref_logprobs = self.trainer.score_ref(
            batch.input_ids,
            batch.attention_mask,
            sample_out.tokens,
            sample_out.response_mask,
        )
        # Start the device->host copy of what decode_responses will need as
        # soon as the sampler finishes (the copy is scheduled behind the
        # computation): by the time the host fetches, the transfer has
        # already overlapped the previous chunk's scoring.
        for arr in (sample_out.tokens, sample_out.response_mask):
            try:
                arr.copy_to_host_async()
            except (AttributeError, RuntimeError):
                break  # backend without async copies: plain fetch later
        return batch, meta, sample_out, ref_logprobs, dispatch_ms

    def make_experience(self, num_rollouts: int = 128, iter_count: int = 0):
        """Collect one phase of experience — dispatched on the trainer's
        configured rollout engine (``train.rollout``): the fixed-batch
        double-buffered chunk loop (the default and parity baseline), or
        the continuous-batching slot-admission engine
        (docs/inference.md). A TRANSIENT engine-path failure
        (``utils/retry.py::classify_io_error``) degrades to the fixed
        sampler — a health event and a restarted phase, not an aborted
        run (docs/resilience.md); anything else propagates as itself."""
        if getattr(self.trainer, "rollout_engine", "fixed") == "continuous":
            try:
                return self._make_experience_continuous(
                    num_rollouts, iter_count
                )
            except Exception as e:
                from trlx_tpu.resilience.preemption import PreemptionDrain
                from trlx_tpu.telemetry.health import HealthAbort

                if isinstance(e, (HealthAbort, PreemptionDrain)):
                    raise  # policy decisions, not engine-path failures
                async_cfg = getattr(self.trainer, "async_config", None)
                if async_cfg is not None and async_cfg.enabled:
                    # async actor–learner mode: an ENGINE-layer failure
                    # (submit/drive raised — the marker set by
                    # _engine_step below) is a dead/stalled actor — not
                    # a reason to silently retrain on the fixed sampler,
                    # which would change the workload's whole schedule
                    # mid-run. Surface it and hand recovery to the PR-9
                    # supervisor (docs/resilience.md). Anything else —
                    # a learner dispatch, the user reward fn — must
                    # propagate AS ITSELF so the supervisor's
                    # permanent-vs-retriable taxonomy judges the real
                    # error (wrapping a deterministic reward-fn bug as
                    # retriable would burn the restart budget replaying
                    # it).
                    if self._engine_error is e:
                        self._engine_error = None
                        self._actor_dead(e, iter_count)
                    self._engine_error = None
                    raise
                self._engine_error = None
                from trlx_tpu.utils.retry import classify_io_error

                if classify_io_error(e) != "transient":
                    # a compile error, a VMEM/HBM out-of-memory, a shape
                    # the chip refuses, a programming error: swapping
                    # samplers under those would report a run "on the
                    # engine" that never ran on it
                    raise
                self._degrade_engine(e, iter_count)
        return self._make_experience_fixed(num_rollouts, iter_count)

    def _actor_dead(self, error: BaseException, iter_count: int) -> None:
        """Async actor–learner failure path: emit an ``actor-dead``
        health event (the ``engine-fallback`` pattern) and raise
        :class:`~trlx_tpu.trainer.async_rl.ActorDeadError`, which the
        resilience supervisor classifies retriable — restart from the
        last good checkpoint with a fresh actor pool, no hang. The
        active streamed phase is aborted by the raise's unwind
        (:meth:`PPOTrainer._collect_phase`), exactly like any other
        collection failure."""
        from trlx_tpu.trainer.async_rl import ActorDeadError

        tr = self.trainer
        print(
            "resilience: async actor died mid-phase "
            f"({type(error).__name__}: {error}) — raising for the "
            "supervisor (restart from the last good checkpoint)",
            file=sys.stderr,
        )
        emit = getattr(tr, "emit_health_event", None)
        if emit is not None:
            emit(
                detector="actor-dead",
                severity="error",
                series="async",
                message=(
                    "async actor (continuous engine) died mid-phase "
                    f"({type(error).__name__}: {error}); supervisor "
                    "restart requested"
                ),
                step=iter_count,
                phase=getattr(tr, "health_phase_id", None),
            )
        raise ActorDeadError(
            f"async actor died mid-phase at iteration {iter_count} "
            f"({type(error).__name__}: {error})"
        ) from error

    def _degrade_engine(self, error: BaseException, iter_count: int) -> None:
        """Fall back from the continuous engine to the fixed sampler for
        the rest of the run (``make_experience`` calls this for transient
        failures only): flip the trainer's engine selection (the
        fixed sampler is always compiled — evaluation uses it), emit an
        ``engine-fallback`` health event (warning severity: degradation
        is the alternative to the abort policy, never its trigger), and
        restart the current phase cleanly — partial harvests landed by
        the failed engine phase cannot satisfy the stream plan. Epoch-1
        updates the partial phase already dispatched are not rolled
        back, exactly like :meth:`PPOTrainer.abort_streamed_phase`."""
        tr = self.trainer
        print(
            "resilience: continuous rollout engine failed "
            f"({type(error).__name__}: {error}) — falling back to the "
            "fixed sampler for the rest of the run",
            file=sys.stderr,
        )
        tr.rollout_engine = "fixed"
        tr._rollout_engine_obj = None  # drop the poisoned slot pool
        emit = getattr(tr, "emit_health_event", None)
        if emit is not None:
            emit(
                detector="engine-fallback",
                severity="warning",
                series="engine",
                message=(
                    "continuous rollout engine failed "
                    f"({type(error).__name__}: {error}); degraded to the "
                    "fixed sampler"
                ),
                step=iter_count,
                phase=getattr(tr, "health_phase_id", None),
            )
        if getattr(tr, "_stream", None) is not None:
            seed = getattr(tr, "_last_stream_seed", 0)
            tr.abort_streamed_phase()
            tr.begin_streamed_phase(seed=seed)
        else:
            tr.buffer.clear_history()
            if hasattr(tr, "reset_rollout_phase"):
                tr.reset_rollout_phase()

    def _finish_collect_stats(
        self,
        clock,
        collected: int,
        all_scores,
        generate_time: float,
        dispatch_time: float,
        score_time: float,
        iter_count: int,
        extra=None,
    ):
        """Shared collect epilogue: assemble the stats row, feed the
        run-health detectors, and log — identical keys on both engines so
        bench/dashboards diff across the config switch."""
        exp_time = clock.tick() / 1000.0
        scores_cat = np.concatenate(all_scores)
        stats = {
            "exp/generate_time": generate_time,
            "exp/dispatch_time": dispatch_time,
            "exp/score_time": score_time,
            "exp/experience_time": exp_time,
            "exp/score_mean": float(scores_cat.mean()),
            "exp/score_std": float(scores_cat.std()),
            "exp/running_mean": float(self.running.mean),
            "exp/running_std": float(self.running.std),
            "exp/rollouts_per_sec": collected / max(exp_time, 1e-9),
            "policy/mean_rollout_kl": self.trainer.mean_kl,
        }
        if extra:
            stats.update(extra)
        # unified metrics namespace (telemetry/metrics.py): the collect
        # row's host-float stats — engine/* occupancy included via
        # `extra` on the continuous path — become registry gauges, so
        # the ledger/flight/bench snapshots see them without knowing
        # this dict's shape
        telemetry.get_metrics().absorb(stats)
        # run-health: the collect stats row feeds the detectors too —
        # exp/score_std is the reward-saturation series. Host floats
        # only; the device-resident mean_rollout_kl scalar is skipped by
        # the monitor (never forced) and observed later from the phase's
        # fetched update rows.
        observe = getattr(self.trainer, "observe_health", None)
        if observe is not None:
            observe(
                stats,
                step=iter_count,
                phase=getattr(self.trainer, "health_phase_id", None),
            )
        if getattr(self.trainer, "logger", None) is not None:
            self.trainer.logger.log(stats, step=iter_count)
        return stats

    def _make_experience_continuous(
        self, num_rollouts: int, iter_count: int
    ):
        """Drive the continuous-batching engine for one phase: submit the
        phase's prompt draw into the admission queue, then score/land
        each fixed-width harvest group as it completes — rollouts stream
        into the buffer in finish order, and the streamed-phase hook
        dispatches epoch-1 updates exactly as on the fixed path."""
        method: PPOConfig = self.trainer.config.method
        clock = Clock()
        collected = 0
        generate_time = 0.0
        dispatch_time = 0.0
        score_time = 0.0
        all_scores = []
        engine = self.trainer.rollout_engine_obj
        Hw = engine.harvest_width
        # fixed-shape harvest groups: round the target up exactly like
        # the fixed path's full-size chunks overshoot num_rollouts
        target = ((int(num_rollouts) + Hw - 1) // Hw) * Hw
        streamed_hook = getattr(self.trainer, "on_rollouts_landed", None)
        meta_by_row = {}
        have_gt = self.pipeline.response_gt is not None

        with telemetry.span(
            "phase/collect", force=True, rollouts=int(num_rollouts)
        ):
            try:
                with telemetry.span("collect/dispatch", force=True) as sp:
                    # engine_start_params reshards the behavior snapshot
                    # to the actor device subset when one is configured
                    # (async_rl.actor_fraction); otherwise it IS
                    # rollout_params()
                    start_params = (
                        self.trainer.engine_start_params()
                        if hasattr(self.trainer, "engine_start_params")
                        else self.trainer.rollout_params()
                    )
                    self._engine_step(
                        engine.start_phase,
                        start_params,
                        self.trainer.rollout_phase_key(),
                    )
                    # draw the phase's prompts into the admission queue
                    # (row index = draw order = the per-row RNG identity)
                    while engine.pending + engine.stats.completed < target:
                        with telemetry.span("collect/prompt_draw"):
                            batch, meta = self._draw()
                        batch, meta = self._expand_groups(batch, meta)
                        rows = self._engine_step(
                            engine.submit,
                            np.asarray(batch.input_ids),
                            np.asarray(batch.attention_mask),
                        )
                        for i, r in enumerate(rows):
                            meta_by_row[r] = (
                                meta["prompts_text"][i],
                                meta["response_gt"][i] if have_gt else None,
                            )
                dispatch_time += sp.duration_ms / 1000.0

                # drive() interleaves engine decode with the learner's
                # landing hook (score/rewards/epoch-1 dispatch) in one
                # loop; pulling groups through _engine_step keeps the
                # engine-failure marker scoped to the generator's own
                # raises, not the loop body's
                drive_iter = iter(engine.drive(target))
                while True:
                    try:
                        group = self._engine_step(next, drive_iter)
                    except StopIteration:
                        break
                    if getattr(self.trainer, "_actor_mesh", None) is not None:
                        # actor→learner rollout stream (async device
                        # subsets): one batched reshard of the harvest
                        # group from the actor submesh onto the
                        # learner's batch sharding, before anything
                        # downstream consumes it
                        import jax

                        keys = (
                            "query_tokens", "query_mask", "tokens",
                            "response_mask", "logprobs", "values",
                        )
                        moved = jax.device_put(
                            {k: group[k] for k in keys},
                            self.trainer._batch_sh,
                        )
                        group = dict(group, **moved)
                    # frozen-ref forward queued right behind the harvest;
                    # it runs on device while Python scores the group
                    ref_logprobs = self.trainer.score_ref(
                        group["query_tokens"],
                        group["query_mask"],
                        group["tokens"],
                        group["response_mask"],
                    )
                    with telemetry.span("collect/decode", force=True) as sp:
                        texts = self.trainer.decode_responses(
                            group["tokens"], group["response_mask"]
                        )
                    generate_time += sp.duration_ms / 1000.0
                    rows = group["rows"]
                    queries = [meta_by_row[r][0] for r in rows]
                    gts = (
                        [meta_by_row[r][1] for r in rows] if have_gt else None
                    )
                    with telemetry.span("collect/score", force=True) as sp:
                        scores = np.asarray(
                            self.score(texts, queries, gts), dtype=np.float32
                        )
                    score_time += sp.duration_ms / 1000.0
                    all_scores.append(scores.copy())
                    self._log_rollouts(queries, texts, scores, iter_count)
                    scores = self._scale_scores(scores, method)

                    with telemetry.span("collect/land") as land_sp:
                        rewards = self.trainer.compute_rewards(
                            group["logprobs"],
                            ref_logprobs,
                            group["response_mask"],
                            scores,
                        )
                        self.trainer.buffer.push(
                            PPORolloutBatch(
                                query_tokens=group["query_tokens"],
                                query_mask=group["query_mask"],
                                response_tokens=group["tokens"],
                                response_mask=group["response_mask"],
                                logprobs=group["logprobs"],
                                values=group["values"],
                                rewards=rewards,
                            ),
                            # behavior-version tags (host ints, from the
                            # engine's admission versions): the async
                            # learner's staleness accounting; all-zero
                            # outside async mode (no pushes ever happen)
                            versions=group.get("versions"),
                        )
                        collected += len(rows)
                        land_sp.set(landed=collected)
                        if streamed_hook is not None:
                            streamed_hook()
            except BaseException:
                if self._rollout_writer is not None:
                    self._rollout_writer.flush(reraise=False)
                raise
            if self._rollout_writer is not None:
                self._rollout_writer.flush(reraise=True)

        return self._finish_collect_stats(
            clock, collected, all_scores, generate_time, dispatch_time,
            score_time, iter_count, extra=engine.stats.to_dict(),
        )

    def _make_experience_fixed(
        self, num_rollouts: int = 128, iter_count: int = 0
    ):
        method: PPOConfig = self.trainer.config.method
        clock = Clock()
        stats = {}
        collected = 0
        generate_time = 0.0
        dispatch_time = 0.0
        score_time = 0.0
        all_scores = []

        # Double-buffered collection: chunk k+1's device work is enqueued
        # before chunk k's host-side detokenize + reward run, so the device
        # never idles between chunks. All chunks sample from the same policy
        # params — either literally no update happens inside the phase, or
        # (streamed phase, docs/async_pipeline.md) every sampler/ref
        # forward runs on the trainer's frozen behavior snapshot while
        # epoch-1 updates land underneath — so the pipelining is exactly
        # on-policy: same semantics as the reference's sequential loop
        # (`ppo_orchestrator.py:66-196`).
        streamed_hook = getattr(self.trainer, "on_rollouts_landed", None)
        # one span per phase collect; chunk-level sub-spans (prompt draw,
        # dispatch, decode wait, score, landing) nest inside it — and any
        # streamed epoch-1 train dispatch the landing hook performs nests
        # inside collect/land, making the overlap visible in the trace
        with telemetry.span(
            "phase/collect", force=True, rollouts=int(num_rollouts)
        ):
            try:
                pending = self._dispatch_chunk()
                while collected < num_rollouts:
                    batch, meta, sample_out, ref_logprobs, dispatch_ms = pending
                    dispatch_time += dispatch_ms / 1000.0
                    if collected + len(batch.input_ids) < num_rollouts:
                        pending = self._dispatch_chunk()

                    # time-to-tokens-available: decode_responses blocks on the
                    # device->host copy of the sampler's output, so this is
                    # where generation cost actually lands (the reference's
                    # exp_generate_time meaning); dispatch_time alone reads ~0
                    # because the sampler call above only enqueues work.
                    with telemetry.span("collect/decode", force=True) as sp:
                        texts = self.trainer.decode_responses(
                            sample_out.tokens, sample_out.response_mask
                        )
                    generate_time += sp.duration_ms / 1000.0
                    if meta["prompts_text"][0] is not None:
                        queries = meta["prompts_text"]
                    else:
                        queries = self.trainer.decode_queries(
                            batch.input_ids, batch.attention_mask
                        )

                    with telemetry.span("collect/score", force=True) as sp:
                        scores = np.asarray(
                            self.score(texts, queries, meta["response_gt"]),
                            dtype=np.float32,
                        )
                    score_time += sp.duration_ms / 1000.0
                    all_scores.append(scores.copy())
                    self._log_rollouts(queries, texts, scores, iter_count)

                    scores = self._scale_scores(scores, method)

                    with telemetry.span("collect/land") as land_sp:
                        rewards = self.trainer.compute_rewards(
                            sample_out.logprobs,
                            ref_logprobs,
                            sample_out.response_mask,
                            scores,
                        )

                        self.trainer.buffer.push(
                            PPORolloutBatch(
                                query_tokens=batch.input_ids,
                                query_mask=batch.attention_mask,
                                response_tokens=sample_out.tokens,
                                response_mask=sample_out.response_mask,
                                logprobs=sample_out.logprobs,
                                values=sample_out.values,
                                rewards=rewards,
                            )
                        )
                        collected += len(batch)
                        # post-landing count: this span's chunk is what
                        # made the total reach `landed`, which is the
                        # number the stream plan's readiness gates on
                        land_sp.set(landed=collected)
                        if streamed_hook is not None:
                            # streamed phase: let the trainer dispatch every
                            # epoch-1 minibatch whose rollouts have now landed
                            # (no-op outside an active stream)
                            streamed_hook()
            except BaseException:
                # drain queued rows to disk even when collection raised
                # (writer errors suppressed — the active exception wins);
                # the enclosing `with` closes the span with status=error
                # and never swallows
                if self._rollout_writer is not None:
                    self._rollout_writer.flush(reraise=False)
                raise
            # clean path: the phase-end writer drain belongs to the
            # collect window; a failing drain propagates and the `with`
            # closes the span as the error it is
            if self._rollout_writer is not None:
                self._rollout_writer.flush(reraise=True)

        stats.update(self._finish_collect_stats(
            clock, collected, all_scores, generate_time, dispatch_time,
            score_time, iter_count,
        ))
        return stats
