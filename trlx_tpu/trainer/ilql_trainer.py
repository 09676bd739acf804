"""ILQL trainer: offline Q-learning with jitted updates and in-graph
target-network sync.

Re-design of ``AccelerateILQLModel`` (``trlx/model/accelerate_ilql_model.py``):

- The target-Q param tree is part of the train state; the Polyak sync every
  ``steps_for_target_q_sync`` steps (`accelerate_ilql_model.py:54-56`,
  `ilql_models.py:161-181`) is a ``lax.cond`` *inside* the jitted train step
  — no host round-trip, no ZeRO gather (sharded params sync elementwise).
- Evaluation generation uses the compiled sampler with advantage-shifted
  logits ``log pi_beta + beta * (min_target_Q - V)`` and optional per-token
  ``logit_mask`` (the reference's hand-rolled decode,
  `ilql_models.py:257-327`).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import flax.struct as struct
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.ilql_types import ILQLBatch
from trlx_tpu.models.heads import CausalLMWithILQLHeads
from trlx_tpu.models.registry import num_layers_of
from trlx_tpu.ops.ilql_math import ILQLConfig, ilql_loss, polyak_update
from trlx_tpu.ops.sampling import GenerationConfig, make_sampler, validate_gen_config
from trlx_tpu.parallel import (
    batch_sharding,
    make_partition_specs,
    make_mesh,
    replicated,
)
from trlx_tpu.parallel.mesh import traced_on
from trlx_tpu.trainer import BaseRLTrainer, register_trainer
from trlx_tpu.trainer.common import (
    make_optimizer,
    stop_frozen_gradients,
    unfrozen_param_mask,
)
from trlx_tpu.utils import Clock, set_seed
from trlx_tpu.utils.checkpoint import (
    has_checkpoint,
    load_checkpoint,
    save_checkpoint,
    wait_for_checkpoints,
)
from trlx_tpu.utils.logging import Logger


@struct.dataclass
class ILQLTrainState:
    params: Any
    target_q_params: Any  # copy of the q-head subtree of params["heads"]
    opt_state: Any
    step: jax.Array


def _q_subtree(heads_params: Dict) -> Dict:
    return {k: v for k, v in heads_params.items() if k.startswith("q")}


@register_trainer
class ILQLTrainer(BaseRLTrainer):
    def __init__(
        self,
        config: TRLConfig,
        reward_fn: Optional[Callable] = None,
        metric_fn: Optional[Callable] = None,
        tokenizer=None,
        logit_mask=None,
    ):
        super().__init__(config, reward_fn, metric_fn, tokenizer, logit_mask)
        method: ILQLConfig = config.method
        train = config.train

        if (train.rollout or {}).get("engine", "fixed") != "fixed":
            # ILQL is offline — there is no rollout collect loop for the
            # continuous engine to drive; refuse instead of no-opping
            raise NotImplementedError(
                "train.rollout engine "
                f"{train.rollout.get('engine')!r} is not supported by "
                "ILQLTrainer (offline trainer; no rollout engine)"
            )
        if (train.async_rl or {}).get("enabled"):
            # same loudness: no collect phase to disaggregate
            raise NotImplementedError(
                "train.async_rl is not supported by ILQLTrainer "
                "(offline trainer; there is no actor/collect loop to "
                "run asynchronously)"
            )
        self.mesh = make_mesh(train.mesh)
        self.pp_stages = dict(self.mesh.shape).get("pp", 1)
        self.pp_microbatches = train.pp_microbatches
        self.pp_virtual_stages = train.pp_virtual_stages
        self.pp_remat = train.pp_remat
        if self.pp_remat and self.pp_virtual_stages > 1:
            raise NotImplementedError(
                "pp_remat runs the v=1 schedule; drop pp_virtual_stages "
                "or pp_remat"
            )
        self.rng = set_seed(train.seed)

        if tokenizer is None and config.model.tokenizer_path:
            from transformers import AutoTokenizer

            self.tokenizer = AutoTokenizer.from_pretrained(
                config.model.tokenizer_path, local_files_only=True
            )
            if self.tokenizer.pad_token_id is None:
                self.tokenizer.pad_token = self.tokenizer.eos_token

        from trlx_tpu.trainer.ppo_trainer import get_causal_arch

        self.family, self.model_config, init_params = get_causal_arch(config)
        if self.pp_stages > 1:
            from trlx_tpu.models.pp_runner import supports_pp

            if not supports_pp(self.model_config):
                # without this guard a pp axis would silently replicate all
                # compute across the pp devices (rules never reference pp)
                raise NotImplementedError(
                    f"pp mesh axis is integrated for the causal families "
                    f"(gpt2/gptj/gpt_neo/gpt_neox) but not "
                    f"{type(self.model_config).__name__}: MoE layers have "
                    f"non-uniform per-layer params (no stage stacking); "
                    f"use dp/fsdp/tp/ep instead"
                )
        self.model = CausalLMWithILQLHeads(
            self.model_config,
            two_qs=method.two_qs,
            backbone_cls=self.family.backbone_cls,
        )

        # sampling defaults live in ILQLConfig.gen_kwargs (config-visible,
        # merged by ILQLConfig.from_dict); re-merge here too so code that
        # assigns config.method.gen_kwargs directly (examples do) still gets
        # the reference's eval-decode defaults (top_k=20, ...) under its
        # own keys rather than silently losing them
        from trlx_tpu.ops.ilql_math import DEFAULT_ILQL_GEN_KWARGS

        gen_kwargs = {**DEFAULT_ILQL_GEN_KWARGS, **(method.gen_kwargs or {})}
        self.apply_tokenizer_gen_defaults(gen_kwargs)
        self.gen_config = GenerationConfig.from_dict(gen_kwargs)
        validate_gen_config(
            self.gen_config,
            getattr(self.model_config, "vocab_size", None),
            provided=set(gen_kwargs),
        )
        self.beta = float(method.betas[0])
        self.query_length = min(
            train.seq_length, max(train.seq_length - self.gen_config.max_new_tokens, 1)
        )

        # --- params / state ---
        self.rng, init_rng = jax.random.split(self.rng)
        dummy = jnp.zeros((1, 8), jnp.int32)
        params = self.model.init(init_rng, dummy)["params"]
        if init_params is not None:
            params["transformer"] = init_params

        self.param_shardings = self._shardings_for(params)
        params = jax.device_put(params, self.param_shardings)
        target_q = jax.tree_util.tree_map(jnp.copy, _q_subtree(params["heads"]))
        self.target_shardings = self._shardings_for(target_q)
        target_q = jax.device_put(target_q, self.target_shardings)

        # zero_freezes_all: the reference's ILQL freezing is live code and
        # freezes ALL gpt blocks at num_layers_unfrozen == 0
        # (ilql_models.py:217-225) — unlike the PPO path, whose freezing
        # block is commented out (accelerate_base_model.py:55-69)
        trainable = unfrozen_param_mask(
            params,
            config.model.num_layers_unfrozen,
            num_layers_of(self.model_config),
            zero_freezes_all=True,
        )
        self.trainable_mask = trainable
        self.tx = make_optimizer(train, train.total_steps, trainable)
        opt_shapes = jax.eval_shape(self.tx.init, params)
        self.opt_shardings = self._shardings_for(opt_shapes)
        opt_state = jax.jit(self.tx.init, out_shardings=self.opt_shardings)(params)

        self.state = ILQLTrainState(
            params=params,
            target_q_params=target_q,
            opt_state=opt_state,
            step=jnp.zeros((), jnp.int32),
        )
        self.state_shardings = ILQLTrainState(
            params=self.param_shardings,
            target_q_params=self.target_shardings,
            opt_state=self.opt_shardings,
            step=replicated(self.mesh),
        )

        self.store = None  # installed by OfflineOrchestrator
        self.setup_ep_axis(self.mesh, self.family)
        self._setup_rollout_cast(train)
        self._build_jitted_fns()

    def _setup_rollout_cast(self, train) -> None:
        """Compute-dtype copy of the sampler bundle (params + target-Q) for
        the β(Q−V) decode — same contract as the PPO trainer's
        (`train.rollout_param_cast`): bit-identical (trunk ops cast per use;
        MLPHead fc2 leaves stay f32) and half the per-token weight read."""
        self._rollout_cast_jit = None
        self._rollout_bundle_cache = None
        cdtype = jnp.dtype(getattr(self.model_config, "dtype", train.dtype))
        pdtype = jnp.dtype(
            getattr(self.model_config, "param_dtype", train.param_dtype)
        )
        if (
            not getattr(train, "rollout_param_cast", False)
            or cdtype == pdtype
        ):
            return
        from trlx_tpu.utils import compute_dtype_cast

        bundle_shardings = {
            "params": self.param_shardings,
            "target": self.target_shardings,
        }
        self._rollout_cast_jit = jax.jit(
            lambda bundle: compute_dtype_cast(bundle, cdtype),
            in_shardings=(bundle_shardings,),
            out_shardings=bundle_shardings,
        )

    def rollout_bundle(self):
        """Sampler inputs: the compute-dtype copy when the cast is enabled
        (recast lazily — ILQLTrainState is replaced on update, so object
        identity detects staleness), else the f32 masters."""
        master = {
            "params": self.state.params,
            "target": self.state.target_q_params,
        }
        if self._rollout_cast_jit is None:
            return master
        cache = self._rollout_bundle_cache
        key = (master["params"], master["target"])
        if cache is None or cache[0][0] is not key[0] or cache[0][1] is not key[1]:
            self._rollout_bundle_cache = (key, self._rollout_cast_jit(master))
        return self._rollout_bundle_cache[1]

    def _shardings_for(self, tree):
        specs = make_partition_specs(tree, self.mesh, self.family.partition_rules)
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s),
            specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    def _build_jitted_fns(self):
        method: ILQLConfig = self.config.method
        batch_sh = batch_sharding(self.mesh)
        rep = replicated(self.mesh)
        logit_mask = (
            jnp.asarray(self.logit_mask) if self.logit_mask is not None else None
        )

        moe_family = bool(getattr(self.family, "supports_ep", False))

        def train_step(state: ILQLTrainState, mb: ILQLBatch):
            def loss_fn(params):
                # prune the backward below the freezing boundary (reference
                # `ilql_models.py:217-225` freezes via requires_grad=False)
                params = stop_frozen_gradients(params, self.trainable_mask)
                if self.pp_stages > 1:
                    from trlx_tpu.models.pp_runner import pp_ilql_forward

                    out = pp_ilql_forward(
                        self.model_config, params, mb.input_ids,
                        mb.attention_mask, mb.actions_ixs, mb.states_ixs,
                        self.mesh, self.pp_microbatches,
                        two_qs=method.two_qs,
                        virtual_stages=self.pp_virtual_stages,
                        remat=self.pp_remat,
                    )
                elif moe_family:
                    out, sown = self.model.apply(
                        {"params": params},
                        mb.input_ids,
                        attention_mask=mb.attention_mask,
                        actions_ixs=mb.actions_ixs,
                        states_ixs=mb.states_ixs,
                        mutable=["moe_losses"],
                    )
                else:
                    out = self.model.apply(
                        {"params": params},
                        mb.input_ids,
                        attention_mask=mb.attention_mask,
                        actions_ixs=mb.actions_ixs,
                        states_ixs=mb.states_ixs,
                    )
                target_qs = self.model.apply(
                    {"params": {"heads": state.target_q_params}},
                    out["action_hidden"],
                    method=CausalLMWithILQLHeads.target_qs,
                )
                loss, stats = ilql_loss(
                    out["logits"], out["qs"], target_qs, out["vs"], mb,
                    method, health=self._health_enabled,
                )
                if moe_family:
                    # same Switch load-balancing objective as the PPO path
                    from trlx_tpu.ops.moe import (
                        apply_router_penalty, moe_loss_summary,
                    )

                    loss, stats = apply_router_penalty(
                        loss, stats, moe_loss_summary(sown["moe_losses"]),
                        self.model_config,
                    )
                return loss, stats

            (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params
            )
            updates, new_opt_state = self.tx.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
            new_step = state.step + 1
            # in-graph Polyak target sync (`ilql_models.py:161-181`)
            new_target = jax.lax.cond(
                new_step % method.steps_for_target_q_sync == 0,
                lambda: polyak_update(
                    _q_subtree(new_params["heads"]),
                    state.target_q_params,
                    method.alpha,
                ),
                lambda: state.target_q_params,
            )
            stats["optimizer/grad_norm"] = optax.global_norm(grads)
            return (
                ILQLTrainState(
                    params=new_params,
                    target_q_params=new_target,
                    opt_state=new_opt_state,
                    step=new_step,
                ),
                stats,
            )

        self._train_step_jit = jax.jit(
            traced_on(self.mesh, train_step),
            in_shardings=(self.state_shardings, batch_sh),
            out_shardings=(self.state_shardings, rep),
            donate_argnums=(0,),
        )

        # chunked fused scan: k consecutive updates in one dispatch (the
        # in-graph lax.cond target sync keys off state.step, so scanning
        # preserves the sync schedule exactly)
        from trlx_tpu.parallel.mesh import stacked_batch_sharding

        self._stacked_batch_sh = stacked_batch_sharding(self.mesh)

        def train_chunk(state, mbs):
            return jax.lax.scan(train_step, state, mbs)

        self._train_chunk_jit = jax.jit(
            traced_on(self.mesh, train_chunk),
            in_shardings=(self.state_shardings, self._stacked_batch_sh),
            out_shardings=(self.state_shardings, rep),
            donate_argnums=(0,),
        )

        # --- advantage-shifted sampler (`ilql_models.py:257-327`) ---
        def shift_logits(raw_logits, qs_tuple, vs, input_ids, last_only):
            """β(Q−V)-shifted sampling logits + adjacency mask — shared by
            the plain and pp sampler applies."""
            minq = qs_tuple[0]
            for tq in qs_tuple[1:]:
                minq = jnp.minimum(minq, tq)
            adv = minq - vs[..., None]
            logits = jax.nn.log_softmax(raw_logits, axis=-1) + self.beta * adv
            if logit_mask is not None:
                ids = input_ids[:, -1:] if last_only else input_ids
                allowed = logit_mask[ids]  # [B, T or 1, V] bool
                logits = jnp.where(allowed, logits, -1e9)
            return logits

        if self.pp_stages > 1:
            # pp decode: trunk pipelined with stage-resident KV buffers;
            # logits + Q/V/target-Q heads replicated over pp at the last
            # position only (all the advantage-shifted decode reads)
            from trlx_tpu.models.heads import ILQLHeads
            from trlx_tpu.models.pp_runner import (
                pp_cached_hidden,
                pp_decode_kit,
                pp_slice_logits,
                pp_stack_sampler_params,
            )

            heads_mod = ILQLHeads(self.model_config, method.two_qs)

            def sample_apply(bundle, input_ids, attention_mask=None,
                             position_ids=None, cache=None, cache_index=None,
                             last_only=False):
                params = bundle["params"]
                h, new_cache = pp_cached_hidden(
                    self.model_config, params["transformer"], input_ids,
                    attention_mask, position_ids, cache, cache_index,
                    self.mesh, self.pp_microbatches,
                    stacked=params["stacked_blocks"],
                )
                hs = h[:, -1:]
                raw = pp_slice_logits(
                    self.model_config, params["transformer"], hs
                )
                # only V from the live heads; the advantage shift reads
                # target-Q (live Q heads would trace dead matmuls)
                vs = heads_mod.apply(
                    {"params": params["heads"]}, hs, method=ILQLHeads.v
                )
                target_qs = heads_mod.apply(
                    {"params": bundle["target"]}, hs, method=ILQLHeads.q
                )
                logits = shift_logits(raw, target_qs, vs, input_ids, True)
                return {"logits": logits, "cache": new_cache}

            init_cache_fn, cache_sharding = pp_decode_kit(
                self.model_config, self.mesh
            )
            inner = make_sampler(
                sample_apply,
                init_cache_fn,
                self.gen_config,
                self.query_length,
                with_values=False,
                cache_sharding=cache_sharding,
            )

            def sampler(bundle, prompt_ids, prompt_mask, rng):
                # stack/reshard the trunk blocks ONCE per invocation, not
                # once per decoded token inside the sampler's scan
                packed = pp_stack_sampler_params(
                    self.model_config, self.mesh, bundle["params"]
                )
                return inner(
                    {"params": packed, "target": bundle["target"]},
                    prompt_ids, prompt_mask, rng,
                )
        else:
            def sample_apply(bundle, input_ids, attention_mask=None,
                             position_ids=None, cache=None, cache_index=None,
                             last_only=False):
                # last_only (prefill): logits + Q/V heads only at the final
                # position — the advantage-shifted decode reads one row.
                out = self.model.apply(
                    {"params": bundle["params"]},
                    input_ids,
                    attention_mask=attention_mask,
                    position_ids=position_ids,
                    cache=cache,
                    cache_index=cache_index,
                    last_only=last_only,
                )
                target_qs = self.model.apply(
                    {"params": {"heads": bundle["target"]}},
                    out["action_hidden"],
                    method=CausalLMWithILQLHeads.target_qs,
                )
                logits = shift_logits(
                    out["logits"], target_qs, out["vs"], input_ids, last_only
                )
                return {"logits": logits, "cache": out["cache"]}

            sampler = make_sampler(
                sample_apply,
                functools.partial(self.family.init_cache, self.model_config),
                self.gen_config,
                self.query_length,
                with_values=False,
                cache_sharding=self._decode_cache_sharding(),
            )
        bundle_shardings = {
            "params": self.param_shardings,
            "target": self.target_shardings,
        }
        self._sample_jit = jax.jit(
            traced_on(self.mesh, sampler),
            in_shardings=(bundle_shardings, batch_sh, batch_sh, rep),
            out_shardings=batch_sh,
        )

    # ------------------------------------------------------------------ #

    def sample(self, prompt_ids, prompt_mask):
        self.rng, key = jax.random.split(self.rng)
        return self._sample_jit(
            self.rollout_bundle(),
            prompt_ids,
            prompt_mask,
            key,
        )

    @property
    def eval_batch_size(self) -> int:
        return self.config.train.batch_size

    def learn(self) -> Dict[str, Any]:
        """Offline optimization loop (reference `accelerate_base_model.py
        :224-305` without experience refresh)."""
        train = self.config.train
        if self.store is None:
            raise ValueError("no offline data: run OfflineOrchestrator.make_experience")

        # resume (reference Ray session restore, `accelerate_base_model.py:
        # 232-240`)
        if train.resume_from_checkpoint and has_checkpoint(train.checkpoint_dir):
            self.load(train.checkpoint_dir)

        n_minibatches = max(len(self.store) // train.batch_size, 1)
        total_steps = min(train.total_steps, train.epochs * n_minibatches)

        logger = Logger(
            project_name=train.project_name,
            run_name=train.run_name,
            config=self.config.to_dict(),
            tags=train.tags,
            total_steps=total_steps,
        )
        self.logger = logger
        try:
            result = self._learn_body(logger, total_steps, n_minibatches)
        except BaseException as e:
            # crash forensics (telemetry/flight_recorder.py): no-op when
            # health is off, at most one dump per run
            self.flight_dump_on_exception(e)
            # run ledger (telemetry/run_ledger.py): the failed-run
            # manifest records the error outcome
            self.append_run_ledger(status="error", error=e)
            raise
        else:
            self.append_run_ledger(status="ok")
            return result
        finally:
            # single epilogue for every exit (incl. exceptions): join
            # in-flight async checkpoint writes, close the logger even if
            # that join raises
            try:
                wait_for_checkpoints()
            finally:
                logger.finish()

    def _learn_body(
        self, logger: Logger, total_steps: int, n_minibatches: int
    ) -> Dict[str, Any]:
        train = self.config.train
        stats = self.evaluate()
        logger.log(stats, step=0)

        clock = Clock()
        self._chunk_index = -1  # flight-recorder "phase" = fused chunk
        iter_count = int(self.state.step)  # nonzero after resume
        if iter_count >= total_steps:
            self._final_stats = {}
            return {}
        final_stats: Dict[str, Any] = {}
        # Chunked fused loop: consecutive updates up to the next eval/save
        # boundary (or total_steps) run as one scanned dispatch; per-step log
        # rows are replayed from the stacked stats, so cadence matches the
        # stepwise loop exactly.
        MAX_CHUNK = 32

        def next_chunk_len(step: int, remaining_mbs: int) -> int:
            k = min(MAX_CHUNK, remaining_mbs, total_steps - step)
            for boundary in (train.eval_interval, train.checkpoint_interval):
                to_boundary = boundary - (step % boundary)
                k = min(k, to_boundary)
            return max(k, 1)

        # Resume alignment (docs/resilience.md): a run resumed at step s
        # continues the SAME epoch/minibatch schedule the uninterrupted
        # run would — epoch s // n_minibatches, at minibatch
        # s % n_minibatches of that epoch's seeded order — instead of
        # retraining the early epochs and never reaching the schedule's
        # tail before total_steps cuts the run off.
        epoch0 = iter_count // n_minibatches
        row0 = iter_count % n_minibatches
        for epoch in range(epoch0, train.epochs):
            order = self.store.epoch_order(
                train.batch_size, shuffle=True, seed=train.seed + epoch
            )
            row = row0 if epoch == epoch0 else 0
            while row < len(order):
                k = next_chunk_len(iter_count, len(order) - row)
                mbs = self.store.stacked_slice(
                    order[row : row + k], sharding=self._stacked_batch_sh
                )
                row += k
                # free the compute-dtype sampler bundle through the train
                # chunk (memory high-water mark); eval recasts lazily
                self._rollout_bundle_cache = None
                self.state, stacked = self._train_chunk_jit(self.state, mbs)
                chunk_time = clock.tick(train.batch_size) / 1000.0
                # one transfer event for the whole stacked stats tree AND
                # the step counter — save() reuses the fetched step instead
                # of paying its own device_get round-trip
                rows, host_step = jax.device_get((stacked, self.state.step))
                self._chunk_index += 1
                if self.health_monitor is not None:
                    # every fetched chunk row feeds the detectors — the
                    # batched transfer above already paid; one flight
                    # record per chunk (the ILQL "phase"). BEFORE
                    # check_anomalies: a NaN chunk must reach the
                    # nan-precursor trip + flight ring before the
                    # anomaly abort raises
                    hrow = self.observe_health_rows(
                        rows, step0=iter_count, phase=self._chunk_index
                    )
                    self.record_flight_phase(
                        self._chunk_index, step=iter_count + k,
                        stats_row=hrow,
                    )
                self.check_anomalies(rows, iter_count)
                for j in range(k):
                    iter_count += 1
                    step_stats = {key: float(v[j]) for key, v in rows.items()}
                    step_stats["time/batch"] = chunk_time / k
                    if iter_count % train.log_interval == 0:
                        logger.log(step_stats, step=iter_count)
                        final_stats = dict(step_stats)
                iv = self.intervals(iter_count)
                if iv["do_eval"] and iter_count < total_steps:
                    eval_stats = self.evaluate()
                    logger.log(eval_stats, step=iter_count)
                    final_stats.update(eval_stats)
                if iv["do_save"] and iter_count < total_steps:
                    self.save(step=int(host_step))
                if iter_count >= total_steps:
                    self.save(step=int(host_step))
                    eval_stats = self.evaluate()
                    logger.log(eval_stats, step=iter_count)
                    final_stats.update(eval_stats)
                    self._final_stats = final_stats
                    return final_stats
                # preemption drain point (docs/resilience.md): the ILQL
                # "phase boundary" is the fused chunk — emergency
                # checkpoint + PreemptionDrain before the next dispatch
                self.maybe_drain(phase=self._chunk_index, step=iter_count)
        self._final_stats = final_stats
        return final_stats

    def save(
        self, directory: Optional[str] = None, step: Optional[int] = None
    ) -> None:
        """``step`` lets the train loop reuse its already-fetched counter
        (batched with the stats transfer) instead of a second round-trip."""
        if step is None:
            step = int(jax.device_get(self.state.step))
        save_checkpoint(
            directory or self.config.train.checkpoint_dir,
            self.state,
            metadata=self._save_metadata(),
            async_save=self.config.train.async_checkpoint,
            step=step,
        )

    def _save_metadata(self) -> Dict[str, Any]:
        """Host-metadata pytree (JSON-safe; see the resume auditor's
        ``state_manifest`` lock)."""
        return {
            # sample() splits self.rng per call: without carrying the
            # chain, a resumed run's post-resume samples would replay
            # the seed-time keys and diverge from the uninterrupted run
            # (the resume-state gap engine 15's differ pins)
            "rng_key": np.asarray(jax.device_get(self.rng))
            .ravel()
            .tolist(),
            "host_state": self.host_state_dict(),
        }

    def load(self, directory: str) -> None:
        abstract = jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            self.state,
            self.state_shardings,
        )
        self.state, meta = load_checkpoint(directory, abstract)
        rng_key = meta.get("rng_key")
        if rng_key is not None:
            self.rng = jnp.asarray(
                np.asarray(rng_key, dtype=np.uint32).reshape(
                    np.shape(self.rng)
                )
            )
        self.load_host_state_dict(meta.get("host_state") or {})
