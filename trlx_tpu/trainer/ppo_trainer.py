"""PPO trainer: jitted rollout sampling + jitted PPO updates over a mesh.

Re-design of ``AcceleratePPOModel`` (``trlx/model/accelerate_ppo_model.py``)
+ the training loop of ``AccelerateRLModel.learn``
(``accelerate_base_model.py:224-305``):

- The policy (backbone + value head) lives as a sharded param pytree in an
  explicit :class:`TrainState`; the frozen KL reference model is a second
  (backbone-only) param pytree — the fork's full-frozen-copy path
  (`ppo_orchestrator.py:41-43`) with no second process-visible module.
- ``loss()`` (`accelerate_ppo_model.py:79-128`) becomes one jitted
  ``train_step``: policy forward, response logprobs/values, GAE (reversed
  ``lax.scan``), clipped surrogate, grads, optax update — gradient sync is
  the psum GSPMD inserts for the sharded batch; there is no
  ``accelerator.backward``.
- Generation is the compiled sampler from ``ops/sampling.py``; behavior
  logprobs and values are emitted during decode, so the orchestrator's
  policy-recompute forward disappears.
- The KL coefficient is host loop state updated per batch via the adaptive
  controller (`accelerate_ppo_model.py:136-137`), passed into the reward
  computation as a device scalar (no retrace).

Model-family specifics (forward slicing, sampler construction, checkpoint
conversion) are isolated in overridable hooks; the seq2seq (T5/UL2) variant
lives in ``seq2seq_ppo_trainer.py``.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from trlx_tpu import telemetry
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.ppo_types import PPORolloutBatch
from trlx_tpu.models.heads import CausalLMWithValueHead
from trlx_tpu.ops.ppo_math import (
    PPOConfig,
    get_advantages_and_returns,
    kl_controller_update,
    policy_entropy,
    ppo_loss,
    reward_health_stats,
)
from trlx_tpu.ops.sampling import (
    GenerationConfig,
    SampleOutput,
    make_sampler,
    validate_gen_config,
)
from trlx_tpu.parallel import (
    batch_sharding,
    logprobs_from_logits,
    make_partition_specs,
    make_mesh,
    replicated,
)
from trlx_tpu.parallel.mesh import traced_on
from trlx_tpu.pipeline.ppo_buffer import (
    PPORolloutBuffer,
    StreamPlan,
    make_stream_plan,
)
from trlx_tpu.trainer import BaseRLTrainer, register_trainer
from trlx_tpu.trainer.common import (
    TrainState,
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


def get_causal_arch(config: TRLConfig):
    """(family, arch config, optional converted checkpoint params) for the
    configured causal model_type (reference ``get_arch``,
    `accelerate_ppo_model.py:56-59`, generalized over gpt2/gptj/gpt_neox)."""
    from trlx_tpu.models.registry import get_model_family

    family = get_model_family(config.model.model_type)
    overrides = dict(config.model.model_arch)
    overrides.setdefault("dtype", config.train.dtype)
    overrides.setdefault("param_dtype", config.train.param_dtype)
    if config.model.model_path:
        arch, params = family.load_checkpoint(
            config.model.model_path, dtype=config.train.param_dtype
        )
        arch = type(arch)(
            **{
                **arch.__dict__,
                "dtype": overrides["dtype"],
                "param_dtype": overrides["param_dtype"],
            }
        )
        return family, arch, params
    return family, family.config_cls.from_dict(overrides), None


def get_gpt2_arch(config: TRLConfig):
    """Back-compat shim; prefer :func:`get_causal_arch`."""
    _, arch, params = get_causal_arch(config)
    return arch, params


# canonical definition lives in ops/ppo_math.py (shared with ilql_loss);
# the underscore alias keeps this module's historical import surface
# (seq2seq_ppo_trainer imports it from here)
_policy_entropy = policy_entropy


class _StreamedPhase:
    """Host-side state of one streamed collect→train phase
    (docs/async_pipeline.md): the fixed update plan, the dispatch cursor
    over epoch-1 minibatches, their pending stats, and the monotonic
    marks (the tracer's clock) the overlap attribution is computed
    from."""

    def __init__(self, plan: StreamPlan, overlap: bool):
        self.plan = plan
        self.overlap = overlap
        self.next_mb = 0  # epoch-1 minibatches dispatched so far
        self.epoch1_stats: List[Dict[str, jax.Array]] = []
        self.t_first_dispatch: Optional[float] = None
        self.dispatched_during_collect = 0


class _AsyncStreamedPhase(_StreamedPhase):
    """The asynchronous actor–learner phase's extra host state
    (trainer/async_rl.py): the learner's update-version counter, the
    per-consumed-minibatch staleness record, guard-hold / learner-busy
    wall time (the ``async/*`` attribution stats), and the weight-push
    count. The underlying plan/dispatch machinery is the streamed
    phase's — async is a *policy* over when dispatches happen and what
    params the actors hold, never a different schedule."""

    def __init__(self, plan: StreamPlan, overlap: bool):
        super().__init__(plan, overlap)
        self.learner_version = 0
        self.staleness: List[int] = []  # in-flight lag per consumed mb
        self.consumed_lag: List[int] = []  # row age at consumption
        self.weight_pushes = 0
        self.guard_hold_ms = 0.0  # row-ready time spent behind the guard
        self.t_guard_hold: Optional[float] = None
        self.learner_busy_ms = 0.0  # epoch-1 dispatch spans (+ residual)
        self.t_begin = telemetry.monotonic()
        # set by finish_streamed_phase before the forced drain: rollouts
        # still in flight then (a chunk-rounded over-submission) can
        # never land into THIS plan, so they neither hold the staleness
        # accounting nor deserve further weight pushes
        self.collect_done = False


@register_trainer
class PPOTrainer(BaseRLTrainer):
    # param-tree key holding the (KL-reference) backbone
    backbone_key = "transformer"

    def __init__(
        self,
        config: TRLConfig,
        reward_fn: Optional[Callable] = None,
        metric_fn: Optional[Callable] = None,
        tokenizer=None,
        logit_mask=None,
    ):
        super().__init__(config, reward_fn, metric_fn, tokenizer, logit_mask)
        method: PPOConfig = config.method
        train = config.train

        self.mesh = make_mesh(train.mesh)
        self.rng = set_seed(train.seed)
        # grouped sampling (orchestrator repeats each chunk prompt G times);
        # scale_reward "group" whitens scores within each group. Validated
        # before any model construction — config errors should be instant.
        self.group_size = int(getattr(method, "group_size", 1) or 1)
        if method.scale_reward == "group" and self.group_size < 2:
            raise ValueError(
                'scale_reward "group" needs method.group_size >= 2 '
                f"(got {self.group_size})"
            )

        from trlx_tpu.trainer.grpo_trainer import GRPOConfig, GRPOMixin

        if isinstance(method, GRPOConfig) and not isinstance(self, GRPOMixin):
            # GRPO needs the grouped sampler expansion + advantage path;
            # running its config through plain PPO would silently train
            # classic PPO with vf_coef=0 on ungrouped rollouts
            raise ValueError(
                "method GRPOConfig requires a GRPO trainer (GRPOTrainer / "
                f"Seq2SeqGRPOTrainer); got {type(self).__name__}"
            )

        if tokenizer is None and config.model.tokenizer_path:
            from transformers import AutoTokenizer

            self.tokenizer = AutoTokenizer.from_pretrained(
                config.model.tokenizer_path, local_files_only=True
            )
            if self.tokenizer.pad_token_id is None:
                self.tokenizer.pad_token = self.tokenizer.eos_token

        init_params = self._setup_model()

        # Pipeline parallelism: with a pp axis of size > 1, the PPO
        # update's full-sequence forwards (policy response_forward + frozen
        # ref) run the transformer blocks through the GPipe pipeline
        # (`models/pp_runner.py`); embed/heads and the sampler run under
        # plain GSPMD, replicated over pp.
        self.pp_stages = dict(self.mesh.shape).get("pp", 1)
        self.pp_microbatches = train.pp_microbatches
        self.pp_virtual_stages = train.pp_virtual_stages
        self.pp_remat = train.pp_remat
        if self.pp_remat and self.pp_virtual_stages > 1:
            raise NotImplementedError(
                "pp_remat runs the v=1 schedule; drop pp_virtual_stages "
                "or pp_remat (the two memory/bubble trades do not compose "
                "yet)"
            )
        if self.pp_stages > 1:
            self._validate_pp_mesh(config, train)

        gen_kwargs = dict(method.gen_kwargs)
        self.apply_tokenizer_gen_defaults(gen_kwargs)
        self._amend_gen_kwargs(gen_kwargs)
        self.gen_config = GenerationConfig.from_dict(gen_kwargs)
        # decode-budget sizing state for bind_prompt_budget: the
        # configured ceiling, and the min real prompt length of every
        # pipeline bound so far (train + eval)
        self._gen_budget_cap = self.gen_config.max_new_tokens
        self._bound_min_prompts: Dict[str, int] = {}
        self.query_length = train.seq_length
        self._check_response_budget(train)
        validate_gen_config(
            self.gen_config,
            getattr(self.model_config, "vocab_size", None),
            provided=set(gen_kwargs),
        )
        # rollout engine selection (train.rollout; docs/inference.md):
        # "continuous" drives collection through the slot-admission
        # engine (trlx_tpu/inference/engine.py) instead of the
        # fixed-batch sampler; per-row RNG keys make the two engines
        # per-row token-identical, so the fixed path stays the parity
        # baseline. Parsed before _build_jitted_fns: per_row_rng changes
        # the sampler's compiled key plumbing.
        from trlx_tpu.inference import RolloutEngineConfig

        self.rollout_config = RolloutEngineConfig.from_dict(train.rollout)
        self.rollout_engine = self.rollout_config.engine
        if self.rollout_engine == "continuous":
            self._validate_continuous_engine()
        # Asynchronous actor–learner mode (train.async_rl,
        # trainer/async_rl.py, docs/async_pipeline.md): the streamed
        # phase gains version-tagged rollouts, a bounded-staleness
        # version-lag guard, and in-flight weight pushes to the engine.
        # Parsed here (after the rollout engine) because async requires
        # the continuous engine — the actors ARE the engine.
        from trlx_tpu.trainer.async_rl import AsyncRLConfig

        self.async_config = AsyncRLConfig.from_dict(train.async_rl)
        if self.async_config.enabled:
            self._validate_async_rl()
        # actor device-subset state (async_rl.actor_fraction < 1): built
        # lazily with the engine; None = actors share the trainer mesh
        self._actor_mesh = None
        self._actor_param_shardings = None
        if self.rollout_config.rows_per_row_rng:
            import dataclasses

            self.gen_config = dataclasses.replace(
                self.gen_config, per_row_rng=True
            )
        self._rollout_engine_obj = None
        # per-row RNG phase state: one phase key (split from self.rng
        # exactly once per collect phase, lazily) + a row cursor counting
        # rows in draw order — fold_in(phase_key, draw_index) is each
        # row's base key on BOTH engines, which is what makes their
        # rollouts comparable row-by-row
        self._rollout_phase_key = None
        self._rollout_row_cursor = 0
        if train.logprob_chunk:
            if train.logprob_chunk < 0:
                raise ValueError(
                    f"train.logprob_chunk={train.logprob_chunk} must be >= 0"
                )
            if not self._supports_logprob_chunk():
                # a silently-ignored memory flag is worse than a refusal
                raise NotImplementedError(
                    f"train.logprob_chunk is not supported by "
                    f"{type(self).__name__} (causal-path feature; the "
                    f"seq2seq forward computes its own logits); remove "
                    f"the key"
                )
            if self.gen_config.max_new_tokens % train.logprob_chunk:
                raise ValueError(
                    f"train.logprob_chunk={train.logprob_chunk} must divide "
                    f"gen max_new_tokens={self.gen_config.max_new_tokens}"
                )

        # --- params, shardings, optimizer, state ---
        self.rng, init_rng = jax.random.split(self.rng)
        params = self._init_params(init_rng)
        if init_params is not None:
            params[self.backbone_key] = init_params

        self.param_shardings = self._shardings_for(params)
        params = jax.device_put(params, self.param_shardings)

        # Frozen KL reference. Two modes, as upstream (`ppo_models.py:505-558`
        # vs `ppo_orchestrator.py:41-43`):
        # - hydra (branch depth > 0): keep only the top-k blocks + ln_f +
        #   embedding as the frozen branch; the (frozen) trunk is shared
        #   with the policy — half the reference-model memory;
        # - full copy otherwise (the fork's active path for T5).
        # The branch depth is `model.ref_branch_layers` when set, else
        # `num_layers_unfrozen` — decoupled because in the reference as
        # shipped num_layers_unfrozen ONLY sizes the branch
        # (`ppo_models.py:525-536`; the freezing block is commented out)
        # while the policy trains all layers.
        # jnp.copy forces fresh buffers — the policy's are donated each step.
        self.ref_branch = config.model.resolved_ref_branch_layers
        if not 0 <= self.ref_branch <= self._n_layers():
            key = (
                "model.ref_branch_layers"
                if config.model.ref_branch_layers is not None
                # unset: the value defaulted from num_layers_unfrozen —
                # name the key the user actually wrote
                else "model.num_layers_unfrozen"
            )
            raise ValueError(
                f"{key}={self.ref_branch} must be in "
                f"[0, n_layer={self._n_layers()}]"
            )
        self.use_hydra = self.ref_branch > 0 and self._supports_hydra()
        if self.use_hydra:
            self.branch_start = self._n_layers() - self.ref_branch
            backbone = params[self.backbone_key]
            # keep top-k blocks + everything the LM head path needs (ln_f,
            # tied wte or untied lm_head); drop trunk blocks + wpe
            ref_subset = {
                k: v
                for k, v in backbone.items()
                if not k.startswith(("h_", "wpe"))
                or (k.startswith("h_") and int(k.split("_")[1]) >= self.branch_start)
            }
            self.ref_shardings = self._shardings_for(ref_subset)
            self.ref_params = jax.device_put(
                jax.tree_util.tree_map(jnp.copy, ref_subset), self.ref_shardings
            )
        else:
            self.ref_shardings = self._shardings_for(params[self.backbone_key])
            self.ref_params = jax.device_put(
                jax.tree_util.tree_map(jnp.copy, params[self.backbone_key]),
                self.ref_shardings,
            )

        trainable = unfrozen_param_mask(
            params, config.model.num_layers_unfrozen, self._n_layers()
        )
        self.trainable_mask = trainable
        self.tx = make_optimizer(train, train.total_steps, trainable)
        opt_shapes = jax.eval_shape(self.tx.init, params)
        self.opt_shardings = self._shardings_for(opt_shapes)
        opt_state = jax.jit(self.tx.init, out_shardings=self.opt_shardings)(params)

        self.state_shardings = TrainState(
            params=self.param_shardings,
            opt_state=self.opt_shardings,
            step=replicated(self.mesh),
        )
        # the counter is placed as the train step returns it: left
        # uncommitted, its type differs from the returned state's by the
        # mesh, and the second update of a run traces, lowers, keys and
        # loads the whole train program again (PERF.md §6, PR 37)
        self.state = TrainState(
            params=params,
            opt_state=opt_state,
            step=jax.device_put(
                jnp.zeros((), jnp.int32), self.state_shardings.step
            ),
        )

        self.buffer = PPORolloutBuffer()
        self.kl_coef = float(method.init_kl_coef)
        self.mean_kl = 0.0
        # streamed collect→train phase state (docs/async_pipeline.md):
        # while a phase is active, `_behavior_params` is the frozen
        # behavior-policy snapshot every sampler/ref forward runs on —
        # epoch-1 updates mutate `self.state` underneath without touching
        # rollout semantics.
        self._stream: Optional[_StreamedPhase] = None
        self._behavior_params = None
        self._last_overlap_stats: Dict[str, float] = {}
        self._last_phase_mean_kl = 0.0
        # phase counter + single-phase profiler window (telemetry/
        # profiler.py): _collect_phase opens phase N, the learn-loop's
        # phase epilogue closes it (train.profile_phase). A disabled
        # placeholder until learn() arms it so orchestrator-driven runs
        # outside learn (bench, A/Bs) can hit the hooks safely.
        from trlx_tpu.telemetry.profiler import PhaseProfiler

        self._phase_index = -1
        self._phase_profiler = PhaseProfiler(None, None)
        # health/flight phase id for direct drivers of the phase API
        # (bench, perf/health-smoke harnesses): learn() advances
        # _phase_index via _collect_phase; outside learn it stays -1,
        # so health_phase_id falls back to a counter bumped by
        # begin_streamed_phase
        self._health_phase = -1

        self.setup_ep_axis(self.mesh, self.family)
        # MoE families contribute router load-balancing losses to the
        # training objective (collected via the "moe_losses" sow in
        # _forward_logprobs_values)
        self._moe_family = bool(getattr(self.family, "supports_ep", False))
        self._setup_rollout_cast(train)
        self._build_jitted_fns()

    # ------------------- rollout-phase weight precision ------------------ #

    def _supports_rollout_cast(self) -> bool:
        """Causal families keep bit-identical outputs under the cast (every
        op casts params to the compute dtype per use; see TrainConfig).
        Subclasses whose models consume f32 params directly override."""
        return True

    def _setup_rollout_cast(self, train) -> None:
        """Build the jitted master->compute-dtype param cast for the rollout
        phase (`rollout_param_cast`). Decode re-reads all weights once per
        token, so f32 masters double its HBM traffic; the sampler and the
        frozen ref instead get a compute-dtype copy, refreshed once per
        collect phase. Leaves computing in f32 — value/Q-head ``fc2``, MoE
        ``router`` — stay f32 so outputs are bit-identical."""
        self._rollout_cast_jit = None
        self._rollout_params_cache = None
        self._rollout_compute_dtype = None
        cdtype = jnp.dtype(getattr(self.model_config, "dtype", train.dtype))
        # the params' ACTUAL storage dtype is the arch's param_dtype (which
        # model_arch may override independently of train.param_dtype)
        pdtype = jnp.dtype(
            getattr(self.model_config, "param_dtype", train.param_dtype)
        )
        if (
            not getattr(train, "rollout_param_cast", False)
            or not self._supports_rollout_cast()
            or cdtype == pdtype
        ):
            return

        from trlx_tpu.utils import compute_dtype_cast

        def cast_tree(params):
            return compute_dtype_cast(params, cdtype)

        self._rollout_compute_dtype = cdtype
        self._rollout_cast_jit = jax.jit(
            cast_tree,
            in_shardings=(self.param_shardings,),
            out_shardings=self.param_shardings,
        )
        # the frozen ref is inference-only: cast once, permanently (also
        # halves its resident memory)
        self.ref_params = jax.jit(
            cast_tree,
            in_shardings=(self.ref_shardings,),
            out_shardings=self.ref_shardings,
        )(self.ref_params)

    def rollout_params(self):
        """Params the rollout phase runs on.

        While a streamed phase is active: the frozen behavior snapshot
        taken at :meth:`begin_streamed_phase` — NOT the live masters,
        which epoch-1 updates are mutating (and donating) underneath.
        Otherwise: the compute-dtype copy when the cast is enabled (recast
        lazily after each train phase — TrainState is replaced on update,
        so object identity detects staleness), else the f32 masters."""
        if self._behavior_params is not None:
            return self._behavior_params
        if self._rollout_cast_jit is None:
            return self.state.params
        master = self.state.params
        cache = self._rollout_params_cache
        if cache is None or cache[0] is not master:
            self._rollout_params_cache = (master, self._rollout_cast_jit(master))
        return self._rollout_params_cache[1]

    # ----------------------- model-family hooks ----------------------- #

    def _setup_model(self):
        """Build arch config + flax modules; return converted checkpoint
        params (or None)."""
        self.family, self.model_config, init_params = get_causal_arch(self.config)
        self.model = CausalLMWithValueHead(
            self.model_config, backbone_cls=self.family.backbone_cls
        )
        self.backbone = self.family.backbone_cls(self.model_config)
        self.partition_rules = self.family.partition_rules
        return init_params

    def _amend_gen_kwargs(self, gen_kwargs: Dict) -> None:
        pass

    def _validate_pp_mesh(self, config, train) -> None:
        """Family/shape checks for a pp axis > 1 (overridable per trainer:
        the seq2seq variant validates both T5 stacks instead)."""
        from trlx_tpu.models.pp_runner import supports_pp

        if not supports_pp(self.model_config):
            raise NotImplementedError(
                f"pp mesh axis is integrated for the causal families "
                f"(gpt2/gptj/gpt_neo/gpt_neox) but not "
                f"{type(self.model_config).__name__}: MoE layers have "
                f"non-uniform per-layer params (no stage stacking); "
                f"use dp/fsdp/tp/sp/ep instead"
            )
        L = self._n_layers()
        if L % self.pp_stages:
            raise ValueError(
                f"n_layer={L} must divide into pp={self.pp_stages} stages"
            )
        if config.model.resolved_ref_branch_layers > 0:
            # hydra under pp needs the branch point on a stage boundary
            # (the capture is a stage's input — round 3; previously
            # refused outright)
            chunk = L // self.pp_stages
            branch = L - config.model.resolved_ref_branch_layers
            if branch % chunk:
                raise NotImplementedError(
                    f"hydra under pp needs the branch point on a stage "
                    f"boundary: L={L}, pp={self.pp_stages} gives stage "
                    f"size {chunk}, but L - ref_branch_layers = "
                    f"{branch}; adjust num_layers_unfrozen / "
                    f"ref_branch_layers or use the full-copy reference"
                )
            if train.pp_virtual_stages > 1:
                raise NotImplementedError(
                    "hydra under pp runs the v=1 schedule (the branch "
                    "capture is a single stage's input, which the "
                    "interleaved schedule does not expose); drop "
                    "pp_virtual_stages or use the full-copy reference"
                )

    def _check_response_budget(self, train) -> None:
        """Every rollout must have >= 1 response token by construction: a
        zero-length response's terminal score lands on a masked slot and
        GAE (`ops/ppo_math.py` rewards*mask) silently zeroes it. For causal
        LMs, gen max_length caps prompt + generated — but whether a prompt
        can fill that budget depends on *real* (non-pad) prompt lengths,
        which only the pipeline knows (train.seq_length is just the padded
        width; the reference's own `configs/ppo_config.yml` pairs
        max_length 49 with seq_length 512 and is valid because its prompts
        are short). The exact check runs in :meth:`bind_prompt_budget`
        when the orchestrator attaches the training pipeline."""

    def bind_prompt_budget(self, pipeline, role: str = "train") -> None:
        """Validate + bound the decode budget against a bound pipeline's
        real prompt lengths (causal: ``max_length`` caps prompt +
        generated).

        - ``role="train"``: raises when some prompt already fills
          ``max_length`` — its rollout would have zero response tokens,
          whose terminal score lands on a masked slot and GAE silently
          drops it. For ``role="eval"`` the same situation only warns
          (an empty eval generation is scored as an empty string, not
          a corrupted update).
        - Sizes ``max_new_tokens`` to the largest per-row budget over
          *all* bound pipelines (``max_length`` − shortest real prompt
          anywhere) when the config over-allocated (reference configs
          write HF's ``max_length``; ``GenerationConfig.from_dict`` maps
          it to the decode budget) — the compiled decode then scans
          fewer steps and sizes a smaller KV cache, without capping a
          later-bound short-prompt eval pipeline below its entitlement.
          Rebuilds the jitted sampler on change.
        """
        max_len = self.gen_config.max_length
        longest = getattr(pipeline, "max_prompt_tokens", None)
        if max_len <= 0 or longest is None or not len(pipeline):
            return
        if longest >= max_len:
            msg = (
                f"a prompt with {longest} real tokens fills gen_kwargs "
                f"max_length={max_len} (prompt + generated), leaving "
                "zero response tokens; raise max_length, shorten the "
                "prompts, or use max_new_tokens"
            )
            if role == "train":
                raise ValueError(
                    msg + " (a zero-length rollout's terminal reward is "
                    "silently dropped by PPO)"
                )
            import warnings

            warnings.warn(msg + " (eval will score an empty string)")
        # keyed by role so a *replaced* pipeline overrides (not
        # min-accumulates) its predecessor's entitlement — the budget can
        # re-shrink when a short-prompt eval pipeline is swapped out
        self._bound_min_prompts[role] = int(pipeline.min_prompt_tokens)
        budget = max_len - min(self._bound_min_prompts.values())
        new = min(self._gen_budget_cap, budget) if budget > 0 else (
            self._gen_budget_cap
        )
        if new != self.gen_config.max_new_tokens:
            import dataclasses

            self.gen_config = dataclasses.replace(
                self.gen_config, max_new_tokens=new
            )
            self._rebuild_sampler()

    def add_eval_pipeline(self, pipeline) -> None:
        super().add_eval_pipeline(pipeline)
        self.bind_prompt_budget(pipeline, role="eval")

    def _n_layers(self) -> int:
        from trlx_tpu.models.registry import num_layers_of

        return num_layers_of(self.model_config)

    def _init_params(self, rng):
        dummy = jnp.zeros((1, 8), jnp.int32)
        return self.model.init(rng, dummy)["params"]

    def _make_sampler(self) -> Callable:
        """Jittable (params, prompt_ids, prompt_mask, rng) -> SampleOutput.

        Under a pp mesh the rollout runs the pipelined cached forward with
        STAGE-RESIDENT KV buffers (`models/pp_runner.py`): each pp device
        holds only its stage's layers + cache during the dominant phase,
        instead of a full replicated copy."""
        if self.pp_stages > 1:
            from trlx_tpu.models.pp_runner import (
                make_pp_sampler_apply,
                pp_decode_kit,
                pp_stack_sampler_params,
            )

            init_cache_fn, cache_sharding = pp_decode_kit(
                self.model_config, self.mesh
            )
            inner = make_sampler(
                make_pp_sampler_apply(
                    self.model_config, self.mesh, self.pp_microbatches
                ),
                init_cache_fn,
                self.gen_config,
                self.query_length,
                with_values=True,
                cache_sharding=cache_sharding,
            )

            def sampler(params, prompt_ids, prompt_mask, rng):
                # stack/reshard the trunk blocks ONCE per invocation, not
                # once per decoded token inside the sampler's scan
                packed = pp_stack_sampler_params(
                    self.model_config, self.mesh, params
                )
                return inner(packed, prompt_ids, prompt_mask, rng)

            return sampler

        def apply_fn(params, input_ids, attention_mask=None, position_ids=None,
                     cache=None, cache_index=None, last_only=False):
            return self.model.apply(
                {"params": params},
                input_ids,
                attention_mask=attention_mask,
                position_ids=position_ids,
                cache=cache,
                cache_index=cache_index,
                last_only=last_only,
            )

        return make_sampler(
            apply_fn,
            functools.partial(self.family.init_cache, self.model_config),
            self.gen_config,
            self.query_length,
            with_values=True,
            cache_sharding=self._decode_cache_sharding(),
        )

    def _forward_logprobs_values(self, params, mb: PPORolloutBatch):
        """Policy forward -> (logprobs, values, entropy?, moe_losses?) over
        response positions.

        Causal LM: forward [query; response]; hidden states are sliced to
        positions Q-1..Q+R-2 (the states that *predict* each response token)
        *before* the LM/value heads run (``response_forward``). Per-position
        entropy is computed only when the entropy bonus is on. For MoE
        families the forward opens the ``moe_losses`` sow collection and
        returns the aggregated router regularizers (Switch aux + z-loss +
        load diagnostic) for the training loss."""
        Q = self.query_length
        full_ids = jnp.concatenate([mb.query_tokens, mb.response_tokens], axis=1)
        full_mask = jnp.concatenate([mb.query_mask, mb.response_mask], axis=1)
        moe = None
        if self.pp_stages > 1:
            from trlx_tpu.models.pp_runner import pp_response_forward

            logits, values = pp_response_forward(
                self.model_config, params, full_ids, full_mask, Q,
                self.mesh, self.pp_microbatches,
                virtual_stages=self.pp_virtual_stages,
                remat=self.pp_remat,
            )
        elif self._moe_family:
            from trlx_tpu.ops.moe import moe_loss_summary

            (logits, values), state = self.model.apply(
                {"params": params}, full_ids, full_mask, Q,
                method=self.model.response_forward, mutable=["moe_losses"],
            )
            moe = moe_loss_summary(state["moe_losses"])
        elif self._logprob_chunk_active():
            # chunked logprob/CE (train.logprob_chunk): head + log-softmax
            # + gather per chunk under jax.checkpoint — the full [B, R, V]
            # f32 logits buffer never materializes; bwd recomputes each
            # chunk's logits from its saved hidden slice
            hidden, values = self.model.apply(
                {"params": params}, full_ids, full_mask, Q,
                method=self.model.response_hidden,
            )
            c = self.config.train.logprob_chunk
            B, R, d = hidden.shape
            if R % c:
                raise ValueError(
                    f"train.logprob_chunk={c} does not divide the bound "
                    f"response width {R} (bind_prompt_budget shrank the "
                    f"decode budget); pick a chunk dividing both"
                )
            n = R // c
            hs = hidden.reshape(B, n, c, d).swapaxes(0, 1)  # [n, B, c, d]
            toks = mb.response_tokens.reshape(B, n, c).swapaxes(0, 1)
            backbone_params = params[self.backbone_key]

            @jax.checkpoint
            def chunk_logprobs(h_c, t_c):
                logits_c = self.backbone.apply(
                    {"params": backbone_params}, h_c,
                    method=self.backbone.logits,
                )
                return logprobs_from_logits(
                    logits_c.astype(jnp.float32), t_c
                )

            def body(carry, xs):
                h_c, t_c = xs
                return carry, chunk_logprobs(h_c, t_c)

            _, lps = jax.lax.scan(body, None, (hs, toks))
            logprobs = lps.swapaxes(0, 1).reshape(B, R)
            return logprobs, values.astype(jnp.float32), None, moe
        else:
            logits, values = self.model.apply(
                {"params": params}, full_ids, full_mask, Q,
                method=self.model.response_forward,
            )
        logprobs = logprobs_from_logits(logits, mb.response_tokens)
        # entropy also under health (train.health.enabled) at ent_coef=0:
        # the entropy-collapse detector needs the series; the loss only
        # consumes it when the bonus coefficient is nonzero
        entropy = (
            _policy_entropy(logits)
            if (self.config.method.ent_coef or self._health_enabled)
            else None
        )
        return logprobs, values.astype(jnp.float32), entropy, moe

    def _supports_logprob_chunk(self) -> bool:
        """Whether this trainer class can honor ``train.logprob_chunk``
        at all (the seq2seq trainer overrides its forward and returns
        False — the flag refuses loudly there instead of no-opping)."""
        return True

    def _logprob_chunk_active(self) -> bool:
        """Chunked logprobs apply on the plain causal path only: pp has
        its own response forward, MoE threads the sow collection through
        response_forward, and the entropy bonus needs full-vocab terms."""
        c = self.config.train.logprob_chunk
        return bool(c) and not (
            self.pp_stages > 1
            or self._moe_family
            or self.config.method.ent_coef
        )

    def _supports_hydra(self) -> bool:
        return True

    def _ref_logprobs(self, ref_params, policy_params, q_ids, q_mask, r_ids, r_mask):
        """KL-reference logprobs of the sampled responses.

        Hydra mode re-runs only the frozen-copy top blocks from the shared
        trunk's activation (`ppo_models.py:541-558`); ``policy_params``
        provide the trunk. Whether that trunk is stationary depends on the
        freezing config: with ``num_layers_unfrozen > 0`` the trunk layers
        are frozen and the reference is fixed; with the decoupled faithful
        config (``num_layers_unfrozen: 0`` + ``ref_branch_layers``) the
        trunk TRAINS, so the hydra reference drifts with the policy —
        exactly as the reference-as-shipped behaves (its
        ``forward_hydra`` reads the live trunk while only the branch
        copies are frozen). Do not cache these logprobs across updates."""
        Q = self.query_length
        full_ids = jnp.concatenate([q_ids, r_ids], axis=1)
        full_mask = jnp.concatenate([q_mask, r_mask], axis=1)
        if self.pp_stages > 1:
            if self.use_hydra:
                from trlx_tpu.models.pp_runner import pp_hydra_ref_logits

                logits = pp_hydra_ref_logits(
                    self.model_config, policy_params[self.backbone_key],
                    ref_params, full_ids, full_mask, Q, self.branch_start,
                    self.mesh, self.pp_microbatches,
                )
                return logprobs_from_logits(logits, r_ids)
            from trlx_tpu.models.pp_runner import pp_ref_logits

            logits = pp_ref_logits(
                self.model_config, ref_params, full_ids, full_mask, Q,
                self.mesh, self.pp_microbatches,
                virtual_stages=self.pp_virtual_stages,
            )
            return logprobs_from_logits(logits, r_ids)
        if self.use_hydra:
            trunk_out = self.backbone.apply(
                {"params": policy_params[self.backbone_key]},
                full_ids,
                attention_mask=full_mask,
                capture_hidden_at=self.branch_start,
                compute_logits=False,  # only the captured hidden is used
            )
            out = self.backbone.apply(
                {"params": ref_params},
                full_ids,
                attention_mask=full_mask,
                start_layer=self.branch_start,
                hidden_override=trunk_out["branch_hidden"],
                compute_logits=False,
            )
        else:
            out = self.backbone.apply(
                {"params": ref_params}, full_ids, attention_mask=full_mask,
                compute_logits=False,
            )
        # LM head only on response-predicting positions
        logits = self.backbone.apply(
            {"params": ref_params}, out["hidden"][:, Q - 1 : -1],
            method=self.backbone.logits,
        )
        return logprobs_from_logits(logits, r_ids)

    # ------------------------------------------------------------------ #

    def _shape_rewards(self, logprobs, ref_logprobs, response_mask, scores, kl_coef):
        """Per-token shaped rewards: −kl_coef·KL with the terminal score at
        the last valid slot (reference `ppo_orchestrator.py:163-167`).
        Jitted by ``_build_jitted_fns``; subclasses may post-process (GRPO
        stores group-normalized advantages here instead)."""
        maskf = response_mask.astype(jnp.float32)
        kl_per_token = (logprobs - ref_logprobs) * maskf
        rewards = -kl_coef * kl_per_token
        last = jnp.clip(jnp.sum(response_mask, axis=1) - 1, 0, None)
        rewards = rewards.at[jnp.arange(rewards.shape[0]), last].add(scores)
        mean_kl = jnp.mean(jnp.sum(kl_per_token, axis=1))
        return rewards, mean_kl

    def _advantages_and_returns(self, mb: PPORolloutBatch):
        """(advantages, returns) for the PPO loss — GAE over the stored
        values/rewards by default; traced inside the jitted train step."""
        method: PPOConfig = self.config.method
        return get_advantages_and_returns(
            mb.values, mb.rewards, mb.response_mask, method.gamma, method.lam
        )

    def _shardings_for(self, tree):
        specs = make_partition_specs(tree, self.mesh, self.partition_rules)
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s),
            specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    def _rebuild_sampler(self):
        """(Re)jit the rollout sampler from the current ``gen_config`` —
        called at construction and again by :meth:`bind_prompt_budget`
        when the decode budget shrinks (jit is lazy; no compile happens
        until the first rollout, so a rebuild before training is free)."""
        batch_sh = batch_sharding(self.mesh)
        rep = replicated(self.mesh)
        self._sample_jit = jax.jit(
            traced_on(self.mesh, self._make_sampler()),
            in_shardings=(self.param_shardings, batch_sh, batch_sh, rep),
            out_shardings=batch_sh,
        )
        # a changed decode budget resizes the engine's KV capacity and
        # output buffers — rebuild it lazily from the new gen_config
        self._rollout_engine_obj = None

    def _build_jitted_fns(self):
        method: PPOConfig = self.config.method
        batch_sh = batch_sharding(self.mesh)
        rep = replicated(self.mesh)
        self._batch_sh = batch_sh

        self._rebuild_sampler()

        # Behavior-policy snapshot for the streamed phase: the compute-dtype
        # cast (when enabled) plus an unconditional per-leaf copy. The copy
        # matters: pjit forwards pass-through inputs to outputs, so a leaf
        # the cast leaves untouched (ROLLOUT_CAST_EXCLUDE, or every leaf in
        # the no-cast path) would ALIAS the master buffer — which the very
        # first streamed train step donates. The snapshot must own every
        # buffer it serves to in-flight samplers.
        cast_active = self._rollout_cast_jit is not None
        snap_dtype = self._rollout_compute_dtype

        def behavior_snapshot(params):
            if cast_active:
                from trlx_tpu.utils import compute_dtype_cast

                params = compute_dtype_cast(params, snap_dtype)
            return jax.tree_util.tree_map(jnp.copy, params)

        self._behavior_snapshot_jit = jax.jit(
            behavior_snapshot,
            in_shardings=(self.param_shardings,),
            out_shardings=self.param_shardings,
        )
        # Async actor–learner weight push (trainer/async_rl.py): the
        # refreshed behavior policy actors receive MID-generation. Same
        # math as the phase-start snapshot — compute-dtype cast (when
        # enabled) + unconditional per-leaf copy, and the copy is just
        # as load-bearing here: the pushed tree must own every buffer it
        # hands the engine, because the very next train step donates the
        # masters it would otherwise alias. A separate jit instance so
        # the analysis harness audits the push program the async path
        # actually dispatches (subject ppo.async_weight_push).
        self._weight_push_jit = jax.jit(
            behavior_snapshot,
            in_shardings=(self.param_shardings,),
            out_shardings=self.param_shardings,
        )

        # device-trace scope names (ref_score, train_step, train_phase,
        # policy_forward, loss, optimizer) are a contract:
        # docs/observability.md "Device scope names"
        self._score_ref_jit = jax.jit(
            traced_on(
                self.mesh, jax.named_scope("ref_score")(self._ref_logprobs)
            ),
            in_shardings=(
                self.ref_shardings,
                self.param_shardings,
                batch_sh,
                batch_sh,
                batch_sh,
                batch_sh,
            ),
            out_shardings=batch_sh,
        )

        self._compute_rewards_jit = jax.jit(
            self._shape_rewards,
            in_shardings=(batch_sh, batch_sh, batch_sh, batch_sh, rep),
            out_shardings=(batch_sh, rep),
        )

        def train_step_with_adv(
            state: TrainState, mb: PPORolloutBatch, advantages, returns
        ):
            def loss_fn(params):
                # stop_gradient on frozen leaves: XLA prunes the backward
                # below the branch point (real work-avoidance when
                # num_layers_unfrozen > 0 re-enables the reference's
                # commented-out freezing)
                params = stop_frozen_gradients(params, self.trainable_mask)
                with jax.named_scope("policy_forward"):
                    logprobs, values, entropy, moe = (
                        self._forward_logprobs_values(params, mb)
                    )
                with jax.named_scope("loss"):
                    loss, stats = ppo_loss(
                        logprobs,
                        values,
                        mb.logprobs,
                        mb.values,
                        advantages,
                        returns,
                        mb.response_mask,
                        method.cliprange,
                        method.cliprange_value,
                        method.vf_coef,
                        ent_coef=method.ent_coef,
                        entropy=entropy,
                        health=self._health_enabled,
                        health_ev=self._health_ev,
                    )
                if moe is not None:
                    # Switch load-balancing: without this, top-1 routing
                    # collapses onto few experts once capacity drops are
                    # real (anything below capacity_factor >= n_experts)
                    from trlx_tpu.ops.moe import apply_router_penalty

                    loss, stats = apply_router_penalty(
                        loss, stats, moe, self.model_config
                    )
                return loss, stats

            (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params
            )
            with jax.named_scope("optimizer"):
                updates, new_opt_state = self.tx.update(
                    grads, state.opt_state, state.params
                )
                new_params = optax.apply_updates(state.params, updates)
            stats["optimizer/grad_norm"] = optax.global_norm(grads)
            if self._health_enabled:
                # shaped-return distribution next to the loss stats — a
                # pure extra output riding the same transfer, so the
                # one-transfer-per-update discipline holds (pinned in
                # tests/test_health.py)
                stats.update(
                    reward_health_stats(mb.rewards, mb.response_mask)
                )
            new_state = TrainState(
                params=new_params, opt_state=new_opt_state, step=state.step + 1
            )
            return new_state, stats

        @jax.named_scope("train_step")
        def train_step(state: TrainState, mb: PPORolloutBatch):
            advantages, returns = self._advantages_and_returns(mb)
            return train_step_with_adv(state, mb, advantages, returns)

        self._train_step_jit = jax.jit(
            traced_on(self.mesh, train_step),
            in_shardings=(self.state_shardings, batch_sh),
            out_shardings=(self.state_shardings, rep),
            donate_argnums=(0,),
        )

        @jax.named_scope("train_phase")
        def train_phase(state: TrainState, mbs: PPORolloutBatch):
            """One full buffer pass in a single dispatch: flat scan over
            [n_mb * ppo_epochs] pre-repeated minibatch slices (the reference
            inner loop, `accelerate_base_model.py:253-266`, realized as
            consecutive identical slices) — one train-step body to compile.

            GAE/whitening is params-INDEPENDENT, so it is hoisted out of
            the scan and computed for every minibatch in one batched pass:
            inside the scan it was a fresh R-step sequential chain per
            update, latency- and not compute-bound (its share of a train
            step is not measured on the chip). vmap turns the sequential
            chains into one
            chain of batched steps; per-minibatch whitening semantics are
            bitwise preserved (vmap axis = the minibatch axis the stats
            were already computed within)."""
            advantages, returns = jax.vmap(self._advantages_and_returns)(mbs)

            @jax.named_scope("train_step")
            def step(st, xs):
                mb, adv, ret = xs
                return train_step_with_adv(st, mb, adv, ret)

            return jax.lax.scan(step, state, (mbs, advantages, returns))

        from trlx_tpu.parallel.mesh import stacked_batch_sharding

        self._stacked_batch_sh = stacked_batch_sharding(self.mesh)
        self._train_phase_jit = jax.jit(
            traced_on(self.mesh, train_phase),
            in_shardings=(self.state_shardings, self._stacked_batch_sh),
            out_shardings=(self.state_shardings, rep),
            donate_argnums=(0,),
        )

    # --------------------- rollout engine (continuous) ----------------- #

    def _supports_continuous_engine(self) -> bool:
        """Causal-LM trainers share the engine's apply/cache contract;
        the seq2seq trainer (encoder/decoder split, cross-KV) overrides
        to refuse loudly instead of silently running the fixed path."""
        return True

    def _validate_continuous_engine(self) -> None:
        if not self._supports_continuous_engine():
            raise NotImplementedError(
                f"train.rollout engine 'continuous' is not supported by "
                f"{type(self).__name__} (causal-LM decode path); use "
                "engine: fixed"
            )
        if self.pp_stages > 1:
            raise NotImplementedError(
                "train.rollout engine 'continuous' does not compose with "
                "a pp mesh axis yet (the engine decodes under plain "
                "GSPMD; pp decode uses stage-resident KV buffers); use "
                "engine: fixed or drop the pp axis"
            )
        if self.group_size > 1:
            raise NotImplementedError(
                "train.rollout engine 'continuous' does not support "
                "grouped sampling (method.group_size > 1 / GRPO) yet: "
                "harvest groups complete in finish order, breaking the "
                "group-contiguity the grouped reward shaping assumes; "
                "use engine: fixed"
            )

    def _validate_async_rl(self) -> None:
        """``train.async_rl.enabled`` preconditions, checked at
        construction so config errors are instant: the actors ARE the
        continuous engine (whose own validation already refuses pp
        meshes, grouped/GRPO sampling, and seq2seq)."""
        if self.rollout_engine != "continuous":
            raise ValueError(
                "train.async_rl.enabled requires train.rollout.engine: "
                "'continuous' — the asynchronous actors run the "
                "slot-admission engine (docs/async_pipeline.md); add "
                "rollout: {engine: continuous} or disable async_rl"
            )
        if not self.config.train.phase_overlap:
            # the landing hook is the learner's whole consumption path;
            # with overlap globally off the run would be silently serial
            # while the user believes async is on — refuse loudly, like
            # every other invalid async combination
            raise ValueError(
                "train.async_rl.enabled requires train.phase_overlap: "
                "true (the streamed landing hook is how the async "
                "learner consumes rollouts); drop phase_overlap: false "
                "or disable async_rl"
            )

    def _to_actor(self, params):
        """Reshard a learner-mesh param tree onto the actor device
        subset (identity when actors share the trainer mesh). This is
        the learner→actor transfer of the disaggregated layout — on
        multi-host it becomes the ICI weight broadcast."""
        if self._actor_param_shardings is None:
            return params
        return jax.device_put(params, self._actor_param_shardings)

    def engine_start_params(self):
        """Params the engine's phase starts on: the behavior snapshot
        (or cast masters), resharded to the actor subset when one is
        configured."""
        return self._to_actor(self.rollout_params())

    def reset_rollout_phase(self) -> None:
        """Start a fresh rollout phase for per-row RNG: the next sampler
        or engine call derives a new phase key (ONE split of self.rng,
        identical across engines) and row indices restart at 0."""
        self._rollout_phase_key = None
        self._rollout_row_cursor = 0

    def rollout_phase_key(self):
        """The phase's per-row RNG base key (lazily split once)."""
        if self._rollout_phase_key is None:
            self.rng, self._rollout_phase_key = jax.random.split(self.rng)
        return self._rollout_phase_key

    def take_row_keys(self, n: int):
        """[n, 2] per-row keys for the next ``n`` drawn rows (advances
        the draw cursor) — the fixed sampler's per-row-RNG rng argument."""
        from trlx_tpu.ops.sampling import make_row_keys

        start = self._rollout_row_cursor
        self._rollout_row_cursor += n
        return make_row_keys(
            self.rollout_phase_key(), np.arange(start, start + n)
        )

    @property
    def rollout_engine_obj(self):
        """The continuous-batching engine, built on first use (after
        bind_prompt_budget has settled the decode budget)."""
        if self._rollout_engine_obj is None:
            self._rollout_engine_obj = self._build_rollout_engine()
        return self._rollout_engine_obj

    def _build_rollout_engine(self):
        from trlx_tpu.inference.engine import ContinuousBatchingEngine

        cfg = self.rollout_config
        chunk = int(
            getattr(self.config.method, "chunk_size", 0)
            or self.config.train.batch_size
        )
        num_slots = cfg.slots or chunk

        def apply_fn(params, input_ids, attention_mask=None,
                     position_ids=None, cache=None, cache_index=None,
                     last_only=False):
            return self.model.apply(
                {"params": params},
                input_ids,
                attention_mask=attention_mask,
                position_ids=position_ids,
                cache=cache,
                cache_index=cache_index,
                last_only=last_only,
            )

        # actor device subset (async_rl.actor_fraction < 1): the engine
        # lives on its own dp-only submesh; params reshard to it on
        # every weight push and harvest groups reshard back at landing —
        # the single-process rehearsal of multi-host actor/learner
        # placement (ROADMAP direction 3). cache sp-sharding does not
        # apply on the dp-only actor mesh.
        engine_mesh = self.mesh
        engine_shardings = self.param_shardings
        cache_sharding = self._decode_cache_sharding()
        admit_width = cfg.admit_width
        harvest_width = cfg.harvest_width
        if self.async_config.enabled and self.async_config.actor_fraction < 1:
            from trlx_tpu.trainer.async_rl import actor_submesh

            amesh = actor_submesh(self.mesh, self.async_config.actor_fraction)
            if amesh is not None:
                specs = make_partition_specs(
                    self.state.params, amesh, self.partition_rules
                )
                ashardings = jax.tree_util.tree_map(
                    lambda s: NamedSharding(amesh, s),
                    specs,
                    is_leaf=lambda x: isinstance(x, P),
                )
                self._actor_mesh = amesh
                self._actor_param_shardings = ashardings
                engine_mesh, engine_shardings = amesh, ashardings
                cache_sharding = None
                # harvest groups cross from the actor submesh to the
                # LEARNER mesh at landing (score_ref/rewards/store all
                # run there), so the admit/harvest widths must divide
                # over BOTH meshes' data shards — round them up to the
                # lcm here (the engine itself only knows its own mesh)
                import math

                shape = dict(self.mesh.shape)
                lshard = shape.get("dp", 1) * shape.get("fsdp", 1)
                ashape = dict(amesh.shape)
                ashard = ashape.get("dp", 1) * ashape.get("fsdp", 1)
                mult = math.lcm(lshard, ashard)

                def up(n: int) -> int:
                    return ((n + mult - 1) // mult) * mult

                admit_width = up(admit_width or max(1, num_slots // 4))
                harvest_width = up(harvest_width or admit_width)
                if harvest_width > num_slots:
                    raise ValueError(
                        f"async actor/learner meshes need harvest "
                        f"groups of a multiple of {mult} rows, but "
                        f"{harvest_width} exceeds the {num_slots}-slot "
                        "pool; raise rollout.slots or actor_fraction"
                    )

        spec = cfg.spec_decode
        spec_on = spec is not None and spec.enabled
        return ContinuousBatchingEngine(
            apply_fn=apply_fn,
            init_cache_fn=functools.partial(
                self.family.init_cache, self.model_config
            ),
            gen_config=self.gen_config,
            query_length=self.query_length,
            vocab_size=self.model_config.vocab_size,
            num_slots=num_slots,
            admit_width=admit_width,
            harvest_width=harvest_width,
            block_size=cfg.block_size,
            done_poll_interval=cfg.poll_interval,
            mesh=engine_mesh,
            param_shardings=engine_shardings,
            cache_sharding=cache_sharding,
            with_values=True,
            prefill_chunk=cfg.prefill_chunk,
            prefill_chunks_per_pump=cfg.prefill_chunks_per_pump,
            # the trainer path has no prefix pool, so rollout
            # spec_decode.drafter: trie degrades to the per-row n-gram
            # fallback (TrieDrafter with pool=None behaves identically)
            spec_max_draft=spec.max_draft if spec_on else 0,
            spec_min_accept_ewma=(
                spec.min_accept_ewma if spec_on else 0.0
            ),
        )

    # ------------------------------------------------------------------ #

    def sample(self, prompt_ids, prompt_mask) -> SampleOutput:
        """Run the compiled rollout sampler on a prompt batch."""
        if self.gen_config.per_row_rng:
            key = self.take_row_keys(prompt_ids.shape[0])
        else:
            self.rng, key = jax.random.split(self.rng)
        return self._sample_jit(
            self.rollout_params(), prompt_ids, prompt_mask, key
        )

    def score_ref(self, q_ids, q_mask, r_ids, r_mask):
        # policy params only feed the hydra trunk here (the CURRENT
        # trunk, trained or frozen per config — see _ref_logprobs) —
        # the compute-dtype copy is exact for it, and halves the read
        return self._score_ref_jit(
            self.ref_params, self.rollout_params(), q_ids, q_mask, r_ids, r_mask
        )

    def compute_rewards(self, logprobs, ref_logprobs, response_mask, scores):
        rewards, mean_kl = self._compute_rewards_jit(
            logprobs,
            ref_logprobs,
            response_mask,
            jnp.asarray(scores, jnp.float32),
            jnp.asarray(self.kl_coef, jnp.float32),
        )
        # Keep the rollout KL as a device scalar: pulling it to host here
        # would block on a transfer once per chunk. Consumers (KL
        # controller, stats logging) operate on it lazily; Logger.log
        # batches the eventual fetch.
        self.mean_kl = mean_kl
        return rewards

    def train_on_buffer(
        self, seed: int = 0, n_minibatches: Optional[int] = None
    ) -> Tuple[int, Dict[str, Any], List[float]]:
        """One fused buffer pass: every minibatch x ``ppo_epochs`` update in a
        single device dispatch (vs one dispatch per update). Returns
        ``(n_steps_taken, stacked_stats, kl_seq)``: each stats leaf has a
        leading [n_minibatches * ppo_epochs] dim (one row per update in
        execution order); ``kl_seq[k]`` is the KL coefficient after
        minibatch k (``kl_seq[0]`` = value on entry).

        The adaptive KL coefficient is advanced once per minibatch with the
        same compounding as the stepwise path (`accelerate_ppo_model.py:
        136-137`) — it only feeds the *next* experience collection, so
        updating it after the fused pass is exact.
        """
        train = self.config.train
        method: PPOConfig = self.config.method
        # n_minibatches (optional) fixes the pass size — learn() passes
        # its planned per-pass count so a buffer over-collected by a
        # non-dividing final chunk cannot train more updates than the
        # step accounting (iter_count / total_steps) assumes
        mbs = self.buffer.stacked_minibatches(
            train.batch_size, shuffle=True, seed=seed,
            sharding=self._stacked_batch_sh, repeat=method.ppo_epochs,
            n_minibatches=n_minibatches,
        )
        n_mb = len(self.buffer) // train.batch_size
        if n_minibatches is not None:
            n_mb = min(n_mb, n_minibatches)
        # the compute-dtype rollout copy is dead weight through the train
        # phase (the memory high-water mark); free it before dispatch —
        # it is recast from the new masters at the next collect anyway
        self._rollout_params_cache = None
        self.state, stats = self._train_phase_jit(self.state, mbs)
        kl_seq = [self.kl_coef]
        for _ in range(n_mb):
            kl_seq.append(
                kl_controller_update(
                    method, kl_seq[-1], self.mean_kl, train.batch_size
                )
            )
        self.kl_coef = kl_seq[-1]
        return n_mb * method.ppo_epochs, stats, kl_seq

    # ------------------ streamed collect→train phase ------------------ #
    #
    # The phase barrier between `make_experience` and the buffer pass is
    # broken while preserving EXACT on-policy semantics
    # (docs/async_pipeline.md):
    #
    # 1. `begin_streamed_phase` snapshots the behavior policy once (fresh
    #    buffers; donation-safe) and fixes the entire update schedule up
    #    front (`StreamPlan`) from the known rollout total;
    # 2. the orchestrator calls `on_rollouts_landed` after each chunk
    #    lands in the streaming buffer; epoch-1 minibatch updates are
    #    dispatched the moment their constituent rollouts exist — while
    #    later chunks are still decoding against the frozen snapshot;
    # 3. `finish_streamed_phase` dispatches any remainder, runs epochs
    #    2..ppo_epochs through the same train step, advances the KL
    #    controller once per minibatch (it only feeds the NEXT phase),
    #    and reports overlap attribution stats.
    #
    # Every rollout samples from the same frozen snapshot and behavior
    # logprobs are recorded at decode time, so the overlapped schedule is
    # semantically identical to running the same plan serially — pinned
    # bitwise in tests/test_phase_overlap.py.

    @property
    def health_phase_id(self) -> int:
        """Phase id health events and flight records are stamped with:
        learn()'s phase counter when it is driving, else the
        begin_streamed_phase fallback counter (direct drivers) — one
        id per phase across the collect window and the epilogue."""
        return (
            self._phase_index if self._phase_index >= 0
            else self._health_phase
        )

    def begin_streamed_phase(
        self,
        seed: int = 0,
        num_rollouts: Optional[int] = None,
        overlap: Optional[bool] = None,
    ) -> "_StreamedPhase":
        """Open a streamed phase: snapshot the behavior policy, fix the
        minibatch plan, and switch the buffer to incremental stream mode.
        ``overlap=False`` runs the identical schedule serially (every
        update dispatched in :meth:`finish_streamed_phase`) — the parity
        baseline."""
        if self._stream is not None:
            raise RuntimeError(
                "a streamed phase is already active; finish_streamed_phase "
                "(or abort_streamed_phase after an error) before beginning "
                "another"
            )
        # the phase's timing row starts here (health on), and so does
        # the first of the three spans that tile a streamed phase: what
        # the program does before the orchestrator's first
        # collect/dispatch (the plan, the buffer, the behaviour snapshot)
        self.mark_phase_timing()
        with telemetry.span("phase/begin", force=True):
            method: PPOConfig = self.config.method
            train = self.config.train
            total = int(num_rollouts if num_rollouts is not None
                        else method.num_rollouts)
            plan = make_stream_plan(
                total, train.batch_size, method.ppo_epochs, seed
            )
            if len(self.buffer):
                self.buffer.clear_history()
            self.buffer.begin_stream(plan.total)
            # direct drivers (bench, harnesses) never advance _phase_index;
            # bump the fallback health-phase id HERE so collect-window
            # events and the phase's flight record agree on the id
            self._health_phase += 1
            # the legacy lazy cast copy is dead weight once the snapshot exists
            self._rollout_params_cache = None
            # recorded so error recovery (the engine-fallback path in the
            # orchestrator) can re-begin THIS phase with the same plan seed
            self._last_stream_seed = seed
            # fresh per-row RNG phase: both rollout engines derive row keys
            # from the same single split, so a phase collected continuously
            # is row-comparable to the same phase collected fixed-batch
            self.reset_rollout_phase()
            self._behavior_params = self._behavior_snapshot_jit(self.state.params)
            # async actor–learner mode rides the streamed-phase machinery
            # with version/guard/push state on top (trainer/async_rl.py);
            # the explicit overlap=False escape (the serial parity baseline)
            # still runs the plain serial schedule even under async config
            phase_cls = (
                _AsyncStreamedPhase
                if self.async_config.enabled and overlap is not False
                else _StreamedPhase
            )
            self._stream = phase_cls(
                plan,
                overlap=train.phase_overlap if overlap is None else bool(overlap),
            )
            return self._stream

    def on_rollouts_landed(self) -> None:
        """Orchestrator hook, called after each rollout chunk lands in the
        buffer: dispatch every epoch-1 minibatch whose rows now exist.
        No-op outside a streamed phase or in serial (parity) mode."""
        st = self._stream
        if st is None or not st.overlap:
            return
        self._dispatch_ready_minibatches()

    def _dispatch_ready_minibatches(self, force: bool = False) -> None:
        st = self._stream
        plan = st.plan
        is_async = isinstance(st, _AsyncStreamedPhase)
        landed = len(self.buffer)
        while st.next_mb < plan.n_minibatches and (
            force or plan.ready(st.next_mb, landed)
        ):
            if is_async and not force:
                # version-lag guard (trainer/async_rl.py::guard_allows):
                # defer consumption whenever advancing the learner would
                # push any in-flight rollout's staleness past the
                # window. staleness_window=0 defers EVERYTHING while the
                # actors work — the bitwise-serial degenerate mode.
                from trlx_tpu.trainer.async_rl import guard_allows

                engine = self._rollout_engine_obj
                inflight = (
                    engine.min_inflight_version()
                    if engine is not None
                    else None
                )
                if not guard_allows(
                    st.learner_version,
                    inflight,
                    self.async_config.staleness_window,
                ):
                    # learner-idle attribution: rows are ready, the
                    # guard is what's holding them
                    if st.t_guard_hold is None:
                        st.t_guard_hold = telemetry.monotonic()
                    return
            if is_async and st.t_guard_hold is not None:
                st.guard_hold_ms += (
                    telemetry.monotonic() - st.t_guard_hold
                ) * 1000.0
                st.t_guard_hold = None
            # one span per epoch-1 dispatch: during collection these nest
            # strictly inside the phase/collect span (via collect/land),
            # which is how the trace shows what overlapped with what;
            # forced so the window mark survives a disabled tracer
            with telemetry.span(
                "train/epoch1_dispatch", force=True, minibatch=st.next_mb
            ) as sp:
                mb = self.buffer.gather(
                    plan.epoch1[st.next_mb], sharding=self._batch_sh
                )
                self.state, stats = self._train_step_jit(self.state, mb)
            if st.t_first_dispatch is None:
                st.t_first_dispatch = sp.start
            st.epoch1_stats.append(stats)
            st.next_mb += 1
            if is_async:
                self._after_async_update(st, plan, sp)

    def _after_async_update(
        self, st: "_AsyncStreamedPhase", plan: StreamPlan, sp
    ) -> None:
        """Async actor–learner bookkeeping after one consumed epoch-1
        minibatch: record its staleness (learner version at consumption
        minus the oldest behavior version among its rows), advance the
        learner version, and — while the actors still have work in
        flight — push the refreshed weights to the engine
        mid-generation (the in-flight update; the engine applies it at
        its harvest→admit safe point). No push once the actors are
        drained OR collection is closed: it could change nothing this
        plan consumes, and skipping it is what makes the
        staleness_window=0 run bitwise-serial (zero pushes ⇒ rollouts
        identical to the serial baseline — including when a
        chunk-rounded over-submission leaves rows in flight at the
        forced drain)."""
        # consumption lag (PipelineRL's "how old is the data"): learner
        # updates between a minibatch's oldest row being GENERATED and
        # it being trained — read from the stream store's version
        # column. Bounded by the plan (serial PPO has the same lag),
        # reported for attribution, never guarded on.
        consumed = plan.epoch1[st.next_mb - 1]
        st.consumed_lag.append(
            int(
                st.learner_version
                - int(self.buffer.row_versions(consumed).min())
            )
        )
        st.learner_version += 1
        st.learner_busy_ms += sp.duration_ms
        if st.collect_done:
            # post-collection (forced drain): nothing in flight can land
            # into this plan — the bounded in-flight lag is vacuously 0
            # and a push could only perturb the NEXT phase's snapshot
            st.staleness.append(0)
            return
        engine = self._rollout_engine_obj
        # the bounded quantity — in-flight generation lag AFTER this
        # update: how many learner versions ahead of the oldest rollout
        # still being generated the policy now is. The guard admitted
        # this update, so the recorded value is <= staleness_window by
        # construction; the staleness-breach detector watching the
        # phase max is therefore a true invariant check, not a tuning
        # knob. (Consumption lag — how many updates a LANDED row waits
        # before epoch-1 trains it — is bounded by the plan itself and
        # is not a staleness hazard: serial PPO has the same lag.)
        inflight = (
            engine.min_inflight_version() if engine is not None else None
        )
        st.staleness.append(
            0 if inflight is None
            else max(0, st.learner_version - int(inflight))
        )
        if engine is None or not engine.pending:
            return
        with telemetry.span(
            "async/weight_push", force=True, version=st.learner_version
        ) as push_sp:
            pushed = self._weight_push_jit(self.state.params)
            engine.push_weights(
                self._to_actor(pushed), version=st.learner_version
            )
        st.weight_pushes += 1
        st.learner_busy_ms += push_sp.duration_ms

    def finish_streamed_phase(
        self,
    ) -> Tuple[int, Dict[str, np.ndarray], List[float]]:
        """Close the active streamed phase: run everything the plan still
        owes (all of epoch 1 in serial mode; epochs 2..ppo_epochs always),
        advance the KL controller, and return ``(n_updates, rows,
        kl_seq)`` — ``rows`` maps each stats key to an [n_updates] host
        array in execution order (epoch-major: all epoch-1 updates, then
        epoch 2, ...)."""
        st = self._stream
        if st is None:
            raise RuntimeError("no streamed phase is active")
        method: PPOConfig = self.config.method
        train = self.config.train
        plan = st.plan

        # All phase timing below is span-sourced (telemetry/tracer.py):
        # the spans ARE the stopwatches — the same records feed the trace
        # exporter, bench's span payload, and the --perf-audit lockfile.
        # Forced spans still measure when the tracer is disabled (the
        # exp/overlap_* stats stay correct), they just go unrecorded.
        residual_ms = 0.0
        if isinstance(st, _AsyncStreamedPhase):
            st.collect_done = True
        with telemetry.span(
            "phase/train", force=True, updates=plan.n_updates
        ) as train_sp:
            t_collect_end = train_sp.start
            st.dispatched_during_collect = st.next_mb
            # Drain: how long the host still waits on epoch-1 device work
            # after collection ended (tail dispatches included). A serial
            # schedule pays the WHOLE epoch-1 compute here; overlap pays
            # only the unhidden tail. The fence was always at this
            # boundary — the span adds no new sync.
            with telemetry.span("train/drain", force=True) as drain_sp:
                self._dispatch_ready_minibatches(force=True)
                jax.block_until_ready(st.epoch1_stats[-1])
            drain_ms = drain_sp.duration_ms

            # the snapshot is dead weight for the residual epochs — drop
            # our reference before they are dispatched (in-flight consumers
            # keep the device buffers alive until they complete)
            self._behavior_params = None

            # Epochs 2..E through the SAME program as epoch 1, a dispatch a
            # minibatch and nothing blocked on between them: the fused scan
            # (`_train_phase_jit`) took twelve steps' device time to the
            # 0.1% and cost every run a second model-sized forward-and-
            # backward program to trace, lower, key and load (PERF.md §6,
            # PR 37). Same steps in the same order, so parameters and
            # statistics are the fused pass's.
            residual_stats = []
            if plan.residual.size:
                with telemetry.span("train/residual", force=True) as res_sp:
                    for row in plan.residual:
                        mb = self.buffer.gather(row, sharding=self._batch_sh)
                        self.state, stats = self._train_step_jit(
                            self.state, mb
                        )
                        residual_stats.append(stats)
                    jax.block_until_ready(self.state.params)
                residual_ms = res_sp.duration_ms

            # one transfer event for every host consumer of the phase
            step_rows, mean_kl = jax.device_get(
                (st.epoch1_stats + residual_stats, self.mean_kl)
            )
        rows: Dict[str, np.ndarray] = {
            key: np.stack([np.asarray(r[key]) for r in step_rows])
            for key in step_rows[0]
        }

        # adaptive KL controller: one update per minibatch, compounding as
        # the stepwise/fused paths do — it only feeds the NEXT collection,
        # so advancing it after the phase is exact
        self._last_phase_mean_kl = float(mean_kl)
        kl_seq = [float(self.kl_coef)]
        for _ in range(plan.n_minibatches):
            kl_seq.append(float(kl_controller_update(
                method, kl_seq[-1], self._last_phase_mean_kl,
                train.batch_size,
            )))
        self.kl_coef = kl_seq[-1]

        # Overlap attribution (exp/overlap_saved_ms). Ground truth would be
        # an interleaved A/B on the chip (no cell runs one; not measured);
        # these stats are the cheap per-phase estimate: epoch-1 serial cost is taken from the
        # residual pass (same programs, (ppo_epochs-1) identical epochs)
        # when available, else bounded by the dispatch window. Every term
        # is span-derived: drain/residual from their span durations, the
        # window from the first epoch-1 dispatch span's start mark.
        window_ms = (
            max(0.0, (t_collect_end - st.t_first_dispatch) * 1000.0)
            if st.t_first_dispatch is not None
            else 0.0
        )
        if method.ppo_epochs > 1 and residual_ms > 0.0:
            epoch1_est_ms = residual_ms / (method.ppo_epochs - 1)
            saved_ms = max(0.0, epoch1_est_ms - drain_ms)
        else:
            saved_ms = max(0.0, window_ms - drain_ms)
        self._last_overlap_stats = {
            "exp/overlap_saved_ms": saved_ms,
            "exp/overlap_drain_ms": drain_ms,
            "exp/overlap_window_ms": window_ms,
            "exp/overlap_streamed_updates": float(
                st.dispatched_during_collect
            ),
            "exp/phase_residual_ms": residual_ms,
        }
        # allocator gauges next to the phase timing (empty on backends
        # without memory_stats, e.g. CPU): live/peak HBM per phase rides
        # the same stats row the spans feed
        from trlx_tpu.telemetry.device_metrics import phase_memory_stats

        self._last_overlap_stats.update(phase_memory_stats())

        # async actor–learner attribution (docs/async_pipeline.md):
        # staleness distribution over consumed epoch-1 minibatches,
        # learner idle (post-collect drain + time row-ready minibatches
        # sat behind the version-lag guard), actor/learner occupancy,
        # and the in-flight push count. async/staleness (the max) is
        # the staleness-breach detector's series.
        async_staleness_max: Optional[float] = None
        if isinstance(st, _AsyncStreamedPhase):
            st.learner_busy_ms += residual_ms
            staleness = np.asarray(st.staleness or [0], np.float64)
            lag = np.asarray(st.consumed_lag or [0], np.float64)
            wall_ms = max(
                (telemetry.monotonic() - st.t_begin) * 1000.0, 1e-9
            )
            async_staleness_max = float(staleness.max())
            engine = self._rollout_engine_obj
            self._last_overlap_stats.update({
                "async/staleness_p50": float(np.percentile(staleness, 50)),
                "async/staleness_max": async_staleness_max,
                "async/consumed_lag_p50": float(np.percentile(lag, 50)),
                "async/consumed_lag_max": float(lag.max()),
                "async/weight_pushes": float(st.weight_pushes),
                "async/guard_hold_ms": st.guard_hold_ms,
                "async/learner_idle_ms": drain_ms + st.guard_hold_ms,
                "async/learner_occupancy": min(
                    st.learner_busy_ms / wall_ms, 1.0
                ),
                "async/actor_occupancy": (
                    engine.stats.slot_util if engine is not None else 0.0
                ),
            })

        self._stream = None

        # unified metrics namespace: the phase's overlap/async/memory
        # attribution stats become registry gauges (async/guard_hold_ms,
        # async/learner_idle_ms, mem/hbm_* — the bubble-breakdown
        # inputs), snapshot-able by the ledger/flight recorder/bench
        telemetry.get_metrics().absorb(self._last_overlap_stats)
        # the host's counters stand at 0.0, not absent, in a phase in
        # which no collection fell and nothing stalled
        telemetry.touch_host_counters()

        # run-health: feed every fetched update row to the detector
        # engine in execution order, the phase-level rollout KL (the
        # kl-spike series) once per phase, then append the phase's
        # flight record. This lives HERE — not in _learn_body — so
        # direct drivers of the phase API (bench, the perf/health-smoke
        # harnesses) get monitoring without running learn(). Host
        # floats only: the single batched fetch above already paid the
        # transfer. The phase state is closed first so an `abort`
        # policy raising out of observe_health leaves the trainer
        # re-enterable.
        if self.health_monitor is not None:
            phase_id = self.health_phase_id
            last_row: Dict[str, Any] = {}
            phase_row: Dict[str, Any] = {
                "policy/mean_rollout_kl": self._last_phase_mean_kl
            }
            if async_staleness_max is not None:
                # the staleness-breach circuit-breaker's series: one
                # observation per phase (kind "above" is always armed)
                phase_row["async/staleness"] = async_staleness_max
            try:
                last_row = self.observe_health_rows(
                    rows,
                    phase=phase_id,
                    phase_row=phase_row,
                )
                # which part of the phase grew, if its wall did
                last_row.update(self.observe_phase_timing(phase_id))
            finally:
                self.record_flight_phase(
                    phase_id, stats_row=last_row, kl_seq=kl_seq
                )

        return plan.n_updates, rows, kl_seq

    def _stream_eligible(self, iter_count: int) -> bool:
        """Whether the NEXT collect+train pass can run as a streamed phase:
        overlap enabled, an orchestrator attached, at least one planned
        minibatch, and no eval/checkpoint boundary or total_steps cutoff
        strictly inside the pass (those fall back to the legacy
        fused/stepwise paths, which honor mid-pass cadence). A profiler
        window (``train.profile_dir`` / ``train.profile_phase``) never
        changes the answer: it profiles the schedule the run has."""
        train = self.config.train
        method: PPOConfig = self.config.method
        if not train.phase_overlap or self.orch is None:
            return False
        n_mb = method.num_rollouts // train.batch_size
        if n_mb < 1:
            return False
        pass_steps = n_mb * method.ppo_epochs
        total_steps = min(
            train.total_steps, train.epochs * pass_steps
        )
        if iter_count + pass_steps > total_steps:
            return False
        # interior MINIBATCH boundaries only — the same set the fused
        # path's gate checks: no execution path can evaluate/save at a
        # mid-minibatch step, so an interval multiple landing there must
        # not disable streaming for the whole run
        for k in range(1, n_mb):
            s = iter_count + method.ppo_epochs * k
            if s % train.eval_interval == 0 or s % train.checkpoint_interval == 0:
                return False
        return True

    def abort_streamed_phase(self) -> None:
        """Error-recovery escape hatch: drop an active streamed phase
        without running its remaining updates. Clears the plan and the
        behavior snapshot and empties the buffer (a partial phase's
        experience cannot satisfy the plan). Epoch-1 updates already
        dispatched are NOT rolled back — on-policy semantics of the next
        phase are unaffected since it snapshots afresh."""
        self._stream = None
        self._behavior_params = None
        self.buffer.clear_history()

    def _collect_phase(self, iter_count: int, seed: int) -> None:
        """Collect one phase of experience — streamed (the default) when
        the coming pass is eligible, else the plain serial collection the
        legacy train paths consume. A collection failure aborts the
        stream so a caller's retry starts from a clean slate instead of
        wedging on the stale plan."""
        # each collection opens a new phase; the profiler window (if one
        # is configured for this phase index) starts before any of the
        # phase's device work dispatches
        self._phase_index += 1
        self._phase_profiler.on_phase_start(self._phase_index)
        self.mark_phase_timing()
        # non-streamed collections need the per-row phase reset too
        # (begin_streamed_phase repeats it harmlessly for streamed ones)
        self.reset_rollout_phase()
        if self._stream_eligible(iter_count):
            self.begin_streamed_phase(seed=seed)
        try:
            self.orch.make_experience(
                self.config.method.num_rollouts, iter_count
            )
        except BaseException:
            if self._stream is not None:
                self.abort_streamed_phase()
            raise

    def learn(self) -> Dict[str, Any]:
        """PPO optimization loop (reference `accelerate_base_model.py:224-305`
        + `accelerate_ppo_model.py:130-156`): per-epoch buffer pass with
        ``ppo_epochs`` updates per minibatch, on-policy refresh each epoch."""
        train = self.config.train
        method: PPOConfig = self.config.method

        # resume (reference Ray session restore, `accelerate_base_model.py:
        # 232-240`): restore params/opt/step + KL-controller state, continue
        # the step count from the checkpoint
        if train.resume_from_checkpoint and has_checkpoint(train.checkpoint_dir):
            self.load(train.checkpoint_dir)
            if int(self.state.step) >= train.total_steps:
                # finished run: skip rollout collection entirely
                self._final_stats = {}
                return {}

        # single-phase profiler window (train.profile_phase, or phase 0
        # with train.profile_dir alone): constructed before the initial
        # collection so phase 0 is profileable
        from trlx_tpu.telemetry.profiler import PhaseProfiler

        self._phase_index = -1
        self._phase_profiler = PhaseProfiler(
            train.profile_dir, train.profile_phase
        )

        # the loop's step counter must come from BEFORE any streamed
        # epoch-1 update advances state.step during the initial collection
        start_step = int(self.state.step)
        # Resume alignment (kill/resume parity, docs/resilience.md): a
        # run resumed at the end of epoch k must collect its next phase
        # with the SAME seed the uninterrupted run would (train.seed +
        # phase index) and run the epoch loop from k, not 0 — otherwise
        # the resumed run replays phase-0 prompts/shuffles and diverges
        # from the run it is continuing. The per-pass step count is
        # derived from the config (the streamed plan uses the same
        # numbers), so the mapping needs no buffer state. The floor
        # assumes the checkpoint sits on a pass boundary — true for
        # preemption-drain and end-of-pass cadence saves; a MID-pass
        # stepwise-cadence checkpoint resumes at its enclosing pass
        # boundary's schedule (the partial pass is re-collected fresh —
        # valid PPO, but bitwise parity is only guaranteed for
        # boundary checkpoints, docs/resilience.md).
        pass_steps = method.ppo_epochs * max(
            method.num_rollouts // train.batch_size, 1
        )
        self._epoch0 = start_step // pass_steps if start_step else 0
        if len(self.buffer) == 0 and self.orch is not None:
            self._collect_phase(start_step, seed=train.seed + self._epoch0)

        if self._stream is not None:
            # streamed phases advance iter_count by the PLAN's update
            # count; rows a non-dividing final chunk over-collects are
            # stored but never scheduled, so sizing the loop from
            # len(buffer) would set a total_steps the phases can never
            # reach (skipping the end-of-run save + eval)
            n_minibatches = self._stream.plan.n_minibatches
        else:
            n_minibatches = max(len(self.buffer) // train.batch_size, 1)
        total_steps = min(
            train.total_steps, train.epochs * method.ppo_epochs * n_minibatches
        )

        logger = Logger(
            project_name=train.project_name,
            run_name=train.run_name,
            config=self.config.to_dict(),
            tags=train.tags,
            total_steps=total_steps,
        )
        self.logger = logger
        try:
            result = self._learn_body(
                logger, total_steps, n_minibatches, start_step
            )
        except BaseException as e:
            # crash forensics: one flight dump per run on the way down
            # (telemetry/flight_recorder.py; no-op when health is off,
            # deduped when a HealthAbort's detector already dumped)
            self.flight_dump_on_exception(e)
            # run ledger (telemetry/run_ledger.py): failed runs are
            # history too — the manifest records the error outcome
            self.append_run_ledger(status="error", error=e)
            raise
        else:
            self.append_run_ledger(status="ok")
            return result
        finally:
            # single epilogue for every exit (incl. exceptions): stop a
            # live profiler window, join in-flight async checkpoint
            # writes (surfacing background write errors), close the
            # logger even if that join raises
            try:
                self._phase_profiler.close()
            finally:
                try:
                    wait_for_checkpoints()
                finally:
                    logger.finish()

    def _end_of_pass(
        self,
        logger: Logger,
        iter_count: int,
        total_steps: int,
        final_stats: Dict[str, Any],
        epoch: int,
    ) -> Tuple[Dict[str, Any], bool]:
        """Shared epilogue of a whole-pass branch (streamed or fused) of
        ``_learn_body``: interval-gated eval/save at the pass boundary,
        the end-of-run save + final eval, and the on-policy refresh for
        the next epoch. Returns ``(final_stats, done)`` — ``done`` means
        the run is complete and the caller must return."""
        train = self.config.train
        iv = self.intervals(iter_count)
        if iv["do_save"] and iter_count >= total_steps:
            # the end-of-run branch below saves this same step
            iv["do_save"] = False
        if iv["do_eval"]:
            eval_stats = self.evaluate()
            logger.log(eval_stats, step=iter_count)
            final_stats.update(eval_stats)
        if iv["do_save"]:
            self.save()
        if iter_count >= total_steps:
            self.save()
            eval_stats = self.evaluate()
            logger.log(eval_stats, step=iter_count)
            final_stats.update(eval_stats)
            self._final_stats = final_stats
            return final_stats, True
        if self.orch is not None and epoch < train.epochs - 1:
            # preemption drain point (docs/resilience.md): AFTER this
            # boundary's eval/save (so the saved RNG chain includes any
            # eval sampling — kill/resume parity), BEFORE the next
            # phase's collection dispatches
            self.maybe_drain(phase=self._phase_index, step=iter_count)
            self.buffer.clear_history()
            self._collect_phase(iter_count, seed=train.seed + epoch + 1)
        return final_stats, False

    def _learn_body(
        self,
        logger: Logger,
        total_steps: int,
        n_minibatches: int,
        start_step: int = 0,
    ) -> Dict[str, Any]:
        train = self.config.train
        method: PPOConfig = self.config.method

        # (with a streamed phase active, the sampler serves the frozen
        # behavior snapshot — this eval reflects the pre-phase policy even
        # though epoch-1 updates may already be in flight). A mid-run
        # RESUME skips this step-0 eval: the uninterrupted run did not
        # evaluate at this point, and the extra eval would advance the
        # sampler RNG chain — breaking the bitwise kill/resume parity
        # the preemption drain guarantees (docs/resilience.md).
        if start_step == 0:
            stats = self.evaluate()
            logger.log(stats, step=0)
            if hasattr(self, "_last_samples"):
                logger.log_samples(
                    self._last_samples[1], self._last_samples[0], step=0
                )

        clock = Clock()
        iter_count = start_step  # nonzero after resume
        final_stats: Dict[str, Any] = {}
        self._final_stats = final_stats
        if iter_count >= total_steps:
            # resumed a finished run: nothing left to train
            return final_stats
        for epoch in range(getattr(self, "_epoch0", 0), train.epochs):
            # Streamed phase (the default): collection already interleaved
            # epoch-1 updates against the behavior snapshot; close the
            # phase (residual epochs + stats) and log per-minibatch
            # exactly like the fused path.
            if self._stream is not None:
                n_up, rows, kl_seq = self.finish_streamed_phase()
                phase_time = clock.tick(train.batch_size) / 1000.0
                self.check_anomalies(rows, iter_count)
                n_mb = n_up // method.ppo_epochs
                step_stats = {}
                for k in range(n_mb):
                    iter_count += method.ppo_epochs
                    # mb k's FINAL inner update: epoch-major order puts it
                    # in the last epoch's span (epoch-1 row k when E == 1)
                    row = (method.ppo_epochs - 1) * n_mb + k
                    step_stats = {
                        key: float(v[row]) for key, v in rows.items()
                    }
                    step_stats["time/batch"] = phase_time / n_mb
                    step_stats["policy/kl_coef"] = float(kl_seq[k + 1])
                    step_stats["policy/mean_rollout_kl"] = (
                        self._last_phase_mean_kl
                    )
                    step_stats.update(self._last_overlap_stats)
                    if iter_count % train.log_interval == 0:
                        logger.log(step_stats, step=iter_count)
                        final_stats = dict(step_stats)
                # phase boundary: the profiled phase's updates are done and
                # fetched — close the window here (no new sync)
                self._phase_profiler.on_phase_end(sync=self.state.params)
                final_stats, done = self._end_of_pass(
                    logger, iter_count, total_steps, final_stats, epoch
                )
                if done:
                    return final_stats
                continue
            # Fused path: the whole buffer pass is one device dispatch
            # (lax.scan over minibatches) — used whenever no eval/save
            # boundary or total_steps cutoff falls strictly inside the pass
            # (log cadence is honored post-hoc from the stacked stats).
            pass_steps = method.ppo_epochs * n_minibatches
            interior = [
                iter_count + method.ppo_epochs * k
                for k in range(1, n_minibatches)
            ]
            fused_ok = (
                len(self.buffer) >= train.batch_size
                and iter_count + pass_steps <= total_steps
                and not any(
                    s % train.eval_interval == 0
                    or (s > 0 and s % train.checkpoint_interval == 0)
                    for s in interior
                )
            )
            if fused_ok:
                # phase/train on the fused path covers dispatch AND the
                # stats fetch that forces it — the same window the
                # streamed path's span measures
                with telemetry.span(
                    "phase/train", force=True,
                    updates=n_minibatches * method.ppo_epochs,
                ):
                    _, stacked, kl_seq = self.train_on_buffer(
                        seed=train.seed + epoch, n_minibatches=n_minibatches
                    )
                    # one transfer event for the whole stacked stats tree
                    # + KL state (per-key np.asarray would block once per
                    # leaf)
                    rows, kl_seq, mean_kl = jax.device_get(
                        (stacked, kl_seq, self.mean_kl)
                    )
                phase_time = clock.tick(train.batch_size) / 1000.0
                # every fetched update row feeds the detectors (the
                # streamed path does the same in finish_streamed_phase);
                # the phase-constant rollout KL is observed once. BEFORE
                # check_anomalies: on a NaN row the nan-precursor trip +
                # flight-recorder policy must see the offending phase
                # before the anomaly abort raises
                self.observe_health_rows(
                    rows,
                    step0=iter_count,
                    phase=self._phase_index,
                    phase_row={"policy/mean_rollout_kl": float(mean_kl)},
                )
                telemetry.touch_host_counters()
                self.observe_phase_timing(self._phase_index)
                self.check_anomalies(rows, iter_count)
                step_stats = {}
                for k in range(n_minibatches):
                    iter_count += method.ppo_epochs
                    # the stepwise loop logs the last inner update per mb
                    row = k * method.ppo_epochs + method.ppo_epochs - 1
                    step_stats = {key: float(v[row]) for key, v in rows.items()}
                    step_stats["time/batch"] = phase_time / n_minibatches
                    step_stats["policy/kl_coef"] = float(kl_seq[k + 1])
                    step_stats["policy/mean_rollout_kl"] = float(mean_kl)
                    if iter_count % train.log_interval == 0:
                        logger.log(step_stats, step=iter_count)
                        final_stats = dict(step_stats)
                self.record_flight_phase(
                    self._phase_index, step=iter_count,
                    stats_row=step_stats, kl_seq=list(kl_seq),
                )
                self._phase_profiler.on_phase_end(sync=self.state.params)
                final_stats, done = self._end_of_pass(
                    logger, iter_count, total_steps, final_stats, epoch
                )
                if done:
                    return final_stats
                continue

            step_stats = {}
            for mb in self.buffer.create_loader(
                train.batch_size,
                shuffle=True,
                seed=train.seed + epoch,
                sharding=batch_sharding(self.mesh),
            ):
                for _ in range(method.ppo_epochs):
                    self.state, step_stats = self._train_step_jit(self.state, mb)
                    iter_count += 1
                step_stats["time/batch"] = clock.tick(train.batch_size) / 1000.0
                # adaptive KL controller (post_backward_callback,
                # `accelerate_ppo_model.py:136-137`) — stays device-side;
                # the do_log branch fetches everything in one event
                self.kl_coef = kl_controller_update(
                    method, self.kl_coef, self.mean_kl, train.batch_size
                )
                step_stats["policy/kl_coef"] = self.kl_coef
                step_stats["policy/mean_rollout_kl"] = self.mean_kl

                iv = self.intervals(iter_count)
                at_end = iter_count >= total_steps
                if iv["do_log"] or iv["do_save"] or at_end:
                    # ONE stats fetch per step, shared by every host
                    # consumer (logger, anomaly check before save) — the
                    # log and save branches each paying their own
                    # device_get doubled/tripled the host round-trips
                    step_stats = jax.device_get(step_stats)
                    # detectors read the same fetched row — still the
                    # one transfer this step already paid, and BEFORE
                    # check_anomalies so a NaN row reaches nan-precursor
                    # and the flight policy before the anomaly abort.
                    # The rollout KL is phase-constant, so it is
                    # excluded here and observed once at the pass
                    # boundary below (per-row repeats would collapse
                    # its EWMA variance)
                    self.observe_health(
                        {
                            k: v for k, v in step_stats.items()
                            if k != "policy/mean_rollout_kl"
                        },
                        step=iter_count, phase=self._phase_index,
                    )
                    # never log or persist a NaN state
                    self.check_anomalies(step_stats, iter_count)
                if iv["do_log"]:
                    logger.log(step_stats, step=iter_count)
                    final_stats = {k: float(v) for k, v in step_stats.items()}
                if iv["do_eval"]:
                    eval_stats = self.evaluate()
                    logger.log(eval_stats, step=iter_count)
                    final_stats.update(eval_stats)
                if iv["do_save"] and not at_end:
                    # at_end saves below — don't serialize the same step's
                    # full sharded state twice when the intervals coincide
                    self.save()
                if at_end:
                    self.save()
                    eval_stats = self.evaluate()
                    logger.log(eval_stats, step=iter_count)
                    final_stats.update(eval_stats)
                    self._final_stats = final_stats
                    return final_stats
            # stepwise pass done — phase boundary: the phase-level KL
            # series gets its ONE observation (skipped by the monitor if
            # the value never crossed to host this pass), then the
            # flight record (device leaves in an unfetched last row are
            # dropped by the recorder, never forced)
            if self.health_monitor is not None and step_stats:
                self.observe_health(
                    {
                        "policy/mean_rollout_kl": step_stats.get(
                            "policy/mean_rollout_kl"
                        )
                    },
                    step=iter_count, phase=self._phase_index,
                )
            self.record_flight_phase(
                self._phase_index, step=iter_count, stats_row=step_stats
            )
            self._phase_profiler.on_phase_end(sync=self.state.params)
            # on-policy refresh (post_epoch_callback,
            # `accelerate_ppo_model.py:130-134`)
            if self.orch is not None and epoch < train.epochs - 1:
                # preemption drain point: same boundary as the
                # streamed/fused paths' _end_of_pass
                self.maybe_drain(phase=self._phase_index, step=iter_count)
                self.buffer.clear_history()
                self._collect_phase(iter_count, seed=train.seed + epoch + 1)
        self._final_stats = final_stats
        return final_stats

    # ------------------------------------------------------------------ #

    def host_state_dict(self) -> Dict[str, Any]:
        state = super().host_state_dict()
        # per-row RNG phase state: mid-phase the lazily split phase key
        # and draw cursor decide every remaining row's fold_in key, so
        # a boundary-agnostic checkpoint must carry them (at a phase
        # boundary they are just None/0 and the entry is inert)
        if self._rollout_phase_key is not None:
            state["rollout_phase_key"] = (
                np.asarray(jax.device_get(self._rollout_phase_key))
                .ravel()
                .tolist()
            )
        state["rollout_row_cursor"] = int(self._rollout_row_cursor)
        # continuous-engine drafter: accept-EWMA/probe counters feed the
        # drafting schedule (spec_drafter.state_dict); only present once
        # the engine has been built — a never-built engine has no
        # drafter state worth carrying
        engine = self._rollout_engine_obj
        drafter = getattr(engine, "spec_drafter", None)
        if drafter is not None and hasattr(drafter, "state_dict"):
            state["spec_drafter"] = drafter.state_dict()
        return state

    def load_host_state_dict(self, state: Dict[str, Any]) -> None:
        super().load_host_state_dict(state)
        phase_key = state.get("rollout_phase_key")
        if phase_key is not None:
            self._rollout_phase_key = jnp.asarray(
                np.asarray(phase_key, dtype=np.uint32)
            )
        self._rollout_row_cursor = int(
            state.get("rollout_row_cursor", self._rollout_row_cursor)
        )
        drafter_state = state.get("spec_drafter")
        if drafter_state is not None and self.rollout_engine == "continuous":
            # building the engine here is fine: a resumed
            # continuous-engine run needs it before the first phase
            # anyway, and restoring the drafter EWMAs after that first
            # phase would be too late
            drafter = getattr(self.rollout_engine_obj, "spec_drafter", None)
            if drafter is not None and hasattr(drafter, "load_state_dict"):
                drafter.load_state_dict(drafter_state)

    def _save_metadata(self) -> Dict[str, Any]:
        """The checkpoint's host-metadata pytree (JSON-safe). Split out
        of save() so the resume auditor (engine 15) can fingerprint the
        metadata schema for the ``state_manifest`` lock without writing
        a checkpoint."""
        # one batched fetch for all host-side save inputs
        kl_coef, mean_kl, rng = jax.device_get(
            (self.kl_coef, self.mean_kl, self.rng)
        )
        metadata = {
            "kl_coef": float(kl_coef),
            "mean_kl": float(mean_kl),
            # the sampler RNG chain: one split per phase (plus one
            # per chunk without per-row RNG) — restoring it exactly
            # is half of kill/resume bitwise parity; the other half
            # is the orchestrator state below (docs/resilience.md)
            "rng_key": np.asarray(rng).ravel().tolist(),
            # everything else mutable-but-host-side (drafter EWMAs,
            # health detectors, mid-phase RNG cursor) rides the
            # host-state contract audited by engine 15
            "host_state": self.host_state_dict(),
        }
        orch = getattr(self, "orch", None)
        if orch is not None and hasattr(orch, "state_dict"):
            # reward-scaling running moments + prompt-stream position
            metadata["orchestrator"] = orch.state_dict()
        return metadata

    def save(self, directory: Optional[str] = None) -> None:
        directory = directory or self.config.train.checkpoint_dir
        with telemetry.span("phase/checkpoint"):
            step = int(jax.device_get(self.state.step))
            save_checkpoint(
                directory,
                self.state,
                metadata=self._save_metadata(),
                async_save=self.config.train.async_checkpoint,
                step=step,
            )

    def load(self, directory: str) -> None:
        abstract = jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            self.state,
            self.state_shardings,
        )
        self.state, meta = load_checkpoint(directory, abstract)
        self.kl_coef = float(meta.get("kl_coef", self.kl_coef))
        self.mean_kl = float(meta.get("mean_kl", self.mean_kl))
        rng_key = meta.get("rng_key")
        if rng_key is not None:
            self.rng = jnp.asarray(
                np.asarray(rng_key, dtype=np.uint32).reshape(
                    np.shape(self.rng)
                )
            )
        orch_state = meta.get("orchestrator")
        orch = getattr(self, "orch", None)
        if orch_state and orch is not None and hasattr(orch, "load_state_dict"):
            orch.load_state_dict(orch_state)
        self.load_host_state_dict(meta.get("host_state") or {})
