"""YAML -> nested dataclass config system.

Re-design of the reference config system (``trlx/data/configs.py:10-190``):
same three-section schema (``model`` / ``train`` / ``method``), same recursive
override merge with unknown-key detection (`merge` :10-21, `update` :179-190),
same method dispatch through the method registry (:153). TPU-specific
additions: a ``train.mesh`` axis spec (data/fsdp/tensor parallel sizes), a
compute ``dtype``, and a from-scratch ``model.model_arch`` override so tiny
synthetic tasks (randomwalks) need no checkpoint.
"""

from __future__ import annotations

from dataclasses import asdict, dataclass, field, fields
from typing import Any, Dict, List, Optional, Tuple

import yaml

from trlx_tpu.data.method_configs import MethodConfig, get_method


def merge(base: Dict, update: Dict, updated: set) -> Dict:
    """Recursively merge ``update`` into ``base``, recording touched keys."""
    for k, v in base.items():
        if k in update and isinstance(v, dict):
            base[k] = merge(v, update[k], updated)
            updated.add(k)
        elif k in update:
            base[k] = update[k]
            updated.add(k)
    return base


def _from_dict_strict(cls, config: Dict[str, Any]):
    known = {f.name for f in fields(cls)}
    unknown = set(config) - known
    if unknown:
        raise ValueError(f"Unknown keys for {cls.__name__}: {sorted(unknown)}")
    return cls(**config)


@dataclass
class ModelConfig:
    """Which policy model to train.

    :param model_path: HF checkpoint directory for weight conversion, or empty
        for from-scratch init via ``model_arch``.
    :param tokenizer_path: HF tokenizer path (host-side only).
    :param model_type: architecture family registered in
        ``trlx_tpu.models``: ``"gpt2"`` (causal LM) or ``"t5"`` (seq2seq).
    :param num_layers_unfrozen: train only the top-k transformer blocks
        (reference `configs.py:42`); -1 trains everything. Also (by
        default) sizes the hydra shared-trunk frozen reference branch for
        PPO.
    :param ref_branch_layers: depth of the hydra frozen KL-reference
        branch, decoupled from freezing. In the reference as shipped the
        PPO freezing block is commented out (`accelerate_base_model.py:
        55-69`) — `num_layers_unfrozen` ONLY sizes the hydra branch
        (`ppo_models.py:525-536`) while the policy trains all layers; this
        key expresses that workload (e.g. ``num_layers_unfrozen: 0`` +
        ``ref_branch_layers: 2``). ``None`` (default) follows
        ``num_layers_unfrozen`` when positive; ``0`` forces the full-copy
        reference.
    :param model_arch: from-scratch architecture overrides (n_layer, n_embd,
        n_head, vocab_size, n_positions, ...) when no checkpoint is given.
    """

    model_path: str = ""
    tokenizer_path: str = ""
    model_type: str = "gpt2"
    num_layers_unfrozen: int = -1
    ref_branch_layers: Optional[int] = None
    model_arch: Dict[str, Any] = field(default_factory=dict)

    @property
    def resolved_ref_branch_layers(self) -> int:
        """Hydra branch depth actually in effect (0 = full-copy ref)."""
        if self.ref_branch_layers is not None:
            return self.ref_branch_layers
        return max(self.num_layers_unfrozen, 0)

    @classmethod
    def from_dict(cls, config: Dict[str, Any]):
        return _from_dict_strict(cls, config)


@dataclass
class TrainConfig:
    """Training loop + distributed layout configuration.

    Core fields mirror the reference ``TrainConfig`` (`configs.py:49-127`);
    ``mesh`` / ``dtype`` / ``param_dtype`` are TPU-native additions.

    :param mesh: device-mesh axis sizes ``{"dp": -1, "fsdp": 1, "tp": 1}``;
        -1 consumes all remaining devices on that axis. dp = pure data
        parallel (replicated params), fsdp = ZeRO-style fully sharded data
        parallel (param/opt-state sharding, the DeepSpeed-stage equivalent),
        tp = tensor parallel.
    """

    total_steps: int = 10000
    seq_length: int = 64
    epochs: int = 100
    batch_size: int = 16

    lr_init: float = 1.0e-4
    lr_target: float = 1.0e-4
    opt_betas: Tuple[float, float] = (0.9, 0.95)
    opt_eps: float = 1.0e-8
    weight_decay: float = 1.0e-6
    grad_clip: float = 1.0
    # Storage dtype for BOTH Adam moments ("float32" | "bfloat16"). bf16
    # halves the optimizer's resident bytes and its per-step HBM read+write
    # (its share of a train step is not measured on the chip); stores use
    # stochastic rounding so sub-resolution EMA increments ((1-b2)·g²)
    # still accumulate. Update math stays f32. See trainer/common.py.
    adam_moment_dtype: str = "float32"

    checkpoint_interval: int = 10000
    eval_interval: int = 100
    log_interval: int = 1

    pipeline: str = "PromptPipeline"
    orchestrator: str = "PPOOrchestrator"
    trainer: str = "PPOTrainer"

    checkpoint_dir: str = "ckpts"
    # restore train state + loop counters from checkpoint_dir before
    # training (reference Ray-resume path, `accelerate_base_model.py:232-240`)
    resume_from_checkpoint: bool = False
    # write checkpoints on Orbax's background thread: the train loop resumes
    # as soon as device arrays are snapshotted to host buffers
    async_checkpoint: bool = False
    # failure detection (beyond the reference, SURVEY §5.3 "none"): abort
    # with a clear error when the fetched loss stats go non-finite, instead
    # of silently training on NaNs. Checked wherever stats already cross to
    # host (every fused pass / ILQL chunk; log steps on the stepwise path).
    detect_anomalies: bool = True
    # Run-health monitoring (telemetry/health.py, docs/observability.md):
    # {"enabled": true, "on_error": "warn"|"dump"|"abort", "window": ...,
    #  "detectors": {"kl-spike": {"zmax": ...}, ...}, "disable": [...]}.
    # With enabled, each trainer's jitted step fuses training-dynamics
    # scalars (entropy at ent_coef=0, log-ratio extremes, value explained
    # variance, reward quantiles) into its stats pytree — riding the
    # existing per-step transfer — and streaming detectors (kl-spike,
    # entropy-collapse, ratio-explosion, grad-spike, reward-saturation,
    # nan-precursor) watch the fetched rows on host. Bitwise-inert on
    # training (tests/test_phase_overlap.py). Default off: the jitted
    # programs stay byte-identical to a pre-health build.
    health: Dict[str, Any] = field(default_factory=dict)
    # dump one flight-recorder forensics JSON (telemetry/flight_recorder.py)
    # at the END of exactly phase N, on demand — crash dumps need no flag;
    # requires health.enabled
    flight_dump_phase: Optional[int] = None
    # Run ledger & live watching (telemetry/run_ledger.py,
    # docs/observability.md "Run ledger"): with a directory set, the run
    # mirrors each flight-recorder phase record into
    # <run_dir>/phases.jsonl (the `python -m trlx_tpu.telemetry --watch
    # <run_dir>` feed; live rows require health.enabled, which drives the
    # phase records) and the learn() epilogue appends a RunManifest to
    # <run_dir>/manifest.json plus the ledger JSONL ($TRLX_RUN_LEDGER, or
    # <run_dir>/ledger.jsonl). Default off: nothing is written.
    run_dir: Optional[str] = None
    # Fault tolerance (trlx_tpu/resilience, docs/resilience.md):
    # {"enabled": true, "max_restarts": 2, "resume_on_preemption": true,
    #  "preempt_signals": ["SIGTERM", "SIGINT"], "restart_delay_s": 0.0,
    #  "retry": {"max_attempts": ..., "base_delay_s": ...},
    #  "chaos": [{"site": ..., "mode": ..., "phase": ..., "count": ...}]}.
    # With enabled, api.train runs under the resilience supervisor: a
    # SIGTERM/SIGINT drains gracefully at the next phase boundary
    # (emergency atomic checkpoint + flight dump, exit code 75), and
    # retriable failures (transient I/O, HealthAbort, preemption) restart
    # from the latest good checkpoint within a bounded restart budget.
    # Default off: no signal handlers are installed and nothing changes.
    resilience: Dict[str, Any] = field(default_factory=dict)
    project_name: str = "trlx_tpu"
    run_name: str = ""
    seed: int = 1000

    mesh: Dict[str, int] = field(default_factory=lambda: {"dp": -1, "fsdp": 1, "tp": 1})
    # GPipe microbatches per batch shard when the mesh has a pp axis > 1
    # (must divide batch_size / (dp * fsdp)); see models/pp_runner.py
    pp_microbatches: int = 2
    # Interleaved virtual stages per pp device for the TRAIN schedule
    # (Megatron-style): each device holds v round-robin layer chunks, the
    # fill/drain bubble shrinks ~v x at the cost of v x more ppermute hops.
    # Requires pp_microbatches <= pp and n_layer % (pp * v) == 0; decode
    # keeps the plain stage-major schedule (the stage-resident KV layout
    # is contiguous). See parallel/pipeline.py::pipeline_span_layer_units.
    pp_virtual_stages: int = 1
    # Rematerialized pipeline backward for the TRAIN schedule (the memory
    # half of 1F1B — the bubble spans of GPipe-fwd+bwd and 1F1B are equal):
    # the forward saves only each stage's input per microbatch and the
    # custom backward recomputes stages under jax.vjp on the mirrored
    # schedule, instead of autodiff saving every tick's layer internals.
    # Cuts the update's peak activation memory (measured via XLA
    # memory_analysis in tests/test_pipeline_parallel.py); costs one extra
    # stage forward per backward (the standard remat trade). v=1 only;
    # exact grad parity vs the autodiffed schedule is pinned in tests.
    pp_remat: bool = False
    # Compute the PPO update's response logprobs in chunks of this many
    # positions (0 = off): the LM head + log-softmax + gather run per
    # chunk under jax.checkpoint, so the [B, R, vocab] f32 logits buffer
    # — the train step's largest intermediate — never materializes at
    # full width; the backward recomputes each chunk's logits (one extra
    # head matmul). Must divide gen max_new_tokens. Not measured on the
    # chip (no cell sets it): enable only where a chip run shows a win;
    # entropy-bonus runs (ent_coef) fall back to the full buffer.
    logprob_chunk: int = 0
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # Serve the rollout phase (sampler + frozen-ref scoring) a one-time
    # compute-dtype copy of the master params instead of the f32 masters.
    # Every matrix an op casts to the compute dtype per use (Dense and
    # expert kernels, embedding tables) comes out bit-identical, and the
    # matrices named in utils.ROLLOUT_CAST_EXCLUDE (value-head fc2, MoE
    # router logits) keep their width. Not every leaf is cast per use,
    # though: LayerNorm / RMSNorm apply scale and bias at f32, so the copy
    # rounds them (exact on the initialisers' ones and zeros, not on
    # trained vectors), and granite's / zaya's convolution taps, shared
    # expert output and tied table are multiplied at f32
    # (ModelFamily.stored_width_leaves). A server's copy leaves all of
    # these as stored: utils.served_params. Its speed is not measured on the
    # chip (every cell runs the default; XLA may hoist the loop-invariant
    # f32->bf16 weight conversion out of the decode scan anyway); kept
    # default-on for the halved frozen-ref HBM residency and because on an
    # fsdp mesh the compute-dtype copy halves rollout param all-gather
    # volume.
    # Causal families only — the seq2seq trainer keeps f32 (T5's RMSNorm
    # scales / relative bias are consumed at f32).
    rollout_param_cast: bool = True

    # Rollout engine selection (docs/inference.md): {"engine": "fixed" |
    # "continuous", "slots": ..., "admit_width": ..., "harvest_width":
    # ..., "block_size": ..., "per_row_rng": ...} — parsed into
    # trlx_tpu.inference.RolloutEngineConfig. "continuous" replaces the
    # fixed-batch segmented-scan sampler on the collect path with the
    # slot-admission decode loop over a paged KV cache
    # (trlx_tpu/inference/engine.py): prompts are admitted into vacated
    # decode slots the step after a row emits eos, and completed
    # rollouts stream into the buffer in fixed-width harvest groups.
    # Per-row token-identical to the fixed sampler under per-row RNG
    # (tests/test_inference_engine.py). Causal PPO-family trainers only
    # (no pp mesh axis, no grouped/GRPO sampling yet); "fixed" is the
    # default and the parity baseline. "prefill_chunk" (> 0) runs the
    # engine's admission prefill as need-gated block-aligned prompt
    # chunks (skips leading pad + prefix-pool-covered blocks; bitwise
    # vs the monolithic program — docs/inference.md "Chunked prefill"),
    # and "prefill_chunks_per_pump" bounds chunk forwards per serving
    # pump (stall-free admission under bursts). An InferenceServer given
    # neither derives both: a chunk of Q // 4 columns, one forward a pump
    # (a group that can skip under half its chunks: the whole prefill).
    rollout: Dict[str, Any] = field(default_factory=dict)
    # Multi-tenant serving tier (trlx_tpu/serving, docs/serving.md),
    # parsed into trlx_tpu.serving.ServingConfig and consumed by
    # InferenceServer only (training ignores it): {"tenants": {...},
    # "slo_classes": {...}, "prefix_cache_blocks": N, "stream_buffer": N,
    # "aging_half_ms": ...}. prefix_cache_blocks > 0 turns on
    # cross-request shared-prefix KV (the engine gains a shared block
    # pool); tenants/slo_classes type the QoS scheduler's admission.
    serving: Dict[str, Any] = field(default_factory=dict)

    # Span-tracer tuning (trlx_tpu/telemetry, docs/observability.md):
    # {"ring_size": N} — capacity of the bounded span ring. Per-request
    # serving traces (request_trace.py) multiply span volume, so a
    # high-traffic InferenceServer deployment raises this; the
    # TRLX_TELEMETRY_RING env var overrides. Default {} keeps the
    # built-in ring (tracer.DEFAULT_RING_SIZE).
    telemetry: Dict[str, Any] = field(default_factory=dict)

    # Asynchronous actor–learner PPO (docs/async_pipeline.md):
    # {"enabled": true, "staleness_window": 1, "actor_fraction": 1.0} —
    # parsed into trlx_tpu.trainer.async_rl.AsyncRLConfig. With enabled
    # (requires rollout.engine: continuous), the phase barrier between
    # collect and train is removed: actors stream version-tagged
    # rollouts through the stream store while the learner consumes
    # planned minibatches as they land and pushes refreshed weights to
    # the actors MID-GENERATION, bounded by staleness_window (the
    # version-lag guard defers consumption that would exceed it; the
    # staleness-breach health detector is the circuit-breaker).
    # staleness_window: 0 is the bitwise-serial degenerate mode — the
    # async schedule is then bit-identical to the serial same-plan
    # phase (tests/test_async_rl.py). actor_fraction < 1 places the
    # engine on its own device subset (the single-process rehearsal of
    # multi-host actor/learner placement). Default off: nothing changes.
    async_rl: Dict[str, Any] = field(default_factory=dict)

    # Streamed collect→train phase overlap (PPO-family trainers;
    # docs/async_pipeline.md): the behavior policy is snapshotted once per
    # phase, rollout chunks land incrementally in the streaming buffer, and
    # epoch-1 minibatch updates are dispatched as soon as each planned
    # minibatch's rollouts exist — while later chunks are still decoding.
    # Exactly on-policy (every rollout samples from the frozen snapshot;
    # behavior logprobs are recorded at decode time) and bitwise-identical
    # to running the same schedule serially (tests/test_phase_overlap.py).
    # NOTE the streamed UPDATE SCHEDULE itself differs from the legacy
    # fused/stepwise one (and from the torch reference): epoch-MAJOR
    # (epoch 1 over arrival-block minibatches, then epochs 2..E over
    # fresh global permutations) instead of minibatch-major (each
    # shuffled minibatch repeated ppo_epochs times consecutively). Both
    # are standard PPO; reproducing a pre-overlap run exactly requires
    # phase_overlap: false. Passes with a mid-pass eval/checkpoint
    # boundary, a total_steps cutoff, or an active profiler fall back to
    # the legacy fused/stepwise paths automatically. False disables
    # streaming entirely (legacy schedule everywhere).
    phase_overlap: bool = True

    # when set, every collected rollout chunk is appended (one JSON line per
    # sample: query/response text + raw score) to rollouts_<iter>.jsonl here
    rollout_logging_dir: Optional[str] = None
    # output directory of the single-phase jax.profiler window (SURVEY
    # §5.1: timing stats + optional jax.profiler integration); set
    # without profile_phase it traces phase 0. The run's schedule is the
    # same with and without it.
    profile_dir: Optional[str] = None
    # dump one xplane trace for EXACTLY phase N (one collect→train pair)
    # into profile_dir (default "profiles"): a programmatic jax.profiler
    # window opened before phase N's collection dispatches and closed at
    # its phase boundary — see telemetry/profiler.py, docs/observability.md
    profile_phase: Optional[int] = None
    tags: List[str] = field(default_factory=list)

    @classmethod
    def from_dict(cls, config: Dict[str, Any]):
        if "opt_betas" in config:
            config = dict(config, opt_betas=tuple(config["opt_betas"]))
        return _from_dict_strict(cls, config)


@dataclass
class TRLConfig:
    """Top-level config: ``model`` + ``train`` + ``method`` sections."""

    model: ModelConfig
    train: TrainConfig
    method: MethodConfig

    @classmethod
    def load_yaml(cls, yml_fp: str) -> "TRLConfig":
        with open(yml_fp) as f:
            config = yaml.safe_load(f)
        return cls.from_dict(config)

    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "TRLConfig":
        return cls(
            model=ModelConfig.from_dict(config.get("model", {})),
            train=TrainConfig.from_dict(config.get("train", {})),
            method=get_method(config["method"]["name"]).from_dict(
                {k: v for k, v in config["method"].items()}
            ),
        )

    def to_dict(self) -> Dict[str, Any]:
        return {
            "model": asdict(self.model),
            "train": asdict(self.train),
            "method": self.method.to_dict(),
        }

    def update(self, **kwargs) -> None:
        """Apply flat or nested overrides; raise on keys that match nothing.

        Accepts both nested dicts (``{"train": {"lr_init": 1e-5}}``) and flat
        dotted/bare keys (``lr_init=1e-5``) as the reference's sweep merge
        does (`configs.py:179-190`).
        """
        updates = set()
        sections = {"model": self.model, "train": self.train, "method": self.method}
        for k, v in kwargs.items():
            if k in sections and isinstance(v, dict):
                unknown = set(v) - set(sections[k].__dict__)
                if unknown:
                    raise ValueError(
                        f"Unknown config keys in {k!r}: {sorted(unknown)}"
                    )
                merge(sections[k].__dict__, v, updates)
                updates.add(k)
            elif "." in k:
                section_name, _, field = k.partition(".")
                section = sections.get(section_name)
                if section is not None and hasattr(section, field):
                    setattr(section, field, v)
                    updates.add(k)
            else:
                for section in sections.values():
                    if hasattr(section, k):
                        setattr(section, k, v)
                        updates.add(k)
                        break
        rest = set(kwargs) - updates
        if rest:
            raise ValueError(f"Unknown config keys: {sorted(rest)}")

    def __str__(self):
        import json

        return "TRLConfig:\n" + json.dumps(self.to_dict(), indent=2)
