"""Benchmark: PPO samples/sec/chip on the BASELINE workload shape.

Workload (BASELINE.md): gpt2-small policy (124M, bf16), query length 64,
48-token rollouts (reference test_config: gen len 48, batch 16,
128 rollouts/phase, 4 ppo_epochs). One full PPO phase = collect 128 rollouts
(compiled sampler + reward + KL penalty vs frozen ref) + 32 optimizer steps
(8 minibatches x 4 ppo_epochs). Weights are randomly initialized (zero-
egress environment: no HF downloads) — identical compute to the pretrained
model.

BOTH workload definitions are measured every round (VERDICT r4 #1):

- **Headline (`value`): the faithful reconstruction of the reference as
  shipped.** In the actual reference code the PPO-path freezing block is
  COMMENTED OUT (`accelerate_base_model.py:55-69`) — with test_config.yml's
  `num_layers_unfrozen: 2` the policy still trains ALL 12 layers; the
  setting only sizes the hydra frozen KL-ref branch (`ppo_models.py:
  525-536`). Expressed here as `num_layers_unfrozen: 0` +
  `ref_branch_layers: 2` (full training, 2-layer hydra ref). This is the
  same definition rounds 1-3 measured (they paid a FULL-COPY ref — strictly
  more ref compute than the reference's own hydra branch).
- **Secondary (`value_frozen_top2`): the lightened workload round 4
  mistakenly reported as faithful** (freezing re-enabled: only the top 2
  blocks train, backward pruned below the branch point). Kept for series
  continuity with BENCH_r04 and as the work-avoidance capability number.

MFU accounting charges only performed FLOPs per definition (_phase_flops).

The reference publishes no numbers (BASELINE.md), so the falsifiable
claims here are the hardware-grounded ones: decode/train tokens/s,
achieved FLOP/s, and MFU against the chip's published bf16 peak (FLOP
accounting below). ``vs_baseline`` is kept for continuity against a
documented single-A100 *estimate* for torch trlX on this workload
(HF generate rollouts + DDP updates, ~12 samples/s) — an estimate, not a
measurement.

Prints one JSON line: {"metric", "value", "unit", "vs_baseline", + extras}.

Device numbers come from the chip only: the run refuses to start unless jax
finds a TPU whose ``device_kind`` is in the one peaks table
(``telemetry/attribution.py``), and an exception in any phase fails the
process — what was measured up to that point is still printed, then the
exit code is nonzero.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

A100_BASELINE_SAMPLES_PER_SEC = 12.0

# BENCH payload schema: bump when a top-level key changes meaning, so
# round-over-round diffs (and the run-ledger compare) are
# machine-checkable against the layout they were written under.
BENCH_SCHEMA_VERSION = 1

# Published per-chip peaks (bf16 TFLOP/s, HBM GB/s) by device_kind —
# single source shared with the attribution layer
# (telemetry/attribution.py); a device missing from it is an error.
from trlx_tpu.telemetry.attribution import (  # noqa: E402
    device_peaks,
    require_tpu,
)


def _collect_bytes(d, V, L, Q, R, B, kv_cache_bytes=1, weight_bytes=2):
    """Architecturally-required HBM bytes for one collect phase — the
    roofline denominator for ``collect_phase_hbm_util`` (VERDICT r3 #2).
    Decode is memory-bound, so MFU alone cannot distinguish "near the HBM
    bound" from "leaving 2x on the table"; this counts the traffic the
    phase MUST move:

    - weights once per decode step (the defining cost of autoregressive
      decode: trunk + tied lm head, compute-dtype bytes), once for
      prefill, once for the frozen-ref forward;
    - KV cache: read of all prior positions + one-position write per
      step, at the cache dtype (int8 here);
    - the per-step logits pipeline ([B, V] f32 written by the head, then
      read by eos-suppression/sampling/logsumexp — counted as 4 passes).

    Activations inside fused layers are NOT counted (they live in
    VMEM/registers when fusion works), so the number is a *lower bound* on
    true traffic and the util an *upper bound* on unavoidable-traffic
    efficiency.

    ``B`` must be the PER-CHIP batch: under dp replication every chip
    streams the full weights itself (weight terms don't divide over
    chips), while cache/logits traffic scales with the chip's batch
    shard."""
    w_step = (L * (12 * d * d + 13 * d) + V * d + 2 * d) * weight_bytes
    cache_read = sum(
        2 * L * B * (Q + t + 1) * d * kv_cache_bytes for t in range(R)
    )
    cache_write = R * 2 * L * B * d * kv_cache_bytes
    logits = R * 4 * B * V * 4
    decode = R * w_step + cache_read + cache_write + logits
    prefill = w_step + 2 * L * B * Q * d * kv_cache_bytes
    ref = w_step + 2 * B * R * V * 4
    return decode + prefill + ref


def _train_step_bytes(d, V, L, Q, R, B, unfrozen=0):
    """Architecturally-required HBM bytes for ONE optimizer step — the
    roofline denominator for ``train_phase_hbm_util`` (VERDICT r4 #2,
    mirrors bench_train_audit.py). Lower bound: fused per-layer
    activations uncounted.

    - weights: the fwd reads the full bf16 compute cast; the bwd is
      PRUNED below the branch point (matching `_phase_flops`), so it
      re-reads only the unfrozen blocks + the (tied) head transpose for
      dlogits; f32 grads written for the trainable slice;
    - optimizer: trainable slice only (frozen leaves carry no moments and
      take no update — the mask freezes wte/wpe + bottom blocks, so the
      trainable slice is the unfrozen blocks + ln_f, NOT a flat fraction
      of all params);
    - logits pipeline: the [B, R, V] f32 buffer crosses HBM ~5 times
      (head write, logsumexp read, bwd softmax rebuild+read, dlogits
      write+read into the head transpose);
    - residual stream saved for bwd (bf16 write+read per unfrozen layer).
    """
    blocks = L * (12 * d * d + 13 * d)
    head = V * d
    n_params = blocks + head + 2 * d
    frac = unfrozen / L if 0 < unfrozen < L else 1.0
    # all-trainable: every param (incl. wte/wpe). Frozen: unfrozen blocks
    # + ln_f only — the mask freezes the embeddings, and the tied head
    # weight IS the frozen wte (value head negligible)
    trainable = n_params if frac == 1.0 else blocks * frac + 2 * d
    weights = (
        2 * n_params            # fwd reads the full bf16 cast
        + 2 * (blocks * frac + head)  # pruned bwd re-reads
        + 4 * trainable         # f32 grads written
    )
    optimizer = 4 * trainable + 16 * trainable + 8 * trainable
    logits = 5 * B * R * V * 4
    acts = 2 * 2 * B * (Q + R) * d * (L * frac)
    return weights + optimizer + logits + acts


def _phase_flops(d, V, L, Q, R, B, ppo_epochs, unfrozen=0):
    """Total matmul FLOPs for one PPO phase (collect + train), exact —
    counting only FLOPs the programs actually perform.

    Trunk weights touched per token: qkv+proj (4 d^2) + mlp (8 d^2) per
    layer. Attention scores/values: 4*d*c FLOPs per token at context
    length c per layer (QK^T and AV, 2 FLOPs/MAC). The lm_head (d*V) is
    counted only where the code actually applies it: the last prefill
    position (`last_only` sampling), each decode step, and the R response
    positions in ref scoring / training (`response_forward` slices hidden
    to responses before the heads). Value head and layernorms negligible.

    With ``unfrozen=k > 0`` (the frozen-top2 SECONDARY workload — the
    reference as shipped trains all layers, its freezing block being
    commented out): the backward is pruned below the branch point
    (stop_gradient + dead-code elimination), so bwd = 2x the top-k trunk
    slice + one d_hidden matmul through the (frozen, tied) lm head.

    The ref term is one full-depth pass in BOTH definitions: a hydra ref
    is (L-k) shared-trunk layers (XLA prunes the capture pass's top-k —
    only branch_hidden is consumed; pinned by
    ``test_freezing.py::test_hydra_capture_flops_match_truncated_trunk``)
    plus k frozen-branch layers + head, and a full-copy ref is L layers +
    head — identical FLOPs.
    """
    trunk = L * 12 * d * d
    T = Q + R

    def trunk_fwd(tokens, ctx_sum, frac=1.0):
        return frac * (2 * trunk * tokens + 4 * L * d * ctx_sum)

    def fwd(tokens, ctx_sum, head_tokens):
        return trunk_fwd(tokens, ctx_sum) + 2 * d * V * head_tokens

    # collect: prefill over Q (logits at the last position only), R
    # single-token decode steps at growing context, and the frozen-ref
    # forward over T with logits at the R response positions
    prefill = fwd(Q, Q * (Q + 1) // 2, 1)
    decode = fwd(R, sum(Q + t + 1 for t in range(R)), R)
    ctx_T = T * (T + 1) // 2
    if 0 < unfrozen < L:
        frac = unfrozen / L
        # hydra ref executes exactly one full-depth pass: (L-k) shared
        # trunk layers (XLA prunes the capture pass's top-k — only
        # branch_hidden is consumed) + k frozen-branch layers + head
        ref = fwd(T, ctx_T, R)
        bwd = 2 * trunk_fwd(T, ctx_T, frac) + 2 * d * V * R  # pruned
    else:
        ref = fwd(T, ctx_T, R)
        bwd = 2 * fwd(T, ctx_T, R)
    collect = B * (prefill + decode + ref)
    train = ppo_epochs * B * (fwd(T, ctx_T, R) + bwd)
    return collect, train

def _reward_tier(budget_seconds=300.0, eps=0.01, patience=4, min_phases=8):
    """The BASELINE metric's other half: mean reward, measured to PLATEAU —
    PPO-steer the locally-pretrained two-topic stand-in checkpoint (the
    offline tier of the reference's gpt2-imdb + distilbert sentiment
    workload, `examples/ppo_sentiments.py:23-54`) until the full-eval mean
    reward stops improving (< ``eps`` gain over the best in ``patience``
    consecutive evals) or the wall-clock budget runs out. Reward is in
    [-1, 1] (response-token sentiment), starting near 0 on balanced
    prompts; the artifact records the whole per-eval curve, so it answers
    "how good does the policy get", not just "did it move" (VERDICT r3 #5).
    """
    import numpy as np

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "examples"))
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.utils.loading import (
        get_orchestrator, get_pipeline, get_trainer,
    )
    from pretrained_standin import (
        causal_rl_config, ensure_gpt2_checkpoint, make_prompts,
        sentiment_reward,
    )

    ckpt_dir = ensure_gpt2_checkpoint()
    config = TRLConfig.from_dict(causal_rl_config(ckpt_dir))
    trainer = get_trainer(config.train.trainer)(
        config, reward_fn=sentiment_reward
    )
    pipeline = get_pipeline(config.train.pipeline)(
        make_prompts(np.random.default_rng(1), 256, 8),
        config.train.seq_length,
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=sentiment_reward,
        chunk_size=config.method.chunk_size,
    )
    # eval on the same prompt set as rounds 1-3 (api.train defaults
    # eval_prompts to the training prompts by reusing the pipeline
    # object — create_loader returns independent generators)
    trainer.add_eval_pipeline(pipeline)

    t0 = time.time()
    curve = [round(float(trainer.evaluate()["reward/mean"]), 4)]
    updates_per_phase = config.method.ppo_epochs * (
        config.method.num_rollouts // config.train.batch_size
    )
    phases = 0
    plateaued = False
    while time.time() - t0 < budget_seconds:
        trainer.buffer.clear_history()
        orch.make_experience(config.method.num_rollouts, phases)
        trainer.train_on_buffer(seed=config.train.seed + phases)
        phases += 1
        curve.append(round(float(trainer.evaluate()["reward/mean"]), 4))
        # plateau only counts after the slow-start window: the curve
        # sits near 0 for the first ~half-dozen phases before moving
        if (
            phases >= min_phases
            and max(curve[-patience:]) < max(curve[:-patience]) + eps
        ):
            plateaued = True
            break
    return {
        "mean_reward_pre": curve[0],
        "mean_reward_post": curve[-1],
        "reward_plateau": max(curve),
        # updates to the PEAK eval (curve[0] is the pre-train eval),
        # not to loop exit — the patience tail is excluded
        "reward_plateau_steps": curve.index(max(curve)) * updates_per_phase,
        "reward_plateaued": plateaued,
        "reward_curve": curve,
        "reward_tier_seconds": round(time.time() - t0, 1),
    }


def _workload_config(num_layers_unfrozen, ref_branch_layers):
    """The BASELINE workload at one of the two freezing definitions.

    Faithful (headline): ``(0, 2)`` — the reference as shipped trains ALL
    layers (freezing commented out, `accelerate_base_model.py:55-69`) with
    the 2-layer hydra KL-ref branch that `test_config.yml:5` actually
    sizes. Frozen-top2 (secondary): ``(2, None)`` — freezing re-enabled.
    """
    from trlx_tpu.data.configs import TRLConfig

    # rollout engine selection (docs/inference.md): default stays the
    # fixed-batch sampler so the r01-r05 series keeps comparing; set
    # TRLX_BENCH_ROLLOUT_ENGINE=continuous to measure the slot-admission
    # engine (the payload then carries collect/admit_ms + slot_util next
    # to the phase tree)
    rollout_engine = os.environ.get("TRLX_BENCH_ROLLOUT_ENGINE", "fixed")
    # asynchronous actor–learner mode (docs/async_pipeline.md): set
    # TRLX_BENCH_ASYNC_RL=1 to run the phases on the async schedule
    # (forces the continuous engine; TRLX_BENCH_ASYNC_STALENESS tunes
    # the window, default 1). The default fixed-path r01–r05 series
    # stays comparable — async is opt-in per round, and the payload
    # then carries async/staleness_p50, async/learner_idle_ms and the
    # actor/learner occupancy next to the span tree.
    async_rl_on = os.environ.get("TRLX_BENCH_ASYNC_RL") == "1"
    async_rl = (
        {
            "enabled": True,
            "staleness_window": int(
                os.environ.get("TRLX_BENCH_ASYNC_STALENESS", "1")
            ),
        }
        if async_rl_on
        else {}
    )
    if async_rl_on:
        rollout_engine = "continuous"

    return TRLConfig.from_dict(
        {
            "model": {
                "model_type": "gpt2",
                "num_layers_unfrozen": num_layers_unfrozen,
                "ref_branch_layers": ref_branch_layers,
                "model_arch": {
                    "vocab_size": 50257,
                    "n_positions": 1024,
                    "n_embd": 768,
                    "n_layer": 12,
                    "n_head": 12,
                    # "auto" resolves to int8 at this cache shape (cap
                    # 112 <= INT8_KV_MAX_CAPACITY): measured 1.10x on the
                    # sampler (interleaved A/B, ab_int8_kv.py) — decode is
                    # HBM-bound and the cache is its dominant traffic.
                    # bf16 beyond the measured long-context crossover.
                    "kv_cache_dtype": "auto",
                },
            },
            "train": {
                "seq_length": 64,
                "batch_size": 16,
                "epochs": 3,
                "total_steps": 10000,
                "eval_interval": 100000,
                "checkpoint_interval": 1000000,
                "lr_init": 1.412e-4,
                "lr_target": 1.412e-4,
                "mesh": {"dp": -1, "fsdp": 1, "tp": 1},
                "dtype": "bfloat16",
                # run-health monitoring on (docs/observability.md): the
                # fused health scalars ride the phase's existing stats
                # transfer and the detector/event counts ship in the
                # BENCH payload — a bench round that tripped kl-spike or
                # entropy-collapse is not a clean perf sample.
                # SERIES NOTE (r06+): enabling health adds real device
                # work to the timed train step (full-vocab softmax
                # entropy at ent_coef=0, reward quantiles) — a one-time,
                # instrumentation-caused discontinuity vs the r01-r05
                # series; attribute any small train-phase delta at r06
                # here first before hunting regressions (the CPU perf
                # gate's harness keeps health off, so engine 10's
                # lockfile is unaffected)
                "health": {"enabled": True},
                "rollout": {"engine": rollout_engine},
                "async_rl": async_rl,
            },
            "method": {
                "name": "PPOConfig",
                "num_rollouts": 128,
                "chunk_size": 128,
                "ppo_epochs": 4,
                "init_kl_coef": 0.2,
                "target": 6,
                "horizon": 10000,
                "cliprange_reward": 10,
                "scale_reward": "running",
                "gen_kwargs": {
                    "max_new_tokens": 48,
                    # fixed-length rollouts, as the reference workload
                    # (ppo_config.yml: min_length == max_length)
                    "min_new_tokens": 48,
                    "top_k": 0,
                    "do_sample": True,
                    "eos_token_id": 50256,
                    "pad_token_id": 50256,
                },
            },
        }
    )

def measure_throughput(config, out, n_phases=5):
    """Run the PPO phase loop for one workload definition and fill ``out``
    with the hardware-grounded metrics (samples/s/chip, tok/s, MFU, HBM
    util) as they become available — a phase that raises leaves what was
    measured before it in ``out`` for ``main`` to print."""
    import jax
    import numpy as np

    from trlx_tpu.utils.loading import get_orchestrator, get_pipeline, get_trainer

    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(100, 40000, size=rng.integers(4, 33)))
               for _ in range(512)]

    def reward_fn(samples, queries, response_gt=None):
        # cheap host reward: length-normalized char diversity
        return [len(set(s)) / max(len(s), 1) for s in samples]

    trainer = get_trainer(config.train.trainer)(config, reward_fn=reward_fn)
    pipeline = get_pipeline(config.train.pipeline)(
        prompts, config.train.seq_length
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_fn, chunk_size=config.method.chunk_size
    )

    # compile accounting (docs/static_analysis.md, engine 8): the same
    # monitor the --compile-audit gate uses counts every actual XLA
    # compile over the bench's phase loop, so a retrace burning wall
    # clock shows up NEXT TO the throughput number it depressed
    from trlx_tpu.analysis.compile_audit import CompileMonitor

    monitor = CompileMonitor()

    # span accounting (docs/observability.md): the phase loop is
    # instrumented by the telemetry tracer — the measured window's span
    # tree ships in the BENCH payload under stable keys so the perf
    # trajectory is machine-diffable across rounds (engine 10 gates the
    # same spans on the CPU tier)
    from trlx_tpu import telemetry

    tracer = telemetry.configure(enabled=True)

    times = {"collect": 0.0, "train": 0.0}
    overlap_saved = {"ms": 0.0, "phases": 0}
    phase_seed = [0]

    def one_phase(record=False):
        trainer.buffer.clear_history()
        phase_seed[0] += 1
        # streamed phase (the production default, docs/async_pipeline.md):
        # epoch-1 updates dispatch during collection; epochs 2..E run as
        # the fused residual scan in finish_streamed_phase. Falls back to
        # the legacy fused pass when overlap is disabled in the config.
        streamed = config.train.phase_overlap
        t0 = time.time()
        if streamed:
            trainer.begin_streamed_phase(seed=phase_seed[0])
        orch.make_experience(config.method.num_rollouts, 0)
        # make_experience ends on host-side reward work; the buffer is
        # device-resident, so the collect/train split is the dispatch
        # boundary here (the train window's block covers any tail — note
        # that with overlap on, epoch-1 device work already ran inside
        # the collect window: that is the effect being measured)
        t1 = time.time()
        if streamed:
            _, phase_rows, _ = trainer.finish_streamed_phase()
            phase_stats = phase_rows  # host rows already fetched
        else:
            # one fused dispatch for all minibatch x ppo_epoch updates
            _, phase_stats, _ = trainer.train_on_buffer()
        # fence: wait for the updated params AND read one program output
        # back, so the train window closes on a value the host holds and
        # no train time can slide into the next phase's collect window
        jax.block_until_ready(trainer.state.params)
        float(np.asarray(jax.device_get(next(iter(
            jax.tree_util.tree_leaves(phase_stats)
        )))).ravel()[0])
        t2 = time.time()
        if record:
            times["collect"] += t1 - t0
            times["train"] += t2 - t1
            if streamed:
                overlap_saved["ms"] += trainer._last_overlap_stats.get(
                    "exp/overlap_saved_ms", 0.0
                )
                overlap_saved["phases"] += 1

    # __exit__ MUST run even when a phase raises: the monitor holds jax's
    # pxla/dispatch loggers at DEBUG with a handler attached, and a leaked
    # handler swallows compile logs process-wide (counts stay readable
    # after exit)
    monitor.__enter__()
    try:
        one_phase()  # warmup: compile sampler + fused train phase
        one_phase()  # 2nd warmup: absorbs any donated-buffer relayout retrace
        monitor.mark_steady()  # any compile past here retraced mid-measurement
        tracer.clear()  # span stats cover the measured phases only

        start = time.time()
        for _ in range(n_phases):
            one_phase(record=True)
        elapsed = time.time() - start
    finally:
        monitor.__exit__(None, None, None)

    n_chips = len(jax.devices())
    samples_per_sec = n_phases * config.method.num_rollouts / elapsed
    per_chip = samples_per_sec / n_chips

    # hardware-grounded numbers: tokens/s per phase, FLOP/s, MFU
    arch = config.model.model_arch
    B, Q = config.method.num_rollouts, config.train.seq_length
    R = config.method.gen_kwargs["max_new_tokens"]
    collect_flops, train_flops = _phase_flops(
        d=arch["n_embd"], V=arch["vocab_size"], L=arch["n_layer"],
        Q=Q, R=R, B=B, ppo_epochs=config.method.ppo_epochs,
        unfrozen=config.model.num_layers_unfrozen,
    )
    kind = jax.devices()[0].device_kind
    peak, hbm_peak = device_peaks(kind)
    achieved_tflops = (
        n_phases * (collect_flops + train_flops) / elapsed / n_chips / 1e12
    )
    out.update({
        "value": round(per_chip, 3),
        # generated tokens over the whole collect window (incl. prefill,
        # frozen-ref forward, host reward) — rollout throughput, not a
        # bare decode-step rate
        "rollout_tok_per_sec_per_chip": round(
            n_phases * B * R / times["collect"] / n_chips, 1
        ),
        "train_tok_per_sec_per_chip": round(
            n_phases * config.method.ppo_epochs * B * (Q + R)
            / times["train"] / n_chips,
            1,
        ),
        "achieved_tflops_per_chip": round(achieved_tflops, 2),
        "device_kind": kind,
        "collect_ms_per_phase": round(times["collect"] / n_phases * 1e3, 1),
        "train_ms_per_phase": round(times["train"] / n_phases * 1e3, 1),
    })
    if overlap_saved["phases"]:
        # per-phase estimate of epoch-1 device time hidden under the
        # collect window by the streamed schedule (docs/async_pipeline.md;
        # ground truth for the wall-clock delta is ab_phase_overlap.py)
        out["exp/overlap_saved_ms"] = round(
            overlap_saved["ms"] / overlap_saved["phases"], 1
        )
    # async actor–learner attribution (TRLX_BENCH_ASYNC_RL=1,
    # docs/async_pipeline.md): staleness distribution, learner idle,
    # and actor/learner occupancy of the last measured phase ride the
    # payload next to the span tree (ground truth for the wall-clock
    # delta is ab_async_rl.py, which self-records)
    for key in (
        "async/staleness_p50", "async/staleness_max",
        "async/consumed_lag_p50", "async/consumed_lag_max",
        "async/learner_idle_ms", "async/guard_hold_ms",
        "async/actor_occupancy", "async/learner_occupancy",
        "async/weight_pushes",
    ):
        if key in trainer._last_overlap_stats:
            out[key] = round(float(trainer._last_overlap_stats[key]), 4)
    out["mfu"] = round(achieved_tflops / peak, 4)
    out["bf16_peak_tflops"] = peak
    out["train_phase_mfu"] = round(
        n_phases * train_flops / times["train"] / n_chips / 1e12 / peak, 4
    )
    # the weakest phase gets its own falsifiable number (VERDICT r2):
    # collect = compiled sampler + frozen-ref forward + host reward
    out["collect_phase_mfu"] = round(
        n_phases * collect_flops / times["collect"] / n_chips / 1e12 / peak,
        4,
    )
    # per-chip traffic: weights replicate over dp (each chip streams
    # them in full), cache/logits follow the chip's batch shard
    from trlx_tpu.models.gpt2 import resolve_kv_cache_dtype

    kv_dtype = resolve_kv_cache_dtype(
        arch.get("kv_cache_dtype", "bfloat16"), Q + R
    )
    per_chip_bytes = _collect_bytes(
        d=arch["n_embd"], V=arch["vocab_size"], L=arch["n_layer"],
        Q=Q, R=R, B=B // n_chips,
        kv_cache_bytes=1 if kv_dtype == "int8" else 2,
    )
    gbps = n_phases * per_chip_bytes / times["collect"] / 1e9
    out["collect_phase_hbm_gbps"] = round(gbps, 1)
    out["collect_phase_hbm_util"] = round(gbps / hbm_peak, 4)
    # train-phase roofline next to its MFU (VERDICT r4 #2): required
    # bytes per step x steps over measured train time
    steps = config.method.ppo_epochs * (B // config.train.batch_size)
    step_bytes = _train_step_bytes(
        d=arch["n_embd"], V=arch["vocab_size"], L=arch["n_layer"],
        Q=Q, R=R, B=config.train.batch_size // n_chips,
        unfrozen=config.model.num_layers_unfrozen,
    )
    tgbps = n_phases * steps * step_bytes / times["train"] / 1e9
    out["train_phase_hbm_gbps"] = round(tgbps, 1)
    out["train_phase_hbm_util"] = round(tgbps / hbm_peak, 4)
    # per-phase span tree over the measured window (stable keys: the
    # engine-10 gated spans as flat *_ms p50s + the full stats table) —
    # the round-over-round perf diff reads these instead of eyeballing
    # collect_ms/train_ms
    span_stats = tracer.stats()
    for key, flat in (
        ("phase/collect", "phase/collect_ms"),
        ("phase/train", "phase/train_ms"),
        ("train/drain", "phase/drain_ms"),
        # continuous-engine decode-loop spans (docs/inference.md):
        # admission bookkeeping, prefill dispatch, harvest/recycle —
        # present only when the engine ran this round
        ("collect/admit", "collect/admit_ms"),
        ("collect/prefill", "collect/prefill_ms"),
        ("collect/slot_recycle", "collect/slot_recycle_ms"),
    ):
        if key in span_stats:
            out[flat] = round(span_stats[key]["p50_ms"], 1)
    # slot-occupancy stats ride the payload next to the span tree when
    # the continuous engine collected this round
    if (
        getattr(trainer, "rollout_engine", "fixed") == "continuous"
        and getattr(trainer, "_rollout_engine_obj", None) is not None
    ):
        engine_stats = trainer._rollout_engine_obj.stats.to_dict()
        # one canonical key for occupancy; the remaining engine/*
        # counters keep their namespaced names
        out["slot_util"] = engine_stats.pop("engine/slot_util")
        out.update(engine_stats)
    out["spans"] = {
        name: {
            "count": int(s["count"]),
            "p50_ms": round(s["p50_ms"], 2),
            "p95_ms": round(s["p95_ms"], 2),
            "total_ms": round(s["total_ms"], 1),
        }
        for name, s in span_stats.items()
    }
    # ring evictions skew the p50s above with no other signal — surface
    # the count in the payload and warn once on stderr when nonzero
    out["spans_dropped"] = telemetry.warn_on_span_drops(tracer)
    # utilization attribution (telemetry/attribution.py,
    # docs/observability.md): engine-7 statics ÷ the measured span walls
    # above — measured MFU + HBM-BW util per traced program, the async
    # bubble breakdown, and phase goodput. The table prints to stderr
    # (stdout stays one JSON line); the payload carries the same rows.
    out.update(
        _attribution_payload(trainer, config, span_stats, n_phases, n_chips)
    )
    # run-health summary (docs/observability.md): detector trip counts
    # over the measured window (a tripped kl-spike/entropy-collapse
    # means the throughput sample rode a diverging run) + the last
    # observed training-dynamics scalars. NOTE: distinct name — the
    # health block used to rebind `monitor` (the CompileMonitor), so
    # every health-enabled bench run crashed at the compile-counts
    # epilogue below with HealthMonitor.counts()
    health_mon = getattr(trainer, "health_monitor", None)
    if health_mon is not None:
        out["health_events"] = dict(sorted(health_mon.event_counts.items()))
        out["health"] = health_mon.health_summary()
    static_res = _static_resources(trainer)
    out.update(static_res)
    out.update(_compiled_resources(trainer, static_res))
    out.update(
        _measured_memory(static_res.get("static_train_step_peak_hbm_gb"))
    )
    # per-callable compile counts + trace/compile wall time over the
    # whole run (warmups included); steady_compiles > 0 means a program
    # RETRACED inside the measured window — the throughput above paid
    # for XLA time and the run deserves a --compile-audit triage. One-off
    # warmup compiles of eager primitives are folded into a single total
    # so the phase programs (and anything that compiled twice) stand out.
    counts = monitor.counts()
    steady = monitor.counts(steady_only=True)
    phase_programs = {
        "sampler", "train_step", "train_phase", "behavior_snapshot",
    }
    out["compile_counts"] = {
        name: n
        for name, n in sorted(counts.items())
        if name in phase_programs or n > 1 or steady.get(name)
    }
    out["eager_op_compiles"] = sum(
        n for name, n in counts.items()
        if name not in out["compile_counts"]
    )
    if steady:
        out["steady_compiles"] = dict(sorted(steady.items()))
    out["trace_seconds"] = round(monitor.trace_seconds, 1)
    out["compile_seconds"] = round(monitor.compile_seconds, 1)
    # metrics snapshot for THIS workload's ledger manifest — the
    # registry is process-global, so without capturing here the frozen
    # secondary run would overwrite the gauges the faithful manifest
    # reports; _record() drops this from the printed JSON line
    out["_metrics_snapshot"] = telemetry.get_metrics().snapshot()


def _attribution_payload(trainer, config, span_stats, n_phases, n_chips):
    """Measured-MFU ledger for the bench window (docs/observability.md,
    "Utilization attribution"): engine-7 statics traced at the REAL
    workload shape joined with the measured span walls. Prints the
    "where did the time go" table + async bubble breakdown to stderr;
    returns the machine-readable payload keys."""
    import jax

    from trlx_tpu.telemetry import attribution

    method = config.method
    n_mb = max(method.num_rollouts // config.train.batch_size, 1)
    resources = attribution.trainer_program_resources(
        trainer,
        kind="ppo",
        chunk_size=method.chunk_size,
        residual_len=n_mb * max(method.ppo_epochs - 1, 0),
    )
    engine = (
        "continuous"
        if getattr(trainer, "rollout_engine", "fixed") == "continuous"
        else "fixed"
    )
    counts = {}
    if getattr(trainer, "_rollout_engine_obj", None) is not None:
        # EngineStats resets every start_phase, so the counters
        # cover the LAST measured phase only, while the span walls
        # accumulate over all n_phases — scale to the whole window
        # (identical workload per phase) or the count_key rows
        # would understate utilization by n_phases x
        counts.update(
            {
                k: v * n_phases
                for k, v in trainer._rollout_engine_obj.stats.to_dict().items()
                if isinstance(v, (int, float))
                and k != "engine/slot_util"  # a ratio, not a counter
            }
        )
    rows = attribution.attribute(
        resources,
        span_stats,
        device_kind=jax.devices()[0].device_kind,
        n_devices=n_chips,
        work=attribution.default_work(engine),
        counts=counts,
    )
    bubbles = attribution.bubble_breakdown(
        span_stats,
        getattr(trainer, "_last_overlap_stats", None),
        phases=n_phases,
    )
    goodput = attribution.phase_goodput(
        span_stats, method.num_rollouts, phases=n_phases
    )
    print(
        attribution.format_attribution(rows, bubbles, goodput),
        file=sys.stderr,
    )
    out = {
        "attribution": [r.to_dict() for r in rows],
        "bubbles": {
            k: round(v, 4) for k, v in bubbles.items()
        },
    }
    if "goodput_samples_per_sec" in goodput:
        out["goodput_samples_per_sec"] = round(
            goodput["goodput_samples_per_sec"], 3
        )
    return out


def _static_resources(trainer):
    """Static resource-auditor numbers for the jitted train step at the
    REAL workload shape (docs/static_analysis.md, engine 6) — tracing
    only, no compilation. Printed next to the measured stats so every
    bench run surfaces the same contracts CI gates: peak live HBM per
    device (donation- and sharding-aware), modeled collective bytes, and
    counted step FLOPs (an exact-arithmetic cross-check of
    ``_phase_flops``' closed form)."""
    from trlx_tpu.analysis.resource_audit import trainer_step_resources

    res = trainer_step_resources(trainer)
    return {
        "static_train_step_peak_hbm_gb": round(
            res.peak_hbm_bytes / 2**30, 3
        ),
        "static_train_step_collective_mb": round(
            res.collective_bytes / 2**20, 3
        ),
        "static_train_step_gflops": round(res.flops / 1e9, 1),
    }


def _compiled_resources(trainer, static_res):
    """Compiled ground truth next to the engine-6 statics
    (docs/static_analysis.md, engine 13): the train step's actual
    post-SPMD HLO collective payload and buffer-assignment peak from
    the SAME jit instance the bench drives (the step is already
    compiled by the measured window, so this re-lowers from cache).
    The ``static_vs_compiled`` ratios are the live twin of the
    hlo-memory-drift / collective-profile gates CI runs — a bench
    round where compiled/static drifts while the lockfile is green
    means the bench shape diverged from the audit shape, not XLA."""
    from trlx_tpu.analysis.hlo_audit import compiled_step_stats

    kind = (
        "ilql"
        if trainer.__class__.__name__.startswith("ILQL")
        else "ppo"
    )
    stats = compiled_step_stats(trainer, kind)
    out = {
        k: round(v, 3) for k, v in stats.items()
    }
    ratios = {}
    static_mb = static_res.get("static_train_step_collective_mb")
    if static_mb and "compiled_train_step_collective_mb" in stats:
        ratios["collective_mb_compiled_over_static"] = round(
            stats["compiled_train_step_collective_mb"] / static_mb, 3
        )
    static_gb = static_res.get("static_train_step_peak_hbm_gb")
    if static_gb and "compiled_train_step_peak_hbm_gb" in stats:
        ratios["peak_hbm_compiled_over_static"] = round(
            stats["compiled_train_step_peak_hbm_gb"] / static_gb, 3
        )
    if ratios:
        out["static_vs_compiled"] = ratios
    return out


def _measured_memory(static_peak_gb):
    """Allocator-measured HBM next to the static engine-7 prediction
    (telemetry/device_metrics.py). The measured value is the PROCESS
    peak (sampler + snapshot + stream store + train step together), so
    the ratio against the static train-step contract is a
    phase-footprint signal — a round-over-round rise means the run's
    memory grew somewhere the step lockfile does not gate. Reuses the
    static number `_static_resources` already computed (the engine-7
    trace costs seconds at the bench shape)."""
    from trlx_tpu.telemetry.device_metrics import static_vs_measured

    static_bytes = (
        int(static_peak_gb * 2**30) if static_peak_gb else None
    )
    res = static_vs_measured(static_peak_bytes=static_bytes)
    out = {}
    if "measured_peak_hbm_bytes" in res:
        out["measured_peak_hbm_gb"] = round(
            res["measured_peak_hbm_bytes"] / 2**30, 3
        )
    if "measured_process_peak_over_static_step" in res:
        out["measured_process_peak_over_static_step"] = res[
            "measured_process_peak_over_static_step"
        ]
    return out


def _record(device, faithful, frozen, reward):
    """Assemble the printed JSON record from whatever the phases measured
    (after a failed phase the later keys are simply absent)."""
    extras = dict(faithful)
    extras.pop("_metrics_snapshot", None)
    per_chip = extras.pop("value", None)
    if "value" in frozen:
        extras["value_frozen_top2"] = frozen["value"]
        extras["vs_baseline_frozen_top2"] = round(
            frozen["value"] / A100_BASELINE_SAMPLES_PER_SEC, 3
        )
    for k in ("train_tok_per_sec_per_chip", "train_phase_mfu",
              "train_ms_per_phase", "collect_ms_per_phase"):
        if k in frozen:
            extras[f"{k}_frozen_top2"] = frozen[k]
    extras.update(reward)
    record = {
        "schema_version": BENCH_SCHEMA_VERSION,
        "metric": "ppo_samples_per_sec_per_chip_gpt2s",
        "value": per_chip,
        "unit": "samples/s/chip",
        "device": device,
    }
    if per_chip is not None:
        ratio = per_chip / A100_BASELINE_SAMPLES_PER_SEC
        record["vs_baseline"] = round(ratio, 3)
        extras["north_star_throughput_ratio"] = round(ratio, 3)
        extras["north_star_throughput_met"] = ratio >= 4.0
        extras["north_star_reward_status"] = "env-blocked-standin"
        if "reward_plateau" in extras:
            extras["standin_reward_plateau"] = extras["reward_plateau"]
            verb = (
                "plateaus at" if extras.get("reward_plateaued")
                else "reaches (budget-capped, still rising)"
            )
            extras["north_star"] = (
                f"throughput {per_chip:.0f} samples/s/chip (faithful "
                f"full-train workload) = {ratio:.1f}x the documented "
                f"single-A100 torch-trlX estimate (>=4x required); reward "
                f">=1.2 on gpt2-imdb+distilbert is env-blocked (zero "
                f"egress) — stand-in sentiment task {verb} "
                f"{extras['reward_plateau']} (range [-1,1]) after "
                f"{extras['reward_plateau_steps']} updates"
            )
    record.update(extras)
    return record


def main():
    os.environ.setdefault("WANDB_DISABLED", "1")
    from trlx_tpu.utils.compile_cache import enable_compile_cache

    # the gate: no TPU, or one without published peaks, is a refusal —
    # raised before anything compiles
    device = require_tpu()
    enable_compile_cache()

    faithful, frozen, reward = {}, {}, {}
    try:
        # HEADLINE: faithful reconstruction of the reference as shipped —
        # all 12 layers train (the reference's PPO freezing is commented
        # out), 2-layer hydra KL-ref branch (what test_config.yml:5
        # actually sizes).
        measure_throughput(_workload_config(0, 2), faithful)
        # SECONDARY: the frozen-top2 workload (freezing re-enabled as
        # work-avoidance; lighter train phase).
        measure_throughput(_workload_config(2, None), frozen)
        reward.update(_reward_tier())
    finally:
        # a phase that raised still leaves what was measured on stdout;
        # the exception then ends the process with a nonzero code
        record = _record(device, faithful, frozen, reward)
        print(json.dumps(record))

    # run ledger (telemetry/run_ledger.py): every bench round appends a
    # manifest — config fingerprint, platform, git sha, the attribution
    # table, and the full payload — so `python -m trlx_tpu.telemetry
    # --compare` diffs rounds mechanically.
    from trlx_tpu.telemetry.run_ledger import (
        append_manifest,
        build_manifest,
        numeric_payload,
    )

    path = append_manifest(
        build_manifest(
            "bench",
            payload=numeric_payload(record),
            attribution=record.get("attribution") or [],
            span_stats=record.get("spans") or {},
            # the faithful (headline) workload's registry snapshot —
            # never part of the printed JSON line
            metrics=faithful.get("_metrics_snapshot"),
        )
    )
    print(f"bench: run manifest appended to {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
