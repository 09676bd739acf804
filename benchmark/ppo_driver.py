"""Driver ``ppo``: whole PPO phases (collect, score, reward, update) through
the program's trainer, orchestrator and pipeline, driven phase by phase in
the way ``bench.py::measure_throughput`` drives them — the streamed phase
where ``phase_overlap`` is on (the default), the window closed on
``block_until_ready`` of the new parameters plus a fetched program output.

A traffic file with ``"driver": "ppo"`` gives: ``seq_length``,
``prompt_lengths``, ``new_tokens``, ``num_rollouts``, ``chunk_size``,
``batch_size``, ``ppo_epochs``, ``num_layers_unfrozen``,
``ref_branch_layers``, ``lr``, ``warmup_phases``, ``trace_phases`` and,
optionally, ``engine``: the program's ``train.rollout.engine`` (``fixed``,
the compiled sampler, where the file has no such key; ``continuous`` is
the slot engine).
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict

import numpy as np

from benchmark import checks, harness, loadgen
from benchmark.arithmetic import model_shape, ppo_phase_flops

N_CHECK_ROWS = 4


def build_config(cell: Dict[str, Any], seed: int):
    from trlx_tpu.data.configs import TRLConfig

    cf, t = cell["config_file"], cell["traffic_file"]
    arch = harness.arch_of(cf)
    eos = cf["vocab_size"] - 1
    scratch = os.path.join(harness.REPO, ".bench_trace", "program_out")
    return TRLConfig.from_dict({
        "model": {
            "model_type": cf["model_type"],
            "num_layers_unfrozen": t["num_layers_unfrozen"],
            "ref_branch_layers": t["ref_branch_layers"],
            "model_arch": arch,
        },
        "train": {
            "seed": loadgen.program_seed(seed),
            "seq_length": t["seq_length"],
            "batch_size": t["batch_size"],
            "epochs": 1,
            "total_steps": 1000000,
            "eval_interval": 1000000,
            "checkpoint_interval": 1000000,
            "checkpoint_dir": os.path.join(scratch, "ckpt"),
            "lr_init": t["lr"],
            "lr_target": t["lr"],
            "mesh": dict(cell["mesh"]),
            "dtype": cf["run"]["dtype"],
            "param_dtype": cf["run"]["param_dtype"],
            "health": {"enabled": True, "dump_dir": os.path.join(scratch, "health_dumps")},
            "rollout": {"engine": t.get("engine", "fixed")},
        },
        "method": {
            "name": "PPOConfig",
            "num_rollouts": t["num_rollouts"],
            "chunk_size": t["chunk_size"],
            "ppo_epochs": t["ppo_epochs"],
            "init_kl_coef": 0.2,
            "target": 6,
            "horizon": 10000,
            "cliprange_reward": 10,
            "scale_reward": "running",
            "gen_kwargs": {
                "max_new_tokens": t["new_tokens"],
                "min_new_tokens": t["new_tokens"],
                "top_k": 0,
                "do_sample": True,
                "eos_token_id": eos,
                "pad_token_id": eos,
            },
        },
    })


class RewardProbe:
    """``bench.py``'s cheap host reward — a deterministic function of the
    sampled token ids (without a tokenizer a sample is its ids joined by
    spaces) — which also keeps the accounting: samples seen, and the
    smallest and largest token id."""

    def __init__(self, spans: harness.Spans):
        self.spans = spans
        self.samples = 0
        self.lo, self.hi = 1 << 62, -1

    def __call__(self, samples, queries, response_gt=None):
        with self.spans.span("reward"):
            for s in samples:
                ids = [int(t) for t in s.split()]
                self.samples += 1
                if ids:
                    self.lo, self.hi = min(self.lo, min(ids)), max(self.hi, max(ids))
            return [len(set(s)) / max(len(s), 1) for s in samples]


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_start: float, device: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    from trlx_tpu.utils.loading import get_orchestrator, get_pipeline, get_trainer

    cf, t = cell["config_file"], cell["traffic_file"]
    compiles = harness.CompileCounter().install()
    spans = harness.Spans()
    config = build_config(cell, seed)
    reward = RewardProbe(spans)
    prompts = loadgen.draw_prompts(
        t["prompt_lengths"], 4 * t["num_rollouts"], cf["vocab_size"],
        t["traffic_seed"], seed,
    )
    t_imported = time.time()
    trainer = get_trainer(config.train.trainer)(config, reward_fn=reward)
    t_built = time.time()
    pipeline = get_pipeline(config.train.pipeline)(prompts, config.train.seq_length)
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward, chunk_size=config.method.chunk_size
    )
    streamed = bool(config.train.phase_overlap)
    rollouts = t["num_rollouts"]
    state = {"phase": 0, "nonfinite": 0, "rows": 0}

    def one_phase() -> None:
        trainer.buffer.clear_history()
        state["phase"] += 1
        with spans.span("phase"):
            with spans.span("collect"):
                if streamed:
                    trainer.begin_streamed_phase(seed=state["phase"])
                orch.make_experience(rollouts, 0)
            with spans.span("train"):
                if streamed:
                    _, stats, _ = trainer.finish_streamed_phase()
                else:
                    _, stats, _ = trainer.train_on_buffer()
                # the fence of bench.py: the new parameters are ready and
                # one program output is on the host
                jax.block_until_ready(trainer.state.params)
                leaves = [np.asarray(jax.device_get(x)) for x in jax.tree_util.tree_leaves(stats)]
        state["rows"] += len(trainer.buffer)
        state["nonfinite"] += sum(int((~np.isfinite(x.astype(np.float64))).sum()) for x in leaves)

    warm = []
    for _ in range(int(t["warmup_phases"])):
        w0 = time.time()
        one_phase()
        warm.append(round(time.time() - w0, 1))
    print(f"note setup: to_imports={t_imported - t_start:.1f}s trainer={t_built - t_imported:.1f}s "
          f"warmup_phases={warm} compiles={compiles.count} compile_s={compiles.seconds:.1f}",
          flush=True)
    fingerprint = jax.jit(
        lambda p: jax.numpy.stack([jax.numpy.abs(x).sum() for x in jax.tree_util.tree_leaves(p)])
    )
    before = np.asarray(fingerprint(trainer.state.params))
    step0 = int(trainer.state.step)
    spans.clear()
    state.update(nonfinite=0, rows=0)
    reward.samples = 0
    from trlx_tpu import telemetry

    tracer = telemetry.get_tracer()
    tracer.clear()
    window = harness.ProfilerWindow(cell["name"]) if trace else None
    setup_s = time.time() - t_start
    mark = compiles.mark()
    scalars = harness.registry_scalars()

    attempted = failed = 0
    if window:
        window.start()
    limit = int(t["trace_phases"]) if trace else 1 << 30
    t0 = t_fenced = time.perf_counter()
    while attempted < limit and (attempted == 0 or time.perf_counter() - t0 < seconds):
        attempted += 1
        try:
            one_phase()
            t_fenced = time.perf_counter()
        except Exception as e:  # a phase that raises is a failed operation
            failed += 1
            print(f"phase {attempted} raised {type(e).__name__}: {e}", flush=True)
            if streamed:
                trainer.abort_streamed_phase()
            break
    xplane = window.stop() if window else None
    compiled_in_window = compiles.mark()[0] - mark[0]
    scalars = harness.registry_scalars(scalars)
    done = attempted - failed

    # ---------------- outside the window: what decides `correct` ---------------- #
    memory_peak = harness.memory_peak_bytes()  # the program's own: read before the reference runs
    log = harness.CheckLog()
    ok = True
    steps = int(trainer.state.step) - step0
    ok &= log.line("accounting.samples_trained_eq_collected",
                   steps * t["batch_size"], f"== {done * rollouts * t['ppo_epochs']}",
                   steps * t["batch_size"] == done * rollouts * t["ppo_epochs"]
                   and state["rows"] == done * rollouts
                   and reward.samples == done * rollouts)
    ok &= log.line("accounting.token_ids_in_vocab", [reward.lo, reward.hi],
                   f"in [0, {cf['vocab_size']})",
                   0 <= reward.lo and reward.hi < cf["vocab_size"])
    ok &= log.line("accounting.nonfinite_step_statistics", state["nonfinite"], "== 0",
                   state["nonfinite"] == 0)
    after = np.asarray(fingerprint(trainer.state.params))
    moved = int((before != after).sum())
    ok &= log.line("accounting.parameter_leaves_moved", moved, ">= 1",
                   moved >= 1 and bool(np.isfinite(after).all()))
    ok &= log.line("accounting.compiles_in_window", compiled_in_window, "== 0",
                   compiled_in_window == 0)
    ok &= reference_check(cell, trainer, seed, log)
    events = dict(sorted(trainer.health_monitor.event_counts.items()))
    print(f"note health_events (not part of correct): {events}", flush=True)

    phase_s = [ms / 1e3 for ms in spans.durations_ms("phase")]
    print(f"note ppo: phases={done} wall_to_last_fence_s={t_fenced - t0:.4f} "
          f"inside_phase_spans_s={sum(phase_s):.4f} "
          f"samples_per_s={done * rollouts / (t_fenced - t0) if done else 0.0:.4f}", flush=True)
    # each phase's own length: a run that reads far off shows here whether
    # one phase stalled or all of them were slow
    print(f"note ppo: phase_s={[round(x, 3) for x in phase_s]}", flush=True)
    shape = model_shape(cell["family"], cf)
    record = {
        "kind": "ppo", "cell": cell, "device": device, "spans": spans,
        "tracer_stats": tracer.stats(), "phases": done, "window_s": t_fenced - t0,
        "counters": scalars["counters"], "gauges": scalars["gauges"],
        "setup_s": setup_s, "compile_s_setup": mark[1], "xplane": xplane,
        "chips": cell["chips"], "shape": shape,
        "flops": ppo_phase_flops(shape, t["seq_length"], t["new_tokens"], rollouts,
                                 t["ppo_epochs"], t["num_layers_unfrozen"] or 0),
        "kv_cache_dtype": harness.kv_dtype_of(cf, t["seq_length"] + t["new_tokens"]),
        "state_dtype": cf["run"].get("state_dtype"), "memory_peak_bytes": memory_peak,
        # one sampler call decodes a chunk for new_tokens steps (its prefill
        # rides in the same module and is not counted as required bytes)
        "decode": {
            "batch": t["chunk_size"], "steps_per_call": t["new_tokens"],
            "mean_context": t["seq_length"] + t["new_tokens"] / 2.0,
            "weight_shards": cell["mesh"].get("fsdp", 1) * cell["mesh"].get("tp", 1),
        },
    }
    end_to_end = {
        # over all the time from the window's start to the last phase's
        # fence, so whatever the host does between phases counts
        "ppo_samples_per_s": {
            "value": done * rollouts / (t_fenced - t0) if done else 0.0,
            "unit": "samples/s",
        },
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    return {"correct": bool(ok), "attempted": attempted, "failed": failed,
            "end_to_end": end_to_end, "record": record, "checks": log}


def reference_check(cell: Dict[str, Any], trainer, seed: int, log: harness.CheckLog) -> bool:
    """Fresh rollouts from the trained parameters through the program's
    compiled sampler; for ``N_CHECK_ROWS`` of them, seeded, the logits of
    the update's forward and the sampler's recorded log-probabilities
    against the float32 reference on the same (master) parameters."""
    import jax
    import jax.numpy as jnp

    cf, t = cell["config_file"], cell["traffic_file"]
    Q = t["seq_length"]
    full = trainer.buffer.full
    # host copies: the sampler's jit lays its inputs out itself
    all_ids = np.asarray(jax.device_get(full.query_tokens))
    all_mask = np.asarray(jax.device_get(full.query_mask))
    out = trainer.sample(all_ids, all_mask)
    rows = np.sort(loadgen.rng_for(seed, "check").choice(
        all_ids.shape[0], N_CHECK_ROWS, replace=False))
    take = lambda a: np.asarray(jax.device_get(a))[rows]
    q_ids, q_mask = all_ids[rows], all_mask[rows]
    r_ids, r_mask, r_lp = take(out.tokens), take(out.response_mask), take(out.logprobs)
    ids = np.concatenate([q_ids, r_ids], axis=1)
    mask = np.concatenate([q_mask, r_mask], axis=1)
    # the single-device reference: parameters gathered onto one device
    one = jax.devices()[0]
    params = jax.device_put(trainer.state.params, one)
    ids_d, mask_d = jax.device_put(jnp.asarray(ids), one), jax.device_put(jnp.asarray(mask), one)
    ref = checks.reference_logits(cell["family"], cf, params[trainer.backbone_key], ids_d, mask_d, Q)
    model = trainer.model
    # the update's forward on the parameters as the program holds them
    # (sharded over the cell's mesh where it has one)
    upd = jax.jit(lambda p, i, m: model.apply(
        {"params": p}, i, m, Q, method=model.response_forward)[0])(trainer.state.params, ids, mask)
    tol = checks.tolerances_of(cf, harness.kv_dtype_of(cf, Q + t["new_tokens"]), cell["root"])
    return checks.compare_with_reference(
        log, "reference", ref, r_ids, r_mask, r_lp, np.asarray(upd), tol)
