"""Train-phase audit (VERDICT r4 #2): where do the non-MFU milliseconds go?

The faithful workload's train phase is now ~75% of phase wall-clock at
~31% MFU (BENCH r5: 579 ms/phase = 18.1 ms/step at B=16, T=112, 32
steps/phase). This audit decomposes one update step into separately-timed
components at the exact minibatch shape, then puts a HBM roofline next to
the MFU so "31% MFU" can be read correctly (compute-bound vs traffic-bound
vs neither):

- ``fwd``: policy forward -> response logprobs/values (incl. the [B,R,V]
  f32 logits materialization — the prime traffic suspect);
- ``fwd_bwd``: value_and_grad of the full PPO loss (adds the backward);
- ``gae_whiten``: advantages/returns + whitening (host-free, tiny?);
- ``optimizer``: AdamW update on precomputed grads (f32 m+v read+write is
  ~28 B/param — the other traffic suspect);
- ``train_step``: the real fused step; ``train_phase_per_step``: the real
  32-step scanned phase divided by 32 (captures scan-level fusion/layout
  wins and any dispatch overhead the components hide).

Methodology: every component loops ITERS times inside ONE jit via
lax.scan with a real data dependency (no per-iteration dispatch, no
constant folding), one block_until_ready, best of 3 — see
bench_longctx.py.

Prints one JSON object with component ms, the component sum vs the real
step (unaccounted gap), the train-step HBM roofline, and the phase MFU.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

ITERS = 20


def timed(fn, *args):
    """Best-of-3 wall time of a jitted fn's device work (one dispatch)."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.time()
        jax.block_until_ready(fn(*args))
        best = min(best, time.time() - t0)
    return best


def main():
    os.environ.setdefault("WANDB_DISABLED", "1")
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from bench import (
        _phase_flops, _workload_config, device_peaks,
    )
    from trlx_tpu.data.ppo_types import PPORolloutBatch
    from trlx_tpu.ops.ppo_math import get_advantages_and_returns
    from trlx_tpu.utils.loading import get_trainer

    # default: the faithful (headline) workload; `frozen_top2` audits the
    # r4 secondary definition (freezing on, backward pruned) so the GAE-
    # hoist A/B exists on BOTH definitions (VERDICT r4 #2 asks for r4's)
    workload = sys.argv[1] if len(sys.argv) > 1 else "faithful"
    if workload not in ("faithful", "frozen_top2"):
        raise ValueError(
            f"unknown workload {workload!r}: expected 'faithful' or "
            f"'frozen_top2' (a typo here would mislabel the artifact)"
        )
    config = (
        _workload_config(2, None) if workload == "frozen_top2"
        else _workload_config(0, 2)
    )
    trainer = get_trainer(config.train.trainer)(
        config, reward_fn=lambda **kw: [0.0]
    )
    method = config.method
    B = config.train.batch_size
    Q = config.train.seq_length
    R = method.gen_kwargs["max_new_tokens"]
    arch = config.model.model_arch
    V, L, d = arch["vocab_size"], arch["n_layer"], arch["n_embd"]

    rng = np.random.default_rng(0)
    mb = PPORolloutBatch(
        query_tokens=jnp.asarray(rng.integers(100, 40000, (B, Q)), jnp.int32),
        query_mask=jnp.ones((B, Q), jnp.int32),
        response_tokens=jnp.asarray(
            rng.integers(100, 40000, (B, R)), jnp.int32
        ),
        response_mask=jnp.ones((B, R), jnp.int32),
        logprobs=jnp.asarray(rng.normal(size=(B, R)) - 8, jnp.float32),
        values=jnp.asarray(rng.normal(size=(B, R)) * 0.1, jnp.float32),
        rewards=jnp.asarray(rng.normal(size=(B, R)) * 0.1, jnp.float32),
    )
    state = trainer.state
    params = state.params

    def scan_loop(body, init_carry):
        """ITERS dependent iterations inside one jit (one dispatch, so
        the window holds device work, not host dispatch gaps)."""

        def wrapped(carry, _):
            return body(carry), None

        def run(c):
            c, _ = jax.lax.scan(wrapped, c, None, length=ITERS)
            return c

        return jax.jit(run), init_carry

    results = {}

    # --- fwd: forward -> logprobs/values (perturb params to carry a dep)
    def fwd_body(p):
        logprobs, values, _, _ = trainer._forward_logprobs_values(p, mb)
        eps = (jnp.mean(logprobs) + jnp.mean(values)) * 1e-30
        return jax.tree_util.tree_map(lambda x: x + eps.astype(x.dtype), p)

    fn, c = scan_loop(fwd_body, params)
    results["fwd_ms"] = timed(fn, c) / ITERS * 1e3
    print("fwd done", file=sys.stderr)

    # --- fwd+bwd: value_and_grad of the full PPO loss
    def loss_fn(p):
        logprobs, values, entropy, _ = trainer._forward_logprobs_values(p, mb)
        advantages, returns = trainer._advantages_and_returns(mb)
        from trlx_tpu.ops.ppo_math import ppo_loss

        loss, _ = ppo_loss(
            logprobs, values, mb.logprobs, mb.values, advantages, returns,
            mb.response_mask, method.cliprange, method.cliprange_value,
            method.vf_coef,
        )
        return loss

    def fwd_bwd_body(p):
        loss, grads = jax.value_and_grad(loss_fn)(p)
        eps = loss * 1e-30
        return jax.tree_util.tree_map(
            lambda x, g: x + (eps + 0 * jnp.mean(g)).astype(x.dtype), p, grads
        )

    fn, c = scan_loop(fwd_bwd_body, params)
    results["fwd_bwd_ms"] = timed(fn, c) / ITERS * 1e3
    print("fwd_bwd done", file=sys.stderr)

    # --- GAE + whitening alone (part of every loss eval)
    def gae_body(vals):
        adv, ret = get_advantages_and_returns(
            vals, mb.rewards, mb.response_mask, method.gamma, method.lam
        )
        return vals + jnp.mean(adv + ret) * 1e-30

    fn, c = scan_loop(gae_body, mb.values)
    results["gae_whiten_ms"] = timed(fn, c) / ITERS * 1e3
    print("gae done", file=sys.stderr)

    # --- optimizer: AdamW update on fixed grads. Grads are an ARGUMENT,
    # not a closure: closed-over arrays become 500 MB of constants
    # serialized into the program body
    grads = jax.jit(jax.grad(loss_fn))(params)
    jax.block_until_ready(grads)

    def opt_run(carry, g):
        def body(c, _):
            p, opt_state = c
            updates, new_opt = trainer.tx.update(g, opt_state, p)
            return (optax.apply_updates(p, updates), new_opt), None

        c, _ = jax.lax.scan(body, carry, None, length=ITERS)
        return c

    fn = jax.jit(opt_run)
    results["optimizer_ms"] = (
        timed(fn, (params, state.opt_state), grads) / ITERS * 1e3
    )
    print("optimizer done", file=sys.stderr)

    # --- the real fused phase program at its real shape:
    # 32 pre-stacked minibatches = one phase dispatch. Methodology:
    # FRESH token inputs per call, built OUTSIDE the timed window, and
    # the window ends on a scalar fetch of the program's stats output.
    n_mb = method.num_rollouts // B
    steps = n_mb * method.ppo_epochs

    def stack_for(seed):
        r = np.random.default_rng(seed)
        fresh = mb.replace(
            response_tokens=jnp.asarray(
                r.integers(100, 40000, (B, R)), jnp.int32
            ),
            rewards=jnp.asarray(r.normal(size=(B, R)) * 0.1, jnp.float32),
        )
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (steps,) + x.shape), fresh
        )

    # Three phase variants, INTERLEAVED across rounds (wall-clock swings
    # ±20% with shared-machine load — back-to-back A/Bs measured the GAE
    # hoist anywhere from 1.09x to 0.96x; interleaving is the defense):
    # - "hoisted": the shipped train_phase (GAE vmapped before the scan)
    # - "gae_in_scan": the pre-r5 program (GAE's sequential R-chain
    #   recomputed inside every scanned step), reconstructed by scanning
    #   the per-step program
    # - "chunked": train.logprob_chunk=16 on top of hoisted (the [B,R,V]
    #   f32 logits buffer never materializes; bwd recomputes chunks)
    old_phase = jax.jit(
        lambda s, mbs: jax.lax.scan(
            lambda s_, m: trainer._train_step_jit(s_, m), s, mbs
        ),
    )
    chunk_config = (
        _workload_config(2, None) if workload == "frozen_top2"
        else _workload_config(0, 2)
    )
    chunk_config.train.logprob_chunk = 16
    chunk_trainer = get_trainer(chunk_config.train.trainer)(
        chunk_config, reward_fn=lambda **kw: [0.0]
    )
    # each variant owns its state copy — the phase programs DONATE their
    # state argument, so sharing one tree across variants dies with
    # "Array has been deleted" on the second variant's warm call
    copy_state = lambda s: jax.tree_util.tree_map(jnp.copy, s)
    variants = {
        "hoisted": (trainer._train_phase_jit, copy_state(state)),
        "gae_in_scan": (old_phase, copy_state(state)),
        "chunked": (chunk_trainer._train_phase_jit, chunk_trainer.state),
    }

    def one_call(phase_fn, st, seed):
        # input prep (host RNG + device puts) stays OUTSIDE the window
        stk = jax.block_until_ready(stack_for(seed))
        t0 = time.time()
        st, stats = phase_fn(st, stk)
        float(np.asarray(jax.device_get(
            next(iter(jax.tree_util.tree_leaves(stats)))
        )).ravel()[0])
        return time.time() - t0, st

    carries, best = {}, {}
    for name, (fn, st0) in variants.items():  # compile + warm each
        _, carries[name] = one_call(fn, st0, 0)
        best[name] = float("inf")
    for r in range(1, 5):  # 4 interleaved rounds
        for name, (fn, _) in variants.items():
            t, carries[name] = one_call(fn, carries[name], 100 * r)
            best[name] = min(best[name], t)

    results["train_phase_ms"] = best["hoisted"] * 1e3
    results["train_phase_per_step_ms"] = best["hoisted"] / steps * 1e3
    results["train_phase_gae_in_scan_ms"] = best["gae_in_scan"] * 1e3
    results["gae_hoist_speedup"] = round(
        best["gae_in_scan"] / best["hoisted"], 3
    )
    results["train_phase_chunked_logprob_ms"] = best["chunked"] * 1e3
    results["chunked_logprob_speedup"] = round(
        best["hoisted"] / best["chunked"], 3
    )
    del chunk_trainer, carries

    # --- component sum vs the real step
    results["component_sum_ms"] = (
        results["fwd_bwd_ms"] + results["optimizer_ms"]
    )
    results["unaccounted_ms_per_step"] = round(
        results["train_phase_per_step_ms"] - results["component_sum_ms"], 3
    )

    # --- FLOPs side: phase MFU at this shape
    _, train_flops = _phase_flops(
        d=d, V=V, L=L, Q=Q, R=R, B=method.num_rollouts,
        ppo_epochs=method.ppo_epochs,
        unfrozen=config.model.num_layers_unfrozen,
    )
    kind = jax.devices()[0].device_kind
    peak, hbm_peak = device_peaks(kind)
    step_flops = train_flops / steps
    results["train_step_tflops"] = round(step_flops / 1e12, 3)
    results["train_phase_mfu"] = round(
        step_flops / (results["train_phase_per_step_ms"] / 1e3)
        / 1e12 / peak, 4,
    )

    # --- HBM roofline: architecturally-required bytes per train step
    # (lower bound; fused activations uncounted) — delegated to bench.py's
    # `_train_step_bytes` (single byte model for artifact and audit: fwd
    # reads full weights, bwd pruned below the branch point, optimizer
    # traffic for the true trainable slice — unfrozen blocks + ln_f, the
    # mask freezes wte/wpe and the tied head)
    from bench import _train_step_bytes

    k_unfrozen = config.model.num_layers_unfrozen
    frac = k_unfrozen / L if 0 < k_unfrozen < L else 1.0
    blocks = L * (12 * d * d + 13 * d)
    head = V * d
    n_all = blocks + head + 2 * d
    trainable = n_all if frac == 1.0 else blocks * frac + 2 * d
    bytes_weights = (
        2 * (blocks + head + 2 * d)
        + 2 * (blocks * frac + head)
        + 4 * trainable
    )
    bytes_opt = 28 * trainable
    bytes_logits = 5 * B * R * V * 4
    bytes_acts = 2 * 2 * B * (Q + R) * d * (L * frac)
    step_bytes = _train_step_bytes(
        d=d, V=V, L=L, Q=Q, R=R, B=B, unfrozen=k_unfrozen
    )
    assert abs(
        step_bytes - (bytes_weights + bytes_opt + bytes_logits + bytes_acts)
    ) < 1e6  # the split must reconcile with the shared model
    results["workload"] = workload
    results["train_step_required_gb"] = round(step_bytes / 1e9, 3)
    results["bytes_split"] = {
        "weights_grads": round(bytes_weights / 1e9, 3),
        "optimizer": round(bytes_opt / 1e9, 3),
        "logits_pipeline": round(bytes_logits / 1e9, 3),
        "trunk_activations": round(bytes_acts / 1e9, 3),
    }
    gbps = step_bytes / (results["train_phase_per_step_ms"] / 1e3) / 1e9
    results["train_phase_hbm_gbps"] = round(gbps, 1)
    results["train_phase_hbm_util"] = round(gbps / hbm_peak, 4)
    results["device_kind"] = kind

    for k, v in list(results.items()):
        if isinstance(v, float):
            results[k] = round(v, 3)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
