"""Multi-process smoke worker: one rank of a 2-process sharded PPO step.

The reference's launch story is multi-process by construction
(``accelerate launch``, `README.md:35-40`; startup barrier across ranks,
`accelerate_base_model.py:38-41`; WORLD_SIZE batch math, `trlx/trlx.py:44`).
This worker proves the TPU-native equivalent actually executes:
``parallel/distributed.py::initialize`` wires N CPU processes into one JAX
runtime (the same ``jax.distributed`` control plane a TPU pod uses), every
rank builds the SAME global mesh over all N×local devices, and one sharded
PPO train step runs SPMD across processes — the collectives GSPMD inserts
for the dp/fsdp/tp axes ride the cross-process transport.

Run as::

    python -m trlx_tpu.parallel._mp_smoke <coordinator> <num_procs> <rank>

with ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` in the env
(each rank contributes K virtual CPU devices). Launched by
``tests/test_multiprocess.py`` and by the driver's ``dryrun_multichip``.
"""

from __future__ import annotations

import sys


def main(coordinator: str, num_processes: int, process_id: int) -> None:
    import jax

    # a CPU tool: each rank contributes virtual CPU devices whatever the
    # host offers (two ranks cannot share one chip) — pinned before the
    # first backend touch
    jax.config.update("jax_platforms", "cpu")

    from trlx_tpu.parallel.distributed import (
        barrier,
        broadcast_host_value,
        initialize,
        is_main_process,
    )

    initialize(coordinator, num_processes, process_id)
    assert jax.process_count() == num_processes, jax.process_count()
    assert jax.process_index() == process_id, jax.process_index()
    n_local = len(jax.local_devices())
    n_global = len(jax.devices())
    assert n_global == num_processes * n_local, (n_global, n_local)

    # startup barrier across ranks (reference `accelerate_base_model.py:40`)
    barrier("startup")

    # host-value broadcast: every rank must end up with rank 0's value
    value = broadcast_host_value(1234 if process_id == 0 else -1)
    assert int(value) == 1234, value

    import jax.numpy as jnp
    import numpy as np

    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.data.ppo_types import PPORolloutBatch
    from trlx_tpu.parallel.mesh import batch_sharding
    from trlx_tpu.utils.loading import get_trainer

    # global mesh over every device of every process: dp=2 x fsdp=2 x tp=2
    # for 8 devices — dp/fsdp collectives cross the process boundary
    tp = 2 if n_global % 2 == 0 else 1
    fsdp = 2 if n_global % 4 == 0 else 1
    dp = n_global // (tp * fsdp)
    B, Q, R = max(dp * fsdp * 2, 8), 8, 6
    config = TRLConfig.from_dict(
        {
            "model": {
                "model_type": "gpt2",
                "model_arch": {
                    "vocab_size": 256,
                    "n_positions": 32,
                    "n_embd": 64,
                    "n_layer": 2,
                    "n_head": 4,
                },
            },
            "train": {
                "seq_length": Q,
                "batch_size": B,
                "mesh": {"dp": dp, "fsdp": fsdp, "tp": tp},
                "dtype": "float32",
            },
            "method": {
                "name": "PPOConfig",
                "num_rollouts": B,
                "chunk_size": B,
                "gen_kwargs": {
                    "max_new_tokens": R,
                    "do_sample": True,
                    "eos_token_id": 254,
                    "pad_token_id": 255,
                },
            },
        }
    )
    trainer = get_trainer("PPOTrainer")(config, reward_fn=lambda **kw: [0.0])
    assert trainer.mesh.devices.size == n_global

    # identical host inputs on every rank (SPMD: same program, same data;
    # jit shards them onto the global batch sharding)
    rng = np.random.default_rng(0)
    prompt_ids = jnp.asarray(rng.integers(1, 250, size=(B, Q)), jnp.int32)
    prompt_mask = jnp.ones((B, Q), jnp.int32)

    out = trainer.sample(prompt_ids, prompt_mask)
    ref_lp = trainer.score_ref(
        prompt_ids, prompt_mask, out.tokens, out.response_mask
    )
    rewards = trainer.compute_rewards(
        out.logprobs, ref_lp, out.response_mask, np.zeros((B,), np.float32)
    )
    mb = jax.device_put(
        PPORolloutBatch(
            query_tokens=prompt_ids,
            query_mask=prompt_mask,
            response_tokens=out.tokens,
            response_mask=out.response_mask,
            logprobs=out.logprobs,
            values=out.values,
            rewards=rewards,
        ),
        batch_sharding(trainer.mesh),
    )
    trainer.state, stats = trainer._train_step_jit(trainer.state, mb)
    jax.block_until_ready(trainer.state.params)
    # total_loss is replicated -> addressable on every rank
    loss = float(stats["losses/total_loss"])
    assert np.isfinite(loss), loss

    # pipeline-parallel leg (round 4): the GPipe schedule's ppermute hops
    # must ride the cross-PROCESS transport, not just intra-process ICI.
    # One device from EACH process forms a pp=2 mesh (the canonical
    # dp-major mesh would place pp pairs within a process), a 2-stage
    # pipeline runs a stacked linear stage, and the result must equal the
    # local composition of both stages.
    from trlx_tpu.parallel.mesh import make_mesh
    from trlx_tpu.parallel.pipeline import pipeline_apply

    d0, d1 = jax.devices()[0], jax.devices()[n_local]
    assert d0.process_index != d1.process_index, (d0, d1)
    pp_mesh = make_mesh({"dp": 1, "pp": 2}, devices=[d0, d1])
    stage_w = jnp.stack(
        [jnp.eye(16) * 2.0, jnp.eye(16) + 0.5]
    )  # [S=2, 16, 16]
    xb = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)

    pp_out = pipeline_apply(
        lambda p, h: h @ p, stage_w, xb, pp_mesh, num_microbatches=2
    )
    expected = np.asarray(xb @ stage_w[0] @ stage_w[1])
    got = np.asarray(pp_out.addressable_shards[0].data)
    np.testing.assert_allclose(got, expected, rtol=1e-5)

    barrier("done")
    if is_main_process():
        print(
            f"mp_smoke ok: procs={num_processes} devices={n_global} "
            f"mesh dp={dp} fsdp={fsdp} tp={tp} "
            f"(+cross-process pp=2 ppermute) loss={loss:.4f}",
            flush=True,
        )


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))
