"""Audit harness: build tiny trainers on a CPU mesh and trace their
jitted programs abstractly.

The jaxpr audit needs *real* trainer-constructed programs — the same
``_train_step_jit`` / ``_sample_jit`` callables production uses — traced
with ``jax.make_jaxpr`` on shape-only inputs. This module owns the tiny
configs (bf16 compute / f32 params, the production default, so the
precision-leak rule sees the real dtype story) and the abstract input
construction for all four trainers:

- ``ppo``      — ``PPOTrainer``          (causal gpt2)
- ``ilql``     — ``ILQLTrainer``         (causal gpt2)
- ``grpo``     — ``GRPOTrainer``         (causal gpt2, grouped rollouts)
- ``seq2seq``  — ``Seq2SeqPPOTrainer``   (T5)

Runs on any device count: the audit mesh uses ``tp=2``/``fsdp=2`` when the
host exposes enough (virtual) devices — ``python -m trlx_tpu.analysis``
forces 8 virtual CPU devices before importing jax — and degrades to
single-axis otherwise.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Optional, Sequence, Set, Tuple

TRAINER_KINDS = ("ppo", "ilql", "grpo", "seq2seq")


def audit_mesh_config() -> Dict[str, int]:
    """Mesh axis sizes for the audit, adapted to the device count."""
    import jax

    n = len(jax.devices())
    tp = 2 if n % 2 == 0 and n >= 2 else 1
    fsdp = 2 if n % (2 * tp) == 0 and n >= 2 * tp else 1
    return {"dp": -1, "fsdp": fsdp, "tp": tp}


def audit_mesh():
    from trlx_tpu.parallel.mesh import make_mesh

    return make_mesh(audit_mesh_config())


_CAUSAL_ARCH = {
    "vocab_size": 32,
    "n_positions": 32,
    "n_embd": 32,
    "n_layer": 2,
    "n_head": 2,
}

_T5_ARCH = {
    "vocab_size": 32,
    "d_model": 32,
    "d_kv": 8,
    "d_ff": 64,
    "num_layers": 2,
    "num_decoder_layers": 2,
    "num_heads": 4,
    "relative_attention_num_buckets": 8,
    "relative_attention_max_distance": 16,
    "feed_forward_proj": "gated-gelu",
    "tie_word_embeddings": False,
}


def _base_train(mesh: Dict[str, int]) -> Dict[str, Any]:
    return {
        "seq_length": 8,
        "batch_size": 8,
        "epochs": 1,
        "total_steps": 4,
        "eval_interval": 1000,
        "checkpoint_interval": 100000,
        "mesh": mesh,
        # production defaults: bf16 compute over f32 masters — the
        # precision-leak rule audits the dtype story the TPU runs
        "dtype": "bfloat16",
        "param_dtype": "float32",
    }


def tiny_config_dict(
    kind: str,
    mesh: Optional[Dict[str, int]] = None,
    train_overrides: Optional[Dict[str, Any]] = None,
) -> Dict:
    mesh = dict(mesh or audit_mesh_config())
    train = _base_train(mesh)
    # harness-level knobs (the lockstep simulator enables train.health so
    # the rank-0 monitor/flight-recorder construction paths are exercised
    # per simulated host); applied before the per-kind sections so those
    # keep the last word on their own keys
    train.update(dict(train_overrides or {}))
    if kind in ("ppo", "grpo"):
        method: Dict[str, Any] = {
            "name": "GRPOConfig" if kind == "grpo" else "PPOConfig",
            "num_rollouts": 8,
            "chunk_size": 8,
            "ppo_epochs": 1,
            "init_kl_coef": 0.02,
            "gen_kwargs": {
                "max_new_tokens": 6,
                "do_sample": True,
                "eos_token_id": 30,
                "pad_token_id": 31,
            },
        }
        if kind == "grpo":
            method["group_size"] = 4
            train["trainer"] = "GRPOTrainer"
        return {
            "model": {"model_type": "gpt2", "model_arch": dict(_CAUSAL_ARCH)},
            "train": train,
            "method": method,
        }
    if kind == "ilql":
        train["trainer"] = "ILQLTrainer"
        train["orchestrator"] = "OfflineOrchestrator"
        return {
            "model": {"model_type": "gpt2", "model_arch": dict(_CAUSAL_ARCH)},
            "train": train,
            "method": {
                "name": "ILQLConfig",
                "gen_kwargs": {
                    "max_new_tokens": 6,
                    "do_sample": False,
                    "eos_token_id": 30,
                    "pad_token_id": 31,
                },
            },
        }
    if kind == "seq2seq":
        train["trainer"] = "Seq2SeqPPOTrainer"
        return {
            "model": {"model_type": "t5", "model_arch": dict(_T5_ARCH)},
            "train": train,
            "method": {
                "name": "PPOConfig",
                "num_rollouts": 8,
                "chunk_size": 8,
                "ppo_epochs": 1,
                "init_kl_coef": 0.02,
                "gen_kwargs": {
                    "max_new_tokens": 5,
                    "do_sample": True,
                    "eos_token_id": 1,
                    "pad_token_id": 0,
                    "decoder_start_token_id": 0,
                },
            },
        }
    raise ValueError(f"unknown trainer kind {kind!r}; know {TRAINER_KINDS}")


def build_trainer(
    kind: str,
    mesh: Optional[Dict[str, int]] = None,
    train_overrides: Optional[Dict[str, Any]] = None,
):
    from trlx_tpu.data.configs import TRLConfig

    config = TRLConfig.from_dict(
        tiny_config_dict(kind, mesh, train_overrides=train_overrides)
    )
    if kind in ("ppo",):
        from trlx_tpu.trainer.ppo_trainer import PPOTrainer

        return PPOTrainer(config)
    if kind == "grpo":
        from trlx_tpu.trainer.grpo_trainer import GRPOTrainer

        return GRPOTrainer(config)
    if kind == "ilql":
        from trlx_tpu.trainer.ilql_trainer import ILQLTrainer

        return ILQLTrainer(config)
    from trlx_tpu.trainer.seq2seq_ppo_trainer import Seq2SeqPPOTrainer

    return Seq2SeqPPOTrainer(config)


@dataclass
class TracedProgram:
    subject: str  # e.g. "ppo.train_step"
    closed_jaxpr: Any
    mesh_axes: Set[str]
    # flat state-leaf count the step must donate; None = no donation rule
    n_donated_state_leaves: Optional[int] = None
    # flat keypath label per program input (make_jaxpr flattening order) —
    # lets value-contract engines (nan_flow) seed facts like "masks are
    # 0/1" and "adam nu is nonnegative" at the program boundary
    input_paths: Optional[List[str]] = None
    # mesh axis name -> size of the mesh the program was traced on — the
    # resource auditor's collective cost model needs participant counts
    mesh_shape: Optional[Dict[str, int]] = None
    # per-flat-input sharding divisor (total elements / per-device shard
    # elements, from the trainer's declared in_shardings) — the resource
    # auditor divides each input's bytes by this to get per-device HBM
    input_divisors: Optional[List[int]] = None
    # per-flat-input tuple of mesh-split dimensions (same order) — the
    # HLO auditor's spmd-concat-hazard walk only treats a concatenate as
    # the PR-2 shape when the concat dimension is one the mesh splits
    input_sharded_dims: Optional[List[Tuple[int, ...]]] = None
    # (file, line) of the traced callable's def — findings with no eqn to
    # anchor to (donation-ignored, alias-escape) attach here so inline
    # `# tpu-lint: disable=` directives still work
    def_site: Optional[Tuple[str, int]] = None
    # the jitted callable itself plus the abstract args it was traced
    # with — the HLO auditor AOT-lowers `jit_fn.lower(*example_args)`
    # to get the optimized post-SPMD module XLA actually emits (the
    # jaxpr above is intent; this is ground truth)
    jit_fn: Any = None
    example_args: Any = None


def callable_def_site(fn) -> Optional[Tuple[str, int]]:
    """(file, first line) of the function a jit wrapper wraps."""
    inner = getattr(fn, "__wrapped__", fn)
    code = getattr(inner, "__code__", None)
    if code is None:
        return None
    return code.co_filename, code.co_firstlineno


def _flat_sharding_info(arg_trees, sharding_trees) -> List[Tuple[int, Tuple[int, ...]]]:
    """Per-flat-leaf ``(divisor, sharded_dims)`` in make_jaxpr order.

    ``sharding_trees`` mirrors ``arg_trees``; an entry of ``None`` (or a
    leaf without ``shard_shape``) means replicated -> ``(1, ())``. The
    divisor is ``total elements / per-device shard elements``; the dims
    are the axes along which the per-device shard is strictly smaller
    than the global shape (i.e. the dimensions the mesh actually
    splits).
    """
    import math

    import jax

    info: List[Tuple[int, Tuple[int, ...]]] = []
    for args, shardings in zip(arg_trees, sharding_trees):
        leaves = jax.tree_util.tree_leaves(args)
        if shardings is None:
            info += [(1, ())] * len(leaves)
            continue
        sh_leaves = jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: hasattr(x, "shard_shape")
        )
        if len(sh_leaves) == 1 and len(leaves) > 1:
            # one sharding for a whole tree (e.g. batch_sharding)
            sh_leaves = sh_leaves * len(leaves)
        for leaf, sh in zip(leaves, sh_leaves):
            shape = tuple(getattr(leaf, "shape", ()))
            if not hasattr(sh, "shard_shape") or not shape:
                info.append((1, ()))
                continue
            try:
                shard = sh.shard_shape(shape)
                total = math.prod(shape)
                per_dev = math.prod(shard)
                dims = tuple(
                    d for d, (g, s) in enumerate(zip(shape, shard)) if s < g
                )
                info.append((max(1, total // max(1, per_dev)), dims))
            except Exception:
                info.append((1, ()))
        info += [(1, ())] * (len(leaves) - min(len(leaves), len(sh_leaves)))
    return info


def flat_sharding_divisors(arg_trees, sharding_trees) -> List[int]:
    """Per-flat-leaf sharding divisor (total / per-device elements)."""
    return [d for d, _ in _flat_sharding_info(arg_trees, sharding_trees)]


def flat_sharded_dims(arg_trees, sharding_trees) -> List[Tuple[int, ...]]:
    """Per-flat-leaf tuple of mesh-split dimensions — lets the HLO
    auditor's concat-hazard walk tell a concat *along* a sharded axis
    (the PR-2 miscompile shape) from a benign local concat along a
    replicated one."""
    return [dims for _, dims in _flat_sharding_info(arg_trees, sharding_trees)]


def flat_input_paths(*trees, prefixes: Optional[Sequence[str]] = None) -> List[str]:
    """Flat keypath labels for argument trees, in make_jaxpr's
    flattening order."""
    import jax

    names: List[str] = []
    for i, tree in enumerate(trees):
        prefix = prefixes[i] if prefixes else f"arg{i}"
        for path, _leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            names.append(prefix + jax.tree_util.keystr(path))
    return names


def _sds(tree):
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
    )


def _ppo_minibatch_sds(trainer):
    import jax
    import jax.numpy as jnp

    from trlx_tpu.data.ppo_types import PPORolloutBatch

    B = trainer.config.train.batch_size
    Q = trainer.query_length
    R = trainer.gen_config.max_new_tokens
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    return PPORolloutBatch(
        query_tokens=i32(B, Q),
        query_mask=i32(B, Q),
        response_tokens=i32(B, R),
        response_mask=i32(B, R),
        logprobs=f32(B, R),
        values=f32(B, R),
        rewards=f32(B, R),
    )


def _ilql_minibatch_sds(trainer):
    import jax
    import jax.numpy as jnp

    from trlx_tpu.data.ilql_types import ILQLBatch

    B = trainer.config.train.batch_size
    T = trainer.config.train.seq_length
    A = trainer.gen_config.max_new_tokens
    S = A + 1
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    return ILQLBatch(
        input_ids=i32(B, T),
        attention_mask=i32(B, T),
        rewards=f32(B, A),
        states_ixs=i32(B, S),
        actions_ixs=i32(B, A),
        dones=i32(B, S),
        actions_mask=i32(B, A),
    )


def trace_train_step(kind: str, mesh: Optional[Dict[str, int]] = None):
    """Abstractly trace just one trainer's jitted train step on ``mesh``
    (the collective-divergence engine traces the same step on several
    meshes; the full program set would triple the tracing cost)."""
    import jax

    trainer = build_trainer(kind, mesh)
    state_sds = _sds(trainer.state)
    mb = _ilql_minibatch_sds(trainer) if kind == "ilql" else _ppo_minibatch_sds(trainer)
    return jax.make_jaxpr(trainer._train_step_jit)(state_sds, mb)


def trace_train_step_program(
    kind: str, mesh: Optional[Dict[str, int]] = None
) -> TracedProgram:
    """Like :func:`trace_train_step` but packaged as a
    :class:`TracedProgram` with the jit handle attached — the HLO
    auditor compiles the step on each mesh of the collective-divergence
    matrix (the PR-2 replica-sum only mis-lowered on meshes with a
    spare axis, so single-mesh compiled coverage is not enough)."""
    import jax

    trainer = build_trainer(kind, mesh)
    state_sds = _sds(trainer.state)
    mb = _ilql_minibatch_sds(trainer) if kind == "ilql" else _ppo_minibatch_sds(trainer)
    return TracedProgram(
        subject=f"{kind}.train_step",
        closed_jaxpr=jax.make_jaxpr(trainer._train_step_jit)(state_sds, mb),
        mesh_axes=set(trainer.mesh.axis_names),
        mesh_shape={k: int(v) for k, v in trainer.mesh.shape.items()},
        def_site=callable_def_site(trainer._train_step_jit),
        jit_fn=trainer._train_step_jit,
        example_args=(state_sds, mb),
    )


def concrete_minibatch(trainer, kind: str, seed: int = 0):
    """A concrete, numerically-plausible rollout minibatch for the
    sanitizer's eqn-level replay (abstract tracing can't evaluate
    values): logprobs are small negatives, values/rewards small normals,
    masks cover a realistic prefix of the response."""
    import numpy as np

    import jax.numpy as jnp

    from trlx_tpu.data.ilql_types import ILQLBatch
    from trlx_tpu.data.ppo_types import PPORolloutBatch

    rng = np.random.default_rng(seed)
    B = trainer.config.train.batch_size
    vocab = 30
    if kind == "ilql":
        T = trainer.config.train.seq_length
        A = trainer.gen_config.max_new_tokens
        S = A + 1
        return ILQLBatch(
            input_ids=jnp.asarray(rng.integers(1, vocab, (B, T)), jnp.int32),
            attention_mask=jnp.ones((B, T), jnp.int32),
            rewards=jnp.asarray(rng.normal(0, 0.5, (B, A)), jnp.float32),
            states_ixs=jnp.asarray(
                np.tile(np.arange(S), (B, 1)), jnp.int32
            ),
            actions_ixs=jnp.asarray(
                np.tile(np.arange(A), (B, 1)), jnp.int32
            ),
            dones=jnp.ones((B, S), jnp.int32),
            actions_mask=jnp.ones((B, A), jnp.int32),
        )
    Q = trainer.query_length
    R = trainer.gen_config.max_new_tokens
    lengths = rng.integers(max(1, R - 2), R + 1, B)
    response_mask = (np.arange(R)[None, :] < lengths[:, None]).astype(np.int32)
    return PPORolloutBatch(
        query_tokens=jnp.asarray(rng.integers(1, vocab, (B, Q)), jnp.int32),
        query_mask=jnp.ones((B, Q), jnp.int32),
        response_tokens=jnp.asarray(rng.integers(1, vocab, (B, R)), jnp.int32),
        response_mask=jnp.asarray(response_mask),
        logprobs=jnp.asarray(-np.abs(rng.normal(1.5, 0.7, (B, R))), jnp.float32),
        values=jnp.asarray(rng.normal(0, 0.3, (B, R)), jnp.float32),
        rewards=jnp.asarray(rng.normal(0, 0.5, (B, R)) * response_mask, jnp.float32),
    )


def trace_trainer(
    kind: str, mesh: Optional[Dict[str, int]] = None
) -> List[TracedProgram]:
    """Build one tiny trainer and abstractly trace its jitted programs."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.parallel.mesh import batch_sharding

    trainer = build_trainer(kind, mesh)
    axes = set(trainer.mesh.axis_names)
    mesh_shape = {k: int(v) for k, v in trainer.mesh.shape.items()}
    batch_sh = batch_sharding(trainer.mesh)
    state_sds = _sds(trainer.state)
    n_state = len(jax.tree_util.tree_leaves(state_sds))
    if kind == "ilql":
        mb = _ilql_minibatch_sds(trainer)
    else:
        mb = _ppo_minibatch_sds(trainer)

    step_paths = flat_input_paths(state_sds, mb, prefixes=("state", "batch"))
    programs = [
        TracedProgram(
            subject=f"{kind}.train_step",
            closed_jaxpr=jax.make_jaxpr(trainer._train_step_jit)(
                state_sds, mb
            ),
            mesh_axes=axes,
            n_donated_state_leaves=n_state,
            input_paths=step_paths,
            mesh_shape=mesh_shape,
            input_divisors=flat_sharding_divisors(
                (state_sds, mb), (trainer.state_shardings, batch_sh)
            ),
            input_sharded_dims=flat_sharded_dims(
                (state_sds, mb), (trainer.state_shardings, batch_sh)
            ),
            def_site=callable_def_site(trainer._train_step_jit),
            jit_fn=trainer._train_step_jit,
            example_args=(state_sds, mb),
        )
    ]

    B = trainer.config.train.batch_size
    Q = trainer.query_length
    prompt = jax.ShapeDtypeStruct((B, Q), jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    if kind == "ilql":
        bundle = {
            "params": _sds(trainer.state.params),
            "target": _sds(trainer.state.target_q_params),
        }
        sample_jaxpr = jax.make_jaxpr(trainer._sample_jit)(
            bundle, prompt, prompt, key
        )
    else:
        sample_jaxpr = jax.make_jaxpr(trainer._sample_jit)(
            _sds(trainer.state.params), prompt, prompt, key
        )
    rollout_args = (
        (bundle, prompt, prompt, key)
        if kind == "ilql"
        else (_sds(trainer.state.params), prompt, prompt, key)
    )
    rollout_shardings = (
        (
            {
                "params": trainer.state_shardings.params,
                "target": trainer.state_shardings.target_q_params,
            }
            if kind == "ilql"
            else trainer.state_shardings.params
        ),
        batch_sh,
        batch_sh,
        None,
    )
    programs.append(
        TracedProgram(
            subject=f"{kind}.rollout",
            closed_jaxpr=sample_jaxpr,
            mesh_axes=axes,
            input_paths=flat_input_paths(
                *rollout_args,
                prefixes=("params", "prompt_ids", "prompt_mask", "key"),
            ),
            mesh_shape=mesh_shape,
            input_divisors=flat_sharding_divisors(
                rollout_args, rollout_shardings
            ),
            input_sharded_dims=flat_sharded_dims(
                rollout_args, rollout_shardings
            ),
            def_site=callable_def_site(trainer._sample_jit),
            jit_fn=trainer._sample_jit,
            example_args=rollout_args,
        )
    )

    if kind != "ilql":
        # the fused buffer pass (scan over stacked minibatches) is the
        # production train path — audit it too, with its own donation.
        # Under the streamed collect→train phase (docs/async_pipeline.md)
        # this same program runs the residual epochs 2..ppo_epochs, and
        # `train_step` above IS the streamed epoch-1 step — both streamed
        # dispatch modes are covered by these traces.
        stacked = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((2,) + x.shape, x.dtype), mb
        )
        from trlx_tpu.parallel.mesh import stacked_batch_sharding

        programs.append(
            TracedProgram(
                subject=f"{kind}.train_phase",
                closed_jaxpr=jax.make_jaxpr(trainer._train_phase_jit)(
                    state_sds, stacked
                ),
                mesh_axes=axes,
                n_donated_state_leaves=n_state,
                input_paths=flat_input_paths(
                    state_sds, stacked, prefixes=("state", "batch")
                ),
                mesh_shape=mesh_shape,
                input_divisors=flat_sharding_divisors(
                    (state_sds, stacked),
                    (
                        trainer.state_shardings,
                        stacked_batch_sharding(trainer.mesh),
                    ),
                ),
                input_sharded_dims=flat_sharded_dims(
                    (state_sds, stacked),
                    (
                        trainer.state_shardings,
                        stacked_batch_sharding(trainer.mesh),
                    ),
                ),
                def_site=callable_def_site(trainer._train_phase_jit),
                jit_fn=trainer._train_phase_jit,
                example_args=(state_sds, stacked),
            )
        )
        # the streamed phase's behavior-policy snapshot (compute-dtype
        # cast + donation-safe per-leaf copy): every sampler/ref forward
        # of an overlapped phase consumes its output, so its dtype story
        # belongs in the audit
        params_sds = _sds(trainer.state.params)
        programs.append(
            TracedProgram(
                subject=f"{kind}.behavior_snapshot",
                closed_jaxpr=jax.make_jaxpr(
                    trainer._behavior_snapshot_jit
                )(params_sds),
                mesh_axes=axes,
                input_paths=flat_input_paths(
                    params_sds, prefixes=("params",)
                ),
                mesh_shape=mesh_shape,
                input_divisors=flat_sharding_divisors(
                    (params_sds,), (trainer.state_shardings.params,)
                ),
                input_sharded_dims=flat_sharded_dims(
                    (params_sds,), (trainer.state_shardings.params,)
                ),
                def_site=callable_def_site(trainer._behavior_snapshot_jit),
                jit_fn=trainer._behavior_snapshot_jit,
                example_args=(params_sds,),
            )
        )
    if kind == "ppo":
        # the continuous-batching rollout engine's jitted programs
        # (docs/inference.md) — traced once on the ppo trainer (every
        # causal family shares the same engine code path)
        programs.extend(_trace_engine_programs(trainer, kind, mesh_shape))
        # the async actor–learner programs (docs/async_pipeline.md),
        # traced once on the ppo trainer (the only kind the async mode
        # composes with today): the mid-generation weight push the
        # actors receive, and the stream store's donating versioned
        # landing program
        programs.extend(_trace_async_programs(trainer, kind, mesh_shape))
    return programs


def _trace_async_programs(trainer, kind: str, mesh_shape) -> List[TracedProgram]:
    """Trace the asynchronous actor–learner path's jitted programs
    (``trlx_tpu/trainer/async_rl.py``, docs/async_pipeline.md):

    - ``async_weight_push`` — the refreshed behavior policy pushed to
      the actors MID-generation (compute-dtype cast + donation-safe
      per-leaf copy; a separate jit instance from the phase-start
      snapshot, so the program the async path actually dispatches is
      what gets audited);
    - ``versioned_land`` — the stream store's landing program
      (``pipeline/ppo_buffer.py::land_rows``): one fused, store-DONATING
      ``dynamic_update_slice`` write of a harvest chunk at a dynamic
      offset (the device half of the version-tagged landing; the
      version column itself is host-side plan metadata).

    Traced regardless of the configured ``train.async_rl`` — like the
    engine programs, the audit covers the async path even while a run
    defaults to synchronous.
    """
    import jax
    import jax.numpy as jnp

    from trlx_tpu.parallel.mesh import batch_sharding
    from trlx_tpu.pipeline import ppo_buffer

    axes = set(trainer.mesh.axis_names)
    batch_sh = batch_sharding(trainer.mesh)
    params_sds = _sds(trainer.state.params)
    mb = _ppo_minibatch_sds(trainer)
    # a two-chunk stream store with one harvest-chunk landing at a
    # dynamic offset — the steady-state shape pair of a streamed phase
    store_sds = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((2 * x.shape[0],) + x.shape[1:], x.dtype),
        mb,
    )
    offset_sds = jax.ShapeDtypeStruct((), jnp.int32)
    land_args = (store_sds, mb, offset_sds)
    return [
        TracedProgram(
            subject=f"{kind}.async_weight_push",
            closed_jaxpr=jax.make_jaxpr(trainer._weight_push_jit)(
                params_sds
            ),
            mesh_axes=axes,
            input_paths=flat_input_paths(params_sds, prefixes=("params",)),
            mesh_shape=mesh_shape,
            input_divisors=flat_sharding_divisors(
                (params_sds,), (trainer.state_shardings.params,)
            ),
            input_sharded_dims=flat_sharded_dims(
                (params_sds,), (trainer.state_shardings.params,)
            ),
            def_site=callable_def_site(trainer._weight_push_jit),
            jit_fn=trainer._weight_push_jit,
            example_args=(params_sds,),
        ),
        TracedProgram(
            subject=f"{kind}.versioned_land",
            closed_jaxpr=jax.make_jaxpr(ppo_buffer._land_rows_jit)(
                *land_args
            ),
            mesh_axes=axes,
            n_donated_state_leaves=len(
                jax.tree_util.tree_leaves(store_sds)
            ),
            input_paths=flat_input_paths(
                *land_args, prefixes=("store", "chunk", "offset")
            ),
            mesh_shape=mesh_shape,
            input_divisors=flat_sharding_divisors(
                land_args, (batch_sh, batch_sh, None)
            ),
            input_sharded_dims=flat_sharded_dims(
                land_args, (batch_sh, batch_sh, None)
            ),
            def_site=callable_def_site(ppo_buffer._land_rows_jit),
            jit_fn=ppo_buffer._land_rows_jit,
            example_args=land_args,
        ),
    ]


def _trace_engine_programs(trainer, kind: str, mesh_shape) -> List[TracedProgram]:
    """Trace the continuous-batching engine's prefill / decode_step /
    refill (slot-recycle) programs (``trlx_tpu/inference/engine.py``).

    The engine is built from the trainer's model/shardings regardless of
    the configured ``train.rollout`` engine — the audit covers the
    continuous path even while a run defaults to ``fixed``. Donation:
    prefill/decode take (params, state) with the STATE donated, which the
    donation rule (state-first contract) cannot express — only ``refill``
    (state-first) carries the donation contract here.
    """
    import jax
    import jax.numpy as jnp

    from trlx_tpu.parallel.mesh import batch_sharding

    axes = set(trainer.mesh.axis_names)
    engine = trainer.rollout_engine_obj
    state_sds = jax.eval_shape(engine._make_state)
    params_sds = _sds(trainer.state.params)
    A, C, Q = engine.admit_width, engine.harvest_width, engine.Q
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state_sh = engine.state_sharding()
    batch_sh = batch_sharding(trainer.mesh)
    params_sh = trainer.state_shardings.params
    n_state = len(jax.tree_util.tree_leaves(state_sds))

    prefill_args = (
        params_sds, state_sds, i32(A), i32(A, Q), i32(A, Q), i32(A),
        i32(A), key_sds,
    )
    prefill_prefixes = (
        "params", "state", "slots", "prompt_ids", "prompt_mask",
        "rows", "turns", "phase_key",
    )
    prefill_shardings = (
        params_sh, state_sh, None, batch_sh, batch_sh, None, None, None,
    )
    decode_args = (params_sds, state_sds)
    refill_args = (state_sds, i32(C))
    return [
        TracedProgram(
            subject=f"{kind}.engine_prefill",
            closed_jaxpr=jax.make_jaxpr(engine.prefill_jit)(*prefill_args),
            mesh_axes=axes,
            input_paths=flat_input_paths(
                *prefill_args, prefixes=prefill_prefixes
            ),
            mesh_shape=mesh_shape,
            input_divisors=flat_sharding_divisors(
                prefill_args, prefill_shardings
            ),
            input_sharded_dims=flat_sharded_dims(
                prefill_args, prefill_shardings
            ),
            def_site=callable_def_site(engine.prefill_jit),
            jit_fn=engine.prefill_jit,
            example_args=prefill_args,
        ),
        TracedProgram(
            subject=f"{kind}.engine_decode_step",
            closed_jaxpr=jax.make_jaxpr(engine.decode_step_jit)(
                *decode_args
            ),
            mesh_axes=axes,
            input_paths=flat_input_paths(
                *decode_args, prefixes=("params", "state")
            ),
            mesh_shape=mesh_shape,
            input_divisors=flat_sharding_divisors(
                decode_args, (params_sh, state_sh)
            ),
            input_sharded_dims=flat_sharded_dims(
                decode_args, (params_sh, state_sh)
            ),
            def_site=callable_def_site(engine.decode_step_jit),
            jit_fn=engine.decode_step_jit,
            example_args=decode_args,
        ),
        TracedProgram(
            subject=f"{kind}.engine_refill",
            closed_jaxpr=jax.make_jaxpr(engine.refill_jit)(*refill_args),
            mesh_axes=axes,
            n_donated_state_leaves=n_state,
            input_paths=flat_input_paths(
                *refill_args, prefixes=("state", "slots")
            ),
            mesh_shape=mesh_shape,
            input_divisors=flat_sharding_divisors(
                refill_args, (state_sh, None)
            ),
            input_sharded_dims=flat_sharded_dims(
                refill_args, (state_sh, None)
            ),
            def_site=callable_def_site(engine.refill_jit),
            jit_fn=engine.refill_jit,
            example_args=refill_args,
        ),
    ] + _trace_chunked_prefill_programs(
        trainer, engine, kind, mesh_shape, shared=False
    ) + _trace_serving_engine_programs(
        trainer, engine, kind, mesh_shape
    ) + _trace_spec_engine_programs(trainer, engine, kind, mesh_shape)


def _trace_chunked_prefill_programs(
    trainer, base_engine, kind: str, mesh_shape, shared: bool
) -> List[TracedProgram]:
    """Trace the CHUNKED prefill variant (``rollout.prefill_chunk > 0``,
    docs/inference.md "Chunked prefill"): the same engine geometry with
    the monolithic admission prefill replaced by a host loop over
    ``prefill_chunk``, the one program that forwards a block-aligned
    prompt-column chunk, the final one included. A separate subject
    with its own resource-budget entry — the default engine's
    ``engine_prefill`` stays byte-identical, and the engine-7 FLOP
    count pins a group's ``Q // W`` chunk forwards strictly below the
    monolithic entry at the audit shape (attention runs on the
    prompt-wide view, never the full Q+R capacity).
    """
    import jax
    import jax.numpy as jnp

    from trlx_tpu.inference.engine import ContinuousBatchingEngine
    from trlx_tpu.parallel.mesh import batch_sharding

    engine = ContinuousBatchingEngine(
        apply_fn=base_engine._apply_fn,
        init_cache_fn=base_engine._init_cache_fn,
        gen_config=base_engine.gen_config,
        query_length=base_engine.Q,
        vocab_size=base_engine.vocab_size,
        num_slots=base_engine.num_slots,
        admit_width=base_engine.admit_width,
        harvest_width=base_engine.harvest_width,
        block_size=base_engine.block_size,
        mesh=base_engine.mesh,
        param_shardings=base_engine._param_shardings,
        cache_sharding=base_engine._cache_sharding,
        with_values=base_engine.with_values,
        prefix_pool_blocks=(
            max(2, base_engine.Q // base_engine.block_size)
            if shared
            else 0
        ),
        stream_taps=shared,
        prefill_chunk=max(1, base_engine.Q // 2),
    )
    axes = set(trainer.mesh.axis_names)
    state_sds = jax.eval_shape(engine._make_state)
    params_sds = _sds(trainer.state.params)
    A, Q, nb = engine.admit_width, engine.Q, engine.n_blocks
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state_sh = engine.state_sharding()
    batch_sh = batch_sharding(trainer.mesh)
    params_sh = trainer.state_shardings.params
    suffix = "_shared" if shared else ""

    args = (
        params_sds, state_sds, i32(A), i32(A, Q), i32(A, Q), i32(A),
        i32(A), key_sds, i32(),
    )
    prefixes = (
        "params", "state", "slots", "prompt_ids", "prompt_mask",
        "rows", "turns", "phase_key", "chunk",
    )
    shardings = (
        params_sh, state_sh, None, batch_sh, batch_sh, None, None, None,
        None,
    )
    if shared:
        args += (i32(A, nb), i32(A, nb))
        prefixes += ("shared_map", "publish_map")
        shardings += (None, None)

    return [
        TracedProgram(
            subject=f"{kind}.engine_prefill_chunk{suffix}",
            closed_jaxpr=jax.make_jaxpr(engine.prefill_chunk_jit)(*args),
            mesh_axes=axes,
            input_paths=flat_input_paths(*args, prefixes=prefixes),
            mesh_shape=mesh_shape,
            input_divisors=flat_sharding_divisors(args, shardings),
            input_sharded_dims=flat_sharded_dims(args, shardings),
            def_site=callable_def_site(engine.prefill_chunk_jit),
            jit_fn=engine.prefill_chunk_jit,
            example_args=args,
        )
    ]


def _trace_serving_engine_programs(
    trainer, engine, kind: str, mesh_shape
) -> List[TracedProgram]:
    """Trace the SERVING-tier engine variant (``trlx_tpu/serving``,
    docs/serving.md): the same engine built with a shared-prefix pool
    (``prefix_pool_blocks > 0`` — the cache layers carry the
    replicated ``shared_k/v`` pool plus share/publish tables, and
    prefill takes the per-row sharing maps) and streaming taps
    (``decode_step`` additionally returns this step's (token, live)
    emissions), plus the placeholder ``release`` program. The trainer
    collect path never builds this variant — its three programs above
    stay byte-identical — so these four are separate subjects with
    their own resource-budget entries.
    """
    import jax
    import jax.numpy as jnp

    from trlx_tpu.inference.engine import ContinuousBatchingEngine
    from trlx_tpu.parallel.mesh import batch_sharding

    serving_engine = ContinuousBatchingEngine(
        apply_fn=engine._apply_fn,
        init_cache_fn=engine._init_cache_fn,
        gen_config=engine.gen_config,
        query_length=engine.Q,
        vocab_size=engine.vocab_size,
        num_slots=engine.num_slots,
        admit_width=engine.admit_width,
        harvest_width=engine.harvest_width,
        block_size=engine.block_size,
        mesh=engine.mesh,
        param_shardings=engine._param_shardings,
        cache_sharding=engine._cache_sharding,
        with_values=engine.with_values,
        prefix_pool_blocks=max(2, engine.Q // engine.block_size),
        stream_taps=True,
    )
    axes = set(trainer.mesh.axis_names)
    state_sds = jax.eval_shape(serving_engine._make_state)
    params_sds = _sds(trainer.state.params)
    A, C, Q = (
        serving_engine.admit_width,
        serving_engine.harvest_width,
        serving_engine.Q,
    )
    nb = serving_engine.n_blocks
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state_sh = serving_engine.state_sharding()
    batch_sh = batch_sharding(trainer.mesh)
    params_sh = trainer.state_shardings.params
    n_state = len(jax.tree_util.tree_leaves(state_sds))

    prefill_args = (
        params_sds, state_sds, i32(A), i32(A, Q), i32(A, Q), i32(A),
        i32(A), key_sds, i32(A, nb), i32(A, nb),
    )
    prefill_prefixes = (
        "params", "state", "slots", "prompt_ids", "prompt_mask",
        "rows", "turns", "phase_key", "shared_map", "publish_map",
    )
    prefill_shardings = (
        params_sh, state_sh, None, batch_sh, batch_sh, None, None,
        None, None, None,
    )
    decode_args = (params_sds, state_sds)
    refill_args = (state_sds, i32(C))
    release_args = (state_sds, i32(A))
    return [
        TracedProgram(
            subject=f"{kind}.engine_prefill_shared",
            closed_jaxpr=jax.make_jaxpr(serving_engine.prefill_jit)(
                *prefill_args
            ),
            mesh_axes=axes,
            input_paths=flat_input_paths(
                *prefill_args, prefixes=prefill_prefixes
            ),
            mesh_shape=mesh_shape,
            input_divisors=flat_sharding_divisors(
                prefill_args, prefill_shardings
            ),
            input_sharded_dims=flat_sharded_dims(
                prefill_args, prefill_shardings
            ),
            def_site=callable_def_site(serving_engine.prefill_jit),
            jit_fn=serving_engine.prefill_jit,
            example_args=prefill_args,
        ),
        TracedProgram(
            subject=f"{kind}.engine_decode_step_stream",
            closed_jaxpr=jax.make_jaxpr(serving_engine.decode_step_jit)(
                *decode_args
            ),
            mesh_axes=axes,
            input_paths=flat_input_paths(
                *decode_args, prefixes=("params", "state")
            ),
            mesh_shape=mesh_shape,
            input_divisors=flat_sharding_divisors(
                decode_args, (params_sh, state_sh)
            ),
            input_sharded_dims=flat_sharded_dims(
                decode_args, (params_sh, state_sh)
            ),
            def_site=callable_def_site(serving_engine.decode_step_jit),
            jit_fn=serving_engine.decode_step_jit,
            example_args=decode_args,
        ),
        TracedProgram(
            subject=f"{kind}.engine_refill_shared",
            closed_jaxpr=jax.make_jaxpr(serving_engine.refill_jit)(
                *refill_args
            ),
            mesh_axes=axes,
            n_donated_state_leaves=n_state,
            input_paths=flat_input_paths(
                *refill_args, prefixes=("state", "slots")
            ),
            mesh_shape=mesh_shape,
            input_divisors=flat_sharding_divisors(
                refill_args, (state_sh, None)
            ),
            input_sharded_dims=flat_sharded_dims(
                refill_args, (state_sh, None)
            ),
            def_site=callable_def_site(serving_engine.refill_jit),
            jit_fn=serving_engine.refill_jit,
            example_args=refill_args,
        ),
        TracedProgram(
            subject=f"{kind}.engine_release",
            closed_jaxpr=jax.make_jaxpr(serving_engine.release_jit)(
                *release_args
            ),
            mesh_axes=axes,
            n_donated_state_leaves=n_state,
            input_paths=flat_input_paths(
                *release_args, prefixes=("state", "slots")
            ),
            mesh_shape=mesh_shape,
            input_divisors=flat_sharding_divisors(
                release_args, (state_sh, None)
            ),
            input_sharded_dims=flat_sharded_dims(
                release_args, (state_sh, None)
            ),
            def_site=callable_def_site(serving_engine.release_jit),
            jit_fn=serving_engine.release_jit,
            example_args=release_args,
        ),
    ] + _trace_chunked_prefill_programs(
        trainer, serving_engine, kind, mesh_shape, shared=True
    )


def _trace_spec_engine_programs(
    trainer, engine, kind: str, mesh_shape
) -> List[TracedProgram]:
    """Trace the speculative-decoding ``verify_step`` program
    (docs/inference.md "Speculative decoding"): the multi-token
    drafted verify pass that replaces ``decode_step`` when the
    host-side drafter proposed tokens. Neither the trainer collect
    path nor the default serving build compiles it unless
    ``rollout.spec_decode.enabled`` — so like the serving tier above,
    spec engines are constructed separately here and the default
    engines' subjects stay byte-identical. Two variants:

    - ``engine_verify_step`` — trainer-shaped build (no prefix pool),
      the program behind tier-1 spec-on/spec-off bitwise parity;
    - ``engine_verify_step_shared`` — serving-shaped build (shared
      pool + streaming taps), whose cache state additionally carries
      the replicated shared-block pool the verify gather reads
      through.
    """
    import jax
    import jax.numpy as jnp

    from trlx_tpu.inference.engine import ContinuousBatchingEngine
    from trlx_tpu.parallel.mesh import batch_sharding

    axes = set(trainer.mesh.axis_names)
    params_sds = _sds(trainer.state.params)
    params_sh = trainer.state_shardings.params
    batch_sh = batch_sharding(trainer.mesh)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)

    common = dict(
        apply_fn=engine._apply_fn,
        init_cache_fn=engine._init_cache_fn,
        gen_config=engine.gen_config,
        query_length=engine.Q,
        vocab_size=engine.vocab_size,
        num_slots=engine.num_slots,
        admit_width=engine.admit_width,
        harvest_width=engine.harvest_width,
        block_size=engine.block_size,
        mesh=engine.mesh,
        param_shardings=engine._param_shardings,
        cache_sharding=engine._cache_sharding,
        with_values=engine.with_values,
        spec_max_draft=4,
    )
    out: List[TracedProgram] = []
    for suffix, extra in (
        ("", {}),
        (
            "_shared",
            dict(
                prefix_pool_blocks=max(2, engine.Q // engine.block_size),
                stream_taps=True,
            ),
        ),
    ):
        spec_engine = ContinuousBatchingEngine(**common, **extra)
        if spec_engine.verify_step_jit is None:
            continue  # spec_max_draft clamped to 0 (R == 1)
        state_sds = jax.eval_shape(spec_engine._make_state)
        state_sh = spec_engine.state_sharding()
        B, D = spec_engine.num_slots, spec_engine.spec_max_draft
        verify_args = (params_sds, state_sds, i32(B, D), i32(B))
        verify_prefixes = ("params", "state", "draft", "draft_len")
        verify_shardings = (params_sh, state_sh, batch_sh, batch_sh)
        out.append(
            TracedProgram(
                subject=f"{kind}.engine_verify_step{suffix}",
                closed_jaxpr=jax.make_jaxpr(spec_engine.verify_step_jit)(
                    *verify_args
                ),
                mesh_axes=axes,
                input_paths=flat_input_paths(
                    *verify_args, prefixes=verify_prefixes
                ),
                mesh_shape=mesh_shape,
                input_divisors=flat_sharding_divisors(
                    verify_args, verify_shardings
                ),
                input_sharded_dims=flat_sharded_dims(
                    verify_args, verify_shardings
                ),
                def_site=callable_def_site(spec_engine.verify_step_jit),
                jit_fn=spec_engine.verify_step_jit,
                example_args=verify_args,
            )
        )
    return out


def trace_all(
    kinds: Optional[Sequence[str]] = None,
    mesh: Optional[Dict[str, int]] = None,
) -> Iterator[TracedProgram]:
    for kind in kinds or TRAINER_KINDS:
        yield from trace_trainer(kind, mesh)
