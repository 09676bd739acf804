"""Expert parallelism: top-1 (switch) MoE routing over an ``ep`` mesh axis.

Beyond the reference (SURVEY §2.9: expert parallel "NO ... not required") —
provided as the ``ep`` counterpart of the pipeline/sequence primitives so
the mesh covers every major parallelism axis. TPU-native design: tokens are
sharded over ``ep``, experts are sharded over ``ep`` (leading [E] axis of
the stacked expert params), and dispatch/return ride two ``all_to_all``
collectives over ICI — the switch-transformer layout.

Semantics (Switch Transformer, top-1):
- router logits ``x @ router_w`` pick one expert per token; the gate is the
  softmax probability of the chosen expert (router gradients flow through
  the gate product);
- fixed per-device/per-expert capacity ``ceil(capacity_factor * N_local /
  E)``; tokens over capacity are dropped (their combined output is zero —
  callers keep the residual connection outside, as switch layers do);
- everything is static-shaped: position-in-expert comes from a cumulative
  sum, dispatch/combine are scatter/gather into [E, C, D] buffers.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def moe_apply(
    expert_fn: Callable,  # (expert_params, tokens [n, D]) -> [n, D]
    stacked_expert_params,  # leaves [E, ...]
    x: jax.Array,  # [N, D] tokens, sharded over `axis_name`
    router_w: jax.Array,  # [D, E] router weights (replicated)
    mesh: Mesh,
    axis_name: str = "ep",
    capacity_factor: float = 1.25,
    batch_axes: tuple = (),
) -> jax.Array:
    """Route each token through its top-1 expert; returns [N, D].

    ``E`` (leading dim of the expert params) must be divisible by the ``ep``
    axis size. Dropped (over-capacity) tokens return zeros.

    ``batch_axes``: extra mesh axes the token dim is *also* sharded over
    (e.g. ``("dp", "fsdp")`` inside a training step) — each data-parallel
    group then runs its own expert exchange, with the ``all_to_all`` riding
    only the ``ep`` axis. Without it, tokens are treated as replicated over
    those axes (every device would redo the full batch).
    """
    E = jax.tree_util.tree_leaves(stacked_expert_params)[0].shape[0]
    ep = mesh.shape[axis_name]
    if E % ep:
        raise ValueError(f"{E} experts not divisible by ep={ep}")
    N = x.shape[0]
    n_shards = ep * int(np.prod([mesh.shape[a] for a in batch_axes]))
    if N % n_shards:
        raise ValueError(f"{N} tokens not divisible by {n_shards} shards")
    n_loc = N // n_shards
    C = int(np.ceil(capacity_factor * n_loc / E))  # per (device, expert)

    def local(params, x, router_w):
        # x: [n_loc, D] local tokens; params leaves: [E/ep, ...]
        # routing in float32: near-tied logits must argmax identically to
        # any dense-execution twin of this layer regardless of x.dtype
        logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(probs, axis=-1)  # [n_loc]
        gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]

        onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)  # [n_loc, E]
        pos = jnp.cumsum(onehot, axis=0) - onehot  # tokens ahead, same expert
        pos = jnp.sum(pos * onehot, axis=-1)  # [n_loc]
        keep = pos < C

        # dispatch buffers [E, C, D]; dropped tokens never written
        dispatch = jnp.zeros((E, C) + x.shape[1:], x.dtype)
        dispatch = dispatch.at[
            jnp.where(keep, expert, 0), jnp.where(keep, pos, 0)
        ].add(jnp.where(keep[:, None], x, 0.0))

        # to expert owners: [E, C, D] -> [E/ep, ep*C, D]
        inbox = jax.lax.all_to_all(
            dispatch, axis_name, split_axis=0, concat_axis=1, tiled=True
        )
        outbox = jax.vmap(expert_fn)(params, inbox)  # [E/ep, ep*C, D]
        # back to token owners: [E, C, D]
        returned = jax.lax.all_to_all(
            outbox, axis_name, split_axis=1, concat_axis=0, tiled=True
        )

        y = returned[jnp.where(keep, expert, 0), jnp.where(keep, pos, 0)]
        y = jnp.where(keep[:, None], y, 0.0)
        return (y.astype(jnp.float32) * gate[:, None]).astype(x.dtype)

    from jax import shard_map

    param_specs = jax.tree_util.tree_map(
        lambda _: P(axis_name), stacked_expert_params
    )
    tok_spec = P((*batch_axes, axis_name)) if batch_axes else P(axis_name)
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs, tok_spec, P()),
        out_specs=tok_spec,
    )(stacked_expert_params, x, router_w)
