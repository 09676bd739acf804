"""PPO rollout data types as JAX pytrees.

Re-design of the reference's per-sample ``PPORLElement`` / batched
``PPORLBatch`` (``trlx/data/ppo_types.py:7-57``). Where the reference keeps a
Python list of per-sample CPU tensors and pads at collate time
(`ppo_pipeline.py:39-66`), the TPU design keeps rollouts *batched and
device-resident with static shapes* from the moment they are produced:
queries are left-padded to a fixed query length and responses right-padded to
a fixed response length, so every downstream jitted program sees one shape and
compiles once.
"""

from __future__ import annotations

import flax.struct as struct
import jax
import jax.numpy as jnp


@struct.dataclass
class PPORolloutBatch:
    """A batch of PPO experience, all arrays device-resident.

    Shapes: B = batch, Q = max query length, R = max response length.

    :param query_tokens: [B, Q] int32, left-padded prompts (reference
        flip-pads queries, `ppo_pipeline.py:41-46`).
    :param query_mask: [B, Q] 1 where real prompt tokens.
    :param response_tokens: [B, R] int32, right-padded sampled responses.
    :param response_mask: [B, R] 1 where real response tokens (up to and
        including eos).
    :param logprobs: [B, R] behavior-policy log-probs of response tokens.
    :param values: [B, R] value estimates at each response position.
    :param rewards: [B, R] per-token rewards: -kl_coef*(logp-ref_logp) with
        the scalar score added at the last real token
        (`ppo_orchestrator.py:163-167`).
    """

    query_tokens: jax.Array
    query_mask: jax.Array
    response_tokens: jax.Array
    response_mask: jax.Array
    logprobs: jax.Array
    values: jax.Array
    rewards: jax.Array

    @property
    def batch_size(self) -> int:
        return self.query_tokens.shape[0]

    def __len__(self) -> int:
        return self.batch_size

    def select(self, idx: jax.Array) -> "PPORolloutBatch":
        """Gather a sub-batch by integer indices (for minibatch sampling),
        all fields in ONE device program: taken eagerly a gather is ~50
        launches, which held the host through epoch 1's streamed updates
        and left the chip idle 16 ms a step between back-to-back train
        steps (PERF.md §6, PR 37)."""
        return _select(self, idx)


@jax.jit
def _select(batch, idx):
    return jax.tree_util.tree_map(lambda x: x[idx], batch)


def concat_rollouts(batches) -> PPORolloutBatch:
    """Concatenate rollout batches along the batch axis.

    Implemented as ``dynamic_update_slice`` writes into a fresh buffer,
    NOT ``jnp.concatenate``: on any mesh with a size>1 axis absent from
    the chunks' batch sharding (tp/sp/pp/ep), XLA's SPMD partitioner
    mis-lowers concatenate of the committed-sharded chunk arrays into a
    *sum over the replica axis* — token ids double (11+11=22), masks
    become 2, and the out-of-vocab embed lookups then fill NaN (jax
    0.4.x; eager and jitted concat both reproduce). This was the root
    cause of the fsdp/tp PPO "NaN within a few steps" divergence: the
    first buffer concat corrupted every minibatch. dynamic_update_slice
    resolves the same input shardings correctly; the sanitizer replay
    (``python -m trlx_tpu.analysis --sanitize``) localizes regressions
    of this class to the first NaN-minting equation.
    """
    batches = list(batches)
    if len(batches) == 1:
        return batches[0]

    def cat(*xs):
        total = sum(x.shape[0] for x in xs)
        out = jnp.zeros((total,) + xs[0].shape[1:], xs[0].dtype)
        offset = 0
        for x in xs:
            out = jax.lax.dynamic_update_slice(
                out, x, (offset,) + (0,) * (x.ndim - 1)
            )
            offset += x.shape[0]
        return out

    return jax.tree_util.tree_map(cat, *batches)
