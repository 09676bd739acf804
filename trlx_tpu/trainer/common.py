"""Shared trainer machinery: train state, optimizer, layer freezing.

Replaces the reference's AdamW + cosine schedule setup
(``accelerate_base_model.py:94-106``) and ``num_layers_unfrozen`` freezing
(``ilql_models.py:217-225``). Freezing is an optax mask (frozen params get
zero updates) — under GSPMD the frozen leaves still shard, they just never
change, which is the TPU analogue of requires_grad=False.
"""

from __future__ import annotations

import re
from typing import Any, NamedTuple, Optional, Tuple

import flax.struct as struct
import jax
import jax.numpy as jnp
import optax

from trlx_tpu.data.configs import TrainConfig


@struct.dataclass
class TrainState:
    """Minimal explicit train state; RNG and KL-controller state are threaded
    by the host loop (they are host-decision values, not gradient state)."""

    params: Any
    opt_state: Any
    step: jax.Array  # int32 scalar


def unfrozen_param_mask(
    params: Any,
    num_layers_unfrozen: int,
    n_layer: int,
    zero_freezes_all: bool = False,
) -> Any:
    """True for trainable leaves. With ``num_layers_unfrozen=k > 0``, only the
    top-k transformer blocks + final layernorm + heads train.

    What the reference actually does with ``num_layers_unfrozen`` differs by
    path, and the two are mapped here via ``zero_freezes_all``:

    - **PPO path** (``zero_freezes_all=False``): the freezing block in
      ``accelerate_base_model.py:55-69`` is **commented out** in the
      reference as shipped — the policy trains ALL layers regardless of the
      setting (it only sizes the hydra KL-ref branch, ``ppo_models.py:
      525-536``). So ``k <= 0`` trains everything here, and ``k > 0`` is
      the re-enabled behavior of that commented code (freeze the bottom
      ``n_layer - k`` blocks), offered as real work-avoidance.
    - **ILQL path** (``zero_freezes_all=True``): ``ilql_models.py:217-225``
      is live code — ``0`` freezes ALL blocks, ``k > 0`` freezes the bottom
      ``n_layer - k``, negative freezes none. ``k == 0`` therefore maps to
      ``first_trainable == n_layer`` (every block frozen; heads + ln_f
      still train).

    Documented divergence (PARITY.md quirks): the reference freezes only
    the *blocks* — wte/wpe stay trainable; this mask also freezes the
    embeddings below the branch point, consistent with the hydra branch
    point being the first trainable position."""
    if num_layers_unfrozen > n_layer:
        raise ValueError(
            f"model.num_layers_unfrozen={num_layers_unfrozen} exceeds "
            f"n_layer={n_layer}"
        )
    if num_layers_unfrozen < 0 or (
        num_layers_unfrozen == 0 and not zero_freezes_all
    ):
        return jax.tree_util.tree_map(lambda _: True, params)
    first_trainable = n_layer - num_layers_unfrozen

    def mask_for(path, leaf):
        name = "/".join(
            str(getattr(p, "key", getattr(p, "idx", p))) for p in path
        )
        m = re.search(r"h_(\d+)/", name)
        if m:
            return int(m.group(1)) >= first_trainable
        if "wte" in name or "wpe" in name or "encoder" in name:
            return False
        return True  # ln_f, value/Q heads, anything else

    return jax.tree_util.tree_map_with_path(mask_for, params)


def stochastic_round(x32: jax.Array, key: jax.Array, dtype) -> jax.Array:
    """f32 -> ``dtype`` with stochastic rounding (unbiased: E[out] == x).

    Adds uniform noise below the kept mantissa bits of the IEEE-754 pattern
    and truncates — the standard trick for accumulating EMAs whose per-step
    increment ((1-b2)·g² with b2 up to 0.999) sits below bf16's 2^-8
    relative resolution; round-to-nearest would systematically drop it and
    the moment would stall at its old value."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float32:
        return x32
    if dtype != jnp.bfloat16:
        raise ValueError(f"stochastic_round supports bfloat16, got {dtype}")
    bits = jax.lax.bitcast_convert_type(x32.astype(jnp.float32), jnp.uint32)
    noise = jax.random.bits(key, bits.shape, jnp.uint32) & jnp.uint32(0xFFFF)
    rounded = jax.lax.bitcast_convert_type(
        (bits + noise) & jnp.uint32(0xFFFF0000), jnp.float32
    ).astype(jnp.bfloat16)
    # adding noise to an inf/nan bit pattern would walk into nan space
    return jnp.where(jnp.isfinite(x32), rounded, x32.astype(jnp.bfloat16))


class ScaleByAdamLPState(NamedTuple):
    """Adam state with moments stored in a reduced dtype (mu/nu trees mirror
    the param tree, so partition rules shard them like ScaleByAdamState's)."""

    count: jax.Array
    mu: Any
    nu: Any


def scale_by_adam_low_precision(
    b1: float, b2: float, eps: float, moment_dtype
) -> optax.GradientTransformation:
    """``optax.scale_by_adam`` with BOTH moments stored in ``moment_dtype``
    (optax only offers ``mu_dtype``). All update math runs in f32; stores go
    through :func:`stochastic_round`, keyed deterministically per
    (step, leaf) — bitwise reproducible, no RNG state to checkpoint.

    Halves the optimizer's per-step HBM traffic (m+v read+write is ~8B/param
    at f32; its share of a train step is not measured on the chip) and its
    resident bytes (the `test_neox20b_sharding.py` budget for the 20B
    stretch)."""
    moment_dtype = jnp.dtype(moment_dtype)

    def init_fn(params):
        zeros = lambda t: jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, moment_dtype), t
        )
        return ScaleByAdamLPState(
            count=jnp.zeros((), jnp.int32), mu=zeros(params), nu=zeros(params)
        )

    def update_fn(updates, state, params=None):
        del params
        count = state.count + 1
        f32 = lambda t: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), t
        )
        mu32 = jax.tree_util.tree_map(
            lambda m, g: b1 * m + (1.0 - b1) * g, f32(state.mu), f32(updates)
        )
        nu32 = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1.0 - b2) * g * g, f32(state.nu), f32(updates)
        )
        c1 = 1.0 - b1 ** count.astype(jnp.float32)
        c2 = 1.0 - b2 ** count.astype(jnp.float32)
        new_updates = jax.tree_util.tree_map(
            lambda m, v: (m / c1) / (jnp.sqrt(v / c2) + eps), mu32, nu32
        )
        # rbg keys: XLA's RngBitGenerator is ~3x cheaper than threefry for
        # the 2N uint32 draws a full-model SR store needs — with threefry
        # the RNG cost exceeded the halved-moment traffic saving (a
        # pre-chip reading; not measured on the chip)
        # the literal seed is the CONTRACT here: stochastic rounding must
        # be bitwise reproducible per (step, leaf) with no RNG state to
        # checkpoint — it is noise injection, not statistical sampling
        base = jax.random.fold_in(jax.random.key(0x5EED, impl="rbg"), count)  # tpu-lint: disable=fixed-seed
        leaves_mu, treedef = jax.tree_util.tree_flatten(mu32)
        leaves_nu = treedef.flatten_up_to(nu32)
        keys = jax.random.split(base, 2 * len(leaves_mu))
        mu_st = treedef.unflatten(
            [
                stochastic_round(x, keys[i], moment_dtype)
                for i, x in enumerate(leaves_mu)
            ]
        )
        nu_st = treedef.unflatten(
            [
                stochastic_round(x, keys[len(leaves_mu) + i], moment_dtype)
                for i, x in enumerate(leaves_nu)
            ]
        )
        return new_updates, ScaleByAdamLPState(count=count, mu=mu_st, nu=nu_st)

    return optax.GradientTransformation(init_fn, update_fn)


def stop_frozen_gradients(params: Any, trainable_mask: Optional[Any]) -> Any:
    """``stop_gradient`` on every frozen param leaf, for use *inside* a
    ``loss_fn`` before the forward. The gradients of frozen leaves become
    structural zeros, so XLA dead-code-eliminates their entire backward —
    with bottom-layer freezing that prunes the backprop below the branch
    point (the reference gets the same pruning from requires_grad=False).
    Also makes clip_by_global_norm see only trainable gradients, matching
    torch's behavior where frozen params simply have no .grad."""
    if trainable_mask is None or all(jax.tree_util.tree_leaves(trainable_mask)):
        return params
    return jax.tree_util.tree_map(
        lambda p, t: p if t else jax.lax.stop_gradient(p), params, trainable_mask
    )


def make_optimizer(
    train_config: TrainConfig,
    total_steps: int,
    trainable_mask: Optional[Any] = None,
) -> optax.GradientTransformation:
    """grad-clip -> AdamW(cosine lr_init->lr_target) [-> freeze mask].

    Reference: AdamW + CosineAnnealingLR from lr_init to lr_target
    (`accelerate_base_model.py:94-106`). With
    ``train.adam_moment_dtype: "bfloat16"`` the Adam moments are stored in
    bf16 with stochastic rounding (same chain order as ``optax.adamw``:
    scale_by_adam -> add_decayed_weights -> scale_by_learning_rate)."""
    schedule = optax.cosine_decay_schedule(
        init_value=train_config.lr_init,
        decay_steps=max(total_steps, 1),
        alpha=train_config.lr_target / train_config.lr_init
        if train_config.lr_init
        else 1.0,
    )
    if train_config.adam_moment_dtype not in ("float32", "bfloat16"):
        # validate the raw string BEFORE jnp.dtype — an unknown name (e.g.
        # the natural typo "bf16") would otherwise die in numpy's opaque
        # TypeError instead of this message
        raise ValueError(
            f"train.adam_moment_dtype must be float32 or bfloat16, got "
            f"{train_config.adam_moment_dtype!r}"
        )
    moment_dtype = jnp.dtype(train_config.adam_moment_dtype)
    if moment_dtype == jnp.float32:
        adam = optax.adamw(
            learning_rate=schedule,
            b1=train_config.opt_betas[0],
            b2=train_config.opt_betas[1],
            eps=train_config.opt_eps,
            weight_decay=train_config.weight_decay,
        )
    else:
        adam = optax.chain(
            scale_by_adam_low_precision(
                b1=train_config.opt_betas[0],
                b2=train_config.opt_betas[1],
                eps=train_config.opt_eps,
                moment_dtype=moment_dtype,
            ),
            optax.add_decayed_weights(train_config.weight_decay),
            optax.scale_by_learning_rate(schedule),
        )
    if trainable_mask is not None and not all(
        jax.tree_util.tree_leaves(trainable_mask)
    ):
        # Frozen leaves carry NO optimizer state and see no Adam traffic
        # (optax.masked skips them entirely) — with bottom-layer freezing
        # the moments shrink to the trainable slice, exactly as torch's
        # requires_grad=False does for the reference. The trailing
        # set_to_zero is a hard guarantee that frozen params never move
        # even if a caller feeds unstopped gradients. (Checkpoints from
        # the earlier full-size-moment masked layout do not restore into
        # this structure — frozen-mask runs must restart.)
        tx = optax.chain(
            optax.clip_by_global_norm(train_config.grad_clip),
            optax.masked(adam, trainable_mask),
            optax.masked(
                optax.set_to_zero(),
                jax.tree_util.tree_map(lambda t: not t, trainable_mask),
            ),
        )
    elif trainable_mask is not None:
        # all-trainable: keep the historical opt-state pytree structure
        # (chain(chain(clip, adam), masked(set_to_zero, all-False))) so
        # pre-existing Orbax checkpoints of default-config runs still
        # restore leaf-for-leaf
        tx = optax.chain(
            optax.chain(
                optax.clip_by_global_norm(train_config.grad_clip), adam
            ),
            optax.masked(
                optax.set_to_zero(),
                jax.tree_util.tree_map(lambda t: not t, trainable_mask),
            ),
        )
    else:
        tx = optax.chain(
            optax.clip_by_global_norm(train_config.grad_clip),
            adam,
        )
    return tx
