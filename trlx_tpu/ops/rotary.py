"""Rotary position embeddings — both conventions.

GPT-J rotates interleaved pairs (``rotate_every_two``); GPT-NeoX rotates
concatenated halves (``rotate_half``). Getting the convention right per
family is what exact-logit checkpoint parity hinges on (verified in
``tests/test_gptj_parity.py`` / ``test_neox_parity.py``). Frequencies are
the plain ``base^(-2j/d)`` or, under a published ``rope_scaling`` group of
type ``yarn``, :func:`yarn_frequencies`.
"""

from __future__ import annotations

import math
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(rotary_dim: int, base: float, scaling: Mapping[str, Any]) -> jax.Array:
    """The ``rotary_dim / 2`` frequencies under YaRN (Peng et al. 2023, the
    form the published DeepSeek configurations carry): a pair whose
    wavelength fits ``beta_fast`` times and more into the original context
    keeps its frequency ``f_j = base^(-2j / rotary_dim)``, one that fits
    ``beta_slow`` times or fewer takes ``f_j / factor``, and between the two
    pair indices ``lo = floor(c(beta_fast))`` and ``hi = ceil(c(beta_slow))``,
    ``c(b) = rotary_dim ln(L / (2 pi b)) / (2 ln base)``, the two are mixed
    linearly in ``j``. float32."""
    dim, length = rotary_dim, scaling["original_max_position_embeddings"]
    turn = lambda b: dim * math.log(length / (2 * math.pi * b)) / (2 * math.log(base))
    lo = max(math.floor(turn(scaling["beta_fast"])), 0)
    hi = min(math.ceil(turn(scaling["beta_slow"])), dim - 1)
    j = jnp.arange(dim // 2, dtype=jnp.float32)
    freq = 1.0 / base ** (2 * j / dim)
    ramp = jnp.clip((j - lo) / max(hi - lo, 0.001), 0.0, 1.0)
    return freq * (1 - ramp) + freq / scaling["factor"] * ramp


def yarn_score_scale(scaling: Optional[Mapping[str, Any]]) -> float:
    """``m^2``, what YaRN multiplies attention's ``1 / sqrt(D)`` with:
    ``m = 0.1 mscale_all_dim ln(factor) + 1`` (1.0 without scaling, or
    where the group gives no ``mscale_all_dim``)."""
    if not scaling or not scaling.get("mscale_all_dim"):
        return 1.0
    return _yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2


def rotary_angles(
    position_ids: jax.Array,  # [B, T]
    rotary_dim: int,
    base: float = 10000.0,
    scaling: Optional[Mapping[str, Any]] = None,
):
    """-> (sin, cos) of shape [B, T, rotary_dim/2], float32. ``scaling`` is
    a published ``rope_scaling`` group of ``type: yarn``
    (:func:`yarn_frequencies`) or None; any other type is refused by name,
    and so is a group whose ``mscale`` and ``mscale_all_dim`` differ: sin
    and cos would then carry the ratio of the two ``mscale(factor, .)``,
    which no configuration here asks for."""
    if scaling is None:
        inv_freq = 1.0 / (
            base ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
        )
    else:
        kind = scaling.get("type", scaling.get("rope_type"))
        if kind != "yarn":
            raise ValueError(f"rope_scaling of type {kind!r} is not built (yarn)")
        mscale, all_dim = scaling.get("mscale", 1), scaling.get("mscale_all_dim", 0)
        if _yarn_mscale(scaling["factor"], mscale) != _yarn_mscale(scaling["factor"], all_dim):
            raise ValueError(
                f"rope_scaling with mscale={mscale} != mscale_all_dim={all_dim} (a scaled sin "
                "and cos) is not built"
            )
        inv_freq = yarn_frequencies(rotary_dim, base, scaling)
    angles = position_ids.astype(jnp.float32)[..., None] * inv_freq  # [B, T, D/2]
    return jnp.sin(angles), jnp.cos(angles)


def apply_rotary_interleaved(
    x: jax.Array,  # [B, T, H, D] (first rotary_dim dims rotated)
    sin: jax.Array,  # [B, T, rotary_dim/2]
    cos: jax.Array,
    rotary_dim: int,
) -> jax.Array:
    """GPT-J convention: pairs (x0,x1),(x2,x3),... rotate together."""
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    sin2 = jnp.repeat(sin, 2, axis=-1)[:, :, None, :]  # [B, T, 1, rotary_dim]
    cos2 = jnp.repeat(cos, 2, axis=-1)[:, :, None, :]
    x1 = rot[..., ::2]
    x2 = rot[..., 1::2]
    rotated = jnp.stack([-x2, x1], axis=-1).reshape(rot.shape)
    rot = rot * cos2.astype(x.dtype) + rotated * sin2.astype(x.dtype)
    return jnp.concatenate([rot, rest], axis=-1) if rest.shape[-1] else rot


def apply_rotary_half(
    x: jax.Array,  # [B, T, H, D]
    sin: jax.Array,  # [B, T, rotary_dim/2]
    cos: jax.Array,
    rotary_dim: int,
) -> jax.Array:
    """GPT-NeoX convention: first and second halves of the rotary dims
    rotate against each other."""
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    sin2 = jnp.concatenate([sin, sin], axis=-1)[:, :, None, :]
    cos2 = jnp.concatenate([cos, cos], axis=-1)[:, :, None, :]
    half = rotary_dim // 2
    rotated = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    rot = rot * cos2.astype(x.dtype) + rotated * sin2.astype(x.dtype)
    return jnp.concatenate([rot, rest], axis=-1) if rest.shape[-1] else rot
