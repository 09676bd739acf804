"""Compressed convolutional attention (CCA, Zyphra 2025): what lies between
a layer's down-projections and its attention read. Queries and keys are
projected into a latent narrower than the model (``H_q`` and ``H_kv`` heads
of ``Dh``), **mixed along the sequence** by two small causal convolutions,
tied to each other by a mean, normalised, and attended over as grouped
heads; half of the value heads read the token before.

Per column ``t``, with ``q~ [H_q, Dh]``, ``k~ [H_kv, Dh]`` the projected
latents, ``G = H_q / H_kv`` and ``z = [q~ | k~]`` (``H = H_q + H_kv`` heads):

    c0_t    = sum_j w0[j] * z_{t-K0+1+j} + b0              depthwise (ops/ssm.py)
    c1_t[h] = sum_j c0_{t-K1+1+j}[h] W1[h, j] + b1[h]      per head, Dh x Dh a tap
    q_t[h]  = c1_t[h] + (q~_t[h] + k~_t[h // G]) / 2
    k_t[g]  = c1_t[H_q + g] + (k~_t[g] + mean_{h in g} q~_t[h]) / 2
    q <- sqrt(Dh) q / |q| ;  k <- tau[g] sqrt(Dh) k / |k|   per head
    v_t     = [ own_t | src_{t-1} ]                        the first H_kv / 2 heads from the
                                                           token, the rest from the one before

Everything a column needs from the columns before it is a **tail** a
sequence carries beside its paged keys (``ops/kv_cache.py::tail_buffers``):
the last ``K0 - 1`` rows of ``z``, the last ``K1 - 1`` rows of ``c0`` and the
last row of the shifted value's source, in ``state_dtype``. The conventions
are ``ops/ssm.py``'s: a masked column (left padding, a parked slot's step)
feeds zeros and leaves the tail where the call holds no valid column; a row
that ``fresh`` marks starts from zeros whatever its slot held
(``ssm.call_columns`` reads both from the cache mask). One column a row is
the same sums over a window of tail and column; the traced call sites are
counted in ``cca/path{path=prefill|step}``.

The convolutions, the means and the norms are float32; the per-head mix
takes its operands in the compute dtype and accumulates in float32, as
every projection does. Imports nothing above ``ops/``.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.ops.ssm import causal_conv
from trlx_tpu.telemetry import get_metrics

#: under the square root of both norms: a masked column's latent is its
#: bias alone, which may be zero
NORM_EPS = 1e-6


def carry_columns(x, tail, mask):
    """``x`` [B, T, C] behind its ``tail`` [B, n, C] (the columns before
    this call): ``(padded [B, n + T, C] float32, new tail [B, n, C]
    float32)``, masked columns as zeros, the old tail where the call has
    no valid column (:func:`ops.ssm.causal_conv`'s rule)."""
    n, T = tail.shape[1], x.shape[1]
    x32 = x.astype(jnp.float32) * mask[..., None]
    padded = jnp.concatenate([tail.astype(jnp.float32), x32], axis=1)
    any_valid = jnp.sum(mask, axis=-1) > 0
    return padded, jnp.where(any_valid[:, None, None], padded[:, T:], padded[:, :n])


def grouped_causal_conv(x, weight, bias, tail, mask, dtype):
    """Causal convolution over columns that mixes inside each head. ``x``
    [B, T, H * Dh]; ``weight`` [H, K, Dh, Dh] (``weight[:, K - 1]``
    multiplies the column itself); ``bias`` [H, Dh]; ``tail`` [B, K - 1,
    H * Dh]. The ``K`` taps are laid side by side so that a head is one
    ``[T, K * Dh] x [K * Dh, Dh]`` product in ``dtype``, accumulated in
    float32. Returns ``(out [B, T, H, Dh] float32, new tail)``."""
    H, K, Dh, _ = weight.shape
    B, T = x.shape[:2]
    padded, new_tail = carry_columns(x, tail, mask)
    heads = padded.reshape(B, K - 1 + T, H, Dh).astype(dtype)
    taps = jnp.concatenate([heads[:, j : j + T] for j in range(K)], axis=-1)
    # a batch of H plain products, the heads leading
    out = jnp.einsum(
        "hni,hio->hno", jnp.moveaxis(taps, 2, 0).reshape(H, B * T, K * Dh),
        weight.reshape(H, K * Dh, Dh).astype(dtype), preferred_element_type=jnp.float32,
    )
    out = jnp.moveaxis(out.reshape(H, B, T, Dh), 0, 2)
    return out + bias.astype(jnp.float32), new_tail


def l2_heads(x, scale):
    """``scale * sqrt(Dh) * x / |x|`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + NORM_EPS)
    return x * inv * (scale * jnp.sqrt(jnp.float32(x.shape[-1])))


def cca_mix(
    q_lat, k_lat, v_lat, *, conv0_weight, conv0_bias, conv1_weight, conv1_bias, k_temp,
    n_q: int, n_kv: int, head_dim: int, dtype, mask=None, fresh=None,
    tail: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, Optional[Dict[str, jax.Array]]]:
    """The latents of a call (``q_lat`` [B, T, H_q * Dh], ``k_lat`` and
    ``v_lat`` [B, T, H_kv * Dh], zero where ``mask`` is) -> ``(q [B, T,
    H_q, Dh], k, v [B, T, H_kv, Dh] in dtype, the new tail or None)``,
    before positions are applied. ``tail`` is the layer's by-slot rows
    (``tail_z``, ``tail_c0``, ``tail_v``); None: zeros, and none returned."""
    B, T = q_lat.shape[:2]
    G, Dh, half = n_q // n_kv, head_dim, n_kv // 2
    f32 = jnp.float32
    mask = jnp.ones((B, T), f32) if mask is None else mask.astype(f32)
    K0, K1 = conv0_weight.shape[0], conv1_weight.shape[1]
    width = (n_q + n_kv) * Dh
    if tail is None:
        rows = {"tail_z": (K0 - 1, width), "tail_c0": (K1 - 1, width), "tail_v": (1, half * Dh)}
        old = {k: jnp.zeros((B,) + s, f32) for k, s in rows.items()}
    else:
        old = dict(tail)
        if fresh is not None:
            old = {k: jnp.where(fresh[:, None, None], jnp.zeros((), v.dtype), v) for k, v in old.items()}
    get_metrics().counter(
        "cca/path{path=%s}" % ("step" if tail is not None and T == 1 else "prefill")
    ).inc()
    z = jnp.concatenate([q_lat, k_lat], axis=-1)
    c0, tail_z = causal_conv(z, conv0_weight, conv0_bias, old["tail_z"], mask)
    c1, tail_c0 = grouped_causal_conv(c0, conv1_weight, conv1_bias, old["tail_c0"], mask, dtype)
    q32 = q_lat.astype(f32).reshape(B, T, n_kv, G, Dh)
    k32 = k_lat.astype(f32).reshape(B, T, n_kv, 1, Dh)
    q = c1[:, :, :n_q] + ((q32 + k32) / 2).reshape(B, T, n_q, Dh)
    k = c1[:, :, n_q:] + (k32[:, :, :, 0] + jnp.mean(q32, axis=3)) / 2
    q = l2_heads(q, 1.0)
    k = l2_heads(k, k_temp.astype(f32)[:, None])
    # the second half of the value heads reads the column before
    own, src = v_lat[..., : half * Dh], v_lat[..., half * Dh :]
    behind, tail_v = carry_columns(src, old["tail_v"], mask)
    v = jnp.concatenate([own.astype(f32), behind[:, :T]], axis=-1)
    new_tail = None
    if tail is not None:
        new = {"tail_z": tail_z, "tail_c0": tail_c0, "tail_v": tail_v}
        new_tail = {k: new[k].astype(tail[k].dtype) for k in tail}
    return q.astype(dtype), k.astype(dtype), v.reshape(B, T, n_kv, Dh).astype(dtype), new_tail
