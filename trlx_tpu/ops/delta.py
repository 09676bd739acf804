"""The gated delta rule (Yang, Kautz & Hatamizadeh, "Gated Delta Networks",
arXiv:2412.06464): a linear-attention layer that keeps a matrix state a
head and a sequence, whose update **reads the state back** before it
writes, and no keys.

The recurrence, per value head with a state ``S`` of ``[Dk, Dv]`` (key size
x value size), over the columns ``t`` of a sequence:

    S  <- exp(g_t) S                             g_t <= 0, the decay
    u   = S^T k_t                                what the state holds under k_t
    S  <- S + k_t (beta_t (v_t - u))^T           0 <= beta_t <= 1, the write strength
    o_t = S^T q_t

``q`` and ``k`` are L2-normalised a head (:func:`l2_normalise`; ``q`` also
scaled by ``Dk^-1/2``); ``q``, ``k`` and ``v`` come out of a depthwise
causal convolution over the projected columns (``ops/ssm.py::causal_conv``),
whose last ``K - 1`` inputs are the **tail** a sequence carries beside its
state, exactly as a state-space layer does (the cache dict is the state
kind's, ``ops/kv_cache.py::state_buffers``: ``ssm_state`` ``[B, H, Dk, Dv]``
and ``conv_tail``).

Two ways to compute it, both exact:

- :func:`gated_delta_chunk`, for a call of many columns (a forward without a
  cache, a whole admission, a chunk of one): columns in chunks of ``chunk``
  (the WY form). In a chunk with incoming state ``S_0`` and ``gamma_i =
  sum_{j<=i} g_j``:

      A[i, j] = beta_i (k_i . k_j) exp(gamma_i - gamma_j)    j < i, else 0
      T = (I + A)^-1                                         :func:`unit_lower_inverse`
      W = T diag(beta) (K * exp gamma),  U = T diag(beta) V
      V~ = U - W S_0
      o_i = exp(gamma_i) S_0^T q_i + sum_{j<=i} exp(gamma_i - gamma_j) (q_i . k_j) v~_j
      S_C = exp(gamma_C) S_0 + sum_j exp(gamma_C - gamma_j) k_j v~_j^T

  one unit-triangular solve a chunk where the plain recurrence has a chain
  ``T`` long.
- :func:`gated_delta_step`, for one column a sequence (a decode step): the
  recurrence, with both reads of the state (``S^T k`` and ``S^T q``) taken
  from the state as it came in and the rank-one write's share of ``o`` added
  by hand (``o = exp(g) S^T q + (k . q) delta``): two passes over the state
  (one reads, one reads and writes) where the equations as written make
  three.

``g``, ``beta``, every ``exp``, the solve, the state and the gated norm are
float32; the large products of a chunk take their operands in the compute
dtype and accumulate in float32, as every projection does.

**Masked columns are no-ops.** A column whose mask is 0 arrives with ``beta
= 0`` and ``g = 0`` (the caller multiplies both with the mask) and a zero
convolution input: ``v~ = 0`` and a decay of 1, so the state is left bit for
bit, as ``ops/ssm.py`` keeps its own.

**A decay that is a vector a head** (Kimi Delta Attention, the ``kda_*``
functions): ``S <- Diag(exp(g_t)) S`` with ``g_t`` one number a key channel,
every other line of the recurrence as above. In the chunked form the decay
between two columns no longer leaves ``k_i . k_j`` as one factor:

    A[i, j] = beta_i sum_d k_i[d] k_j[d] exp(gamma_i[d] - gamma_j[d])       j < i

so it goes into the operands, ``k_i * exp(gamma_i - r)`` against ``k_j *
exp(r - gamma_j)`` about a reference row ``r``, and neither factor may leave
float32. The family bounds its gate (``g`` in ``(lower_bound, 0)``, -5 as
published), so over ``sub_chunk`` columns about their middle no exponent
passes ``sub_chunk / 2 * |lower_bound|`` (:func:`kda_sub_chunk`: 16 columns
and 40 at -5; ``exp(80)``, the product of two, fits float32): a chunk's
``[L, L]`` scores are taken a row block of ``sub_chunk`` at a time, the
block's rows lifted about its own middle row and the columns up to its end
lowered about the same row (columns of earlier blocks get factors under 1,
which may underflow to the zero they nearly are). Everything else of the
chunk (``W``, ``U``, the products with the state, the read-out) only ever
multiplies with ``exp`` of something ``<= 0``. Exact in float32 for any gate
within the bound; a gate below it overflows and is the caller's fault.

Imports nothing above ``ops/``.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.ops.ssm import causal_conv
from trlx_tpu.telemetry import get_metrics

HIGHEST = jax.lax.Precision.HIGHEST


def l2_normalise(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + eps)


def rms_norm_gated(y, gate, weight, eps: float):
    """``rms(y) * weight * silu(gate)`` over the last axis, float32: the
    norm first and the gate after (``ops/ssm.py::gated_rms_norm`` is the
    other order)."""
    y32 = y.astype(jnp.float32)
    y32 = y32 * jax.lax.rsqrt(jnp.mean(y32 * y32, axis=-1, keepdims=True) + eps)
    return y32 * weight.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))


def unit_lower_inverse(A):
    """``(I + A)^-1`` of strictly lower-triangular ``A`` [..., L, L], ``L``
    a power of two, float32: blocked forward substitution by doubling. With
    the inverses ``X_1``, ``X_2`` of two neighbouring diagonal blocks of
    size ``b`` and ``A_21`` the block under the first,

        [[I + A_11, 0], [A_21, I + A_22]]^-1 = [[X_1, 0], [-X_2 A_21 X_1, X_2]]

    from blocks of one (whose inverse is 1) up to ``L``: ``log2 L`` levels
    of two small products, no entry ever larger than the inverse's own (the
    Neumann product ``prod_m (I + (-A)^(2^m))`` is the same matrix but its
    factors grow like binomials where keys repeat)."""
    L = A.shape[-1]
    if L & (L - 1):
        raise ValueError(f"unit_lower_inverse takes a power of two; got {L}")
    lead = A.shape[:-2]
    A = A.astype(jnp.float32)
    inv = jnp.ones(lead + (L, 1, 1), jnp.float32)
    b = 1
    while b < L:
        n = L // (2 * b)
        blocks = A.reshape(lead + (n, 2, b, n, 2, b))[..., :, 1, :, :, 0, :]  # [.., n, b, n, b]
        a21 = jnp.moveaxis(jnp.diagonal(blocks, axis1=-4, axis2=-2), -1, -3)  # [.., n, b, b]
        pairs = inv.reshape(lead + (n, 2, b, b))
        x1, x2 = pairs[..., 0, :, :], pairs[..., 1, :, :]
        x21 = -jnp.matmul(jnp.matmul(x2, a21, precision=HIGHEST), x1, precision=HIGHEST)
        top = jnp.concatenate([x1, jnp.zeros_like(x1)], axis=-1)
        inv = jnp.concatenate([top, jnp.concatenate([x21, x2], axis=-1)], axis=-2)
        b *= 2
    return inv.reshape(lead + (L, L))


def gated_delta_chunk(q, k, v, g, beta, state, chunk: int = 64):
    """The rule over ``T`` columns in chunks. ``q``, ``k`` [B, T, H, Dk]
    (normalised, ``q`` scaled) and ``v`` [B, T, H, Dv] in the compute dtype;
    ``g`` (<= 0) and ``beta`` [B, T, H] float32, both 0 at a masked column;
    ``state`` [B, H, Dk, Dv] float32. Returns ``(o [B, T, H, Dv] float32,
    final state float32)``."""
    Bsz, T, H, Dk = q.shape
    cd, f32 = q.dtype, jnp.float32
    L = 1 << max(min(chunk, T) - 1, 0).bit_length()  # the next power of two: the solve doubles its blocks
    pad = (-T) % L
    if pad:  # columns with beta = 0 and g = 0: no-ops
        grow = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        q, k, v, g, beta = grow(q), grow(k), grow(v), grow(g), grow(beta)
    nc = (T + pad) // L
    # [nc, B, H, L, ...]: a head's chunk is one matrix
    chunks = lambda a: jnp.moveaxis(
        jnp.swapaxes(a, 1, 2).reshape((Bsz, H, nc, L) + a.shape[3:]), 2, 0
    )
    rows, cols = jnp.arange(L)[:, None], jnp.arange(L)[None, :]

    def one_chunk(S, xs):
        q_c, k_c, v_c, g_c, b_c = xs  # [B, H, L, D*], [B, H, L]
        gamma = jnp.cumsum(g_c, axis=-1)
        span = gamma[..., :, None] - gamma[..., None, :]
        decay = jnp.where(rows >= cols, jnp.exp(jnp.where(rows >= cols, span, 0.0)), 0.0)
        kk = jnp.einsum("bhid,bhjd->bhij", k_c, k_c, preferred_element_type=f32)
        A = jnp.where(rows > cols, b_c[..., None] * kk * decay, 0.0)
        solve = unit_lower_inverse(A)
        k_in = (k_c.astype(f32) * (b_c * jnp.exp(gamma))[..., None]).astype(cd)
        v_in = (v_c.astype(f32) * b_c[..., None]).astype(cd)
        solve_cd = solve.astype(cd)
        W = jnp.einsum("bhij,bhjd->bhid", solve_cd, k_in, preferred_element_type=f32)
        U = jnp.einsum("bhij,bhjd->bhid", solve_cd, v_in, preferred_element_type=f32)
        S_cd = S.astype(cd)
        new_v = U - jnp.einsum("bhik,bhkv->bhiv", W.astype(cd), S_cd, preferred_element_type=f32)
        qk = jnp.einsum("bhid,bhjd->bhij", q_c, k_c, preferred_element_type=f32) * decay
        o = jnp.einsum("bhik,bhkv->bhiv", q_c, S_cd, preferred_element_type=f32)
        o = o * jnp.exp(gamma)[..., None] + jnp.einsum(
            "bhij,bhjv->bhiv", qk.astype(cd), new_v.astype(cd), preferred_element_type=f32
        )
        to_end = jnp.exp(gamma[..., -1:] - gamma)  # [B, H, L]
        S = S * jnp.exp(gamma[..., -1])[..., None, None] + jnp.einsum(
            "bhjk,bhjv->bhkv", (k_c.astype(f32) * to_end[..., None]).astype(cd), new_v.astype(cd),
            preferred_element_type=f32,
        )
        return S, o

    state, os_ = jax.lax.scan(
        one_chunk, state.astype(f32), (chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta))
    )
    o = jnp.swapaxes(jnp.moveaxis(os_, 0, 2).reshape(Bsz, H, T + pad, -1), 1, 2)
    return o[:, :T], state


def gated_delta_step(q, k, v, g, beta, state):
    """One column a row. ``q``, ``k`` [B, H, Dk]; ``v`` [B, H, Dv]; ``g``,
    ``beta`` [B, H] float32 (0 at a masked row); ``state`` [B, H, Dk, Dv].
    Returns ``(o [B, H, Dv] float32, new state float32)``; a masked row's
    state comes back as it was."""
    f32 = jnp.float32
    q, k, v, S = q.astype(f32), k.astype(f32), v.astype(f32), state.astype(f32)
    a = jnp.exp(g)
    # both reads from the state as it came in, in one pass over it
    held = a[..., None] * jnp.sum(S * k[..., None], axis=-2)  # (exp(g) S)^T k
    read = a[..., None] * jnp.sum(S * q[..., None], axis=-2)  # (exp(g) S)^T q
    delta = beta[..., None] * (v - held)
    new_state = S * a[..., None, None] + k[..., None] * delta[..., None, :]
    o = read + jnp.sum(k * q, axis=-1, keepdims=True) * delta
    return o, new_state


def _carried(cache_layer, fresh, batch: int, state_shape, tail_shape):
    """``(state, tail)`` a mixer starts from: zeros without a cache, else the
    layer's rows, with zeros for the rows ``fresh`` marks."""
    if cache_layer is None:
        return jnp.zeros((batch,) + state_shape, jnp.float32), jnp.zeros((batch,) + tail_shape, jnp.float32)
    state, tail = cache_layer["ssm_state"], cache_layer["conv_tail"]
    if fresh is not None:
        state = jnp.where(fresh[:, None, None, None], jnp.zeros((), state.dtype), state)
        tail = jnp.where(fresh[:, None, None], jnp.zeros((), tail.dtype), tail)
    return state, tail


def _carried_on(cache_layer, new_state, new_tail):
    """The layer's new cache dict in the types it is kept in, or None."""
    if cache_layer is None:
        return None
    return {
        "ssm_state": new_state.astype(cache_layer["ssm_state"].dtype),
        "conv_tail": new_tail.astype(cache_layer["conv_tail"].dtype),
    }


def gated_delta_mix(
    qkv, b_raw, a_raw, *, conv_weight, dt_bias, A_log,
    n_key_heads: int, n_value_heads: int, key_dim: int, value_dim: int, chunk: int = 64,
    mask=None, fresh=None, cache_layer: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """The mixer between its projections: ``qkv`` [B, T, 2 Hk Dk + Hv Dv]
    (``[q | k | v]``, zero where ``mask`` is), ``b_raw`` and ``a_raw``
    [B, T, Hv] -> ``(o [B, T, Hv, Dv] float32, the layer's new cache dict
    or None)``. Value head ``h`` reads key head ``h // (Hv / Hk)``.

    ``cache_layer`` is a state layer's dict (``ops/kv_cache.py``): the rows'
    ``ssm_state`` [B, Hv, Dk, Dv] and ``conv_tail`` [B, K - 1, C]; rows that
    ``fresh`` marks start from zeros instead. One column a row with a cache
    is :func:`gated_delta_step`, everything else :func:`gated_delta_chunk`
    (counted per traced call site in ``gdn/path{path=chunk|step}``)."""
    Bsz, T, width = qkv.shape
    Hk, Hv, Dk, Dv = n_key_heads, n_value_heads, key_dim, value_dim
    K = conv_weight.shape[0]
    f32 = jnp.float32
    mask = jnp.ones((Bsz, T), f32) if mask is None else mask.astype(f32)
    state, tail = _carried(cache_layer, fresh, Bsz, (Hv, Dk, Dv), (K - 1, width))
    # device-trace scope names are a contract (docs/observability.md)
    with jax.named_scope("gdn_conv"):
        conv, new_tail = causal_conv(qkv, conv_weight, None, tail, mask)
        conv = jax.nn.silu(conv) * mask[..., None]
        q = l2_normalise(conv[..., : Hk * Dk].reshape(Bsz, T, Hk, Dk)) * Dk**-0.5
        k = l2_normalise(conv[..., Hk * Dk : 2 * Hk * Dk].reshape(Bsz, T, Hk, Dk))
        v = conv[..., 2 * Hk * Dk :].reshape(Bsz, T, Hv, Dv)
        if Hv != Hk:
            q, k = (jnp.repeat(x, Hv // Hk, axis=2) for x in (q, k))
        q, k, v = (x.astype(qkv.dtype) for x in (q, k, v))
        beta = jax.nn.sigmoid(b_raw.astype(f32)) * mask[..., None]
        g = -jnp.exp(A_log.astype(f32)) * jax.nn.softplus(a_raw.astype(f32) + dt_bias.astype(f32))
        g = g * mask[..., None]
    step = cache_layer is not None and T == 1
    get_metrics().counter("gdn/path{path=%s}" % ("step" if step else "chunk")).inc()
    if step:
        with jax.named_scope("gdn_step"):
            o, new_state = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state)
            o = o[:, None]
    else:
        with jax.named_scope("gdn_chunk"):
            o, new_state = gated_delta_chunk(q, k, v, g, beta, state, chunk)
    return o, _carried_on(cache_layer, new_state, new_tail)


# -- a decay that is a vector a head (Kimi Delta Attention) ---------------- #

# the largest exponent the chunked form's two factors may carry together:
# exp(80) = 5.5e34, times a head's dot product of unit vectors, is in float32
KDA_EXPONENT_ROOM = 80.0


def kda_sub_chunk(lower_bound: float, chunk: int = 64) -> int:
    """The widest power of two of columns (at most ``chunk``) over which a
    gate held in ``(lower_bound, 0)`` keeps both factors of a score in
    float32: lifted and lowered about the middle row, each carries at most
    ``width / 2 * |lower_bound|`` and a masked entry the two together."""
    if not lower_bound < 0:
        raise ValueError(f"kda_lower_bound={lower_bound!r} bounds nothing (a negative number)")
    width = 1
    while 2 * width <= chunk and 2 * width * -lower_bound <= KDA_EXPONENT_ROOM:
        width *= 2
    return width


def kda_chunk(q, k, v, g, beta, state, chunk: int = 64, sub_chunk: int = 16):
    """The rule with a vector decay over ``T`` columns in chunks. ``q``,
    ``k`` [B, T, H, Dk] (normalised, ``q`` scaled) and ``v`` [B, T, H, Dv]
    in the compute dtype; ``g`` [B, T, H, Dk] float32 in ``(lower_bound,
    0]`` with ``sub_chunk = kda_sub_chunk(lower_bound)``, and ``beta`` [B, T,
    H] float32, both 0 at a masked column; ``state`` [B, H, Dk, Dv] float32.
    Returns ``(o [B, T, H, Dv] float32, final state float32)``."""
    Bsz, T, H, Dk = q.shape
    cd, f32 = q.dtype, jnp.float32
    L = 1 << max(min(chunk, T) - 1, 0).bit_length()  # the next power of two: the solve doubles its blocks
    sub = min(sub_chunk, L)
    if sub & (sub - 1):
        raise ValueError(f"kda_chunk takes a power of two of columns a row block; got {sub_chunk}")
    n = L // sub
    pad = (-T) % L
    if pad:  # columns with beta = 0 and g = 0: no-ops
        grow = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        q, k, v, g, beta = grow(q), grow(k), grow(v), grow(g), grow(beta)
    nc = (T + pad) // L
    # [nc, B, H, L, ...]: a head's chunk is one matrix
    chunks = lambda a: jnp.moveaxis(
        jnp.swapaxes(a, 1, 2).reshape((Bsz, H, nc, L) + a.shape[3:]), 2, 0
    )
    rows, cols = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    # [n, L, 1]: the columns a row block's scores reach (up to its own end)
    reached = (jnp.arange(L)[None, :] // sub <= jnp.arange(n)[:, None])[..., None]
    blocks = lambda a: a.reshape(a.shape[:2] + (n, sub) + a.shape[3:])

    def one_chunk(S, xs):
        q_c, k_c, v_c, g_c, b_c = xs  # [B, H, L, D*], [B, H, L]
        q32, k32 = q_c.astype(f32), k_c.astype(f32)
        gamma = jnp.cumsum(g_c, axis=-2)  # [B, H, L, Dk], from 0 a chunk
        middle = blocks(gamma)[:, :, :, (sub - 1) // 2]  # [B, H, n, Dk]: a row block's reference
        lift = jnp.exp(blocks(gamma) - middle[:, :, :, None])  # a block's own rows
        lower = jnp.where(
            reached,
            jnp.exp(jnp.where(reached, middle[:, :, :, None] - gamma[:, :, None], 0.0)),
            0.0,
        )  # [B, H, n, L, Dk]
        k_cols = (k32[:, :, None] * lower).astype(cd)
        scores = lambda x32: jnp.einsum(
            "bhsid,bhsjd->bhsij", (blocks(x32) * lift).astype(cd), k_cols, preferred_element_type=f32
        ).reshape(x32.shape[:2] + (L, L))
        A = jnp.where(rows > cols, b_c[..., None] * scores(k32), 0.0)
        qk = jnp.where(rows >= cols, scores(q32), 0.0)
        solve = unit_lower_inverse(A)
        from_start = jnp.exp(gamma)
        k_in = (k32 * from_start * b_c[..., None]).astype(cd)
        v_in = (v_c.astype(f32) * b_c[..., None]).astype(cd)
        solve_cd = solve.astype(cd)
        W = jnp.einsum("bhij,bhjd->bhid", solve_cd, k_in, preferred_element_type=f32)
        U = jnp.einsum("bhij,bhjd->bhid", solve_cd, v_in, preferred_element_type=f32)
        S_cd = S.astype(cd)
        new_v = U - jnp.einsum("bhik,bhkv->bhiv", W.astype(cd), S_cd, preferred_element_type=f32)
        o = jnp.einsum("bhik,bhkv->bhiv", (q32 * from_start).astype(cd), S_cd, preferred_element_type=f32)
        o = o + jnp.einsum("bhij,bhjv->bhiv", qk.astype(cd), new_v.astype(cd), preferred_element_type=f32)
        to_end = jnp.exp(gamma[..., -1:, :] - gamma)  # [B, H, L, Dk]
        S = S * from_start[..., -1, :, None] + jnp.einsum(
            "bhjk,bhjv->bhkv", (k32 * to_end).astype(cd), new_v.astype(cd), preferred_element_type=f32
        )
        return S, o

    state, os_ = jax.lax.scan(
        one_chunk, state.astype(f32), (chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta))
    )
    o = jnp.swapaxes(jnp.moveaxis(os_, 0, 2).reshape(Bsz, H, T + pad, -1), 1, 2)
    return o[:, :T], state


def kda_step(q, k, v, g, beta, state):
    """One column a row. ``q``, ``k``, ``g`` [B, H, Dk]; ``v`` [B, H, Dv];
    ``beta`` [B, H]; ``g`` and ``beta`` float32, 0 at a masked row;
    ``state`` [B, H, Dk, Dv]. Returns ``(o [B, H, Dv] float32, new state
    float32)``; a masked row's state comes back as it was."""
    f32 = jnp.float32
    q, k, v, S = q.astype(f32), k.astype(f32), v.astype(f32), state.astype(f32)
    a = jnp.exp(g)  # a state row's decay
    # both reads from the state as it came in, in one pass over it
    held = jnp.sum(S * (a * k)[..., None], axis=-2)  # (Diag(a) S)^T k
    read = jnp.sum(S * (a * q)[..., None], axis=-2)  # (Diag(a) S)^T q
    delta = beta[..., None] * (v - held)
    new_state = S * a[..., None] + k[..., None] * delta[..., None, :]
    o = read + jnp.sum(k * q, axis=-1, keepdims=True) * delta
    return o, new_state


def kda_mix(
    qkv, g_raw, b_raw, *, conv_weight, dt_bias, A_log, n_heads: int, key_dim: int,
    value_dim: int, lower_bound: float, chunk: int = 64,
    mask=None, fresh=None, cache_layer: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """The vector-decay mixer between its projections: ``qkv`` [B, T, 2 H
    Dk + H Dv] (``[q | k | v]``, zero where ``mask`` is), ``g_raw`` [B, T,
    H Dk] and ``b_raw`` [B, T, H] -> ``(o [B, T, H, Dv] float32, the
    layer's new cache dict or None)``. The gate is the family's bounded
    one, ``g = lower_bound * sigmoid(exp(A_log[h]) * (g_raw + dt_bias))``
    in ``(lower_bound, 0)`` a key channel, which is what lets
    :func:`kda_chunk` stay in float32.

    ``cache_layer``, ``fresh`` and the choice between :func:`kda_step` and
    :func:`kda_chunk` as :func:`gated_delta_mix` has them (counted per
    traced call site in ``kda/path{path=chunk|step}``; the gauge
    ``kda/sub_chunk`` is the row block a traced chunked form took)."""
    Bsz, T, width = qkv.shape
    H, Dk, Dv = n_heads, key_dim, value_dim
    K = conv_weight.shape[0]
    f32 = jnp.float32
    mask = jnp.ones((Bsz, T), f32) if mask is None else mask.astype(f32)
    state, tail = _carried(cache_layer, fresh, Bsz, (H, Dk, Dv), (K - 1, width))
    # device-trace scope names are a contract (docs/observability.md)
    with jax.named_scope("kda_conv"):
        conv, new_tail = causal_conv(qkv, conv_weight, None, tail, mask)
        conv = jax.nn.silu(conv) * mask[..., None]
        q = l2_normalise(conv[..., : H * Dk].reshape(Bsz, T, H, Dk)) * Dk**-0.5
        k = l2_normalise(conv[..., H * Dk : 2 * H * Dk].reshape(Bsz, T, H, Dk))
        v = conv[..., 2 * H * Dk :].reshape(Bsz, T, H, Dv)
        q, k, v = (x.astype(qkv.dtype) for x in (q, k, v))
    with jax.named_scope("kda_gate"):
        beta = jax.nn.sigmoid(b_raw.astype(f32)) * mask[..., None]
        rate = jnp.exp(A_log.astype(f32))[:, None]  # [H, 1]
        opened = jax.nn.sigmoid(rate * (g_raw.astype(f32) + dt_bias.astype(f32)).reshape(Bsz, T, H, Dk))
        g = lower_bound * opened * mask[..., None, None]
    step = cache_layer is not None and T == 1
    get_metrics().counter("kda/path{path=%s}" % ("step" if step else "chunk")).inc()
    if step:
        with jax.named_scope("kda_step"):
            o, new_state = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state)
            o = o[:, None]
    else:
        sub = kda_sub_chunk(lower_bound, chunk)
        get_metrics().gauge("kda/sub_chunk").set(sub)
        with jax.named_scope("kda_chunk"):
            o, new_state = kda_chunk(q, k, v, g, beta, state, chunk, sub)
    return o, _carried_on(cache_layer, new_state, new_tail)
