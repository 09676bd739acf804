"""State-space mixing (Mamba-2, Dao & Gu 2024): a layer that keeps a
fixed-size state and a convolution tail a sequence, and no keys.

The recurrence, per head ``h`` with a state ``S`` of ``[P, N]`` (head size
x state size), over the columns ``t`` of a sequence:

    a_t = exp(dt_t * A_h)                        A_h < 0, dt_t > 0
    S_t = a_t * S_{t-1} + dt_t * x_t B_t^T       x_t [P], B_t [N]
    y_t = S_t C_t + D_h * x_t                    C_t [N]

``x``, ``B`` and ``C`` come out of a depthwise causal convolution of width
``K`` over the projected columns (:func:`causal_conv`), whose last ``K - 1``
inputs are the **tail** a sequence carries beside its state. ``B`` and ``C``
are shared by the heads of a **group**: all heads read one pair (``[.., N]``,
one group), or ``G`` groups of ``H / G`` consecutive heads read a pair each
(``[.., G, N]``: head ``h`` reads group ``h // (H / G)``). One group is
computed as it always was, the same operations in the same order.

Two ways to compute it, both exact:

- :func:`ssd_scan`, for a call of many columns (a forward without a cache,
  a prefill, a chunk of one): columns in chunks of ``chunk``; inside a
  chunk the outputs are one masked product with the **decay matrix**
  ``exp(cs_t - cs_s)`` (``cs`` the running sum of ``dt * A`` in the chunk),
  between chunks the state is carried: ``2 L`` multiply-adds a column and
  head pair where the plain recurrence has a chain ``T`` long.
- :func:`ssd_step`, for one column a sequence (a decode step): the
  recurrence as written, one read and one write of the state.

``dt``, the running log-decay, every ``exp``, the state and the gated norm
are float32; the two large products of a chunk take their operands in the
compute dtype and accumulate in float32, as every projection does.

**Masked columns are no-ops.** A column whose ``mask`` is 0 (left padding,
an idle or finished slot's step, a chunk that lies before a row's first
token) gets ``dt = 0``: ``a = 1`` and nothing is added, so the state is
left bit for bit; the tail is kept where a call holds no valid column.
A call's valid columns are a suffix of it (left padding), and whatever
lies before a row's first valid column is zero; :func:`call_columns`
reads both from the cache mask and says which rows **start fresh**, with
no valid column before this call: their state and tail are taken as zero
whatever the slot held, so a recycled slot never reads its predecessor's.

Imports nothing above ``ops/``.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.telemetry import get_metrics


def call_columns(attention_mask, cache_index, batch: int, q_len: int):
    """``(mask [B, T], fresh [B])`` of a cached call that writes ``q_len``
    columns a row from ``cache_index`` (a scalar, or ``[B]`` with rows
    parked past the mask's width: the engine's idle sentinel):
    ``mask`` is ``attention_mask`` (over cache columns) at the columns
    being written, 0 past its width; ``fresh`` marks the rows with no
    valid column before ``cache_index``."""
    base = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (batch,))
    if attention_mask is None:
        return jnp.ones((batch, q_len), jnp.float32), base == 0
    width = attention_mask.shape[-1]
    pos = base[:, None] + jnp.arange(q_len, dtype=jnp.int32)[None, :]
    here = jnp.take_along_axis(attention_mask, jnp.clip(pos, 0, width - 1), axis=1)
    mask = jnp.where(pos < width, here, 0).astype(jnp.float32)
    before = jnp.arange(width, dtype=jnp.int32)[None, :] < base[:, None]
    fresh = jnp.sum(jnp.where(before, attention_mask, 0), axis=-1) == 0
    return mask, fresh


def causal_conv(x, weight, bias, tail, mask):
    """Depthwise causal convolution over columns. ``x`` [B, T, C];
    ``weight`` [K, C] (``weight[K - 1]`` multiplies the column itself);
    ``bias`` [C] or None; ``tail`` [B, K - 1, C], the inputs before this
    call; ``mask`` [B, T]. Returns ``(out [B, T, C] float32, new tail
    [B, K - 1, C] float32)``: the tail is the last ``K - 1`` inputs, masked
    columns as zeros, and the old tail where the call has no valid
    column."""
    K, T = weight.shape[0], x.shape[1]
    x32 = x.astype(jnp.float32) * mask[..., None]
    padded = jnp.concatenate([tail.astype(jnp.float32), x32], axis=1)
    w = weight.astype(jnp.float32)
    out = sum(padded[:, k : k + T] * w[k] for k in range(K))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    any_valid = jnp.sum(mask, axis=-1) > 0
    new_tail = jnp.where(any_valid[:, None, None], padded[:, T:], padded[:, : K - 1])
    return out, new_tail


def ssd_scan(x, dt, A, B, C, D, mask, state, chunk: int):
    """The recurrence over ``T`` columns in chunks. ``x`` [B, T, H, P] and
    ``B``, ``C`` [B, T, N] (one group) or [B, T, G, N] in the compute dtype;
    ``dt`` [B, T, H] float32 (after the softplus); ``A`` (negative), ``D``
    [H]; ``mask`` [B, T]; ``state`` [B, H, P, N] float32. Returns ``(y
    [B, T, H, P] float32, final state float32)``."""
    Bsz, T, H, P = x.shape
    grouped = B.ndim == 4
    G = B.shape[2] if grouped else 1
    by_group = lambda a: a.reshape(a.shape[:1] + (G, H // G) + a.shape[2:])  # [B, H, ..] -> [B, G, H/G, ..]
    cd = x.dtype
    L = min(int(chunk), T)
    pad = (-T) % L
    dt = dt * mask[..., None]
    skip = D.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    if pad:  # columns with dt = 0: no-ops
        grow = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        x, dt, B, C = grow(x), grow(dt), grow(B), grow(C)
    nc = (T + pad) // L
    chunks = lambda a: jnp.moveaxis(a.reshape((Bsz, nc, L) + a.shape[2:]), 1, 0)
    lower = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]  # s <= t

    def one_chunk(S, xs):
        x_c, dt_c, B_c, C_c = xs
        cs = jnp.cumsum(jnp.swapaxes(dt_c, 1, 2) * A.astype(jnp.float32)[None, :, None], axis=-1)  # [B, H, L]
        dtx = x_c.astype(jnp.float32) * dt_c[..., None]  # [B, L, H, P]
        # inside the chunk: y_t += sum_{s<=t} exp(cs_t - cs_s) (C_t . B_s) dt_s x_s
        if grouped:  # a group's scores, under each of its heads' decays
            scores = jnp.einsum("btgn,bsgn->bgts", C_c, B_c, preferred_element_type=jnp.float32)
        else:
            scores = jnp.einsum("btn,bsn->bts", C_c, B_c, preferred_element_type=jnp.float32)
        span = jnp.where(lower, cs[..., :, None] - cs[..., None, :], 0.0)
        decay = jnp.where(lower, jnp.exp(span), 0.0)  # [B, H, t, s]
        weights = (
            (scores[:, :, None] * by_group(decay)).reshape(decay.shape) if grouped
            else scores[:, None] * decay
        )
        y = jnp.einsum(
            "bhts,bshp->bthp", weights.astype(cd), dtx.astype(cd),
            preferred_element_type=jnp.float32,
        )
        # from the chunks before: y_t += exp(cs_t) * S C_t (a head's state against its group's C)
        if grouped:
            carried = jnp.einsum(
                "bghpn,btgn->btghp", by_group(S.astype(cd)), C_c, preferred_element_type=jnp.float32
            ).reshape(Bsz, L, H, P)
        else:
            carried = jnp.einsum(
                "bhpn,btn->bthp", S.astype(cd), C_c, preferred_element_type=jnp.float32
            )
        y = y + carried * jnp.swapaxes(jnp.exp(cs), 1, 2)[..., None]
        # the state the chunk leaves
        to_end = jnp.swapaxes(jnp.exp(cs[..., -1:] - cs), 1, 2)  # [B, L, H]
        kept = S * jnp.exp(cs[..., -1])[..., None, None]
        leaves = (dtx * to_end[..., None]).astype(cd)
        if grouped:
            added = jnp.einsum(
                "bsghp,bsgn->bghpn", leaves.reshape(Bsz, L, G, H // G, P), B_c,
                preferred_element_type=jnp.float32,
            ).reshape(S.shape)
        else:
            added = jnp.einsum("bshp,bsn->bhpn", leaves, B_c, preferred_element_type=jnp.float32)
        return kept + added, y

    state, ys = jax.lax.scan(
        one_chunk, state.astype(jnp.float32), (chunks(x), chunks(dt), chunks(B), chunks(C))
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, T + pad, H, P)[:, :T]
    return y + skip, state


def ssd_step(x, dt, A, B, C, D, mask, state):
    """One column a row. ``x`` [B, H, P]; ``dt`` [B, H] float32; ``B``,
    ``C`` [B, N] (one group) or [B, G, N]; ``mask`` [B]; ``state``
    [B, H, P, N]. Returns ``(y [B, H, P] float32, new state float32)``; a
    masked row's state comes back as it was."""
    f32 = jnp.float32
    dt = dt * mask[:, None]
    x32 = x.astype(f32)
    a = jnp.exp(dt * A.astype(f32)[None, :])
    if B.ndim == 3:  # a head reads its group's pair: the state viewed [B, G, H/G, P, N]
        Bsz, H, P = x.shape
        G = B.shape[1]
        S = state.astype(f32).reshape(Bsz, G, H // G, P, -1) * a.reshape(Bsz, G, H // G, 1, 1) + (
            (dt[..., None] * x32).reshape(Bsz, G, H // G, P, 1) * B.astype(f32)[:, :, None, None, :]
        )
        y = jnp.sum(S * C.astype(f32)[:, :, None, None, :], axis=-1).reshape(Bsz, H, P)
        return y + D.astype(f32)[None, :, None] * x32, S.reshape(state.shape)
    S = state.astype(f32) * a[..., None, None] + (
        (dt[..., None] * x32)[..., None] * B.astype(f32)[:, None, None, :]
    )
    y = jnp.sum(S * C.astype(f32)[:, None, None, :], axis=-1)
    return y + D.astype(f32)[None, :, None] * x32, S


def gated_rms_norm(y, gate, weight, eps: float, n_groups: int = 1):
    """``rms(y * silu(gate)) * weight``, float32: the mean square over the
    last axis, or over each of its ``n_groups`` equal runs of channels by
    itself (a group's heads are normalised together and no others)."""
    g = y.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    if n_groups == 1:
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    else:
        runs = g.reshape(g.shape[:-1] + (n_groups, g.shape[-1] // n_groups))
        g = (runs * jax.lax.rsqrt(jnp.mean(runs * runs, axis=-1, keepdims=True) + eps)).reshape(g.shape)
    return g * weight.astype(jnp.float32)


def mamba2_mix(
    xBC, dt_raw, *, conv_weight, conv_bias, dt_bias, A_log, D,
    n_heads: int, head_dim: int, d_state: int, chunk: int,
    mask=None, fresh=None, cache_layer: Optional[Dict[str, jax.Array]] = None,
    n_groups: int = 1,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """The mixer between its two projections: ``xBC`` [B, T, H*P + 2GN]
    (``[x | B | C]``, ``B`` and ``C`` a group after another) and ``dt_raw``
    [B, T, H] (the projected columns, zero where ``mask`` is) -> ``(y
    [B, T, H*P] float32, the layer's new cache dict or None)``.

    ``cache_layer`` is a state layer's dict (``ops/kv_cache.py``): the rows'
    ``ssm_state`` [B, H, P, N] and ``conv_tail`` [B, K - 1, C]; rows that
    ``fresh`` marks start from zeros instead. One column a row with a cache
    is :func:`ssd_step`, everything else :func:`ssd_scan` (counted per
    traced call site in ``ssm/path{path=scan|step}``)."""
    Bsz, T, width = xBC.shape
    H, P, N, G = n_heads, head_dim, d_state, n_groups
    if H % G or width != H * P + 2 * G * N:
        raise ValueError(f"{width} channels are not {H} heads of {P} and {G} groups of B and C of {N}, {H} / {G} heads each")
    K = conv_weight.shape[0]
    f32 = jnp.float32
    if mask is None:
        mask = jnp.ones((Bsz, T), f32)
    mask = mask.astype(f32)
    if cache_layer is None:
        state = jnp.zeros((Bsz, H, P, N), f32)
        tail = jnp.zeros((Bsz, K - 1, width), f32)
    else:
        state, tail = cache_layer["ssm_state"], cache_layer["conv_tail"]
        if fresh is not None:
            state = jnp.where(fresh[:, None, None, None], jnp.zeros((), state.dtype), state)
            tail = jnp.where(fresh[:, None, None], jnp.zeros((), tail.dtype), tail)
    # device-trace scope names are a contract (docs/observability.md)
    with jax.named_scope("ssm_conv"):
        conv, new_tail = causal_conv(xBC, conv_weight, conv_bias, tail, mask)
        conv = (jax.nn.silu(conv) * mask[..., None]).astype(xBC.dtype)
        x = conv[..., : H * P].reshape(Bsz, T, H, P)
        B_, C_ = conv[..., H * P : H * P + G * N], conv[..., H * P + G * N :]
        if G > 1:
            B_, C_ = B_.reshape(Bsz, T, G, N), C_.reshape(Bsz, T, G, N)
        dt = jax.nn.softplus(dt_raw.astype(f32) + dt_bias.astype(f32))
        A = -jnp.exp(A_log.astype(f32))
    step = cache_layer is not None and T == 1
    get_metrics().counter("ssm/path{path=%s}" % ("step" if step else "scan")).inc()
    get_metrics().gauge("ssm/groups").set(G)
    if step:
        with jax.named_scope("ssm_step"):
            y, new_state = ssd_step(x[:, 0], dt[:, 0], A, B_[:, 0], C_[:, 0], D, mask[:, 0], state)
            y = y[:, None]
    else:
        with jax.named_scope("ssm_scan"):
            y, new_state = ssd_scan(x, dt, A, B_, C_, D, mask, state, chunk)
    new_layer = None
    if cache_layer is not None:
        new_layer = {
            "ssm_state": new_state.astype(cache_layer["ssm_state"].dtype),
            "conv_tail": new_tail.astype(cache_layer["conv_tail"].dtype),
        }
    return y.reshape(Bsz, T, H * P), new_layer
