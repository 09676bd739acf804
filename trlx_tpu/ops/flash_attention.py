"""Pallas TPU flash attention (forward + custom-VJP backward).

The reference leaves attention to torch/HF kernels; here the training/prefill
hot op (SURVEY §2.9: "Pallas kernels only where XLA fusion is insufficient")
is a blocked online-softmax kernel so the [B, H, Q, K] score matrix never
round-trips HBM. The kernels use the canonical TPU structure: the key-tile
loop is the innermost *grid* dimension (TPU grids run sequentially), with
VMEM scratch accumulators persisting across those grid steps — initialized
at the first key tile, emitted at the last — so Mosaic double-buffers the
K/V tile DMAs against the MXU work and VMEM stays O(block² + block·D)
regardless of sequence length. ``causal=True`` masks inside the kernel and
predicates away fully-future tiles (half the MXU work) instead of
materializing a [Q, K] causal bias in HBM.

Backward recomputes scores per tile from the saved output/logsumexp (the
standard flash recomputation) in two kernels: dQ (key tiles innermost) and
dK/dV (query tiles innermost); ``delta = rowsum(dO · O)`` is folded into
both rather than materialized.

Under ``LONG_SEQ`` positions one tile covers both axes (``fitted_block``:
on the chip a grid step costs more than skipping a future tile saves), and
then nothing is carried between tiles: the forward is a plain softmax over
all keys and the backward is ONE kernel, both walking the query rows in a
loop inside the grid step (``_fwd_one_tile`` / ``_bwd_one_tile``).

Operand layout (:class:`Slabs`): q, k, v, the output, its cotangent and the
three gradients enter and leave the kernels as ``[B, T, H·Dh]``, the free
reshape of the ``[B, T, H, Dh]`` the models hold, so no transpose and no
activation-sized copy stands between a model's projections and the custom
calls. A grid step takes as many heads as fill whole lanes (``hb`` =
``lcm(Dh, 128) // Dh``: two heads of 64, one of 128 or 256), each head a
range of the block's lanes. Where ``hb`` does not divide the head count the
call holds (an odd number of 64-wide heads under ``tp``, heads of 80) the
same kernels take one head a step from ``[B·H, T, Dh]``, which costs the
transposes to heads-major and back.

Numerics match :func:`trlx_tpu.ops.attention.dot_product_attention`: logits
and softmax statistics in float32, the two MXU matmuls in the input dtype,
finite ``NEG_INF`` masking (fully-masked rows degrade to uniform weights
exactly like ``jax.nn.softmax`` over constant logits — under ``causal`` row
0 always sees one key, so this arises only for all-padding rows).

Bias support: any additive bias broadcastable to [B, H, Q, K]; size-1
batch / head / query / key dims stay size-1 in VMEM — the BlockSpec index
map pins them to block 0. The custom VJP returns a **zero** cotangent for
the bias operand: route learned biases (T5 relative position bias) through
the XLA path instead (``dot_product_attention(..., learned_bias=True)``).

TPU layout notes: row statistics (logsumexp) carry a broadcast 128-lane
trailing dim because Mosaic requires the last two dims of every block to be
(8, 128)-aligned or span the whole array.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trlx_tpu.ops.attention import NEG_INF

# Tiles fitted to the length, from one v5e chip (tools/attention_crossover.py;
# the tables are PERF.md §6 "PR 37", "PR 59" and "PR 61"; [16, T, 16 * 64]
# bf16, causal, forward + backward, ms a layer). Under LONG_SEQ one tile over
# the whole length wins at every length measured, because a grid step (~0.44
# us) costs more than skipping a future tile saves: T 560 as one 560 tile 1.37
# (its rows walked in two chunks of 288; 1.59 in five of 112, 1.17 as straight
# code), padded to one 640 tile 2.07, 256 x 256 over 768 4.7, 320 x 128 over
# 640 5.7, 128 x 128 6.6, and the 512 x 512 over 1024 that min(512, ceil8(T))
# used to choose 5.1 (XLA 3.7); T 592 as one tile 1.47 (two chunks of 304;
# 8.38 in the 37 chunks of 16 rows its divisors allowed, XLA 3.9). From
# LONG_SEQ 512 x 512 (T 1024: 4.7 against XLA's 11.0, and 3.7 as one 1024
# tile, whose VMEM grows with the length; the 1k-4k sweep before it), which
# bounds VMEM at any length.
LONG_SEQ = 1024
LONG_BLOCK = 512
LANES = 128  # trailing broadcast dim for row statistics


def fitted_block(length: int) -> int:
    """The tile along an axis of ``length`` positions: the whole axis,
    rounded up to the sublane multiple (16; 8 for up to 8 positions), under
    ``LONG_SEQ``; ``LONG_BLOCK`` from there."""
    return LONG_BLOCK if length >= LONG_SEQ else _one_tile(length)


def _one_tile(length: int) -> int:
    sub = 8 if length <= 8 else 16  # a bf16 tile holds 16 sublanes
    return -(-length // sub) * sub


# ---------------------------------------------------------------------------
# Operand layout
# ---------------------------------------------------------------------------


class Slabs(NamedTuple):
    """How q, k, v, o and their gradients lie in HBM for the kernels, read
    from the call's own ``[B, T, H, Dh]`` (:func:`operand_layout`): ``per_step``
    heads a grid step, side by side along the lanes of one ``[T, per_step *
    Dh]`` block.

    ``folded``: the operands are ``[B, T, H * Dh]``, the caller's array under
    a free reshape, and a block is ``per_step`` heads' columns of one row.
    Otherwise they are heads-major ``[B * H, T, Dh]`` (a transpose each way),
    a block one head."""

    heads: int
    head_dim: int
    per_step: int
    folded: bool

    @property
    def width(self) -> int:
        return self.per_step * self.head_dim

    def grid(self, operand) -> tuple:
        """(rows, blocks of heads) of a kernel operand."""
        rows = operand.shape[0] if self.folded else operand.shape[0] // self.heads
        return rows, self.heads // self.per_step

    def slabs(self, x):
        """``[B, T, H, Dh]`` as the kernels read it."""
        B, T, H, D = x.shape
        if self.folded:
            return x.reshape(B, T, H * D)
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, T, D)

    def slabs_of_heads_major(self, x):
        """``[B, H, T, Dh]`` as the kernels read it."""
        B, H, T, D = x.shape
        if self.folded:
            return jnp.transpose(x, (0, 2, 1, 3)).reshape(B, T, H * D)
        return x.reshape(B * H, T, D)

    def heads_last(self, y):
        """A kernel operand back as ``[B, T, H, Dh]``."""
        if self.folded:
            return y.reshape(y.shape[0], y.shape[1], self.heads, self.head_dim)
        return jnp.transpose(self.heads_major(y), (0, 2, 1, 3))

    def heads_major(self, y):
        """A kernel operand back as ``[B, H, T, Dh]``."""
        if self.folded:
            return jnp.transpose(self.heads_last(y), (0, 2, 1, 3))
        return y.reshape(-1, self.heads, y.shape[1], self.head_dim)

    def spec(self, block_t, tile=lambda *t: 0):
        """BlockSpec of one grid step's ``[block_t, width]`` block of an
        operand under a ``(B, H // per_step, *tiles)`` grid; ``tile`` picks
        the block along T from the tile indices."""
        if self.folded:
            def index(b, g, *t):
                return b, tile(*t), g
        else:
            def index(b, g, *t):
                return b * self.heads + g, tile(*t), 0
        return pl.BlockSpec(
            (1, block_t, self.width), index, memory_space=pltpu.VMEM
        )

    def lse_spec(self, block_q, tile=lambda *t: 0):
        """... of the ``[B, H, Q, LANES]`` logsumexp: the step's heads."""
        return pl.BlockSpec(
            (1, self.per_step, block_q, LANES),
            lambda b, g, *t: (b, g, tile(*t), 0),
            memory_space=pltpu.VMEM,
        )

    def bias_spec(self, bias_shape, block_q, block_k,
                  q_tile=lambda *t: 0, k_tile=lambda *t: 0):
        """... of a ``[b?, h?, Q?, K?]`` bias. Size-1 bias dims stay size-1
        (index pinned to 0) so broadcast biases never materialize at full
        rank in VMEM; a per-head bias hands the step its heads' planes."""
        b, h, q, k = bias_shape
        block = (
            1,
            self.per_step if h > 1 else 1,
            block_q if q > 1 else 1,
            block_k if k > 1 else 1,
        )

        def index(bi, gi, *t):
            return (
                bi if b > 1 else 0,
                gi if h > 1 else 0,
                q_tile(*t) if q > 1 else 0,
                k_tile(*t) if k > 1 else 0,
            )

        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)


def operand_layout(heads: int, head_dim: int) -> Slabs:
    """The layout a call of ``heads`` heads of ``head_dim`` takes, from those
    two numbers alone: folded, as many heads a step as fill whole lanes,
    where that count divides the heads; else one head a step, heads-major."""
    per_step = math.lcm(head_dim, LANES) // head_dim
    if heads % per_step:
        return Slabs(heads, head_dim, 1, False)
    return Slabs(heads, head_dim, per_step, True)


def _head_lanes(x, j, hb):
    """``x`` [rows, hb * Dh] with every lane outside head ``j``'s zeroed:
    one operand of a product over the lanes so masked makes it the head's
    own product (the MXU contracts 128 lanes a pass whether 64 of them are
    zeros or absent), and as the operand that brings a product's columns it
    leaves zeros in the other heads' columns."""
    if hb == 1:
        return x
    D = x.shape[-1] // hb
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where((lane >= j * D) & (lane < (j + 1) * D), x, jnp.zeros_like(x))


def _join_heads(parts, shape):
    """One ``shape`` = [rows, hb * Dh] array holding ``parts[j]`` (that shape,
    or [rows, 1]) in head ``j``'s lanes."""
    if len(parts) == 1:
        return jnp.broadcast_to(parts[0], shape)
    D = shape[-1] // len(parts)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    out = jnp.broadcast_to(parts[-1], shape)
    for j in range(len(parts) - 2, -1, -1):
        out = jnp.where(lane < (j + 1) * D, parts[j], out)
    return out


def _read_bias(bias_ref, j, rows=slice(None)):
    """Load head ``j``'s (possibly size-1-broadcast) [q?, k?] bias as f32;
    ``rows``: the query rows to take where the bias has them."""
    if bias_ref is None:
        return None
    jb = j if bias_ref.shape[1] > 1 else 0
    if bias_ref.shape[2] > 1:
        return bias_ref[0, jb, rows, :].astype(jnp.float32)
    return bias_ref[0, jb].astype(jnp.float32)


def _causal_mask(q_lo, tq, k_lo, tk):
    """[tq, tk] additive mask: query q_lo+i sees key k_lo+j iff j+k_lo <= i+q_lo."""
    q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    return jnp.where(k_pos <= q_pos, 0.0, NEG_INF).astype(jnp.float32)


def _over_lanes(a, b):
    """a [m, W] x b [n, W] -> [m, n] float32."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _over_rows(a, b):
    """a [r, m] x b [r, n] -> [m, n] float32."""
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _times(a, b):
    """a [m, r] x b [r, n] -> [m, n] float32."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _masked_scores(q_j, k, bias, scale, causal, q_lo, k_lo):
    """[TQ, TK] float32 scores of one head (``q_j``: its query rows with the
    other heads' lanes zeroed) against the keys ``k``, under its bias and the
    causal mask of queries from ``q_lo`` over keys from ``k_lo``."""
    s = _over_lanes(q_j, k) * scale
    if bias is not None:
        s = s + bias
    if causal:
        s = s + _causal_mask(q_lo, q_j.shape[0], k_lo, k.shape[0])
    return s


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, scale, block_q, block_k, hb, has_bias, causal):
    if has_bias:
        q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
        bias_ref = None

    qi = pl.program_id(2)
    ki = pl.program_id(3)
    n_k = pl.num_programs(3)
    q_lo = qi * block_q
    k_lo = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, -jnp.inf)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    live = (k_lo <= q_lo + block_q - 1) if causal else True

    @pl.when(live)
    def _tile():
        q = q_ref[0]  # [TQ, W]
        k_blk = k_ref[0]  # [TK, W]
        v_blk = v_ref[0]
        alphas, pvs = [], []
        for j in range(hb):
            s = _masked_scores(
                _head_lanes(q, j, hb), k_blk, _read_bias(bias_ref, j), scale,
                causal, q_lo, k_lo,
            )  # [TQ, TK]
            m = m_s[j, :, 0:1]
            blk_max = jnp.max(s, axis=-1, keepdims=True)
            new_m = jnp.maximum(m, blk_max)
            alpha = jnp.exp(m - new_m)
            p = jnp.exp(s - new_m)
            l_s[j, :, 0:1] = l_s[j, :, 0:1] * alpha + jnp.sum(
                p, axis=-1, keepdims=True
            )
            m_s[j, :, 0:1] = new_m
            alphas.append(alpha)
            pvs.append(_times(p.astype(v_blk.dtype), v_blk))
        acc_s[:] = acc_s[:] * _join_heads(alphas, acc_s.shape) + _join_heads(
            pvs, acc_s.shape
        )

    @pl.when(ki == n_k - 1)
    def _emit():
        l_safe = [jnp.maximum(l_s[j, :, 0:1], 1e-30) for j in range(hb)]
        o_ref[0] = (acc_s[:] / _join_heads(l_safe, acc_s.shape)).astype(
            o_ref.dtype
        )
        for j in range(hb):
            lse_ref[0, j] = jnp.broadcast_to(
                m_s[j, :, 0:1] + jnp.log(l_safe[j]), (block_q, lse_ref.shape[-1])
            )


def _fwd(q, k, v, bias, *, lay, scale, block_q, block_k, causal, interpret):
    """q/k/v: ``lay``'s slabs, padded to tiles; returns (o, lse
    [B, H, Qp, LANES])."""
    Qp, Kp = q.shape[1], k.shape[1]
    if (block_q, block_k) == (Qp, Kp):
        return _fwd_one_tile(
            q, k, v, bias, lay=lay, scale=scale, causal=causal,
            interpret=interpret,
        )
    B, G = lay.grid(q)
    hb = lay.per_step
    of_q = lambda qi, ki: qi
    of_k = lambda qi, ki: ki

    q_spec = lay.spec(block_q, of_q)
    kv_spec = lay.spec(block_k, of_k)
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(lay.bias_spec(bias.shape, block_q, block_k, of_q, of_k))
        args.append(bias)

    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
            hb=hb, has_bias=bias is not None, causal=causal,
        ),
        grid=(B, G, Qp // block_q, Kp // block_k),
        in_specs=in_specs,
        out_specs=[q_spec, lay.lse_spec(block_q, of_q)],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, lay.heads, Qp, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb, block_q, LANES), jnp.float32),  # running max
            pltpu.VMEM((hb, block_q, LANES), jnp.float32),  # running sum
            pltpu.VMEM((block_q, lay.width), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)
    return o, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _dq_kernel(*refs, scale, block_q, block_k, hb, has_bias, causal):
    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref, lse_ref, dq_ref,
         dq_s) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, dq_s = refs
        bias_ref = None

    qi = pl.program_id(2)
    ki = pl.program_id(3)
    n_k = pl.num_programs(3)
    q_lo = qi * block_q
    k_lo = ki * block_k

    @pl.when(ki == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    live = (k_lo <= q_lo + block_q - 1) if causal else True

    @pl.when(live)
    def _tile():
        q = q_ref[0]
        k_blk = k_ref[0]
        v32 = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        dqs = []
        for j in range(hb):
            do_j = _head_lanes(do, j, hb)
            delta = jnp.sum(do_j * o, axis=-1, keepdims=True)  # [TQ, 1]
            s = _masked_scores(
                _head_lanes(q, j, hb), k_blk, _read_bias(bias_ref, j), scale,
                causal, q_lo, k_lo,
            )
            p = jnp.exp(s - lse_ref[0, j, :, 0:1])  # [TQ, TK]
            ds = p * (_over_lanes(do_j, v32) - delta)
            dqs.append(_times(ds.astype(k_blk.dtype), k_blk))
        dq_s[:] = dq_s[:] + _join_heads(dqs, dq_s.shape)

    @pl.when(ki == n_k - 1)
    def _emit():
        dq_ref[0] = (dq_s[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, block_q, block_k, hb, has_bias, causal):
    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref, lse_ref,
         dk_ref, dv_ref, dk_s, dv_s) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dk_ref, dv_ref,
         dk_s, dv_s) = refs
        bias_ref = None

    ki = pl.program_id(2)
    qi = pl.program_id(3)
    n_q = pl.num_programs(3)
    k_lo = ki * block_k
    q_lo = qi * block_q

    @pl.when(qi == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    # skip q tiles whose last query is before the first key
    live = (q_lo + block_q - 1 >= k_lo) if causal else True

    @pl.when(live)
    def _tile():
        k_blk = k_ref[0]  # [TK, W]
        v32 = v_ref[0].astype(jnp.float32)
        q_blk = q_ref[0]  # [TQ, W]
        do = do_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        for j in range(hb):
            q_j = _head_lanes(q_blk, j, hb)
            do_j = _head_lanes(do, j, hb)
            delta = jnp.sum(do_j * o, axis=-1, keepdims=True)
            s = _masked_scores(
                q_j, k_blk, _read_bias(bias_ref, j), scale, causal, q_lo, k_lo
            )
            p = jnp.exp(s - lse_ref[0, j, :, 0:1])
            # a masked operand's product is zero in the other heads' columns
            dv_s[:] = dv_s[:] + _over_rows(p, do_j)
            ds = p * (_over_lanes(do_j, v32) - delta)  # [TQ, TK]
            dk_s[:] = dk_s[:] + _over_rows(ds, q_j.astype(jnp.float32))

    @pl.when(qi == n_q - 1)
    def _emit():
        dk_ref[0] = (dk_s[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _bwd(q, k, v, bias, o, lse, do, *, lay, scale, block_q, block_k, causal,
         interpret):
    Qp, Kp = q.shape[1], k.shape[1]
    if (block_q, block_k) == (Qp, Kp):
        return _bwd_one_tile(
            q, k, v, bias, o, lse, do, lay=lay, scale=scale, causal=causal,
            interpret=interpret,
        )
    B, G = lay.grid(q)
    n_q, n_k = Qp // block_q, Kp // block_k
    kernel = dict(
        scale=scale, block_q=block_q, block_k=block_k, hb=lay.per_step,
        has_bias=bias is not None, causal=causal,
    )
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    )

    def operands(of_q, of_k):
        q_tile, kv_tile = lay.spec(block_q, of_q), lay.spec(block_k, of_k)
        in_specs = [q_tile, kv_tile, kv_tile]
        args = [q, k, v]
        if bias is not None:
            in_specs.append(
                lay.bias_spec(bias.shape, block_q, block_k, of_q, of_k)
            )
            args.append(bias)
        in_specs += [q_tile, q_tile, lay.lse_spec(block_q, of_q)]
        args += [do, o, lse]
        return in_specs, args, q_tile, kv_tile

    # dQ: grid (B, G, nQ, nK) — K innermost, dq accumulates across it
    in_specs, args, q_tile, _ = operands(lambda qi, ki: qi, lambda qi, ki: ki)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kernel),
        grid=(B, G, n_q, n_k),
        in_specs=in_specs,
        out_specs=q_tile,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, lay.width), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(*args)

    # dK/dV: grid (B, G, nK, nQ) — Q innermost, dk/dv accumulate across it
    in_specs, args, _, kv_tile = operands(lambda ki, qi: qi, lambda ki, qi: ki)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kernel),
        grid=(B, G, n_k, n_q),
        in_specs=in_specs,
        out_specs=[kv_tile, kv_tile],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, lay.width), jnp.float32),
            pltpu.VMEM((block_k, lay.width), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(*args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# One tile over both axes (every length under LONG_SEQ): a loop over query rows
# ---------------------------------------------------------------------------
#
# With the whole key axis in the tile there is nothing to carry from tile to
# tile: a grid step is one row's block of heads, and inside it a ``fori_loop``
# walks the query rows ``ROW_CHUNK`` at a time, each chunk against all keys, a
# head after the other: a plain softmax forward, and one backward kernel that
# recomputes the weights once for dQ, dK and dV together (dK and dV accumulate
# in VMEM across the chunks). The loop keeps Mosaic's code for a [560, 560]
# tile small (a forward call site weighs 33 KB in a compiled program against
# 358 KB unrolled: PERF.md §6, PR 37) and is 3-10% faster than the general
# kernels at one tile.

# Rows a chunk: the stationary operand of each product is [Kp, 128] of keys or
# values, loaded into the MXU once a chunk, so the more query rows stream past
# it the better the MXU is used, and Mosaic unrolls a chunk's [rows, Kp]
# passes, so the code grows with them: the cap is what the code's size allows.
# One v5e chip, tools/attention_crossover.py --fitted --row-caps (PR 61, call
# p61A), [16, T, 16, 64] forward + backward and [64, T, 16, 64] forward, ms a
# layer, and the grad program of tests/test_tpu_compile.py serialized for a
# described v5e (24 of them are most of a train step's cache entry, PERF.md
# §7 (24)):
#   T 560   5 x 112 rows  1.59  2.49  0.92 MB   (its largest divisor: PR 59)
#           3 x 192       1.48  2.05  1.04 MB   (a cap of 256)
#           2 x 288       1.37  1.83  1.16 MB   <- the last 16 rows twice
#           1 x 560       1.17  1.56  1.40 MB   (straight code)
#   T 592   37 x 16 rows  8.38   -              (its only divisor: 16 x 37)
#           3 x 208       1.63   -
#           2 x 304       1.47   -    1.08 MB
#           1 x 592       1.24   -
#   T 512   4 x 128 1.21 (PR 59)  2 x 256 1.00 1.18 MB  1 x 512 0.91 1.37 MB
ROW_CHUNK = 320


def _row_chunk(rows: int) -> int:
    """Query rows a loop iteration takes, fitted to the tile and no divisor
    of it: all of them up to ``ROW_CHUNK``, else the tile over the fewest
    iterations that keep a chunk at most ``ROW_CHUNK``, rounded up to 16 (a
    bf16 tile's sublanes; ``_one_tile`` pads to that)."""
    if rows <= ROW_CHUNK:
        return rows
    n = -(-rows // ROW_CHUNK)
    return -(-rows // (16 * n)) * 16


def one_tile_loop_rows(q_len: int, k_len: int) -> Optional[int]:
    """Query rows a loop iteration takes in a call of these lengths, which
    takes the one-tile kernels; ``None`` from ``LONG_SEQ`` on either axis,
    where the tiled kernels take it."""
    if max(q_len, k_len) >= LONG_SEQ:
        return None
    return _row_chunk(_one_tile(q_len))


def _for_row_chunks(n_rows, chunk, body):
    """``body(r0, fresh)`` for each chunk's first row; one chunk is straight
    code. Where ``chunk`` does not divide ``n_rows`` the last chunk is
    aligned to the tile's end and revisits rows of the one before it:
    ``fresh`` is then the [chunk, 1] mask of the rows no later chunk takes
    again (a sum over the chunks counts those alone, and a plain store ends
    up holding each row's last, fresh, value); ``None`` where every row is."""
    if n_rows == chunk:
        body(0, None)
        return
    n = -(-n_rows // chunk)
    last = n_rows - chunk  # the last chunk's first row

    def step(i, carry):
        if n * chunk == n_rows:
            body(pl.multiple_of(i * chunk, chunk), None)
            return carry
        r0 = pl.multiple_of(jnp.minimum(i * chunk, last), 16)
        # the next chunk's first row; the tile's end after the last
        end = jnp.where(i + 1 < n, jnp.minimum((i + 1) * chunk, last), n_rows)
        row = r0 + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        body(r0, row < end)
        return carry

    jax.lax.fori_loop(0, n, step, 0)


def _fwd_one_tile_kernel(*refs, scale, rows, hb, has_bias, causal):
    if has_bias:
        q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        bias_ref = None

    def chunk(r0, fresh):  # a revisited row is written its own value again
        at = pl.ds(r0, rows)
        q = q_ref[0, at, :]
        k_all = k_ref[0]
        v_all = v_ref[0]
        outs = []
        for j in range(hb):
            s = _masked_scores(
                _head_lanes(q, j, hb), k_all, _read_bias(bias_ref, j, at),
                scale, causal, r0, 0,
            )  # [rows, Kp]
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l_safe = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
            outs.append(_times(p.astype(v_all.dtype), v_all) / l_safe)
            lse_ref[0, j, at, :] = jnp.broadcast_to(
                m + jnp.log(l_safe), (rows, lse_ref.shape[-1])
            )
        o_ref[0, at, :] = _join_heads(outs, outs[0].shape).astype(o_ref.dtype)

    _for_row_chunks(q_ref.shape[1], rows, chunk)


def _bwd_one_tile_kernel(*refs, scale, rows, hb, has_bias, causal):
    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref, lse_ref,
         dq_ref, dk_ref, dv_ref, dk_s, dv_s) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         dq_ref, dk_ref, dv_ref, dk_s, dv_s) = refs
        bias_ref = None

    dk_s[:] = jnp.zeros_like(dk_s)
    dv_s[:] = jnp.zeros_like(dv_s)

    def chunk(r0, fresh):
        at = pl.ds(r0, rows)
        q = q_ref[0, at, :]
        do = do_ref[0, at, :].astype(jnp.float32)
        if fresh is not None:
            # a row the next chunk takes again counts there: with its
            # cotangent zeroed here its delta and ds are zero too, so it adds
            # nothing to dk and dv, and the zeros this chunk stores in its dq
            # are overwritten by the chunk that owns it
            do = jnp.where(fresh, do, 0.0)
        o = o_ref[0, at, :].astype(jnp.float32)
        k_all = k_ref[0]
        v32 = v_ref[0].astype(jnp.float32)
        dqs = []
        for j in range(hb):
            q_j = _head_lanes(q, j, hb)
            do_j = _head_lanes(do, j, hb)
            delta = jnp.sum(do_j * o, axis=-1, keepdims=True)  # [rows, 1]
            s = _masked_scores(
                q_j, k_all, _read_bias(bias_ref, j, at), scale, causal, r0, 0
            )
            p = jnp.exp(s - lse_ref[0, j, at, 0:1])  # [rows, Kp]
            # a masked operand's product is zero in the other heads' columns
            dv_s[:] = dv_s[:] + _over_rows(p, do_j)
            ds = p * (_over_lanes(do_j, v32) - delta)
            dk_s[:] = dk_s[:] + _over_rows(ds, q_j.astype(jnp.float32))
            dqs.append(_times(ds.astype(k_all.dtype), k_all))
        dq_ref[0, at, :] = (_join_heads(dqs, dqs[0].shape) * scale).astype(
            dq_ref.dtype
        )

    _for_row_chunks(q_ref.shape[1], rows, chunk)
    dk_ref[0] = (dk_s[:] * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _one_tile_call(kernel, q, bias, *, lay, scale, causal, interpret, **call):
    return pl.pallas_call(
        functools.partial(
            kernel, scale=scale, rows=_row_chunk(q.shape[1]), hb=lay.per_step,
            has_bias=bias is not None, causal=causal,
        ),
        grid=lay.grid(q),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        **call,
    )


def _one_tile_bias(lay, bias):
    if bias is None:
        return [], []
    return [bias], [lay.bias_spec(bias.shape, bias.shape[2], bias.shape[3])]


def _fwd_one_tile(q, k, v, bias, *, lay, scale, causal, interpret):
    Qp, Kp = q.shape[1], k.shape[1]
    B, _ = lay.grid(q)
    lse = jax.ShapeDtypeStruct((B, lay.heads, Qp, LANES), jnp.float32)
    bias_arg, bias_spec = _one_tile_bias(lay, bias)
    return _one_tile_call(
        _fwd_one_tile_kernel, q, bias, lay=lay, scale=scale, causal=causal,
        interpret=interpret,
        in_specs=[lay.spec(Qp), lay.spec(Kp), lay.spec(Kp)] + bias_spec,
        out_specs=[lay.spec(Qp), lay.lse_spec(Qp)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), lse],
    )(q, k, v, *bias_arg)


def _bwd_one_tile(q, k, v, bias, o, lse, do, *, lay, scale, causal, interpret):
    Qp, Kp = q.shape[1], k.shape[1]
    q_slab, kv_slab = lay.spec(Qp), lay.spec(Kp)
    bias_arg, bias_spec = _one_tile_bias(lay, bias)
    return _one_tile_call(
        _bwd_one_tile_kernel, q, bias, lay=lay, scale=scale, causal=causal,
        interpret=interpret,
        in_specs=[q_slab, kv_slab, kv_slab] + bias_spec
        + [q_slab, q_slab, lay.lse_spec(Qp)],
        out_specs=[q_slab, kv_slab, kv_slab],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (q, k, v)],
        scratch_shapes=[
            pltpu.VMEM((Kp, lay.width), jnp.float32),
            pltpu.VMEM((Kp, lay.width), jnp.float32),
        ],
    )(q, k, v, *bias_arg, do, o, lse)


# ---------------------------------------------------------------------------
# custom_vjp wrapper over the padded slabs
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, bias, lay, scale, block_q, block_k, causal, interpret):
    o, _ = _fwd(
        q, k, v, bias, lay=lay, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, interpret=interpret,
    )
    return o


def _flash_fwd(q, k, v, bias, lay, scale, block_q, block_k, causal, interpret):
    o, lse = _fwd(
        q, k, v, bias, lay=lay, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, interpret=interpret,
    )
    return o, (q, k, v, bias, o, lse)


def _flash_bwd(lay, scale, block_q, block_k, causal, interpret, res, do):
    q, k, v, bias, o, lse = res
    dq, dk, dv = _bwd(
        q, k, v, bias, o, lse, do, lay=lay, scale=scale, block_q=block_q,
        block_k=block_k, causal=causal, interpret=interpret,
    )
    dbias = None if bias is None else jnp.zeros_like(bias)
    return dq, dk, dv, dbias


_flash.defvjp(_flash_fwd, _flash_bwd)


def _prep_block_inputs(q, k, v, bias, block_q, block_k, scale):
    """Shared prologue for the kernel entry points: the operands' layout
    (:func:`operand_layout`), tile sizes fitted to the lengths
    (:func:`fitted_block`; a caller's own are shrunk to one tile over the
    axis), the slabs padded to tiles along T, bias padding/masking, default
    1/sqrt(D) scale."""
    _, Q, H, D = q.shape
    K = k.shape[1]
    lay = operand_layout(H, D)
    if scale is None:
        scale = float(1.0 / (D ** 0.5))
    block_q = fitted_block(Q) if block_q is None else min(block_q, _one_tile(Q))
    block_k = fitted_block(K) if block_k is None else min(block_k, _one_tile(K))
    qs = _pad_to(lay.slabs(q), 1, block_q)
    ks = _pad_to(lay.slabs(k), 1, block_k)
    vs = _pad_to(lay.slabs(v), 1, block_k)
    bias = _prepare_bias(bias, ks.shape[1], K, block_q, block_k)
    return lay, qs, ks, vs, bias, block_q, block_k, scale


def flash_block_fwd(q, k, v, bias, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False):
    """Single-block forward returning the logsumexp — the building block for
    cross-block softmax combination (ring attention over the sp axis).

    q [B, Tq, H, D], k/v [B, Tk, H, D], bias broadcastable to
    [B, H, Tq, Tk]; returns (o [B, H, Tq, D] softmax-normalized in q.dtype,
    lse [B, H, Tq] f32). No causal flag: ring blocks carry positions in the
    bias. Not differentiable by itself — ring's custom VJP calls
    :func:`flash_block_bwd`.
    """
    Q = q.shape[1]
    lay, qs, ks, vs, bias, block_q, block_k, scale = _prep_block_inputs(
        q, k, v, bias, block_q, block_k, scale
    )
    o, lse = _fwd(
        qs, ks, vs, bias, lay=lay, scale=scale, block_q=block_q,
        block_k=block_k, causal=False, interpret=interpret,
    )
    return lay.heads_major(o[:, :Q]), lse[:, :, :Q, 0]


def flash_block_bwd(q, k, v, bias, o, lse, do, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False):
    """Single-block backward against an *external* (combined) logsumexp.

    Layouts: q/k/v [B, T, H, D]; o/do [B, H, Tq, D]; lse [B, H, Tq].
    Returns (dq [B, Tq, H, D], dk, dv [B, Tk, H, D]) in f32. Because ``lse``
    may come from combining many blocks, p = exp(s - lse) are the *global*
    softmax weights — exactly what the flash backward recomputes. Inputs are
    upcast to f32 so ring-accumulated gradients match the XLA block math
    bit-for-bit regardless of the activations' dtype.
    """
    B, Q, H, D = q.shape
    K = k.shape[1]
    lay, qs, ks, vs, bias, block_q, block_k, scale = _prep_block_inputs(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        bias, block_q, block_k, scale,
    )
    Qp = qs.shape[1]
    op = _pad_to(lay.slabs_of_heads_major(o.astype(jnp.float32)), 1, block_q)
    dop = _pad_to(lay.slabs_of_heads_major(do.astype(jnp.float32)), 1, block_q)
    lse_p = jnp.broadcast_to(
        _pad_to(lse, 2, block_q)[..., None], (B, H, Qp, LANES)
    )
    dq, dk, dv = _bwd(
        qs, ks, vs, bias, op, lse_p, dop, lay=lay, scale=scale,
        block_q=block_q, block_k=block_k, causal=False, interpret=interpret,
    )
    return (
        lay.heads_last(dq[:, :Q]),
        lay.heads_last(dk[:, :K]),
        lay.heads_last(dv[:, :K]),
    )


def _prepare_bias(bias, Kp, K, block_q, block_k):
    """Pad a [b?, h?, Q?, K?] bias to tile multiples and mask padded keys."""
    if bias is not None:
        if bias.ndim != 4:
            raise ValueError(f"bias must be rank-4, got {bias.shape}")
        bias = bias.astype(jnp.float32)
        if bias.shape[3] > 1:
            bias = _pad_to(bias, 3, block_k)
        if bias.shape[2] > 1:
            bias = _pad_to(bias, 2, block_q)
    if Kp != K:
        pad_bias = jnp.where(
            jnp.arange(Kp)[None, None, None, :] < K, 0.0, NEG_INF
        ).astype(jnp.float32)
        bias = pad_bias if bias is None else bias + pad_bias
    return bias


def _pad_to(x, axis, multiple):
    rem = -x.shape[axis] % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def flash_attention(
    q: jax.Array,  # [B, Q, H, D]
    k: jax.Array,  # [B, K, H, D]
    v: jax.Array,  # [B, K, H, D]
    bias: Optional[jax.Array] = None,  # broadcastable to [B, H, Q, K]
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention over the framework's [B, T, H, D] layout.

    Pads Q/K to tile multiples (padded keys masked via bias, padded query
    rows dropped) and dispatches the custom-VJP pallas kernels on the
    operands folded to [B, T, H * D], a reshape and no copy (heads-major
    through a transpose where :func:`operand_layout` cannot fold them).
    ``causal=True`` masks in-kernel and skips future key tiles — pass it
    instead of a causal bias. Gradient does NOT flow to ``bias`` (see module
    docstring).

    ``causal`` assumes query position i is absolute position i (offset 0) —
    the training / prefill case. For cache decode at an offset, pass an
    explicit bias.

    The kernels are compiled by Mosaic for the TPU. ``interpret=True`` runs
    them through the Pallas interpreter instead (any backend, slow) — a
    caller asks for it by name (the CPU tests do); it is never inferred
    from the platform.
    """
    Q = q.shape[1]
    lay, qs, ks, vs, bias, block_q, block_k, scale = _prep_block_inputs(
        q, k, v, bias, block_q, block_k, None
    )
    out = _flash(qs, ks, vs, bias, lay, scale, block_q, block_k, causal, interpret)
    return lay.heads_last(out[:, :Q]).astype(q.dtype)
