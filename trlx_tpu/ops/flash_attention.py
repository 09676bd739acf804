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
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trlx_tpu.ops.attention import NEG_INF

# Tiles fitted to the length, from one v5e chip (tools/attention_crossover.py;
# the tables are PERF.md §6 "PR 37"; [16, T, 16, 64] bf16, causal, forward +
# backward, ms a layer). Under LONG_SEQ one tile over the whole length wins at
# every length measured, because a grid step (~0.44 us) costs more than
# skipping a future tile saves: T 560 as one 560 tile 1.99 (2.06 with the
# kernels the tiles below still use), padded to one 640 tile 2.39, 256 x 256
# over 768 6.6, 320 x 128 over 640 7.3, 128 x 128 10.5, and the 512 x 512 over
# 1024 that min(512, ceil8(T)) used to choose 5.9 (XLA 3.6). From LONG_SEQ
# 512 x 512 (T 1024: 5.9 against XLA's 10.7; the 1k-4k sweep before it),
# which bounds VMEM at any length.
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


def _bias_spec(bias_shape, block_q, block_k, q_axis, k_axis):
    """BlockSpec for a [b?, h?, Q?, K?] bias under a (B, H, t1, t2) grid.

    ``q_axis``/``k_axis`` name which grid axis (2 or 3) tiles Q and K.
    Size-1 bias dims stay size-1 (index pinned to 0) so broadcast biases
    never materialize at full rank in VMEM.
    """
    b, h, q, k = bias_shape
    block = (1, 1, block_q if q > 1 else 1, block_k if k > 1 else 1)

    def index(bi, hi, t1, t2):
        ts = {2: t1, 3: t2}
        return (
            bi if b > 1 else 0,
            hi if h > 1 else 0,
            ts[q_axis] if q > 1 else 0,
            ts[k_axis] if k > 1 else 0,
        )

    return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)


def _read_bias(bias_ref):
    """Load the (possibly size-1-broadcast) [q?, k?] bias block as f32."""
    if bias_ref is None:
        return None
    return bias_ref[0, 0].astype(jnp.float32)


def _causal_mask(q_lo, tq, k_lo, tk):
    """[tq, tk] additive mask: query q_lo+i sees key k_lo+j iff j+k_lo <= i+q_lo."""
    q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    return jnp.where(k_pos <= q_pos, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, scale, block_q, block_k, has_bias, causal):
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
        q = q_ref[0, 0]  # [TQ, D]
        k_blk = k_ref[0, 0]  # [TK, D]
        v_blk = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [TQ, TK]
        b = _read_bias(bias_ref)
        if b is not None:
            s = s + b
        if causal:
            s = s + _causal_mask(q_lo, block_q, k_lo, block_k)
        m = m_s[:, 0:1]
        blk_max = jnp.max(s, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, blk_max)
        alpha = jnp.exp(m - new_m)
        p = jnp.exp(s - new_m)
        l_s[:, 0:1] = l_s[:, 0:1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_s[:, 0:1] = new_m

    @pl.when(ki == n_k - 1)
    def _emit():
        m = m_s[:, 0:1]
        l_safe = jnp.maximum(l_s[:, 0:1], 1e-30)
        o_ref[0, 0] = (acc_s[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(
            m + jnp.log(l_safe), (block_q, lse_ref.shape[-1])
        )


def _fwd(q, k, v, bias, *, scale, block_q, block_k, causal, interpret):
    """q/k/v: [B, H, Qp, D] / [B, H, Kp, D]; returns (o, lse)."""
    B, H, Qp, D = q.shape
    Kp = k.shape[2]
    if (block_q, block_k) == (Qp, Kp):
        return _fwd_one_tile(
            q, k, v, bias, scale=scale, causal=causal, interpret=interpret
        )
    grid = (B, H, Qp // block_q, Kp // block_k)

    q_spec = pl.BlockSpec(
        (1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0),
        memory_space=pltpu.VMEM,
    )
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, h, qi, ki: (b, h, ki, 0),
        memory_space=pltpu.VMEM,
    )
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(_bias_spec(bias.shape, block_q, block_k, 2, 3))
        args.append(bias)

    out_specs = [
        q_spec,
        pl.BlockSpec(
            (1, 1, block_q, LANES), lambda b, h, qi, ki: (b, h, qi, 0),
            memory_space=pltpu.VMEM,
        ),
    ]
    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
            has_bias=bias is not None, causal=causal,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Qp, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Qp, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running sum
            pltpu.VMEM((block_q, D), jnp.float32),      # output accumulator
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


def _dq_kernel(*refs, scale, block_q, block_k, has_bias, causal):
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
        q = q_ref[0, 0]
        k_blk = k_ref[0, 0]
        v_blk = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0:1]  # [TQ, 1]
        delta = jnp.sum(do * o, axis=-1, keepdims=True)  # [TQ, 1]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        b = _read_bias(bias_ref)
        if b is not None:
            s = s + b
        if causal:
            s = s + _causal_mask(q_lo, block_q, k_lo, block_k)
        p = jnp.exp(s - lse)  # [TQ, TK]
        dp = jax.lax.dot_general(
            do, v_blk.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        dq_s[:] = dq_s[:] + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == n_k - 1)
    def _emit():
        dq_ref[0, 0] = (dq_s[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, block_q, block_k, has_bias, causal):
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
        k_blk = k_ref[0, 0]  # [TK, D]
        v32 = v_ref[0, 0].astype(jnp.float32)
        q_blk = q_ref[0, 0]  # [TQ, D]
        do = do_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0:1]
        delta = jnp.sum(do * o, axis=-1, keepdims=True)
        s = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [TQ, TK]
        b = _read_bias(bias_ref)
        if b is not None:
            s = s + b
        if causal:
            s = s + _causal_mask(q_lo, block_q, k_lo, block_k)
        p = jnp.exp(s - lse)
        dv_s[:] = dv_s[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v32, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)  # [TQ, TK]
        dk_s[:] = dk_s[:] + jax.lax.dot_general(
            ds, q_blk.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == n_q - 1)
    def _emit():
        dk_ref[0, 0] = (dk_s[:] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[:].astype(dv_ref.dtype)


def _bwd(q, k, v, bias, o, lse, do, *, scale, block_q, block_k, causal,
         interpret):
    B, H, Qp, D = q.shape
    Kp = k.shape[2]
    if (block_q, block_k) == (Qp, Kp):
        return _bwd_one_tile(
            q, k, v, bias, o, lse, do, scale=scale, causal=causal,
            interpret=interpret,
        )
    n_q, n_k = Qp // block_q, Kp // block_k

    q_tile_qk = pl.BlockSpec(
        (1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0),
        memory_space=pltpu.VMEM,
    )
    kv_tile_qk = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, h, qi, ki: (b, h, ki, 0),
        memory_space=pltpu.VMEM,
    )
    lse_tile_qk = pl.BlockSpec(
        (1, 1, block_q, LANES), lambda b, h, qi, ki: (b, h, qi, 0),
        memory_space=pltpu.VMEM,
    )

    # dQ: grid (B, H, nQ, nK) — K innermost, dq accumulates across it
    in_specs = [q_tile_qk, kv_tile_qk, kv_tile_qk]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(_bias_spec(bias.shape, block_q, block_k, 2, 3))
        args.append(bias)
    in_specs += [q_tile_qk, q_tile_qk, lse_tile_qk]
    args += [do, o, lse]
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, block_q=block_q, block_k=block_k,
            has_bias=bias is not None, causal=causal,
        ),
        grid=(B, H, n_q, n_k),
        in_specs=in_specs,
        out_specs=q_tile_qk,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)

    # dK/dV: grid (B, H, nK, nQ) — Q innermost, dk/dv accumulate across it
    q_tile_kq = pl.BlockSpec(
        (1, 1, block_q, D), lambda b, h, ki, qi: (b, h, qi, 0),
        memory_space=pltpu.VMEM,
    )
    kv_tile_kq = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0),
        memory_space=pltpu.VMEM,
    )
    lse_tile_kq = pl.BlockSpec(
        (1, 1, block_q, LANES), lambda b, h, ki, qi: (b, h, qi, 0),
        memory_space=pltpu.VMEM,
    )
    in_specs = [q_tile_kq, kv_tile_kq, kv_tile_kq]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(_bias_spec(bias.shape, block_q, block_k, 3, 2))
        args.append(bias)
    in_specs += [q_tile_kq, q_tile_kq, lse_tile_kq]
    args += [do, o, lse]
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, block_q=block_q, block_k=block_k,
            has_bias=bias is not None, causal=causal,
        ),
        grid=(B, H, n_k, n_q),
        in_specs=in_specs,
        out_specs=[kv_tile_kq, kv_tile_kq],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# One tile over both axes (every length under LONG_SEQ): a loop over query rows
# ---------------------------------------------------------------------------
#
# With the whole key axis in the tile there is nothing to carry from tile to
# tile: a grid step is one (row, head), and inside it a ``fori_loop`` walks
# the query rows ``ROW_CHUNK`` at a time, each chunk against all keys: a
# plain softmax forward, and one backward kernel that recomputes the weights
# once for dQ, dK and dV together (dK and dV accumulate in VMEM across the
# chunks). The loop keeps Mosaic's code for a [560, 560] tile small (a
# forward call site weighs 33 KB in a compiled program against 358 KB
# unrolled: PERF.md §6, PR 37) and is 3-10% faster than the general kernels
# at one tile.

ROW_CHUNK = 128


def _row_chunk(rows: int) -> int:
    """Query rows a loop iteration takes: all of them up to ``ROW_CHUNK``,
    else the largest divisor of ``rows`` that is a multiple of 16 (a bf16
    tile's sublanes; ``_one_tile`` pads to that) and at most ``ROW_CHUNK``."""
    if rows <= ROW_CHUNK:
        return rows
    return max(c for c in range(16, ROW_CHUNK + 1, 16) if rows % c == 0)


def _for_row_chunks(n_rows, chunk, body):
    """``body(r0)`` for each chunk's first row; one chunk is straight code."""
    if n_rows == chunk:
        body(0)
        return

    def step(i, carry):
        body(pl.multiple_of(i * chunk, chunk))
        return carry

    jax.lax.fori_loop(0, n_rows // chunk, step, 0)


def _chunk_scores(q, k_ref, bias_ref, r0, rows, scale, causal):
    """[rows, Kp] float32 masked scores of query rows ``r0..r0+rows``."""
    k_all = k_ref[0, 0]
    s = jax.lax.dot_general(
        q, k_all, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if bias_ref is not None:
        if bias_ref.shape[2] > 1:
            b = bias_ref[0, 0, pl.ds(r0, rows), :]
        else:
            b = bias_ref[0, 0]
        s = s + b.astype(jnp.float32)
    if causal:
        s = s + _causal_mask(r0, rows, 0, k_all.shape[0])
    return s


def _fwd_one_tile_kernel(*refs, scale, rows, has_bias, causal):
    if has_bias:
        q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        bias_ref = None

    def chunk(r0):
        at = pl.ds(r0, rows)
        s = _chunk_scores(
            q_ref[0, 0, at, :], k_ref, bias_ref, r0, rows, scale, causal
        )
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l_safe = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        v_all = v_ref[0, 0]
        acc = jax.lax.dot_general(
            p.astype(v_all.dtype), v_all, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[0, 0, at, :] = (acc / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0, at, :] = jnp.broadcast_to(
            m + jnp.log(l_safe), (rows, lse_ref.shape[-1])
        )

    _for_row_chunks(q_ref.shape[2], rows, chunk)


def _bwd_one_tile_kernel(*refs, scale, rows, has_bias, causal):
    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref, lse_ref,
         dq_ref, dk_ref, dv_ref, dk_s, dv_s) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         dq_ref, dk_ref, dv_ref, dk_s, dv_s) = refs
        bias_ref = None

    dk_s[:] = jnp.zeros_like(dk_s)
    dv_s[:] = jnp.zeros_like(dv_s)

    def chunk(r0):
        at = pl.ds(r0, rows)
        q = q_ref[0, 0, at, :]
        do = do_ref[0, 0, at, :].astype(jnp.float32)
        o = o_ref[0, 0, at, :].astype(jnp.float32)
        delta = jnp.sum(do * o, axis=-1, keepdims=True)  # [rows, 1]
        s = _chunk_scores(q, k_ref, bias_ref, r0, rows, scale, causal)
        p = jnp.exp(s - lse_ref[0, 0, at, 0:1])  # [rows, Kp]
        dv_s[:] = dv_s[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v_ref[0, 0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        dk_s[:] = dk_s[:] + jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        k_all = k_ref[0, 0]
        dq = jax.lax.dot_general(
            ds.astype(k_all.dtype), k_all, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_ref[0, 0, at, :] = (dq * scale).astype(dq_ref.dtype)

    _for_row_chunks(q_ref.shape[2], rows, chunk)
    dk_ref[0, 0] = (dk_s[:] * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv_s[:].astype(dv_ref.dtype)


def _whole(shape):
    """BlockSpec of one (row, head)'s whole [T, X] slab under a (B, H) grid;
    size-1 batch and head dims (a broadcast bias) pin to block 0."""
    b, h = shape[0], shape[1]
    return pl.BlockSpec(
        (1, 1) + tuple(shape[2:]),
        lambda bi, hi: (bi if b > 1 else 0, hi if h > 1 else 0, 0, 0),
        memory_space=pltpu.VMEM,
    )


def _one_tile_call(kernel, q, bias, *, scale, causal, interpret, **call):
    return pl.pallas_call(
        functools.partial(
            kernel, scale=scale, rows=_row_chunk(q.shape[2]),
            has_bias=bias is not None, causal=causal,
        ),
        grid=q.shape[:2],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        **call,
    )


def _fwd_one_tile(q, k, v, bias, *, scale, causal, interpret):
    B, H, Qp, _ = q.shape
    lse = jax.ShapeDtypeStruct((B, H, Qp, LANES), jnp.float32)
    args = [q, k, v] + ([bias] if bias is not None else [])
    return _one_tile_call(
        _fwd_one_tile_kernel, q, bias, scale=scale, causal=causal,
        interpret=interpret,
        in_specs=[_whole(a.shape) for a in args],
        out_specs=[_whole(q.shape), _whole(lse.shape)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), lse],
    )(*args)


def _bwd_one_tile(q, k, v, bias, o, lse, do, *, scale, causal, interpret):
    D = q.shape[-1]
    args = [q, k, v] + ([bias] if bias is not None else []) + [do, o, lse]
    return _one_tile_call(
        _bwd_one_tile_kernel, q, bias, scale=scale, causal=causal,
        interpret=interpret,
        in_specs=[_whole(a.shape) for a in args],
        out_specs=[_whole(q.shape), _whole(k.shape), _whole(v.shape)],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (q, k, v)],
        scratch_shapes=[
            pltpu.VMEM((k.shape[2], D), jnp.float32),
            pltpu.VMEM((k.shape[2], D), jnp.float32),
        ],
    )(*args)


# ---------------------------------------------------------------------------
# custom_vjp wrapper over padded [B, H, Q, D] layout
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, bias, scale, block_q, block_k, causal, interpret):
    o, _ = _fwd(
        q, k, v, bias, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, interpret=interpret,
    )
    return o


def _flash_fwd(q, k, v, bias, scale, block_q, block_k, causal, interpret):
    o, lse = _fwd(
        q, k, v, bias, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, interpret=interpret,
    )
    return o, (q, k, v, bias, o, lse)


def _flash_bwd(scale, block_q, block_k, causal, interpret, res, do):
    q, k, v, bias, o, lse = res
    dq, dk, dv = _bwd(
        q, k, v, bias, o, lse, do, scale=scale, block_q=block_q,
        block_k=block_k, causal=causal, interpret=interpret,
    )
    dbias = None if bias is None else jnp.zeros_like(bias)
    return dq, dk, dv, dbias


_flash.defvjp(_flash_fwd, _flash_bwd)


def _prep_block_inputs(q, k, v, bias, block_q, block_k, scale):
    """Shared prologue for the kernel entry points: tile sizes fitted to
    the lengths (:func:`fitted_block`; a caller's own are shrunk to one
    tile over the axis), [B, H, T, D] transpose + tile padding, bias
    padding/masking, default 1/sqrt(D) scale."""
    D = q.shape[-1]
    if scale is None:
        scale = float(1.0 / (D ** 0.5))
    Q, K = q.shape[1], k.shape[1]
    block_q = fitted_block(Q) if block_q is None else min(block_q, _one_tile(Q))
    block_k = fitted_block(K) if block_k is None else min(block_k, _one_tile(K))
    qt, _ = _pad_to(jnp.transpose(q, (0, 2, 1, 3)), 2, block_q)
    kt, _ = _pad_to(jnp.transpose(k, (0, 2, 1, 3)), 2, block_k)
    vt, _ = _pad_to(jnp.transpose(v, (0, 2, 1, 3)), 2, block_k)
    bias = _prepare_bias(bias, kt.shape[2], K, block_q, block_k)
    return qt, kt, vt, bias, block_q, block_k, scale


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
    qt, kt, vt, bias, block_q, block_k, scale = _prep_block_inputs(
        q, k, v, bias, block_q, block_k, scale
    )
    o, lse = _fwd(
        qt, kt, vt, bias, scale=scale, block_q=block_q, block_k=block_k,
        causal=False, interpret=interpret,
    )
    return o[:, :, :Q, :], lse[:, :, :Q, 0]


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
    qt, kt, vt, bias, block_q, block_k, scale = _prep_block_inputs(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        bias, block_q, block_k, scale,
    )
    Qp = qt.shape[2]
    op, _ = _pad_to(o.astype(jnp.float32), 2, block_q)
    dop, _ = _pad_to(do.astype(jnp.float32), 2, block_q)
    lse_p = jnp.broadcast_to(
        _pad_to(lse, 2, block_q)[0][..., None], (B, H, Qp, LANES)
    )
    dq, dk, dv = _bwd(
        qt, kt, vt, bias, op, lse_p, dop, scale=scale, block_q=block_q,
        block_k=block_k, causal=False, interpret=interpret,
    )
    dq = jnp.transpose(dq[:, :, :Q, :], (0, 2, 1, 3))
    dk = jnp.transpose(dk[:, :, :K, :], (0, 2, 1, 3))
    dv = jnp.transpose(dv[:, :, :K, :], (0, 2, 1, 3))
    return dq, dk, dv


def _prepare_bias(bias, Kp, K, block_q, block_k):
    """Pad a [b?, h?, Q?, K?] bias to tile multiples and mask padded keys."""
    if bias is not None:
        if bias.ndim != 4:
            raise ValueError(f"bias must be rank-4, got {bias.shape}")
        bias = bias.astype(jnp.float32)
        if bias.shape[3] > 1:
            bias, _ = _pad_to(bias, 3, block_k)
        if bias.shape[2] > 1:
            bias, _ = _pad_to(bias, 2, block_q)
    if Kp != K:
        pad_bias = jnp.where(
            jnp.arange(Kp)[None, None, None, :] < K, 0.0, NEG_INF
        ).astype(jnp.float32)
        bias = pad_bias if bias is None else bias + pad_bias
    return bias


def _pad_to(x, axis, multiple):
    size = x.shape[axis]
    rem = -size % multiple
    if rem == 0:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad), size


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
    rows dropped), transposes to [B, H, T, D] for lane-aligned tiles, and
    dispatches the custom-VJP pallas kernels. ``causal=True`` masks in-kernel
    and skips future key tiles — pass it instead of a causal bias. Gradient
    does NOT flow to ``bias`` (see module docstring).

    ``causal`` assumes query position i is absolute position i (offset 0) —
    the training / prefill case. For cache decode at an offset, pass an
    explicit bias.

    The kernels are compiled by Mosaic for the TPU. ``interpret=True`` runs
    them through the Pallas interpreter instead (any backend, slow) — a
    caller asks for it by name (the CPU tests do); it is never inferred
    from the platform.
    """
    Q = q.shape[1]
    qt, kt, vt, bias, block_q, block_k, scale = _prep_block_inputs(
        q, k, v, bias, block_q, block_k, None
    )
    out = _flash(qt, kt, vt, bias, scale, block_q, block_k, causal, interpret)
    out = out[:, :, :Q, :]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
