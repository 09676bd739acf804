"""Pallas TPU read of a paged pool of keys and values by one new position a
slot: only the chunks of the pool, as stored, in which some position can
carry a weight are fetched.

The continuous engine's decode step attends one query row a slot over that
slot's whole pool (``ops/attention.py::decode_attention``, ``path=paged``).
Most of the pool carries weight exactly 0: a finished or idle slot's row is
dropped by the engine, a left-padded prompt's padding and the positions past
the row's depth sit at ``NEG_INF`` in the bias. XLA reads them all the same
(one fused pass over ``[B, C, H_kv, Dh]`` for the scores, one for the
values), and may stage a whole pool through ``S(1)`` to do it. Here the
pools stay in HBM (``memory_space=pl.ANY``) and the kernel copies, slot by
live slot, the chunks ``ops/kv_cache.py::live_chunks`` lists, double
buffered, into VMEM.

The arithmetic is :func:`~trlx_tpu.ops.attention.dot_product_attention`'s
but for the order of float32 sums. A chunk arrives as rows ``[chunk * H_kv,
Dh]`` (row ``c * H_kv + kv``: a bitcast of the pool, whose positions are
major to its heads). All query heads multiply all of a chunk's rows on the
MXU, ``[H, chunk * H_kv]`` float32, and a select keeps, for query head
``h``, the rows of its own KV head ``h // G``; every other column is
``NEG_INF`` like a masked position, so its weight is exactly 0 and it adds
exact zeros to every sum. Scores stay in VMEM for the slot's live chunks;
the softmax is whole, float32, normalised (max, exp, sum, divide: no
running max, nothing rescaled); the weights are rounded to the pool's dtype
where they meet the values, and the values' product accumulates in float32.
A slot with no live chunk is never visited and its output is zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trlx_tpu.ops.kv_cache import LiveChunks

# device-trace scope names are a contract (docs/observability.md)
SCOPE = "paged_live_read"


def _kernel(
    slots_ref,  # [B] the live slots, in order; SMEM
    n_slots_ref,  # [1] how many of them
    counts_ref,  # [B] live chunks a slot
    chunks_ref,  # [B * n_chunks] a slot's live chunks, in order
    q_ref,  # [B, H, D] VMEM
    bias_ref,  # [B, C * R] float32 VMEM: the stored-order bias, a value a pool row
    k_hbm,  # [B, C * R, D] HBM
    v_hbm,
    out_ref,  # [B, H, D] VMEM
    buf,  # [2, rows, D]: a chunk of K or of V
    scores,  # [n_chunks, H, rows] float32: the slot's scores, then its weights
    sems,
    *,
    rows: int,
    n_chunks: int,
    kv_rows: int,
    group: int,
    scale: float,
    floor: float,
):
    H, D = q_ref.shape[1:]
    out_ref[...] = jnp.zeros_like(out_ref)
    n_slots = n_slots_ref[0]
    # query head h reads the rows of KV head h // G: column c * R + kv of a
    # chunk's scores is kept where kv == h // G
    head = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 0) // group
    mine = head == jax.lax.broadcasted_iota(jnp.int32, (H, rows), 1) % kv_rows

    def chunk_rows(b, i):
        """The pool rows of slot ``b``'s ``i``-th live chunk."""
        return pl.ds(pl.multiple_of(chunks_ref[b * n_chunks + i] * rows, rows), rows)

    def copy(pool, b, i, t):
        """Item ``t`` of the kernel's sequence of copies: slot ``b``'s
        ``i``-th live chunk of ``pool`` into buffer ``t % 2``."""
        return pltpu.make_async_copy(
            pool.at[b, chunk_rows(b, i)], buf.at[t % 2], sems.at[t % 2]
        )

    @pl.when(n_slots > 0)
    def _():
        copy(k_hbm, slots_ref[0], 0, 0).start()

    def slot(s, t0):
        """One live slot: its K chunks, the softmax, its V chunks. ``t0``
        counts the copies made before it; every copy is started one item
        ahead of its use, the next slot's first included."""
        b = slots_ref[s]
        n = counts_ref[b]
        q = q_ref[b]

        def keys(i, m):
            t = t0 + i
            copy(k_hbm, b, i, t).wait()

            @pl.when(i + 1 < n)
            def _():
                copy(k_hbm, b, i + 1, t + 1).start()

            @pl.when(i + 1 == n)
            def _():
                copy(v_hbm, b, 0, t + 1).start()

            s_ = jax.lax.dot_general(
                q, buf[t % 2], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            s_ = s_ + bias_ref[pl.ds(b, 1), chunk_rows(b, i)]
            s_ = jnp.where(mine, s_, floor)
            scores[i] = s_
            return jnp.maximum(m, jnp.max(s_, axis=1, keepdims=True))

        m = jax.lax.fori_loop(0, n, keys, jnp.full((H, 1), -jnp.inf, jnp.float32))

        def exps(i, total):
            p = jnp.exp(scores[i] - m)
            scores[i] = p
            return total + jnp.sum(p, axis=1, keepdims=True)

        total = jax.lax.fori_loop(0, n, exps, jnp.zeros((H, 1), jnp.float32))

        def values(i, acc):
            t = t0 + n + i
            copy(v_hbm, b, i, t).wait()

            @pl.when(i + 1 < n)
            def _():
                copy(v_hbm, b, i + 1, t + 1).start()

            @pl.when((i + 1 == n) & (s + 1 < n_slots))
            def _():
                copy(k_hbm, slots_ref[s + 1], 0, t + 1).start()

            w = (scores[i] / total).astype(buf.dtype)
            return acc + jnp.dot(w, buf[t % 2], preferred_element_type=jnp.float32)

        acc = jax.lax.fori_loop(0, n, values, jnp.zeros((H, D), jnp.float32))
        out_ref[b] = acc.astype(out_ref.dtype)
        return t0 + 2 * n

    jax.lax.fori_loop(0, n_slots, slot, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("scale", "floor", "interpret"))
def paged_live_read(q, k, v, bias, live: LiveChunks, *, scale: float, floor: float,
                    interpret: bool = False):
    """``q`` ``[B, 1, H, D]`` over the pools ``k``, ``v`` ``[B, C, H_kv,
    D]`` as stored (``H = G * H_kv``), under ``bias`` ``[B, 1, 1, C]`` in
    stored order, reading the chunks ``live`` lists
    (``ops/kv_cache.py::live_chunks`` of the same bias, at this pool's
    :func:`~trlx_tpu.ops.kv_cache.live_chunk_positions`); returns ``[B, 1,
    H, D]`` in ``q``'s dtype. ``scale`` multiplies the scores, ``floor`` is
    the score of a column that is no position of the head's (the callers'
    ``NEG_INF``). A slot ``live`` gives no chunk comes back as zeros.

    Compiled by Mosaic for the TPU; ``interpret=True`` runs the kernel
    through the Pallas interpreter (any backend, slow)."""
    B, _, H, D = q.shape
    C, R = k.shape[1:3]
    n_chunks = live.chunks.shape[1]
    rows = C // n_chunks * R
    kernel = functools.partial(
        _kernel, rows=rows, n_chunks=n_chunks, kv_rows=R, group=H // R,
        scale=scale, floor=floor,
    )
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    pool = pl.BlockSpec(memory_space=pl.ANY)
    with jax.named_scope(SCOPE):
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(),
                in_specs=[vmem, vmem, pool, pool],
                out_specs=vmem,
                scratch_shapes=[
                    pltpu.VMEM((2, rows, D), k.dtype),
                    pltpu.VMEM((n_chunks, H, rows), jnp.float32),
                    pltpu.SemaphoreType.DMA((2,)),
                ],
            ),
            interpret=interpret,
            name=SCOPE,
        )(
            live.slots, live.n_slots, live.counts, live.chunks.reshape(-1),
            q[:, 0],
            # a value a pool row: the position's, once a KV head
            jnp.repeat(bias[:, 0, 0].astype(jnp.float32), R, axis=-1),
            # positions are major to heads: rows [C * R, D], a bitcast
            k.reshape(B, C * R, D),
            v.reshape(B, C * R, D),
        )
    return out[:, None]
