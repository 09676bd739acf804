"""Attention core and mask/bias builders.

All attention in the framework funnels through :func:`dot_product_attention`
(SURVEY §2.9: "Pallas kernels only where XLA fusion is insufficient"): on
TPU, long sequences route to the Pallas flash kernel
(:mod:`trlx_tpu.ops.flash_attention` — blocked online softmax, causal tile
skipping, custom-VJP backward), from the length at which it was measured to
win for the kind of call (:func:`attention_path`); short sequences and CPU
stay on the XLA einsum path, which writes the ``[B, H, Q, K]`` float32
scores to HBM and reads them back, forward and backward. Masks
are additive float biases built once per program by the helpers below —
models never branch on Python-level conditions inside jit.

Softmax runs in float32 regardless of compute dtype (bf16 logits lose
~3 decimal digits; the MXU matmuls stay bf16 where the FLOPs are).

Attention over a KV cache goes through :func:`decode_attention`, which asks
``ops/kv_cache.py::cache_kind`` what storage it was handed: the fixed
sampler's one-token steps read each layer's buffers once, in the lane-dense
layout ``decode_kv_layout`` gives them, and write the new position in
place; the paged engine's one-token steps read each layer's pool once, as
it is stored; every other cached call (prefill, chunked prefill, the verify
step) is ``dense_write_read`` / ``paged_write_read`` +
:func:`dot_product_attention`. How a cache is stored and written is
``ops/kv_cache.py``'s; the reads here are the math.
"""

from __future__ import annotations

import functools
import numbers
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from trlx_tpu.ops.kv_cache import (
    FOLDED,
    PAGED,
    cache_kind,
    decode_read_widths,
    dense_write_read,
    live_chunk_positions,
    live_chunks,
    paged_write_read,
    quantize_kv,
    reads_as_stored,
    reads_live_chunks,
    stored_order_bias,
)
from trlx_tpu.telemetry import get_metrics

NEG_INF = -1e9  # large-negative mask value; avoids -inf NaN propagation in softmax

# Flash kernel dispatch, measured on one TPU v5e chip, jax 0.9.0
# (tools/attention_crossover.py; the tables are PERF.md §6 "PR 37" and
# "PR 59"): bf16, 16 rows x 16 heads of 64 held [B, T, H * Dh] as a model's
# projections leave them, causal with a left-padding bias, tiles as
# flash_attention.py::fitted_block chooses them, XLA's time over the kernels'.
# Forward + backward: T 256 0.66, T 384 1.48, T 512 2.71, T 560 2.33, T 1024
# 2.33; forward alone: 0.52, 1.54, 2.39, 1.70, 2.03 (heads of 128: 2.63 and
# 5.56 at 512, 2.09 and 4.54 at 640). The thresholds are those of the
# kernels before PR 59, which took heads-major operands through transposes
# (0.25, 1.05, 1.69, 1.82, 1.83 and 0.27, 1.04, 1.63, 1.51, 1.88): the
# uncached causal self-attention of an update or a scoring forward takes the
# kernels from 512, the lowest length at which they clearly won then (384 was
# level and now wins by half: moving the threshold is a change of its own).
# Every other call (a cached call's Q x K rectangle under an explicit bias,
# where nothing is differentiated) keeps the crossover of the earlier sweep
# of 1k-4k contexts, which these tables did not repeat.
FLASH_MIN_SEQ = 1024
FLASH_MIN_SEQ_CAUSAL = 512


def causal_bias(q_len: int, kv_len: int, offset: int = 0, dtype=jnp.float32) -> jax.Array:
    """[1, 1, Q, K] additive bias: query i attends kv j iff j <= i + offset.

    ``offset`` is the absolute position of the first query token — used when
    decoding with a KV cache where queries sit at positions
    ``offset..offset+Q-1`` of a ``kv_len``-capacity buffer. A [B]-vector
    ``offset`` (rows decoding at different depths — the continuous-batching
    engine) yields a [B, 1, Q, K] bias instead.
    """
    off = jnp.asarray(offset)
    k_pos = jnp.arange(kv_len)[None, :]
    if off.ndim:
        q_pos = (
            jnp.arange(q_len)[None, :, None]
            + off.astype(jnp.int32)[:, None, None]
        )  # [B, Q, 1]
        mask = k_pos[None, :, :] <= q_pos
        return jnp.where(mask, 0.0, NEG_INF).astype(dtype)[:, None, :, :]
    q_pos = jnp.arange(q_len)[:, None] + off
    mask = k_pos <= q_pos
    return jnp.where(mask, 0.0, NEG_INF).astype(dtype)[None, None, :, :]


def padding_bias(attention_mask: jax.Array, dtype=jnp.float32) -> jax.Array:
    """[B, 1, 1, K] additive bias from a 0/1 key-validity mask."""
    return jnp.where(attention_mask[:, None, None, :] > 0, 0.0, NEG_INF).astype(dtype)


def combine_biases(*biases: Optional[jax.Array]) -> Optional[jax.Array]:
    out = None
    for b in biases:
        if b is None:
            continue
        out = b if out is None else out + b
    return out


def causal_dispatch(
    q_len: int,
    cache,
    cache_index,
    attention_mask: Optional[jax.Array],
):
    """Shared causal-mask dispatch for the causal-LM families.

    Without a KV cache the causal structure is returned as a flag (so the
    flash kernel can skip future key tiles in-kernel); with one, the
    offset-shifted causal mask must be an explicit bias tensor (the offset
    is traced). Returns ``(bias, causal_flag)`` for
    :func:`dot_product_attention`.

    With a cache, the MASK WIDTH is the attention view width: a caller
    that passes a validity mask narrower than the cache capacity attends
    over only the leading ``mask.shape[-1]`` logical positions
    (``ops/kv_cache.py``'s writes narrow the returned K/V view to the
    bias width). Every full-capacity caller is unchanged — the narrowed
    view is the chunked-prefill contract (docs/inference.md): prompt
    chunks never attend the decode region, whose masked columns carry
    exactly-zero softmax weight anyway.
    """
    pad = padding_bias(attention_mask) if attention_mask is not None else None
    if cache is None:
        return pad, True
    kv_len = (
        attention_mask.shape[-1]
        if attention_mask is not None
        else cache[0]["k"].shape[1]
    )
    offset = jnp.asarray(cache_index)
    if offset.ndim == 2:
        # [B, Q] per-column cache targets (the speculative verify step):
        # the query window is consecutive from each row's first target,
        # so the causal offset is the base column — rows whose window is
        # parked at the OOB sentinel get an over-wide bias exactly like
        # the one-token decode's idle-row ``capacity`` offset (their
        # outputs are discarded; the padding bias still applies)
        offset = offset[:, 0]
    return combine_biases(causal_bias(q_len, kv_len, offset=offset), pad), False


def attention_path(q_shape, k_shape, *, causal, learned_bias, scale) -> str:
    """``"flash"`` or ``"xla"``: which path :func:`dot_product_attention`
    takes, from what the call shows (counted per traced call site in
    ``attention/path{path=...}``).

    The flash kernels run on a TPU only, and never under a learned bias
    (their VJP treats the bias as constant). From ``FLASH_MIN_SEQ`` they
    take every call, and a call with grouped KV heads or a ``scale`` is
    refused by name there. From ``FLASH_MIN_SEQ_CAUSAL`` they take the call
    a trainer's or scorer's uncached forward makes: ``causal=True`` with
    ``Q == K`` (``causal_dispatch`` hands the flag out only without a
    cache; every cached call arrives with ``causal=False`` and an explicit
    bias). Under ``FLASH_MIN_SEQ`` a grouped or scaled call stays on the XLA
    path, which takes both.
    """
    Q, K = q_shape[1], k_shape[1]
    if learned_bias or jax.default_backend() != "tpu":
        return "xla"
    plain = q_shape[2] == k_shape[2] and scale is None
    if min(Q, K) >= FLASH_MIN_SEQ:
        if not plain:
            raise ValueError(
                f"the flash kernels (contexts of {FLASH_MIN_SEQ} and more on "
                f"a TPU) take equal heads and the 1/sqrt(D) scale; got "
                f"{q_shape[2]} query over {k_shape[2]} KV heads, scale={scale}"
            )
        return "flash"
    if causal and Q == K and Q >= FLASH_MIN_SEQ_CAUSAL and plain:
        return "flash"
    return "xla"


def _count_flash_site(folded: bool, loop_rows: Optional[int]) -> None:
    """One traced flash call site: the counter
    ``attention/flash_operands{layout=folded|heads_major}`` and the gauge
    ``attention/flash_folded_share``, the share of the sites traced in the
    process whose kernels read the caller's own ``[B, T, H * Dh]`` (1.0: no
    site pays the transposes to heads-major; never set where no call takes
    the kernels). For a site of the one-tile kernels, ``loop_rows`` query
    rows an iteration of their loop: the gauge ``attention/flash_row_chunk``
    holds the fewest among the sites traced in the process (each key block
    is loaded into the MXU once an iteration, so few rows is a slow site)."""
    metrics = get_metrics()
    layouts = [
        metrics.counter("attention/flash_operands{layout=%s}" % name)
        for name in ("folded", "heads_major")
    ]
    layouts[0 if folded else 1].inc()
    sites = sum(c.value for c in layouts)
    if sites:  # a disabled registry counts nothing
        metrics.gauge("attention/flash_folded_share").set(layouts[0].value / sites)
    if loop_rows is not None:
        fewest = metrics.gauge("attention/flash_row_chunk")
        fewest.set(min(fewest.value or loop_rows, loop_rows))  # 0: not set yet


def flash_on_program_mesh(q, k, v, bias=None, *, causal=False,
                          interpret=False):
    """The flash kernels over the mesh of the program being traced.

    XLA refuses to partition a Mosaic kernel, so inside a GSPMD program on
    more than one device the call is wrapped in a ``shard_map`` over the
    program's mesh (:func:`trlx_tpu.parallel.mesh.traced_on` declares it):
    batch split over dp x fsdp, heads over tp — attention is independent
    across both, so every device runs the kernel on its own shard and
    nothing is gathered. A single-device program, and a call already inside
    a ``shard_map`` body (the pipeline's stages), run the kernel as it is.
    """
    from trlx_tpu.ops.flash_attention import (
        flash_attention,
        one_tile_loop_rows,
        operand_layout,
    )
    from trlx_tpu.parallel.mesh import AXIS_TP, BATCH_AXES, program_mesh

    def kernel(q, k, v, bias=None):
        # traced once a call site, on the shapes the kernels get (under the
        # shard_map below: a device's own heads)
        _count_flash_site(
            operand_layout(q.shape[2], q.shape[3]).folded,
            one_tile_loop_rows(q.shape[1], k.shape[1]),
        )
        return flash_attention(
            q, k, v, bias, causal=causal, interpret=interpret
        )

    mesh = program_mesh()
    if (
        mesh is None
        or mesh.size == 1
        or jax.sharding.get_abstract_mesh().manual_axes
    ):
        return kernel(q, k, v, bias)

    batch = tuple(a for a in BATCH_AXES if a in mesh.axis_names) or None
    heads = AXIS_TP if AXIS_TP in mesh.axis_names else None
    qkv = P(batch, None, heads, None)
    if bias is None:
        args, specs = (q, k, v), (qkv, qkv, qkv)
    else:
        # size-1 (broadcast) bias dims stay whole on every device
        bias_spec = P(
            batch if bias.shape[0] > 1 else None,
            heads if bias.shape[1] > 1 else None,
            None,
            None,
        )
        args, specs = (q, k, v, bias), (qkv, qkv, qkv, bias_spec)
    # pallas_call outputs carry no varying-axes annotation, which trips
    # shard_map's check (same as ring_attention_sharded)
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=specs, out_specs=qkv, check_vma=False
    )(*args)


def dot_product_attention(
    q: jax.Array,  # [B, Q, H, D]
    k: jax.Array,  # [B, K, H, D]
    v: jax.Array,  # [B, K, H, D]
    bias: Optional[jax.Array] = None,  # [B or 1, 1 or H, Q, K] additive
    *,
    causal: bool = False,
    learned_bias: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Multi-head attention; returns [B, Q, H, D] (``D`` the values' head
    size where it differs from the scores': the XLA path takes any, the
    flash kernels one size).

    ``k``/``v`` may hold fewer heads than ``q`` (grouped-query attention,
    ``H = G * H_kv``): query head ``h`` reads KV head ``h // G``, and a
    per-head bias is per query head. ``scale`` multiplies the scores
    (``None``: ``1 / sqrt(D)``). The flash kernels take neither: such a
    call stays on the XLA path under ``FLASH_MIN_SEQ`` and is refused by
    name from there (:func:`attention_path`).

    ``causal=True`` applies offset-0 causal masking (training / prefill) —
    prefer it over baking a causal term into ``bias``: the flash kernel then
    skips future key tiles instead of reading a [Q, K] mask from HBM.
    ``learned_bias=True`` declares that gradient must flow to ``bias`` (T5
    relative position bias) and pins the XLA path, since the flash kernel's
    VJP treats bias as constant.

    XLA path: logits and softmax in float32, output cast back to q.dtype;
    XLA fuses the scale/bias/softmax chain between the two MXU matmuls.
    """
    Q, K = q.shape[1], k.shape[1]
    H, H_kv = q.shape[2], k.shape[2]
    path = attention_path(
        q.shape, k.shape, causal=causal, learned_bias=learned_bias, scale=scale
    )
    get_metrics().counter("attention/path{path=%s}" % path).inc()
    if path == "flash":
        return flash_on_program_mesh(q, k, v, bias, causal=causal)

    if causal:
        bias = combine_biases(causal_bias(Q, K), bias)
    depth = q.shape[-1]
    scale = jax.lax.rsqrt(jnp.float32(depth)) if scale is None else jnp.float32(scale)
    if H != H_kv:
        return _grouped_attention(q, k, v, bias, scale)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", weights.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def _grouped_attention(q, k, v, bias, scale):
    """The XLA path of :func:`dot_product_attention` for ``H = G * H_kv``:
    the same two products and float32 softmax, the query heads of a group
    side by side over their one KV head; K and V are read once, never
    repeated."""
    B, Q, H, D = q.shape
    K, H_kv = k.shape[1], k.shape[2]
    if H % H_kv:
        raise ValueError(f"{H} query heads do not divide over {H_kv} KV heads")
    G = H // H_kv
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q.reshape(B, Q, H_kv, G, D), k,
        preferred_element_type=jnp.float32,
    ) * scale
    if bias is not None:
        logits = logits + _grouped_bias(bias, H_kv, G)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", weights.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, Q, H, v.shape[-1]).astype(q.dtype)


def _grouped_bias(bias, H_kv: int, G: int):
    """``bias`` ``[B or 1, 1 or H, Q, K]`` in float32 over grouped scores
    ``[B, H_kv, G, Q, K]``: a per-head bias is per query head."""
    b32 = bias.astype(jnp.float32)
    if bias.shape[1] == 1:
        return b32[:, :, None]
    return b32.reshape(bias.shape[0], H_kv, G, *bias.shape[2:])


def _lane_rows_read(q, k, v, bias, scale):
    """:func:`_grouped_attention` over pools that hold a head as ``J`` rows
    of one lane row each (``ops/kv_cache.py::hold_pool``): ``k`` and ``v``
    ``[B, K, H_kv * J, Dh // J]``, row ``kv * J + j`` of a position the
    columns ``[j Dh / J, (j + 1) Dh / J)`` of KV head ``kv``, read as they
    are stored. The query is arranged the same way (``[B, Q, H_kv * J, G,
    Dh // J]``, query-sized), so the scores' product is the grouped read
    over ``H_kv * J`` heads of one lane row; a head's ``J`` partial scores
    are added in float32 before the scale, the bias and the softmax, the
    weights are repeated over ``j`` for the values, and the output's lane
    rows go back side by side. The same sums as over whole heads, a head's
    channels taken in ``J`` parts; nothing pool-sized is reshaped."""
    B, Q, H, D = q.shape
    K, R, W = k.shape[1:]
    J = D // W
    H_kv = R // J
    if J * W != D or H_kv * J != R or H % H_kv:
        raise ValueError(
            f"{H} query heads of {D} do not read a pool of {R} rows of {W} a position"
        )
    G = H // H_kv
    scale = jax.lax.rsqrt(jnp.float32(D)) if scale is None else jnp.float32(scale)
    q_rows = jnp.swapaxes(q.reshape(B, Q, H_kv, G, J, W), 3, 4).reshape(B, Q, R, G, W)
    parts = jnp.einsum(
        "bqrgd,bkrd->brgqk", q_rows, k, preferred_element_type=jnp.float32
    )
    logits = jnp.sum(parts.reshape(B, H_kv, J, G, Q, K), axis=2) * scale
    weights = jax.nn.softmax(logits + _grouped_bias(bias, H_kv, G), axis=-1).astype(v.dtype)
    w_rows = jnp.broadcast_to(weights[:, :, None], (B, H_kv, J, G, Q, K)).reshape(B, R, G, Q, K)
    out = jnp.einsum(
        "brgqk,bkrd->bqrgd", w_rows, v, preferred_element_type=jnp.float32
    )
    out = jnp.swapaxes(out.reshape(B, Q, H_kv, J, G, W), 3, 4)
    return out.reshape(B, Q, H, D).astype(q.dtype)


def _decode_read(q, k_new, v_new, cache_kv, cache_index, bias, scale=None):
    """One new position over a cache in ``decode_kv_layout``: write it in
    place, then read K and V once each, as stored. The cache is the fixed
    sampler's carry of all layers (``cache_kind(...).layer`` says which this
    call is for; the carry comes back whole, written at that layer) or one
    layer's own buffers (a layer too large for the compiler to stage, the
    pp stage scan's call): the same operations.

    Per-head products run on the MXU against the folded ``[C, H*Dh]`` buffer:
    ``q`` is laid out block-diagonally (``[H, H*Dh]``, head ``h``'s row holds
    ``q[h]`` in its own ``Dh`` columns and zeros elsewhere), so one matmul
    gives the ``[H, C]`` scores; the ``[H, C]`` weights times the buffer give
    ``[H, H*Dh]``, whose diagonal blocks are the output. Neither needs the
    buffer in ``[C, H, Dh]`` form, a cross-lane reduce, or a dequantised
    copy: int8 values convert to the compute dtype exactly, and their
    per-(position, head) scales multiply the scores and the weights.
    Products accumulate in float32 and the softmax is float32, as in
    :func:`dot_product_attention`; ``scale`` as there. Equal heads only:
    :func:`decode_attention` refuses grouped heads on this read by name.

    How many positions are read follows what the call shows: the whole
    capacity, or under the caller's promise that nothing past
    ``cache_index`` holds anything (``kv_cache.py::written_to_index``, the
    fixed sampler's loop; ``cache_kind(...).written_to_index``) the
    narrowest of ``decode_read_widths(C, first index)`` that holds
    ``cache_index``, chosen by a ``switch`` after the write. The write, the
    block-diagonal ``q``, the scales and the float32 softmax are the same
    at every width; the result differs from the whole read's by float32
    summation order alone (the dropped positions' weights are exactly 0
    there). Each traced width counts once in
    ``attention/decode_read_width{width=...}``.
    """
    B, _, H, Dh = q.shape
    HD = H * Dh
    kind = cache_kind(cache_kv)
    quantized = kind.quantized
    # the carry of all layers ([L, B, ...], this call's layer leading every
    # write and read) or one layer's own buffers: the carry with no such axis
    lead = () if kind.layer is None else (kind.layer,)
    one = (1,) * len(lead)
    new_kv = {}
    for name, new in (("k", k_new), ("v", v_new)):
        if quantized:
            new, absmax = quantize_kv(new)
            new_kv[name + "_scale"] = jax.lax.dynamic_update_slice(
                cache_kv[name + "_scale"],
                absmax.reshape(*one, B, H, 1),
                (*lead, 0, 0, cache_index),
            )
        new_kv[name] = jax.lax.dynamic_update_slice(
            cache_kv[name],
            new.reshape(*one, B, 1, HD).astype(cache_kv[name].dtype),
            (*lead, 0, cache_index, 0),
        )
    # fenced: the update stays ONE in-place op with the loop carry as its
    # only destination. Unfenced, XLA fuses a duplicate of it into the read
    # as well, two ops then write from one operand, neither can be in place
    # and copy insertion copies the whole buffer at every step (PERF.md §6)
    new_kv = jax.lax.optimization_barrier(new_kv)

    def attend(q, mine, bias):
        """One layer's buffers ``[B, w, H*Dh]`` (scales ``[B, H, w]``) read
        under ``bias`` ``[B, 1, 1, w]``: the ``[B, 1, H, Dh]`` output."""
        seg = (jnp.arange(HD)[None, :] // Dh == jnp.arange(H)[:, None])
        q_blocks = jnp.where(seg[None], q.reshape(B, 1, HD), 0).astype(q.dtype)
        scores = jnp.einsum(
            "bhk,bck->bhc", q_blocks, mine["k"].astype(q.dtype),
            preferred_element_type=jnp.float32,
        )
        if quantized:
            scores = scores * mine["k_scale"].astype(jnp.float32)
        scores = scores * (jax.lax.rsqrt(jnp.float32(Dh)) if scale is None else jnp.float32(scale))
        scores = scores + bias[:, 0].astype(jnp.float32)
        weights = jax.nn.softmax(scores, axis=-1)
        if quantized:
            weights = weights * mine["v_scale"].astype(jnp.float32)
        # rounded to the compute dtype where they meet V, as the generic read
        # does: two-term weights (16 + 16 bits) read the same log-probability
        # error on the chip, to 1% (PERF.md §6, PR 25)
        blocks = jnp.einsum(
            "bhc,bck->bhk", weights.astype(q.dtype), mine["v"].astype(q.dtype),
            preferred_element_type=jnp.float32,
        )
        out = jnp.sum(jnp.where(seg[None], blocks, 0.0), axis=1)
        return out.reshape(B, 1, H, Dh).astype(q.dtype)

    widths = decode_read_widths(cache_kv["k"].shape[-2], kind.written_to_index)
    for w in widths:
        get_metrics().counter("attention/decode_read_width{width=%d}" % w).inc()
    if len(widths) == 1:
        # what this layer reads: its slice of the carry, which fuses into the
        # products (read-only: it may be prefetched, never written back)
        mine = new_kv if kind.layer is None else {name: a[kind.layer] for name, a in new_kv.items()}
        out = attend(q, mine, bias)
    else:
        # the caller's promise (``kv_cache.py::written_to_index``): nothing
        # past ``cache_index`` is valid, so the read takes the narrowest of a
        # few static widths that holds it; the positions it drops all carry
        # ``NEG_INF`` in the whole read, where their weights are exactly 0.
        # One branch a width, each reading the carry and returning the
        # layer's output: a ``switch`` around a read, not around the carry,
        # so no branch returns, rewrites or copies a cache
        def at_width(w):
            # fenced: the branches end in the same few operations, which the
            # compiler otherwise moves out of the `switch` and has every
            # branch hand over the [B, H, H*Dh] float32 products instead
            return lambda q, kv, bias: jax.lax.optimization_barrier(attend(
                q,
                {name: _leading(a, kind.layer, w, name) for name, a in kv.items()},
                bias[..., :w],
            ))

        bucket = jnp.sum(cache_index >= jnp.asarray(widths[:-1]), dtype=jnp.int32)
        out = jax.lax.switch(bucket, [at_width(w) for w in widths], q, new_kv, bias)
    return out, new_kv


def _leading(a, layer, width: int, name: str):
    """Layer ``layer``'s leading ``width`` positions of a cache array in
    ``decode_kv_layout`` (``[L, B, C, H*Dh]``, scales ``[L, B, H, C]``;
    ``layer`` ``None``: the array is one layer's own), taken in ONE slice.
    Sliced twice (``a[layer]``, then ``[:, :width]``) inside a branch of a
    ``switch`` the v5e compiler stages the whole layer into ``S(1)`` first
    and narrows it after: every byte is read again
    (``tests/test_tpu_compile.py`` keeps a case that shows it)."""
    axis = a.ndim - (1 if name.endswith("_scale") else 2)
    start, limit = [0] * a.ndim, list(a.shape)
    limit[axis] = width
    if layer is None:
        return jax.lax.slice(a, start, limit)
    start[0], limit[0] = layer, layer + 1
    return jax.lax.squeeze(jax.lax.slice(a, start, limit), (0,))


class Latent(NamedTuple):
    """How a latent cache's rows become keys and values (multi-head latent
    attention, DeepSeek-V2 section 2.1): a cached row is ``[c | k_r]``,
    ``c`` the compressed key-value latent (``w_ukv.shape[0]`` wide) and
    ``k_r`` the one rotated key part every head shares; head ``h``'s key is
    ``[c W_uk[h] | k_r]`` and its value ``c W_uv[h]``, ``w_ukv[:, h]``
    being ``[W_uk[h] | W_uv[h]]`` with ``nope`` columns of the first. A
    query head is ``[q_nope | q_rope]``, ``nope`` and ``k_r``'s width."""

    w_ukv: jax.Array  # [C, H, nope + Dv], the compute dtype
    nope: int


def latent_attention(q, rows, bias, latent: Latent, *, scale, causal: bool = False):
    """Attention over latent ``rows`` [B, K, 1, C + rope] in the published
    form: every row is decompressed through ``w_ukv`` into ``H`` keys and
    values and the heads attend as heads do. What a call of many columns
    takes (an uncached forward over its own rows, an admission over its
    view of the pool): the products are over ``K`` rows once, not once a
    query."""
    C, H = latent.w_ukv.shape[:2]
    # a row's own columns: the pool it was gathered from may be held wider
    # (``ops/kv_cache.py::hold_pool``)
    rope = q.shape[-1] - latent.nope
    with jax.named_scope("mla_decompress"):
        kv = jnp.einsum(
            "bkc,chd->bkhd", rows[:, :, 0, :C], latent.w_ukv,
            preferred_element_type=jnp.float32,
        ).astype(q.dtype)
        shared = jnp.broadcast_to(
            rows[:, :, :, C : C + rope], rows.shape[:2] + (H, rope)
        )
        k = jnp.concatenate([kv[..., : latent.nope], shared], axis=-1)
    return dot_product_attention(
        q, k, kv[..., latent.nope:], bias, causal=causal, scale=scale
    )


def _latent_absorbed_read(q, rows, bias, scale, latent: Latent):
    """The same function with the up-projections absorbed into the query
    and the output (``(q W_uk^T) . c = q . (c W_uk)``): ``H`` query heads
    of ``C + rope`` over the rows as they lie, one cached head whose value
    is the first ``C`` columns of its key. Nothing is decompressed: what a
    one-row query takes (the decode step over a whole pool), where the
    rows are read once and the two small products are per query.

    Both products take the rows in the order they are stored, ``[B, K, C +
    rope]``: the scores with the rows on the left (``K`` rows times the
    ``H`` queries, ``[B, K, H]``), the values with the rows on the right
    (summed over ``K``); where the pool is held wider than a row, a row's
    own columns of it. Written as ``H`` queries times the rows' transpose
    (:func:`dot_product_attention`'s grouped read at one KV head) the v5e
    compiler re-laid the whole pool position-minor for the first product
    and back for the second, two pool-sized copies a layer and step.
    Softmax in float32 over ``K``, weights rounded to the compute dtype
    where they meet the rows, as everywhere here."""
    C, nope = latent.w_ukv.shape[0], latent.nope
    with jax.named_scope("mla_absorbed_read"):
        q_lat = jnp.einsum(
            "bqhn,chn->bqhc", q[..., :nope], latent.w_ukv[..., :nope],
            preferred_element_type=jnp.float32,
        ).astype(q.dtype)
        q_abs = jnp.concatenate([q_lat, q[..., nope:]], axis=-1)[:, 0]  # [B, H, C + rope]
        # a row's own columns of a pool that may be held wider
        # (``ops/kv_cache.py::hold_pool``), taken of the rows viewed [B, K, W]:
        # taken of the four-axis pool the slice re-laid the whole pool
        stored = rows[:, :, 0, :][..., : q_abs.shape[-1]]  # [B, K, C + rope]
        scores = jnp.einsum(
            "bkd,bhd->bkh", stored, q_abs, preferred_element_type=jnp.float32
        ) * jnp.float32(scale)
        scores = scores + jnp.swapaxes(bias[:, 0].astype(jnp.float32), 1, 2)  # [B, K, 1]
        weights = jax.nn.softmax(scores, axis=1)
        out = jnp.einsum(
            "bkh,bkd->bhd", weights.astype(q.dtype), stored,
            preferred_element_type=jnp.float32,
        )[..., :C].astype(q.dtype)
        return jnp.einsum(
            "bhc,chv->bhv", out, latent.w_ukv[..., nope:],
            preferred_element_type=jnp.float32,
        ).astype(q.dtype)[:, None]


def _reads_live_chunks(q, k_new, cache_kv, bias, scale) -> bool:
    """Whether a ``paged`` call of :func:`decode_attention` reads its pools'
    live chunks (:func:`_live_chunks_read`) and not the pools whole, from
    what the call shows: the pool is one the read is built for
    (``kv_cache.py::reads_live_chunks``: keys and values, bfloat16, the
    stored row the call's whole head of whole lane rows), the query is in
    the pool's dtype, the bias is broadcast over heads (its live positions
    are then every head's), the scale is a number of the program's and not
    a traced one, and the program runs on one device (XLA partitions no
    Mosaic kernel; a program on more declares its mesh,
    ``parallel/mesh.py::traced_on``, and keeps the whole read)."""
    from trlx_tpu.parallel.mesh import program_mesh

    mesh = program_mesh()
    return (
        reads_live_chunks(cache_kv, k_new.shape[-1])
        and q.dtype == cache_kv["k"].dtype
        and bias.shape[1] == 1
        and (scale is None or isinstance(scale, numbers.Real))
        and (mesh is None or mesh.size == 1)
    )


def _live_chunks_read(q, k, v, bias, cache_kv, cache_index, scale):
    """The ``paged`` read over the chunks of the pools, as stored, in which
    a position can carry a weight (``ops/paged_live_read.py``): ``bias`` is
    in stored order, and a slot whose ``cache_index`` is at the capacity has
    no such chunk. The same products, float32 softmax and compute-dtype
    weights as :func:`dot_product_attention` over the whole pool, whose
    other positions weigh exactly 0; float32 sums in another order."""
    from trlx_tpu.ops.paged_live_read import paged_live_read

    live = live_chunks(bias, cache_index, live_chunk_positions(cache_kv), NEG_INF / 2)
    return paged_live_read(
        q, k, v, bias, live,
        scale=q.shape[-1] ** -0.5 if scale is None else float(scale),
        floor=NEG_INF,
        interpret=jax.default_backend() != "tpu",
    )


def decode_attention(
    q: jax.Array,  # [B, Q, H, D]
    k_new: jax.Array,  # [B, Q, H, D]
    v_new: Optional[jax.Array],  # [B, Q, H, D]; None into a latent cache
    cache_kv,
    cache_index,
    bias: Optional[jax.Array],
    *,
    causal: bool = False,
    learned_bias: bool = False,
    scale: Optional[float] = None,
    latent: Optional[Latent] = None,
):
    """Write this call's keys/values into ``cache_kv`` at ``cache_index``
    and attend over the cache; returns ``(out [B, Q, H, D], new_kv)``. The
    one cached-attention entry of every family. ``k_new``/``v_new`` and the
    cache may hold fewer heads than ``q`` (grouped-query attention) on the
    ``paged`` and ``generic`` paths (the ``fused`` read refuses them by
    name: no family with grouped heads reaches the fixed sampler) and
    ``scale`` replaces ``1 / sqrt(D)`` on every path, as in
    :func:`dot_product_attention`.

    Dispatch is on what the call shows, at trace time: the cache's kind
    (``ops/kv_cache.py::cache_kind``) and the call's shapes (counted per
    traced call site in ``attention/decode_path{path=...}``):

    - ``fused`` — the cache is in ``decode_kv_layout`` (the fixed
      sampler's decode loop, whose carry holds all layers in one array a
      kind and comes back as ``new_kv`` whole, or one layer's dict where
      its layers are large; the pp stage scan's, one layer's dict):
      :func:`_decode_read`. Such a cache takes one position
      a call under a bias broadcast over heads; anything else is refused,
      not rerouted;
    - ``paged`` — one position a slot into a floating paged pool (the
      continuous engine's decode step; ``kv_cache.py::reads_as_stored``):
      the rows are scattered in place and the pools are read as stored, in
      each slot's physical order, under the bias re-indexed to that order.
      No logical view is gathered. Which read, again on what the call shows
      (counted in ``attention/paged_read{read=live_chunks|whole}``): a pool
      of keys and values in bfloat16 whose stored row is the call's whole
      head of whole lane rows, under a bias broadcast over heads, in a
      program on one device, is read **by its live chunks**
      (:func:`_reads_live_chunks`, :func:`_live_chunks_read`: one Pallas
      kernel over the pools where they lie, fetching only the chunks in
      which some position's bias is above ``NEG_INF / 2``; the same sums in
      another order). Every other ``paged`` call keeps the whole read:
      :func:`dot_product_attention` over the pools, a pool its holder
      keeps with a head as several lane rows (``kv_cache.py::hold_pool``,
      heads of 256) in those rows (:func:`_lane_rows_read`), the pool never
      reshaped. **A slot whose ``cache_index`` is at or past the capacity**
      (the engine's row that is not live: its write is dropped here and its
      output by ``decode_step``) has no live chunk whatever its bias says:
      on this path that slot's output is unspecified (the read by live
      chunks returns zeros, the whole read attends under the slot's stale
      mask) and no caller may use it;
    - ``paged_rows`` — a group's rows inside the whole paged pool (the
      engine's admission programs; ``cache_kind(...).rows``):
      ``paged_write_read`` scatters the call's columns at (slot, physical
      position), in place, and gathers the group's logical view, which
      :func:`dot_product_attention` reads under the bias as it is. The
      rest of the pool is neither sliced, merged nor copied;
    - ``generic`` — everything else, unchanged: ``dense_write_read`` or
      ``paged_write_read`` returns the view the bias was built for and
      :func:`dot_product_attention` reads it. The fixed sampler's
      prefill, the verify step, T5's learned per-head bias, a cache whose
      capacity axis is sharded (the sampler leaves those in the
      ``kv_buffers`` layout), and a one-token call into a paged int8 pool
      or a paged pool with a shared-prefix overlay.

    A **latent** cache (``cache_kind(...).latent``: one row a position, no
    values; ``k_new`` is the call's rows ``[B, Q, 1, C + rope]``, ``v_new``
    None, ``latent`` says how rows become keys and values) is a paged pool
    and takes the same paths by the same rules: ``paged`` reads the pool as
    stored in the absorbed form (:func:`_latent_absorbed_read`),
    ``paged_rows`` (and a paged ``generic`` call) gathers the view and
    attends in the published form (:func:`latent_attention`) over the
    positions the call can see: a call of ``T`` columns from a Python
    ``cache_index`` sees ``cache_index + T`` of them and decompresses no
    more. Outside a paged pool a latent cache is refused by name.
    """
    kind = cache_kind(cache_kv)
    if kind.latent != (latent is not None) or (kind.latent and (
        kind.layout != PAGED or v_new is not None or learned_bias or causal or bias is None
    )):
        raise ValueError(
            "a latent cache (one row a position, no values) is read through a "
            "paged pool (rollout.engine: continuous, InferenceServer) with "
            "`latent` given, v_new=None and an explicit bias, and a cache of "
            f"keys and values without; got layout {kind.layout!r}, "
            f"latent={latent is not None} over a cache that is "
            f"{'latent' if kind.latent else 'keys and values'}"
        )
    # attend over the buffer VIEW the bias was built for: a bias narrower
    # than capacity (the chunked prefill's prompt-only mask) narrows the
    # view to match (0 = the whole capacity)
    view_len = bias.shape[-1] if bias is not None else 0
    fused = kind.layout == FOLDED
    paged = (
        kind.layout == PAGED
        and bias is not None
        and not learned_bias
        and not causal
        and reads_as_stored(cache_kv, k_new, cache_index, view_len)
    )
    path = (
        "fused" if fused
        else "paged" if paged
        else "paged_rows" if kind.rows
        else "generic"
    )
    get_metrics().counter("attention/decode_path{path=%s}" % path).inc()
    if paged:
        k, v, new_kv = paged_write_read(
            cache_kv, k_new, v_new, cache_index, q.dtype, as_stored=True
        )
        bias = stored_order_bias(cache_kv["block_tables"], bias)
        by_chunk = latent is None and _reads_live_chunks(q, k_new, cache_kv, bias, scale)
        get_metrics().counter(
            "attention/paged_read{read=%s}" % ("live_chunks" if by_chunk else "whole")
        ).inc()
        if by_chunk:
            return _live_chunks_read(q, k, v, bias, cache_kv, cache_index, scale), new_kv
        if latent is not None:
            return _latent_absorbed_read(q, k, bias, scale, latent), new_kv
        if k.shape[-1] != k_new.shape[-1]:
            # a head held as several lane rows (kv_cache.py::hold_pool)
            return _lane_rows_read(q, k, v, bias, scale), new_kv
        return dot_product_attention(q, k, v, bias, scale=scale), new_kv
    if latent is not None:
        if isinstance(cache_index, numbers.Integral):
            # columns past the call's last are under its causal mask whatever
            # the bias's width: neither gathered nor decompressed
            view_len = min(view_len, cache_index + q.shape[1])
            bias = bias[..., :view_len]
        rows, _, new_kv = paged_write_read(
            cache_kv, k_new, None, cache_index, q.dtype, view_len=view_len
        )
        return latent_attention(q, rows, bias, latent, scale=scale), new_kv
    if fused:
        if (
            q.shape[1] != 1
            or learned_bias
            or bias is None
            or bias.shape[1] != 1
            or jnp.ndim(cache_index) != 0
        ):
            raise ValueError(
                "a cache in decode_kv_layout takes one position a call, at "
                "a scalar cache_index, under a bias broadcast over heads; "
                f"got q {q.shape}, bias "
                f"{None if bias is None else bias.shape}, "
                f"learned_bias={learned_bias}"
            )
        if q.shape[2] != k_new.shape[2]:
            raise ValueError(
                "the fused read of a cache in decode_kv_layout takes equal "
                f"heads; got {q.shape[2]} query over {k_new.shape[2]} KV heads"
            )
        # device-trace scope names are a contract (docs/observability.md)
        with jax.named_scope("decode_attention"):
            return _decode_read(q, k_new, v_new, cache_kv, cache_index, bias, scale)
    write = paged_write_read if kind.layout == PAGED else dense_write_read
    k, v, new_kv = write(
        cache_kv, k_new, v_new, cache_index, q.dtype, view_len=view_len
    )
    out = dot_product_attention(
        q, k, v, bias, causal=causal, learned_bias=learned_bias, scale=scale
    )
    return out, new_kv
