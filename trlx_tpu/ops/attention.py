"""Attention core and mask/bias builders.

All attention in the framework funnels through :func:`dot_product_attention`
(SURVEY §2.9: "Pallas kernels only where XLA fusion is insufficient"): on
TPU, long sequences route to the Pallas flash kernel
(:mod:`trlx_tpu.ops.flash_attention` — blocked online softmax, causal tile
skipping, custom-VJP backward); short sequences and CPU stay on the XLA
einsum path, which XLA fuses well below the flash crossover point. Masks
are additive float biases built once per program by the helpers below —
models never branch on Python-level conditions inside jit.

Softmax runs in float32 regardless of compute dtype (bf16 logits lose
~3 decimal digits; the MXU matmuls stay bf16 where the FLOPs are).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e9  # large-negative mask value; avoids -inf NaN propagation in softmax

# Flash kernel dispatch: measured crossover on v5e — XLA wins below ~1k
# context (its fused softmax has no kernel-launch/transpose overhead), the
# pallas kernel wins above (2.4x fwd / 4x bwd at 4k). Settable for tests.
FLASH_MIN_SEQ = 1024


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
    (``models/gpt2.py::write_cache`` narrows the returned K/V view to the
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
    from trlx_tpu.ops.flash_attention import flash_attention
    from trlx_tpu.parallel.mesh import AXIS_TP, BATCH_AXES, program_mesh

    kernel = functools.partial(
        flash_attention, causal=causal, interpret=interpret
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
) -> jax.Array:
    """Multi-head attention; returns [B, Q, H, D].

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
    if (
        not learned_bias
        and min(Q, K) >= FLASH_MIN_SEQ
        and jax.default_backend() == "tpu"
    ):
        return flash_on_program_mesh(q, k, v, bias, causal=causal)

    if causal:
        bias = combine_biases(causal_bias(Q, K), bias)
    depth = q.shape[-1]
    scale = jax.lax.rsqrt(jnp.float32(depth))
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
