"""Ring attention: sequence-parallel exact attention over a mesh axis.

Long-context support beyond the reference (which truncates at
``seq_length: 512`` — SURVEY §5.7): activations are sharded along the
sequence dimension over the ``sp`` mesh axis; each device holds one query
block and the key/value blocks rotate around the ring via ``ppermute`` over
ICI, with flash-style online-softmax accumulation so the full [T, T] score
matrix never materializes. Memory per device is O(T/sp * T/sp) per step and
the K/V transfer overlaps with compute in XLA's pipeline.

Usable standalone via :func:`ring_attention_sharded` (a ``shard_map`` over
the mesh) or inside larger shard_mapped programs via :func:`ring_attention`
(expects per-device blocks, runs the collective loop).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from trlx_tpu.ops.attention import NEG_INF


def ring_attention(
    q: jax.Array,  # [B, Tq, H, D] local query block
    k: jax.Array,  # [B, Tk, H, D] local key block
    v: jax.Array,  # [B, Tk, H, D] local value block
    kv_mask: Optional[jax.Array] = None,  # [B, Tk] validity of local keys
    axis_name: str = "sp",
    causal: bool = True,
) -> jax.Array:
    """Exact attention with K/V ring rotation; call inside shard_map.

    Blocks are assumed laid out in sequence order across the axis: device i
    holds global positions ``[i*Tq, (i+1)*Tq)``.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = jax.lax.rsqrt(jnp.float32(D))

    q32 = q.astype(jnp.float32)
    q_pos = idx * Tq + jnp.arange(Tq)  # global query positions

    if kv_mask is None:
        kv_mask = jnp.ones((B, Tk), jnp.int32)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(i, carry):
        acc, m, l, k_blk, v_blk, mask_blk = carry
        # the k/v currently held were rotated i times: they originate from
        # device (idx - i) mod n
        src = (idx - i) % n
        k_pos = src * Tk + jnp.arange(Tk)

        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q32, k_blk.astype(jnp.float32)
        ) * scale
        logits = logits + _block_bias(mask_blk, q_pos, k_pos, causal)

        # online softmax update
        blk_max = jnp.max(logits, axis=-1)  # [B, H, Tq]
        new_m = jnp.maximum(m, blk_max)
        correction = jnp.exp(m - new_m)
        p = jnp.exp(logits - new_m[..., None])  # [B, H, Tq, Tk]
        l = l * correction + jnp.sum(p, axis=-1)
        acc = acc * correction[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32)
        )

        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        mask_blk = jax.lax.ppermute(mask_blk, axis_name, perm)
        return acc, new_m, l, k_blk, v_blk, mask_blk

    # derive the accumulators from q so they carry q's varying-axes type
    # (shard_map requires loop carries to have consistent manual-axes vma)
    zero_bhqd = jnp.transpose(q32 * 0.0, (0, 2, 1, 3))  # [B, H, Tq, D]
    zero_bhq = zero_bhqd[..., 0]
    acc0 = zero_bhqd
    m0 = zero_bhq - jnp.inf
    l0 = zero_bhq
    acc, m, l, _, _, _ = jax.lax.fori_loop(
        0, n, step, (acc0, m0, l0, k, v, kv_mask)
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [B, Tq, H, D]


# ---------------------------------------------------------------------------
# Ring flash attention: blockwise (o, lse) accumulation + custom two-pass VJP
# ---------------------------------------------------------------------------


def _block_bias(mask_blk, q_pos, k_pos, causal):
    """[B, 1, Tq, Tk] additive bias from key validity + causal positions."""
    bias = jnp.where(mask_blk[:, None, None, :] > 0, 0.0, NEG_INF)
    if causal:
        bias = bias + jnp.where(
            k_pos[None, :] <= q_pos[:, None], 0.0, NEG_INF
        )[None, None]
    return bias


def _use_pallas_blocks(Tq: int, Tk: int, interpret_blocks: bool) -> bool:
    """Per-device block sizes above which the pallas kernels take over the
    inner block computation on TPU (below, XLA's fused path wins — the same
    measured crossover as the dense dispatch). ``interpret_blocks`` is the
    caller asking for the kernels in interpret mode at any size and on any
    backend (the CPU tests of the ring <-> kernel hand-off)."""
    from trlx_tpu.ops.attention import FLASH_MIN_SEQ

    return interpret_blocks or (
        min(Tq, Tk) >= FLASH_MIN_SEQ and jax.default_backend() == "tpu"
    )


def _block_fwd(q, k_blk, v_blk, bias, scale, interpret_blocks):
    """Per-block attention with logsumexp.

    q [B, Tq, H, D]; k/v [B, Tk, H, D]; bias [B, 1, Tq, Tk].
    Returns (o [B, H, Tq, D] f32 — softmax-normalized within the block,
    lse [B, H, Tq] f32). Large blocks on TPU run the pallas flash kernel
    (the [Tq, Tk] score matrix stays in VMEM tiles).
    """
    if _use_pallas_blocks(q.shape[1], k_blk.shape[1], interpret_blocks):
        from trlx_tpu.ops.flash_attention import flash_block_fwd

        o, lse = flash_block_fwd(
            q, k_blk, v_blk, bias, scale=scale, interpret=interpret_blocks
        )
        return o.astype(jnp.float32), lse
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k_blk.astype(jnp.float32)
    ) * scale + bias
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bhqd", p / jnp.maximum(l, 1e-30),
                   v_blk.astype(jnp.float32))
    lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
    return o, lse


def _block_bwd(q, k_blk, v_blk, bias, o, lse, do, delta, scale,
               interpret_blocks):
    """Per-block gradients against the *global* (combined) logsumexp.

    ``o``/``do``/``delta`` are the GLOBAL combined output, its cotangent,
    and ``rowsum(do*o)`` — shared by every block of a ring pass (the flash
    backward's delta term is global by definition). Layouts: q [B,Tq,H,D],
    k/v [B,Tk,H,D], o/do [B,H,Tq,D], lse/delta [B,H,Tq]. Returns
    (dq [B,Tq,H,D], dk, dv [B,Tk,H,D]) in f32. Large blocks on TPU run the
    pallas backward kernels.
    """
    if _use_pallas_blocks(q.shape[1], k_blk.shape[1], interpret_blocks):
        from trlx_tpu.ops.flash_attention import flash_block_bwd

        return flash_block_bwd(
            q, k_blk, v_blk, bias, o, lse, do, scale=scale,
            interpret=interpret_blocks,
        )
    q32 = q.astype(jnp.float32)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q32, k_blk.astype(jnp.float32)
    ) * scale + bias
    p = jnp.exp(s - lse[..., None])  # global softmax weights
    dv = jnp.einsum("bhqk,bhqd->bkhd", p, do)
    dp = jnp.einsum("bhqd,bkhd->bhqk", do, v_blk.astype(jnp.float32))
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k_blk.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q32)
    return dq, dk, dv


def _ring_fwd(q, k, v, kv_mask, axis_name, causal, interpret_blocks):
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = float(1.0 / (D ** 0.5))
    q_pos = idx * Tq + jnp.arange(Tq)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(i, carry):
        out, lse, k_blk, v_blk, mask_blk = carry
        src = (idx - i) % n
        k_pos = src * Tk + jnp.arange(Tk)
        bias = _block_bias(mask_blk, q_pos, k_pos, causal)
        o_i, lse_i = _block_fwd(q, k_blk, v_blk, bias, scale, interpret_blocks)

        # combine softmax-normalized block results by their logsumexp weights
        m_new = jnp.maximum(lse, lse_i)
        w_old = jnp.exp(lse - m_new)
        w_new = jnp.exp(lse_i - m_new)
        denom = jnp.maximum(w_old + w_new, 1e-30)
        out = (out * w_old[..., None] + o_i * w_new[..., None]) / denom[..., None]
        lse = m_new + jnp.log(denom)

        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        mask_blk = jax.lax.ppermute(mask_blk, axis_name, perm)
        return out, lse, k_blk, v_blk, mask_blk

    # zeros derived from q for consistent shard_map vma typing
    zero_bhqd = jnp.transpose(q.astype(jnp.float32) * 0.0, (0, 2, 1, 3))
    out0 = zero_bhqd
    lse0 = zero_bhqd[..., 0] - jnp.inf
    out, lse, _, _, _ = jax.lax.fori_loop(
        0, n, step, (out0, lse0, k, v, kv_mask)
    )
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype), lse


def _ring_bwd(q, k, v, kv_mask, out, lse, dout, axis_name, causal,
              interpret_blocks):
    """Second ring pass: recompute per-block softmax weights from the saved
    global logsumexp (exact — no stored score matrices) and accumulate dq
    locally while dk/dv ride the rotating buffers; after the full circle
    each block's gradients land back on its home device."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = float(1.0 / (D ** 0.5))
    q_pos = idx * Tq + jnp.arange(Tq)
    perm = [(i, (i + 1) % n) for i in range(n)]

    q32 = q.astype(jnp.float32)
    do = jnp.transpose(dout.astype(jnp.float32), (0, 2, 1, 3))  # [B,H,Tq,D]
    o32 = jnp.transpose(out.astype(jnp.float32), (0, 2, 1, 3))
    delta = jnp.sum(do * o32, axis=-1)  # [B, H, Tq]

    def step(i, carry):
        dq, k_blk, v_blk, mask_blk, dk_blk, dv_blk = carry
        src = (idx - i) % n
        k_pos = src * Tk + jnp.arange(Tk)
        bias = _block_bias(mask_blk, q_pos, k_pos, causal)
        dq_i, dk_i, dv_i = _block_bwd(
            q, k_blk, v_blk, bias, o32, lse, do, delta, scale,
            interpret_blocks,
        )
        dq = dq + dq_i
        dk_blk = dk_blk + dk_i
        dv_blk = dv_blk + dv_i

        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        mask_blk = jax.lax.ppermute(mask_blk, axis_name, perm)
        dk_blk = jax.lax.ppermute(dk_blk, axis_name, perm)
        dv_blk = jax.lax.ppermute(dv_blk, axis_name, perm)
        return dq, k_blk, v_blk, mask_blk, dk_blk, dv_blk

    dq0 = q32 * 0.0
    dkv0 = jnp.zeros_like(k, dtype=jnp.float32)
    dq, _, _, _, dk, dv = jax.lax.fori_loop(
        0, n, step, (dq0, k, v, kv_mask, dkv0, jnp.zeros_like(dkv0))
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def ring_flash_attention(q, k, v, kv_mask, axis_name="sp", causal=True,
                         interpret_blocks=False):
    """Ring attention with flash-style memory: the backward pass recomputes
    block scores from the saved (output, logsumexp) instead of autodiff
    storing every rotation's [Tq, Tk] score matrix — per-device residual
    memory is O(Tq·D) rather than O(Tq·T_global). Same semantics/layout as
    :func:`ring_attention`; call inside shard_map."""
    out, _ = _ring_fwd(q, k, v, kv_mask, axis_name, causal, interpret_blocks)
    return out


def _rfa_fwd(q, k, v, kv_mask, axis_name, causal, interpret_blocks):
    out, lse = _ring_fwd(q, k, v, kv_mask, axis_name, causal, interpret_blocks)
    return out, (q, k, v, kv_mask, out, lse)


def _rfa_bwd(axis_name, causal, interpret_blocks, res, dout):
    q, k, v, kv_mask, out, lse = res
    dq, dk, dv = _ring_bwd(
        q, k, v, kv_mask, out, lse, dout, axis_name, causal, interpret_blocks
    )
    return dq, dk, dv, None


ring_flash_attention.defvjp(_rfa_fwd, _rfa_bwd)


def ring_attention_sharded(
    q: jax.Array,  # [B, T, H, D] global arrays
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    kv_mask: Optional[jax.Array] = None,  # [B, T]
    axis_name: str = "sp",
    batch_axes=("dp", "fsdp"),
    causal: bool = True,
    impl: str = "flash",  # "flash" (recompute bwd) | "naive" (autodiff)
    interpret_blocks: bool = False,
) -> jax.Array:
    """shard_map wrapper: shards T over ``axis_name``, B over batch axes.

    ``impl="flash"`` (default) uses :func:`ring_flash_attention`, whose
    custom VJP recomputes block scores in a second ring pass — per-device
    residuals stay O(Tq·D) at any global length. ``impl="naive"`` keeps the
    autodiff path (stores each rotation's score panel; useful as a
    reference). ``interpret_blocks=True`` (``impl="flash"`` only) runs the
    per-block math through the pallas kernels in interpret mode whatever
    the block size or backend."""
    from jax import shard_map

    qkv_spec = P(batch_axes, axis_name, None, None)
    mask_spec = P(batch_axes, axis_name)

    if impl not in ("flash", "naive"):
        raise ValueError(f"impl must be 'flash' or 'naive', got {impl!r}")
    if interpret_blocks and impl != "flash":
        raise ValueError('interpret_blocks needs impl="flash"')

    def fn(q, k, v, m):  # custom_vjp requires positional args
        if impl == "naive":
            return ring_attention(q, k, v, m, axis_name, causal)
        return ring_flash_attention(
            q, k, v, m, axis_name, causal, interpret_blocks
        )
    if kv_mask is None:
        kv_mask = jnp.ones(q.shape[:2], jnp.int32)
    # pallas_call outputs carry no vma annotation, which trips shard_map's
    # varying-axes type check — disable it only when the pallas block path
    # will actually run; the pure-XLA paths (incl. impl="naive" at any
    # size) keep the safety check.
    sp = mesh.shape[axis_name]
    pallas_blocks = impl == "flash" and _use_pallas_blocks(
        q.shape[1] // sp, k.shape[1] // sp, interpret_blocks
    )
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec,
        check_vma=not pallas_blocks,
    )(q, k, v, kv_mask)
