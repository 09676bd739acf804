"""GPT-NeoX causal LM (the reference README's 20B stretch target).

Architecture vs GPT-J: fused QKV projection (HF's head-major ``[H, 3*Dh]``
layout preserved so conversion is a transpose-only copy), partial rotary
(``rotary_pct`` of each head dim, half-rotation convention), parallel
residual with *separate* layernorms for attention and MLP
(``use_parallel_residual``), untied ``embed_out`` head without bias.
Same call interface as ``GPT2Model``/``GPTJModel``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from trlx_tpu.ops.attention import (
    causal_dispatch,
    decode_attention,
    dot_product_attention,
)
from trlx_tpu.ops.kv_cache import (
    kv_buffers,
    layer_cache,
    validate_kv_cache_dtype,
    with_layer_cache,
)
from trlx_tpu.ops.rotary import apply_rotary_half, rotary_angles


@dataclass(frozen=True)
class NeoXConfig:
    vocab_size: int = 50432
    max_position_embeddings: int = 2048
    hidden_size: int = 6144
    num_hidden_layers: int = 44
    num_attention_heads: int = 64
    rotary_pct: float = 0.25
    rotary_emb_base: float = 10000.0
    use_parallel_residual: bool = True
    layer_norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # rollout KV-cache storage ("bfloat16" | "int8" | "auto"); see
    # ops/kv_cache.py — decode is HBM-bound and the
    # cache is its dominant traffic, int8 halves it
    kv_cache_dtype: str = "bfloat16"

    def __post_init__(self):
        validate_kv_cache_dtype(self.kv_cache_dtype)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "NeoXConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_embd(self) -> int:
        return self.hidden_size


NEOX_PARTITION_RULES = [
    (r"wte/embedding", P(None, "tp")),
    (r"attn/query_key_value/kernel", P(None, "tp")),
    (r"attn/dense/kernel", P("tp", None)),
    (r"mlp/dense_h_to_4h/kernel", P(None, "tp")),
    (r"mlp/dense_4h_to_h/kernel", P("tp", None)),
    (r"lm_head/kernel", P(None, "tp")),
]


class NeoXAttention(nn.Module):
    config: NeoXConfig

    @nn.compact
    def __call__(self, x, bias, position_ids, cache_kv=None, cache_index=None, causal=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        pdtype = jnp.dtype(cfg.param_dtype)
        B, T, D = x.shape
        H = cfg.num_attention_heads
        head_dim = cfg.hidden_size // H
        rotary_dim = int(head_dim * cfg.rotary_pct)

        qkv = nn.Dense(
            3 * cfg.hidden_size, dtype=dtype, param_dtype=pdtype,
            name="query_key_value",
        )(x)
        # HF layout: [B, T, H, 3*Dh] -> q/k/v slices per head
        qkv = qkv.reshape(B, T, H, 3 * head_dim)
        q = qkv[..., :head_dim]
        k = qkv[..., head_dim : 2 * head_dim]
        v = qkv[..., 2 * head_dim :]

        sin, cos = rotary_angles(position_ids, rotary_dim, cfg.rotary_emb_base)
        q = apply_rotary_half(q, sin, cos, rotary_dim)
        k = apply_rotary_half(k, sin, cos, rotary_dim)

        new_kv = None
        if cache_kv is not None:
            out, new_kv = decode_attention(
                q, k, v, cache_kv, cache_index, bias, causal=causal
            )
        else:
            out = dot_product_attention(q, k, v, bias, causal=causal)
        out = out.reshape(B, T, cfg.hidden_size)
        out = nn.Dense(
            cfg.hidden_size, dtype=dtype, param_dtype=pdtype, name="dense"
        )(out)
        return out, new_kv


class NeoXMLP(nn.Module):
    config: NeoXConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        pdtype = jnp.dtype(cfg.param_dtype)
        h = nn.Dense(
            4 * cfg.hidden_size, dtype=dtype, param_dtype=pdtype,
            name="dense_h_to_4h",
        )(x)
        h = nn.gelu(h, approximate=True)
        return nn.Dense(
            cfg.hidden_size, dtype=dtype, param_dtype=pdtype, name="dense_4h_to_h"
        )(h)


class NeoXBlock(nn.Module):
    config: NeoXConfig

    @nn.compact
    def __call__(self, x, bias, position_ids, cache_kv=None, cache_index=None, causal=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        ln_attn = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=dtype, name="ln_1")(x)
        attn_out, new_kv = NeoXAttention(cfg, name="attn")(
            ln_attn, bias, position_ids, cache_kv, cache_index, causal
        )
        if cfg.use_parallel_residual:
            ln_mlp = nn.LayerNorm(
                epsilon=cfg.layer_norm_eps, dtype=dtype, name="ln_2"
            )(x)
            return x + attn_out + NeoXMLP(cfg, name="mlp")(ln_mlp), new_kv
        x = x + attn_out
        ln_mlp = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=dtype, name="ln_2")(x)
        return x + NeoXMLP(cfg, name="mlp")(ln_mlp), new_kv


class NeoXModel(nn.Module):
    """Same interface as ``GPT2Model`` (incl. hydra hooks)."""

    config: NeoXConfig

    def setup(self):
        cfg = self.config
        pdtype = jnp.dtype(cfg.param_dtype)
        self.wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, param_dtype=pdtype, name="wte"
        )
        self.h = [NeoXBlock(cfg, name=f"h_{i}") for i in range(cfg.num_hidden_layers)]
        self.ln_f = nn.LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=jnp.dtype(cfg.dtype), name="ln_f"
        )
        self.lm_head = nn.Dense(
            cfg.vocab_size,
            use_bias=False,
            dtype=jnp.dtype(cfg.dtype),
            param_dtype=pdtype,
            name="lm_head",
        )

    def logits(self, hidden: jax.Array) -> jax.Array:
        """LM head on (already ln_f-normalized) hidden states; float32."""
        return self.lm_head(hidden).astype(jnp.float32)

    def __call__(
        self,
        input_ids: jax.Array,
        attention_mask: Optional[jax.Array] = None,
        position_ids: Optional[jax.Array] = None,
        cache=None,
        cache_index=None,
        start_layer: int = 0,
        hidden_override: Optional[jax.Array] = None,
        capture_hidden_at: Optional[int] = None,
        compute_logits: bool = True,
    ):
        cfg = self.config
        T = input_ids.shape[1] if hidden_override is None else hidden_override.shape[1]

        if position_ids is None:
            if attention_mask is not None and cache is None:
                position_ids = jnp.clip(jnp.cumsum(attention_mask, axis=-1) - 1, 0, None)
            else:
                position_ids = jnp.broadcast_to(
                    jnp.arange(T)[None, :], (input_ids.shape[0], T)
                )
        else:
            position_ids = jnp.broadcast_to(position_ids, (input_ids.shape[0], T))

        if hidden_override is not None:
            x = hidden_override.astype(jnp.dtype(cfg.dtype))
        else:
            x = self.wte(input_ids).astype(jnp.dtype(cfg.dtype))

        bias, causal = causal_dispatch(T, cache, cache_index, attention_mask)

        branch_hidden = None
        for i in range(start_layer, cfg.num_hidden_layers):
            if capture_hidden_at is not None and i == capture_hidden_at:
                branch_hidden = x
            x, new_kv = self.h[i](x, bias, position_ids, layer_cache(cache, i), cache_index, causal)
            cache = with_layer_cache(cache, i, new_kv)

        x = self.ln_f(x)
        out = {
            "logits": self.logits(x) if compute_logits else None,
            "hidden": x,
            "cache": cache,
        }
        if capture_hidden_at is not None:
            out["branch_hidden"] = branch_hidden
        return out


def init_neox_cache(config: NeoXConfig, batch_size: int, capacity: int):
    return kv_buffers(
        config.num_hidden_layers, batch_size, capacity,
        config.num_attention_heads,
        config.hidden_size // config.num_attention_heads, config.dtype,
        getattr(config, "kv_cache_dtype", "bfloat16"),
    )
