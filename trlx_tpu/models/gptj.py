"""GPT-J causal LM (the reference's 6B PPO config, ``configs/ppo_gptj.yml``).

Architecture vs GPT-2: no position embeddings (rotary, interleaved
convention, applied to the first ``rotary_dim`` dims per head), attention
and MLP computed *in parallel* from one layernorm, bias-free q/k/v/out
projections, untied LM head with bias. Same call interface as
``GPT2Model`` so the PPO/ILQL trainers and samplers are family-agnostic.
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
from trlx_tpu.ops.rotary import apply_rotary_interleaved, rotary_angles


@dataclass(frozen=True)
class GPTJConfig:
    vocab_size: int = 50400
    n_positions: int = 2048
    n_embd: int = 4096
    n_layer: int = 28
    n_head: int = 16
    rotary_dim: int = 64
    layer_norm_epsilon: float = 1e-5
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # rollout KV-cache storage ("bfloat16" | "int8" | "auto"); see
    # ops/kv_cache.py — decode is HBM-bound and the
    # cache is its dominant traffic, int8 halves it
    kv_cache_dtype: str = "bfloat16"

    def __post_init__(self):
        validate_kv_cache_dtype(self.kv_cache_dtype)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "GPTJConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


GPTJ_PARTITION_RULES = [
    (r"wte/embedding", P(None, "tp")),
    (r"attn/(q_proj|k_proj|v_proj)/kernel", P(None, "tp")),
    (r"attn/out_proj/kernel", P("tp", None)),
    (r"mlp/fc_in/kernel", P(None, "tp")),
    (r"mlp/fc_out/kernel", P("tp", None)),
    (r"lm_head/kernel", P(None, "tp")),
]


class GPTJAttention(nn.Module):
    config: GPTJConfig

    @nn.compact
    def __call__(self, x, bias, position_ids, cache_kv=None, cache_index=None, causal=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        pdtype = jnp.dtype(cfg.param_dtype)
        B, T, D = x.shape
        head_dim = cfg.n_embd // cfg.n_head
        proj = lambda name: nn.Dense(
            cfg.n_embd, use_bias=False, dtype=dtype, param_dtype=pdtype, name=name
        )

        q = proj("q_proj")(x).reshape(B, T, cfg.n_head, head_dim)
        k = proj("k_proj")(x).reshape(B, T, cfg.n_head, head_dim)
        v = proj("v_proj")(x).reshape(B, T, cfg.n_head, head_dim)

        sin, cos = rotary_angles(position_ids, cfg.rotary_dim)
        q = apply_rotary_interleaved(q, sin, cos, cfg.rotary_dim)
        k = apply_rotary_interleaved(k, sin, cos, cfg.rotary_dim)

        new_kv = None
        if cache_kv is not None:
            out, new_kv = decode_attention(
                q, k, v, cache_kv, cache_index, bias, causal=causal
            )
        else:
            out = dot_product_attention(q, k, v, bias, causal=causal)
        out = out.reshape(B, T, cfg.n_embd)
        return proj("out_proj")(out), new_kv


class GPTJMLP(nn.Module):
    config: GPTJConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        pdtype = jnp.dtype(cfg.param_dtype)
        h = nn.Dense(4 * cfg.n_embd, dtype=dtype, param_dtype=pdtype, name="fc_in")(x)
        h = nn.gelu(h, approximate=True)
        return nn.Dense(cfg.n_embd, dtype=dtype, param_dtype=pdtype, name="fc_out")(h)


class GPTJBlock(nn.Module):
    config: GPTJConfig

    @nn.compact
    def __call__(self, x, bias, position_ids, cache_kv=None, cache_index=None, causal=False):
        cfg = self.config
        h = nn.LayerNorm(
            epsilon=cfg.layer_norm_epsilon, dtype=jnp.dtype(cfg.dtype), name="ln_1"
        )(x)
        attn_out, new_kv = GPTJAttention(cfg, name="attn")(
            h, bias, position_ids, cache_kv, cache_index, causal
        )
        mlp_out = GPTJMLP(cfg, name="mlp")(h)  # parallel residual branches
        return x + attn_out + mlp_out, new_kv


class GPTJModel(nn.Module):
    """Same interface as ``GPT2Model`` (incl. hydra hooks)."""

    config: GPTJConfig

    def setup(self):
        cfg = self.config
        pdtype = jnp.dtype(cfg.param_dtype)
        self.wte = nn.Embed(cfg.vocab_size, cfg.n_embd, param_dtype=pdtype, name="wte")
        self.h = [GPTJBlock(cfg, name=f"h_{i}") for i in range(cfg.n_layer)]
        self.ln_f = nn.LayerNorm(
            epsilon=cfg.layer_norm_epsilon, dtype=jnp.dtype(cfg.dtype), name="ln_f"
        )
        self.lm_head = nn.Dense(
            cfg.vocab_size,
            use_bias=True,
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
        for i in range(start_layer, cfg.n_layer):
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


def init_gptj_cache(config: GPTJConfig, batch_size: int, capacity: int):
    return kv_buffers(
        config.n_layer, batch_size, capacity, config.n_head,
        config.n_embd // config.n_head, config.dtype,
        getattr(config, "kv_cache_dtype", "bfloat16"),
    )
