"""OLMoE causal LM (Muennighoff et al. 2024; ``allenai/OLMoE-1B-7B-*``):
a pre-norm decoder whose every block has a dropless top-k expert layer.

Against the other causal families: RMSNorm (no biases anywhere); separate
``q/k/v/o`` projections with **QK-norm** - an RMSNorm over the whole
projected width, before the split into heads; half-rotation rotary on every
dimension of a head (``ops/rotary.py``, the code neox uses, ``rotary_pct``
1.0); sequential residuals ``x + attn(rms(x))`` then ``x + moe(rms(x))``;
``num_experts`` gated (SwiGLU) experts ``intermediate_size`` wide of which
a token takes the ``num_experts_per_tok`` with the largest router
probability, the weights being those probabilities as they are unless
``norm_topk_prob`` (``ops/moe.py``); an untied head. Same call interface as
``GPT2Model`` / ``NeoXModel`` (incl. hydra hooks); cached attention is
``decode_attention``, so the fixed sampler takes the fused read and the
engine the paged one.

What the published configuration may say and this family does not build is
refused by name: grouped KV heads (the ops below take them; this family's
QK-norm over the projected width does not), ``clip_qkv``, ``rope_scaling``, a tied
head, an activation other than ``silu``.

Parameters: ``wte``, ``h_<i>/{ln_1, attn/{q_proj, k_proj, v_proj, o_proj,
q_norm, k_norm}, ln_2, mlp/{router, w_gate, w_up, w_down}}`` with the
experts stacked on a leading ``[E]`` axis, ``ln_f``, ``lm_head``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from trlx_tpu.ops import moe
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
class OlmoeConfig:
    vocab_size: int = 50304
    max_position_embeddings: int = 4096
    hidden_size: int = 2048
    num_hidden_layers: int = 16
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    intermediate_size: int = 1024  # one expert's width
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = False
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    router_aux_loss_coef: float = 0.01
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    clip_qkv: Optional[float] = None
    rope_scaling: Optional[Any] = None
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    kv_cache_dtype: str = "bfloat16"

    def __post_init__(self):
        validate_kv_cache_dtype(self.kv_cache_dtype)
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                f"num_key_value_heads={self.num_key_value_heads} != "
                f"num_attention_heads={self.num_attention_heads}: grouped "
                "KV heads are not built for olmoe: its QK-norm spans the "
                "whole projected width, which the two projections would no "
                "longer share (ops/attention.py takes grouped heads; "
                "models/granite_hybrid.py uses them)"
            )
        for key in ("clip_qkv", "rope_scaling"):
            if getattr(self, key) is not None:
                raise ValueError(f"{key}={getattr(self, key)!r} is not built for olmoe")
        if self.tie_word_embeddings:
            raise ValueError("tie_word_embeddings=True is not built for olmoe")
        if self.hidden_act != "silu":
            raise ValueError(f"hidden_act={self.hidden_act!r} is not built for olmoe (silu)")

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "OlmoeConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_embd(self) -> int:
        return self.hidden_size


# attention over tp, the experts' [E] axis over ep, the router whole
OLMOE_PARTITION_RULES = [
    (r"wte/embedding", P(None, "tp")),
    (r"attn/[qkv]_proj/kernel", P(None, "tp")),
    (r"attn/o_proj/kernel", P("tp", None)),
    (r"mlp/router", P(None, None)),
    (r"mlp/w_(gate|up|down)", P("ep", None, None)),
    (r"lm_head/kernel", P(None, "tp")),
]


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale``, computed in float32."""

    epsilon: float
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), self.param_dtype)
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.epsilon)
        return (x32 * scale.astype(jnp.float32)).astype(self.dtype)


class OlmoeAttention(nn.Module):
    config: OlmoeConfig

    @nn.compact
    def __call__(self, x, bias, position_ids, cache_kv=None, cache_index=None, causal=False):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        B, T, D = x.shape
        H = cfg.num_attention_heads
        head_dim = D // H
        proj = lambda name: nn.Dense(D, use_bias=False, dtype=dtype, param_dtype=pdtype, name=name)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype, pdtype, name=name)
        # QK-norm spans the projected width, before the heads are split
        q = norm("q_norm")(proj("q_proj")(x)).reshape(B, T, H, head_dim)
        k = norm("k_norm")(proj("k_proj")(x)).reshape(B, T, H, head_dim)
        v = proj("v_proj")(x).reshape(B, T, H, head_dim)

        sin, cos = rotary_angles(position_ids, head_dim, cfg.rope_theta)
        q = apply_rotary_half(q, sin, cos, head_dim)
        k = apply_rotary_half(k, sin, cos, head_dim)

        new_kv = None
        if cache_kv is not None:
            out, new_kv = decode_attention(q, k, v, cache_kv, cache_index, bias, causal=causal)
        else:
            out = dot_product_attention(q, k, v, bias, causal=causal)
        return proj("o_proj")(out.reshape(B, T, D)), new_kv


class OlmoeSparseMLP(nn.Module):
    """The expert layer (``ops/moe.py``). Returns the output and the
    step's routing statistics."""

    config: OlmoeConfig

    @nn.compact
    def __call__(self, x, token_mask=None):
        from trlx_tpu.models.gpt2_moe import get_ep_mesh

        cfg = self.config
        D, F, E = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (D, E), pdtype)
        w_gate = self.param("w_gate", init, (E, D, F), pdtype)
        w_up = self.param("w_up", init, (E, D, F), pdtype)
        w_down = self.param("w_down", init, (E, F, D), pdtype)
        y, routing = moe.expert_layer(
            x, router, w_gate, w_up, w_down, k=cfg.num_experts_per_tok,
            norm_topk=cfg.norm_topk_prob, dtype=dtype, mesh=get_ep_mesh(),
        )
        if self.is_mutable_collection("moe_losses"):
            for name, value in moe.balance_losses(routing, E, token_mask).items():
                self.sow("moe_losses", name, value)
        return y, moe.routing_stats(routing, E)


class OlmoeBlock(nn.Module):
    config: OlmoeConfig

    @nn.compact
    def __call__(self, x, bias, position_ids, cache_kv=None, cache_index=None, causal=False,
                 token_mask=None):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype, pdtype, name=name)
        attn_out, new_kv = OlmoeAttention(cfg, name="attn")(
            norm("ln_1")(x), bias, position_ids, cache_kv, cache_index, causal
        )
        x = x + attn_out
        y, stats = OlmoeSparseMLP(cfg, name="mlp")(norm("ln_2")(x), token_mask)
        return x + y, new_kv, stats


class OlmoeModel(nn.Module):
    """Same interface as ``GPT2Model`` (incl. hydra hooks). The output also
    carries ``moe_stats``: the routing statistics of this call, averaged
    over its blocks (``max_load``: the largest), as device scalars."""

    config: OlmoeConfig

    def setup(self):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        self.wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, param_dtype=pdtype, name="wte")
        self.h = [OlmoeBlock(cfg, name=f"h_{i}") for i in range(cfg.num_hidden_layers)]
        self.ln_f = RMSNorm(cfg.rms_norm_eps, dtype, pdtype, name="ln_f")
        self.lm_head = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=dtype, param_dtype=pdtype, name="lm_head"
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
        # which tokens balance the router losses: a cached call's mask is
        # over cache slots, not over this call's tokens
        token_mask = attention_mask if cache is None else None

        per_block: List = []
        branch_hidden = None
        for i in range(start_layer, cfg.num_hidden_layers):
            if capture_hidden_at is not None and i == capture_hidden_at:
                branch_hidden = x
            x, new_kv, stats = self.h[i](
                x, bias, position_ids, layer_cache(cache, i), cache_index, causal, token_mask
            )
            cache = with_layer_cache(cache, i, new_kv)
            per_block.append(stats)

        x = self.ln_f(x)
        out = {
            "logits": self.logits(x) if compute_logits else None,
            "hidden": x,
            "cache": cache,
        }
        if per_block:
            stacked = {k: jnp.stack([s[k] for s in per_block]) for k in per_block[0]}
            out["moe_stats"] = {
                "experts_touched": jnp.mean(stacked["experts_touched"]),
                "max_load": jnp.max(stacked["max_load"]),
                "rows_routed": jnp.sum(stacked["rows_routed"]),
            }
        if capture_hidden_at is not None:
            out["branch_hidden"] = branch_hidden
        return out


def init_olmoe_cache(config: OlmoeConfig, batch_size: int, capacity: int):
    return kv_buffers(
        config.num_hidden_layers, batch_size, capacity,
        config.num_attention_heads,
        config.hidden_size // config.num_attention_heads, config.dtype,
        config.kv_cache_dtype,
    )
