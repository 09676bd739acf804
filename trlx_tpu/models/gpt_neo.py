"""GPT-Neo causal LM, written TPU-first in flax.linen.

Completes the reference's supported causal-LM families ("gpt2, gpt-j,
gpt-neo, gpt-neox up to 20B" — reference ``README.md:6``,
``docs/source/index.rst:8-9``); the reference gets the architecture from HF
torch via ``AutoModelForCausalLM`` (``ilql_models.py:187``,
``ppo_models.py:233``). Architecture deltas vs GPT-2:

- separate bias-free q/k/v projections, ``out_proj`` with bias;
- **unscaled** attention logits (no 1/sqrt(d); folded into init by EleutherAI)
  — implemented by pre-multiplying q by sqrt(d) to cancel the shared
  attention core's scale, as T5 does;
- alternating global / local (sliding-window, default 256) attention layers
  per ``attention_types``; local layers use an explicit band bias;
- MLP ``c_fc``/``c_proj`` are torch ``nn.Linear`` (kernels transpose on
  conversion, unlike GPT-2's Conv1D);
- tied LM head, learned position embeddings.

Same call interface as ``GPT2Model`` (incl. hydra ``start_layer`` /
``capture_hidden_at`` hooks and the explicit KV cache).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from trlx_tpu.ops.attention import (
    NEG_INF,
    causal_dispatch,
    combine_biases,
    decode_attention,
    dot_product_attention,
    padding_bias,
)
from trlx_tpu.ops.kv_cache import (
    kv_buffers,
    layer_cache,
    validate_kv_cache_dtype,
    with_layer_cache,
)


def expand_attention_types(attention_types, n_layer: int) -> Tuple[str, ...]:
    """HF ``[[["global", "local"], 12]]`` -> per-layer type tuple."""
    if not attention_types:
        return tuple("global" for _ in range(n_layer))
    layers: List[str] = []
    for pattern, repeat in attention_types:
        layers.extend(list(pattern) * repeat)
    if len(layers) != n_layer:
        raise ValueError(
            f"attention_types expands to {len(layers)} layers, expected {n_layer}"
        )
    return tuple(layers)


@dataclass(frozen=True)
class GPTNeoConfig:
    """Architecture hyperparameters (HF ``GPTNeoConfig`` field names)."""

    vocab_size: int = 50257
    max_position_embeddings: int = 2048
    hidden_size: int = 2048
    num_layers: int = 24
    num_heads: int = 16
    intermediate_size: Optional[int] = None  # None -> 4 * hidden
    window_size: int = 256
    attention_layers: Tuple[str, ...] = ()  # per-layer "global"/"local"; () -> all global
    layer_norm_epsilon: float = 1e-5
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # rollout KV-cache storage ("bfloat16" | "int8" | "auto"); see
    # ops/kv_cache.py — decode is HBM-bound and the
    # cache is its dominant traffic, int8 halves it
    kv_cache_dtype: str = "bfloat16"

    def __post_init__(self):
        validate_kv_cache_dtype(self.kv_cache_dtype)

    @property
    def layer_types(self) -> Tuple[str, ...]:
        if self.attention_layers:
            return self.attention_layers
        return tuple("global" for _ in range(self.num_layers))

    @property
    def inner_dim(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "GPTNeoConfig":
        d = dict(d)
        if "attention_types" in d and "attention_layers" not in d:
            d["attention_layers"] = expand_attention_types(
                d.pop("attention_types"), d.get("num_layers", cls.num_layers)
            )
        if isinstance(d.get("attention_layers"), list):
            d["attention_layers"] = tuple(d["attention_layers"])
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


GPT_NEO_PARTITION_RULES = [
    (r"wte/embedding", P(None, "tp")),
    (r"attn/(q_proj|k_proj|v_proj)/kernel", P(None, "tp")),
    (r"attn/out_proj/kernel", P("tp", None)),
    (r"mlp/c_fc/kernel", P(None, "tp")),
    (r"mlp/c_proj/kernel", P("tp", None)),
]


def local_causal_bias(
    q_len: int,
    kv_len: int,
    window: int,
    offset=0,
    dtype=jnp.float32,
) -> jax.Array:
    """[1, 1, Q, K] band bias: j <= i and i - j < window (sliding window).

    Matches HF GPT-Neo local attention: each query sees at most ``window``
    most recent positions including itself. A [B]-vector ``offset`` (rows
    decoding at different cache depths — the continuous-batching
    engine's per-row ``cache_index``) yields a [B, 1, Q, K] bias, the
    same contract as ``ops/attention.py::causal_bias`` — without this
    branch the slot-admission engine could not serve local-attention
    GPT-Neo configs at all.
    """
    off = jnp.asarray(offset)
    k_pos = jnp.arange(kv_len)[None, :]
    if off.ndim:
        q_pos = (
            jnp.arange(q_len)[None, :, None]
            + off.astype(jnp.int32)[:, None, None]
        )  # [B, Q, 1]
        kb = k_pos[None, :, :]
        visible = (kb <= q_pos) & (q_pos - kb < window)
        return jnp.where(visible, 0.0, NEG_INF).astype(dtype)[:, None, :, :]
    q_pos = jnp.arange(q_len)[:, None] + off
    visible = (k_pos <= q_pos) & (q_pos - k_pos < window)
    return jnp.where(visible, 0.0, NEG_INF).astype(dtype)[None, None, :, :]


class GPTNeoAttention(nn.Module):
    """Windowing is decided by the caller: local layers receive an explicit
    band bias, global layers the shared causal flag/bias — the module itself
    is type-agnostic."""

    config: GPTNeoConfig

    @nn.compact
    def __call__(self, x, bias, cache_kv=None, cache_index=None, causal=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        pdtype = jnp.dtype(cfg.param_dtype)
        B, T, D = x.shape
        head_dim = cfg.hidden_size // cfg.num_heads

        proj = lambda name, use_bias: nn.Dense(
            cfg.hidden_size, use_bias=use_bias, dtype=dtype,
            param_dtype=pdtype, name=name,
        )
        q = proj("q_proj", False)(x).reshape(B, T, cfg.num_heads, head_dim)
        k = proj("k_proj", False)(x).reshape(B, T, cfg.num_heads, head_dim)
        v = proj("v_proj", False)(x).reshape(B, T, cfg.num_heads, head_dim)

        # GPT-Neo does not scale attention logits; cancel the shared core's
        # 1/sqrt(d) (HF computes q @ k^T directly in float32).
        q = q * jnp.asarray(head_dim, q.dtype) ** 0.5
        new_kv = None
        if cache_kv is not None:
            out, new_kv = decode_attention(
                q, k, v, cache_kv, cache_index, bias, causal=causal
            )
        else:
            out = dot_product_attention(q, k, v, bias, causal=causal)
        out = out.reshape(B, T, cfg.hidden_size)
        return proj("out_proj", True)(out), new_kv


class GPTNeoMLP(nn.Module):
    config: GPTNeoConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        pdtype = jnp.dtype(cfg.param_dtype)
        h = nn.Dense(cfg.inner_dim, dtype=dtype, param_dtype=pdtype, name="c_fc")(x)
        h = nn.gelu(h, approximate=True)  # gelu_new
        return nn.Dense(cfg.hidden_size, dtype=dtype, param_dtype=pdtype, name="c_proj")(h)


class GPTNeoBlock(nn.Module):
    config: GPTNeoConfig

    @nn.compact
    def __call__(self, x, bias, cache_kv=None, cache_index=None, causal=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        eps = cfg.layer_norm_epsilon
        h = nn.LayerNorm(epsilon=eps, dtype=dtype, name="ln_1")(x)
        attn_out, new_kv = GPTNeoAttention(cfg, name="attn")(
            h, bias, cache_kv, cache_index, causal
        )
        x = x + attn_out
        h = nn.LayerNorm(epsilon=eps, dtype=dtype, name="ln_2")(x)
        x = x + GPTNeoMLP(cfg, name="mlp")(h)
        return x, new_kv


class GPTNeoModel(nn.Module):
    """Same interface as ``GPT2Model`` (incl. hydra hooks)."""

    config: GPTNeoConfig

    def setup(self):
        cfg = self.config
        pdtype = jnp.dtype(cfg.param_dtype)
        self.wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, param_dtype=pdtype, name="wte"
        )
        self.wpe = nn.Embed(
            cfg.max_position_embeddings, cfg.hidden_size, param_dtype=pdtype,
            name="wpe",
        )
        self.h = [GPTNeoBlock(cfg, name=f"h_{i}") for i in range(cfg.num_layers)]
        self.ln_f = nn.LayerNorm(
            epsilon=cfg.layer_norm_epsilon, dtype=jnp.dtype(cfg.dtype), name="ln_f"
        )

    def logits(self, hidden: jax.Array) -> jax.Array:
        emb = self.wte.embedding.astype(jnp.dtype(self.config.dtype))
        return jnp.einsum(
            "btd,vd->btv", hidden, emb, preferred_element_type=jnp.float32
        )

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

        if hidden_override is not None:
            x = hidden_override.astype(jnp.dtype(cfg.dtype))
        else:
            if position_ids is None:
                if attention_mask is not None and cache is None:
                    position_ids = jnp.clip(
                        jnp.cumsum(attention_mask, axis=-1) - 1, 0, None
                    )
                else:
                    position_ids = jnp.arange(T)[None, :]
            # per-table rounding before the add: keeps the sum invariant to
            # f32-master vs compute-dtype-cast params (rollout weight cast)
            dtype = jnp.dtype(cfg.dtype)
            x = self.wte(input_ids).astype(dtype) + self.wpe(
                position_ids
            ).astype(dtype)

        # global layers share the causal-LM dispatch; local layers always
        # need an explicit band bias (the window isn't expressible as the
        # kernels' causal flag).
        global_bias, causal = causal_dispatch(T, cache, cache_index, attention_mask)
        pad = padding_bias(attention_mask) if attention_mask is not None else None
        if cache is None:
            kv_len, offset = T, 0
        else:
            # mask width == attention view width (the chunked prefill's
            # prompt-only mask narrows the cache view; full-capacity
            # callers are unchanged) — must agree with causal_dispatch
            # or the local band bias misaligns with the padding bias
            kv_len = (
                attention_mask.shape[-1]
                if attention_mask is not None
                else cache[0]["k"].shape[1]
            )
            offset = cache_index
        local_bias = combine_biases(
            local_causal_bias(T, kv_len, cfg.window_size, offset=offset), pad
        )

        types = cfg.layer_types
        branch_hidden = None
        for i in range(start_layer, cfg.num_layers):
            if capture_hidden_at is not None and i == capture_hidden_at:
                branch_hidden = x
            if types[i] == "local":
                x, new_kv = self.h[i](x, local_bias, layer_cache(cache, i), cache_index, False)
            else:
                x, new_kv = self.h[i](x, global_bias, layer_cache(cache, i), cache_index, causal)
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


def init_gpt_neo_cache(config: GPTNeoConfig, batch_size: int, capacity: int):
    return kv_buffers(
        config.num_layers, batch_size, capacity, config.num_heads,
        config.hidden_size // config.num_heads, config.dtype,
        getattr(config, "kv_cache_dtype", "bfloat16"),
    )
