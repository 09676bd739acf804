"""T5/UL2 encoder-decoder, written TPU-first in flax.linen.

Native re-implementation of the architecture behind the fork's
``T5HeadWithValueModel`` (``trlx/model/nn/ppo_models.py:607-655``, which
wraps HF ``AutoModelForSeq2SeqLM`` in bf16). Differences from the GPT-2
stack that this file owns:

- RMS layer norm without bias/mean-centering (fp32), pre-norm residuals;
- relative position bias buckets (encoder bidirectional, decoder causal),
  parameterized only in layer 0 and shared down the stack;
- unscaled attention (T5 folds the 1/sqrt(d) into initialization);
- ReLU or gated-GELU feed-forward (UL2/v1.1 uses gated);
- tied or untied LM head (v1.1/UL2 untie; tied head rescales by
  ``d_model**-0.5``);
- decoder self-attention KV cache + precomputed cross-attention KV for the
  compiled seq2seq sampler (``ops/sampling.py::make_seq2seq_sampler``).

Weight-compatible with HF T5/MT5/UL2 checkpoints via
``trlx_tpu.models.conversion.convert_t5_state_dict`` (torch ``nn.Linear``
stores (out, in): kernels transpose on conversion).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from trlx_tpu.ops.attention import (
    NEG_INF,
    decode_attention,
    dot_product_attention,
)


@dataclass(frozen=True)
class T5Config:
    """Architecture hyperparameters (HF ``T5Config`` field names)."""

    vocab_size: int = 32128
    d_model: int = 512
    d_kv: int = 64
    d_ff: int = 2048
    num_layers: int = 6
    num_decoder_layers: int = 6
    num_heads: int = 8
    relative_attention_num_buckets: int = 32
    relative_attention_max_distance: int = 128
    layer_norm_epsilon: float = 1e-6
    feed_forward_proj: str = "relu"  # "relu" | "gated-gelu"
    tie_word_embeddings: bool = True
    decoder_start_token_id: int = 0
    dtype: str = "bfloat16"
    param_dtype: str = "float32"

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "T5Config":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @property
    def is_gated_act(self) -> bool:
        return "gated" in self.feed_forward_proj


# TP rules: attention and FF input projections shard outputs; output
# projections shard inputs (one activation all-reduce per sub-layer).
T5_PARTITION_RULES = [
    (r"shared/embedding", P(None, "tp")),
    (r"(SelfAttention|EncDecAttention)/(q|k|v)/kernel", P(None, "tp")),
    (r"(SelfAttention|EncDecAttention)/o/kernel", P("tp", None)),
    (r"DenseReluDense/(wi|wi_0|wi_1)/kernel", P(None, "tp")),
    (r"DenseReluDense/wo/kernel", P("tp", None)),
    (r"lm_head/kernel", P(None, "tp")),
]


class T5LayerNorm(nn.Module):
    """RMS norm: no mean subtraction, no bias, fp32 accumulation."""

    epsilon: float = 1e-6
    dtype: str = "bfloat16"
    param_dtype: str = "float32"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param(
            "weight", nn.initializers.ones, (x.shape[-1],), jnp.dtype(self.param_dtype)
        )
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        xf = xf * jax.lax.rsqrt(var + self.epsilon)
        return (xf * scale).astype(jnp.dtype(self.dtype))


def relative_position_bucket(
    relative_position: jax.Array,
    bidirectional: bool,
    num_buckets: int,
    max_distance: int,
) -> jax.Array:
    """T5's log-spaced relative position bucketing (jit-safe)."""
    ret = jnp.zeros_like(relative_position)
    n = -relative_position
    if bidirectional:
        num_buckets //= 2
        ret = ret + (n < 0).astype(jnp.int32) * num_buckets
        n = jnp.abs(n)
    else:
        n = jnp.maximum(n, 0)
    max_exact = num_buckets // 2
    is_small = n < max_exact
    val_if_large = max_exact + (
        jnp.log(jnp.maximum(n, 1).astype(jnp.float32) / max_exact)
        / jnp.log(max_distance / max_exact)
        * (num_buckets - max_exact)
    ).astype(jnp.int32)
    val_if_large = jnp.minimum(val_if_large, num_buckets - 1)
    return ret + jnp.where(is_small, n, val_if_large)


class RelPosBias(nn.Module):
    """Relative attention bias embedding -> [1, H, Q, K] additive bias."""

    config: T5Config
    bidirectional: bool

    @nn.compact
    def __call__(self, q_positions: jax.Array, k_positions: jax.Array) -> jax.Array:
        cfg = self.config
        rel = k_positions[None, :] - q_positions[:, None]  # [Q, K]
        buckets = relative_position_bucket(
            rel,
            self.bidirectional,
            cfg.relative_attention_num_buckets,
            cfg.relative_attention_max_distance,
        )
        table = nn.Embed(
            cfg.relative_attention_num_buckets,
            cfg.num_heads,
            param_dtype=jnp.dtype(cfg.param_dtype),
            name="relative_attention_bias",
        )
        bias = table(buckets)  # [Q, K, H]
        return jnp.transpose(bias, (2, 0, 1))[None].astype(jnp.float32)


class T5Attention(nn.Module):
    config: T5Config

    def setup(self):
        cfg = self.config
        inner = cfg.num_heads * cfg.d_kv
        kw = dict(
            use_bias=False,
            dtype=jnp.dtype(cfg.dtype),
            param_dtype=jnp.dtype(cfg.param_dtype),
        )
        self.q = nn.Dense(inner, **kw)
        self.k = nn.Dense(inner, **kw)
        self.v = nn.Dense(inner, **kw)
        self.o = nn.Dense(cfg.d_model, **kw)

    def __call__(
        self,
        x: jax.Array,  # [B, T, D] (already layer-normed)
        kv_source: Optional[jax.Array] = None,  # cross-attn keys source
        bias: Optional[jax.Array] = None,  # additive [*, H or 1, Q, K]
        cache_kv: Optional[Dict[str, jax.Array]] = None,
        cache_index: Optional[jax.Array] = None,
        static_kv: Optional[Tuple[jax.Array, jax.Array]] = None,  # precomputed cross k,v
        learned_bias: bool = False,  # True when bias carries the rel-pos table
    ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
        cfg = self.config
        B, T, _ = x.shape
        inner = cfg.num_heads * cfg.d_kv

        # T5 attention is unscaled: pre-multiply q by sqrt(d_kv) to cancel
        # the 1/sqrt(d) inside the shared attention core.
        q = self.q(x).reshape(B, T, cfg.num_heads, cfg.d_kv)
        q = q * jnp.asarray(cfg.d_kv, q.dtype) ** 0.5
        new_kv = None
        if static_kv is not None:
            k, v = static_kv
        else:
            src = x if kv_source is None else kv_source
            S = src.shape[1]
            k = self.k(src).reshape(B, S, cfg.num_heads, cfg.d_kv)
            v = self.v(src).reshape(B, S, cfg.num_heads, cfg.d_kv)
        if cache_kv is None or static_kv is not None:
            out = dot_product_attention(
                q, k, v, bias, learned_bias=learned_bias
            )
        else:
            # shared cached-attention entry (would make int8 a config flip
            # for seq2seq decode too; t5 currently ships bf16 only); the
            # learned per-head bias keeps it on the generic read
            out, new_kv = decode_attention(
                q, k, v, cache_kv, cache_index, bias,
                learned_bias=learned_bias,
            )
        out = out.reshape(B, T, inner)
        return self.o(out), new_kv

    def project_kv(self, src: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Precompute cross-attention K/V from encoder output (decode path)."""
        cfg = self.config
        B, S, _ = src.shape
        return (
            self.k(src).reshape(B, S, cfg.num_heads, cfg.d_kv),
            self.v(src).reshape(B, S, cfg.num_heads, cfg.d_kv),
        )


class T5FF(nn.Module):
    config: T5Config

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        pdtype = jnp.dtype(cfg.param_dtype)
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=dtype, param_dtype=pdtype, name=name
        )
        if cfg.is_gated_act:
            # HF "gated-gelu" resolves to gelu_new (tanh approximation)
            h = nn.gelu(dense(cfg.d_ff, "wi_0")(x), approximate=True) * dense(
                cfg.d_ff, "wi_1"
            )(x)
        else:
            h = nn.relu(dense(cfg.d_ff, "wi")(x))
        return dense(cfg.d_model, "wo")(h)


class T5EncoderBlock(nn.Module):
    config: T5Config

    @nn.compact
    def __call__(self, x, bias):
        cfg = self.config
        ln = lambda name: T5LayerNorm(
            cfg.layer_norm_epsilon, cfg.dtype, cfg.param_dtype, name=name
        )
        h, _ = T5Attention(cfg, name="SelfAttention")(
            ln("ln_self")(x), bias=bias, learned_bias=True
        )
        x = x + h
        x = x + T5FF(cfg, name="DenseReluDense")(ln("ln_ff")(x))
        return x


class T5DecoderBlock(nn.Module):
    config: T5Config

    def setup(self):
        cfg = self.config
        ln = lambda: T5LayerNorm(cfg.layer_norm_epsilon, cfg.dtype, cfg.param_dtype)
        self.ln_self = ln()
        self.SelfAttention = T5Attention(cfg)
        self.ln_cross = ln()
        self.EncDecAttention = T5Attention(cfg)
        self.ln_ff = ln()
        self.DenseReluDense = T5FF(cfg)

    def __call__(
        self,
        x,
        self_bias,
        cross_bias,
        encoder_hidden=None,
        cache_kv=None,
        cache_index=None,
        cross_kv=None,
    ):
        h, new_kv = self.SelfAttention(
            self.ln_self(x), bias=self_bias,
            cache_kv=cache_kv, cache_index=cache_index, learned_bias=True,
        )
        x = x + h
        h, _ = self.EncDecAttention(
            self.ln_cross(x),
            kv_source=encoder_hidden,
            bias=cross_bias,
            static_kv=cross_kv,
        )
        x = x + h
        x = x + self.DenseReluDense(self.ln_ff(x))
        return x, new_kv

    def cross_kv(self, encoder_hidden):
        return self.EncDecAttention.project_kv(encoder_hidden)


class T5Model(nn.Module):
    """Encoder-decoder with explicit decode cache.

    Methods (all usable via ``apply(..., method=...)``):
    - ``__call__``: full training forward (teacher-forced decoder);
    - ``encode``: encoder only;
    - ``decode``: decoder with optional KV cache + precomputed cross-KV;
    - ``init_cross_kv``: per-layer cross-attention K/V from encoder output.
    """

    config: T5Config

    def setup(self):
        cfg = self.config
        self.shared = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            param_dtype=jnp.dtype(cfg.param_dtype),
            name="shared",
        )
        self.enc_rel_bias = RelPosBias(cfg, bidirectional=True, name="enc_rel_bias")
        self.dec_rel_bias = RelPosBias(cfg, bidirectional=False, name="dec_rel_bias")
        self.enc_blocks = [
            T5EncoderBlock(cfg, name=f"enc_{i}") for i in range(cfg.num_layers)
        ]
        self.dec_blocks = [
            T5DecoderBlock(cfg, name=f"dec_{i}")
            for i in range(cfg.num_decoder_layers)
        ]
        self.enc_final_ln = T5LayerNorm(
            cfg.layer_norm_epsilon, cfg.dtype, cfg.param_dtype, name="enc_final_ln"
        )
        self.dec_final_ln = T5LayerNorm(
            cfg.layer_norm_epsilon, cfg.dtype, cfg.param_dtype, name="dec_final_ln"
        )
        if not cfg.tie_word_embeddings:
            self.lm_head = nn.Dense(
                cfg.vocab_size,
                use_bias=False,
                dtype=jnp.dtype(cfg.dtype),
                param_dtype=jnp.dtype(cfg.param_dtype),
                name="lm_head",
            )

    def encode(self, input_ids: jax.Array, attention_mask: Optional[jax.Array] = None):
        cfg = self.config
        T = input_ids.shape[1]
        x = self.shared(input_ids).astype(jnp.dtype(cfg.dtype))
        pos = jnp.arange(T)
        bias = self.enc_rel_bias(pos, pos)  # [1, H, T, T]
        if attention_mask is not None:
            bias = bias + jnp.where(
                attention_mask[:, None, None, :] > 0, 0.0, NEG_INF
            )
        for block in self.enc_blocks:
            x = block(x, bias)
        return self.enc_final_ln(x)

    def logits(self, hidden: jax.Array) -> jax.Array:
        cfg = self.config
        if cfg.tie_word_embeddings:
            # T5 1.0 rescales tied-head inputs by d_model**-0.5
            hidden = hidden * (cfg.d_model**-0.5)
            emb = self.shared.embedding.astype(hidden.dtype)
            return jnp.einsum(
                "btd,vd->btv", hidden, emb, preferred_element_type=jnp.float32
            )
        return self.lm_head(hidden).astype(jnp.float32)

    def init_cross_kv(self, encoder_hidden: jax.Array):
        return tuple(b.cross_kv(encoder_hidden) for b in self.dec_blocks)

    def decode(
        self,
        decoder_input_ids: jax.Array,  # [B, T]
        encoder_hidden: Optional[jax.Array] = None,
        encoder_mask: Optional[jax.Array] = None,
        decoder_mask: Optional[jax.Array] = None,  # [B, T] (training) / [B, C] (cache)
        cache: Optional[Tuple] = None,
        cache_index: Optional[jax.Array] = None,
        cross_kv: Optional[Tuple] = None,
    ):
        cfg = self.config
        B, T = decoder_input_ids.shape
        x = self.shared(decoder_input_ids).astype(jnp.dtype(cfg.dtype))

        if cache is None:
            q_pos = jnp.arange(T)
            k_pos = jnp.arange(T)
            causal = jnp.where(
                k_pos[None, :] <= q_pos[:, None], 0.0, NEG_INF
            )[None, None]
            self_bias = self.dec_rel_bias(q_pos, k_pos) + causal
            if decoder_mask is not None:
                self_bias = self_bias + jnp.where(
                    decoder_mask[:, None, None, :] > 0, 0.0, NEG_INF
                )
        else:
            C = cache[0]["k"].shape[1]
            q_pos = cache_index + jnp.arange(T)
            k_pos = jnp.arange(C)
            causal = jnp.where(
                k_pos[None, :] <= q_pos[:, None], 0.0, NEG_INF
            )[None, None]
            self_bias = self.dec_rel_bias(q_pos, k_pos) + causal
            if decoder_mask is not None:
                self_bias = self_bias + jnp.where(
                    decoder_mask[:, None, None, :] > 0, 0.0, NEG_INF
                )

        cross_bias = None
        if encoder_mask is not None:
            cross_bias = jnp.where(
                encoder_mask[:, None, None, :] > 0, 0.0, NEG_INF
            ).astype(jnp.float32)

        new_cache: List = []
        for i, block in enumerate(self.dec_blocks):
            x, new_kv = block(
                x,
                self_bias,
                cross_bias,
                encoder_hidden=encoder_hidden,
                cache_kv=cache[i] if cache is not None else None,
                cache_index=cache_index,
                cross_kv=cross_kv[i] if cross_kv is not None else None,
            )
            new_cache.append(new_kv)

        x = self.dec_final_ln(x)
        return {
            "logits": self.logits(x),
            "hidden": x,
            "cache": tuple(new_cache) if cache is not None else None,
        }

    def __call__(
        self,
        input_ids: jax.Array,
        attention_mask: Optional[jax.Array] = None,
        decoder_input_ids: Optional[jax.Array] = None,
        decoder_attention_mask: Optional[jax.Array] = None,
    ):
        """Teacher-forced training forward; returns logits/hidden over the
        decoder sequence plus the encoder output."""
        encoder_hidden = self.encode(input_ids, attention_mask)
        out = self.decode(
            decoder_input_ids,
            encoder_hidden=encoder_hidden,
            encoder_mask=attention_mask,
            decoder_mask=decoder_attention_mask,
        )
        out["encoder_hidden"] = encoder_hidden
        return out


def init_t5_cache(config: T5Config, batch_size: int, capacity: int):
    """Fixed-capacity decoder self-attention KV buffers."""
    shape = (batch_size, capacity, config.num_heads, config.d_kv)
    dtype = jnp.dtype(config.dtype)
    return tuple(
        {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        for _ in range(config.num_decoder_layers)
    )


def shift_tokens_right(
    input_ids: jax.Array, pad_token_id: int, decoder_start_token_id: int
) -> jax.Array:
    """Teacher-forcing shift (reference `accelerate_ppo_model.py:18-25`)."""
    shifted = jnp.concatenate(
        [
            jnp.full_like(input_ids[:, :1], decoder_start_token_id),
            input_ids[:, :-1],
        ],
        axis=1,
    )
    return jnp.where(shifted == -100, pad_token_id, shifted)
