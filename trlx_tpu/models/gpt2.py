"""GPT-2-family causal LM, written TPU-first in flax.linen.

Native re-implementation of the architecture behind the reference's
``GPTHeadWithValueModel`` / ``GPTHydraHeadWithValueModel``
(``trlx/model/nn/ppo_models.py:225-603``), which wrap HF torch GPT-2. Here
the transformer itself is a JAX module so that:

- generation runs as one compiled program (prefill + ``lax.scan`` decode over
  an explicit KV-cache pytree) instead of HF's Python token loop;
- hidden-dim / head-dim matmuls carry tensor-parallel sharding rules
  (``partition_rules``) for the mesh's ``tp`` axis;
- the hydra frozen-branch trick (`ppo_models.py:505-558`) is a plain
  ``blocks_from`` method re-running the top-k blocks with frozen params.

Weight-compatible with HF GPT-2 checkpoints via
``trlx_tpu.models.conversion`` (HF Conv1D stores kernels as (in, out), which
matches flax Dense — conversion is a transpose-free copy).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

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
    Cache,
    kv_buffers,
    layer_cache,
    # not used here: benchmark/harness.py imports it from this module, and
    # no file under benchmark/ may change outside a `benchmark` issue; held
    # until one repoints that import at ops/kv_cache.py
    resolve_kv_cache_dtype,  # noqa: F401
    validate_kv_cache_dtype,
    with_layer_cache,
)


@dataclass(frozen=True)
class GPT2Config:
    """Architecture hyperparameters (HF ``GPT2Config`` field names)."""

    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    layer_norm_epsilon: float = 1e-5
    dtype: str = "bfloat16"  # compute dtype (MXU path)
    param_dtype: str = "float32"
    # Rollout KV-cache storage. Single-token decode is HBM-bound and the
    # cache is its dominant traffic (grows with context while weights
    # stay fixed), so "int8" halves the bottleneck: K/V quantized per
    # (token, head) on write (absmax/127 scale); on read the fixed
    # sampler scales scores and weights, the generic path dequantises the
    # buffer (ops/attention.py::decode_attention). Training/scoring
    # forwards never touch this — only the sampler's cache buffers.
    # "auto" resolves per cache shape: int8 up to the capacity its read is
    # measured to (ops/kv_cache.py::INT8_KV_MAX_CAPACITY), bf16 beyond it.
    kv_cache_dtype: str = "bfloat16"  # "bfloat16" | "int8" | "auto"

    def __post_init__(self):
        validate_kv_cache_dtype(self.kv_cache_dtype)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "GPT2Config":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


# Tensor-parallel placement: attention/MLP input projections shard the output
# dim, output projections shard the input dim, so each block needs a single
# all-reduce of activations (inserted by GSPMD) per sub-layer.
PARTITION_RULES = [
    (r"wte/embedding", P(None, "tp")),
    (r"attn/c_attn/kernel", P(None, "tp")),
    (r"attn/c_proj/kernel", P("tp", None)),
    (r"mlp/c_fc/kernel", P(None, "tp")),
    (r"mlp/c_proj/kernel", P("tp", None)),
]


class MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        x = nn.Dense(4 * cfg.n_embd, dtype=dtype, param_dtype=jnp.dtype(cfg.param_dtype), name="c_fc")(x)
        x = nn.gelu(x, approximate=True)  # GPT-2 uses gelu_new
        x = nn.Dense(cfg.n_embd, dtype=dtype, param_dtype=jnp.dtype(cfg.param_dtype), name="c_proj")(x)
        return x


class Attention(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(
        self,
        x: jax.Array,  # [B, T, D]
        bias: Optional[jax.Array],
        cache_kv: Optional[Dict[str, jax.Array]] = None,
        cache_index: Optional[jax.Array] = None,
        causal: bool = False,
    ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        pdtype = jnp.dtype(cfg.param_dtype)
        B, T, D = x.shape
        head_dim = cfg.n_embd // cfg.n_head

        qkv = nn.Dense(3 * cfg.n_embd, dtype=dtype, param_dtype=pdtype, name="c_attn")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, cfg.n_head, head_dim)
        k = k.reshape(B, T, cfg.n_head, head_dim)
        v = v.reshape(B, T, cfg.n_head, head_dim)

        new_kv = None
        if cache_kv is not None:
            # write this step's keys/values into the capacity buffer at
            # cache_index and attend over it (invalid positions are masked
            # by `bias`)
            out, new_kv = decode_attention(
                q, k, v, cache_kv, cache_index, bias, causal=causal
            )
        else:
            out = dot_product_attention(q, k, v, bias, causal=causal)
        out = out.reshape(B, T, cfg.n_embd)
        out = nn.Dense(cfg.n_embd, dtype=dtype, param_dtype=pdtype, name="c_proj")(out)
        return out, new_kv


class Block(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, bias, cache_kv=None, cache_index=None, causal=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        eps = cfg.layer_norm_epsilon
        h = nn.LayerNorm(epsilon=eps, dtype=dtype, name="ln_1")(x)
        attn_out, new_kv = Attention(cfg, name="attn")(
            h, bias, cache_kv, cache_index, causal
        )
        x = x + attn_out
        h = nn.LayerNorm(epsilon=eps, dtype=dtype, name="ln_2")(x)
        x = x + MLP(cfg, name="mlp")(h)
        return x, new_kv


class GPT2Model(nn.Module):
    """GPT-2 transformer with tied-embedding LM head and explicit KV cache.

    Call modes (all jit-safe, static shapes):
    - training/scoring: ``cache=None`` — full-sequence causal forward.
    - prefill/decode:   ``cache`` given — keys/values written at
      ``cache_index`` into fixed-capacity buffers; ``bias`` must mask
      invalid cache positions (built by the sampler).
    """

    config: GPT2Config

    def setup(self):
        cfg = self.config
        pdtype = jnp.dtype(cfg.param_dtype)
        self.wte = nn.Embed(cfg.vocab_size, cfg.n_embd, param_dtype=pdtype, name="wte")
        self.wpe = nn.Embed(cfg.n_positions, cfg.n_embd, param_dtype=pdtype, name="wpe")
        self.h = [Block(cfg, name=f"h_{i}") for i in range(cfg.n_layer)]
        self.ln_f = nn.LayerNorm(
            epsilon=cfg.layer_norm_epsilon, dtype=jnp.dtype(cfg.dtype), name="ln_f"
        )

    def embed(self, input_ids: jax.Array, position_ids: jax.Array) -> jax.Array:
        # each table rounds to the compute dtype BEFORE the add, so the sum
        # is invariant to whether params are stored f32 or pre-cast to the
        # compute dtype (the rollout-phase weight cast relies on this)
        dtype = jnp.dtype(self.config.dtype)
        return self.wte(input_ids).astype(dtype) + self.wpe(position_ids).astype(dtype)

    def logits(self, hidden: jax.Array) -> jax.Array:
        """Tied LM head; logits in float32 for stable softmax/log-softmax."""
        emb = self.wte.embedding.astype(jnp.dtype(self.config.dtype))
        return jnp.einsum(
            "btd,vd->btv", hidden, emb, preferred_element_type=jnp.float32
        )

    def __call__(
        self,
        input_ids: jax.Array,  # [B, T]
        attention_mask: Optional[jax.Array] = None,  # [B, T] (no cache) / [B, C] (cache)
        position_ids: Optional[jax.Array] = None,
        cache: Optional[Cache] = None,
        cache_index: Optional[jax.Array] = None,
        start_layer: int = 0,
        hidden_override: Optional[jax.Array] = None,
        capture_hidden_at: Optional[int] = None,
        compute_logits: bool = True,
    ):
        """Returns ``{"logits", "hidden", "cache"[, "branch_hidden"]}``.

        ``compute_logits=False`` skips the LM head (callers that only need a
        slice of positions apply :meth:`logits` to sliced hidden — the full
        [B, T, vocab] float32 tensor is the single most expensive
        intermediate in the PPO update).

        The hydra frozen-branch mechanism (`ppo_models.py:505-558`):
        ``capture_hidden_at=k`` additionally returns the activation entering
        block k; ``start_layer=k`` + ``hidden_override`` re-runs blocks
        ``k..n_layer`` from that activation (with the frozen branch's own
        params) to produce reference logits without a second trunk pass.
        """
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
            x = self.embed(input_ids, position_ids)

        bias, causal = causal_dispatch(T, cache, cache_index, attention_mask)

        branch_hidden = None
        for i in range(start_layer, cfg.n_layer):
            if capture_hidden_at is not None and i == capture_hidden_at:
                branch_hidden = x
            x, new_kv = self.h[i](x, bias, layer_cache(cache, i), cache_index, causal)
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


def init_cache(config: GPT2Config, batch_size: int, capacity: int) -> Cache:
    """Fixed-capacity KV buffers (one compile for the whole decode loop)."""
    return kv_buffers(
        config.n_layer, batch_size, capacity, config.n_head,
        config.n_embd // config.n_head, config.dtype,
        getattr(config, "kv_cache_dtype", "bfloat16"),
    )
