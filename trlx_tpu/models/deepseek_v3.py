"""DeepSeek-V3 causal LM (``model_type: deepseek_v3``,
``deepseek-ai/DeepSeek-V3``; R1 and V3.1 are this architecture): a pre-norm
decoder whose attention keeps one compressed row a position (multi-head
latent attention) and whose blocks, after ``first_k_dense_replace`` dense
ones, route each token to 8 of 256 experts by sigmoid scores, limited to 4
of 8 groups, beside one shared expert.

Written from the family's published ``config.json``, the technical report
(DeepSeek-AI 2024, arXiv:2412.19437, sections 2.1.1 and 2.1.2) and the
family's own inference code (``benchmark/reference/deepseek_v3.py`` spells
every equation and lists what is assumed):

    h += attn(rms(h));  h += ffn(rms(h))                 eps 1e-6, no biases
    logits = rms(h_L) W_head                             untied

- **attention**: ``c_q = rms(x W_dq)``; ``q = c_q W_uq``, per head ``[q_nope
  | q_rope]``; ``[c_kv | k_r] = x W_dkv``, ``c_kv <- rms(c_kv)``; ``q_rope``
  and the one ``k_r`` all heads share are rotated (interleaved pairs, YaRN
  frequencies: ``ops/rotary.py``). **The cache row of a position is
  ``[c_kv | k_r]``** (``kv_lora_rank + qk_rope_head_dim`` values,
  ``ops/kv_cache.py::latent_buffers``) and there is no value pool. Keys and
  values are ``c_kv W_ukv`` a head (``ops/attention.py::Latent``): an
  uncached forward and an admission decompress them and attend with heads
  of ``nope + rope`` (scores) and ``v_head_dim`` (values); the decode step
  attends absorbed, over the rows as stored. ``W_ukv`` is one matrix,
  ``kv_b_proj``; the absorbed form slices it. Scores are scaled by
  ``(nope + rope)^-1/2 m^2``, ``m`` YaRN's ``mscale``.
- **ffn** of a dense block: SwiGLU ``intermediate_size`` wide. Of a routed
  block: ``ops/moe.py::route_group_limited`` over ``num_router_experts``
  (the published ``n_routed_experts``) with the selection bias
  ``e_score_correction_bias`` (zeros at first), weights renormalised and
  scaled by ``routed_scaling_factor``; SwiGLU experts
  ``moe_intermediate_size`` wide, of which this program holds
  ``n_routed_experts`` from ``first_local_expert`` on: all of them, or one
  chip's share of an expert-parallel group, whose part of the sum is what
  the layer returns; one shared SwiGLU, ``n_shared_experts *
  moe_intermediate_size`` wide, computed whole and added in float32.

Same call interface as ``OlmoeModel`` (``cache``, ``cache_index``,
``compute_logits``, ``moe_stats``, ``logits()``). What the published
configuration may say and this family does not build, or the repository
does not build for a latent cache, is refused by name: the multi-token
prediction module (``num_nextn_predict_layers``), a ``scoring_func`` other
than ``sigmoid``, a ``topk_method`` other than ``noaux_tc``,
``norm_topk_prob`` false, no ``q_lora_rank``, ``moe_layer_freq`` other than
1, ``attention_bias``, a tied head, an activation other than ``silu``,
grouped KV heads, ``rope_scaling`` of a type other than ``yarn``, an int8
latent; the hydra branch, per-column cache targets (``verify_step``), an
``ep`` mesh; and, where they are built, the fixed sampler
(``ops/sampling.py``), a shared-prefix pool, a drafter and ``tp`` / ``ep``
/ ``pp`` meshes under the engine (``inference/engine.py``), a cache that is
not paged (``ops/attention.py::decode_attention``) and a checkpoint path.

Parameters: ``wte``, ``h_<i>/{ln_1, attn/{q_a_proj, q_a_norm, q_b_proj,
kv_a_proj, kv_a_norm, kv_b_proj, o_proj}, ln_2, mlp/{gate_proj, up_proj,
down_proj} | mlp/{router, router_bias, w_gate, w_up, w_down} +
shared/{gate_proj, up_proj, down_proj}}``, ``ln_f``, ``lm_head``; every
matrix ``[in, out]``, normal(0.02); scales ones, the selection bias zeros.
Nothing trains the router here (no loss is sown, and the registry does not
list the family among those an ``ep`` axis may shard).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from trlx_tpu.models.olmoe import RMSNorm
from trlx_tpu.ops import moe
from trlx_tpu.ops.attention import Latent, causal_dispatch, decode_attention, latent_attention
from trlx_tpu.ops.kv_cache import latent_buffers
from trlx_tpu.ops.rotary import apply_rotary_interleaved, rotary_angles, yarn_score_scale


@dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 129280
    max_position_embeddings: int = 163840
    hidden_size: int = 7168
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    moe_layer_freq: int = 1
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18432  # a dense block's width
    moe_intermediate_size: int = 2048  # one expert's width
    n_routed_experts: int = 256  # the experts held here
    # the cut's own: the router's published width (None: all are held) and
    # the first expert held
    num_router_experts: Optional[int] = None
    first_local_expert: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    norm_topk_prob: bool = True
    rope_scaling: Optional[Any] = None  # the published group, type yarn
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 0
    ep_size: int = 1
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    kv_cache_dtype: str = "bfloat16"

    def __post_init__(self):
        def refuse(what: str):
            raise ValueError(f"{what} is not built for deepseek_v3")

        if self.num_router_experts is None:
            object.__setattr__(self, "num_router_experts", self.n_routed_experts)
        if self.rope_scaling is not None:
            group = dict(self.rope_scaling)
            if group.get("type", group.get("rope_type")) != "yarn":
                refuse(f"rope_scaling of type {group.get('type', group.get('rope_type'))!r} (yarn)")
            # hashable, as a frozen dataclass that flax closes over must be
            object.__setattr__(self, "rope_scaling", tuple(sorted(group.items())))
        if self.num_nextn_predict_layers:
            refuse(f"num_nextn_predict_layers={self.num_nextn_predict_layers} (the multi-token prediction module)")
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc" or not self.norm_topk_prob:
            refuse(
                f"scoring_func={self.scoring_func!r} / topk_method={self.topk_method!r} / "
                f"norm_topk_prob={self.norm_topk_prob} (sigmoid, noaux_tc, true)"
            )
        if self.q_lora_rank is None:
            refuse("q_lora_rank=None (a query without its low-rank pair)")
        if self.moe_layer_freq != 1 or self.ep_size != 1:
            refuse(f"moe_layer_freq={self.moe_layer_freq} / ep_size={self.ep_size} (1, 1)")
        if self.attention_bias:
            refuse("attention_bias")
        if self.tie_word_embeddings:
            refuse("tie_word_embeddings=True")
        if self.hidden_act != "silu":
            refuse(f"hidden_act={self.hidden_act!r} (silu)")
        if self.num_key_value_heads != self.num_attention_heads:
            refuse(f"num_key_value_heads={self.num_key_value_heads} != num_attention_heads (every head reads the one latent)")
        if self.kv_cache_dtype != "bfloat16":
            refuse(f"kv_cache_dtype={self.kv_cache_dtype!r} for a latent row (bfloat16)")
        if self.n_shared_experts < 1:
            refuse(f"n_shared_experts={self.n_shared_experts} (at least 1)")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace is not among the layers")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim is rotated in pairs")
        if not 0 <= self.first_local_expert <= self.num_router_experts - self.n_routed_experts:
            raise ValueError(
                f"experts {self.first_local_expert} .. "
                f"{self.first_local_expert + self.n_routed_experts} are not among the "
                f"router's {self.num_router_experts}"
            )

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "DeepseekV3Config":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_embd(self) -> int:
        return self.hidden_size

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """What a position keeps in the cache: ``[c_kv | k_r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def yarn(self) -> Optional[Dict[str, Any]]:
        return None if self.rope_scaling is None else dict(self.rope_scaling)

    @property
    def score_scale(self) -> float:
        return self.qk_head_dim ** -0.5 * yarn_score_scale(self.yarn)


# no rule: a latent row has no head axis for tp to shard, the router's
# finished routing is built off an ep mesh only, and the engine refuses tp /
# ep / pp meshes for a latent cache by name (inference/engine.py); a
# trainer's dp x fsdp mesh shards every leaf by the partitioner's fallback
DEEPSEEK_V3_PARTITION_RULES: list = []

_normal = nn.initializers.normal(0.02)


def _dense(features: int, cfg, name: str):
    return nn.Dense(
        features, use_bias=False, dtype=jnp.dtype(cfg.dtype),
        param_dtype=jnp.dtype(cfg.param_dtype), kernel_init=_normal, name=name,
    )


class _Kernel(nn.Module):
    """A matrix its caller multiplies itself (both halves of ``kv_b_proj``,
    a product with a float32 result), under the path a ``Dense`` of its
    name would give it."""

    shape: Tuple[int, int]
    param_dtype: Any

    @nn.compact
    def __call__(self):
        return self.param("kernel", _normal, self.shape, self.param_dtype)


class DeepseekV3Attention(nn.Module):
    config: DeepseekV3Config

    @nn.compact
    def __call__(self, x, bias, position_ids, cache_kv=None, cache_index=None, causal=False):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        B, T, D = x.shape
        H, C = cfg.num_attention_heads, cfg.kv_lora_rank
        nope, rope, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype, pdtype, name=name)
        sin, cos = rotary_angles(position_ids, rope, cfg.rope_theta, cfg.yarn)
        # device-trace scope names are a contract (docs/observability.md)
        with jax.named_scope("mla_q"):
            c_q = norm("q_a_norm")(_dense(cfg.q_lora_rank, cfg, "q_a_proj")(x))
            q = _dense(H * cfg.qk_head_dim, cfg, "q_b_proj")(c_q).reshape(B, T, H, cfg.qk_head_dim)
            q = jnp.concatenate(
                [q[..., :nope], apply_rotary_interleaved(q[..., nope:], sin, cos, rope)], axis=-1
            )
        with jax.named_scope("mla_kv_down"):
            down = _dense(cfg.latent_width, cfg, "kv_a_proj")(x)
            k_r = apply_rotary_interleaved(down[:, :, None, C:], sin, cos, rope)
            rows = jnp.concatenate([norm("kv_a_norm")(down[..., :C])[:, :, None, :], k_r], axis=-1)
        w_ukv = _Kernel((C, H * (nope + Dv)), pdtype, name="kv_b_proj")()
        latent = Latent(w_ukv.astype(dtype).reshape(C, H, nope + Dv), nope)
        new_kv = None
        if cache_kv is not None:
            out, new_kv = decode_attention(
                q, rows, None, cache_kv, cache_index, bias, causal=causal,
                scale=cfg.score_scale, latent=latent,
            )
        else:
            out = latent_attention(q, rows, bias, latent, scale=cfg.score_scale, causal=causal)
        return _dense(D, cfg, "o_proj")(out.reshape(B, T, H * Dv)), new_kv


class DeepseekV3MLP(nn.Module):
    """SwiGLU ``width`` wide: a dense block's feed-forward, and the shared
    expert (float32 out, for the sum with the routed part)."""

    config: DeepseekV3Config
    width: int
    f32_out: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = jax.nn.silu(_dense(self.width, cfg, "gate_proj")(x)) * _dense(self.width, cfg, "up_proj")(x)
        if not self.f32_out:
            return _dense(cfg.hidden_size, cfg, "down_proj")(h)
        w_down = _Kernel((self.width, cfg.hidden_size), jnp.dtype(cfg.param_dtype), name="down_proj")()
        return jnp.dot(h, w_down.astype(h.dtype), preferred_element_type=jnp.float32)


class DeepseekV3SparseMLP(nn.Module):
    """The routed experts held here under the family's router, plus
    ``shared`` (``ops/moe.py``). Returns the output and the step's routing
    statistics."""

    config: DeepseekV3Config

    @nn.compact
    def __call__(self, x, shared):
        from trlx_tpu.models.gpt2_moe import get_ep_mesh

        cfg = self.config
        if get_ep_mesh() is not None:
            raise ValueError(
                "an ep mesh is not built for deepseek_v3: its router hands ops/moe.py a "
                "finished routing (a caller's own routing is built off a mesh only)"
            )
        D, F = cfg.hidden_size, cfg.moe_intermediate_size
        E, held, first = cfg.num_router_experts, cfg.n_routed_experts, cfg.first_local_expert
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        router = self.param("router", _normal, (D, E), pdtype)
        bias = self.param("router_bias", nn.initializers.zeros, (E,), pdtype)
        w_gate = self.param("w_gate", _normal, (held, D, F), pdtype)
        w_up = self.param("w_up", _normal, (held, D, F), pdtype)
        w_down = self.param("w_down", _normal, (held, F, D), pdtype)
        with jax.named_scope("moe_group_router"):
            routing = moe.route_group_limited(
                x.reshape(-1, D), router, bias, cfg.num_experts_per_tok,
                n_group=cfg.n_group, topk_group=cfg.topk_group, scale=cfg.routed_scaling_factor,
            )
        y, _ = moe.expert_layer(
            x, None, w_gate, w_up, w_down, dtype=dtype, routing=routing,
            first_expert=first, shared=shared,
        )
        return y, moe.routing_stats(routing, E, first, held)


class DeepseekV3Block(nn.Module):
    config: DeepseekV3Config
    routed: bool

    @nn.compact
    def __call__(self, x, bias, position_ids, cache_kv=None, cache_index=None, causal=False):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype, pdtype, name=name)
        attn_out, new_kv = DeepseekV3Attention(cfg, name="attn")(
            norm("ln_1")(x), bias, position_ids, cache_kv, cache_index, causal
        )
        x = x + attn_out
        h = norm("ln_2")(x)
        if not self.routed:
            return x + DeepseekV3MLP(cfg, cfg.intermediate_size, name="mlp")(h), new_kv, None
        with jax.named_scope("moe_shared"):
            shared = DeepseekV3MLP(
                cfg, cfg.n_shared_experts * cfg.moe_intermediate_size, f32_out=True, name="shared"
            )(h)
        y, stats = DeepseekV3SparseMLP(cfg, name="mlp")(h, shared)
        return x + y, new_kv, stats


class DeepseekV3Model(nn.Module):
    """Same interface as ``OlmoeModel`` (``moe_stats``: the routing
    statistics of this call over its routed blocks) but for the hydra
    hooks, which are refused."""

    config: DeepseekV3Config

    def setup(self):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        self.wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, param_dtype=pdtype, embedding_init=_normal, name="wte",
        )
        self.h = [
            DeepseekV3Block(cfg, i >= cfg.first_k_dense_replace, name=f"h_{i}")
            for i in range(cfg.num_hidden_layers)
        ]
        self.ln_f = RMSNorm(cfg.rms_norm_eps, dtype, pdtype, name="ln_f")
        self.lm_head = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=dtype, param_dtype=pdtype,
            kernel_init=_normal, name="lm_head",
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
        if start_layer or hidden_override is not None or capture_hidden_at is not None:
            raise ValueError(
                "the hydra branch (start_layer / hidden_override / capture_hidden_at) is not "
                "built for deepseek_v3: nothing trains it with a branch; use "
                "num_layers_unfrozen = -1 (a whole reference copy)"
            )
        if cache is not None and jnp.ndim(cache_index) == 2:
            raise ValueError(
                "per-column cache targets (the speculative verify step) are not built for "
                "deepseek_v3: a rejected column's latent row has no rollback"
            )
        B, T = input_ids.shape
        if position_ids is None:
            if attention_mask is not None and cache is None:
                position_ids = jnp.clip(jnp.cumsum(attention_mask, axis=-1) - 1, 0, None)
            else:
                position_ids = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        else:
            position_ids = jnp.broadcast_to(position_ids, (B, T))
        x = self.wte(input_ids).astype(jnp.dtype(cfg.dtype))

        bias, causal = causal_dispatch(T, cache, cache_index, attention_mask)

        new_cache: List = []
        per_block: List = []
        for i in range(cfg.num_hidden_layers):
            x, new_kv, stats = self.h[i](
                x, bias, position_ids, None if cache is None else cache[i], cache_index, causal,
            )
            new_cache.append(new_kv)
            if stats is not None:
                per_block.append(stats)

        x = self.ln_f(x)
        out = {
            "logits": self.logits(x) if compute_logits else None,
            "hidden": x,
            "cache": tuple(new_cache) if cache is not None else None,
        }
        if per_block:
            stacked = {k: jnp.stack([s[k] for s in per_block]) for k in per_block[0]}
            out["moe_stats"] = {
                k: (jnp.max if k == "max_load" else jnp.sum if k == "rows_routed" else jnp.mean)(v)
                for k, v in stacked.items()
            }
        return out


def init_deepseek_v3_cache(config: DeepseekV3Config, batch_size: int, capacity: int):
    """One row ``[c_kv | k_r]`` a position a layer, and no values."""
    return latent_buffers(
        config.num_hidden_layers, batch_size, capacity, config.latent_width,
        config.dtype, config.kv_cache_dtype,
    )


def no_deepseek_v3_checkpoint(path: str, dtype: str = "float32"):
    raise ValueError(
        "no checkpoint converter is built for deepseek_v3; give the sizes as "
        "model.model_arch (weights from the seed)"
    )
