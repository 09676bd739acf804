"""Ling-3.0-flash causal LM (``model_type: ling``, alias ``bailing_hybrid``;
``inclusionAI/Ling-3.0-flash`` and the language model of ``-VL``): a pre-norm
decoder whose blocks mix with Kimi Delta Attention (a delta rule whose decay
is a vector over a head's key channels: a matrix state a head, no keys) and,
every ``layer_group_size``-th block, with multi-head latent attention (one
compressed row a position); after ``first_k_dense_replace`` dense blocks each
routes a token to 8 of 512 experts by sigmoid scores, limited to 4 of 8
groups, beside one shared expert.

Written from the family's published ``config.json`` keys and the published
rules they name (Kimi Linear, arXiv:2510.26692, for the delta rule with a
vector decay; DeepSeek-V2 / -V3 for the latent attention and the router);
``benchmark/reference/ling.py`` spells every equation and marks each
reading that is this repository's own (``[a]``, listed under ``assumed`` in
the configuration file):

    h += mix_i(rms(h))       mix_i: latent attention where (i + 1) % layer_group_size == 0, else KDA
    h += ffn_i(rms(h))       dense SwiGLU for i < first_k_dense_replace, else the routed one
    logits = rms(h_L) W_head                              untied, eps 1e-6, no biases anywhere

- **KDA** (``ops/delta.py``, ``kda_*``): ``[q | k | v] = silu(conv4(u
  W_qkv))`` depthwise and causal; ``q``, ``k`` L2-normalised a head, ``q``
  scaled by ``head_dim^-1/2``; ``g = kda_lower_bound * sigmoid(exp(A_log[h])
  (u W_g + dt_bias))`` a key channel, in ``(-5, 0)``; ``beta = sigmoid(u
  W_b)`` a head; ``S <- Diag(exp g) S; S <- S + k (beta (v - S^T k))^T; o =
  S^T q`` on a ``[128, 128]`` float32 state a head; ``y = (rms_128(o) * w_n
  * sigmoid(u W_z)[h]) W_o``, the gate one number a head. It keeps a state
  and a convolution tail a sequence (the state kind of cache) and no keys.
- **latent attention** (``ops/attention.py``: ``Latent``,
  ``latent_attention``, the absorbed decode read): ``q = u W_q`` with no
  low-rank pair, per head ``[q_nope | q_rope]``; ``[c | k_r] = u W_kva``,
  ``c <- rms(c)``; plain rotary at ``rope_theta`` on ``q_rope`` and on the
  one ``k_r`` every head shares, interleaved pairs; the cache row of a
  position is ``[c | k_r]`` and keys and values are ``c W_kvb`` a head;
  scores scaled by ``(nope + rope)^-1/2``.
- **routed ffn**: ``models/deepseek_v3.py``'s own (``ops/moe.py::
  route_group_limited`` over ``num_router_experts``, the published
  ``num_experts``; the chosen scores renormalised and scaled by
  ``routed_scaling_factor``; SwiGLU experts of which this program holds
  ``num_experts`` from ``first_local_expert`` on, whose part of the sum is
  what the layer returns; one shared SwiGLU expert computed whole and added
  in float32).

Same call interface as ``OlmoeModel`` but for the hydra hooks, which are
refused; ``moe_stats`` in its output. The cache is a tuple whose layers
differ (``ops/kv_cache.py::hybrid_cache`` with ``latent_width``): a latent
layer goes through ``decode_attention(..., latent=...)``, a KDA layer
through ``ops/delta.py::kda_mix``, which reads from the cache mask which
columns of a call are valid and which rows start fresh
(``ops/ssm.py::call_columns``).

What the published configuration may say and this family does not build is
refused by name: a ``rope_scaling``, a ``q_lora_rank``, ``use_mla_nope``, a
non-zero entry of either SwiGLU limit list among the blocks kept, separate
key heads for the delta rule, ``kda_safe_gate`` false, a low-rank gate
(``use_kda_lora`` / ``no_kda_lora`` false), ``linear_silu`` false, a group
norm over more than a head, a gate granularity other than ``head_wise``,
``use_qk_norm`` false, ``value_norm`` / ``up_proj_norm`` / ``use_nGPT`` /
``scale_router_input``, a ``score_function`` other than ``sigmoid``,
``norm_topk_prob`` / ``moe_router_enable_expert_bias`` false, a tied head,
an int8 cache, a state below float32, a ``tp`` / ``ep`` / ``pp`` mesh, the
hydra branch and the speculative verify step; and, where they are built,
the fixed sampler, a shared-prefix pool, a drafter and a cache that is not
paged. The multi-token-prediction module and the vision tower have no count
key in the catalog's ``config`` and are not built.

Parameters: ``wte``, ``h_<i>/{ln_1, kda/{in_proj_qkv, g_proj, in_proj_bz,
conv_weight, dt_bias, A_log, norm, out_proj} | attn/{q_proj, kv_a_proj,
kv_a_norm, kv_b_proj, o_proj}, ln_2, mlp/{gate_proj, up_proj, down_proj} |
mlp/{router, router_bias, w_gate, w_up, w_down} + shared/{gate_proj,
up_proj, down_proj}}``, ``ln_f``, ``lm_head``; every matrix ``[in, out]``,
``in_proj_bz``'s columns ``[b | z]``. Initialisers: normal(0.02), scales
ones, the selection bias zeros, and the gate's two vectors so that a seeded
model forgets as slowly as a trained one (``A_log = log U(0.5, 1.5)``,
``dt_bias`` the logit of ``dt`` log-uniform in ``[0.001, 0.1]``: with
``dt_bias`` zeros every channel would decay by ``exp(-2.5)`` a position and
no comparison could tell whether the state is carried).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from trlx_tpu.models.deepseek_v3 import DeepseekV3MLP, DeepseekV3SparseMLP, _dense, _Kernel
from trlx_tpu.models.granite_hybrid import DT_RANGE
from trlx_tpu.models.olmoe import RMSNorm
from trlx_tpu.models.qwen3_next import _refuse_sharded_mesh
from trlx_tpu.ops import delta, ssm
from trlx_tpu.ops.attention import Latent, causal_dispatch, decode_attention, latent_attention
from trlx_tpu.ops.kv_cache import VALID_STATE_DTYPES, hybrid_cache
from trlx_tpu.ops.rotary import apply_rotary_interleaved, rotary_angles

KDA, LATENT = "kda", "latent_attention"


@dataclass(frozen=True)
class LingConfig:
    vocab_size: int = 157184
    max_position_embeddings: int = 131072
    hidden_size: int = 2560
    num_hidden_layers: int = 42
    first_k_dense_replace: int = 2
    layer_group_size: int = 6
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 128  # a KDA head's keys and values
    num_kv_heads_for_linear_attn: int = 0  # 0: as many key heads as heads
    short_conv_kernel_size: int = 4
    linear_silu: bool = True
    kda_lower_bound: float = -5.0
    kda_safe_gate: bool = True
    no_kda_lora: bool = True
    use_kda_lora: bool = False
    group_norm_size: int = 1
    gated_attention_proj_granularity_type: str = "head_wise"
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rotary_dim: int = 64
    rope_theta: float = 6000000.0
    rope_scaling: Optional[Any] = None
    use_mla_nope: bool = False
    use_qk_norm: bool = True
    value_norm: bool = False
    up_proj_norm: bool = False
    use_nGPT: bool = False
    intermediate_size: int = 6144  # a dense block's width
    moe_intermediate_size: int = 768  # one expert's width
    moe_shared_expert_intermediate_size: int = 768
    num_experts: int = 512  # the experts held here
    # the cut's own: the router's published width (None: all are held) and
    # the first expert held
    num_router_experts: Optional[int] = None
    first_local_expert: int = 0
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    score_function: str = "sigmoid"
    norm_topk_prob: bool = True
    moe_router_enable_expert_bias: bool = True
    scale_router_input: bool = False
    expert_swiglu_limit_list: Optional[Tuple[float, ...]] = None
    share_expert_swiglu_limit_list: Optional[Tuple[float, ...]] = None
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    state_dtype: str = "float32"
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    kv_cache_dtype: str = "bfloat16"

    def __post_init__(self):
        def refuse(what: str):
            raise ValueError(f"{what} is not built for ling")

        if self.num_router_experts is None:
            object.__setattr__(self, "num_router_experts", self.num_experts)
        for name in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
            limits = tuple(getattr(self, name) or ())
            object.__setattr__(self, name, limits)  # hashable, as flax closes over the config
            clamped = [i for i, x in enumerate(limits[: self.num_hidden_layers]) if x]
            if clamped:
                refuse(f"{name} with a non-zero limit at blocks {clamped} (a SwiGLU clamp)")
        if self.rope_scaling is not None:
            refuse(f"rope_scaling={self.rope_scaling!r}")
        if self.q_lora_rank is not None:
            refuse(f"q_lora_rank={self.q_lora_rank} (a low-rank query pair; the family publishes null)")
        if self.use_mla_nope:
            refuse("use_mla_nope=True (a latent layer without its rotation)")
        if self.num_kv_heads_for_linear_attn not in (0, self.num_attention_heads):
            refuse(f"num_kv_heads_for_linear_attn={self.num_kv_heads_for_linear_attn} (0: a key head a head)")
        if not self.kda_safe_gate or self.use_kda_lora or not self.no_kda_lora:
            refuse(
                f"kda_safe_gate={self.kda_safe_gate} / use_kda_lora={self.use_kda_lora} / "
                f"no_kda_lora={self.no_kda_lora} (the bounded gate at full rank: true, false, true)"
            )
        if not self.linear_silu:
            refuse("linear_silu=False")
        if self.group_norm_size != 1 or self.gated_attention_proj_granularity_type != "head_wise":
            refuse(
                f"group_norm_size={self.group_norm_size} / gated_attention_proj_granularity_type="
                f"{self.gated_attention_proj_granularity_type!r} (1, 'head_wise')"
            )
        if not self.use_qk_norm:
            refuse("use_qk_norm=False")
        if self.value_norm or self.up_proj_norm or self.use_nGPT or self.scale_router_input:
            refuse("value_norm / up_proj_norm / use_nGPT / scale_router_input")
        if self.score_function != "sigmoid" or not self.norm_topk_prob or not self.moe_router_enable_expert_bias:
            refuse(
                f"score_function={self.score_function!r} / norm_topk_prob={self.norm_topk_prob} / "
                f"moe_router_enable_expert_bias={self.moe_router_enable_expert_bias} (sigmoid, true, true)"
            )
        if self.tie_word_embeddings:
            refuse("tie_word_embeddings=True")
        if self.kv_cache_dtype != "bfloat16":
            refuse(f"kv_cache_dtype={self.kv_cache_dtype!r} for a latent row beside state layers (bfloat16)")
        if self.state_dtype not in VALID_STATE_DTYPES:
            refuse(f"state_dtype={self.state_dtype!r} {VALID_STATE_DTYPES}")
        if self.num_key_value_heads != self.num_attention_heads:
            refuse(f"num_key_value_heads={self.num_key_value_heads} != num_attention_heads (every head reads the one latent)")
        if self.rotary_dim != self.qk_rope_head_dim or self.qk_rope_head_dim % 2:
            raise ValueError(f"rotary_dim={self.rotary_dim} is the latent layer's qk_rope_head_dim, rotated in pairs")
        if self.layer_group_size < 1 or not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("layer_group_size / first_k_dense_replace are not among the layers")
        delta.kda_sub_chunk(self.kda_lower_bound)  # refuses a bound that bounds nothing
        if not 0 <= self.first_local_expert <= self.num_router_experts - self.num_experts:
            raise ValueError(
                f"experts {self.first_local_expert} .. "
                f"{self.first_local_expert + self.num_experts} are not among the "
                f"router's {self.num_router_experts}"
            )

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "LingConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @property
    def layer_types(self) -> Tuple[str, ...]:
        every = self.layer_group_size
        return tuple(LATENT if (i + 1) % every == 0 else KDA for i in range(self.num_hidden_layers))

    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_embd(self) -> int:
        return self.hidden_size

    @property
    def key_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def conv_channels(self) -> int:
        return 3 * self.key_width

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """What a position keeps in a latent layer's cache: ``[c | k_r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    # what models/deepseek_v3.py's feed-forward modules read, under its names
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts


# no rule: neither a state nor a latent row has a head axis a tp rule shards
# here, the router's finished routing is built off an ep mesh only, and the
# model refuses tp / ep / pp meshes by name; a trainer's dp x fsdp mesh
# shards every leaf by the partitioner's fallback
LING_PARTITION_RULES: list = []

_normal = nn.initializers.normal(0.02)
RATE_RANGE = (0.5, 1.5)


def _a_log_init(key, shape, dtype):
    """``log`` of the gate's rate a head, uniform over ``RATE_RANGE``."""
    lo, hi = RATE_RANGE
    return jnp.log(jax.random.uniform(key, shape, minval=lo, maxval=hi)).astype(dtype)


def _gate_bias_init(key, shape, dtype):
    """The logit of ``dt`` log-uniform over ``DT_RANGE``: at a zero
    projection a channel's log-decay is ``kda_lower_bound * dt`` at rate 1."""
    lo, hi = (jnp.log(x) for x in DT_RANGE)
    dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(key, shape))
    return (jnp.log(dt) - jnp.log1p(-dt)).astype(dtype)


class LingKDA(nn.Module):
    config: LingConfig

    @nn.compact
    def __call__(self, x, mask, fresh, cache_layer=None):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        B, T, _ = x.shape
        H, Dh = cfg.num_attention_heads, cfg.head_dim
        conv_weight = self.param("conv_weight", _normal, (cfg.short_conv_kernel_size, cfg.conv_channels), pdtype)
        dt_bias = self.param("dt_bias", _gate_bias_init, (H * Dh,), pdtype)
        A_log = self.param("A_log", _a_log_init, (H,), pdtype)
        norm = self.param("norm", nn.initializers.ones, (Dh,), pdtype)
        # device-trace scope names are a contract (docs/observability.md)
        with jax.named_scope("kda_in_proj"):
            if mask is not None:
                x = x * mask[..., None].astype(x.dtype)
            qkv = _dense(cfg.conv_channels, cfg, "in_proj_qkv")(x)
            g_raw = _dense(H * Dh, cfg, "g_proj")(x)
            bz = _dense(2 * H, cfg, "in_proj_bz")(x)
        o, new_layer = delta.kda_mix(
            qkv, g_raw, bz[..., :H], conv_weight=conv_weight, dt_bias=dt_bias, A_log=A_log,
            n_heads=H, key_dim=Dh, value_dim=Dh, lower_bound=cfg.kda_lower_bound,
            mask=mask, fresh=fresh, cache_layer=cache_layer,
        )
        with jax.named_scope("kda_out"):
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_norm_eps)
            o = o * norm.astype(jnp.float32) * jax.nn.sigmoid(bz[..., H:].astype(jnp.float32))[..., None]
            return _dense(cfg.hidden_size, cfg, "out_proj")(o.astype(dtype).reshape(B, T, H * Dh)), new_layer


class LingLatentAttention(nn.Module):
    """``models/deepseek_v3.py``'s sublayer with a full-rank query and plain
    rotary: the latent row, its decompression and both reads are
    ``ops/attention.py``'s."""

    config: LingConfig

    @nn.compact
    def __call__(self, x, bias, position_ids, cache_kv=None, cache_index=None, causal=False):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        B, T, D = x.shape
        H, C = cfg.num_attention_heads, cfg.kv_lora_rank
        nope, rope, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        sin, cos = rotary_angles(position_ids, rope, cfg.rope_theta)
        # device-trace scope names are a contract (docs/observability.md)
        with jax.named_scope("mla_q"):
            q = _dense(H * cfg.qk_head_dim, cfg, "q_proj")(x).reshape(B, T, H, cfg.qk_head_dim)
            q = jnp.concatenate(
                [q[..., :nope], apply_rotary_interleaved(q[..., nope:], sin, cos, rope)], axis=-1
            )
        with jax.named_scope("mla_kv_down"):
            down = _dense(cfg.latent_width, cfg, "kv_a_proj")(x)
            k_r = apply_rotary_interleaved(down[:, :, None, C:], sin, cos, rope)
            c = RMSNorm(cfg.rms_norm_eps, dtype, pdtype, name="kv_a_norm")(down[..., :C])
            rows = jnp.concatenate([c[:, :, None, :], k_r], axis=-1)
        w_ukv = _Kernel((C, H * (nope + Dv)), pdtype, name="kv_b_proj")()
        latent = Latent(w_ukv.astype(dtype).reshape(C, H, nope + Dv), nope)
        scale = cfg.qk_head_dim ** -0.5
        new_kv = None
        if cache_kv is not None:
            out, new_kv = decode_attention(
                q, rows, None, cache_kv, cache_index, bias, causal=causal, scale=scale, latent=latent,
            )
        else:
            out = latent_attention(q, rows, bias, latent, scale=scale, causal=causal)
        return _dense(D, cfg, "o_proj")(out.reshape(B, T, H * Dv)), new_kv


class LingBlock(nn.Module):
    config: LingConfig
    kind: str
    routed: bool

    @nn.compact
    def __call__(self, x, bias, position_ids, cache_layer=None, cache_index=None, causal=False,
                 columns=(None, None)):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype, pdtype, name=name)
        h = norm("ln_1")(x)
        if self.kind == LATENT:
            mixed, new_layer = LingLatentAttention(cfg, name="attn")(
                h, bias, position_ids, cache_layer, cache_index, causal
            )
        else:
            mixed, new_layer = LingKDA(cfg, name="kda")(h, *columns, cache_layer)
        x = x + mixed
        h = norm("ln_2")(x)
        if not self.routed:
            return x + DeepseekV3MLP(cfg, cfg.intermediate_size, name="mlp")(h), new_layer, None
        with jax.named_scope("moe_shared"):
            shared = DeepseekV3MLP(
                cfg, cfg.moe_shared_expert_intermediate_size, f32_out=True, name="shared"
            )(h)
        y, stats = DeepseekV3SparseMLP(cfg, name="mlp")(h, shared)
        return x + y, new_layer, stats


class LingModel(nn.Module):
    """Same interface as ``OlmoeModel`` (``moe_stats``: the routing
    statistics of this call over its routed blocks) but for the hydra
    hooks, which are refused."""

    config: LingConfig

    def setup(self):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        self.wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, param_dtype=pdtype, embedding_init=_normal, name="wte",
        )
        self.h = [
            LingBlock(cfg, kind, i >= cfg.first_k_dense_replace, name=f"h_{i}")
            for i, kind in enumerate(cfg.layer_types)
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
                "built for ling: nothing trains it with a branch; use "
                "num_layers_unfrozen = -1 (a whole reference copy)"
            )
        if cache is not None and jnp.ndim(cache_index) == 2:
            raise ValueError(
                "per-column cache targets (the speculative verify step) are not built "
                "for ling: a rejected column cannot be taken out of a state"
            )
        _refuse_sharded_mesh("ling")
        B, T = input_ids.shape
        if position_ids is None:
            if attention_mask is not None and cache is None:
                position_ids = jnp.clip(jnp.cumsum(attention_mask, axis=-1) - 1, 0, None)
            else:
                position_ids = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        else:
            position_ids = jnp.broadcast_to(position_ids, (B, T))
        x = self.wte(input_ids).astype(jnp.dtype(cfg.dtype))

        if cache is None:
            bias, causal = causal_dispatch(T, None, None, attention_mask)
            columns = (attention_mask, None)
        else:
            rows = [c for c, kind in zip(cache, cfg.layer_types) if kind == LATENT]
            bias, causal = (
                causal_dispatch(T, rows, cache_index, attention_mask) if rows else (None, False)
            )
            columns = ssm.call_columns(attention_mask, cache_index, B, T)

        new_cache: List = []
        per_block: List = []
        for i in range(cfg.num_hidden_layers):
            x, new_layer, stats = self.h[i](
                x, bias, position_ids, None if cache is None else cache[i], cache_index, causal,
                columns,
            )
            new_cache.append(new_layer)
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


def init_ling_cache(config: LingConfig, batch_size: int, capacity: int):
    """One row ``[c | k_r]`` a position for a latent layer, a matrix state
    and a convolution tail for a KDA layer."""
    return hybrid_cache(
        config.layer_types, batch_size, capacity,
        dtype=config.dtype, kv_cache_dtype=config.kv_cache_dtype,
        state={
            "n_head": config.num_attention_heads, "head_dim": config.head_dim,
            "d_state": config.head_dim, "conv_width": config.short_conv_kernel_size,
            "conv_channels": config.conv_channels,
        },
        state_dtype=config.state_dtype, keys=(LATENT,), latent_width=config.latent_width,
    )


def no_ling_checkpoint(path: str, dtype: str = "float32"):
    raise ValueError(
        "no checkpoint converter is built for ling; give the sizes as "
        "model.model_arch (weights from the seed)"
    )
