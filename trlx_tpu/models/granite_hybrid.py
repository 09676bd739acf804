"""Granite 4.0-H causal LM (``model_type: granitemoehybrid``,
``ibm-granite/granite-4.0-h-*``): a pre-norm decoder whose layers are
Mamba-2 mixers with one grouped-KV attention layer in a period, each
followed by routed experts with a shared expert beside them.

Written from the family's published ``config.json`` and its
``transformers`` module:

    h0 = embedding_multiplier * E[ids]
    h += residual_multiplier * mix(rms(h))           mix: mamba | attention
    h += residual_multiplier * (moe(rms(h)) + shared(rms(h)))
    logits = rms(h) E^T / logits_scaling             (tied head)

- **mamba** (``ops/ssm.py``): ``[z | xBC | dt] = u W_in`` with ``u`` the
  normed input, zero at masked columns; ``xBC`` through a depthwise causal
  convolution of ``mamba_d_conv`` with bias and ``silu``; ``mamba_n_heads``
  heads of ``mamba_d_head`` over a state of ``mamba_d_state``, one group of
  ``B``/``C``; ``(rms(y * silu(z)) * w) W_out``. It keeps a state and a
  convolution tail a sequence and no keys.
- **attention**: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` KV heads, no bias, **no positions** (``nope``),
  scores scaled by ``attention_multiplier`` (not ``1 / sqrt(Dh)``).
- **experts** (``ops/moe.py``): a router over ``num_router_experts`` (the
  published ``num_local_experts``), ``num_experts_per_tok`` a token, the
  weights the softmax over the chosen logits; SwiGLU experts
  ``intermediate_size`` wide. This program holds ``num_local_experts`` of
  them from ``first_local_expert`` on: all of them, or one chip's share of
  an ``ep`` group, whose part of the sum is what the layer returns. The
  shared SwiGLU MLP, ``shared_intermediate_size`` wide, is computed whole
  and added in float32.

Same call interface as ``OlmoeModel`` (incl. hydra hooks), ``moe_stats``
in its output, a tied ``logits()``. The cache is a tuple whose layers
differ (``ops/kv_cache.py::hybrid_cache``): a KV layer goes through
``decode_attention``, a state layer through ``ops/ssm.py``, which reads from
the cache mask which columns of a call are valid and which rows start
fresh.

What the published configuration may say and this family does not build is
refused by name: ``rope_scaling``, positions other than ``nope``,
``attention_bias``, ``mamba_n_groups`` > 1, ``mamba_proj_bias``, an untied
head, an activation other than ``silu``, an int8 cache.

Parameters: ``wte``, ``h_<i>/{ln_1, mamba/{in_proj, conv_weight, conv_bias,
dt_bias, A_log, D, norm, out_proj} | attn/{q_proj, k_proj, v_proj, o_proj},
ln_2, mlp/{router, w_gate, w_up, w_down}, shared/{gate_proj, up_proj,
down_proj}}``, ``ln_f``. Initialisers follow the family's module (``A_log =
log(1..H)``, ``D`` ones, normal(0.02) elsewhere) but for ``dt_bias``: the
module's placeholder ones give ``dt = softplus(1 + ..) ~ 1.3`` and a decay of
``exp(-1.3 h)`` a position: a state that is numerically dead three positions
on (dropping the carried state altogether would move a mixer's output by
about 0.3%), so no comparison with a reference could tell whether the state
is carried at all. ``dt_bias`` takes Mamba-2's own (Dao & Gu's reference
code): the inverse softplus of ``dt`` drawn log-uniformly from ``[0.001,
0.1]``, a state that reaches over tens to hundreds of positions, as a trained
model's does (PERF.md section 6, PR 35).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from trlx_tpu.models.olmoe import RMSNorm
from trlx_tpu.ops import moe, ssm
from trlx_tpu.ops.attention import (
    causal_dispatch,
    decode_attention,
    dot_product_attention,
)
from trlx_tpu.ops.kv_cache import VALID_STATE_DTYPES, hybrid_cache

LAYER_KINDS = ("mamba", "attention")


@dataclass(frozen=True)
class GraniteMoeHybridConfig:
    vocab_size: int = 100352
    max_position_embeddings: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 40
    layer_types: Optional[Tuple[str, ...]] = None  # None: every layer mamba
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    intermediate_size: int = 768  # one expert's width
    shared_intermediate_size: int = 1536
    num_local_experts: int = 72  # the experts held here
    # the cut's own: the router's published width (None: all are held) and
    # the first expert held
    num_router_experts: Optional[int] = None
    first_local_expert: int = 0
    num_experts_per_tok: int = 10
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    router_aux_loss_coef: float = 0.01
    position_embedding_type: str = "nope"
    normalization_function: str = "rmsnorm"
    hidden_act: str = "silu"
    tie_word_embeddings: bool = True
    attention_bias: bool = False
    rope_scaling: Optional[Any] = None
    rope_theta: float = 10000.0  # published; unread without positions
    state_dtype: str = "float32"
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    kv_cache_dtype: str = "bfloat16"

    def __post_init__(self):
        def refuse(what: str):
            raise ValueError(f"{what} is not built for granitemoehybrid")

        if self.layer_types is None:
            object.__setattr__(self, "layer_types", ("mamba",) * self.num_hidden_layers)
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.num_router_experts is None:
            object.__setattr__(self, "num_router_experts", self.num_local_experts)
        if len(self.layer_types) != self.num_hidden_layers or set(self.layer_types) - set(LAYER_KINDS):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers from {LAYER_KINDS}; "
                f"got {self.layer_types}"
            )
        if self.rope_scaling is not None:
            refuse(f"rope_scaling={self.rope_scaling!r}")
        if self.position_embedding_type != "nope":
            refuse(f"position_embedding_type={self.position_embedding_type!r} (nope)")
        if self.attention_bias or self.mamba_proj_bias:
            refuse("attention_bias / mamba_proj_bias")
        if self.mamba_n_groups != 1:
            refuse(f"mamba_n_groups={self.mamba_n_groups} (1)")
        if not self.tie_word_embeddings:
            refuse("tie_word_embeddings=False")
        if self.hidden_act != "silu" or self.normalization_function != "rmsnorm":
            refuse(f"hidden_act={self.hidden_act!r} / {self.normalization_function!r} (silu, rmsnorm)")
        if self.kv_cache_dtype != "bfloat16":
            refuse(f"kv_cache_dtype={self.kv_cache_dtype!r} beside state layers (bfloat16)")
        if self.state_dtype not in VALID_STATE_DTYPES:
            refuse(f"state_dtype={self.state_dtype!r} {VALID_STATE_DTYPES}")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_expand * self.hidden_size:
            raise ValueError("mamba_n_heads * mamba_d_head != mamba_expand * hidden_size")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads does not divide over num_key_value_heads")
        if not 0 <= self.first_local_expert <= self.num_router_experts - self.num_local_experts:
            raise ValueError(
                f"experts {self.first_local_expert} .. "
                f"{self.first_local_expert + self.num_local_experts} are not among the "
                f"router's {self.num_router_experts}"
            )

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "GraniteMoeHybridConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_embd(self) -> int:
        return self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self) -> int:
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state


# attention and the mixer's projections over tp, the experts' [E] axis over
# ep (a whole model: all experts held), everything else whole
GRANITE_HYBRID_PARTITION_RULES = [
    (r"wte/embedding", P(None, "tp")),
    (r"attn/[qkv]_proj/kernel", P(None, "tp")),
    (r"attn/o_proj/kernel", P("tp", None)),
    (r"shared/(gate|up)_proj/kernel", P(None, "tp")),
    (r"shared/down_proj/kernel", P("tp", None)),
    (r"mlp/router", P(None, None)),
    (r"mlp/w_(gate|up|down)", P("ep", None, None)),
]

_normal = nn.initializers.normal(0.02)
DT_RANGE = (0.001, 0.1)


def _dt_bias_init(key, shape, dtype):
    """Inverse softplus of ``dt`` log-uniform over ``DT_RANGE`` (Mamba-2)."""
    lo, hi = (jnp.log(x) for x in DT_RANGE)
    dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(key, shape))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _dense(features: int, cfg, name: str, dtype=None):
    return nn.Dense(
        features, use_bias=False, dtype=jnp.dtype(dtype or cfg.dtype),
        param_dtype=jnp.dtype(cfg.param_dtype), kernel_init=_normal, name=name,
    )


class GraniteMambaMixer(nn.Module):
    config: GraniteMoeHybridConfig

    @nn.compact
    def __call__(self, x, mask, fresh, cache_layer=None):
        cfg = self.config
        pdtype = jnp.dtype(cfg.param_dtype)
        H, inner, width = cfg.mamba_n_heads, cfg.mamba_inner, cfg.conv_channels
        conv_weight = self.param("conv_weight", _normal, (cfg.mamba_d_conv, width), pdtype)
        conv_bias = (
            self.param("conv_bias", _normal, (width,), pdtype) if cfg.mamba_conv_bias else None
        )
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,), pdtype)
        A_log = self.param(
            "A_log", lambda key, shape, dtype: jnp.log(jnp.arange(1, shape[0] + 1)).astype(dtype),
            (H,), pdtype,
        )
        D = self.param("D", nn.initializers.ones, (H,), pdtype)
        norm = self.param("norm", nn.initializers.ones, (inner,), pdtype)
        # device-trace scope names are a contract (docs/observability.md)
        with jax.named_scope("ssm_in_proj"):
            if mask is not None:
                x = x * mask[..., None].astype(x.dtype)
            proj = _dense(inner + width + H, cfg, "in_proj")(x)
            z, xBC, dt = proj[..., :inner], proj[..., inner : inner + width], proj[..., inner + width :]
        y, new_layer = ssm.mamba2_mix(
            xBC, dt, conv_weight=conv_weight, conv_bias=conv_bias, dt_bias=dt_bias,
            A_log=A_log, D=D, n_heads=H, head_dim=cfg.mamba_d_head, d_state=cfg.mamba_d_state,
            chunk=cfg.mamba_chunk_size, mask=mask, fresh=fresh, cache_layer=cache_layer,
        )
        with jax.named_scope("ssm_out"):
            y = ssm.gated_rms_norm(y, z, norm, cfg.rms_norm_eps).astype(jnp.dtype(cfg.dtype))
            return _dense(cfg.hidden_size, cfg, "out_proj")(y), new_layer


class GraniteAttention(nn.Module):
    config: GraniteMoeHybridConfig

    @nn.compact
    def __call__(self, x, bias, cache_kv=None, cache_index=None, causal=False):
        cfg = self.config
        B, T, D = x.shape
        H, H_kv, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = _dense(H * Dh, cfg, "q_proj")(x).reshape(B, T, H, Dh)
        k = _dense(H_kv * Dh, cfg, "k_proj")(x).reshape(B, T, H_kv, Dh)
        v = _dense(H_kv * Dh, cfg, "v_proj")(x).reshape(B, T, H_kv, Dh)
        scale = cfg.attention_multiplier
        new_kv = None
        if cache_kv is not None:
            out, new_kv = decode_attention(
                q, k, v, cache_kv, cache_index, bias, causal=causal, scale=scale
            )
        else:
            out = dot_product_attention(q, k, v, bias, causal=causal, scale=scale)
        return _dense(D, cfg, "o_proj")(out.reshape(B, T, H * Dh)), new_kv


class GraniteSharedMLP(nn.Module):
    """The shared expert: SwiGLU over the whole input; float32 out, for
    the sum with the routed part."""

    config: GraniteMoeHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        F = cfg.shared_intermediate_size
        with jax.named_scope("moe_shared"):
            h = jax.nn.silu(_dense(F, cfg, "gate_proj")(x)) * _dense(F, cfg, "up_proj")(x)
            return _dense(cfg.hidden_size, cfg, "down_proj", dtype=jnp.float32)(h)


class GraniteSparseMLP(nn.Module):
    """The routed experts held here plus ``shared`` (``ops/moe.py``).
    Returns the output and the step's routing statistics."""

    config: GraniteMoeHybridConfig

    @nn.compact
    def __call__(self, x, shared, token_mask=None):
        from trlx_tpu.models.gpt2_moe import get_ep_mesh

        cfg = self.config
        D, F = cfg.hidden_size, cfg.intermediate_size
        E, held, first = cfg.num_router_experts, cfg.num_local_experts, cfg.first_local_expert
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        router = self.param("router", _normal, (D, E), pdtype)
        w_gate = self.param("w_gate", _normal, (held, D, F), pdtype)
        w_up = self.param("w_up", _normal, (held, D, F), pdtype)
        w_down = self.param("w_down", _normal, (held, F, D), pdtype)
        y, routing = moe.expert_layer(
            x, router, w_gate, w_up, w_down, k=cfg.num_experts_per_tok, norm_topk=True,
            dtype=dtype, mesh=get_ep_mesh() if held == E else None,
            first_expert=first, shared=shared,
        )
        if self.is_mutable_collection("moe_losses"):
            for name, value in moe.balance_losses(routing, E, token_mask).items():
                self.sow("moe_losses", name, value)
        return y, moe.routing_stats(routing, E, first, held)


class GraniteHybridBlock(nn.Module):
    config: GraniteMoeHybridConfig
    kind: str

    @nn.compact
    def __call__(self, x, bias, cache_layer=None, cache_index=None, causal=False,
                 token_mask=None, columns=(None, None)):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype, pdtype, name=name)

        def add(x, branch):  # the multiplier as it is, not rounded to the compute dtype
            scaled = branch.astype(jnp.float32) * cfg.residual_multiplier
            return (x.astype(jnp.float32) + scaled).astype(dtype)

        h = norm("ln_1")(x)
        if self.kind == "attention":
            mixed, new_layer = GraniteAttention(cfg, name="attn")(
                h, bias, cache_layer, cache_index, causal
            )
        else:
            mixed, new_layer = GraniteMambaMixer(cfg, name="mamba")(h, *columns, cache_layer)
        x = add(x, mixed)
        h = norm("ln_2")(x)
        y, stats = GraniteSparseMLP(cfg, name="mlp")(
            h, GraniteSharedMLP(cfg, name="shared")(h), token_mask
        )
        return add(x, y), new_layer, stats


class GraniteMoeHybridModel(nn.Module):
    """Same interface as ``OlmoeModel`` (incl. hydra hooks and
    ``moe_stats``: the routing statistics of this call over its blocks)."""

    config: GraniteMoeHybridConfig

    def setup(self):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        self.wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, param_dtype=pdtype,
            embedding_init=_normal, name="wte",
        )
        self.h = [
            GraniteHybridBlock(cfg, kind, name=f"h_{i}") for i, kind in enumerate(cfg.layer_types)
        ]
        self.ln_f = RMSNorm(cfg.rms_norm_eps, dtype, pdtype, name="ln_f")

    def logits(self, hidden: jax.Array) -> jax.Array:
        """The tied head on (already ln_f-normalized) hidden states,
        divided by ``logits_scaling``; float32."""
        cfg = self.config
        out = self.wte.attend(hidden.astype(jnp.dtype(cfg.dtype)))
        return out.astype(jnp.float32) / cfg.logits_scaling

    def __call__(
        self,
        input_ids: jax.Array,
        attention_mask: Optional[jax.Array] = None,
        position_ids: Optional[jax.Array] = None,  # accepted and unread: no positions
        cache=None,
        cache_index=None,
        start_layer: int = 0,
        hidden_override: Optional[jax.Array] = None,
        capture_hidden_at: Optional[int] = None,
        compute_logits: bool = True,
    ):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        if hidden_override is not None:
            x = hidden_override.astype(dtype)
        else:
            x = (self.wte(input_ids) * cfg.embedding_multiplier).astype(dtype)
        B, T = x.shape[:2]

        if cache is None:
            bias, causal = causal_dispatch(T, None, None, attention_mask)
            columns = (attention_mask, None)
        else:
            if jnp.ndim(cache_index) == 2:
                raise ValueError(
                    "per-column cache targets (the speculative verify step) are not built "
                    "for granitemoehybrid: a rejected column cannot be taken out of a state"
                )
            kv_layers = [c for c, kind in zip(cache, cfg.layer_types) if kind == "attention"]
            bias, causal = (
                causal_dispatch(T, kv_layers, cache_index, attention_mask)
                if kv_layers else (None, False)
            )
            columns = ssm.call_columns(attention_mask, cache_index, B, T)
        # which tokens balance the router losses: a cached call's mask is
        # over cache slots, not over this call's tokens
        token_mask = attention_mask if cache is None else None

        new_cache: List = []
        per_block: List = []
        branch_hidden = None
        for i in range(start_layer, cfg.num_hidden_layers):
            if capture_hidden_at is not None and i == capture_hidden_at:
                branch_hidden = x
            layer_cache = cache[i] if cache is not None else None
            x, new_layer, stats = self.h[i](
                x, bias, layer_cache, cache_index, causal, token_mask, columns
            )
            new_cache.append(new_layer)
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
        if capture_hidden_at is not None:
            out["branch_hidden"] = branch_hidden
        return out


def init_granite_hybrid_cache(config: GraniteMoeHybridConfig, batch_size: int, capacity: int):
    return hybrid_cache(
        config.layer_types, batch_size, capacity,
        n_kv_head=config.num_key_value_heads, head_dim=config.head_dim,
        dtype=config.dtype, kv_cache_dtype=config.kv_cache_dtype,
        state={
            "n_head": config.mamba_n_heads, "head_dim": config.mamba_d_head,
            "d_state": config.mamba_d_state, "conv_width": config.mamba_d_conv,
            "conv_channels": config.conv_channels,
        },
        state_dtype=config.state_dtype,
    )


def no_granite_checkpoint(path: str, dtype: str = "float32"):
    raise ValueError(
        "no checkpoint converter is built for granitemoehybrid; give the sizes as "
        "model.model_arch (weights from the seed)"
    )
