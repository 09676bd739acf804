"""Nemotron-H causal LM (``model_type: nemotron_h``,
``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``): a pre-norm decoder whose
**layer is one sublayer**, a Mamba-2 mixer, a grouped-KV attention or a
routed MLP whose experts work in a latent of the stream, by the characters of
``hybrid_override_pattern``.

Written from the family's published ``config.json`` keys and its published
module; ``benchmark/reference/nemotron_h.py`` spells every equation and marks
each reading that is this repository's own (``[a]``, listed under ``assumed``
in the configuration file):

    h += mix_i(rms_i(h))     mix_i: ``M`` mamba | ``*`` attention | ``E`` the routed MLP
    logits = rms_f(h) W_head                       untied, no biases but the convolution's

- **M** (``ops/ssm.py``): ``[z | xBC | dt] = u W_in``; ``xBC`` through a
  depthwise causal convolution of ``conv_kernel`` with bias and ``silu``;
  ``mamba_num_heads`` heads of ``mamba_head_dim`` over a state of
  ``ssm_state_size``, ``B`` and ``C`` in ``n_groups`` groups (head ``h``
  reads group ``h // (H / G)``); ``(rms_group(y * silu(z)) * w) W_out``, the
  norm over a group's channels at a time. It keeps a state and a convolution
  tail a sequence and no keys.
- **\\*** : ``num_attention_heads`` query heads over ``num_key_value_heads``
  KV heads of ``head_dim``, no bias, no positions, scores scaled by
  ``head_dim^-1/2``.
- **E** (``ops/moe.py``): ``route_group_limited`` on the normed stream
  (sigmoid scores under a selection bias, ``num_experts_per_tok`` of
  ``num_router_experts``, renormalised and scaled by
  ``routed_scaling_factor``); the stream goes down to ``moe_latent_size``,
  the plain experts ``W2 act(W1 l)`` (``mlp_hidden_act``, no gate) work
  there, of which this program holds ``n_routed_experts`` from
  ``first_local_expert`` on, and their weighted sum goes back up; one shared
  plain expert reads the stream itself and is added in float32 after the way
  up.

Same call interface as ``OlmoeModel`` but for the hydra hooks, which are
refused; ``moe_stats`` in its output. **The cache is over the layers that
keep something**: an ``E`` layer keeps nothing, so ``init_nemotron_h_cache``
builds ``ops/kv_cache.py::hybrid_cache`` over the ``M`` and ``*`` layers in
their order (``NemotronHConfig.cache_layer_types``) and the model maps a
layer to its entry.

What the published configuration may say and this family does not build is
refused by name: a pattern character other than ``M``, ``E``, ``*`` (the
dense ``-`` of the family's siblings included), ``num_nextn_predict_layers``
> 0 (the multi-token-prediction module), any bias but the convolution's, a
tied head, ``residual_in_fp32``, a sliding window, ``norm_topk_prob`` false,
other than one shared expert, a mixer activation other than ``silu``, an
expert activation ``ops/moe.py`` has no name for, an int8 cache, a state
below float32, a ``tp`` / ``ep`` / ``pp`` mesh, the hydra branch and the
speculative verify step; and, where they are built, the fixed sampler, a
shared-prefix pool, a drafter and a cache that is not paged.

Parameters: ``wte``, ``h_<i>/{ln_1, mamba/{in_proj, conv_weight, conv_bias,
dt_bias, A_log, D, norm, out_proj} | attn/{q_proj, k_proj, v_proj, o_proj} |
mlp/{router, router_bias, latent_down, latent_up, w_up, w_down} +
shared/{up_proj, down_proj}}``, ``ln_f``, ``lm_head``; every matrix ``[in,
out]``. Initialisers: normal(0.02), scales and ``D`` ones, the selection bias
zeros, ``A_log = log(1..H)``, ``dt_bias`` Mamba-2's own from the three
published numbers (the inverse softplus of ``dt`` log-uniform in
``[time_step_min, time_step_max]``, floored at ``time_step_floor``), and the
convolution's taps and bias uniform in ``+-conv_kernel^-1/2``, which is what
the family's module leaves them at (a depthwise ``Conv1d``'s own default; its
``_init_weights`` sets the matrices and the three vectors and not these).
With taps of normal(0.02) ``x``, ``B`` and ``C`` are so small that the whole
state term ``S C`` is a thousandth of a logit's deviation and no comparison
with a reference can tell whether a head reads its own group's ``B`` and
``C``, or whether a state is carried at all (PERF.md section 6, PR 66: at the
published widths every head reading group 0 moves the logits by 0.1% of their
deviation under those taps and by 26% under these).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from trlx_tpu.models.deepseek_v3 import _dense, _Kernel
from trlx_tpu.models.olmoe import RMSNorm
from trlx_tpu.models.qwen3_next import _refuse_sharded_mesh
from trlx_tpu.ops import moe, ssm
from trlx_tpu.ops.attention import causal_dispatch, decode_attention, dot_product_attention
from trlx_tpu.ops.kv_cache import VALID_STATE_DTYPES, hybrid_cache
from trlx_tpu.telemetry import get_metrics

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    max_position_embeddings: int = 262144
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
    )
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attention_bias: bool = False
    sliding_window: Optional[int] = None
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    expand: int = 2
    use_conv_bias: bool = True
    use_bias: bool = False
    mamba_proj_bias: bool = False
    mamba_hidden_act: str = "silu"
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 0.0001
    mlp_hidden_act: str = "relu2"
    mlp_bias: bool = False
    n_routed_experts: int = 512  # the experts held here
    # the cut's own: the router's published width (None: all are held) and
    # the first expert held
    num_router_experts: Optional[int] = None
    first_local_expert: int = 0
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688  # one expert's width
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = False
    residual_in_fp32: bool = False
    num_nextn_predict_layers: int = 0
    rope_theta: float = 10000.0  # published; unread without positions
    state_dtype: str = "float32"
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    kv_cache_dtype: str = "bfloat16"

    def __post_init__(self):
        def refuse(what: str):
            raise ValueError(f"{what} is not built for nemotron_h")

        if self.num_router_experts is None:
            object.__setattr__(self, "num_router_experts", self.n_routed_experts)
        pattern = self.hybrid_override_pattern
        unknown = sorted(set(pattern) - {MAMBA, ATTENTION, EXPERTS})
        if unknown:
            refuse(f"hybrid_override_pattern's {unknown} (M a mixer, E the routed MLP, * attention; the dense "
                   "MLP '-' of the family's siblings has no layer here)")
        if len(pattern) != self.num_hidden_layers:
            raise ValueError(
                f"hybrid_override_pattern names {len(pattern)} layers, num_hidden_layers={self.num_hidden_layers}"
            )
        if self.num_nextn_predict_layers:
            refuse(f"num_nextn_predict_layers={self.num_nextn_predict_layers} (the multi-token-prediction module)")
        if self.attention_bias or self.use_bias or self.mamba_proj_bias or self.mlp_bias:
            refuse("attention_bias / use_bias / mamba_proj_bias / mlp_bias")
        if self.tie_word_embeddings:
            refuse("tie_word_embeddings=True")
        if self.residual_in_fp32:
            refuse("residual_in_fp32=True")
        if self.sliding_window is not None:
            refuse(f"sliding_window={self.sliding_window}")
        if not self.norm_topk_prob or self.n_shared_experts != 1:
            refuse(f"norm_topk_prob={self.norm_topk_prob} / n_shared_experts={self.n_shared_experts} (true, 1)")
        if self.mamba_hidden_act != "silu":
            refuse(f"mamba_hidden_act={self.mamba_hidden_act!r} (silu)")
        if self.mlp_hidden_act not in moe.ACTIVATIONS:
            refuse(f"mlp_hidden_act={self.mlp_hidden_act!r} {sorted(moe.ACTIVATIONS)}")
        if self.kv_cache_dtype != "bfloat16":
            refuse(f"kv_cache_dtype={self.kv_cache_dtype!r} beside state layers (bfloat16)")
        if self.state_dtype not in VALID_STATE_DTYPES:
            refuse(f"state_dtype={self.state_dtype!r} {VALID_STATE_DTYPES}")
        if self.mamba_num_heads * self.mamba_head_dim != self.expand * self.hidden_size:
            raise ValueError("mamba_num_heads * mamba_head_dim != expand * hidden_size")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(f"mamba_num_heads={self.mamba_num_heads} does not divide into n_groups={self.n_groups}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads does not divide over num_key_value_heads")
        if not 0 <= self.first_local_expert <= self.num_router_experts - self.n_routed_experts:
            raise ValueError(
                f"experts {self.first_local_expert} .. "
                f"{self.first_local_expert + self.n_routed_experts} are not among the "
                f"router's {self.num_router_experts}"
            )

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "NemotronHConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(self.hybrid_override_pattern)

    @property
    def cache_layer_types(self) -> Tuple[str, ...]:
        """The layers that keep something, in their order: what the cache
        has an entry for."""
        return tuple(kind for kind in self.layer_types if kind != EXPERTS)

    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_embd(self) -> int:
        return self.hidden_size

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size


# no rule: a state has no head axis a tp rule shards here, the router's
# finished routing and the plain expert are built off an ep mesh only, and
# the model refuses tp / ep / pp meshes by name; a trainer's dp x fsdp mesh
# shards every leaf by the partitioner's fallback
NEMOTRON_H_PARTITION_RULES: list = []

_normal = nn.initializers.normal(0.02)


def _conv_init(kernel: int):
    """Uniform in ``+-kernel^-1/2``: a depthwise ``Conv1d``'s default (its
    fan-in is the kernel's width), taps and bias alike."""
    bound = kernel ** -0.5
    return lambda key, shape, dtype: jax.random.uniform(key, shape, dtype, -bound, bound)


def _dt_bias_init(cfg: NemotronHConfig):
    """Inverse softplus of ``dt`` log-uniform over the published
    ``[time_step_min, time_step_max]``, floored at ``time_step_floor``
    (Mamba-2's own)."""
    lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)

    def init(key, shape, dtype):
        dt = jnp.maximum(jnp.exp(lo + (hi - lo) * jax.random.uniform(key, shape)), cfg.time_step_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    return init


class NemotronHMamba(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x, mask, fresh, cache_layer=None):
        cfg = self.config
        pdtype = jnp.dtype(cfg.param_dtype)
        H, inner, width = cfg.mamba_num_heads, cfg.mamba_inner, cfg.conv_channels
        conv_init = _conv_init(cfg.conv_kernel)
        conv_weight = self.param("conv_weight", conv_init, (cfg.conv_kernel, width), pdtype)
        conv_bias = self.param("conv_bias", conv_init, (width,), pdtype) if cfg.use_conv_bias else None
        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (H,), pdtype)
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
            A_log=A_log, D=D, n_heads=H, head_dim=cfg.mamba_head_dim, d_state=cfg.ssm_state_size,
            chunk=cfg.chunk_size, mask=mask, fresh=fresh, cache_layer=cache_layer,
            n_groups=cfg.n_groups,
        )
        with jax.named_scope("ssm_out"):
            y = ssm.gated_rms_norm(y, z, norm, cfg.layer_norm_epsilon, cfg.n_groups)
            return _dense(cfg.hidden_size, cfg, "out_proj")(y.astype(jnp.dtype(cfg.dtype))), new_layer


class NemotronHAttention(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x, bias, cache_kv=None, cache_index=None, causal=False):
        cfg = self.config
        B, T, D = x.shape
        H, H_kv, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = _dense(H * Dh, cfg, "q_proj")(x).reshape(B, T, H, Dh)
        k = _dense(H_kv * Dh, cfg, "k_proj")(x).reshape(B, T, H_kv, Dh)
        v = _dense(H_kv * Dh, cfg, "v_proj")(x).reshape(B, T, H_kv, Dh)
        new_kv = None
        if cache_kv is not None:
            out, new_kv = decode_attention(q, k, v, cache_kv, cache_index, bias, causal=causal)
        else:
            out = dot_product_attention(q, k, v, bias, causal=causal)
        return _dense(D, cfg, "o_proj")(out.reshape(B, T, H * Dh)), new_kv


class NemotronHSharedMLP(nn.Module):
    """The shared expert: plain, on the stream itself; float32 out, for the
    sum with the routed part."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        F = cfg.moe_shared_expert_intermediate_size
        with jax.named_scope("moe_shared"):
            h = moe.ACTIVATIONS[cfg.mlp_hidden_act](_dense(F, cfg, "up_proj")(x))
            w_down = _Kernel((F, cfg.hidden_size), jnp.dtype(cfg.param_dtype), name="down_proj")()
            return jnp.dot(h, w_down.astype(h.dtype), preferred_element_type=jnp.float32)


class NemotronHLatentMoE(nn.Module):
    """The routed experts held here, in their latent (``ops/moe.py``): the
    router reads the stream, the experts its projection. Returns the
    layer's routed part at the model's width in float32 and the step's
    routing statistics."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        D, Z, F = cfg.hidden_size, cfg.moe_latent_size, cfg.moe_intermediate_size
        E, held, first = cfg.num_router_experts, cfg.n_routed_experts, cfg.first_local_expert
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        router = self.param("router", _normal, (D, E), pdtype)
        bias = self.param("router_bias", nn.initializers.zeros, (E,), pdtype)
        w_up = self.param("w_up", _normal, (held, Z, F), pdtype)
        w_down = self.param("w_down", _normal, (held, F, Z), pdtype)
        get_metrics().gauge("moe/latent_width").set(Z)
        with jax.named_scope("moe_group_router"):
            routing = moe.route_group_limited(
                x.reshape(-1, D), router, bias, cfg.num_experts_per_tok,
                n_group=cfg.n_group, topk_group=cfg.topk_group, scale=cfg.routed_scaling_factor,
            )
        with jax.named_scope("moe_latent_down"):
            latent = _dense(Z, cfg, "latent_down")(x)
        routed, _ = moe.expert_layer(
            latent, None, None, w_up, w_down, dtype=dtype, routing=routing,
            first_expert=first, activation=cfg.mlp_hidden_act,
        )
        with jax.named_scope("moe_latent_up"):
            w_back = _Kernel((Z, D), pdtype, name="latent_up")()
            y = jnp.dot(routed, w_back.astype(dtype), preferred_element_type=jnp.float32)
        return y, moe.routing_stats(routing, E, first, held)


class NemotronHLayer(nn.Module):
    config: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x, bias, cache_layer=None, cache_index=None, causal=False, columns=(None, None)):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        h = RMSNorm(cfg.layer_norm_epsilon, dtype, jnp.dtype(cfg.param_dtype), name="ln_1")(x)
        new_layer = stats = None
        if self.kind == MAMBA:
            mixed, new_layer = NemotronHMamba(cfg, name="mamba")(h, *columns, cache_layer)
        elif self.kind == ATTENTION:
            mixed, new_layer = NemotronHAttention(cfg, name="attn")(h, bias, cache_layer, cache_index, causal)
        else:
            routed, stats = NemotronHLatentMoE(cfg, name="mlp")(h)
            mixed = (routed + NemotronHSharedMLP(cfg, name="shared")(h)).astype(dtype)
        return x + mixed, new_layer, stats


class NemotronHModel(nn.Module):
    """Same interface as ``OlmoeModel`` (``moe_stats``: the routing
    statistics of this call over its routed layers) but for the hydra
    hooks, which are refused. ``cache`` has an entry for each layer of
    ``config.cache_layer_types``, in that order."""

    config: NemotronHConfig

    def setup(self):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        self.wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, param_dtype=pdtype, embedding_init=_normal, name="wte",
        )
        self.h = [NemotronHLayer(cfg, kind, name=f"h_{i}") for i, kind in enumerate(cfg.layer_types)]
        self.ln_f = RMSNorm(cfg.layer_norm_epsilon, dtype, pdtype, name="ln_f")
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
        position_ids: Optional[jax.Array] = None,  # accepted and unread: no positions
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
                "built for nemotron_h: nothing trains it with a branch; use "
                "num_layers_unfrozen = -1 (a whole reference copy)"
            )
        if cache is not None and jnp.ndim(cache_index) == 2:
            raise ValueError(
                "per-column cache targets (the speculative verify step) are not built "
                "for nemotron_h: a rejected column cannot be taken out of a state"
            )
        _refuse_sharded_mesh("nemotron_h")
        B, T = input_ids.shape
        x = self.wte(input_ids).astype(jnp.dtype(cfg.dtype))

        kept = cfg.cache_layer_types
        if cache is None:
            bias, causal = causal_dispatch(T, None, None, attention_mask)
            columns = (attention_mask, None)
        else:
            if len(cache) != len(kept):
                raise ValueError(
                    f"the cache has {len(cache)} entries; nemotron_h keeps one for each of its "
                    f"{len(kept)} mixer and attention layers (init_nemotron_h_cache), none for an expert layer"
                )
            kv_layers = [c for c, kind in zip(cache, kept) if kind == ATTENTION]
            bias, causal = (
                causal_dispatch(T, kv_layers, cache_index, attention_mask) if kv_layers else (None, False)
            )
            columns = ssm.call_columns(attention_mask, cache_index, B, T)

        entries = iter(cache or ())  # one a layer that keeps something, in the layers' order
        new_cache: List = []
        per_block: List = []
        for i, kind in enumerate(cfg.layer_types):
            kept_here = next(entries) if cache is not None and kind != EXPERTS else None
            x, new_layer, stats = self.h[i](x, bias, kept_here, cache_index, causal, columns)
            if kept_here is not None:
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


def init_nemotron_h_cache(config: NemotronHConfig, batch_size: int, capacity: int):
    """A state and a convolution tail for an ``M`` layer, keys and values
    for a ``*`` layer, nothing for an ``E`` layer: one entry a layer of
    ``config.cache_layer_types``."""
    return hybrid_cache(
        config.cache_layer_types, batch_size, capacity,
        n_kv_head=config.num_key_value_heads, head_dim=config.head_dim,
        dtype=config.dtype, kv_cache_dtype=config.kv_cache_dtype,
        state={
            "n_head": config.mamba_num_heads, "head_dim": config.mamba_head_dim,
            "d_state": config.ssm_state_size, "conv_width": config.conv_kernel,
            "conv_channels": config.conv_channels,
        },
        state_dtype=config.state_dtype, keys=(ATTENTION,),
    )


def no_nemotron_h_checkpoint(path: str, dtype: str = "float32"):
    raise ValueError(
        "no checkpoint converter is built for nemotron_h; give the sizes as "
        "model.model_arch (weights from the seed)"
    )
