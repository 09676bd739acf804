"""Qwen3-Next causal LM (``model_type: qwen3_next``,
``Qwen/Qwen3-Next-80B-A3B-*``): a pre-norm decoder whose layers are gated
delta-rule mixers (linear attention: a matrix state a head, no keys) with one
gated softmax-attention layer in ``full_attention_interval``, each followed
by routed experts with a gated shared expert beside them.

Written from the family's published ``config.json`` and its ``transformers``
module (the rule: Yang, Kautz & Hatamizadeh, arXiv:2412.06464):

    norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)      zero-centred, w zeros at first
    h += mix_i(norm(h))        mix_i: attention where (i + 1) % interval == 0, else the rule
    h += moe(norm(h))
    logits = norm(h) W_head                             (untied head, no biases anywhere)

- **linear attention** (``ops/delta.py``): ``[q | k | v | z] = u W_qkvz``
  and ``[b | a] = u W_ba`` with ``u`` the normed input, zero at masked
  columns; ``[q | k | v]`` through a depthwise causal convolution of
  ``linear_conv_kernel_dim`` without bias and ``silu``; ``q``, ``k``
  L2-normalised a head, ``linear_num_value_heads`` value heads over
  ``linear_num_key_heads`` key heads; ``beta = sigmoid(b)``, ``g =
  -exp(A_log) softplus(a + dt_bias)``; the gated delta rule on a state of
  ``[key, value]`` a value head; ``(rms(o) * w_n * silu(z)) W_o`` a head. It
  keeps a state and a convolution tail a sequence and no keys.
- **attention**: ``[q | gate] = u W_q`` split a head, ``num_attention_heads``
  query heads over ``num_key_value_heads`` KV heads of ``head_dim``,
  zero-centred RMSNorm on ``q`` and ``k`` a head, rotary on the first
  ``partial_rotary_factor`` of a head in the half-split convention, scores
  scaled by ``head_dim^-1/2``, the output times ``sigmoid(gate)``.
- **experts** (``ops/moe.py``): a softmax router over
  ``num_router_experts`` (the published ``num_experts``),
  ``num_experts_per_tok`` a token, their weights renormalised
  (``norm_topk_prob``); SwiGLU experts ``moe_intermediate_size`` wide. This
  program holds ``num_experts`` of them from ``first_local_expert`` on: all
  of them, or one chip's share of an ``ep`` group, whose part of the sum is
  what the layer returns. The shared SwiGLU expert,
  ``shared_expert_intermediate_size`` wide, is computed whole, multiplied
  with ``sigmoid(u w_s)`` and added in float32.

Same call interface as ``OlmoeModel`` but for the hydra hooks, which are
refused; ``moe_stats`` in its output. The cache is a tuple whose layers
differ (``ops/kv_cache.py::hybrid_cache``): a ``full_attention`` layer goes
through ``decode_attention``, a ``linear_attention`` layer through
``ops/delta.py``, which reads from the cache mask which columns of a call
are valid and which rows start fresh (``ops/ssm.py::call_columns``).

What the published configuration may say and this family does not build is
refused by name: ``rope_scaling``, ``mlp_only_layers``,
``decoder_sparse_step`` other than 1, ``use_sliding_window``,
``attention_bias``, a tied head, an activation other than ``silu``, an int8
cache, a ``tp`` / ``ep`` / ``pp`` mesh, the hydra branch and the speculative
verify step. The multi-token-prediction module of the published checkpoint
has no key in ``config.json`` and is not built.

Parameters: ``wte``, ``h_<i>/{ln_1, linear_attn/{in_proj_qkvz, in_proj_ba,
conv_weight, dt_bias, A_log, norm, out_proj} | attn/{q_proj, k_proj, v_proj,
q_norm, k_norm, o_proj}, ln_2, mlp/{router, w_gate, w_up, w_down},
shared/{gate_proj, up_proj, down_proj, gate}}``, ``ln_f``, ``lm_head``. The
fused projections' columns are ``[q | k | v | z]`` and ``[b | a]`` whole (the
published module interleaves them a key head; a converter would permute the
columns). Initialisers: normal(0.02), norm offsets zeros, the rule's norm
ones, and the rule's two vectors as the Gated DeltaNet layer draws them:
``A_log = log(U(0, 16))`` and ``dt_bias`` the inverse softplus of ``dt``
log-uniform in ``[0.001, 0.1]`` (``models/granite_hybrid.py::_dt_bias_init``
and its reason: a decay a trained model has, under which a comparison can
tell whether the state is carried).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from trlx_tpu.models.deepseek_v3 import _Kernel
from trlx_tpu.models.granite_hybrid import _dt_bias_init
from trlx_tpu.ops import delta, moe, ssm
from trlx_tpu.ops.attention import causal_dispatch, decode_attention, dot_product_attention
from trlx_tpu.ops.kv_cache import VALID_STATE_DTYPES, hybrid_cache
from trlx_tpu.ops.rotary import apply_rotary_half, rotary_angles

FULL, LINEAR = "full_attention", "linear_attention"


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    max_position_embeddings: int = 262144
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    layer_types: Optional[Tuple[str, ...]] = None  # None: from full_attention_interval
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    rope_scaling: Optional[Any] = None
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    linear_chunk_size: int = 64  # the family's chunk; no published key
    intermediate_size: int = 5120  # published; no layer is dense
    moe_intermediate_size: int = 512  # one expert's width
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512  # the experts held here
    # the cut's own: the router's published width (None: all are held) and
    # the first expert held
    num_router_experts: Optional[int] = None
    first_local_expert: int = 0
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    router_aux_loss_coef: float = 0.001
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    attention_bias: bool = False
    use_sliding_window: bool = False
    tie_word_embeddings: bool = False
    state_dtype: str = "float32"
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    kv_cache_dtype: str = "bfloat16"

    def __post_init__(self):
        def refuse(what: str):
            raise ValueError(f"{what} is not built for qwen3_next")

        if self.layer_types is None:
            every = self.full_attention_interval
            object.__setattr__(self, "layer_types", tuple(
                FULL if (i + 1) % every == 0 else LINEAR for i in range(self.num_hidden_layers)
            ))
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "mlp_only_layers", tuple(self.mlp_only_layers or ()))
        if self.num_router_experts is None:
            object.__setattr__(self, "num_router_experts", self.num_experts)
        if len(self.layer_types) != self.num_hidden_layers or set(self.layer_types) - {FULL, LINEAR}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers from "
                f"{(FULL, LINEAR)}; got {self.layer_types}"
            )
        if self.rope_scaling is not None:
            refuse(f"rope_scaling={self.rope_scaling!r}")
        if self.mlp_only_layers:
            refuse(f"mlp_only_layers={list(self.mlp_only_layers)} (a dense block)")
        if self.decoder_sparse_step != 1:
            refuse(f"decoder_sparse_step={self.decoder_sparse_step} (1: every block routed)")
        if self.use_sliding_window or self.attention_bias:
            refuse("use_sliding_window / attention_bias")
        if self.tie_word_embeddings:
            refuse("tie_word_embeddings=True")
        if self.hidden_act != "silu":
            refuse(f"hidden_act={self.hidden_act!r} (silu)")
        if self.kv_cache_dtype != "bfloat16":
            refuse(f"kv_cache_dtype={self.kv_cache_dtype!r} beside state layers (bfloat16)")
        if self.state_dtype not in VALID_STATE_DTYPES:
            refuse(f"state_dtype={self.state_dtype!r} {VALID_STATE_DTYPES}")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"partial_rotary_factor x head_dim = {self.rotary_dim} is rotated in halves")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads does not divide over num_key_value_heads")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("linear_num_value_heads does not divide over linear_num_key_heads")
        if not 0 <= self.first_local_expert <= self.num_router_experts - self.num_experts:
            raise ValueError(
                f"experts {self.first_local_expert} .. "
                f"{self.first_local_expert + self.num_experts} are not among the "
                f"router's {self.num_router_experts}"
            )

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Qwen3NextConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_embd(self) -> int:
        return self.hidden_size

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_width(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_channels(self) -> int:
        return 2 * self.key_width + self.value_width


# no rule: a state has no head axis a tp rule shards here, the shared term
# beside the experts is built off an ep mesh only, and the model refuses tp /
# ep / pp meshes by name; a trainer's dp x fsdp mesh shards every leaf by the
# partitioner's fallback
QWEN3_NEXT_PARTITION_RULES: list = []

_normal = nn.initializers.normal(0.02)
A_RANGE = (0.0, 16.0)


def _a_log_init(key, shape, dtype):
    """``log(A)`` with ``A`` uniform over ``A_RANGE`` (the Gated DeltaNet
    layer's own; the smallest draw is kept off zero)."""
    lo, hi = A_RANGE
    return jnp.log(jnp.maximum(jax.random.uniform(key, shape, minval=lo, maxval=hi), 1e-4)).astype(dtype)


def _dense(features: int, cfg, name: str):
    return nn.Dense(
        features, use_bias=False, dtype=jnp.dtype(cfg.dtype),
        param_dtype=jnp.dtype(cfg.param_dtype), kernel_init=_normal, name=name,
    )


class ZeroCentredRMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * (1 + scale)``, computed in float32;
    ``scale`` zeros at first."""

    epsilon: float
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.zeros, (x.shape[-1],), self.param_dtype)
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.epsilon)
        return (x32 * (1.0 + scale.astype(jnp.float32))).astype(self.dtype)


class Qwen3NextGatedDeltaNet(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, mask, fresh, cache_layer=None):
        cfg = self.config
        pdtype = jnp.dtype(cfg.param_dtype)
        B, T, _ = x.shape
        Hv, Dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
        width = cfg.conv_channels
        conv_weight = self.param("conv_weight", _normal, (cfg.linear_conv_kernel_dim, width), pdtype)
        dt_bias = self.param("dt_bias", _dt_bias_init, (Hv,), pdtype)
        A_log = self.param("A_log", _a_log_init, (Hv,), pdtype)
        norm = self.param("norm", nn.initializers.ones, (Dv,), pdtype)
        # device-trace scope names are a contract (docs/observability.md)
        with jax.named_scope("gdn_in_proj"):
            if mask is not None:
                x = x * mask[..., None].astype(x.dtype)
            qkvz = _dense(width + cfg.value_width, cfg, "in_proj_qkvz")(x)
            ba = _dense(2 * Hv, cfg, "in_proj_ba")(x)
        o, new_layer = delta.gated_delta_mix(
            qkvz[..., :width], ba[..., :Hv], ba[..., Hv:], conv_weight=conv_weight,
            dt_bias=dt_bias, A_log=A_log, n_key_heads=cfg.linear_num_key_heads,
            n_value_heads=Hv, key_dim=cfg.linear_key_head_dim, value_dim=Dv,
            chunk=cfg.linear_chunk_size, mask=mask, fresh=fresh, cache_layer=cache_layer,
        )
        with jax.named_scope("gdn_out"):
            z = qkvz[..., width:].reshape(B, T, Hv, Dv)
            o = delta.rms_norm_gated(o, z, norm, cfg.rms_norm_eps).astype(jnp.dtype(cfg.dtype))
            return _dense(cfg.hidden_size, cfg, "out_proj")(o.reshape(B, T, Hv * Dv)), new_layer


class Qwen3NextAttention(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, bias, position_ids, cache_kv=None, cache_index=None, causal=False):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        B, T, D = x.shape
        H, H_kv, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        norm = lambda name: ZeroCentredRMSNorm(cfg.rms_norm_eps, dtype, pdtype, name=name)
        q_gate = _dense(H * 2 * Dh, cfg, "q_proj")(x).reshape(B, T, H, 2 * Dh)
        q, gate = norm("q_norm")(q_gate[..., :Dh]), q_gate[..., Dh:]
        k = norm("k_norm")(_dense(H_kv * Dh, cfg, "k_proj")(x).reshape(B, T, H_kv, Dh))
        v = _dense(H_kv * Dh, cfg, "v_proj")(x).reshape(B, T, H_kv, Dh)

        sin, cos = rotary_angles(position_ids, cfg.rotary_dim, cfg.rope_theta)
        q = apply_rotary_half(q, sin, cos, cfg.rotary_dim)
        k = apply_rotary_half(k, sin, cos, cfg.rotary_dim)

        new_kv = None
        if cache_kv is not None:
            out, new_kv = decode_attention(q, k, v, cache_kv, cache_index, bias, causal=causal)
        else:
            out = dot_product_attention(q, k, v, bias, causal=causal)
        with jax.named_scope("attn_gate"):
            gated = out.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
            out = gated.astype(dtype).reshape(B, T, H * Dh)
        return _dense(D, cfg, "o_proj")(out), new_kv


class Qwen3NextSharedExpert(nn.Module):
    """The shared expert under its gate: ``sigmoid(x w_s) * SwiGLU(x)``,
    float32 out, for the sum with the routed part."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        F = cfg.shared_expert_intermediate_size
        pdtype = jnp.dtype(cfg.param_dtype)
        with jax.named_scope("moe_shared"):
            h = jax.nn.silu(_dense(F, cfg, "gate_proj")(x)) * _dense(F, cfg, "up_proj")(x)
            w_down = _Kernel((F, cfg.hidden_size), pdtype, name="down_proj")()
            y = jnp.dot(h, w_down.astype(h.dtype), preferred_element_type=jnp.float32)
            w_s = self.param("gate", _normal, (cfg.hidden_size,), pdtype)
            score = jnp.sum(x.astype(jnp.float32) * w_s.astype(jnp.float32), axis=-1, keepdims=True)
            return jax.nn.sigmoid(score) * y


class Qwen3NextSparseMLP(nn.Module):
    """The routed experts held here plus ``shared`` (``ops/moe.py``).
    Returns the output and the step's routing statistics."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, shared):
        cfg = self.config
        D, F = cfg.hidden_size, cfg.moe_intermediate_size
        E, held, first = cfg.num_router_experts, cfg.num_experts, cfg.first_local_expert
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        router = self.param("router", _normal, (D, E), pdtype)
        w_gate = self.param("w_gate", _normal, (held, D, F), pdtype)
        w_up = self.param("w_up", _normal, (held, D, F), pdtype)
        w_down = self.param("w_down", _normal, (held, F, D), pdtype)
        y, routing = moe.expert_layer(
            x, router, w_gate, w_up, w_down, k=cfg.num_experts_per_tok,
            norm_topk=cfg.norm_topk_prob, dtype=dtype, first_expert=first, shared=shared,
        )
        return y, moe.routing_stats(routing, E, first, held)


class Qwen3NextBlock(nn.Module):
    config: Qwen3NextConfig
    kind: str

    @nn.compact
    def __call__(self, x, bias, position_ids, cache_layer=None, cache_index=None, causal=False,
                 columns=(None, None)):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        norm = lambda name: ZeroCentredRMSNorm(cfg.rms_norm_eps, dtype, pdtype, name=name)
        h = norm("ln_1")(x)
        if self.kind == FULL:
            mixed, new_layer = Qwen3NextAttention(cfg, name="attn")(
                h, bias, position_ids, cache_layer, cache_index, causal
            )
        else:
            mixed, new_layer = Qwen3NextGatedDeltaNet(cfg, name="linear_attn")(h, *columns, cache_layer)
        x = x + mixed
        h = norm("ln_2")(x)
        y, stats = Qwen3NextSparseMLP(cfg, name="mlp")(h, Qwen3NextSharedExpert(cfg, name="shared")(h))
        return x + y, new_layer, stats


def _refuse_sharded_mesh(family: str = "qwen3_next"):
    """A ``tp``, ``ep`` or ``pp`` axis on the mesh the traced program
    declares (``parallel/mesh.py::traced_on``), or an installed ``ep`` mesh
    context: none shards ``family`` (this one, and ``models/ling.py``)."""
    from trlx_tpu.models.gpt2_moe import get_ep_mesh
    from trlx_tpu.parallel.mesh import program_mesh

    mesh = program_mesh()
    sizes = dict(mesh.shape) if mesh is not None else {}
    sharded = {axis for axis in ("tp", "ep", "pp") if sizes.get(axis, 1) > 1}
    if get_ep_mesh() is not None:
        sharded.add("ep")
    for axis in ("tp", "ep", "pp"):
        if axis in sharded:
            raise ValueError(
                f"a {axis} mesh is not built for {family}: a state has no head axis "
                "sharded here and the shared expert beside the experts is built "
                "off an ep mesh only (ops/moe.py); use dp / fsdp"
            )


class Qwen3NextModel(nn.Module):
    """Same interface as ``OlmoeModel`` (``moe_stats``: the routing
    statistics of this call over its blocks) but for the hydra hooks, which
    are refused."""

    config: Qwen3NextConfig

    def setup(self):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        self.wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, param_dtype=pdtype, embedding_init=_normal, name="wte",
        )
        self.h = [Qwen3NextBlock(cfg, kind, name=f"h_{i}") for i, kind in enumerate(cfg.layer_types)]
        self.ln_f = ZeroCentredRMSNorm(cfg.rms_norm_eps, dtype, pdtype, name="ln_f")
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
                "built for qwen3_next: nothing trains it with a branch; use "
                "num_layers_unfrozen = -1 (a whole reference copy)"
            )
        if cache is not None and jnp.ndim(cache_index) == 2:
            raise ValueError(
                "per-column cache targets (the speculative verify step) are not built "
                "for qwen3_next: a rejected column cannot be taken out of a state"
            )
        _refuse_sharded_mesh()
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
            kv_layers = [c for c, kind in zip(cache, cfg.layer_types) if kind == FULL]
            bias, causal = (
                causal_dispatch(T, kv_layers, cache_index, attention_mask)
                if kv_layers else (None, False)
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


def init_qwen3_next_cache(config: Qwen3NextConfig, batch_size: int, capacity: int):
    """Keys and values for a ``full_attention`` layer, a matrix state and a
    convolution tail for a ``linear_attention`` layer."""
    return hybrid_cache(
        config.layer_types, batch_size, capacity,
        n_kv_head=config.num_key_value_heads, head_dim=config.head_dim,
        dtype=config.dtype, kv_cache_dtype=config.kv_cache_dtype,
        state={
            "n_head": config.linear_num_value_heads, "head_dim": config.linear_key_head_dim,
            "d_state": config.linear_value_head_dim, "conv_width": config.linear_conv_kernel_dim,
            "conv_channels": config.conv_channels,
        },
        state_dtype=config.state_dtype, keys=(FULL,),
    )


def no_qwen3_next_checkpoint(path: str, dtype: str = "float32"):
    raise ValueError(
        "no checkpoint converter is built for qwen3_next; give the sizes as "
        "model.model_arch (weights from the seed)"
    )
