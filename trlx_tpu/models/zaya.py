"""ZAYA1 causal LM (``model_type: zaya``, ``Zyphra/ZAYA1-8B``): a pre-norm
decoder whose every layer attends in a compressed latent with a
convolution tail (CCA, ``ops/cca.py``) and then routes each token to one of
``num_experts`` experts, or to none, through a router that is an MLP with a
carry from the layer before.

Written from the family's published ``config.json`` and Zyphra's
descriptions of CCA and of ZAYA1 (``benchmark/reference/zaya.py`` marks each
term by where it comes from and lists what is assumed):

    block l, input x [T, d], router carry r_prev [T, R] (zeros at l = 0)

    u = rms(x)
    q~ = u Wq [H_q Dh]   k~ = u Wk [H_kv Dh]   v~ = u Wv [H_kv Dh]
    (q, k, v) = cca_mix(q~, k~, v~)        two causal convolutions over [q~ | k~], the
                                           q-k mean, L2 norms with a temperature on k,
                                           half of the value heads from the token before
    rotary (half-split) on the first partial_rotary_factor of a head
    a = softmax(q k^T / sqrt(Dh) + causal) v,  H_q / H_kv query heads a KV head;  a Wo
    x <- (al1 x + be1) + (ga1 a + de1)     learned vectors, 1, 0, 1, 0 at first

    u = rms(x)
    r = u Wd + bd + eta * r_prev           carried on to block l + 1
    s = softmax(W3 gelu(W2 gelu(W1 rms(r))))   over num_experts + 1: the last is a skip
    e = argmax(s + b),  p = s[e]           b moves the choice, not the weight
    y = p * expert_e(u) if e < num_experts else 0
    x <- (al2 x + be2) + (ga2 y + de2)

    logits = rms(x_L) E^T                  (tied head)

The router is float32 throughout (``Precision.HIGHEST``), hands
``ops/moe.py::expert_layer`` a finished ``Routing`` 17 wide over the 16
experts held, and the skip's copies are multiplied with nothing. A layer's
cache is both at once (``ops/kv_cache.py``): paged keys and values of
``H_kv`` heads, and the mix's tail a slot (``init_zaya_cache``).

Same call interface as ``OlmoeModel`` (``cache``, ``cache_index``,
``compute_logits``, ``moe_stats``, a tied ``logits()``); the router carry is
threaded through the blocks inside the model. What the family does not
build is refused by name: the hydra branch (``start_layer`` /
``hidden_override`` / ``capture_hidden_at``: a branch would need the carry
of the layer below it), per-column cache targets (``verify_step``), a
``hybrid_sliding`` layer or a ``sliding_window``, ``attention_bias`` /
``lm_head_bias``, an untied head, more than one expert a token, an
activation other than ``silu``, an int8 cache, an ``ep`` mesh (a skip has
no rank to live on).

Parameters: ``wte``, ``h_<i>/{ln_1, attn/{q_proj, k_proj, v_proj, o_proj,
conv0_weight [K0, C], conv0_bias, conv1_weight [H, K1, Dh, Dh], conv1_bias,
k_temp}, merge_1, ln_2, mlp/{router/{down, carry_scale, norm, fc1, fc2, out,
balance_bias}, w_gate, w_up, w_down}, merge_2}``, ``ln_f``. Initialisers:
normal(0.02) for matrices and the table, ones for scales and ``k_temp``,
zeros for biases, but for the router's MLP: ``fc1``/``fc2`` keep their
input's size (normal(``R ** -0.5``)), and ``out`` is normal(
``ROUTER_OUT_STD * R ** -0.5``), which sets how peaked the scores are
(PERF.md section 6, PR 45: what the flip rate read). A block sows its
router's choices into ``intermediates`` (``router_choice``), which is how
that rate is read.
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
from trlx_tpu.ops import cca, moe, ssm
from trlx_tpu.ops.attention import (
    causal_dispatch,
    decode_attention,
    dot_product_attention,
)
from trlx_tpu.ops.kv_cache import VALID_STATE_DTYPES, kv_buffers, split_tail, tail_buffers
from trlx_tpu.ops.rotary import apply_rotary_half, rotary_angles

LAYER_KIND = "hybrid"


def _frozen(value):
    """A published nested group as something a frozen dataclass can hash."""
    if isinstance(value, dict):
        return tuple((k, _frozen(v)) for k, v in sorted(value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_frozen(v) for v in value)
    return value


@dataclass(frozen=True)
class ZayaConfig:
    vocab_size: int = 262272
    max_position_embeddings: int = 131072
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: Optional[Tuple[str, ...]] = None  # None: every layer hybrid
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    moe_intermediate_size: int = 2048  # one expert's width
    num_experts: int = 16
    num_experts_per_tok: int = 1
    router_hidden_size: int = 256
    partial_rotary_factor: float = 0.5
    # the published nested group; its ``hybrid`` entry gives theta and the
    # rotary share (a flat ``rope_theta`` is read only where it is absent)
    rope_parameters: Optional[Any] = None
    rope_theta: float = 5000000.0
    rms_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    attention_bias: bool = False
    lm_head_bias: bool = False
    sliding_window: Optional[int] = None
    tie_word_embeddings: bool = True
    router_aux_loss_coef: float = 0.0  # balanced by the bias, not by a loss
    state_dtype: str = "float32"
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    kv_cache_dtype: str = "bfloat16"

    def __post_init__(self):
        def refuse(what: str):
            raise ValueError(f"{what} is not built for zaya")

        if self.layer_types is None:
            object.__setattr__(self, "layer_types", (LAYER_KIND,) * self.num_hidden_layers)
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(f"layer_types must name {self.num_hidden_layers} layers")
        if set(self.layer_types) - {LAYER_KIND}:
            refuse(f"a layer of kind {sorted(set(self.layer_types) - {LAYER_KIND})} (a window among the layers)")
        if self.rope_parameters is not None:
            group = dict(_frozen(self.rope_parameters)).get(LAYER_KIND)
            if group is None:
                raise ValueError(f"rope_parameters has no {LAYER_KIND!r} group")
            group = dict(group)
            if group.get("rope_type", "default") != "default":
                refuse(f"rope_type={group['rope_type']!r}")
            if group.get("partial_rotary_factor", self.partial_rotary_factor) != self.partial_rotary_factor:
                raise ValueError("rope_parameters and partial_rotary_factor disagree")
            object.__setattr__(self, "rope_theta", float(group["rope_theta"]))
            object.__setattr__(self, "rope_parameters", _frozen(self.rope_parameters))
        if self.sliding_window is not None:
            refuse(f"sliding_window={self.sliding_window!r}")
        if self.attention_bias or self.lm_head_bias:
            refuse("attention_bias / lm_head_bias")
        if not self.tie_word_embeddings:
            refuse("tie_word_embeddings=False")
        if self.num_experts_per_tok != 1:
            refuse(f"num_experts_per_tok={self.num_experts_per_tok} (the router's top-1 with a skip)")
        if self.hidden_act != "silu":
            refuse(f"hidden_act={self.hidden_act!r} (silu)")
        if self.kv_cache_dtype != "bfloat16":
            refuse(f"kv_cache_dtype={self.kv_cache_dtype!r} beside a tail (bfloat16)")
        if self.state_dtype not in VALID_STATE_DTYPES:
            refuse(f"state_dtype={self.state_dtype!r} {VALID_STATE_DTYPES}")
        if self.num_attention_heads % self.num_key_value_heads or self.num_key_value_heads % 2:
            raise ValueError(
                "num_attention_heads must divide over an even num_key_value_heads "
                "(half of the value heads read the token before)"
            )
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"partial_rotary_factor gives {self.rotary_dim} of {self.head_dim} dimensions")
        if min(self.cca_time0, self.cca_time1) < 2:
            raise ValueError("cca_time0 and cca_time1 are convolution widths of at least 2")

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ZayaConfig":
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
    def mix_heads(self) -> int:
        return self.num_attention_heads + self.num_key_value_heads

    @property
    def mix_channels(self) -> int:
        return self.mix_heads * self.head_dim


# the latent projections over tp, everything else whole; the experts' [E]
# axis stays whole too: no ep mesh for a router with a skip
ZAYA_PARTITION_RULES = [
    (r"wte/embedding", P(None, "tp")),
    (r"attn/[qkv]_proj/kernel", P(None, "tp")),
    (r"attn/o_proj/kernel", P("tp", None)),
    (r"mlp/router", P()),
    (r"mlp/w_(gate|up|down)", P(None, None, None)),
]

_normal = nn.initializers.normal(0.02)
#: the router's output matrix is normal(this / sqrt(R)): seeded scores then put
#: 0.60 on the chosen expert in the mean, as a trained top-1 router's do, where
#: normal(0.02) gives 17 near-equal ones (PERF.md section 6, PR 45)
ROUTER_OUT_STD = 8.0


def _dense(features: int, cfg, name: str, init=_normal, use_bias=False, dtype=None):
    return nn.Dense(
        features, use_bias=use_bias, dtype=jnp.dtype(dtype or cfg.dtype),
        param_dtype=jnp.dtype(cfg.param_dtype), kernel_init=init, name=name,
    )


class ZayaAttention(nn.Module):
    config: ZayaConfig

    @nn.compact
    def __call__(self, x, bias, position_ids, mask, fresh, cache_layer=None, cache_index=None,
                 causal=False):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        B, T, D = x.shape
        H, H_kv, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        heads, K0, K1 = cfg.mix_heads, cfg.cca_time0, cfg.cca_time1
        zeros, ones = nn.initializers.zeros, nn.initializers.ones
        mix = dict(
            conv0_weight=self.param("conv0_weight", _normal, (K0, cfg.mix_channels), pdtype),
            conv0_bias=self.param("conv0_bias", zeros, (cfg.mix_channels,), pdtype),
            conv1_weight=self.param("conv1_weight", _normal, (heads, K1, Dh, Dh), pdtype),
            conv1_bias=self.param("conv1_bias", zeros, (heads, Dh), pdtype),
            k_temp=self.param("k_temp", ones, (H_kv,), pdtype),
        )
        kv, tail = (None, None) if cache_layer is None else split_tail(cache_layer)
        # device-trace scope names are a contract (docs/observability.md)
        with jax.named_scope("cca_proj"):
            if mask is not None:
                x = x * mask[..., None].astype(x.dtype)
            q_lat = _dense(H * Dh, cfg, "q_proj")(x)
            k_lat = _dense(H_kv * Dh, cfg, "k_proj")(x)
            v_lat = _dense(H_kv * Dh, cfg, "v_proj")(x)
        with jax.named_scope("cca_mix"):
            q, k, v, new_tail = cca.cca_mix(
                q_lat, k_lat, v_lat, **mix, n_q=H, n_kv=H_kv, head_dim=Dh, dtype=dtype,
                mask=mask, fresh=fresh, tail=tail,
            )
        with jax.named_scope("cca_attn"):
            sin, cos = rotary_angles(position_ids, cfg.rotary_dim, cfg.rope_theta)
            q = apply_rotary_half(q, sin, cos, cfg.rotary_dim)
            k = apply_rotary_half(k, sin, cos, cfg.rotary_dim)
            new_layer = None
            if kv is not None:
                out, new_kv = decode_attention(q, k, v, kv, cache_index, bias, causal=causal)
                new_layer = dict(new_kv, **new_tail)
            else:
                out = dot_product_attention(q, k, v, bias, causal=causal)
        with jax.named_scope("cca_out"):
            return _dense(D, cfg, "o_proj")(out.reshape(B, T, H * Dh)), new_layer


class ResidualMerge(nn.Module):
    """``(al * x + be) + (ga * branch + de)`` with learned vectors, in
    float32; 1, 0, 1, 0 at first: the plain residual."""

    config: ZayaConfig

    @nn.compact
    def __call__(self, x, branch):
        cfg = self.config
        d, pdtype = x.shape[-1], jnp.dtype(cfg.param_dtype)
        ones, zeros = nn.initializers.ones, nn.initializers.zeros
        vec = lambda name, init: self.param(name, init, (d,), pdtype).astype(jnp.float32)
        kept = x.astype(jnp.float32) * vec("skip_scale", ones) + vec("skip_bias", zeros)
        added = branch.astype(jnp.float32) * vec("branch_scale", ones) + vec("branch_bias", zeros)
        return (kept + added).astype(jnp.dtype(cfg.dtype))


class ZayaRouter(nn.Module):
    """The router: float32, ``(routing over num_experts + 1, the carry
    handed on)`` for ``u`` [N, d] and ``carry`` [N, R] or None."""

    config: ZayaConfig

    @nn.compact
    def __call__(self, u, carry):
        cfg = self.config
        R, E = cfg.router_hidden_size, cfg.num_experts
        pdtype, f32 = jnp.dtype(cfg.param_dtype), jnp.float32
        keep = nn.initializers.normal(R ** -0.5)
        mat = lambda name, shape, init: self.param(name, init, shape, pdtype).astype(f32)
        dot = lambda a, w: jnp.dot(a, w, precision=jax.lax.Precision.HIGHEST)
        r = dot(u.astype(f32), mat("down", (cfg.hidden_size, R), _normal))
        r = r + mat("down_bias", (R,), nn.initializers.zeros)
        scale = mat("carry_scale", (R,), nn.initializers.ones)
        if carry is not None:
            r = r + scale * carry
        h = r * jax.lax.rsqrt(jnp.mean(r * r, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        h = h * mat("norm", (R,), nn.initializers.ones)
        h = jax.nn.gelu(dot(h, mat("fc1", (R, R), keep)), approximate=False)
        h = jax.nn.gelu(dot(h, mat("fc2", (R, R), keep)), approximate=False)
        logits = dot(h, mat("out", (R, E + 1), nn.initializers.normal(ROUTER_OUT_STD * R ** -0.5)))
        probs = jax.nn.softmax(logits, axis=-1)
        chosen = jnp.argmax(probs + mat("balance_bias", (E + 1,), nn.initializers.zeros), axis=-1)
        weight = jnp.take_along_axis(probs, chosen[:, None], axis=-1)
        return moe.Routing(logits, probs, weight, chosen[:, None].astype(jnp.int32)), r


class ZayaSparseMLP(nn.Module):
    """The routed experts (``ops/moe.py``) under the family's own router.
    Returns the output, the carry and the step's routing statistics."""

    config: ZayaConfig

    @nn.compact
    def __call__(self, x, carry, token_mask=None):
        from trlx_tpu.models.gpt2_moe import get_ep_mesh

        cfg = self.config
        if get_ep_mesh() is not None:
            raise ValueError(
                "an ep mesh is not built for zaya: the router's skip is a choice no rank "
                "holds (ops/moe.py, a caller's own routing)"
            )
        D, F, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        w_gate = self.param("w_gate", _normal, (E, D, F), pdtype)
        w_up = self.param("w_up", _normal, (E, D, F), pdtype)
        w_down = self.param("w_down", _normal, (E, F, D), pdtype)
        with jax.named_scope("moe_router"):
            flat_carry = None if carry is None else carry.reshape(-1, carry.shape[-1])
            routing, new_carry = ZayaRouter(cfg, name="router")(x.reshape(-1, D), flat_carry)
        self.sow("intermediates", "router_choice", routing.experts[:, 0])
        y, _ = moe.expert_layer(x, None, w_gate, w_up, w_down, dtype=dtype, routing=routing)
        if self.is_mutable_collection("moe_losses"):
            for name, value in moe.balance_losses(routing, E + 1, token_mask).items():
                self.sow("moe_losses", name, value)
        stats = moe.routing_stats(routing, E + 1, 0, E, skip=E)
        return y, new_carry.reshape(x.shape[:-1] + (-1,)), stats


class ZayaBlock(nn.Module):
    config: ZayaConfig

    @nn.compact
    def __call__(self, x, carry, bias, position_ids, columns=(None, None), cache_layer=None,
                 cache_index=None, causal=False, token_mask=None):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype, pdtype, name=name)
        attn_out, new_layer = ZayaAttention(cfg, name="attn")(
            norm("ln_1")(x), bias, position_ids, *columns, cache_layer, cache_index, causal
        )
        x = ResidualMerge(cfg, name="merge_1")(x, attn_out)
        y, carry, stats = ZayaSparseMLP(cfg, name="mlp")(norm("ln_2")(x), carry, token_mask)
        return ResidualMerge(cfg, name="merge_2")(x, y), carry, new_layer, stats


class ZayaModel(nn.Module):
    """Same interface as ``OlmoeModel`` (``moe_stats``: the routing
    statistics of this call over its blocks) but for the hydra hooks, which
    are refused: a branch would start without the router carry."""

    config: ZayaConfig

    def setup(self):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        self.wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, param_dtype=pdtype,
            embedding_init=_normal, name="wte",
        )
        self.h = [ZayaBlock(cfg, name=f"h_{i}") for i in range(cfg.num_hidden_layers)]
        self.ln_f = RMSNorm(cfg.rms_norm_eps, dtype, pdtype, name="ln_f")

    def logits(self, hidden: jax.Array) -> jax.Array:
        """The tied head on (already ln_f-normalized) hidden states; float32."""
        return self.wte.attend(hidden.astype(jnp.dtype(self.config.dtype))).astype(jnp.float32)

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
                "built for zaya: a branch that starts at a layer needs the router carry of "
                "the layer below; use num_layers_unfrozen = -1 (a whole reference copy)"
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

        if cache is None:
            bias, causal = causal_dispatch(T, None, None, attention_mask)
            columns = (attention_mask, None)
        else:
            if jnp.ndim(cache_index) == 2:
                raise ValueError(
                    "per-column cache targets (the speculative verify step) are not built "
                    "for zaya: a rejected column cannot be taken out of a tail"
                )
            bias, causal = causal_dispatch(T, cache, cache_index, attention_mask)
            columns = ssm.call_columns(attention_mask, cache_index, B, T)
        # which tokens balance the router losses: a cached call's mask is
        # over cache slots, not over this call's tokens
        token_mask = attention_mask if cache is None else None

        new_cache: List = []
        per_block: List = []
        carry = None  # zeros at layer 0
        for i in range(cfg.num_hidden_layers):
            layer_cache = cache[i] if cache is not None else None
            x, carry, new_layer, stats = self.h[i](
                x, carry, bias, position_ids, columns, layer_cache, cache_index, causal, token_mask
            )
            new_cache.append(new_layer)
            per_block.append(stats)

        x = self.ln_f(x)
        stacked = {k: jnp.stack([s[k] for s in per_block]) for k in per_block[0]}
        return {
            "logits": self.logits(x) if compute_logits else None,
            "hidden": x,
            "cache": tuple(new_cache) if cache is not None else None,
            "moe_stats": {
                k: (jnp.max if k == "max_load" else jnp.sum if k == "rows_routed" else jnp.mean)(v)
                for k, v in stacked.items()
            },
        }


def init_zaya_cache(config: ZayaConfig, batch_size: int, capacity: int):
    """A layer's keys and values by position (``H_kv`` heads) and the mix's
    tail by slot: the last ``K0 - 1`` rows of ``[q~ | k~]``, the last ``K1 -
    1`` rows of the first convolution's output, the shifted value's source."""
    layers = kv_buffers(
        config.num_hidden_layers, batch_size, capacity, config.num_key_value_heads,
        config.head_dim, config.dtype, config.kv_cache_dtype,
    )
    rows = {
        "z": (config.cca_time0 - 1, config.mix_channels),
        "c0": (config.cca_time1 - 1, config.mix_channels),
        "v": (1, config.num_key_value_heads // 2 * config.head_dim),
    }
    return tuple(dict(layer, **tail_buffers(batch_size, rows, config.state_dtype)) for layer in layers)


def no_zaya_checkpoint(path: str, dtype: str = "float32"):
    raise ValueError(
        "no checkpoint converter is built for zaya; give the sizes as "
        "model.model_arch (weights from the seed)"
    )
