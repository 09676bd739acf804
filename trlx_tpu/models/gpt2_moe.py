"""GPT-2 Switch-MoE family: expert parallelism integrated into a real
trainable policy.

The reference has no MoE at all (SURVEY §2.9: expert parallel "NO"); round
1 shipped `parallel/moe.py` only as a standalone primitive. This family
makes the ``ep`` mesh axis a *training* capability: every ``moe_every``-th
transformer block replaces its dense MLP with a top-1 switch layer whose
experts shard over ``ep`` — dispatch/return ride two ``all_to_all``
collectives per layer (`parallel/moe.py`), composed with dp/fsdp on the
same mesh.

Two numerically-matching execution paths, chosen by the installed ep mesh:
- **dense** (no ``ep`` axis, decode, CPU tests): every expert computes all
  tokens; the one-hot gate selects — exact switch semantics with no
  capacity drops, affordable at small E and single-token decode;
- **sharded** (``ep`` > 1): `moe_apply`'s static-shape dispatch with
  per-device expert capacity ``ceil(capacity_factor · n_local / E)``.
  With ``capacity_factor >= n_experts`` nothing drops and the two paths
  agree exactly (`tests/test_moe_integration.py`).

The mesh is process state, not config (a ``Mesh`` can't live in a frozen
flax module): trainers install it via :func:`set_ep_mesh` before tracing;
``None`` (the default) keeps every forward on the dense path.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from trlx_tpu.models.gpt2 import Attention, GPT2Model, PARTITION_RULES
# one copy for both MoE families; re-exported for this module's importers
from trlx_tpu.ops.moe import apply_router_penalty, moe_loss_summary  # noqa: F401

_EP_MESH: Optional[Mesh] = None


def set_ep_mesh(mesh: Optional[Mesh]) -> None:
    """Install (or clear) the mesh whose ``ep`` axis shards switch experts.
    Takes effect at trace time — call before building jitted programs."""
    global _EP_MESH
    _EP_MESH = mesh if mesh is not None and dict(mesh.shape).get("ep", 1) > 1 else None


def get_ep_mesh() -> Optional[Mesh]:
    """The currently-installed ep mesh context (None = dense path)."""
    return _EP_MESH


def reset() -> None:
    """Clear the module-global ep mesh context.

    The context is process state (see module docstring): an MoE trainer
    installs it and nothing ever uninstalls it, so a later *non*-MoE
    trace in the same process can silently re-enter the sharded expert
    path on a stale mesh. Test suites must call this between tests
    (``tests/conftest.py`` does, autouse); long-lived training processes
    that build successive trainers should call it when a trainer is
    discarded."""
    set_ep_mesh(None)


@dataclass
class GPT2MoEConfig:
    """GPT-2 arch + switch-MoE knobs. Deliberately not a GPT2Config
    subclass: the pp runner and HF converters key on exact GPT2Config."""

    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    layer_norm_epsilon: float = 1e-5
    n_experts: int = 4
    moe_every: int = 2  # blocks 1, 1+k, ... use the switch MLP
    capacity_factor: float = 2.0
    # load-balancing regularizers (training only; see SwitchMLP): the
    # Switch-Transformer auxiliary loss (α, paper §2.2 uses 0.01) keeps
    # top-1 routing from collapsing onto few experts once capacity drops
    # are real, and the ST-MoE router z-loss bounds router logit growth
    router_aux_coef: float = 0.01
    router_z_coef: float = 0.001
    dtype: str = "bfloat16"
    param_dtype: str = "float32"

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "GPT2MoEConfig":
        known = {f for f in cls.__dataclass_fields__}
        return cls(**{k: v for k, v in d.items() if k in known})


class SwitchMLP(nn.Module):
    """Top-1 switch MLP (router + E gelu experts), gate-weighted output.
    Residual stays outside (in the block), as switch layers require —
    over-capacity tokens on the sharded path contribute zero."""

    config: GPT2MoEConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:  # [B, T, D]
        cfg = self.config
        D, F, E = cfg.n_embd, 4 * cfg.n_embd, cfg.n_experts
        dtype = jnp.dtype(cfg.dtype)
        pdtype = jnp.dtype(cfg.param_dtype)
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (D, E), pdtype)
        wi = self.param("wi", init, (E, D, F), pdtype)
        bi = self.param("bi", nn.initializers.zeros, (E, F), pdtype)
        wo = self.param("wo", init, (E, F, D), pdtype)
        bo = self.param("bo", nn.initializers.zeros, (E, D), pdtype)

        shape = x.shape
        toks = x.reshape(-1, D).astype(dtype)

        # Router pass over the full token set, in float32 (near-tied logits
        # must argmax identically to the sharded twin of this layer).
        logits = toks.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)  # [N, E]
        expert = jnp.argmax(probs, axis=-1)  # [N]

        # Load-balancing regularizers, sown for the trainer's loss when it
        # opens the "moe_losses" collection (training forwards only; the
        # sampler applies immutably, where sow is a no-op):
        # - Switch-Transformer aux loss (§2.2): E · Σ_e f_e·P_e, with f_e
        #   the fraction of tokens argmax-routed to expert e (no gradient,
        #   as the paper prescribes) and P_e the mean router probability
        #   (carries the gradient). Uniform routing gives the minimum 1.
        # - ST-MoE router z-loss: mean(logsumexp(logits)²) bounds logit
        #   growth, keeping the f32 softmax sharp but stable.
        # - max_load: busiest expert's token fraction (diagnostic; 1/E is
        #   perfect balance, ~1 is router collapse).
        if self.is_mutable_collection("moe_losses"):
            frac = jnp.mean(
                jax.nn.one_hot(expert, E, dtype=jnp.float32), axis=0
            )
            pmean = jnp.mean(probs, axis=0)
            self.sow("moe_losses", "aux_loss", E * jnp.sum(frac * pmean))
            self.sow(
                "moe_losses", "router_z",
                jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
            )
            self.sow("moe_losses", "max_load", jnp.max(frac))

        # Single-token forwards (T == 1, a static trace-time property —
        # exactly the sampler's decode steps) always take the dense path:
        # per-step token count is only B, so sharded per-device expert
        # capacity ceil(cf·B/(dp·fsdp·ep)/E) rounds to ~1 and routing
        # imbalance would silently zero dropped tokens' MLP output
        # mid-rollout; dense at B tokens is cheap and exact.
        mesh = _EP_MESH if shape[1] > 1 else None
        if mesh is not None:
            from trlx_tpu.parallel.moe import moe_apply

            def expert_fn(p, t):
                h = nn.gelu(t @ p["wi"] + p["bi"], approximate=True)
                return h @ p["wo"] + p["bo"]

            stacked = {
                "wi": wi.astype(dtype), "bi": bi.astype(dtype),
                "wo": wo.astype(dtype), "bo": bo.astype(dtype),
            }
            y = moe_apply(
                expert_fn, stacked, toks, router.astype(jnp.float32),
                mesh, capacity_factor=cfg.capacity_factor,
                batch_axes=("dp", "fsdp"),
            )
        else:
            gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)
            h = jnp.einsum("nd,edf->enf", toks, wi.astype(dtype))
            h = nn.gelu(h + bi.astype(dtype)[:, None], approximate=True)
            out_e = jnp.einsum("enf,efd->end", h, wo.astype(dtype))
            out_e = out_e + bo.astype(dtype)[:, None]
            sel = jax.nn.one_hot(expert, E, dtype=jnp.float32) * gate  # [N, E]
            y = jnp.einsum("end,ne->nd", out_e.astype(jnp.float32), sel)
        return y.reshape(shape).astype(dtype)


class MoEBlock(nn.Module):
    """`gpt2.Block` with the dense MLP swapped for :class:`SwitchMLP`."""

    config: GPT2MoEConfig

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
        x = x + SwitchMLP(cfg, name="mlp")(h)
        return x, new_kv


class GPT2MoEModel(GPT2Model):
    """GPT-2 trunk with switch-MoE MLPs every ``moe_every``-th block
    (starting at block 1 so block 0 stays dense, as switch transformers
    interleave). Shares `GPT2Model`'s embed/logits/call interface — the
    samplers, hydra hooks, and trainers work unchanged."""

    config: GPT2MoEConfig

    def setup(self):
        cfg = self.config
        pdtype = jnp.dtype(cfg.param_dtype)
        self.wte = nn.Embed(cfg.vocab_size, cfg.n_embd, param_dtype=pdtype, name="wte")
        self.wpe = nn.Embed(cfg.n_positions, cfg.n_embd, param_dtype=pdtype, name="wpe")
        from trlx_tpu.models.gpt2 import Block

        # MoE at blocks moe_every-1, 2*moe_every-1, ... (moe_every=1 =>
        # every block; =2 => alternating with block 0 dense)
        is_moe = [
            i % cfg.moe_every == cfg.moe_every - 1 for i in range(cfg.n_layer)
        ]
        if not any(is_moe):
            raise ValueError(
                f"gpt2_moe with n_layer={cfg.n_layer}, "
                f"moe_every={cfg.moe_every} has no MoE blocks — an ep mesh "
                "axis would have no experts to shard; lower moe_every or "
                "use the dense gpt2 family"
            )
        self.h = [
            (MoEBlock if is_moe[i] else Block)(cfg, name=f"h_{i}")
            for i in range(cfg.n_layer)
        ]
        self.ln_f = nn.LayerNorm(
            epsilon=cfg.layer_norm_epsilon, dtype=jnp.dtype(cfg.dtype), name="ln_f"
        )


# experts live stacked on a leading [E] axis sharded over ep; dense blocks
# keep the gpt2 tp rules
GPT2_MOE_PARTITION_RULES = list(PARTITION_RULES) + [
    (r"mlp/router", P(None, None)),
    (r"mlp/wi", P("ep", None, None)),
    (r"mlp/bi", P("ep", None)),
    (r"mlp/wo", P("ep", None, None)),
    (r"mlp/bo", P("ep", None)),
]


def _no_checkpoint(path: str, dtype: str = "float32"):
    raise ValueError(
        "gpt2_moe has no HF checkpoint counterpart; train from scratch "
        "(model_arch) or convert a dense GPT-2 and grow experts offline"
    )
