"""Value / Q heads and the policy wrapper modules.

Re-design of the reference's head machinery:
- ``make_head`` 2-layer MLP (`trlx/model/nn/ppo_models.py:216-222`, bf16 in
  the fork) -> :class:`MLPHead`.
- ``GPTHeadWithValueModel`` (`ppo_models.py:225-289`) ->
  :class:`CausalLMWithValueHead`: backbone + scalar value head, one forward
  returning logits *and* values (no separate ModelOutput class — outputs are
  plain dicts of arrays).
- ``ILQLHeads`` (`trlx/model/nn/ilql_models.py:119-181`) ->
  :class:`ILQLHeads`: V head + twin Q heads. Target-Q params are NOT module
  params here — they live as a separate pytree in the ILQL train state and
  Polyak-sync is a jitted tree op (the ZeRO-3 ``GatheredParameters`` dance at
  `ilql_models.py:170-181` is unnecessary under GSPMD).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from trlx_tpu.models.gpt2 import GPT2Config, GPT2Model
from trlx_tpu.models.t5 import T5Config, T5Model


class MLPHead(nn.Module):
    """``make_head`` equivalent: Dense(2n) -> ReLU -> Dense(out)."""

    hidden_size: int
    output_size: int = 1
    dtype: str = "bfloat16"
    param_dtype: str = "float32"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dtype = jnp.dtype(self.dtype)
        pdtype = jnp.dtype(self.param_dtype)
        x = nn.Dense(self.hidden_size * 2, dtype=dtype, param_dtype=pdtype, name="fc1")(x)
        x = nn.relu(x)
        x = nn.Dense(self.output_size, dtype=jnp.float32, param_dtype=pdtype, name="fc2")(x)
        return x


class CausalLMWithValueHead(nn.Module):
    """Causal LM backbone + scalar value head (PPO policy).

    ``backbone_cls`` may be any causal family module with the shared call
    interface (GPT2Model / GPTJModel / NeoXModel). Values are computed in
    float32 (the head's final layer) — value-loss clipping is sensitive to
    bf16 rounding.
    """

    config: Any
    backbone_cls: Any = GPT2Model

    def setup(self):
        from trlx_tpu.models.registry import hidden_size_of

        self.backbone = self.backbone_cls(self.config, name="transformer")
        self.v_head = MLPHead(
            hidden_size_of(self.config),
            1,
            dtype=self.config.dtype,
            param_dtype=self.config.param_dtype,
            name="v_head",
        )

    def __call__(
        self,
        input_ids: jax.Array,
        attention_mask: Optional[jax.Array] = None,
        position_ids: Optional[jax.Array] = None,
        cache=None,
        cache_index=None,
        last_only: bool = False,
    ):
        """``last_only=True`` computes logits/values only for the final
        position (sampler prefill: the [B, Q, vocab] float32 logits tensor
        for the whole prompt would be written to HBM just to read one row).
        """
        out = self.backbone(
            input_ids,
            attention_mask=attention_mask,
            position_ids=position_ids,
            cache=cache,
            cache_index=cache_index,
            compute_logits=not last_only,
        )
        if last_only:
            h = out["hidden"][:, -1:]
            out["logits"] = self.backbone.logits(h)
            out["values"] = self.v_head(h)[..., 0]
        else:
            out["values"] = self.v_head(out["hidden"])[..., 0]
        return out

    def response_forward(
        self,
        input_ids: jax.Array,
        attention_mask: jax.Array,
        query_length: int,
    ):
        """(logits, values) over response-predicting positions only.

        The PPO update needs logits/values at positions Q-1..Q+R-2 (the
        states that predict each response token); computing the LM head for
        the query positions too would write (and backprop through) a
        [B, Q+R, vocab] float32 tensor for nothing.
        """
        h, values = self.response_hidden(
            input_ids, attention_mask, query_length
        )
        return self.backbone.logits(h), values

    def response_hidden(
        self,
        input_ids: jax.Array,
        attention_mask: jax.Array,
        query_length: int,
    ):
        """(hidden, values) over response-predicting positions — the
        logits-free half of :meth:`response_forward`, for callers that
        compute logprobs chunked (``train.logprob_chunk``) instead of
        materializing the [B, R, vocab] f32 logits buffer."""
        out = self.backbone(
            input_ids, attention_mask=attention_mask, compute_logits=False
        )
        h = out["hidden"][:, query_length - 1 : -1]
        return h, self.v_head(h)[..., 0]

    def lm_only(
        self,
        input_ids: jax.Array,
        attention_mask: Optional[jax.Array] = None,
        position_ids: Optional[jax.Array] = None,
        cache=None,
        cache_index=None,
    ):
        """Backbone forward without the value head (frozen KL reference)."""
        return self.backbone(
            input_ids,
            attention_mask=attention_mask,
            position_ids=position_ids,
            cache=cache,
            cache_index=cache_index,
        )


class T5WithValueHead(nn.Module):
    """T5/UL2 + scalar value head on decoder hidden states — the fork's
    policy model (``T5HeadWithValueModel``, `ppo_models.py:607-655`; value
    head on ``d_model``, applied to decoder hidden states :638-641, but
    without the reference's fragile ``decoder_hidden_states`` tuple-vs-tensor
    assumption).

    Methods mirror the backbone's: full teacher-forced ``__call__`` plus
    ``encode`` / ``decode`` / ``init_cross_kv`` for compiled sampling.
    """

    config: T5Config

    def setup(self):
        self.backbone = T5Model(self.config, name="t5")
        self.v_head = MLPHead(
            self.config.d_model,
            1,
            dtype=self.config.dtype,
            param_dtype=self.config.param_dtype,
            name="v_head",
        )

    def __call__(
        self,
        input_ids,
        attention_mask=None,
        decoder_input_ids=None,
        decoder_attention_mask=None,
    ):
        out = self.backbone(
            input_ids,
            attention_mask=attention_mask,
            decoder_input_ids=decoder_input_ids,
            decoder_attention_mask=decoder_attention_mask,
        )
        out["values"] = self.v_head(out["hidden"])[..., 0]
        return out

    def encode(self, input_ids, attention_mask=None):
        return self.backbone.encode(input_ids, attention_mask)

    def init_cross_kv(self, encoder_hidden):
        return self.backbone.init_cross_kv(encoder_hidden)

    def decode(
        self,
        decoder_input_ids,
        encoder_mask=None,
        decoder_mask=None,
        cache=None,
        cache_index=None,
        cross_kv=None,
    ):
        out = self.backbone.decode(
            decoder_input_ids,
            encoder_mask=encoder_mask,
            decoder_mask=decoder_mask,
            cache=cache,
            cache_index=cache_index,
            cross_kv=cross_kv,
        )
        out["values"] = self.v_head(out["hidden"])[..., 0]
        return out


class ILQLHeads(nn.Module):
    """V head + ``n_qs`` Q heads over full vocab (`ilql_models.py:119-136`).

    Q heads map action-state hidden -> vocab-size action values; the V head
    maps state hidden -> a scalar. Target-Q evaluation reuses the same
    module applied with a *separate target param tree* (see
    ``CausalLMWithILQLHeads.target_qs``), replacing the reference's frozen
    ``target_q_heads`` submodules + ZeRO-gather sync (`ilql_models.py:170-181`).
    """

    config: Any
    two_qs: bool = True

    def setup(self):
        from trlx_tpu.models.registry import hidden_size_of

        n = hidden_size_of(self.config)
        v = self.config.vocab_size
        kw = dict(dtype=self.config.dtype, param_dtype=self.config.param_dtype)
        self.q_heads = [
            MLPHead(n, v, name=f"q{i+1}_head", **kw)
            for i in range(2 if self.two_qs else 1)
        ]
        self.v_head = MLPHead(n, 1, name="v_head", **kw)

    def q(self, action_hidden: jax.Array) -> Tuple[jax.Array, ...]:
        return tuple(h(action_hidden) for h in self.q_heads)

    def v(self, state_hidden: jax.Array) -> jax.Array:
        return self.v_head(state_hidden)[..., 0]

    def __call__(self, action_hidden, state_hidden):
        return self.q(action_hidden), self.v(state_hidden)


class CausalLMWithILQLHeads(nn.Module):
    """Causal LM + ILQL heads (reference ``CausalLMWithValueHeads``,
    `ilql_models.py:184-335`).

    Forward gathers hidden states at ``states_ixs``/``actions_ixs``
    (`ilql_models.py:138-159`) and returns ``(logits, qs, vs,
    action_hidden)``; target-Q values come from :meth:`target_qs` applied
    with the target param tree held in the ILQL train state.
    """

    config: Any
    two_qs: bool = True
    backbone_cls: Any = GPT2Model

    def setup(self):
        self.backbone = self.backbone_cls(self.config, name="transformer")
        self.ilql_heads = ILQLHeads(self.config, self.two_qs, name="heads")

    def __call__(
        self,
        input_ids: jax.Array,
        attention_mask: Optional[jax.Array] = None,
        position_ids: Optional[jax.Array] = None,
        actions_ixs: Optional[jax.Array] = None,
        states_ixs: Optional[jax.Array] = None,
        cache=None,
        cache_index=None,
        last_only: bool = False,
    ):
        """``last_only=True``: logits and Q/V heads only for the final
        position (sampler prefill — the advantage-shifted decode reads one
        row; without this the prefill writes [B, Q, vocab] logits plus
        per-position Q/V for the whole prompt)."""
        out = self.backbone(
            input_ids,
            attention_mask=attention_mask,
            position_ids=position_ids,
            cache=cache,
            cache_index=cache_index,
            compute_logits=not last_only,
        )
        hidden = out["hidden"]
        if last_only:
            if actions_ixs is not None or states_ixs is not None:
                raise ValueError(
                    "last_only truncates hidden to the final position; "
                    "actions_ixs/states_ixs gathers would silently clamp "
                    "to it — these options are mutually exclusive"
                )
            hidden = hidden[:, -1:]
            out["logits"] = self.backbone.logits(hidden)
        if actions_ixs is not None:
            action_hidden = jnp.take_along_axis(
                hidden, actions_ixs[..., None], axis=1
            )
        else:
            action_hidden = hidden
        if states_ixs is not None:
            state_hidden = jnp.take_along_axis(hidden, states_ixs[..., None], axis=1)
        else:
            state_hidden = hidden
        qs, vs = self.ilql_heads(action_hidden, state_hidden)
        out.update(qs=qs, vs=vs, action_hidden=action_hidden)
        return out

    def target_qs(self, action_hidden: jax.Array) -> Tuple[jax.Array, ...]:
        """Q heads only — apply with ``{"params": {"heads": target_tree}}``."""
        return self.ilql_heads.q(action_hidden)
