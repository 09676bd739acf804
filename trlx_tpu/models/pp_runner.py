"""Pipeline-parallel trunk forward for the causal-LM families.

Integrates ``parallel/pipeline.py``'s GPipe primitive into the real models:
the full-sequence forwards the PPO update runs (policy ``response_forward``
and the frozen-ref scoring pass) route their transformer blocks through
``pipeline_apply`` over the mesh's ``pp`` axis, with embeddings and heads
running replicated over pp. This makes ``mesh: {dp: ..., pp: ...}`` a real
training capability rather than a standalone demo (the reference has no pp
at all — SURVEY §2.9 "PP: NO"; this is a beyond-parity axis).

Family coverage (round 3 widened from GPT-2-only): **gpt2, gptj, gpt_neo,
gpt_neox** — every causal family. The per-family differences ride a small
kit: rotary families (gptj/neox) thread ``position_ids`` into each block
via the schedule's aux tree; gpt_neo's alternating global/local (sliding
window) layers select between two explicit biases with a per-layer flag
scanned alongside the stage params. MoE (`gpt2_moe`) stays excluded — its
per-layer param structure is non-uniform (router/experts on MoE layers
only), so stage stacking does not apply. The seq2seq (T5) family has its
own pipeline section below (``pp_t5_forward``): both trunk stacks run the
schedule back to back, with the per-stack rel-pos bias and the encoder
output riding the aux tree.

Scope and composition:
- Stage s runs blocks ``[s*L/S, (s+1)*L/S)`` with an in-stage ``lax.scan``;
  activations hop stages via ``ppermute`` (GPipe schedule, differentiable).
- Param *residency* (at rest) follows the existing fsdp/tp partition
  rules. During the pipeline loop itself, stage params are all-gathered
  over fsdp at the shard_map boundary (`parallel/pipeline.py`): pp shards
  params/compute *across stages*; fsdp shards the at-rest copy and the
  optimizer state, not the running stage's working set.
- Autoregressive decode runs the SAME pipeline schedule with
  stage-resident KV caches: the sampler's cache is layer-major
  ``[L, B, C, H, Dh]`` sharded over pp (bf16 or int8 value+scale leaves),
  so each device holds only its stage's layers and cache during rollouts
  (``pp_cached_hidden`` / ``make_pp_sampler_apply`` below) — no replicated
  full-model copy.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from trlx_tpu.models.gpt2 import Block, GPT2Config, GPT2Model
from trlx_tpu.models.heads import MLPHead
from trlx_tpu.models.registry import hidden_size_of, n_heads_of, num_layers_of
from trlx_tpu.ops.attention import (
    causal_bias,
    combine_biases,
    padding_bias,
)
from trlx_tpu.ops.kv_cache import resolve_kv_cache_dtype
from trlx_tpu.parallel.pipeline import (
    pipeline_apply,
    spmd_stack,
    stack_stage_params,
)


@dataclass(frozen=True)
class _PPKit:
    """Family adapter for the pipeline schedule."""

    backbone_cls: Any
    block_cls: Any
    takes_positions: bool  # block signature threads position_ids (rotary)
    has_wpe: bool  # embed = wte + wpe (else wte only)
    windowed: bool  # per-layer global/local band attention (gpt_neo)


def _pp_kit(config) -> Optional[_PPKit]:
    from trlx_tpu.models.gpt_neo import GPTNeoBlock, GPTNeoConfig, GPTNeoModel
    from trlx_tpu.models.gptj import GPTJBlock, GPTJConfig, GPTJModel
    from trlx_tpu.models.neox import NeoXBlock, NeoXConfig, NeoXModel

    if isinstance(config, GPT2Config):
        return _PPKit(GPT2Model, Block, False, True, False)
    if isinstance(config, GPTJConfig):
        return _PPKit(GPTJModel, GPTJBlock, True, False, False)
    if isinstance(config, GPTNeoConfig):
        return _PPKit(GPTNeoModel, GPTNeoBlock, False, True, True)
    if isinstance(config, NeoXConfig):
        return _PPKit(NeoXModel, NeoXBlock, True, False, False)
    return None


def supports_pp(model_config) -> bool:
    return _pp_kit(model_config) is not None


def _stack_stages(block_params, stages: int, virtual: int = 1):
    """[L] per-block param trees -> leaves [S, L/S, ...] (stage-major), or
    [S, v, L/(S·v), ...] when ``virtual > 1`` (interleaved: chunk
    c = lap·S + d on device d — round-robin layer placement)."""
    # per-layer stacking goes through spmd_stack, never jnp.stack: these
    # arrays feed shard_map P("pp") in_specs, where XLA's SPMD partitioner
    # miscompiles a stack/concatenate operand under jit on any mesh with
    # a second size>1 axis (tools/pp_miscompile_repro.py)
    groups = stages * virtual
    per = len(block_params) // groups
    group_trees = [
        jax.tree_util.tree_map(
            spmd_stack, *block_params[g * per : (g + 1) * per]
        )
        for g in range(groups)
    ]
    if virtual > 1:
        from trlx_tpu.parallel.pipeline import stack_stage_params_interleaved

        return stack_stage_params_interleaved(group_trees, stages, virtual)
    return stack_stage_params(group_trees)


def _local_flags(config, stages: int, virtual: int = 1) -> Optional[jax.Array]:
    """gpt_neo per-layer local-attention flags, stage-stacked like params."""
    types = config.layer_types
    flags = [jnp.asarray(t == "local") for t in types]
    return _stack_stages(flags, stages, virtual)


def _embed(kit: _PPKit, config, backbone_params, input_ids, position_ids):
    """Token (+ absolute position) embedding via the family's own tables;
    per-table rounding to the compute dtype (matches the backbones)."""
    dtype = jnp.dtype(config.dtype)
    backbone = kit.backbone_cls(config)
    if kit.has_wpe:
        return backbone.apply(
            {"params": backbone_params}, input_ids, position_ids,
            method=lambda m, i, p: m.wte(i).astype(dtype)
            + m.wpe(p).astype(dtype),
        )
    return backbone.apply(
        {"params": backbone_params}, input_ids,
        method=lambda m, i: m.wte(i).astype(dtype),
    )


def _ln_f(kit: _PPKit, config, backbone_params, h):
    return kit.backbone_cls(config).apply(
        {"params": backbone_params}, h, method=lambda m, v: m.ln_f(v)
    )


def _logits(kit: _PPKit, config, backbone_params, hidden: jax.Array):
    """LM head on (already-sliced) hidden states via the family's own
    ``logits`` definition (tied wte or separate lm_head)."""
    cls = kit.backbone_cls
    return cls(config).apply(
        {"params": backbone_params}, hidden, method=cls.logits
    )


def _neo_local_bias(config, T, kv_len, offset, pad):
    from trlx_tpu.models.gpt_neo import local_causal_bias

    return combine_biases(
        local_causal_bias(T, kv_len, config.window_size, offset=offset), pad
    )


def _stage_body(kit: _PPKit, block, aux_mb, causal: bool, cached: bool):
    """One scan body serving both schedules: unpack per-layer xs (params
    [+ cache slice] [+ local flag]), select the bias (windowed families pick
    per layer between aux "local" and "global"), thread rotary positions.
    Cached mode reads ``aux_mb["idx"]`` as the cache write index."""

    def body(h, xs):
        if kit.windowed:
            (p, *rest, flag) = xs
            bias = jnp.where(flag, aux_mb["local"], aux_mb["global"])
        else:
            (p, *rest) = xs if cached else (xs,)
            bias = aux_mb["global"]
        args = (h, bias) + ((aux_mb["pos"],) if kit.takes_positions else ())
        if cached:
            return block.apply(
                {"params": p}, *args, cache_kv=rest[0],
                cache_index=aux_mb["idx"], causal=False,
            )
        h, _ = block.apply({"params": p}, *args, causal=causal)
        return h, None

    return body


def _run_schedule(stage_fn, stage_tree, x, mesh, num_microbatches, aux,
                  virtual_stages, remat):
    """One dispatch point for the plain train schedule: autodiffed GPipe /
    interleaved (v > 1) or the rematerialized backward. Centralizes the
    remat-vs-v guard so every caller fails the same way."""
    if remat:
        if virtual_stages > 1:
            raise NotImplementedError(
                "pp_remat runs the v=1 schedule; drop pp_virtual_stages "
                "or pp_remat (the two memory/bubble trades do not "
                "compose yet)"
            )
        from trlx_tpu.parallel.pipeline import pipeline_apply_remat

        return pipeline_apply_remat(
            stage_fn, stage_tree, x, mesh,
            num_microbatches=num_microbatches, aux=aux,
        )
    return pipeline_apply(
        stage_fn, stage_tree, x, mesh,
        num_microbatches=num_microbatches, aux=aux,
        virtual_stages=virtual_stages,
    )


def pp_hidden_forward(
    config,
    backbone_params,
    input_ids: jax.Array,  # [B, T]
    attention_mask: jax.Array,  # [B, T]
    mesh: Mesh,
    num_microbatches: int = 2,
    virtual_stages: int = 1,
    capture_layer: int = None,
    capture_only: bool = False,
    remat: bool = False,
) -> jax.Array:
    """Full-sequence causal trunk forward (embed -> pp blocks -> ln_f),
    numerically identical to the family backbone's ``__call__`` with
    ``cache=None``. Embedding / ln_f / heads reuse the flax module methods
    (one definition) — only the block loop is replaced by the pipeline
    schedule. Rotary position_ids and gpt_neo's per-layer band biases ride
    the schedule's aux tree. ``virtual_stages > 1`` runs the interleaved
    schedule (`train.pp_virtual_stages`): bubble shrinks ~v× at the cost
    of v× more ppermute hops (`pipeline_span_layer_units`).
    ``capture_layer=k`` (v=1, k on a stage boundary) additionally returns
    the activation entering block k — the hydra branch point (the non-pp
    backbones' ``capture_hidden_at``); the return becomes
    ``(h_after_ln_f, captured)``."""
    kit = _pp_kit(config)
    if kit is None:
        raise NotImplementedError(
            f"pp is not available for {type(config).__name__}"
        )
    S = mesh.shape["pp"]
    v = virtual_stages
    L = num_layers_of(config)
    if L % (S * v):
        raise ValueError(
            f"n_layer={L} must divide into pp={S} stages x {v} virtual"
        )
    B, T = input_ids.shape
    position_ids = jnp.clip(jnp.cumsum(attention_mask, axis=-1) - 1, 0, None)
    x = _embed(kit, config, backbone_params, input_ids, position_ids)

    pad = padding_bias(attention_mask)
    if kit.windowed:
        # the causal FLAG cannot vary per scanned layer, so the windowed
        # family uses explicit biases for all layers (same mask values)
        aux = {
            "global": jnp.broadcast_to(
                combine_biases(causal_bias(T, T), pad),
                (B, 1, T, T),
            ),
            "local": jnp.broadcast_to(
                _neo_local_bias(config, T, T, 0, pad), (B, 1, T, T)
            ),
        }
        causal = False
    else:
        aux = {"global": pad}
        causal = True
    if kit.takes_positions:
        aux["pos"] = position_ids

    stacked = _stack_stages(
        [backbone_params[f"h_{i}"] for i in range(L)], S, v
    )
    flags = _local_flags(config, S, v) if kit.windowed else None
    block = kit.block_cls(config)

    def stage_fn(stage_params, h, aux_mb):
        params, lflags = stage_params if kit.windowed else (stage_params, None)
        body = _stage_body(kit, block, aux_mb, causal, cached=False)
        xs = (params, lflags) if kit.windowed else params
        h, _ = jax.lax.scan(body, h, xs)
        return h

    capture_stage = None
    if capture_layer is not None:
        chunk = L // S
        if capture_layer % chunk:
            raise NotImplementedError(
                f"hydra branch point at layer {capture_layer} does not sit "
                f"on a stage boundary (stage size {chunk}); choose "
                f"num_layers_unfrozen so L - unfrozen is a multiple of L/pp"
            )
        capture_stage = capture_layer // chunk

    stage_tree = (stacked, flags) if kit.windowed else stacked
    if capture_stage is not None:
        if remat:
            raise NotImplementedError(
                "pp_remat has no hydra capture; use the autodiffed schedule"
            )
        res = pipeline_apply(
            stage_fn, stage_tree, x, mesh,
            num_microbatches=num_microbatches, aux=aux, virtual_stages=v,
            capture_stage=capture_stage, capture_only=capture_only,
        )
    else:
        res = _run_schedule(
            stage_fn, stage_tree, x, mesh, num_microbatches, aux, v, remat
        )
    if capture_stage is None:
        return _ln_f(kit, config, backbone_params, res)
    h, caps = res
    if capture_only:
        # the schedule stopped at the capture; h never finished (stages
        # >= k did not run) — return only the branch activation
        return None, caps
    return _ln_f(kit, config, backbone_params, h), caps


def pp_response_forward(
    config,
    params,  # CausalLMWithValueHead params: {"transformer", "v_head"}
    input_ids: jax.Array,
    attention_mask: jax.Array,
    query_length: int,
    mesh: Mesh,
    num_microbatches: int = 2,
    virtual_stages: int = 1,
    remat: bool = False,
):
    """pp counterpart of ``CausalLMWithValueHead.response_forward``:
    (logits, values) over the response-predicting positions Q-1..Q+R-2.
    ``remat=True`` routes the trunk through the rematerialized-backward
    schedule (`pipeline_apply_remat`) — stage inputs are the only saved
    residuals, cutting the update's peak activation memory."""
    kit = _pp_kit(config)
    h = pp_hidden_forward(
        config, params["transformer"], input_ids, attention_mask,
        mesh, num_microbatches, virtual_stages, remat=remat,
    )
    hs = h[:, query_length - 1 : -1]
    v_head = MLPHead(
        hidden_size_of(config), 1, dtype=config.dtype,
        param_dtype=config.param_dtype,
    )
    values = v_head.apply({"params": params["v_head"]}, hs)[..., 0]
    return _logits(kit, config, params["transformer"], hs), values


def pp_ref_logits(
    config,
    backbone_params,
    input_ids: jax.Array,
    attention_mask: jax.Array,
    query_length: int,
    mesh: Mesh,
    num_microbatches: int = 2,
    virtual_stages: int = 1,
) -> jax.Array:
    """Frozen-reference logits over response-predicting positions (the
    full-copy ref path; the hydra shared-trunk variant is
    :func:`pp_hydra_ref_logits`)."""
    kit = _pp_kit(config)
    h = pp_hidden_forward(
        config, backbone_params, input_ids, attention_mask,
        mesh, num_microbatches, virtual_stages,
    )
    return _logits(kit, config, backbone_params, h[:, query_length - 1 : -1])


def pp_hydra_ref_logits(
    config,
    policy_backbone_params,
    ref_params,  # hydra subset: top blocks + ln_f + head tables
    input_ids: jax.Array,
    attention_mask: jax.Array,
    query_length: int,
    branch_start: int,
    mesh: Mesh,
    num_microbatches: int = 2,
) -> jax.Array:
    """Hydra shared-trunk KL reference under pp (`ppo_models.py:505-558`).

    The frozen trunk activation at the branch point is captured from the
    policy trunk's OWN pipeline schedule (the input of the stage owning
    block ``branch_start`` — a stage boundary, enforced by
    ``pp_hidden_forward``), then the small frozen branch (the top
    ``L - branch_start`` blocks + ln_f + LM head from ``ref_params``) runs
    replicated over pp — exactly the non-pp hydra semantics
    (``capture_hidden_at`` + ``start_layer``/``hidden_override``), with
    the branch too small to be worth pipelining."""
    kit = _pp_kit(config)
    L = num_layers_of(config)
    # capture_only: the schedule stops once the last microbatch reaches
    # the branch stage — the frozen top stages are not re-run for a result
    # nobody reads (they'd cost more than the full-copy ref otherwise)
    _, x = pp_hidden_forward(
        config, policy_backbone_params, input_ids, attention_mask,
        mesh, num_microbatches, capture_layer=branch_start,
        capture_only=True,
    )
    position_ids = jnp.clip(jnp.cumsum(attention_mask, axis=-1) - 1, 0, None)
    pad = padding_bias(attention_mask)
    block = kit.block_cls(config)
    types = config.layer_types if kit.windowed else None
    T = input_ids.shape[1]
    for i in range(branch_start, L):
        if kit.windowed and types[i] == "local":
            bias, causal = _neo_local_bias(config, T, T, 0, pad), False
        else:
            bias, causal = pad, True
        args = (x, bias) + ((position_ids,) if kit.takes_positions else ())
        x, _ = block.apply(
            {"params": ref_params[f"h_{i}"]}, *args, causal=causal
        )
    x = _ln_f(kit, config, ref_params, x)
    return _logits(kit, config, ref_params, x[:, query_length - 1 : -1])


# ------------------------- seq2seq (T5) pipeline ------------------------- #


def supports_pp_seq2seq(model_config) -> bool:
    from trlx_tpu.models.t5 import T5Config

    return isinstance(model_config, T5Config)


def _pp_t5_encode(
    config,
    t5_params,
    input_ids,
    attention_mask,
    mesh: Mesh,
    num_microbatches: int,
    enc_stacked=None,
    virtual_stages: int = 1,
    remat: bool = False,
):
    """Pipelined encoder pass (embed → rel-pos bias + mask → schedule →
    final LN), numerically identical to ``T5Model.encode``. ONE definition
    shared by the train forward (`pp_t5_forward`) and the rollout sampler
    (`make_pp_seq2seq_sampler_fns`) — hand-synced copies of a schedule
    invite silent rollout-vs-update divergence. ``enc_stacked`` lets the
    sampler pass blocks pre-stacked once per invocation."""
    from trlx_tpu.models.t5 import T5EncoderBlock, T5Model
    from trlx_tpu.ops.attention import NEG_INF

    backbone = T5Model(config)
    dtype = jnp.dtype(config.dtype)
    B, T_enc = input_ids.shape

    def bb(fn, *args):
        return backbone.apply({"params": t5_params}, *args, method=fn)

    x = bb(lambda m, i: m.shared(i).astype(dtype), input_ids)
    pos = jnp.arange(T_enc)
    enc_bias = bb(lambda m, q, k: m.enc_rel_bias(q, k), pos, pos)
    if attention_mask is not None:
        enc_bias = enc_bias + jnp.where(
            attention_mask[:, None, None, :] > 0, 0.0, NEG_INF
        )
    enc_bias = jnp.broadcast_to(enc_bias, (B,) + enc_bias.shape[1:])
    if enc_stacked is None:
        enc_stacked = _stack_stages(
            [t5_params[f"enc_{i}"] for i in range(config.num_layers)],
            mesh.shape["pp"], virtual_stages,
        )
    enc_block = T5EncoderBlock(config)

    def enc_stage(stage_params, h, aux_mb):
        def body(h, p):
            return enc_block.apply({"params": p}, h, aux_mb["bias"]), None

        h, _ = jax.lax.scan(body, h, stage_params)
        return h

    x = _run_schedule(
        enc_stage, enc_stacked, x, mesh, num_microbatches,
        {"bias": enc_bias}, virtual_stages, remat,
    )
    return bb(lambda m, v_: m.enc_final_ln(v_), x)


def pp_t5_forward(
    config,
    backbone_params,  # T5Model params ("t5" subtree)
    input_ids: jax.Array,  # [B, S_enc]
    attention_mask: jax.Array,  # [B, S_enc]
    decoder_input_ids: jax.Array,  # [B, T]
    decoder_attention_mask: jax.Array,  # [B, T]
    mesh: Mesh,
    num_microbatches: int = 2,
    virtual_stages: int = 1,
    remat: bool = False,
):
    """Teacher-forced enc→dec forward with BOTH stacks' blocks pipelined
    over pp (two schedules back to back), numerically identical to
    ``T5Model.__call__`` (`models/t5.py:431-448` — the fork's policy model,
    `ppo_models.py:607-655`). Embeddings, the learned rel-pos bias tables,
    final LayerNorms, and the LM head run replicated over pp; each stack's
    shared bias tensor is computed once outside the schedule and rides the
    aux tree (batch-leading), so gradient flows to the rel-pos embeddings
    through aux. The encoder output rides the decoder schedule's aux the
    same way (every device holds its batch shard).

    ``virtual_stages > 1`` (round 4): both stacks run the interleaved
    schedule — each device holds v round-robin layer chunks per stack, the
    fill/drain bubble shrinks ~v× per stack (the seq2seq path pays TWO
    schedules per forward, so the win applies twice)."""
    from trlx_tpu.models.t5 import T5DecoderBlock, T5EncoderBlock, T5Model
    from trlx_tpu.ops.attention import NEG_INF

    S = mesh.shape["pp"]
    v = virtual_stages
    L_enc, L_dec = config.num_layers, config.num_decoder_layers
    if L_enc % (S * v) or L_dec % (S * v):
        raise ValueError(
            f"num_layers={L_enc} and num_decoder_layers={L_dec} must both "
            f"divide into pp={S} stages x {v} virtual"
        )
    backbone = T5Model(config)
    dtype = jnp.dtype(config.dtype)
    B, T_enc = input_ids.shape

    def bb(fn, *args):
        return backbone.apply({"params": backbone_params}, *args, method=fn)

    # --- encoder stack: ONE pipelined-encoder definition shared with the
    # rollout sampler (`_pp_t5_encode`) ---
    encoder_hidden = _pp_t5_encode(
        config, backbone_params, input_ids, attention_mask, mesh,
        num_microbatches, virtual_stages=v, remat=remat,
    )

    # --- decoder stack (bias construction mirrors T5Model.decode) ---
    T = decoder_input_ids.shape[1]
    y = bb(lambda m, i: m.shared(i).astype(dtype), decoder_input_ids)
    q_pos = jnp.arange(T)
    k_pos = jnp.arange(T)
    causal = jnp.where(k_pos[None, :] <= q_pos[:, None], 0.0, NEG_INF)[
        None, None
    ]
    self_bias = bb(lambda m, q, k: m.dec_rel_bias(q, k), q_pos, k_pos) + causal
    if decoder_attention_mask is not None:
        self_bias = self_bias + jnp.where(
            decoder_attention_mask[:, None, None, :] > 0, 0.0, NEG_INF
        )
    self_bias = jnp.broadcast_to(self_bias, (B,) + self_bias.shape[1:])
    if attention_mask is not None:
        cross_bias = jnp.where(
            attention_mask[:, None, None, :] > 0, 0.0, NEG_INF
        ).astype(jnp.float32)
    else:  # unmasked cross-attention, as T5Model.decode's None path
        cross_bias = jnp.zeros((B, 1, 1, T_enc), jnp.float32)
    dec_stacked = _stack_stages(
        [backbone_params[f"dec_{i}"] for i in range(L_dec)], S, v
    )
    dec_block = T5DecoderBlock(config)

    def dec_stage(stage_params, h, aux_mb):
        def body(h, p):
            h, _ = dec_block.apply(
                {"params": p}, h, aux_mb["sb"], aux_mb["cb"],
                encoder_hidden=aux_mb["eh"],
            )
            return h, None

        h, _ = jax.lax.scan(body, h, stage_params)
        return h

    y = _run_schedule(
        dec_stage, dec_stacked, y, mesh, num_microbatches,
        {"sb": self_bias, "cb": cross_bias, "eh": encoder_hidden}, v, remat,
    )
    hidden = bb(lambda m, v_: m.dec_final_ln(v_), y)
    logits = bb(T5Model.logits, hidden)
    return {"logits": logits, "hidden": hidden}


def pp_t5_response_forward(
    config,
    params,  # T5WithValueHead params: {"t5", "v_head"}
    input_ids,
    attention_mask,
    decoder_input_ids,
    decoder_attention_mask,
    mesh: Mesh,
    num_microbatches: int = 2,
    virtual_stages: int = 1,
    remat: bool = False,
):
    """(logits, values) — the seq2seq PPO update's policy forward with the
    trunk stacks pipelined; the value head reads decoder hidden states
    (`ppo_models.py:638-641`) replicated over pp."""
    out = pp_t5_forward(
        config, params["t5"], input_ids, attention_mask,
        decoder_input_ids, decoder_attention_mask, mesh, num_microbatches,
        virtual_stages=virtual_stages, remat=remat,
    )
    v_head = MLPHead(
        config.d_model, 1, dtype=config.dtype, param_dtype=config.param_dtype
    )
    values = v_head.apply({"params": params["v_head"]}, out["hidden"])[..., 0]
    return out["logits"], values


def pp_t5_ref_logits(
    config,
    ref_params,  # T5Model params (full frozen copy — the fork's ref path)
    input_ids,
    attention_mask,
    decoder_input_ids,
    decoder_attention_mask,
    mesh: Mesh,
    num_microbatches: int = 2,
    virtual_stages: int = 1,
) -> jax.Array:
    """Frozen-reference logits with the trunk stacks pipelined (the fork
    uses a full frozen copy for T5 — `ppo_orchestrator.py:41-43`)."""
    return pp_t5_forward(
        config, ref_params, input_ids, attention_mask,
        decoder_input_ids, decoder_attention_mask, mesh, num_microbatches,
        virtual_stages=virtual_stages,
    )["logits"]


def pp_ilql_forward(
    config,
    params,  # CausalLMWithILQLHeads params: {"transformer", "heads"}
    input_ids: jax.Array,
    attention_mask: jax.Array,
    actions_ixs: Optional[jax.Array],
    states_ixs: Optional[jax.Array],
    mesh: Mesh,
    num_microbatches: int = 2,
    two_qs: bool = True,
    virtual_stages: int = 1,
    remat: bool = False,
):
    """pp counterpart of ``CausalLMWithILQLHeads.__call__`` (no cache):
    trunk blocks through the GPipe schedule; logits and the Q/V heads run
    replicated over pp on the gathered positions. Returns the same dict
    the flax module's forward does (`models/heads.py`)."""
    from trlx_tpu.models.heads import ILQLHeads

    kit = _pp_kit(config)
    h = pp_hidden_forward(
        config, params["transformer"], input_ids, attention_mask,
        mesh, num_microbatches, virtual_stages, remat=remat,
    )
    logits = _logits(kit, config, params["transformer"], h)
    action_hidden = (
        jnp.take_along_axis(h, actions_ixs[..., None], axis=1)
        if actions_ixs is not None
        else h
    )
    state_hidden = (
        jnp.take_along_axis(h, states_ixs[..., None], axis=1)
        if states_ixs is not None
        else h
    )
    qs, vs = ILQLHeads(config, two_qs).apply(
        {"params": params["heads"]}, action_hidden, state_hidden
    )
    return {
        "logits": logits,
        "qs": qs,
        "vs": vs,
        "action_hidden": action_hidden,
    }


def pp_slice_logits(config, backbone_params, hidden: jax.Array):
    """Family LM head on (already-sliced) hidden states — public wrapper
    for pp callers that slice before the head (`GPT2Model.logits`-class
    methods; the full [B, T, vocab] tensor is the most expensive
    intermediate)."""
    return _logits(_pp_kit(config), config, backbone_params, hidden)


def pp_decode_kit(config, mesh: Mesh):
    """The pp decode wiring both trainers share: ``(init_cache_fn,
    cache_sharding)`` for ``make_sampler`` — layer-major stage-resident
    buffers sharded ``P(pp, batch)``. One definition so a layout change
    cannot silently diverge the PPO and ILQL rollout paths."""
    import functools

    from jax.sharding import NamedSharding, PartitionSpec

    from trlx_tpu.parallel.mesh import BATCH_AXES

    return (
        functools.partial(pp_init_cache, config),
        NamedSharding(mesh, PartitionSpec("pp", BATCH_AXES)),
    )


# --------------------------- pp rollout decode --------------------------- #
#
# Decode under a pp mesh does not replicate the full model per device. The
# sampler's KV cache becomes layer-major [L, B, C, H, Dh] sharded
# P(pp, (dp, fsdp)) — each device holds the cache AND compute of its own
# stage's L/S layers only — and every sampler forward (prefill + each decode
# token) runs the GPipe schedule with the cache resident in the stages
# (`parallel/pipeline.py::pipeline_apply_cached`). Embedding, ln_f, LM head,
# and the value head stay replicated over pp (they are a small fraction of
# weights and need the full batch anyway).


def pp_init_cache(config, batch_size: int, capacity: int):
    """Layer-major KV buffers for pp decode: ``{"k","v"}: [L, B, C, H, Dh]``
    (vs the GSPMD sampler's per-layer tuple). ``kv_cache_dtype="int8"``
    composes: value+scale leaves, stage-sliced and microbatch-sliced like
    any other cache leaf (``ops/kv_cache.py::cache_kind`` keys on the
    ``k_scale`` entry, so the per-layer dict the stage scan hands to the
    block is already in the quantized layout). The layer-major allocation
    is this file's own; folding it into ``ops/kv_cache.py`` is a named debt
    (ROADMAP.md)."""
    L = num_layers_of(config)
    H = n_heads_of(config)
    head_dim = hidden_size_of(config) // H
    shape = (L, batch_size, capacity, H, head_dim)
    kv_dtype = resolve_kv_cache_dtype(
        getattr(config, "kv_cache_dtype", "bfloat16"), capacity
    )
    if kv_dtype == "int8":
        sshape = shape[:-1] + (1,)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.bfloat16),
            "v_scale": jnp.zeros(sshape, jnp.bfloat16),
        }
    if kv_dtype != "bfloat16":
        # mirror kv_buffers: a future cache dtype (e.g. fp8) must fail loudly
        # here rather than silently allocating bf16 stage buffers
        raise ValueError(
            f"kv_cache_dtype={kv_dtype!r} has no pp stage-resident layout yet"
        )
    dtype = jnp.dtype(config.dtype)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def pp_stack_sampler_params(config, mesh: Mesh, params):
    """Pre-stack the trunk blocks for the pp sampler, ONCE per sampler
    invocation (outside the decode scan): the jnp.stack of every layer and
    the regather to P('pp') residency are loop-invariant, and leaving them
    inside the per-token apply would rely on XLA hoisting them out of the
    while-loop body. Returns the packed params pytree the
    ``make_pp_sampler_apply`` closure expects."""
    from jax.sharding import NamedSharding, PartitionSpec

    S = mesh.shape["pp"]
    stacked = _stack_stages(
        [params["transformer"][f"h_{i}"] for i in range(num_layers_of(config))],
        S,
    )
    stacked = jax.tree_util.tree_map(
        lambda p: jax.lax.with_sharding_constraint(
            p, NamedSharding(mesh, PartitionSpec("pp"))
        ),
        stacked,
    )
    # pass every head tree through untouched (PPO: v_head; ILQL: heads)
    return {**params, "stacked_blocks": stacked}


def pp_cached_hidden(
    config,
    backbone_params,
    input_ids: jax.Array,  # [B, T]
    attention_mask: jax.Array,  # [B, C] cache-validity mask
    position_ids: jax.Array,  # [B, T]
    cache,  # pp_init_cache layout
    cache_index,
    mesh: Mesh,
    num_microbatches: int = 2,
    stacked=None,  # pre-stacked blocks (pp_stack_sampler_params)
):
    """(hidden after ln_f, new cache) for a cached forward (prefill T=Q or
    decode T=1) with blocks pipelined over pp and stage-resident caches."""
    from trlx_tpu.parallel.pipeline import pipeline_apply_cached

    kit = _pp_kit(config)
    if kit is None:
        raise NotImplementedError(
            f"pp is not available for {type(config).__name__}"
        )
    S = mesh.shape["pp"]
    L = num_layers_of(config)
    if L % S:
        raise ValueError(f"n_layer={L} must divide pp={S}")
    x = _embed(kit, config, backbone_params, input_ids, position_ids)
    T = input_ids.shape[1]
    C = cache["k"].shape[2]
    B = input_ids.shape[0]
    # explicit per-row biases (aux rides microbatch slicing, so batch-lead)
    pad = padding_bias(attention_mask)
    aux = {
        "global": jnp.broadcast_to(
            combine_biases(causal_bias(T, C, offset=cache_index), pad),
            (B, 1, T, C),
        )
    }
    if kit.windowed:
        aux["local"] = jnp.broadcast_to(
            _neo_local_bias(config, T, C, cache_index, pad), (B, 1, T, C)
        )
    if kit.takes_positions:
        aux["pos"] = position_ids

    if stacked is None:
        stacked = _stack_stages(
            [backbone_params[f"h_{i}"] for i in range(L)], S
        )
    flags = _local_flags(config, S) if kit.windowed else None
    block = kit.block_cls(config)

    def stage_fn(stage_params, h, aux_mb, stage_cache_mb, idx):
        # stage_cache_mb leaves [L/S, bm, C, ...]: scan layers, thread h
        params, lflags = stage_params if kit.windowed else (stage_params, None)
        body = _stage_body(
            kit, block, {**aux_mb, "idx": idx}, causal=False, cached=True
        )
        xs = (
            (params, stage_cache_mb, lflags)
            if kit.windowed
            else (params, stage_cache_mb)
        )
        h, new_kvs = jax.lax.scan(body, h, xs)
        return h, new_kvs

    stage_tree = (stacked, flags) if kit.windowed else stacked
    h, new_cache = pipeline_apply_cached(
        stage_fn, stage_tree, x, cache, cache_index, mesh,
        num_microbatches=num_microbatches, aux=aux,
    )
    return _ln_f(kit, config, backbone_params, h), new_cache


def make_pp_sampler_apply(
    config,
    mesh: Mesh,
    num_microbatches: int = 2,
):
    """Sampler ``apply_fn`` for a pp mesh: matches the contract of
    ``CausalLMWithValueHead`` applies in `trainer/ppo_trainer.py` —
    ``(params, input_ids, attention_mask, position_ids, cache,
    cache_index, last_only) -> {"logits", "values", "cache"}`` — with the
    trunk pipelined and the cache stage-resident. ``params`` is the PACKED
    tree from :func:`pp_stack_sampler_params` (blocks pre-stacked once per
    sampler invocation, not once per decoded token). Logits/values are
    computed at the LAST position only (shape [B, 1, ...]), which is all
    the sampler reads for both prefill and decode."""
    kit = _pp_kit(config)
    v_head = MLPHead(
        hidden_size_of(config), 1, dtype=config.dtype,
        param_dtype=config.param_dtype,
    )

    def apply_fn(params, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None, last_only=False):
        h, new_cache = pp_cached_hidden(
            config, params["transformer"], input_ids, attention_mask,
            position_ids, cache, cache_index, mesh, num_microbatches,
            stacked=params["stacked_blocks"],
        )
        hs = h[:, -1:]
        logits = _logits(kit, config, params["transformer"], hs)
        values = v_head.apply({"params": params["v_head"]}, hs)[..., 0]
        return {"logits": logits, "values": values, "cache": new_cache}

    return apply_fn


# ----------------------- pp seq2seq rollout decode ----------------------- #
#
# The T5 family's rollouts under a pp mesh (VERDICT r3 #3 — previously the
# compiled seq2seq sampler stayed GSPMD with params replicated over pp):
# - the ENCODER runs once per chunk through the same GPipe schedule as the
#   update's forward, with its blocks stage-stacked and resident;
# - the decoder self-attention KV cache is layer-major [L_dec, B, cap, H,
#   d_kv] sharded P(pp, batch) — each device holds its stage's cache only;
# - the cross-attention K/V are precomputed ONCE per chunk from the encoder
#   output (one batched einsum over the layer-stacked EncDecAttention
#   projections) into the same layer-major stage-resident layout, and ride
#   the schedule as pipeline_apply_cached's READ-ONLY ``static_cache``;
# - embeddings, rel-pos bias tables, final LayerNorms, LM head, and the
#   value head stay replicated over pp (small, need the full batch).
#
# Reference capability being scaled: the fork's T5 generate path
# (`ppo_models.py:620-622`), which on torch runs a full replicated model.


def pp_t5_init_cache(config, batch_size: int, capacity: int):
    """Layer-major decoder self-attn KV buffers for pp seq2seq decode
    (bf16 — the t5 cache ships bf16 only, matching `init_t5_cache`)."""
    shape = (
        config.num_decoder_layers, batch_size, capacity,
        config.num_heads, config.d_kv,
    )
    dtype = jnp.dtype(config.dtype)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def pp_t5_stack_sampler_params(config, mesh: Mesh, params):
    """Stack BOTH T5 stacks' blocks for the pp sampler, once per invocation
    (the seq2seq analogue of :func:`pp_stack_sampler_params`)."""
    from jax.sharding import NamedSharding, PartitionSpec

    S = mesh.shape["pp"]
    t5 = params["t5"]
    pin = lambda tree: jax.tree_util.tree_map(
        lambda p: jax.lax.with_sharding_constraint(
            p, NamedSharding(mesh, PartitionSpec("pp"))
        ),
        tree,
    )
    return {
        **params,
        "enc_stacked": pin(_stack_stages(
            [t5[f"enc_{i}"] for i in range(config.num_layers)], S
        )),
        "dec_stacked": pin(_stack_stages(
            [t5[f"dec_{i}"] for i in range(config.num_decoder_layers)], S
        )),
    }


def make_pp_seq2seq_sampler_fns(config, mesh: Mesh, num_microbatches: int = 2):
    """``(encode_fn, decode_fn, init_cross_kv_fn)`` for
    ``ops.sampling.make_seq2seq_sampler`` under a pp mesh. All three consume
    the PACKED param tree from :func:`pp_t5_stack_sampler_params`. Bias
    construction mirrors ``T5Model.encode`` / ``T5Model.decode`` exactly
    (token-exact parity vs the GSPMD sampler is pinned in
    ``tests/test_pp_integration.py``)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from trlx_tpu.models.t5 import T5DecoderBlock, T5Model
    from trlx_tpu.ops.attention import NEG_INF
    from trlx_tpu.parallel.mesh import BATCH_AXES
    from trlx_tpu.parallel.pipeline import pipeline_apply_cached

    backbone = T5Model(config)
    dtype = jnp.dtype(config.dtype)
    v_head = MLPHead(
        config.d_model, 1, dtype=config.dtype, param_dtype=config.param_dtype
    )
    resident = NamedSharding(mesh, PartitionSpec("pp", BATCH_AXES))

    def bb(t5_params, fn, *args):
        return backbone.apply({"params": t5_params}, *args, method=fn)

    def encode_fn(packed, input_ids, attention_mask):
        return _pp_t5_encode(
            config, packed["t5"], input_ids, attention_mask, mesh,
            num_microbatches, enc_stacked=packed["enc_stacked"],
        )

    def init_cross_kv_fn(packed, encoder_hidden):
        # one batched einsum over the layer-stacked EncDecAttention k/v
        # projections (T5Attention.project_kv per layer, vectorized), cast
        # exactly as nn.Dense(dtype=cfg.dtype) would
        dec = packed["dec_stacked"]["EncDecAttention"]
        B, T_enc = encoder_hidden.shape[:2]
        L = config.num_decoder_layers
        layer_sh = NamedSharding(mesh, PartitionSpec("pp"))

        def proj(kernel):  # [S, L/S, d_model, inner] -> [L, B, T, H, d_kv]
            w = kernel.reshape(L, config.d_model, -1).astype(dtype)
            # keep the layer dim sharded over pp through the reshape so
            # GSPMD partitions the einsum per stage (each device projects
            # only its own L/S layers) instead of all-gathering the
            # kernels and computing all L layers replicated
            w = jax.lax.with_sharding_constraint(w, layer_sh)
            out = jnp.einsum("btd,ldi->lbti", encoder_hidden.astype(dtype), w)
            out = out.reshape(L, B, T_enc, config.num_heads, config.d_kv)
            return jax.lax.with_sharding_constraint(out, resident)

        return {"k": proj(dec["k"]["kernel"]), "v": proj(dec["v"]["kernel"])}

    def decode_fn(packed, decoder_input_ids, encoder_mask=None,
                  decoder_mask=None, cache=None, cache_index=None,
                  cross_kv=None):
        t5p = packed["t5"]
        B, T = decoder_input_ids.shape
        y = bb(t5p, lambda m, i: m.shared(i).astype(dtype), decoder_input_ids)
        C = cache["k"].shape[2]
        q_pos = cache_index + jnp.arange(T)
        k_pos = jnp.arange(C)
        causal = jnp.where(
            k_pos[None, :] <= q_pos[:, None], 0.0, NEG_INF
        )[None, None]
        self_bias = (
            bb(t5p, lambda m, q, k: m.dec_rel_bias(q, k), q_pos, k_pos)
            + causal
        )
        if decoder_mask is not None:
            self_bias = self_bias + jnp.where(
                decoder_mask[:, None, None, :] > 0, 0.0, NEG_INF
            )
        self_bias = jnp.broadcast_to(self_bias, (B,) + self_bias.shape[1:])
        cross_bias = jnp.where(
            encoder_mask[:, None, None, :] > 0, 0.0, NEG_INF
        ).astype(jnp.float32)
        dec_block = T5DecoderBlock(config)

        def stage_fn(stage_params, h, aux_mb, cache_mb, static_mb, idx):
            def body(h, xs):
                p, c_mb, x_mb = xs
                h, new_kv = dec_block.apply(
                    {"params": p}, h, aux_mb["sb"], aux_mb["cb"],
                    cache_kv=c_mb, cache_index=idx,
                    cross_kv=(x_mb["k"], x_mb["v"]),
                )
                return h, new_kv

            h, new_kvs = jax.lax.scan(
                body, h, (stage_params, cache_mb, static_mb)
            )
            return h, new_kvs

        h, new_cache = pipeline_apply_cached(
            stage_fn, packed["dec_stacked"], y, cache, cache_index, mesh,
            num_microbatches=num_microbatches,
            aux={"sb": self_bias, "cb": cross_bias}, static_cache=cross_kv,
        )
        h = bb(t5p, lambda m, v_: m.dec_final_ln(v_), h)
        logits = bb(t5p, T5Model.logits, h)
        values = v_head.apply({"params": packed["v_head"]}, h)[..., 0]
        return {"logits": logits, "values": values, "cache": new_cache}

    return encode_fn, decode_fn, init_cross_kv_fn
