"""Jitted autoregressive sampling: prefill + a compiled decode loop.

Replaces the reference's HF ``generate`` Python token loop
(``trlx/model/nn/ppo_models.py:620-622``; ILQL's hand-rolled loop
``ilql_models.py:257-327``) with one compiled XLA program:

- prompts are left-padded to a fixed query length Q, so the last prompt
  token always sits at buffer slot Q-1 and decode writes slots Q..Q+R-1 —
  static shapes, zero recompilation across batches;
- the decode loop is one ``lax.while_loop`` carrying the KV cache, at most
  R steps, fewer once every row has finished (the seq2seq sampler scans R);
- per-step behavior logprobs (under the *raw* logits, matching the
  training-time recompute — the reference likewise recomputes logprobs from
  unfiltered logits, `ppo_orchestrator.py:126-155`) and value estimates are
  emitted *during* decode, so the orchestrator's separate policy recompute
  forward (`ppo_orchestrator.py:126-131`) is folded into generation
  (SURVEY §7.1 design stance).

Sampling controls: temperature, top-k, top-p, greedy; eos early-finish per
sequence with pad fill (`ilql_models.py:314-325` semantics).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import flax.struct as struct
import jax
import jax.numpy as jnp

from trlx_tpu.ops.kv_cache import (
    WRITTEN_TO_INDEX,
    cache_kind,
    decode_kv_layout,
    decode_read_widths,
    written_to_index,
)
from trlx_tpu.telemetry import get_metrics
from trlx_tpu.utils import topk_mask


@dataclass(frozen=True)
class GenerationConfig:
    """Static generation parameters (hashable: safe as a jit static arg)."""

    max_new_tokens: int = 48
    # eos suppression (HF MinLengthLogitsProcessor semantics; without it a
    # policy can collapse into emitting eos immediately — a degenerate local
    # optimum the reference randomwalks config guards with `min_length: 2`):
    # - ``min_new_tokens``: suppress eos for the first k decode steps;
    # - ``min_length``: minimum *total* length. For causal LMs we count
    #   *real* (non-pad) prompt tokens per row — a deliberate divergence
    #   from HF's MinLengthLogitsProcessor, which counts the padded row
    #   width (input_ids.shape[-1]) and so under-suppresses short prompts
    #   in left-padded mixed-length batches. For seq2seq: decoder tokens
    #   incl. the start token, as HF counts.
    min_new_tokens: int = 0
    min_length: int = 0
    # HF-style total-length cap (prompt + generated for causal; decoder
    # tokens incl. start for seq2seq): sequences reaching it finish early
    # even though the compiled decode always runs max_new_tokens steps
    # (static shapes) — remaining steps emit pad with mask 0.
    max_length: int = 0
    temperature: float = 1.0
    top_k: int = 0  # 0 = disabled
    top_p: float = 1.0  # 1.0 = disabled
    do_sample: bool = True
    eos_token_id: int = 50256
    pad_token_id: int = 50256
    # seq2seq/forced-BOS support (the fork forces a Chinese BOS token,
    # `ppo_models.py:620-622`); -1 = disabled
    forced_bos_token_id: int = -1
    decoder_start_token_id: int = 0
    # Per-row RNG (docs/inference.md): the sampler's ``rng`` argument is a
    # [B, 2] array of per-row base keys instead of one batch key, and step
    # t of row b samples with ``fold_in(row_keys[b], t)`` — each row's
    # token sequence depends only on (its key, its logits), never on batch
    # composition or position. This is the contract that makes the
    # continuous-batching engine (which always samples per-row) per-row
    # token-identical to this fixed-batch sampler regardless of admission
    # order. Default off: the legacy one-key-per-step batch draw stays
    # bitwise-stable for existing runs.
    per_row_rng: bool = False

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "GenerationConfig":
        d = dict(d)
        # reference configs write HF's ``max_length`` (their gen budget;
        # `configs/ppo_config.yml` "LM max sample gen length") — map it to
        # the decode budget rather than silently dropping it. Note this
        # over-allocates: the compiled decode scans max_length steps (and
        # sizes the KV cache for them) even when long prompts eat most of
        # the total budget; the cap masks the surplus steps as pad. Set
        # max_new_tokens explicitly to bound decode work for long prompts.
        if "max_length" in d and "max_new_tokens" not in d:
            d["max_new_tokens"] = d["max_length"]
        known = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in known}
        # reference YAMLs write numeric fields as floats (``top_k: 0.0``,
        # `configs/ppo_gptj.yml`); coerce integral fields
        for name in ("max_new_tokens", "min_new_tokens", "min_length",
                     "max_length", "top_k",
                     "eos_token_id", "pad_token_id", "forced_bos_token_id",
                     "decoder_start_token_id"):
            if name in d and d[name] is not None:
                d[name] = int(d[name])
        return cls(**d)


@struct.dataclass
class SampleOutput:
    """Rollout result, shapes [B, R]; all device-resident."""

    tokens: jax.Array  # sampled response tokens (pad after eos)
    response_mask: jax.Array  # 1 up to and including the eos token
    logprobs: jax.Array  # behavior logprobs under raw logits
    values: jax.Array  # value-head estimates at each decision point


def validate_gen_config(cfg: GenerationConfig, vocab_size, provided=None) -> None:
    """Fail loudly on token ids outside the model's vocab — an out-of-range
    ``forced_bos_token_id`` (e.g. the UL2 fork's Chinese BOS 21128 against a
    small from-scratch vocab) otherwise surfaces as NaNs deep in generation.
    No-op when the model config exposes no vocab size. When ``provided`` is
    given (the keys the user/tokenizer actually set), only those fields are
    checked — dataclass defaults (gpt2's eos 50256) must not crash a
    small-vocab from-scratch config that never set them.
    """
    if not vocab_size:
        return
    for name in ("eos_token_id", "pad_token_id", "forced_bos_token_id",
                 "decoder_start_token_id"):
        if provided is not None and name not in provided:
            continue
        tid = getattr(cfg, name)
        if tid is None or tid < 0:
            continue
        if tid >= vocab_size:
            raise ValueError(
                f"gen_kwargs {name}={tid} is outside the model vocab "
                f"(vocab_size={vocab_size}) — check that the generation "
                f"config matches the checkpoint/arch"
            )


def suppress_eos_before_min(
    logits: jax.Array,
    t: jax.Array,
    cfg: GenerationConfig,
    min_new: Optional[jax.Array] = None,
) -> jax.Array:
    """Mask the eos logit while ``t < min_new`` (HF MinLengthLogitsProcessor
    semantics; applied before top-k/top-p as HF does). ``min_new`` is the
    per-sequence [B] (or scalar) number of suppressed steps the caller
    derives from min_new_tokens/min_length; no-op when eos is unset."""
    if min_new is None or cfg.eos_token_id is None or cfg.eos_token_id < 0:
        return logits
    eos_col = (
        jnp.zeros((logits.shape[-1],), bool).at[cfg.eos_token_id].set(True)
    )
    active = jnp.asarray(t < min_new)
    if active.ndim == 0:
        active = active[None]
    return jnp.where(active[:, None] & eos_col[None, :], -jnp.inf, logits)


def concat_cols(a: jax.Array, b: jax.Array) -> jax.Array:
    """[B, Qa] ++ [B, Qb] along axis 1 via dynamic_update_slice.

    NOT jnp.concatenate: the masks this builds feed shard_map programs
    (pp decode) and committed-sharded buffers, and XLA's SPMD partitioner
    mis-lowers a concatenate operand on any mesh with a spare size>1
    axis — the same compiler-bug family as the sharded rollout-concat
    replica-sum (data/ppo_types.py::concat_rollouts) and the stage
    stacking (tools/pp_miscompile_repro.py). Shared by the fixed-batch
    sampler and the continuous engine's mask construction."""
    buf = jnp.zeros((a.shape[0], a.shape[1] + b.shape[1]), a.dtype)
    buf = jax.lax.dynamic_update_slice(buf, a, (0, 0))
    return jax.lax.dynamic_update_slice(buf, b.astype(a.dtype), (0, a.shape[1]))


def stack_cols(xs) -> jax.Array:
    """Stack [B] columns into [B, len(xs)] via dynamic_update_slice
    writes — NOT ``jnp.stack``, for the same SPMD mis-lowering reasons
    as :func:`concat_cols` (the verify step's per-column outputs are
    committed-sharded on the batch axis)."""
    first = xs[0]
    buf = jnp.zeros((first.shape[0], len(xs)), first.dtype)
    for j, x in enumerate(xs):
        buf = jax.lax.dynamic_update_slice(
            buf, x.astype(first.dtype)[:, None], (0, j)
        )
    return buf


def make_row_keys(phase_key: jax.Array, indices: jax.Array) -> jax.Array:
    """[N, 2] per-row base keys: ``fold_in(phase_key, index)`` per row.

    ``indices`` are the rows' global draw positions within the phase —
    the same prompt drawn at the same position gets the same key whether
    it decodes in the fixed batch or through the continuous engine's
    slots, which is the root of the two engines' per-row parity."""
    return jax.vmap(lambda i: jax.random.fold_in(phase_key, i))(
        jnp.asarray(indices, jnp.int32)
    )


def choose_tokens(
    gen_config: GenerationConfig,
    logits_last: jax.Array,  # [B, V] float32 raw logits
    t,  # scalar or [B] per-row decode step
    finished: jax.Array,  # [B] bool
    value_last: jax.Array,  # [B] float32
    n_real,  # [B] real prompt lengths (for the max_length cap)
    min_new=None,  # scalar/[B] eos-suppression horizon (None = off)
    key=None,  # batch mode: one key for the whole [B, V] draw
    row_keys=None,  # per-row mode: [B, 2] base keys, folded with t
):
    """One decode step's token selection — the kernel shared by the
    fixed-batch sampler and the continuous engine's ``decode_step``.

    Returns ``(token, live_i32, logprob, value_out, finished_next)`` with
    the fixed sampler's exact semantics: finished rows emit deterministic
    ``(pad, 0, 0.0, 0.0)``; the behavior logprob is taken under the RAW
    logits; ``finished_next`` folds in eos and the HF total-length cap.
    Exactly one of ``key`` / ``row_keys`` must be given when sampling.
    """
    if gen_config.forced_bos_token_id >= 0:
        forced = jnp.full(
            (logits_last.shape[0],), gen_config.forced_bos_token_id, jnp.int32
        )
    else:
        forced = None
    choice_logits = suppress_eos_before_min(logits_last, t, gen_config, min_new)
    if gen_config.do_sample:
        filtered = filter_logits(choice_logits, gen_config)
        if row_keys is not None:
            B = logits_last.shape[0]
            # the verify step calls this once per drafted column, so one
            # `row_keys` lineage feeds D+1 fold_ins in a single program —
            # each folds a DISTINCT step index t0+j (independent streams
            # by the fold constant), which the key-reuse dataflow rule
            # cannot prove from the jaxpr alone
            keys_t = jax.vmap(jax.random.fold_in)(  # tpu-lint: disable=key-reuse
                row_keys, jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))
            )
            token = jax.vmap(
                lambda kk, lg: jax.random.categorical(kk, lg)
            )(keys_t, filtered)
        else:
            token = jax.random.categorical(key, filtered, axis=-1)
    else:
        token = jnp.argmax(choice_logits, axis=-1)
    token = token.astype(jnp.int32)
    if forced is not None:
        token = jnp.where(jnp.asarray(t) == 0, forced, token)
    token = jnp.where(finished, gen_config.pad_token_id, token)

    # behavior logprob under the *raw* logits: gather + logsumexp
    # (one [B] gather instead of materializing [B, V] log_softmax)
    logprob = (
        jnp.take_along_axis(logits_last, token[:, None], axis=-1)[:, 0]
        - jax.scipy.special.logsumexp(logits_last, axis=-1)
    )
    live = jnp.logical_not(finished)
    # finished rows emit deterministic zeros for logprob/value (these
    # slots are response_mask==0 everywhere downstream): the emissions
    # then depend only on `finished`, never on the post-finish
    # logits/values — which is what lets the segmented decode (and the
    # engine's recycled slots) skip/ignore stale state bitwise-safely.
    logprob = jnp.where(live, logprob, 0.0)
    value_out = jnp.where(live, value_last, 0.0)
    finished = jnp.logical_or(finished, token == gen_config.eos_token_id)
    if gen_config.max_length > 0:
        # HF total-length cap: prompt + generated >= max_length
        finished = jnp.logical_or(
            finished, n_real + jnp.asarray(t) + 1 >= gen_config.max_length
        )
    return token, live.astype(jnp.int32), logprob, value_out, finished


def accept_drafts(
    gen_config: GenerationConfig,
    logits_seq: jax.Array,  # [B, D, V] f32: column j-1 = logits after the
    #   anchor and the first j-1 draft tokens (predicts token t0 + j)
    values_seq: jax.Array,  # [B, D] f32 value estimates at those columns
    t0,  # [B] int32 decode step of the anchor token
    finished: jax.Array,  # [B] bool AFTER the anchor (its finished_next)
    accepted0: jax.Array,  # [B] bool — the anchor token was live
    n_real,  # [B] real prompt lengths
    draft: jax.Array,  # [B, D] int32 host-proposed tokens for t0+1..t0+D
    draft_len: jax.Array,  # [B] int32 valid draft columns (0..D)
    row_keys: jax.Array,  # [B, 2] per-row base keys
    min_new=None,
    budget: int = 0,  # R — tokens past it are never accepted
):
    """Longest-prefix draft acceptance — the speculative verify step's
    token kernel (docs/inference.md "Speculative decoding").

    Runs the EXACT one-token kernel (:func:`choose_tokens`, under the
    same ``fold_in(row_key, t0+j)`` per-row keys) at every drafted
    position and accepts draft ``j`` iff every earlier position was
    accepted and the target sample equals the draft token. Because the
    per-row RNG contract makes token ``t`` a pure function of
    (row key, logits at ``t``) and the accepted prefix reproduces the
    sequential loop's inputs position by position, accepted tokens are
    bitwise the tokens the one-token loop would have sampled — rejection
    never needs a rollback, only the refusal to accept what follows.

    Unrolled over the (small, static) draft width D so every column is
    literally a ``choose_tokens`` call — one parity surface, no scan
    re-association. Returns ``(tokens, accepted, logprobs, values,
    n_accepted, finished_next)`` with shapes [B, D] / [B]; ``accepted``
    is a contiguous int32 prefix mask per row.
    """
    B, D = draft.shape[0], draft.shape[1]
    acc_prev = jnp.asarray(accepted0, bool)
    fin = finished
    n_acc = jnp.zeros((B,), jnp.int32)
    toks, accs, lps, vals = [], [], [], []
    for j in range(1, D + 1):
        token, live, logprob, value_out, fin_next = choose_tokens(
            gen_config,
            logits_seq[:, j - 1],
            t0 + j,
            fin,
            values_seq[:, j - 1],
            n_real,
            min_new=min_new,
            row_keys=row_keys,
        )
        ok = (
            acc_prev
            & (live == 1)
            & (j <= draft_len)
            & (token == draft[:, j - 1])
            & (t0 + j < budget)
        )
        # finished advances only along the accepted prefix: a rejected
        # position's eos (if any) is re-sampled by a later step
        fin = jnp.where(ok, fin_next, fin)
        n_acc = n_acc + ok.astype(jnp.int32)
        acc_prev = ok
        toks.append(token)
        accs.append(ok.astype(jnp.int32))
        lps.append(logprob)
        vals.append(value_out)
    return (
        stack_cols(toks),
        stack_cols(accs),
        stack_cols(lps),
        stack_cols(vals),
        n_acc,
        fin,
    )


def filter_logits(logits: jax.Array, cfg: GenerationConfig) -> jax.Array:
    """Temperature / top-k / top-p filtering (float32 in, float32 out)."""
    if cfg.temperature != 1.0:
        logits = logits / cfg.temperature
    if cfg.top_k > 0:
        logits = topk_mask(logits, cfg.top_k)
    if cfg.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens until cumulative prob exceeds top_p (always >= 1 token)
        cutoff_mask = cum - probs < cfg.top_p
        kth = jnp.sum(cutoff_mask, axis=-1, keepdims=True)  # tokens kept
        threshold = jnp.take_along_axis(sorted_logits, kth - 1, axis=-1)
        logits = jnp.where(logits < threshold, -jnp.inf, logits)
    return logits


def make_sampler(
    apply_fn: Callable,
    init_cache_fn: Callable,
    gen_config: GenerationConfig,
    query_length: int,
    with_values: bool = True,
    cache_sharding=None,
):
    """Build a jittable ``(params, prompt_ids, prompt_mask, rng) ->
    SampleOutput`` closure.

    ``apply_fn(params, input_ids, attention_mask, position_ids, cache,
    cache_index)`` must return a dict with "logits", "cache" and (if
    ``with_values``) "values". ``init_cache_fn(batch, capacity)`` builds the
    KV buffers.

    ``cache_sharding`` (optional ``NamedSharding``): pins the KV buffers'
    layout — e.g. ``P((dp, fsdp), "sp")`` to shard the *capacity* axis over
    a sequence-parallel mesh axis, so long-context rollouts hold only
    ``cap / sp`` of the cache per device. The decode attention over the
    sharded cache is expressed normally; GSPMD inserts the cross-shard
    softmax reduction (the collective moves [B, H, cap] logits, head_dim
    times less than gathering the cache itself). Applied to the initial
    buffers and re-pinned on each step's updated cache so the constraint
    sticks through the loop carry. A cache whose capacity axis is sharded
    stays in the ``kv_buffers`` layout and decodes through the generic read;
    every other is carried in ``ops/kv_cache.py::decode_kv_layout``: one
    array a kind for all layers, which the model's layers write in place
    and read by slice, where a layer's own buffer is small enough for the
    compiler to stage and write back whole, else a folded dict a layer
    (the gauge ``sampler/carry_buffers`` counts the carry's arrays when the
    sampler is traced: 2, or 4 with int8; times the layers for the latter).

    What the loop knows and no layer can see, it promises the decode read:
    the prefill fills ``[0, Q)``, step ``t`` writes ``Q + t`` and nothing
    past it holds anything. Each step puts that on the folded cache it
    hands the model (``kv_cache.py::written_to_index(cache, Q)``) and takes
    it off what comes back, so the carry is arrays alone; the read then
    takes the leading ``decode_read_widths(Q + R, Q)`` positions and no
    more (64 + 448: 128, 256, 384, 512; 512 + 48: 560 alone, the program
    it was). The gauge ``sampler/read_share`` is the mean over the ``R``
    steps of (the width a step reads / the capacity). A cache whose
    capacity axis is sharded, and the pp sampler's layer-major dict (its
    stage scan takes the cache apart itself), are promised nothing.
    """
    Q = query_length
    R = gen_config.max_new_tokens
    cap = Q + R

    def pin_cache(cache, sharding=cache_sharding):
        if sharding is None:
            return cache
        return jax.tree_util.tree_map(
            lambda a: jax.lax.with_sharding_constraint(a, sharding), cache
        )

    def capacity_sharded(cache):
        # kv_buffers layout [..., C, H, Dh]; a pp cache leads with L
        if cache_sharding is None:
            return False
        k = (cache if isinstance(cache, dict) else cache[0])["k"]
        spec, axis = cache_sharding.spec, k.ndim - 3
        return len(spec) > axis and spec[axis] is not None

    # Optional fast-prefill contract: an apply_fn accepting ``last_only``
    # may skip LM-head/value computation for all but the final position.
    import inspect

    _prefill_kwargs = (
        {"last_only": True}
        if "last_only" in inspect.signature(apply_fn).parameters
        else {}
    )

    def sampler(params, prompt_ids, prompt_mask, rng) -> SampleOutput:
        B = prompt_ids.shape[0]
        n_real = jnp.sum(prompt_mask, axis=-1)  # [B]

        # eos-suppression horizon: min_length counts real prompt tokens +
        # generated (HF causal semantics)
        if gen_config.min_new_tokens > 0 or gen_config.min_length > 0:
            min_new = jnp.maximum(
                gen_config.min_new_tokens, gen_config.min_length - n_real
            )
        else:
            min_new = None

        cache = init_cache_fn(B, cap)
        if not isinstance(cache, dict) and any(
            cache_kind(layer).tail or cache_kind(layer).latent for layer in cache
        ):
            raise ValueError(
                "the fixed sampler carries KV layers only (its loop folds "
                "every layer into decode_kv_layout): a model with state "
                "layers (granitemoehybrid), a latent cache (deepseek_v3) or "
                "a tail beside its keys (zaya) "
                "samples through rollout.engine: continuous"
            )
        cache = pin_cache(cache)
        # prefill: cache validity = prompt mask over slots [0, Q)
        pad_tail = jnp.zeros((B, R), dtype=prompt_mask.dtype)
        cache_mask = concat_cols(prompt_mask, pad_tail)
        positions = jnp.clip(jnp.cumsum(prompt_mask, axis=-1) - 1, 0, None)
        # device-trace scope names are a contract (docs/observability.md)
        with jax.named_scope("prefill"):
            out = apply_fn(
                params,
                prompt_ids,
                attention_mask=cache_mask,
                position_ids=positions,
                cache=cache,
                cache_index=0,
                **_prefill_kwargs,
            )
        cache = out["cache"]
        carry_sharding = cache_sharding
        # what this loop can promise the decode read and no layer can see:
        # the prefill filled [0, Q), step t writes Q + t, nothing past it
        # holds anything (kv_cache.py::written_to_index). Made for the
        # model's own layers over a folded cache; the pp stage scan takes
        # its layer-major dict apart itself and is promised nothing
        first_written = None
        if not capacity_sharded(cache):
            # the decode loop carries the lane-dense layout, which
            # decode_attention writes in place and reads once a step: all
            # layers in one array a kind where a layer's own buffer would
            # be staged and written back whole, else a layer at a time
            prefilled, cache = cache, decode_kv_layout(cache)
            stacked = isinstance(cache, dict) and not isinstance(prefilled, dict)
            first_written = None if isinstance(prefilled, dict) else Q
            if cache_sharding is not None and stacked:
                # a tuple's carry leads with the layers, which no axis shards
                carry_sharding = jax.sharding.NamedSharding(
                    cache_sharding.mesh,
                    jax.sharding.PartitionSpec(None, *cache_sharding.spec),
                )
        cache = pin_cache(cache, carry_sharding)
        get_metrics().gauge("sampler/carry_buffers").set(
            len(jax.tree_util.tree_leaves(cache))
        )
        # the mean over the R steps of (the width step t reads / capacity)
        widths = decode_read_widths(cap, first_written)
        get_metrics().gauge("sampler/read_share").set(
            sum(min(w for w in widths if w > Q + t) for t in range(R)) / (max(R, 1) * cap)
        )
        logits_last = out["logits"][:, -1].astype(jnp.float32)  # [B, V]
        if with_values:
            value_last = out["values"][:, -1].astype(jnp.float32)
        else:
            value_last = jnp.zeros((B,), jnp.float32)

        slot_ids = jnp.arange(cap)[None, :]

        @jax.named_scope("decode_step")
        def step(carry):
            t, cache, logits_last, value_last, finished, rng, ys = carry
            if gen_config.per_row_rng:
                # `rng` is the [B, 2] per-row base keys — folded with t
                # inside choose_tokens, never chained through the carry
                key, row_keys = None, rng
            else:
                rng, key = jax.random.split(rng)
                row_keys = None
            # token selection + behavior logprob: the kernel shared with
            # the continuous engine's decode_step (finished rows emit
            # deterministic (pad, 0, 0.0, 0.0) — see choose_tokens)
            token, live, logprob, value_out, finished = choose_tokens(
                gen_config, logits_last, t, finished, value_last, n_real,
                min_new=min_new, key=key, row_keys=row_keys,
            )
            ys = tuple(
                jax.lax.dynamic_update_slice(buf, y[None], (t, 0))
                for buf, y in zip(ys, (token, live, logprob, value_out))
            )

            # forward the sampled token at slot Q+t
            cache_mask_t = (slot_ids <= Q + t).astype(jnp.int32) * concat_cols(
                prompt_mask, jnp.ones((B, R), prompt_mask.dtype)
            )
            out = apply_fn(
                params,
                token[:, None],
                attention_mask=cache_mask_t,
                position_ids=(n_real + t)[:, None],
                cache=cache if first_written is None else written_to_index(cache, first_written),
                cache_index=Q + t,
            )
            new_logits = out["logits"][:, 0].astype(jnp.float32)
            new_value = (
                out["values"][:, 0].astype(jnp.float32)
                if with_values
                else jnp.zeros((B,), jnp.float32)
            )
            new_cache = out["cache"]
            if isinstance(new_cache, dict):
                # the model hands the carry back as it came, promise and
                # all; the loop carries the arrays
                new_cache = {k: a for k, a in new_cache.items() if k != WRITTEN_TO_INDEX}
            return (t + 1, pin_cache(new_cache, carry_sharding), new_logits,
                    new_value, finished, rng, ys)

        if gen_config.max_length > 0:
            # prompts already at/over the total-length cap emit no tokens
            finished0 = n_real >= gen_config.max_length
        else:
            finished0 = jnp.zeros((B,), bool)
        # Early exit: the loop stops at R steps or once every row has
        # finished. The outputs are pre-filled with what a finished row
        # emits, (pad, 0, 0.0, 0.0), and each step writes its row `t`, so
        # they are bitwise what the full R-step run gives (rows never
        # un-finish; the RNG carry is not an output). The cache is a plain
        # loop carry: no `cond` hands it through, whose branch boundaries
        # copy it (PERF.md §6, PR 25). The one `switch` in the step is
        # around the decode read (attention.py::_decode_read): its branches
        # take the carry read-only and return a layer's output, and
        # compiled for a described v5e at longgen's shapes the body holds
        # no cache-shaped copy, nothing of the carry in S(1), and the 96
        # writes in place (tests/test_tpu_compile.py; PERF.md §6, PR 56).
        ys0 = (
            jnp.full((R, B), gen_config.pad_token_id, jnp.int32),
            jnp.zeros((R, B), jnp.int32),
            jnp.zeros((R, B), jnp.float32),
            jnp.zeros((R, B), jnp.float32),
        )
        *_, (tokens, mask, logprobs, values) = jax.lax.while_loop(
            lambda carry: (carry[0] < R) & ~jnp.all(carry[4]),
            step,
            (jnp.int32(0), cache, logits_last, value_last, finished0, rng,
             ys0),
        )
        return SampleOutput(
            tokens=tokens.T,
            response_mask=mask.T,
            logprobs=logprobs.T,
            values=values.T,
        )

    return sampler


def make_seq2seq_sampler(
    encode_fn: Callable,
    decode_fn: Callable,
    init_cross_kv_fn: Callable,
    init_cache_fn: Callable,
    gen_config: GenerationConfig,
    with_values: bool = True,
    cache_sharding=None,
):
    """Compiled encoder-decoder sampling (the fork's T5 ``generate`` path,
    `ppo_models.py:620-622`, as one XLA program).

    ``cache_sharding`` (optional ``NamedSharding``): shards the
    cross-attention K/V's *encoder length* axis (dim 1) — the long-context
    object for seq2seq rollouts — over a sequence-parallel mesh axis. The
    decoder self-attn cache (capacity = generation length + 1) stays
    replicated: it is short by construction.

    Encoder runs once; cross-attention K/V are precomputed per layer; the
    decoder scan feeds one token per step into a fixed-capacity self-attn
    cache. The decoder-start token occupies cache slot 0 (stripped from the
    response, as the reference strips it at `ppo_orchestrator.py:80`);
    ``forced_bos_token_id`` (the fork's Chinese BOS) is emitted at step 0
    when configured.

    - ``encode_fn(params, input_ids, attention_mask) -> encoder_hidden``
    - ``init_cross_kv_fn(params, encoder_hidden) -> cross_kv``
    - ``decode_fn(params, decoder_input_ids, encoder_mask, decoder_mask,
      cache, cache_index, cross_kv) -> {"logits", "values"?, "cache"}``
    - ``init_cache_fn(batch, capacity) -> decoder KV buffers``
    """
    R = gen_config.max_new_tokens
    cap = R + 1  # slot 0 = decoder start token

    def sampler(params, prompt_ids, prompt_mask, rng) -> SampleOutput:
        B = prompt_ids.shape[0]
        # min_length counts decoder tokens incl. the start token (HF
        # encoder-decoder semantics)
        if gen_config.min_new_tokens > 0 or gen_config.min_length > 0:
            min_new = jnp.maximum(
                gen_config.min_new_tokens, gen_config.min_length - 1
            )
        else:
            min_new = None
        encoder_hidden = encode_fn(params, prompt_ids, prompt_mask)
        cross_kv = init_cross_kv_fn(params, encoder_hidden)
        if cache_sharding is not None:
            cross_kv = jax.tree_util.tree_map(
                lambda a: jax.lax.with_sharding_constraint(a, cache_sharding),
                cross_kv,
            )
        cache = init_cache_fn(B, cap)
        slot_ids = jnp.arange(cap)[None, :]

        start = jnp.full((B, 1), gen_config.decoder_start_token_id, jnp.int32)
        out = decode_fn(
            params,
            start,
            encoder_mask=prompt_mask,
            decoder_mask=(slot_ids <= 0).astype(jnp.int32).repeat(B, 0),
            cache=cache,
            cache_index=0,
            cross_kv=cross_kv,
        )
        cache = out["cache"]
        logits_last = out["logits"][:, -1].astype(jnp.float32)
        value_last = (
            out["values"][:, -1].astype(jnp.float32)
            if with_values
            else jnp.zeros((B,), jnp.float32)
        )

        @jax.named_scope("decode_step")
        def step(carry, t):
            cache, logits_last, value_last, finished, rng = carry
            rng, key = jax.random.split(rng)

            choice_logits = suppress_eos_before_min(logits_last, t, gen_config, min_new)
            if gen_config.do_sample:
                filtered = filter_logits(choice_logits, gen_config)
                token = jax.random.categorical(key, filtered, axis=-1)
            else:
                token = jnp.argmax(choice_logits, axis=-1)
            token = token.astype(jnp.int32)
            if gen_config.forced_bos_token_id >= 0:
                token = jnp.where(
                    t == 0,
                    jnp.full((B,), gen_config.forced_bos_token_id, jnp.int32),
                    token,
                )
            token = jnp.where(finished, gen_config.pad_token_id, token)

            logprob = (
                jnp.take_along_axis(logits_last, token[:, None], axis=-1)[:, 0]
                - jax.scipy.special.logsumexp(logits_last, axis=-1)
            )
            live = jnp.logical_not(finished)
            finished = jnp.logical_or(finished, token == gen_config.eos_token_id)
            if gen_config.max_length > 0:
                # decoder tokens incl. the start token: (t+1 generated) + 1
                finished = jnp.logical_or(
                    finished, t + 2 >= gen_config.max_length
                )
            ys = (token, live.astype(jnp.int32), logprob, value_last)

            dec_mask = (slot_ids <= t + 1).astype(jnp.int32).repeat(B, 0)
            out = decode_fn(
                params,
                token[:, None],
                encoder_mask=prompt_mask,
                decoder_mask=dec_mask,
                cache=cache,
                cache_index=t + 1,
                cross_kv=cross_kv,
            )
            new_logits = out["logits"][:, 0].astype(jnp.float32)
            new_value = (
                out["values"][:, 0].astype(jnp.float32)
                if with_values
                else jnp.zeros((B,), jnp.float32)
            )
            return (out["cache"], new_logits, new_value, finished, rng), ys

        if gen_config.max_length > 0:
            finished0 = jnp.full((B,), 1 >= gen_config.max_length)
        else:
            finished0 = jnp.zeros((B,), bool)
        _, (tokens, mask, logprobs, values) = jax.lax.scan(
            step,
            (cache, logits_last, value_last, finished0, rng),
            jnp.arange(R),
        )
        return SampleOutput(
            tokens=tokens.T,
            response_mask=mask.T,
            logprobs=logprobs.T,
            values=values.T,
        )

    return sampler
