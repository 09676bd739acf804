"""Continuous-batching inference subsystem (docs/inference.md).

The collect-phase decode loop re-built as a real inference engine
(ROADMAP "make the rollout engine a real inference server"; PipelineRL's
continuous rollout streams in PAPERS.md):

- :mod:`trlx_tpu.ops.kv_cache` (below the models, shared with the fixed
  sampler) — paged/block KV cache: the same ``[B, capacity]`` physical
  buffers the fixed sampler uses, plus per-slot block tables indirecting
  logical positions through fixed-size blocks, honoring
  ``kv_cache_dtype`` (int8) and an sp-sharded capacity axis;
- :mod:`trlx_tpu.inference.engine` — the continuous-batching decode
  loop: a fixed pool of decode slots, a host-side admission queue that
  prefills a fresh prompt into a slot the step after its row emits eos,
  per-row RNG keys (each row's tokens independent of admission order),
  and completed rollouts harvested in fixed-width groups;
- :mod:`trlx_tpu.inference.server` — the same engine as a standalone
  batched-serving path (submit/poll against a loaded policy checkpoint,
  no trainer required).

Config surface: ``train.rollout`` (see :class:`RolloutEngineConfig`),
e.g. ``rollout: {engine: continuous, slots: 128, block_size: 16}``. The
fixed-batch sampler stays the default (``engine: fixed``) and the parity
baseline: under per-row RNG the two engines produce per-row
token-identical rollouts (tests/test_inference_engine.py).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional

ROLLOUT_ENGINES = ("fixed", "continuous")
SPEC_DRAFTERS = ("trie", "ngram")


@dataclass(frozen=True)
class SpecDecodeConfig:
    """Parsed ``train.rollout.spec_decode`` section
    (docs/inference.md "Speculative decoding").

    :param enabled: turn drafted verify steps on. Off — the default —
        keeps every jitted engine program byte-identical to the
        spec-less build.
    :param max_draft: draft-token cap per slot per verify step (the
        verify program forwards ``max_draft + 1`` columns); clamped by
        the engine to ``max_new_tokens - 1``.
    :param drafter: ``"trie"`` (shared-prefix-trie corpus + per-row
        n-gram fallback, :class:`trlx_tpu.serving.TrieDrafter`) or
        ``"ngram"`` (per-row self-lookup only).
    :param min_accept_ewma: per-tenant accept-rate floor below which a
        tenant's rows degrade to one-token decode (graceful — drafting
        resumes if later probe drafts raise the EWMA back over the
        bar). 0 never degrades.
    """

    enabled: bool = False
    max_draft: int = 4
    drafter: str = "trie"
    min_accept_ewma: float = 0.0

    def __post_init__(self):
        if self.max_draft < 1:
            raise ValueError(
                f"train.rollout spec_decode.max_draft={self.max_draft} "
                "must be >= 1"
            )
        if self.drafter not in SPEC_DRAFTERS:
            raise ValueError(
                f"train.rollout spec_decode.drafter={self.drafter!r} is "
                f"not supported (choose one of {SPEC_DRAFTERS})"
            )
        if not 0.0 <= self.min_accept_ewma <= 1.0:
            raise ValueError(
                "train.rollout spec_decode.min_accept_ewma="
                f"{self.min_accept_ewma} must be in [0, 1]"
            )

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "SpecDecodeConfig":
        d = dict(d or {})
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"Unknown train.rollout spec_decode keys: "
                f"{sorted(unknown)} (known: {sorted(known)})"
            )
        if "enabled" in d and d["enabled"] is not None:
            d["enabled"] = bool(d["enabled"])
        if "max_draft" in d and d["max_draft"] is not None:
            d["max_draft"] = int(d["max_draft"])
        if "min_accept_ewma" in d and d["min_accept_ewma"] is not None:
            d["min_accept_ewma"] = float(d["min_accept_ewma"])
        return cls(**d)


@dataclass(frozen=True)
class RolloutEngineConfig:
    """Parsed ``train.rollout`` section.

    :param engine: ``"fixed"`` (the segmented-scan sampler,
        ``ops/sampling.py``) or ``"continuous"`` (the slot-admission
        engine, :mod:`trlx_tpu.inference.engine`).
    :param slots: decode-slot pool size B; 0 = the orchestrator's
        ``chunk_size`` (so the engine's steady-state batch matches the
        fixed sampler's).
    :param admit_width: static width of one admission/prefill call
        (padded with dummy rows); 0 = ``max(shard, slots // 4)`` where
        ``shard`` is the mesh's data-shard count. Smaller = prompter
        refills but more prefill dispatches.
    :param harvest_width: completed rollouts per harvest group — the
        downstream chunk size every scoring/ref/reward program compiles
        at; 0 = ``admit_width``. Must divide into ``slots`` (<= slots).
    :param block_size: paged-KV block size; auto-shrunk to the largest
        divisor of the cache capacity (Q + max_new_tokens) so the
        logical view stays exactly capacity-wide (bitwise parity with
        the fixed cache needs no tail padding).
    :param poll_interval: fetch the engine's [B] ``done`` flags every
        k-th decode step instead of every step (the flags are sticky, so
        the amortized poll is exact); 1 — the default — is bitwise the
        poll-every-step loop, larger values trade up to k-1 idle steps
        per finished slot for k× fewer blocking host fetches on the
        decode critical path.
    :param per_row_rng: force per-row RNG keys in the FIXED sampler too
        (``None`` = only when ``engine == "continuous"``, which always
        samples per-row). The parity tests run the fixed baseline with
        ``per_row_rng: true``.
    :param prefill_chunk: chunked-prefill width in prompt columns
        (docs/inference.md "Chunked prefill"). ``> 0`` replaces the
        engine's monolithic admission prefill with one dispatch a
        block-aligned prompt-column chunk, skipping the chunks no
        admitted row needs — leading all-pad columns of left-padded
        prompts and blocks served from the shared-prefix pool — so
        prefill compute scales with the group's real prompt length,
        and prefix sharing saves prefill FLOPs, not just HBM traffic.
        Rounded to a block-aligned divisor of the query length
        (``ops/kv_cache.py::choose_prefill_chunk``). Chunked and
        monolithic prefill are token/mask-bitwise-identical
        (logprobs/values at the engine's established bf16 resolution).
        0 — the default — keeps the monolithic program byte-identical
        on the trainer's collect loop; an ``InferenceServer`` reads 0 as
        "not set" and derives the width from Q
        (``ops/kv_cache.py::serving_prefill_chunk``) with a budget of
        one chunk forward a pump, and forwards a group whole where it
        can skip under half its chunks (docs/inference.md "Who sets
        it").
    :param prefill_chunks_per_pump: serving-pump chunk budget
        (Sarathi-style stall-free admission; needs ``prefill_chunk``):
        one ``pump()`` dispatches at most this many prefill-chunk
        forwards before advancing decode, so an admission burst
        interleaves with decode steps instead of stalling them. 0 =
        unbounded; the trainer collect loop (``drive``) always completes
        an admission inline. A server that derives the chunk sets 1.
    :param spec_decode: speculative-decoding section
        (:class:`SpecDecodeConfig`): host drafter + multi-token verify
        steps, bitwise-pinned against the one-token loop
        (docs/inference.md "Speculative decoding"). ``None``/disabled
        keeps the engine's jitted programs byte-identical to the
        spec-less build. Continuous engine only.
    """

    engine: str = "fixed"
    slots: int = 0
    admit_width: int = 0
    harvest_width: int = 0
    block_size: int = 16
    poll_interval: int = 1
    per_row_rng: Optional[bool] = None
    prefill_chunk: int = 0
    prefill_chunks_per_pump: int = 0
    spec_decode: Optional[SpecDecodeConfig] = None

    def __post_init__(self):
        if (
            self.spec_decode is not None
            and self.spec_decode.enabled
            and self.engine != "continuous"
        ):
            raise ValueError(
                "train.rollout spec_decode.enabled needs the continuous "
                f"engine (got engine={self.engine!r}) — the fixed "
                "sampler has no verify step"
            )
        if self.engine not in ROLLOUT_ENGINES:
            raise ValueError(
                f"train.rollout engine={self.engine!r} is not supported "
                f"(choose one of {ROLLOUT_ENGINES})"
            )
        if self.block_size < 1:
            raise ValueError(
                f"train.rollout block_size={self.block_size} must be >= 1"
            )
        if self.poll_interval < 1:
            raise ValueError(
                f"train.rollout poll_interval={self.poll_interval} must "
                "be >= 1"
            )
        if self.prefill_chunk < 0:
            raise ValueError(
                f"train.rollout prefill_chunk={self.prefill_chunk} must "
                "be >= 0 (0 = monolithic prefill)"
            )
        if self.prefill_chunks_per_pump < 0:
            raise ValueError(
                "train.rollout prefill_chunks_per_pump="
                f"{self.prefill_chunks_per_pump} must be >= 0 "
                "(0 = unbounded)"
            )
        if self.prefill_chunks_per_pump and not self.prefill_chunk:
            raise ValueError(
                "train.rollout prefill_chunks_per_pump needs chunked "
                "prefill (prefill_chunk > 0) — the monolithic program "
                "has nothing to budget"
            )

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "RolloutEngineConfig":
        d = dict(d or {})
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"Unknown train.rollout keys: {sorted(unknown)} "
                f"(known: {sorted(known)})"
            )
        for name in (
            "slots", "admit_width", "harvest_width", "block_size",
            "poll_interval", "prefill_chunk", "prefill_chunks_per_pump",
        ):
            if name in d and d[name] is not None:
                d[name] = int(d[name])
        if "spec_decode" in d and isinstance(d["spec_decode"], dict):
            d["spec_decode"] = SpecDecodeConfig.from_dict(d["spec_decode"])
        return cls(**d)

    @property
    def rows_per_row_rng(self) -> bool:
        """Whether the FIXED sampler should use per-row keys under this
        config (the continuous engine always does)."""
        if self.per_row_rng is not None:
            return bool(self.per_row_rng)
        return self.engine == "continuous"


__all__ = [
    "ROLLOUT_ENGINES",
    "SPEC_DRAFTERS",
    "RolloutEngineConfig",
    "SpecDecodeConfig",
]
