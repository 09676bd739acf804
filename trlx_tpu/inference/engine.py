"""Continuous-batching decode engine: slot-admission rollout generation.

The fixed-batch sampler (``ops/sampling.py``) decodes B prompts in
lockstep: a row that emits eos at step 3 still occupies its batch lane
for all ``max_new_tokens`` steps, emitting pad (how much of a collect
phase that wastes is not measured on the chip: the benchmark's PPO cells
generate fixed lengths). This engine replaces the lockstep with a **fixed
pool of B decode slots** and a host-side admission queue:

- ``decode_step`` advances every slot one token (one compiled program,
  static shapes — the pool IS the batch);
- the step after a row emits eos (or exhausts its budget) the host sees
  its ``done`` flag, harvests the finished rollout in a fixed-width
  group, and **prefills a fresh prompt into the vacated slot** — decode
  lanes never idle while prompts remain;
- per-row RNG keys (``fold_in(phase_key, row_draw_index)`` then
  ``fold_in(row_key, t)`` per step — ``ops/sampling.py::make_row_keys``/
  ``choose_tokens``) make each row's tokens independent of admission
  order and batch composition, so the engine is per-row token-identical
  to the fixed sampler under ``per_row_rng`` (the parity contract,
  tests/test_inference_engine.py);
- the KV cache is the paged/block cache (``ops/kv_cache.py``):
  slot recycling hands the new occupant a rotated block table, writes
  and reads resolve through the table, and ``kv_cache_dtype: int8`` and
  the sp-sharded capacity layout compose unchanged.

Three jitted programs per engine (registered with the analysis harness
as ``ppo.engine_prefill`` / ``ppo.engine_decode_step`` /
``ppo.engine_refill``):

- ``prefill(params, state, slots, prompts, mask, rows, turns, key)`` —
  admission: forward the padded prompt batch, write its KV through the
  (freshly rotated) block tables, seed per-slot sampling state;
- ``decode_step(params, state)`` — one token for every slot; emissions
  land in per-slot device output buffers; returns the [B] ``done``
  flags the host polls;
- ``refill(state, slots)`` — harvest: gather the finished slots'
  rollouts and mark the slots free (the admission queue refills them on
  the next poll).

Host loop cost model: **the loop reads one step behind what it has
dispatched.** ``_decode_once`` dispatches step n, starts the async
copies of its outputs and holds them (:class:`HeldStep`); then it
fetches, routes and polls the outputs of step n-1 that the call before
held. While the host blocks in that fetch, routes the tokens, lands a
group, returns to its caller, schedules, stages an admission and makes
the next call into ``decode_step_jit``, step n is running, and n+1 is
queued behind it before it ends: no fetch of the steady loop drains the
chip. A held step carries the ``(slot, row)`` pairs that stood at its
dispatch, so a slot harvested and re-admitted between the dispatch and
the read routes nothing of its old occupant to the new row; a step's
tokens and flags are read together, so a stream holds a row's last
token before its flag can close it. A finished slot is therefore seen
one step later than its flag was computed (the step rides it along as
a non-live row, as it does any slot waiting for its harvest group), and
harvest and the admission into it follow one iteration later. What is
held is read without a new dispatch where there is nothing to step
(:meth:`pump` with no seeded row, :meth:`drive` at its target): a lone
request finishes with no further traffic. A drafted ``verify_step``
round stays synchronous, since the next draft continues the tokens the
host has seen: an engine that drafts reads what is held before it
drafts. The flags cost one small [B]-bool device->host fetch per
``done_poll_interval`` steps read (they are *sticky* — a finished slot
stays done until harvested — so polling only every k-th step's flags is
exact); at k>1 the round-trip amortizes over k dispatches and slots
idle at most k-1 further steps before harvest. Per-row tokens, masks,
log-probabilities and values never depend on any of this (the parity
contract, tests/test_async_rl.py, tests/test_serving_step_ahead.py);
the composition and order of harvest groups may.

Asynchronous actor–learner support (``train.async_rl``,
docs/async_pipeline.md): :meth:`push_weights` hands the engine a
refreshed behavior policy **mid-generation** — the swap is deferred to
the drive loop's safe point (after harvest bookkeeping, before the next
admission) so a push landing between a harvest and its refill can never
drop the queued admit group; rows are tagged with the params version
they were admitted under. :meth:`min_inflight_version` over those tags
is what the learner's bounded-staleness guard checks before each
update, and every harvest group carries the tags out to the stream
store's version column, where the learner reads them back as the
``async/consumed_lag`` attribution (how many updates old each consumed
minibatch's data is).
"""

from __future__ import annotations

import dataclasses
import inspect
import threading
from typing import (
    Any,
    Callable,
    Dict,
    Iterable,
    Iterator,
    List,
    Optional,
    Tuple,
)

import flax.struct as struct
import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu import telemetry
from trlx_tpu.ops.attention import NEG_INF, padding_bias
from trlx_tpu.ops.kv_cache import (
    PAGED,
    SHARED_POOL_KEYS,
    SHARE_TABLE_KEYS,
    STATE,
    block_view_is_bitcast,
    cache_kind,
    choose_block_size,
    choose_prefill_chunk,
    empty_share_tables,
    held_row_width,
    hold_pool,
    identity_block_tables,
    init_shared_pool,
    live_chunk_positions,
    live_chunks,
    reads_live_chunks,
    starting_at_block,
    stored_order_bias,
    writes_whole_blocks,
)
from trlx_tpu.ops.sampling import (
    GenerationConfig,
    accept_drafts,
    choose_tokens,
    concat_cols,
    make_row_keys,
)
from trlx_tpu.utils import sched_points, tree_gb

#: what the host was doing while the chip sat drained (the starved
#: ledger, docs/observability.md "The serving loop, from inside"): a
#: part is entered with ``mark_starved`` and ``other`` is what no part
#: claimed, so the parts sum to the total
STARVED_PARTS = ("tap", "admit", "land", "caller", "other")


@dataclasses.dataclass
class HeldStep:
    """A dispatched decode step's outputs, held unread while the step
    runs: the loop reads them after it has dispatched the next step, so
    the chip always has one queued. Everything the read needs is what
    stood at the dispatch, since a harvest and an admission may come
    between the two."""

    seq: int  # the loop's count of dispatches when this one was entered
    rows: List[Tuple[int, int]]  # ``_seeded_rows()`` at the dispatch
    done: jax.Array  # [B] bool
    moe_stats: Dict[str, jax.Array]  # a routed family's gauges, else {}
    taps: Optional[Tuple[jax.Array, jax.Array]]  # (token, live) if routed
    log_end: int  # the cadence log's absolute end (``trace_requests``)
    forwards: int  # admission forwards dispatched before it, all told
    wholes: int  # of them, the groups a chunked engine forwarded whole


@struct.dataclass
class EngineState:
    """Device-resident state of the slot pool; every leaf's leading axis
    is the slot axis (sharded over dp×fsdp like any batch)."""

    cache: Any  # paged KV cache (tuple of per-layer dicts)
    row_keys: jax.Array  # [B, 2] uint32 per-row base keys
    t: jax.Array  # [B] int32 tokens emitted by the current occupant
    n_real: jax.Array  # [B] int32 real prompt length
    logits_last: jax.Array  # [B, V] float32 logits at the next decision
    value_last: jax.Array  # [B] float32 value estimate at that decision
    active: jax.Array  # [B] bool — slot holds an unharvested row
    finished: jax.Array  # [B] bool — row hit eos / length cap
    out_tokens: jax.Array  # [B, R] int32 (pad after eos)
    out_mask: jax.Array  # [B, R] int32
    out_logprobs: jax.Array  # [B, R] float32
    out_values: jax.Array  # [B, R] float32
    query_ids: jax.Array  # [B, Q] int32 (left-padded prompt)
    query_mask: jax.Array  # [B, Q] int32
    row_index: jax.Array  # [B] int32 global draw index of the occupant


@dataclasses.dataclass
class EngineStats:
    """Host-side occupancy/throughput counters for one phase.

    Single-thread contract (engine 14 allowlist): every counter is
    mutated only by the thread running the drive/pump loop; the metrics
    absorber and phase summaries read them at phase boundaries, after
    drive() returned on that same thread. No lock — cross-thread traffic
    into the engine goes through push_weights (the one locked entry)."""

    admitted: int = 0
    completed: int = 0
    prefills: int = 0
    decode_steps: int = 0
    recycles: int = 0
    # decode steps dispatched while an earlier step's outputs were still
    # unread: the chip had work queued behind the step it was running.
    # ``decode_steps`` less this is the restarts from an empty pipeline
    # (a phase's first step, one after a tail was read out, every round
    # of an engine that drafts)
    steps_ahead: int = 0
    occupancy_sum: int = 0  # sum over steps of active slots
    num_slots: int = 0
    done_polls: int = 0  # [B]-bool device->host fetches actually paid
    weight_pushes: int = 0  # mid-generation behavior refreshes applied
    released: int = 0  # placeholder rows force-finished on admission
    # what the params the engine was handed hold (GB, from shapes and
    # dtypes at start_phase and at an applied push): what every decode
    # step reads, whoever cast it
    param_gb: float = 0.0
    # wall the host spent blocked in the step loop's device->host
    # fetches (the engine/fetch spans), and in a call that dispatched a
    # step and came back only when the step before it had ended (a
    # backend that keeps one program in flight: the CPU's multi-device
    # client): a step's wall less this is the host's own exposed cost
    host_blocked_ms: float = 0.0
    # wall of the calls that dispatch a decode or verify step (the
    # engine/dispatch spans), on the loop's own clock reads so it stands
    # with the tracer off: short where the backend queues the step,
    # and where it grew the host was held inside the call
    dispatch_ms: float = 0.0
    # the starved ledger: wall from the return of a *draining* fetch (a
    # blocking fetch of an output of the newest dispatched program: that
    # program has ended and nothing is queued behind it) to the entry of
    # the next call that dispatches one, by the part of the loop the
    # host was in (STARVED_PARTS). Closed episodes only; a lower bound
    # on the chip's idle time (the launch after and the transfer's tail
    # before are the device clock's to show). A host that runs ahead of
    # the chip drains nothing and reads 0 here
    starved_by_ms: Dict[str, float] = dataclasses.field(
        default_factory=lambda: dict.fromkeys(STARVED_PARTS, 0.0)
    )
    # chunked prefill (rollout.prefill_chunk > 0): chunks actually RUN
    # (the final chunk included) and prompt columns whose forward was
    # skipped (leading pad + pool-covered shared blocks). What one
    # skipped column would have cost is asked of the engine when
    # ``prefill_flops_saved`` is READ (an abstract trace of the chunk
    # program: seconds of host time at pythia-1.4b's size, which PR 30
    # found inside a serving window when the first skip priced it)
    prefill_chunks: int = 0
    prefill_cols_skipped: int = 0
    # groups a chunked engine forwarded whole (``prefill_min_skip_share``):
    # one monolithic ``prefill`` each, no chunk program run
    prefill_whole: int = 0
    col_flops: Callable[[], float] = dataclasses.field(
        default=lambda: 0.0, repr=False, compare=False
    )
    # cross-request prefix sharing (serving tier): block-granular lookup
    # accounting per admitted real row — hits are blocks served from the
    # shared pool WITHOUT this row publishing them (true reuse), saved
    # counts the private-region writes skipped (hit + published blocks)
    prefix_lookup_blocks: int = 0
    prefix_hit_blocks: int = 0
    prefix_published_blocks: int = 0
    # speculative decoding (rollout.spec_decode): verify steps
    # dispatched, (row, step) pairs that proposed a draft, draft tokens
    # proposed/accepted (anchors excluded — they are ordinary decode
    # tokens), and the proposed lengths (the p50 gauge's sample set,
    # bounded by the phase's step count)
    spec_steps: int = 0
    spec_row_steps: int = 0
    spec_drafted: int = 0
    spec_accepted: int = 0
    spec_draft_lens: List[int] = dataclasses.field(default_factory=list)

    @property
    def starved_ms(self) -> float:
        """The ledger's total: its parts, summed in their one order."""
        return sum(self.starved_by_ms.values())

    @property
    def forwards(self) -> int:
        """Admission forwards dispatched: a group whole, or a chunk."""
        return self.prefills + self.prefill_chunks

    @property
    def prefill_flops_saved(self) -> float:
        """Exact dot-FLOPs the skipped columns would have cost (per-chunk
        cost from the traced program — engine-7's counter, not an
        estimate)."""
        skipped = self.prefill_cols_skipped
        return skipped * self.col_flops() if skipped else 0.0

    @property
    def slot_util(self) -> float:
        denom = self.num_slots * self.decode_steps
        return self.occupancy_sum / denom if denom else 0.0

    @property
    def prefix_hit_rate(self) -> float:
        if not self.prefix_lookup_blocks:
            return 0.0
        return self.prefix_hit_blocks / self.prefix_lookup_blocks

    @property
    def prefix_blocks_saved(self) -> int:
        """Private-region prefix blocks never written (served from or
        redirected into the shared pool)."""
        return self.prefix_hit_blocks + self.prefix_published_blocks

    @property
    def spec_accept_rate(self) -> float:
        if not self.spec_drafted:
            return 0.0
        return self.spec_accepted / self.spec_drafted

    @property
    def spec_tokens_per_step(self) -> float:
        """Tokens committed per drafted (row, step): the anchor (always
        accepted for a live row) plus the accepted draft prefix."""
        if not self.spec_row_steps:
            return 0.0
        return 1.0 + self.spec_accepted / self.spec_row_steps

    @property
    def spec_draft_len_p50(self) -> float:
        if not self.spec_draft_lens:
            return 0.0
        return float(np.median(self.spec_draft_lens))

    def to_dict(self) -> Dict[str, float]:
        return {
            "engine/admitted": float(self.admitted),
            "engine/completed": float(self.completed),
            "engine/prefills": float(self.prefills),
            "engine/decode_steps": float(self.decode_steps),
            "engine/steps_ahead": float(self.steps_ahead),
            "engine/slot_recycles": float(self.recycles),
            "engine/slot_util": round(self.slot_util, 4),
            "engine/done_polls": float(self.done_polls),
            "engine/weight_pushes": float(self.weight_pushes),
            "engine/released": float(self.released),
            "engine/param_gb": round(self.param_gb, 4),
            "engine/host_blocked_ms": round(self.host_blocked_ms, 3),
            "engine/dispatch_ms": round(self.dispatch_ms, 3),
            "engine/starved_ms": round(self.starved_ms, 3),
            "engine/prefill_chunks": float(self.prefill_chunks),
            "engine/prefill_cols_skipped": float(self.prefill_cols_skipped),
            "engine/prefill_flops_saved": float(self.prefill_flops_saved),
            "engine/prefix_hit_rate": round(self.prefix_hit_rate, 4),
            "engine/prefix_blocks_saved": float(self.prefix_blocks_saved),
            "engine/spec_draft_len_p50": round(self.spec_draft_len_p50, 4),
            "engine/spec_accept_rate": round(self.spec_accept_rate, 4),
            "engine/spec_tokens_per_step": round(
                self.spec_tokens_per_step, 4
            ),
        }


class ContinuousBatchingEngine:
    """Slot-admission decode over a paged KV cache.

    :param apply_fn: the model forward —
        ``apply_fn(params, input_ids, attention_mask, position_ids,
        cache, cache_index[, last_only]) -> {"logits", "cache"
        [, "values"]}`` (the same contract ``make_sampler`` consumes).
    :param init_cache_fn: ``(batch, capacity) -> linear KV buffers``
        (the family's ``init_cache``; the engine adds block tables).
    :param gen_config: generation parameters; the engine always samples
        per-row (``per_row_rng`` is forced on).
    :param num_slots: decode-slot pool size B.
    :param admit_width: static admission batch width (padded with dummy
        rows; one compiled prefill shape).
    :param harvest_width: completed rollouts per harvest group — the
        chunk size downstream consumers compile at. Must be <= num_slots.
    :param block_size: requested paged-KV block size (shrunk to divide
        Q + max_new_tokens).
    :param done_poll_interval: fetch the [B] ``done`` flags every k-th
        decode step (flags are sticky, so the latest fetch is exact);
        k=1 — the default — reproduces the poll-every-step loop
        bitwise, k>1 amortizes the host round-trip over k dispatches at
        the cost of up to k-1 idle steps per finished slot.
    :param mesh / param_shardings / cache_sharding: optional GSPMD
        pinning; ``cache_sharding`` shards the capacity axis (sp).
    :param prefix_pool_blocks: size (in blocks) of the cross-request
        shared-prefix KV pool (``ops/kv_cache.py``; managed by
        :class:`trlx_tpu.serving.prefix_cache.PrefixBlockPool`). 0 — the
        default, and the trainer collect path — disables sharing and
        keeps every jitted program byte-identical to the pool-less
        engine.
    :param stream_taps: make ``decode_step`` additionally return this
        step's (token, live) vectors so the host can stream tokens into
        per-request queues (:mod:`trlx_tpu.serving.streaming`) the step
        they are produced instead of at harvest. Off (the default) keeps
        the trainer-path program unchanged.
    :param prefill_chunk: chunked-prefill width in prompt columns
        (``rollout.prefill_chunk``; rounded by
        :func:`~trlx_tpu.ops.kv_cache.choose_prefill_chunk` to a
        block-aligned divisor of Q). ``> 0`` replaces the monolithic
        admission prefill with a host loop over block-aligned
        prompt-column chunks, one ``prefill_chunk`` dispatch each, that
        SKIPS the chunks no row in the admit group needs — leading
        all-pad columns of left-padded prompts (the mirror of PR-3's
        segmented decode early-exit: compute scales with
        ``ceil(max_real_len/chunk)`` instead of Q) and blocks served
        read-only from the shared-prefix pool (prefix sharing becomes a
        prefill-FLOP win, not just an HBM one). Chunk forwards attend a
        prompt-wide (Q) cache view instead of the full Q+R capacity —
        masked decode-region columns carry exactly-zero softmax weight,
        so the narrowing is bitwise-safe and the chunked prefill is
        token/mask-identical to the monolithic program (logprobs/values
        at the established bf16 resolution). 0 — the default, and the
        trainer collect path unless configured — keeps the monolithic
        program byte-identical. ``InferenceServer`` never passes 0: where
        the user set none it passes
        :func:`~trlx_tpu.ops.kv_cache.serving_prefill_chunk` of Q and a
        budget of one.
    :param prefill_chunks_per_pump: with ``prefill_chunk > 0``, bound
        how many chunk forwards one :meth:`pump` iteration dispatches
        (Sarathi-style stall-free admission): a large admission burst
        spreads its prefill across pump iterations, each followed by a
        decode step for the already-running slots, instead of stalling
        decode for the whole burst. 0 = unbounded (a group's whole
        prefill dispatches in one pump, as the monolithic path does).
        :meth:`drive` (the trainer collect loop) always completes an
        admission inline regardless.
    :param prefill_min_skip_share: with ``prefill_chunk > 0``, a group
        that can skip less than this share of its chunks is dispatched
        as the one monolithic ``prefill`` instead of in chunks: chunking
        pays through the columns it skips, and such a group's forwards
        would hold the admission path for most of ``Q / chunk``
        iterations (the requests behind it wait, its own first token
        comes that much later) and pay what a forward costs whatever
        its columns (the gather of the group's prompt-wide view, the
        launch of every layer) each time. Same tokens and masks either
        way. 0 — the default — never: every group goes in chunks. ``InferenceServer`` passes
        :data:`~trlx_tpu.ops.kv_cache.SERVING_PREFILL_MIN_SKIP_SHARE`
        where it derives the chunk itself.
    :param spec_max_draft: speculative decoding (``rollout.spec_decode``,
        docs/inference.md): ``> 0`` adds a jitted ``verify_step`` program
        that forwards each slot's anchor sample plus up to this many
        host-drafted tokens in ONE pass and accepts the longest prefix
        where the target sample equals the draft — bitwise the one-token
        loop's tokens under the per-row RNG contract
        (``ops/sampling.py::accept_drafts``). Rows with no draft ride
        through with ``draft_len 0`` (anchor-only — exactly a decode
        step), and a round where nothing drafted dispatches the plain
        ``decode_step``. Forces :attr:`stream_taps` on: the host drafter
        needs per-step token visibility to keep its histories. 0 — the
        default, and every pre-existing path — builds no verify program
        and keeps all other programs byte-identical.
    :param spec_drafter: host-side drafter
        (:mod:`trlx_tpu.serving.spec_drafter` API: ``observe_context`` /
        ``observe_tokens`` / ``observe_accept`` / ``draft`` / ``forget``).
        ``None`` with ``spec_max_draft > 0`` builds the n-gram
        self-lookup drafter; the serving tier passes the trie drafter
        bound to its shared-prefix pool.
    :param spec_min_accept_ewma: accept-rate floor handed to the default
        drafter — a row/tenant whose acceptance EWMA falls below it
        stops drafting (graceful per-slot degrade to one-token decode,
        never an abort).
    """

    def __init__(
        self,
        *,
        apply_fn: Callable,
        init_cache_fn: Callable,
        gen_config: GenerationConfig,
        query_length: int,
        vocab_size: int,
        num_slots: int,
        admit_width: int = 0,
        harvest_width: int = 0,
        block_size: int = 16,
        done_poll_interval: int = 1,
        mesh=None,
        param_shardings=None,
        cache_sharding=None,
        with_values: bool = True,
        prefix_pool_blocks: int = 0,
        stream_taps: bool = False,
        prefill_chunk: int = 0,
        prefill_chunks_per_pump: int = 0,
        prefill_min_skip_share: float = 0.0,
        spec_max_draft: int = 0,
        spec_drafter=None,
        spec_min_accept_ewma: float = 0.0,
    ):
        self.gen_config = dataclasses.replace(gen_config, per_row_rng=True)
        self.Q = int(query_length)
        self.R = int(self.gen_config.max_new_tokens)
        self.capacity = self.Q + self.R
        self.vocab_size = int(vocab_size)
        self.num_slots = int(num_slots)
        self.block_size = choose_block_size(self.capacity, block_size)
        self.n_blocks = self.capacity // self.block_size
        self.prefix_pool_blocks = int(prefix_pool_blocks)
        if spec_max_draft < 0:
            raise ValueError(
                f"spec_max_draft={spec_max_draft} must be >= 0 (0 "
                "disables speculative decoding)"
            )
        # the verify window is draft + anchor; a draft wider than R-1
        # could never be fully accepted (per-position budget guard), so
        # shrink silently like choose_block_size does
        self.spec_max_draft = min(int(spec_max_draft), max(0, self.R - 1))
        self.spec_min_accept_ewma = float(spec_min_accept_ewma)
        self.spec_drafter = spec_drafter
        if self.spec_max_draft > 0 and self.spec_drafter is None:
            from trlx_tpu.serving.spec_drafter import NGramDrafter

            self.spec_drafter = NGramDrafter(
                max_draft=self.spec_max_draft,
                min_accept_ewma=self.spec_min_accept_ewma,
            )
        # spec decode needs per-step token visibility host-side (drafter
        # histories), which is exactly the streaming tap
        self.stream_taps = bool(stream_taps) or self.spec_max_draft > 0
        self.prefill_chunk = choose_prefill_chunk(
            self.Q, int(prefill_chunk), self.block_size
        )
        self.n_prefill_chunks = (
            self.Q // self.prefill_chunk if self.prefill_chunk else 0
        )
        self.prefill_chunks_per_pump = int(prefill_chunks_per_pump)
        if self.prefill_chunks_per_pump < 0:
            raise ValueError(
                f"prefill_chunks_per_pump={prefill_chunks_per_pump} "
                "must be >= 0 (0 = unbounded)"
            )
        if self.prefill_chunks_per_pump and not self.prefill_chunk:
            raise ValueError(
                "prefill_chunks_per_pump needs chunked prefill "
                "(prefill_chunk > 0) — there is nothing to budget on the "
                "monolithic program"
            )
        self.prefill_min_skip_share = float(prefill_min_skip_share)
        if not 0.0 <= self.prefill_min_skip_share <= 1.0 or (
            self.prefill_min_skip_share and not self.prefill_chunk
        ):
            raise ValueError(
                f"prefill_min_skip_share={prefill_min_skip_share} must "
                "lie in [0, 1] and needs chunked prefill (prefill_chunk "
                "> 0): without it every group already takes the "
                "monolithic program"
            )
        #: host callback ``{row: token_id} -> None`` fired per decode
        #: step with the step's live emissions (requires stream_taps)
        self.token_sink: Optional[Callable[[Dict[int, int]], None]] = None
        self.with_values = with_values
        self.done_poll_interval = int(done_poll_interval)
        if self.done_poll_interval < 1:
            raise ValueError(
                f"done_poll_interval={done_poll_interval} must be >= 1"
            )
        self._apply_fn = apply_fn
        self._init_cache_fn = init_cache_fn
        self.mesh = mesh
        shard = 1
        if mesh is not None:
            shape = dict(mesh.shape)
            shard = shape.get("dp", 1) * shape.get("fsdp", 1)
        self._shard = shard

        def round_up(n: int) -> int:
            return max(shard, ((n + shard - 1) // shard) * shard)

        self.admit_width = round_up(
            admit_width or max(1, self.num_slots // 4)
        )
        self.admit_width = min(self.admit_width, round_up(self.num_slots))
        self.harvest_width = round_up(harvest_width or self.admit_width)
        if self.harvest_width > self.num_slots:
            raise ValueError(
                f"harvest_width={self.harvest_width} cannot exceed "
                f"num_slots={self.num_slots} (a harvest group must fit "
                "in the pool or the drain deadlocks)"
            )
        if self.num_slots % shard:
            raise ValueError(
                f"num_slots={self.num_slots} must divide over the "
                f"{shard} data shards of the mesh"
            )

        fn_params = inspect.signature(apply_fn).parameters
        self._prefill_kwargs = (
            {"last_only": True} if "last_only" in fn_params else {}
        )
        self._param_shardings = param_shardings
        self._cache_sharding = cache_sharding
        self._latent_pinned_share: Optional[float] = None  # read in init_state
        self._block_bitcast_share: Optional[float] = None  # read in _make_state
        self._cache_gb = self._measure_cache()
        self._pads_a_pool = jax.eval_shape(self._held_cache) != jax.eval_shape(
            lambda: self._init_cache_fn(self.num_slots, self.capacity)
        )
        self._live_read_layer = self._first_live_read_layer()
        self._build_programs()

        # host bookkeeping (reset per phase)
        self._state: Optional[EngineState] = None
        self._params = None
        self._phase_key = None
        # queue entries: (ids, mask, row, shared_map|None,
        #                 publish_map|None, release)
        self._queue: List[Tuple] = []
        self._free: List[int] = []
        self._busy_rows: Dict[int, int] = {}  # slot -> row index
        self._done_slots: List[int] = []
        # chunked prefill: the admission group currently mid-prefill
        # (slots reserved, some chunks dispatched) — the serving pump
        # advances it by at most ``prefill_chunks_per_pump`` chunk
        # forwards per iteration; drive() completes it inline
        self._inflight_admission: Optional[Dict[str, Any]] = None
        self._chunk_flops: Optional[float] = None  # lazy exact per-chunk cost
        # spec decode: the next step's prefetched (draft, lens) host
        # arrays — invalidated by a weight push, an admission, or a
        # harvest (anything that changes what the pool is decoding)
        self._staged_drafts: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._recycle_counts = np.zeros(self.num_slots, np.int64)
        self._next_row = 0
        # behavior-policy versioning (async actor–learner): every slot
        # records the params version it was admitted under; push_weights
        # stages a refresh that the drive loop applies at its safe point
        self.param_version = 0
        self._slot_versions = np.zeros(self.num_slots, np.int64)
        # staged (params, version) swapped as ONE reference under
        # _push_lock: push_weights arrives from the learner thread while
        # the drive thread's safe point applies it and
        # min_inflight_version reads it — staging two separate fields
        # can be observed torn (new params, old version tag), which
        # mis-tags every row admitted before the safe point
        self._pending_push: Optional[Tuple[Any, int]] = None
        self._push_lock = threading.Lock()
        self._steps_since_poll = 0
        # the decode step that is running or queued and whose outputs
        # nobody has read yet (None: the pipeline is empty), and the
        # count of dispatches entered: a held step whose ``seq`` is still
        # that count is the newest program, and its fetch drains the chip
        self._held: Optional[HeldStep] = None
        self._dispatches = 0
        self._last_refill = -1  # that count at the newest harvest
        #: admission forwards (whole or chunk) dispatched ahead of the
        #: newest step read: where it grew, the step this iteration
        #: waited for ran behind a forward (``serve/admit_pump_ms``);
        #: ``wholes_waited`` likewise for a group forwarded whole, many
        #: times a chunk's length (``host-stall`` keeps the two apart)
        self.forwards_waited = 0
        self.wholes_waited = 0
        # the starved ledger's open episode (EngineStats.starved_by_ms
        # holds the closed ones): when the chip was seen drained (None:
        # it is fed, or the host runs ahead of it), the part of the loop
        # the host is in (None: charged to nobody), the episode's parts
        self._drained_at: Optional[float] = None
        self._starved_part: Optional[str] = None
        self._episode = dict.fromkeys(STARVED_PARTS, 0.0)
        #: host callback fired with the admitted rows' indices right
        #: after each prefill dispatch — the serving tier marks newly
        #: published prefix blocks ready for later admission groups here
        #: (dispatch order guarantees the device writes land first)
        self._admit_listener: Optional[Callable[[List[int]], None]] = None
        self.stats = self._new_stats()
        # per-request latency bookkeeping (docs/observability.md,
        # "Serving metrics"): submit/admit/prefill/complete marks on the
        # shared telemetry clock, popped by the serving layer into its
        # latency histograms. Host dispatch timing — on an accelerator
        # the prefill mark is the dispatch wall, not device occupancy.
        self._req_times: Dict[int, Dict[str, float]] = {}
        #: request tracing (telemetry/request_trace.py): with this on,
        #: the host loop additionally logs one (dispatch time, admission
        #: epoch) pair per decode step and stamps done-poll marks, so
        #: the serving tier can emit per-request decode-cadence spans —
        #: the decode-step gap structure is the device-occupancy bound
        #: the dispatch-wall spans cannot give. Off (the default, and
        #: the trainer collect path) adds zero host work per step.
        self.trace_requests = False
        # the cadence log is PRUNED as rows harvest (entries below every
        # in-flight row's admit window drop; _step_base keeps the marks'
        # absolute indices valid) — a long-lived server's memory stays
        # bounded by its in-flight window, not its lifetime
        self._step_log: List[Tuple[float, int]] = []
        self._step_base = 0

    # ------------------------- jitted programs ------------------------- #

    def _new_stats(self) -> EngineStats:
        return EngineStats(
            num_slots=self.num_slots,
            col_flops=lambda: self._chunk_flop_cost() / self.prefill_chunk,
        )

    def init_state(self) -> EngineState:
        """Fresh all-idle pool, committed to the engine's shardings.
        Idle slots are ``active=False, finished=True``:
        ``choose_tokens`` then emits deterministic (pad, 0, 0.0, 0.0)
        for them and their output/cache writes hit the out-of-bounds
        discard sentinel."""
        state = self._make_state()
        if self.mesh is not None:
            state = jax.device_put(state, self.state_sharding())
        self._measure_pinned(state)
        return state

    def _measure_pinned(self, state: EngineState) -> None:
        """Gauge ``cache/latent_pinned_share``: the share of the latent
        pools' bytes that ``state`` holds as its programs compute on them,
        read off the arrays as they lie: rows of whole lanes
        (``ops/kv_cache.py::held_row_width``) and, of the axes that are
        longer than one, the row minor-most on the device and the positions
        next. Nothing where there is no latent pool."""
        held = total = 0
        for layer in state.cache:
            if not cache_kind(layer).latent:
                continue
            pool = layer["k"]
            total += pool.nbytes
            order = [a for a in pool.format.layout.major_to_minor if pool.shape[a] > 1]
            if held_row_width(layer) == pool.shape[-1] and order[-2:] == [1, 3]:
                held += pool.nbytes
        self._latent_pinned_share = held / total if total else None
        self._publish_cache_gauges(self._cache_gb)

    def _measure_cache(self) -> Dict[str, float]:
        """What the pool will hold (GB; gauges ``cache/state_gb``,
        ``cache/kv_gb``, ``cache/tail_gb`` and ``cache/latent_gb``), from
        the shapes the model asks for (logical bytes: the state holds a
        latent row padded to whole lanes, ``_make_state``, 576 -> 640
        values, a ninth more): a state layer's rows, the pools of keys and values,
        the rows a layer of keys keeps a slot beside them
        (``cache_kind(...).tail``) and the pools of latent rows
        (``.latent``); and the refusals they bring. By-slot rows cannot be
        rolled back to a rejected draft's column or shared by prefix, and
        the pp runner carries KV layers only; a latent row has no value
        pool for ``with_pool`` to mirror, no rollback under ``verify_step``
        and no head axis for a ``tp`` mesh, and its experts' routing is
        built off an ``ep`` mesh only."""
        linear = jax.eval_shape(lambda: self._init_cache_fn(self.num_slots, self.capacity))
        gb = {"state": 0.0, "kv": 0.0, "tail": 0.0, "latent": 0.0}
        for layer in linear:
            kind = cache_kind(layer)
            for k, v in layer.items():
                key = (
                    "state" if kind.layout == STATE
                    else "tail" if k in kind.tail
                    else "latent" if kind.latent
                    else "kv"
                )
                gb[key] += v.size * v.dtype.itemsize / 1e9
        axes = dict(self.mesh.shape) if self.mesh is not None else {}
        by_slot, latent = bool(gb["state"] or gb["tail"]), bool(gb["latent"])
        for what, on in (
            ("prefix_pool_blocks > 0 (a shared prefix of states)", by_slot and self.prefix_pool_blocks > 0),
            ("a speculative drafter / verify_step (a state snapshot)", by_slot and self.spec_max_draft > 0),
            ("a pp mesh", by_slot and axes.get("pp", 1) > 1),
        ):
            if on:
                raise ValueError(
                    f"{what} is not built for a model with state layers "
                    "or a tail beside its keys (ops/kv_cache.py: rows kept a slot)"
                )
        for what, on in (
            ("prefix_pool_blocks > 0 (a shared pool of latent rows)", self.prefix_pool_blocks > 0),
            ("a speculative drafter / verify_step (a latent row's rollback)", self.spec_max_draft > 0),
            *((f"a {axis} mesh", axes.get(axis, 1) > 1) for axis in ("tp", "ep", "pp")),
        ):
            if latent and on:
                raise ValueError(
                    f"{what} is not built for a latent cache (ops/kv_cache.py: "
                    "one row a position, no values)"
                )
        self._publish_cache_gauges(gb)
        return gb

    def _publish_cache_gauges(self, gb: Dict[str, float]) -> None:
        registry = telemetry.get_metrics()
        for key, value in gb.items():
            registry.gauge(f"cache/{key}_gb").set(value)
        if self._latent_pinned_share is not None:
            registry.gauge("cache/latent_pinned_share").set(self._latent_pinned_share)
        if self._block_bitcast_share is not None:
            registry.gauge("cache/block_write_bitcast_share").set(self._block_bitcast_share)

    def _held_cache(self):
        """The model's cache with every pool as its holder keeps it across
        programs (``ops/kv_cache.py::hold_pool``): a latent pool's rows
        padded to whole lanes, or each program copies the pool in and out;
        a head of several lane rows as those rows, or each admission
        re-tiles the pool around its block write."""
        return tuple(
            hold_pool(layer) for layer in self._init_cache_fn(self.num_slots, self.capacity)
        )

    def _first_live_read_layer(self) -> Optional[int]:
        """The first layer whose pool the decode step reads by its live
        chunks (``ops/kv_cache.py::reads_live_chunks``, the rule
        ``decode_attention`` dispatches its ``paged`` read by, asked of the
        pool as held and the head as the model makes it), ``None`` where no
        layer does, where a shared-prefix overlay keeps every read on the
        logical view, and on a mesh of several devices, whose programs keep
        the whole read (XLA partitions no Mosaic kernel)."""
        if self.prefix_pool_blocks > 0 or (self.mesh is not None and self.mesh.size > 1):
            return None
        made = jax.eval_shape(lambda: self._init_cache_fn(self.num_slots, self.capacity))
        tables = jax.ShapeDtypeStruct((self.num_slots, self.n_blocks), jnp.int32)
        for i, (layer, held) in enumerate(zip(made, jax.eval_shape(self._held_cache))):
            if "k" in layer and reads_live_chunks(
                dict(held, block_tables=tables), layer["k"].shape[-1]
            ):
                return i
        return None

    def _measure_block_bitcast(self, cache) -> None:
        """Gauge ``cache/block_write_bitcast_share``: of the layers of
        ``cache`` (as held) that keep keys and values, the share whose
        pool the admission's block write views by blocks without moving it
        (``ops/kv_cache.py::block_view_is_bitcast``: read off the held
        shape, the predicate ``hold_pool`` holds a pool by). Nothing where
        no layer keeps keys and values."""
        kinds = [(layer, cache_kind(layer)) for layer in cache]
        votes = [
            block_view_is_bitcast(layer)
            for layer, kind in kinds
            if kind.layout != STATE and not kind.latent
        ]
        self._block_bitcast_share = sum(votes) / len(votes) if votes else None

    def _make_state(self) -> EngineState:
        B, Q, R, V = self.num_slots, self.Q, self.R, self.vocab_size
        cfg = self.gen_config
        # a pool held at another shape than the model's is built in one
        # program, so that the zeros it is made from never lie beside it
        linear = jax.jit(self._held_cache)() if self._pads_a_pool else self._held_cache()
        self._measure_block_bitcast(linear)
        tables = identity_block_tables(B, self.n_blocks)
        # one table array PER layer (logically shared, physically
        # distinct): the jitted programs donate the whole state, and XLA
        # refuses to donate one buffer appearing as several arguments.
        # A state layer has no positions to indirect and gets none.
        cache = tuple(
            layer
            if cache_kind(layer).layout == STATE
            else dict(layer, block_tables=jnp.array(tables))
            for layer in linear
        )
        if self.prefix_pool_blocks > 0:
            def with_pool(layer):
                kv = layer["k"]
                pool = init_shared_pool(
                    self.prefix_pool_blocks,
                    self.block_size,
                    kv.shape[2],
                    kv.shape[3],
                    kv.dtype,
                    "int8" if cache_kind(layer).quantized else "bfloat16",
                )
                return dict(
                    layer,
                    **pool,
                    shared_tables=empty_share_tables(B, self.n_blocks),
                    publish_tables=empty_share_tables(B, self.n_blocks),
                )

            cache = tuple(with_pool(layer) for layer in cache)
        return EngineState(
            cache=cache,
            row_keys=jnp.zeros((B, 2), jnp.uint32),
            t=jnp.zeros((B,), jnp.int32),
            n_real=jnp.zeros((B,), jnp.int32),
            logits_last=jnp.zeros((B, V), jnp.float32),
            value_last=jnp.zeros((B,), jnp.float32),
            active=jnp.zeros((B,), bool),
            finished=jnp.ones((B,), bool),
            out_tokens=jnp.full((B, R), cfg.pad_token_id, jnp.int32),
            out_mask=jnp.zeros((B, R), jnp.int32),
            out_logprobs=jnp.zeros((B, R), jnp.float32),
            out_values=jnp.zeros((B, R), jnp.float32),
            query_ids=jnp.zeros((B, Q), jnp.int32),
            query_mask=jnp.zeros((B, Q), jnp.int32),
            row_index=jnp.full((B,), -1, jnp.int32),
        )

    def state_sharding(self):
        """Sharding pytree for :class:`EngineState`: slot axis over
        dp×fsdp everywhere; cache K/V capacity axis additionally over sp
        when a ``cache_sharding`` was given; the shared-prefix pool (no
        slot axis — a broadcast structure every data shard reads)
        replicates."""
        from trlx_tpu.parallel.mesh import batch_sharding, replicated

        batch_sh = batch_sharding(self.mesh)
        cache_sh = self._cache_sharding or batch_sh
        rep = replicated(self.mesh)

        def layer_sharding(layer: Dict[str, Any]) -> Dict[str, Any]:
            # rows kept a slot have no capacity axis for sp to shard: the
            # slot axis, as the rest
            by_slot = cache_kind(layer).tail
            return {
                k: (
                    rep
                    if k in SHARED_POOL_KEYS
                    else (cache_sh if v.ndim == 4 and k not in by_slot else batch_sh)
                )
                for k, v in layer.items()
            }

        def pick(state: EngineState):
            cache = tuple(layer_sharding(l) for l in state.cache)
            other = {
                f.name: batch_sh
                for f in dataclasses.fields(EngineState)
                if f.name != "cache"
            }
            return EngineState(cache=cache, **other)

        # build from an abstract state so no buffers materialize here
        return pick(jax.eval_shape(self._make_state))

    def _build_programs(self) -> None:
        cfg = self.gen_config
        Q, R, cap, B = self.Q, self.R, self.capacity, self.num_slots
        nb, bs = self.n_blocks, self.block_size
        apply_fn = self._apply_fn
        with_values = self.with_values
        prefill_kwargs = self._prefill_kwargs

        def pin_cache(cache):
            if self._cache_sharding is None:
                return cache
            sh = self._cache_sharding

            def pin_layer(layer):
                by_slot = cache_kind(layer).tail
                return {
                    k: (
                        jax.lax.with_sharding_constraint(v, sh)
                        if v.ndim == 4 and k not in by_slot
                        else v
                    )
                    for k, v in layer.items()
                }

            return tuple(pin_layer(layer) for layer in cache)

        sharing = self.prefix_pool_blocks > 0

        def group_cache(state, slot_ids, table_turns,
                        shared_map, publish_map):
            """The cache an admission forward is handed — shared by the
            monolithic prefill and every chunked-prefill call (one
            implementation, one parity surface). A paged layer goes
            WHOLE: its pools (and the shared-prefix pool) as they lie,
            with the group's freshly-rotated block tables, its
            share/publish maps and its slot ids under ``"slot_ids"``, so
            the forward scatters its columns at (slot, physical position)
            in the donated pool and gathers the group's view alone
            (``ops/kv_cache.py::paged_write_read``, ``cache_kind().rows``):
            no slice of the group's rows, no merge back, no copy of a
            pool. Recycled slots get a rotated table: physical block
            reuse order differs from logical order, so table resolution
            is exercised on every refill."""
            new_tables = (
                (jnp.arange(nb, dtype=jnp.int32)[None, :]
                 + table_turns[:, None])
                % nb
            )

            def group_layer(layer):
                kind = cache_kind(layer)
                # what is kept a slot (a state layer's rows, the tail beside
                # a layer's keys): the slots' rows as they stand. The model
                # starts a row from zeros where no valid column precedes
                # the call (ops/ssm.py::call_columns), so a recycled slot
                # never reads its predecessor's and a later chunk carries
                # on from the one before
                rows = {k: jnp.take(layer[k], slot_ids, axis=0) for k in kind.tail}
                if kind.layout == STATE:
                    return rows
                out = dict(layer, **rows, block_tables=new_tables, slot_ids=slot_ids)
                if sharing:
                    # the admitted rows' share/publish assignments; the
                    # recycled slots' stale ones are replaced on landing
                    out["shared_tables"] = shared_map
                    out["publish_tables"] = publish_map
                return out

            return tuple(group_layer(l) for l in state.cache)

        def land_group_cache(state, slot_ids, cache_out):
            """The state's cache after an admission forward: the pools as
            the forward left them, and the group's rows of everything
            kept a slot (block tables, share/publish maps, a state
            layer's rows, a tail) set at ``slot_ids`` (a dummy's drop)."""
            def land_layer(full, out):
                by_slot = cache_kind(full).tail

                def one(k):
                    if k in by_slot or k == "block_tables" or k in SHARE_TABLE_KEYS:
                        return (
                            full[k]
                            .at[slot_ids]
                            .set(out[k].astype(full[k].dtype), mode="drop")
                        )
                    return out[k]

                return {k: one(k) for k in full}

            return tuple(
                land_layer(f, o) for f, o in zip(state.cache, cache_out)
            )

        @jax.named_scope("prefill")
        def prefill(
            params,
            state: EngineState,
            slot_ids,  # [A] int32; num_slots = dummy (writes drop)
            prompt_ids,  # [A, Q] int32 left-padded
            prompt_mask,  # [A, Q] int32
            row_index,  # [A] int32 global draw index
            table_turns,  # [A] int32 block-table rotation per slot
            phase_key,  # [2] uint32
            shared_map=None,  # [A, nb] int32 pool block per logical
            publish_map=None,  # block (-1 = private / no publish)
        ) -> EngineState:
            A = prompt_ids.shape[0]
            row_keys = make_row_keys(phase_key, row_index)
            n_real = jnp.sum(prompt_mask, axis=-1).astype(jnp.int32)

            cache_in = group_cache(
                state, slot_ids, table_turns, shared_map, publish_map
            )
            cache_mask = concat_cols(
                prompt_mask, jnp.zeros((A, R), prompt_mask.dtype)
            )
            positions = jnp.clip(jnp.cumsum(prompt_mask, axis=-1) - 1, 0, None)
            out = apply_fn(
                params,
                prompt_ids,
                attention_mask=cache_mask,
                position_ids=positions,
                cache=cache_in,
                cache_index=0,
                **prefill_kwargs,
            )
            logits_last = out["logits"][:, -1].astype(jnp.float32)
            if with_values:
                value_last = out["values"][:, -1].astype(jnp.float32)
            else:
                value_last = jnp.zeros((A,), jnp.float32)
            if cfg.max_length > 0:
                finished0 = n_real >= cfg.max_length
            else:
                finished0 = jnp.zeros((A,), bool)

            new_cache = land_group_cache(state, slot_ids, out["cache"])

            def put(field, rows):
                return field.at[slot_ids].set(
                    rows.astype(field.dtype), mode="drop"
                )

            return dataclasses.replace(
                state,
                cache=pin_cache(new_cache),
                row_keys=put(state.row_keys, row_keys),
                t=put(state.t, jnp.zeros((A,), jnp.int32)),
                n_real=put(state.n_real, n_real),
                logits_last=put(state.logits_last, logits_last),
                value_last=put(state.value_last, value_last),
                active=put(state.active, jnp.ones((A,), bool)),
                finished=put(state.finished, finished0),
                out_tokens=put(
                    state.out_tokens,
                    jnp.full((A, R), cfg.pad_token_id, jnp.int32),
                ),
                out_mask=put(state.out_mask, jnp.zeros((A, R), jnp.int32)),
                out_logprobs=put(
                    state.out_logprobs, jnp.zeros((A, R), jnp.float32)
                ),
                out_values=put(
                    state.out_values, jnp.zeros((A, R), jnp.float32)
                ),
                query_ids=put(state.query_ids, prompt_ids),
                query_mask=put(state.query_mask, prompt_mask),
                row_index=put(state.row_index, row_index),
            )

        @jax.named_scope("decode_step")
        def decode_step(params, state: EngineState):
            """One token for every slot. Finished/idle slots ride along
            with deterministic pad emissions whose output and cache
            writes resolve out of bounds and drop: such a slot's
            ``cache_index`` is the capacity, the sentinel by which
            ``decode_attention`` knows a row nobody reads, so a layer that
            reads its pool by live chunks fetches none of that slot's and
            hands back zeros for it. Where some layer does, the step also
            polls the share of the pools' chunks it read
            (``attention/paged_chunks_read_share``)."""
            if cfg.min_new_tokens > 0 or cfg.min_length > 0:
                min_new = jnp.maximum(
                    cfg.min_new_tokens, cfg.min_length - state.n_real
                )
            else:
                min_new = None
            token, live, logprob, value_out, finished = choose_tokens(
                cfg,
                state.logits_last,
                state.t,
                state.finished,
                state.value_last,
                state.n_real,
                min_new=min_new,
                row_keys=state.row_keys,
            )
            rows = jnp.arange(B, dtype=jnp.int32)
            # emissions land at [slot, t] for live rows; non-live rows
            # write at R (out of bounds -> dropped)
            w = jnp.where(live == 1, state.t, R)
            out_tokens = state.out_tokens.at[rows, w].set(token, mode="drop")
            out_mask = state.out_mask.at[rows, w].set(live, mode="drop")
            out_logprobs = state.out_logprobs.at[rows, w].set(
                logprob, mode="drop"
            )
            out_values = state.out_values.at[rows, w].set(
                value_out, mode="drop"
            )

            # forward the sampled token at per-row cache slot Q + t;
            # non-live rows write at capacity (dropped by the paged
            # cache's OOB sentinel)
            slot_pos = jnp.arange(cap)[None, :]
            cache_mask_t = (
                slot_pos <= Q + state.t[:, None]
            ).astype(jnp.int32) * concat_cols(
                state.query_mask, jnp.ones((B, R), state.query_mask.dtype)
            )
            cache_index = jnp.where(live == 1, Q + state.t, cap)
            cache, chunks_read = state.cache, {}
            if self._live_read_layer is not None:
                # some layer reads its pool by live chunks: every paged
                # layer's table is the same array by value (an admission
                # sets the group's rows in all of them), so the step reads
                # one, and what each layer derives from tables and mask
                # (the bias in stored order, the chunk lists) is derived
                # once; the state keeps its own array a layer
                layer = state.cache[self._live_read_layer]
                tables = layer["block_tables"]
                cache = tuple(
                    dict(l, block_tables=tables) if "block_tables" in l else l
                    for l in state.cache
                )
                chunks_read["paged_chunks_read_share"] = live_chunks(
                    stored_order_bias(tables, padding_bias(cache_mask_t)),
                    cache_index, live_chunk_positions(layer), NEG_INF / 2,
                ).share
            out = apply_fn(
                params,
                token[:, None],
                attention_mask=cache_mask_t,
                position_ids=(state.n_real + state.t)[:, None],
                cache=cache,
                cache_index=cache_index,
            )
            if self._live_read_layer is not None:
                out["cache"] = tuple(
                    dict(new, block_tables=old["block_tables"]) if "block_tables" in old else new
                    for new, old in zip(out["cache"], state.cache)
                )
            new_logits = out["logits"][:, 0].astype(jnp.float32)
            new_value = (
                out["values"][:, 0].astype(jnp.float32)
                if with_values
                else jnp.zeros((B,), jnp.float32)
            )
            t_next = jnp.where(live == 1, state.t + 1, state.t)
            done = state.active & (finished | (t_next >= R))
            new_state = dataclasses.replace(
                state,
                cache=pin_cache(out["cache"]),
                t=t_next,
                logits_last=new_logits,
                value_last=new_value,
                finished=finished,
                out_tokens=out_tokens,
                out_mask=out_mask,
                out_logprobs=out_logprobs,
                out_values=out_values,
            )
            # what the host polls: the done flags and, from a routed
            # family, the step's routing statistics (device scalars in the
            # same fetch: no wait of their own)
            polled = {"done": done, **out.get("moe_stats", {}), **chunks_read}
            if self.stream_taps:
                # streaming decode: this step's emissions come home with
                # the done flags so the host can route tokens the step
                # they exist instead of at harvest (TTFT decouples from
                # harvest-group completion)
                return new_state, polled, token, live
            return new_state, polled

        def refill(state: EngineState, slot_ids):
            """Harvest ``slot_ids``'s finished rollouts and free the
            slots (the admission queue prefills them next poll)."""
            outs = {
                "query_tokens": jnp.take(state.query_ids, slot_ids, axis=0),
                "query_mask": jnp.take(state.query_mask, slot_ids, axis=0),
                "tokens": jnp.take(state.out_tokens, slot_ids, axis=0),
                "response_mask": jnp.take(state.out_mask, slot_ids, axis=0),
                "logprobs": jnp.take(state.out_logprobs, slot_ids, axis=0),
                "values": jnp.take(state.out_values, slot_ids, axis=0),
                "row_index": jnp.take(state.row_index, slot_ids, axis=0),
            }
            active = state.active.at[slot_ids].set(False, mode="drop")
            return dataclasses.replace(state, active=active), outs

        def release(state: EngineState, slot_ids):
            """Force-finish ``slot_ids`` right after admission: the next
            decode step emits the deterministic pad for them and flags
            them done, so a padding placeholder costs ONE decode step
            instead of decoding its full token budget (the serving
            tier's partial-harvest-group fix, docs/serving.md)."""
            finished = state.finished.at[slot_ids].set(True, mode="drop")
            return dataclasses.replace(state, finished=finished)

        # ------------- speculative verify (rollout.spec_decode) ------------ #
        D = self.spec_max_draft

        @jax.named_scope("decode_step")
        def verify_step(params, state: EngineState, draft, draft_len):
            """Drafted multi-token decode: sample each slot's anchor
            token from the carried logits (always the correct next token
            — all-rejected still commits it), forward the anchor plus up
            to D host-drafted tokens in ONE pass through the paged
            cache, and accept the longest draft prefix where the target
            sample equals the draft (``accept_drafts`` — bitwise the
            one-token loop's tokens under the per-row keys). Accepted
            emissions land exactly where sequential decode would put
            them; rejected/beyond-draft columns write at the per-column
            OOB sentinel and their outputs are never read (garbage KV
            above the accept frontier is either causally masked to
            exactly-zero softmax weight or overwritten by a later step's
            scatter before its first unmasked read). The carried
            logits/value are re-anchored at the LAST accepted column, so
            verify and decode steps mix freely over the same state."""
            if cfg.min_new_tokens > 0 or cfg.min_length > 0:
                min_new = jnp.maximum(
                    cfg.min_new_tokens, cfg.min_length - state.n_real
                )
            else:
                min_new = None
            token0, live0, lp0, v0, fin1 = choose_tokens(
                cfg,
                state.logits_last,
                state.t,
                state.finished,
                state.value_last,
                state.n_real,
                min_new=min_new,
                row_keys=state.row_keys,
            )
            T = D + 1
            col = jnp.arange(T, dtype=jnp.int32)[None, :]
            inputs = concat_cols(token0[:, None], draft)
            # per-column cache targets: anchor + valid draft columns land
            # at Q+t+j, everything else at capacity (per-column OOB drop
            # — the idle-slot sentinel applied columnwise)
            write_pos = jnp.where(
                (live0 == 1)[:, None] & (col <= draft_len[:, None]),
                Q + state.t[:, None] + col,
                cap,
            )
            slot_pos = jnp.arange(cap)[None, :]
            # window-wide validity mask: the causal bias (base column =
            # write_pos[:, 0]) narrows each query j to <= Q+t+j, and the
            # extra columns it excludes carry exactly-zero softmax
            # weight — bitwise the one-token step's attention per query
            cache_mask_t = (
                slot_pos <= Q + state.t[:, None] + D
            ).astype(jnp.int32) * concat_cols(
                state.query_mask, jnp.ones((B, R), state.query_mask.dtype)
            )
            out = apply_fn(
                params,
                inputs,
                attention_mask=cache_mask_t,
                position_ids=(state.n_real + state.t)[:, None] + col,
                cache=state.cache,
                cache_index=write_pos,
            )
            logits_seq = out["logits"].astype(jnp.float32)
            values_seq = (
                out["values"].astype(jnp.float32)
                if with_values
                else jnp.zeros((B, T), jnp.float32)
            )
            d_toks, d_acc, d_lps, d_vals, n_acc, fin = accept_drafts(
                cfg,
                logits_seq[:, :-1],
                values_seq[:, :-1],
                state.t,
                fin1,
                live0 == 1,
                state.n_real,
                draft,
                draft_len,
                state.row_keys,
                min_new=min_new,
                budget=R,
            )
            tokens_bt = concat_cols(token0[:, None], d_toks)
            acc_bt = concat_cols(live0[:, None], d_acc)
            lps_bt = concat_cols(lp0[:, None], d_lps)
            vals_bt = concat_cols(v0[:, None], d_vals)
            rows = jnp.arange(B, dtype=jnp.int32)[:, None]
            w = jnp.where(acc_bt == 1, state.t[:, None] + col, R)
            out_tokens = state.out_tokens.at[rows, w].set(
                tokens_bt, mode="drop"
            )
            out_mask = state.out_mask.at[rows, w].set(acc_bt, mode="drop")
            out_logprobs = state.out_logprobs.at[rows, w].set(
                lps_bt, mode="drop"
            )
            out_values = state.out_values.at[rows, w].set(
                vals_bt, mode="drop"
            )
            # re-anchor the decode invariant: logits/value at the last
            # accepted column predict the next un-emitted token
            new_logits = jnp.take_along_axis(
                logits_seq, n_acc[:, None, None], axis=1
            )[:, 0]
            new_value = jnp.take_along_axis(
                values_seq, n_acc[:, None], axis=1
            )[:, 0]
            t_next = state.t + live0 + n_acc
            done = state.active & (fin | (t_next >= R))
            new_state = dataclasses.replace(
                state,
                cache=pin_cache(out["cache"]),
                t=t_next,
                logits_last=new_logits,
                value_last=new_value,
                finished=fin,
                out_tokens=out_tokens,
                out_mask=out_mask,
                out_logprobs=out_logprobs,
                out_values=out_values,
            )
            return new_state, done, tokens_bt, acc_bt

        # ------------- chunked prefill (rollout.prefill_chunk) ------------- #
        W = self.prefill_chunk
        n_pc = self.n_prefill_chunks

        def chunk_cache(cache, c):
            """The group's cache as chunk ``c``'s forward is handed it:
            where a chunk is whole blocks, with the promise that a traced
            ``c * W`` cannot make itself, that the call's first column is
            the first of logical block ``c * (W // bs)``
            (``ops/kv_cache.py::writes_whole_blocks``)."""
            if W % bs:
                return cache
            return starting_at_block(cache, c * (W // bs))

        def seed_group(
            state, seed_ids, new_cache, prompt_ids, prompt_mask,
            row_index, phase_key, out,
        ) -> EngineState:
            """The state with ``new_cache`` and, for the rows of
            ``seed_ids`` (out of bounds: none), the slot fields of a
            group whose final chunk's forward gave ``out``."""
            A = prompt_ids.shape[0]
            row_keys = make_row_keys(phase_key, row_index)
            n_real = jnp.sum(prompt_mask, axis=-1).astype(jnp.int32)
            logits_last = out["logits"][:, -1].astype(jnp.float32)
            if with_values:
                value_last = out["values"][:, -1].astype(jnp.float32)
            else:
                value_last = jnp.zeros((A,), jnp.float32)
            if cfg.max_length > 0:
                finished0 = n_real >= cfg.max_length
            else:
                finished0 = jnp.zeros((A,), bool)

            def put(field, rows):
                return field.at[seed_ids].set(
                    rows.astype(field.dtype), mode="drop"
                )

            return dataclasses.replace(
                state,
                cache=pin_cache(new_cache),
                row_keys=put(state.row_keys, row_keys),
                t=put(state.t, jnp.zeros((A,), jnp.int32)),
                n_real=put(state.n_real, n_real),
                logits_last=put(state.logits_last, logits_last),
                value_last=put(state.value_last, value_last),
                active=put(state.active, jnp.ones((A,), bool)),
                finished=put(state.finished, finished0),
                out_tokens=put(
                    state.out_tokens,
                    jnp.full((A, R), cfg.pad_token_id, jnp.int32),
                ),
                out_mask=put(state.out_mask, jnp.zeros((A, R), jnp.int32)),
                out_logprobs=put(
                    state.out_logprobs, jnp.zeros((A, R), jnp.float32)
                ),
                out_values=put(
                    state.out_values, jnp.zeros((A, R), jnp.float32)
                ),
                query_ids=put(state.query_ids, prompt_ids),
                query_mask=put(state.query_mask, prompt_mask),
                row_index=put(state.row_index, row_index),
            )

        @jax.named_scope("prefill")
        def prefill_chunk(
            params,
            state: EngineState,
            slot_ids,
            prompt_ids,
            prompt_mask,
            row_index,
            table_turns,
            phase_key,
            c,  # int32 scalar: which chunk, the final one included
            shared_map=None,
            publish_map=None,
        ) -> EngineState:
            """Chunk ``c`` of the group's prompt columns, whichever it is:
            the one program of a chunked admission, which the host
            dispatches once for every chunk some row needs and once for
            the final chunk (``_advance_admission``). With LEFT-padded
            prompts the chunks nobody needs are the LEADING ones (all-pad
            columns before the group's longest row starts, blocks served
            read-only from the shared-prefix pool), so compute scales
            with ``ceil(max_real_len / W)``, not Q.

            Why it agrees with the monolithic ``prefill`` bitwise on
            tokens and masks (log-probabilities and values at the
            established bf16 resolution; tests/test_chunked_prefill.py):
            the forward is handed the PROMPT-WIDE mask (width Q, not the
            capacity) as its attention view (``ops/attention.py``'s
            mask-width contract). Prompt queries never attend the decode
            region, whose masked columns carry exactly-zero softmax
            weight in the monolithic program, so dropping them is exact
            and shrinks the static attention FLOPs from Q*(Q+R) to Q*Q
            before any chunk is skipped. A skipped chunk leaves its cache
            positions zero; every read of them is masked (pad) or
            overlaid from the shared pool, and a masked column's softmax
            weight underflows to exactly 0.0.

            Why a straight line, and no ``lax.scan`` of ``lax.cond``s
            over the chunks: inside a scan's ``while`` the chip's
            compiler converts float32 served weights to bf16 whole and
            holds 3.3x the temporaries, 47.7 ms a forward against 38.3
            in pythia-1.4b's serving cell (PERF.md section 6, PR 30).
            (Where every program copies its pools whole, as at gpt2's
            64-wide heads, a dispatch a chunk pays those copies a chunk
            and measured slower under ``drive()`` than a scan did: the
            copies are the fault there, PERF.md section 6, PR 52.)

            Why the final chunk is this program too: a server builds its
            programs before it takes traffic, and each costs 2-3 s of
            tracing there. The final chunk always runs (every
            left-padded row's last real column lives there) and seeds
            the group's slots from its last column's logits and value;
            any other chunk writes its KV alone: its seeds go to the
            out-of-bounds slot and drop, and its heads see one column a
            row (``last_only``)."""
            cache_in = group_cache(
                state, slot_ids, table_turns, shared_map, publish_map
            )
            positions = jnp.clip(
                jnp.cumsum(prompt_mask, axis=-1) - 1, 0, None
            )
            out = apply_fn(
                params,
                jax.lax.dynamic_slice_in_dim(prompt_ids, c * W, W, axis=1),
                attention_mask=prompt_mask,  # Q-wide view
                position_ids=jax.lax.dynamic_slice_in_dim(
                    positions, c * W, W, axis=1
                ),
                cache=chunk_cache(cache_in, c),
                cache_index=c * W,
                **prefill_kwargs,
            )
            return seed_group(
                state,
                jnp.where(c == n_pc - 1, slot_ids, self.num_slots),
                land_group_cache(state, slot_ids, out["cache"]),
                prompt_ids, prompt_mask, row_index, phase_key, out,
            )

        # what ``engine/prefill_block_write_share`` observes a dispatched
        # forward: the write's own predicate (``writes_whole_blocks``) on
        # each program's call, asked in shapes alone of the cache the
        # forward is handed (a chunk's index is traced and comes with the
        # chunk's promise; the whole forward's is a Python 0)
        A = self.admit_width
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731

        def handed(state, c, *group):
            cache = group_cache(state, *group)
            return cache, chunk_cache(cache, c)

        group_sds, chunk_sds = jax.eval_shape(
            handed, jax.eval_shape(self._make_state), i32(), i32(A), i32(A),
            *((i32(A, nb), i32(A, nb)) if sharing else (None, None)),
        )

        def block_write_share(cache, T, cache_index):
            """The share of the forward's paged layers that write their
            ``T`` columns by block; ``None`` without a paged layer."""
            votes = [
                writes_whole_blocks(
                    layer,
                    jax.ShapeDtypeStruct((A, T) + layer["k"].shape[2:], layer["k"].dtype),
                    cache_index,
                )
                for layer in cache
                if cache_kind(layer).layout == PAGED
            ]
            return sum(votes) / len(votes) if votes else None

        self._block_write_share = {"prefill": block_write_share(group_sds, Q, 0)}
        if W > 0:
            self._block_write_share["prefill_chunk"] = block_write_share(
                chunk_sds, W, i32()
            )

        self.prefill_chunk_jit = None
        if self.mesh is not None and self._param_shardings is not None:
            from trlx_tpu.parallel.mesh import (
                batch_sharding,
                replicated,
                traced_on,
            )

            # the programs that run a model forward over prompt columns
            # declare their mesh (long prompts route attention to the
            # flash kernels, which need it — parallel/mesh.py::traced_on)
            prefill = traced_on(self.mesh, prefill)
            prefill_chunk = traced_on(self.mesh, prefill_chunk)
            verify_step = traced_on(self.mesh, verify_step)
            # and the step, whose read of a paged pool by live chunks is a
            # Mosaic kernel on one device and the whole read on more
            decode_step = traced_on(self.mesh, decode_step)
            state_sh = self.state_sharding()
            batch_sh = batch_sharding(self.mesh)
            rep = replicated(self.mesh)
            prefill_in = [
                self._param_shardings,
                state_sh,
                rep,
                batch_sh,
                batch_sh,
                rep,
                rep,
                rep,
            ]
            if sharing:
                prefill_in += [rep, rep]  # shared_map, publish_map
            decode_out = (
                (state_sh, rep, rep, rep)
                if self.stream_taps
                else (state_sh, rep)
            )
            self.prefill_jit = jax.jit(
                prefill,
                in_shardings=tuple(prefill_in),
                out_shardings=state_sh,
                donate_argnums=(1,),
            )
            self.decode_step_jit = jax.jit(
                decode_step,
                in_shardings=(self._param_shardings, state_sh),
                out_shardings=decode_out,
                donate_argnums=(1,),
            )
            self.refill_jit = jax.jit(
                refill,
                in_shardings=(state_sh, rep),
                out_shardings=(state_sh, batch_sh),
                donate_argnums=(0,),
            )
            self.release_jit = jax.jit(
                release,
                in_shardings=(state_sh, rep),
                out_shardings=state_sh,
                donate_argnums=(0,),
            )
            if W > 0:
                # ``prefill``'s arguments, then the chunk's index
                # (replicated) ahead of the sharing maps
                self.prefill_chunk_jit = jax.jit(
                    prefill_chunk,
                    in_shardings=tuple(
                        prefill_in[:8] + [rep] + prefill_in[8:]
                    ),
                    out_shardings=state_sh,
                    donate_argnums=(1,),
                )
        else:
            self.prefill_jit = jax.jit(prefill, donate_argnums=(1,))
            self.decode_step_jit = jax.jit(decode_step, donate_argnums=(1,))
            self.refill_jit = jax.jit(refill, donate_argnums=(0,))
            self.release_jit = jax.jit(release, donate_argnums=(0,))
            if W > 0:
                self.prefill_chunk_jit = jax.jit(
                    prefill_chunk, donate_argnums=(1,)
                )

        self.verify_step_jit = None
        if D > 0:
            if self.mesh is not None and self._param_shardings is not None:
                from trlx_tpu.parallel.mesh import batch_sharding, replicated

                state_sh = self.state_sharding()
                batch_sh = batch_sharding(self.mesh)
                rep = replicated(self.mesh)
                self.verify_step_jit = jax.jit(
                    verify_step,
                    in_shardings=(
                        self._param_shardings,
                        state_sh,
                        batch_sh,
                        batch_sh,
                    ),
                    out_shardings=(state_sh, rep, rep, rep),
                    donate_argnums=(1,),
                )
            else:
                self.verify_step_jit = jax.jit(
                    verify_step, donate_argnums=(1,)
                )

    # --------------------------- host loop ----------------------------- #

    def start_phase(self, params, phase_key, row_start: int = 0) -> None:
        """Reset the pool for a new collect phase. ``params`` is the
        frozen behavior policy every prefill/decode of the phase runs on
        (under the streamed phase: the trainer's behavior snapshot);
        ``phase_key`` seeds the per-row keys; ``row_start`` offsets the
        global draw index (usually 0 per phase)."""
        self._params = params
        self._phase_key = jnp.asarray(phase_key, jnp.uint32)
        self._state = self.init_state()
        self._queue = []
        self._free = list(range(self.num_slots))
        self._busy_rows = {}
        self._done_slots = []
        self._inflight_admission = None
        self._staged_drafts = None
        if self.spec_drafter is not None and hasattr(
            self.spec_drafter, "reset"
        ):
            self.spec_drafter.reset()
        self._recycle_counts[:] = 0
        self._next_row = row_start
        self.param_version = 0
        self._slot_versions[:] = 0
        with sched_points.guard(self._push_lock, "engine.push_lock"):
            self._pending_push = None
        self._steps_since_poll = 0
        # a step of the pool that is gone: its rows went with it
        self._held = None
        self._dispatches = 0
        self._last_refill = -1
        self.forwards_waited = 0
        self.wholes_waited = 0
        self._drained_at = None
        self._starved_part = None
        self._episode = dict.fromkeys(STARVED_PARTS, 0.0)
        self.stats = self._new_stats()
        self.stats.param_gb = tree_gb(params)
        self._req_times = {}
        self._step_log = []
        self._step_base = 0

    def push_weights(self, params, version: Optional[int] = None) -> None:
        """Stage a refreshed behavior policy for in-flight application
        (PipelineRL-style mid-generation weight update). The swap itself
        happens at the drive loop's safe point — after harvest
        bookkeeping, before the next admission — NEVER here: a push
        landing between a harvest and its refill must not disturb the
        queued admit group or the freed-slot bookkeeping (the admission
        starvation edge pinned in tests/test_async_rl.py). Rows already
        decoding continue from their current position under the new
        params (their recorded per-token logprobs remain the true
        behavior logprobs — PPO's importance ratio corrects the rest);
        rows admitted after the swap are tagged with the new version.

        ``params`` must own its buffers (the learner's masters are
        donated by every train step — push a snapshot/copy, not the
        live tree). Consecutive pushes before the next safe point
        coalesce: only the newest params are ever applied.

        This is the engine's only cross-thread entry point: the staged
        (params, version) pair is one reference written under
        ``_push_lock`` so the drive thread can never observe new params
        with an old version tag."""
        sched_points.yield_point("engine.push")
        with sched_points.guard(self._push_lock, "engine.push_lock"):
            self._pending_push = (
                params,
                int(version) if version is not None
                else self.param_version + 1,
            )

    def _apply_pending_push(self) -> None:
        sched_points.yield_point("engine.safe_point")
        with sched_points.guard(self._push_lock, "engine.push_lock"):
            staged, self._pending_push = self._pending_push, None
            if staged is None:
                return
            self._params, self.param_version = staged
        # a weight push invalidates outstanding speculative drafts: the
        # next verify step's targets come from the refreshed params, so
        # prefetched proposals re-draft at the next step (drafts are
        # param-independent token guesses — dropping them affects accept
        # rate only, never correctness, but the invalidation keeps the
        # drafting overlap window inside one params version)
        self._staged_drafts = None
        self.stats.weight_pushes += 1
        self.stats.param_gb = tree_gb(self._params)

    def min_inflight_version(self) -> Optional[int]:
        """Oldest behavior version any not-yet-harvested work will carry:
        the min admission version over busy/done-awaiting-harvest slots,
        and — when prompts are still queued — the version they WILL be
        admitted under (the current one, or a staged push's). ``None``
        when nothing is in flight (the bounded-staleness guard is then
        vacuous)."""
        # the staged pair and the current version are read under the
        # push lock so a concurrent push_weights cannot be seen torn
        with sched_points.guard(self._push_lock, "engine.push_lock"):
            staged = self._pending_push
            current = self.param_version
        # _busy_rows covers decoding AND done-awaiting-harvest slots
        # (slots leave it only at harvest), so one pass covers both
        versions = [int(self._slot_versions[s]) for s in self._busy_rows]
        if self._queue:
            versions.append(staged[1] if staged is not None else current)
        return min(versions) if versions else None

    def submit(
        self,
        prompt_ids,
        prompt_mask,
        *,
        shared_maps=None,
        publish_maps=None,
        release: bool = False,
        submit_times=None,
    ) -> List[int]:
        """Enqueue prompts (host arrays, [n, Q]); returns their global
        row indices (draw order — the per-row RNG identity). Carries
        the ``engine.admit`` fault-injection site (resilience/chaos.py):
        an injected admission failure drives the orchestrator's
        fixed-sampler fallback and the server's admission retry.

        ``shared_maps`` / ``publish_maps`` ([n, n_blocks] int32, -1 =
        private) are the serving tier's per-row prefix-sharing
        assignments (requires ``prefix_pool_blocks > 0``);
        ``release=True`` marks the batch as padding placeholders that
        are force-finished the moment they are admitted (one decode
        step each instead of a full token budget); ``submit_times``
        (per-row floats on the telemetry clock) backdates the latency
        marks to when the request entered the SERVING tier, so
        ``serve/queue_wait_ms`` includes scheduler queueing, not just
        the slot-pool wait."""
        from trlx_tpu.resilience import chaos

        chaos.check("engine.admit")
        ids = np.asarray(prompt_ids)
        mask = np.asarray(prompt_mask)
        if ids.ndim != 2 or ids.shape[1] != self.Q:
            raise ValueError(
                f"submit expects [n, Q={self.Q}] prompt ids, got {ids.shape}"
            )
        if (
            shared_maps is not None or publish_maps is not None
        ) and self.prefix_pool_blocks < 1:
            raise ValueError(
                "prefix-sharing maps need an engine built with "
                "prefix_pool_blocks > 0"
            )
        rows = []
        t_submit = telemetry.monotonic()
        for i in range(ids.shape[0]):
            row = self._next_row
            self._next_row += 1
            self._queue.append((
                ids[i],
                mask[i],
                row,
                None if shared_maps is None else np.asarray(
                    shared_maps[i], np.int32
                ),
                None if publish_maps is None else np.asarray(
                    publish_maps[i], np.int32
                ),
                bool(release),
            ))
            self._req_times[row] = {
                "submitted": (
                    float(submit_times[i])
                    if submit_times is not None
                    else t_submit
                )
            }
            rows.append(row)
        return rows

    @property
    def pending(self) -> int:
        """Rows submitted but not yet harvested. ``_busy_rows`` covers
        decoding AND done-awaiting-harvest slots (``_done_slots`` is a
        subset of it until harvest pops both), so it is NOT added
        twice."""
        return len(self._queue) + len(self._busy_rows)

    def pop_request_timing(self, row: int) -> Optional[Dict[str, float]]:
        """The per-request latency decomposition for a HARVESTED row,
        in milliseconds — popped (each row reports once; un-popped rows
        are cleared at the next ``start_phase``):

        - ``queue_wait_ms``: submit → admission (slot-pool wait),
        - ``prefill_ms``: admission → first-token mark (the prefill
          dispatch that produces the row's first token),
        - ``ttft_ms``: submit → first token,
        - ``decode_ms``: first token → harvest,
        - ``e2e_ms``: submit → harvest.

        ``None`` for unknown/unfinished rows. Host dispatch timing on
        the shared telemetry clock; the serving layer divides
        ``decode_ms`` by the row's token count for per-token decode."""
        record = self.pop_request_record(row)
        return None if record is None else record["timing"]

    def pop_request_record(self, row: int) -> Optional[Dict[str, Any]]:
        """The full per-request trace record for a HARVESTED row — the
        ``timing`` decomposition of :meth:`pop_request_timing` plus the
        raw ``marks`` (submit/admit/first-token/done/completed seconds
        on the shared telemetry clock) and, under
        :attr:`trace_requests`, the row's decode-cadence slice:
        ``step_times`` (dispatch wall per decode step while the row was
        live) and ``step_epochs`` (the admission-prefill count at each
        step — an epoch change mid-row means the host loop interrupted
        this row's decode run to admit another group, which is exactly
        the bubble the trace analyzer attributes). Popped — each row
        reports once."""
        marks = self._req_times.get(row)
        if not marks or "completed" not in marks:
            return None
        self._req_times.pop(row, None)
        submitted = marks["submitted"]
        admitted = marks.get("admitted", submitted)
        first = marks.get("first_token", admitted)
        completed = marks["completed"]
        ms = 1000.0
        record: Dict[str, Any] = {
            "timing": {
                "queue_wait_ms": max(0.0, (admitted - submitted) * ms),
                "prefill_ms": max(0.0, (first - admitted) * ms),
                "ttft_ms": max(0.0, (first - submitted) * ms),
                "decode_ms": max(0.0, (completed - first) * ms),
                "e2e_ms": max(0.0, (completed - submitted) * ms),
            },
            "marks": dict(marks),
        }
        step_log = getattr(self, "_step_log", None)
        if step_log and "admit_step" in marks:
            base = getattr(self, "_step_base", 0)
            lo = max(0, int(marks["admit_step"]) - base)
            hi = min(
                int(marks.get("done_step", base + len(step_log))) - base,
                len(step_log),
            )
            window = step_log[lo:hi]
            record["step_times"] = [t for t, _ in window]
            record["step_epochs"] = [e for _, e in window]
        return record

    def _plan_chunk_need(self, prompt_mask, shared_map, publish_map):
        """[n_prefill_chunks] bool: which prompt-column chunks ANY row of
        the admit group actually needs computed. Column-granular: a
        column is needed when it is a real (non-pad) column not served
        read-only from the shared-prefix pool, or when its block is
        being PUBLISHED into the pool (the donor must compute what it
        publishes, pad columns included — readers gather the donor's
        bits). Leading all-pad chunks of a left-padded group and
        fully-pool-covered shared chunks come out un-needed. The plan is
        the host's alone: a chunk nobody needs is never dispatched, so
        the skip accounting is transfer-free."""
        Q, W = self.Q, self.prefill_chunk
        mask = np.asarray(prompt_mask)
        first_real = Q - mask.sum(axis=1)
        cols = np.arange(Q)
        needed = cols[None, :] >= first_real[:, None]
        if shared_map is not None:
            bs = self.block_size
            col_blk = np.minimum(cols // bs, self.n_blocks - 1)
            covered = (shared_map[:, col_blk] >= 0) & (
                publish_map[:, col_blk] < 0
            )
            publishes = publish_map[:, col_blk] >= 0
            needed = (needed & ~covered) | publishes
        return needed.reshape(mask.shape[0], Q // W, W).any(2).any(0)

    def _begin_admission(self) -> None:
        """Reserve slots for the next ``admit_width`` group and stage its
        host arrays; the device dispatch happens in
        :meth:`_advance_admission` (one monolithic prefill call, or one
        ``prefill_chunk`` call a needed chunk and the final chunk)."""
        sharing = self.prefix_pool_blocks > 0
        nb_prompt = self.Q // self.block_size  # shareable prompt blocks
        with telemetry.span("collect/admit", force=True):
            A = self.admit_width
            take = min(len(self._free), len(self._queue), A)
            slots = [self._free.pop(0) for _ in range(take)]
            entries = [self._queue.pop(0) for _ in range(take)]
            prompt_ids = np.zeros((A, self.Q), np.int32)
            prompt_mask = np.zeros((A, self.Q), np.int32)
            slot_ids = np.full((A,), self.num_slots, np.int32)  # dummies
            row_index = np.zeros((A,), np.int32)
            turns = np.zeros((A,), np.int32)
            shared_map = np.full((A, self.n_blocks), -1, np.int32)
            publish_map = np.full((A, self.n_blocks), -1, np.int32)
            released_slots = []
            for i, (
                slot,
                (ids, mask, row, sh_row, pub_row, release),
            ) in enumerate(zip(slots, entries)):
                prompt_ids[i] = ids
                prompt_mask[i] = mask
                slot_ids[i] = slot
                row_index[i] = row
                turns[i] = self._recycle_counts[slot]
                self._busy_rows[slot] = row
                # behavior-version tag: the params this row's whole
                # prefill (and its first decode steps) run under
                self._slot_versions[slot] = self.param_version
                if release:
                    released_slots.append(slot)
                if sh_row is not None:
                    shared_map[i, : len(sh_row)] = sh_row
                if pub_row is not None:
                    publish_map[i, : len(pub_row)] = pub_row
                if sharing and not release:
                    hits = int(
                        np.sum(
                            (shared_map[i] >= 0) & (publish_map[i] < 0)
                        )
                    )
                    self.stats.prefix_lookup_blocks += nb_prompt
                    self.stats.prefix_hit_blocks += hits
                    self.stats.prefix_published_blocks += int(
                        np.sum(publish_map[i] >= 0)
                    )
                if self.spec_drafter is not None and not release:
                    # seed the drafter's per-row history with the real
                    # prompt tokens (left-padded: the mask selects them
                    # in order)
                    self.spec_drafter.observe_context(
                        row, [int(x) for x in np.asarray(ids)[
                            np.asarray(mask).astype(bool)
                        ]]
                    )
            args = (prompt_ids, prompt_mask)
            if self.mesh is not None:
                from trlx_tpu.parallel.mesh import batch_sharding

                self._fed()
                args = jax.device_put(args, batch_sharding(self.mesh))
        self._inflight_admission = {
            "take": take,
            "entries": entries,
            "slot_ids": slot_ids,
            "row_index": row_index,
            "turns": turns,
            "ids": args[0],
            "mask": args[1],
            "shared_map": shared_map if sharing else None,
            "publish_map": publish_map if sharing else None,
            "released_slots": released_slots,
            "need": (
                self._plan_chunk_need(
                    prompt_mask,
                    shared_map if sharing else None,
                    publish_map if sharing else None,
                )
                if self.prefill_chunk > 0
                else None
            ),
            "whole": False,
            "next_chunk": 0,
            "chunk_walls": [],
            "t_admit": telemetry.monotonic(),
        }
        if self.prefill_min_skip_share:
            # the final chunk always runs, whatever ``need`` says of it
            need = self._inflight_admission["need"]
            skippable = need.size - 1 - np.count_nonzero(need[:-1])
            self._inflight_admission["whole"] = bool(
                skippable < self.prefill_min_skip_share * need.size
            )

    def _advance_admission(
        self, budget: Optional[int]
    ) -> Tuple[bool, int]:
        """Dispatch the in-flight admission's next slice of prefill work:
        the whole group (monolithic, or ``budget=None``), else at most
        ``budget`` chunk forwards (the serving pump's Sarathi-style
        stall-free bound — skipped chunks are free and never count).
        Returns ``(admission complete, chunk forwards dispatched)``."""
        adm = self._inflight_admission
        sharing = self.prefix_pool_blocks > 0
        self._fed()
        map_args = []
        if sharing:
            map_args = [
                jnp.asarray(adm["shared_map"]),
                jnp.asarray(adm["publish_map"]),
            ]
        if self.prefill_chunk == 0 or adm["whole"]:
            with telemetry.span(
                "collect/prefill", force=True, admitted=adm["take"]
            ):
                self._state = self.prefill_jit(
                    self._params,
                    self._state,
                    jnp.asarray(adm["slot_ids"]),
                    adm["ids"],
                    adm["mask"],
                    jnp.asarray(adm["row_index"]),
                    jnp.asarray(adm["turns"]),
                    self._phase_key,
                    *map_args,
                )
            self._observe_block_write("prefill")
            if adm["whole"]:
                self.stats.prefill_whole += 1
                adm["skipped"] = 0
            self._finalize_admission()
            return True, 1
        # the needed chunks yet to run, then the final chunk, which always
        # runs: every left-padded row's last real column lives there, and
        # it produces logits_last
        need, last = adm["need"], self.n_prefill_chunks - 1
        todo = [c for c in range(adm["next_chunk"], last) if need[c]] + [last]
        run = todo if budget is None else todo[:budget]
        done = run[-1] == last
        # one span for the non-final chunks of this call, one for the final
        parts = [(run, {})]
        if done:
            parts = [(run[:-1], {}), (run[-1:], {"finish": True})]
        for part, attrs in parts:
            if not part:
                continue
            with telemetry.span(
                "collect/prefill", force=True,
                admitted=adm["take"], chunks=len(part), **attrs,
            ):
                for c in part:
                    self._dispatch_chunk(adm, c, map_args)
            self.stats.prefill_chunks += len(part)
            adm["chunk_walls"].append(
                (part[0] * self.prefill_chunk, telemetry.monotonic())
            )
        adm["next_chunk"] = run[-1] + 1
        if not done:
            return False, len(run)
        adm["skipped"] = int(last - np.count_nonzero(need[:last]))
        self.stats.prefill_cols_skipped += (
            adm["skipped"] * self.prefill_chunk
        )
        self._finalize_admission()
        return True, len(run)

    def _observe_block_write(self, program: str) -> None:
        """``engine/prefill_block_write_share``, once an admission forward
        dispatched: the share of ``program``'s paged layers that write
        whole blocks into the pool (1.0 or 0.0 where they are alike)."""
        share = self._block_write_share[program]
        if share is not None:
            telemetry.get_metrics().histogram(
                "engine/prefill_block_write_share"
            ).observe(share)

    def _dispatch_chunk(self, adm, c: int, map_args) -> None:
        """Chunk ``c`` of the in-flight group through ``prefill_chunk``."""
        self._observe_block_write("prefill_chunk")
        self._state = self.prefill_chunk_jit(
            self._params,
            self._state,
            jnp.asarray(adm["slot_ids"]),
            adm["ids"],
            adm["mask"],
            jnp.asarray(adm["row_index"]),
            jnp.asarray(adm["turns"]),
            self._phase_key,
            jnp.asarray(c, jnp.int32),
            *map_args,
        )

    def _finalize_admission(self) -> None:
        """Admission bookkeeping after the group's LAST prefill dispatch:
        placeholder release, latency marks, stats/gauges, and the admit
        listener (published prefix blocks become readable only now —
        every chunk that writes them has been dispatched)."""
        adm = self._inflight_admission
        self._inflight_admission = None
        sharing = self.prefix_pool_blocks > 0
        A = self.admit_width
        released_slots = adm["released_slots"]
        if released_slots:
            # padding placeholders: force-finish now so they cost
            # one decode step, not a full token budget. Fixed
            # admit_width call shape (num_slots = OOB dummy, the
            # scatter drops) — one compiled program regardless of
            # how many placeholders an admission carried.
            rel = np.full((A,), self.num_slots, np.int32)
            rel[: len(released_slots)] = released_slots
            self._state = self.release_jit(self._state, jnp.asarray(rel))
            self.stats.released += len(released_slots)
        # the last prefill dispatch computes the group's FIRST tokens,
        # so its dispatch end is the host-side time-to-first-token mark
        t_first = telemetry.monotonic()
        chunk_offsets = [
            {
                "col": int(col),
                "ms": round((t - adm["t_admit"]) * 1000.0, 3),
            }
            for col, t in adm["chunk_walls"]
        ]
        for entry in adm["entries"]:
            marks = self._req_times.get(entry[2])
            if marks is not None:
                marks["admitted"] = adm["t_admit"]
                marks["first_token"] = t_first
                if chunk_offsets:
                    # per-chunk-window dispatch offsets (column, ms after
                    # admission): the serve/prefill trace span carries
                    # these so --trace-report attributes chunked
                    # admissions (docs/observability.md)
                    marks["prefill_chunk_offsets"] = chunk_offsets
                if self.trace_requests:
                    # decode-cadence window start: this row's live
                    # steps begin at the current step-log position
                    # (absolute index — survives log pruning)
                    marks["admit_step"] = (
                        self._step_base + len(self._step_log)
                    )
        self.stats.prefills += 1
        self.stats.admitted += adm["take"]
        # new occupants joined the pool: a prefetched draft matrix no
        # longer covers it
        self._staged_drafts = None
        registry = telemetry.get_metrics()
        if sharing:
            registry.gauge("engine/prefix_hit_rate").set(
                self.stats.prefix_hit_rate
            )
            registry.gauge("engine/prefix_blocks_saved").set(
                self.stats.prefix_blocks_saved
            )
        if self.prefill_chunk > 0:
            # once an admission, so a reader that clears the registry at
            # its window's start sees the window's own admissions (the
            # gauges below count from the engine's construction)
            registry.histogram("engine/prefill_skip_share").observe(
                adm["skipped"] / self.n_prefill_chunks
            )
            registry.gauge("engine/prefill_chunks").set(
                float(self.stats.prefill_chunks)
            )
            registry.gauge("engine/prefill_cols_skipped").set(
                float(self.stats.prefill_cols_skipped)
            )
        if self._admit_listener is not None:
            self._admit_listener([e[2] for e in adm["entries"]])

    def _chunk_flop_cost(self) -> float:
        """Exact dot-FLOPs of ONE prefill chunk forward, read off the
        trace of the program that runs it (``prefill_chunk``) with
        engine-7's counter (``analysis/resource_audit.py::count_flops``).
        Traced once per engine, when ``stats.prefill_flops_saved`` is
        first read and never by an admission — abstract trace only, no
        compilation — so ``engine/prefill_flops_saved`` is a real FLOP
        number, not a heuristic; 0.0 when tracing is unavailable."""
        if self._chunk_flops is not None:
            return self._chunk_flops
        self._chunk_flops = 0.0
        if self.prefill_chunk_jit is None or self._params is None:
            return self._chunk_flops
        try:
            from trlx_tpu.analysis.resource_audit import count_flops

            sds = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                self._params,
            )
            A, Q = self.admit_width, self.Q
            i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
            args = [
                sds,
                jax.eval_shape(self._make_state),
                i32(A),
                i32(A, Q),
                i32(A, Q),
                i32(A),
                i32(A),
                jax.ShapeDtypeStruct((2,), jnp.uint32),
                i32(),
            ]
            if self.prefix_pool_blocks > 0:
                args += [i32(A, self.n_blocks), i32(A, self.n_blocks)]
            closed = jax.make_jaxpr(self.prefill_chunk_jit)(*args)
            self._chunk_flops = float(count_flops(closed.jaxpr))
        except Exception:  # pragma: no cover - accounting must never kill
            self._chunk_flops = 0.0
        return self._chunk_flops

    def _admit(self) -> None:
        """Complete every possible admission inline (the drive() /
        unbudgeted-pump path): one padded prefill per ``admit_width``
        group — monolithic, or every chunk of the group's plan."""
        if self._inflight_admission is not None:
            self._advance_admission(None)
        while self._free and self._queue:
            self._begin_admission()
            self._advance_admission(None)

    def _pump_admission(self, budget: int) -> None:
        """Advance admission by at most ``budget`` chunk forwards this
        pump iteration (``rollout.prefill_chunks_per_pump``): a large
        admission burst interleaves with decode steps instead of
        stalling them. A staged weight push applies only BETWEEN groups
        — a group's whole prefill runs under one params version (the
        version-tag contract push_weights documents)."""
        remaining = budget
        while remaining > 0:
            if self._inflight_admission is None:
                self._apply_pending_push()
                if not (self._free and self._queue):
                    return
                self._begin_admission()
            done, spent = self._advance_admission(remaining)
            remaining -= max(1, spent)
            if not done:
                return

    def compile_admission_programs(self) -> None:
        """Build every program an admission can dispatch, whatever the
        first requests happen to hold, by running each once on a group of
        dummies: every slot id is out of bounds, so each write drops and
        the pool is what it was. A server calls this before it takes
        traffic (after :meth:`start_phase`): a pump must never compile
        under a running stream, and which of ``prefill`` (a group
        forwarded whole), ``prefill_chunk`` and ``release`` the first
        prompts reach is the traffic's business."""
        A, Q = self.admit_width, self.Q
        slot_ids = jnp.full((A,), self.num_slots, jnp.int32)
        ids = np.full((A, Q), self.gen_config.pad_token_id, np.int32)
        mask = np.zeros((A, Q), np.int32)
        mask[:, -1] = 1
        if self.mesh is not None:
            from trlx_tpu.parallel.mesh import batch_sharding

            ids, mask = jax.device_put(
                (ids, mask), batch_sharding(self.mesh)
            )
        zeros = jnp.zeros((A,), jnp.int32)
        maps = []
        if self.prefix_pool_blocks > 0:
            maps = [jnp.full((A, self.n_blocks), -1, jnp.int32)] * 2
        if self.prefill_chunk == 0 or self.prefill_min_skip_share:
            self._state = self.prefill_jit(
                self._params, self._state, slot_ids, ids, mask, zeros,
                zeros, self._phase_key, *maps,
            )
        if self.prefill_chunk > 0:
            self._state = self.prefill_chunk_jit(
                self._params, self._state, slot_ids, ids, mask, zeros,
                zeros, self._phase_key, jnp.zeros((), jnp.int32), *maps,
            )
        self._state = self.release_jit(self._state, slot_ids)

    def _harvest_ready(self) -> Iterator[Dict[str, Any]]:
        """Yield fixed-width harvest groups while enough slots are done."""
        C = self.harvest_width
        while len(self._done_slots) >= C:
            slots = self._done_slots[:C]
            self._done_slots = self._done_slots[C:]
            with telemetry.span(
                "collect/slot_recycle", force=True, harvested=C
            ):
                self._fed()
                self._last_refill = self._dispatches
                self._state, outs = self.refill_jit(
                    self._state, jnp.asarray(slots, jnp.int32)
                )
            rows = [self._busy_rows.pop(s) for s in slots]
            versions = [int(self._slot_versions[s]) for s in slots]
            t_done = telemetry.monotonic()
            for r in rows:
                marks = self._req_times.get(r)
                if marks is not None:
                    marks["completed"] = t_done
            for s in slots:
                self._recycle_counts[s] += 1
                self._free.append(s)
            if self.spec_drafter is not None:
                for r in rows:
                    self.spec_drafter.forget(r)
                self._staged_drafts = None
            self.stats.recycles += C
            self.stats.completed += C
            outs = dict(outs)
            outs["rows"] = rows  # host-side draw indices, harvest order
            # host-side behavior-version tag per row (admission version):
            # the stream store's version column / staleness accounting
            outs["versions"] = versions
            if self.trace_requests:
                self._prune_step_log()
            yield outs

    def _prune_step_log(self) -> None:
        """Drop cadence-log entries no un-popped request can still
        reference (everything below the minimum in-flight ``admit_step``
        — un-admitted rows stamp at or past the current end, so they
        never constrain). ``_step_base`` keeps the retained marks'
        absolute indices valid. Bounds a long-lived server's cadence
        memory by its in-flight window instead of its lifetime."""
        if not self._step_log:
            return
        end = self._step_base + len(self._step_log)
        floor = min(
            (
                int(m["admit_step"])
                for m in self._req_times.values()
                if "admit_step" in m
            ),
            default=end,
        )
        drop = min(floor, end) - self._step_base
        if drop > 0:
            del self._step_log[:drop]
            self._step_base += drop

    def drive(self, target: int) -> Iterator[Dict[str, Any]]:
        """Run the admission/decode/harvest loop until ``target``
        completed rollouts have been yielded (in ``harvest_width``
        groups). ``target`` must be a multiple of ``harvest_width`` and
        must not exceed the submitted row count."""
        C = self.harvest_width
        if target % C:
            raise ValueError(
                f"target={target} must be a multiple of "
                f"harvest_width={C} (fixed-shape harvest groups)"
            )
        if target > self.pending + self.stats.completed:
            raise ValueError(
                f"drive(target={target}) but only {self.pending} rows "
                "are pending — submit the phase's prompts first"
            )
        yielded = 0
        self._steps_since_poll = 0
        while yielded < target:
            sched_points.yield_point("engine.drive")
            for group in self._harvest_ready():
                yield group
                yielded += len(group["rows"])
                if yielded >= target:
                    # the tail: what the last step left is read out
                    self._read_held()
                    return
            # safe point for a staged weight push (async actor–learner):
            # harvest bookkeeping is settled and the queued admit group
            # is about to prefill under the refreshed params — a push
            # can never drop or reorder it. Never swap params while an
            # admission group is mid-prefill (chunked, pump-interleaved):
            # its chunks must all run under one version.
            if self._inflight_admission is None:
                self._apply_pending_push()
            self._admit()
            if not self._busy_rows:
                # nothing decoding and nothing harvestable: the queue
                # must be empty too (else _admit would have filled)
                raise RuntimeError(
                    "engine starved: no active slots and no full "
                    f"harvest group ({len(self._done_slots)} done < "
                    f"{C}) — target/harvest_width mismatch"
                )
            self._step_once()

    def _step_once(self) -> None:
        """Advance every slot one step: the drafted ``verify_step`` when
        spec decode is on and any slot proposed a draft, else the plain
        one-token ``decode_step`` (the fall-through — draftless rounds
        never pay the wider program)."""
        drafted = False
        if self.spec_max_draft > 0:
            # a draft continues the tokens the host has seen, and a
            # drafted round needs the last acceptance before the next:
            # an engine that drafts reads what is held first, and its
            # ``verify_step`` rounds keep the synchronous order
            self._read_held()
            draft, lens = self._take_drafts()
            drafted = bool(lens.any())
        if drafted:
            self._verify_once(draft, lens)
        else:
            self._decode_once()
        # the step's own bookkeeping ends here (the ledger's ``tap``)
        self.mark_starved("other")

    def _seeded_rows(self) -> Iterable[Tuple[int, int]]:
        """``(slot, row)`` of the busy slots whose device rows are their
        row's. A slot the in-flight admission has reserved holds its
        previous occupant's state until the final chunk seeds it (a
        budget-ended occupant still reads live there), so between the
        chunk forwards of a pump-budgeted admission no token, draft or
        acceptance of such a slot belongs to the row waiting for it."""
        adm = self._inflight_admission
        if adm is None:
            return self._busy_rows.items()
        reserved = set(adm["slot_ids"][: adm["take"]].tolist())
        return [
            (slot, row)
            for slot, row in self._busy_rows.items()
            if slot not in reserved
        ]

    def _take_drafts(self) -> Tuple[np.ndarray, np.ndarray]:
        """The next step's per-slot draft matrix: the prefetched stage
        if it survived (no weight push / admission / harvest since it
        was drafted), else drafted fresh."""
        if self._staged_drafts is not None:
            staged = self._staged_drafts
            self._staged_drafts = None
            return staged
        return self._draft_now()

    def _draft_now(self) -> Tuple[np.ndarray, np.ndarray]:
        """Ask the drafter for up to ``spec_max_draft`` proposed tokens
        per busy, not-yet-done slot. [B, D] int32 matrix + [B] lens."""
        D = self.spec_max_draft
        draft = np.zeros((self.num_slots, D), np.int32)
        lens = np.zeros((self.num_slots,), np.int32)
        if self.spec_drafter is None:
            return draft, lens
        done = set(self._done_slots)
        for slot, row in self._seeded_rows():
            if slot in done:
                continue
            toks = self.spec_drafter.draft(row)
            if not toks:
                continue
            toks = list(toks)[:D]
            draft[slot, : len(toks)] = toks
            lens[slot] = len(toks)
        return draft, lens

    def _verify_once(self, draft: np.ndarray, lens: np.ndarray) -> None:
        """Dispatch one drafted verify step, land its accepted emissions
        into the drafter histories / stream taps, and prefetch the next
        step's drafts (host drafting overlaps the device's next work;
        the stage is dropped if a push/admission/harvest intervenes)."""
        entered = telemetry.monotonic()
        with telemetry.span("engine/dispatch", program="verify_step"):
            self._fed()
            self._state, done, toks, acc = self.verify_step_jit(
                self._params,
                self._state,
                jnp.asarray(draft),
                jnp.asarray(lens),
            )
        self.stats.dispatch_ms += (telemetry.monotonic() - entered) * 1000.0
        try:
            done.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass
        self.stats.decode_steps += 1
        self.stats.spec_steps += 1
        self.stats.occupancy_sum += len(self._busy_rows)
        if self.trace_requests:
            self._step_log.append(
                (telemetry.monotonic(), self.stats.prefills)
            )
        tok_host, acc_host = self.fetch(
            toks, acc, what="tokens", newest=True
        )
        self.forwards_waited = self.stats.forwards
        self.wholes_waited = self.stats.prefill_whole
        with telemetry.span("engine/route"):
            self._route_verified(lens, tok_host, acc_host)
        registry = telemetry.get_metrics()
        registry.gauge("engine/spec_accept_rate").set(
            self.stats.spec_accept_rate
        )
        registry.gauge("engine/spec_tokens_per_step").set(
            self.stats.spec_tokens_per_step
        )
        self._staged_drafts = self._draft_now()
        self._poll_done(done)

    def _route_verified(self, lens, tok_host, acc_host) -> None:
        """A verify step's accepted emissions into the acceptance
        counters, the drafter's histories and the stream taps."""
        seeded = self._seeded_rows()
        for slot, row in seeded:
            n_cols = int(acc_host[slot].sum())  # anchor + accepted drafts
            if lens[slot]:
                n_drafted = int(lens[slot])
                n_accepted = max(0, n_cols - 1)
                self.stats.spec_row_steps += 1
                self.stats.spec_drafted += n_drafted
                self.stats.spec_accepted += n_accepted
                self.stats.spec_draft_lens.append(n_drafted)
                if self.spec_drafter is not None:
                    self.spec_drafter.observe_accept(
                        row, n_drafted, n_accepted
                    )
                marks = self._req_times.get(row)
                if marks is not None:
                    # ride the trace record: the serve/decode span's
                    # spec_segments/accepted attrs keep --trace-report's
                    # cadence estimator honest about multi-token steps
                    marks["spec_segments"] = (
                        marks.get("spec_segments", 0) + 1
                    )
                    marks["spec_accepted"] = (
                        marks.get("spec_accepted", 0) + n_accepted
                    )
            if n_cols and self.spec_drafter is not None:
                self.spec_drafter.observe_tokens(
                    row, [int(t) for t in tok_host[slot, :n_cols]]
                )
        if self.token_sink is not None:
            # route per accepted depth: each sink call keeps the
            # one-token {row: token} contract, in emission order
            for j in range(acc_host.shape[1]):
                emitted = {
                    row: int(tok_host[slot, j])
                    for slot, row in seeded
                    if acc_host[slot, j]
                }
                if emitted:
                    self.token_sink(emitted)

    def _decode_once(self) -> None:
        """Dispatch one decode step for the whole pool and hold its
        outputs; then read the step the call before held (the module
        docstring's cost model): the host's work on step n-1 runs under
        step n."""
        prev = self._held
        behind = prev is not None and not prev.done.is_ready()
        entered = telemetry.monotonic()
        with telemetry.span("engine/dispatch", program="decode_step"):
            self._fed()
            if self.stream_taps:
                self._state, polled, token, live = self.decode_step_jit(
                    self._params, self._state
                )
            else:
                self._state, polled = self.decode_step_jit(
                    self._params, self._state
                )
                token = live = None
        dispatch_ms = (telemetry.monotonic() - entered) * 1000.0
        self.stats.dispatch_ms += dispatch_ms
        if behind and prev.done.is_ready():
            # queued behind a running step, the call came back with that
            # step ended: it waited for the device (a backend that keeps
            # one program in flight), which is no work of the host's
            self.stats.host_blocked_ms += dispatch_ms
        done = polled.pop("done")
        # streaming tap: this step's live emissions go to the per-request
        # queues when the step is read — time-to-first-token decouples
        # from harvest-group completion (the per-step fetch is the
        # streaming cost; non-streaming runs leave token_sink unset and
        # the unfetched outputs are dropped on device). Spec decode reads
        # the same tap to keep the drafter histories current through
        # draftless fall-through steps.
        routed = token is not None and (
            self.token_sink is not None or self.spec_drafter is not None
        )
        taps = (token, live) if routed else None
        for out in (done, *polled.values(), *(taps or ())):
            try:
                out.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass
        self.stats.decode_steps += 1
        self.stats.steps_ahead += prev is not None
        self.stats.occupancy_sum += len(self._busy_rows)
        if self.trace_requests:
            # one (dispatch wall, admission epoch) pair per decode step:
            # the per-request cadence slice the trace analyzer turns
            # into host-loop/admission bubble estimates. Epoch = the
            # prefill count, so an epoch change inside a row's window
            # marks the admission that interrupted its decode run.
            self._step_log.append(
                (telemetry.monotonic(), self.stats.prefills)
            )
        self._held = HeldStep(
            seq=self._dispatches,
            rows=list(self._seeded_rows()),
            done=done,
            moe_stats=polled,
            taps=taps,
            log_end=self._step_base + len(self._step_log),
            forwards=self.stats.forwards,
            wholes=self.stats.prefill_whole,
        )
        if prev is not None:
            self._read_step(prev)

    def _read_held(self) -> None:
        """Read what is held without a new dispatch: the tail of a run
        (nothing left to step), or a round that must see every token
        before it goes on (an engine that drafts)."""
        held, self._held = self._held, None
        if held is not None:
            self._read_step(held)
            self.mark_starved("other")

    def _read_step(self, held: HeldStep) -> None:
        """Route a dispatched step's tokens and poll its flags, both
        from the one step and against the rows that stood at its
        dispatch, less those harvested since (all a harvested row was
        owed it has: its flag was read with its last token)."""
        # nothing dispatched since: this fetch drains the chip
        newest = held.seq == self._dispatches
        self.forwards_waited = held.forwards
        self.wholes_waited = held.wholes
        rows = [
            (slot, row)
            for slot, row in held.rows
            if self._busy_rows.get(slot) == row
        ]
        if held.taps is not None:
            tok_host, live_host = self.fetch(
                *held.taps, what="tokens", newest=newest
            )
            with telemetry.span("engine/route"):
                if self.spec_drafter is not None:
                    for slot, row in rows:
                        if live_host[slot]:
                            self.spec_drafter.observe_tokens(
                                row, [int(tok_host[slot])]
                            )
                if self.token_sink is not None:
                    emitted = {
                        row: int(tok_host[slot])
                        for slot, row in rows
                        if live_host[slot]
                    }
                    if emitted:
                        self.token_sink(emitted)
        self._poll_done(
            held.done, held.moe_stats, rows=rows, newest=newest,
            log_end=held.log_end,
        )

    def _poll_done(
        self, done, moe_stats=None, *, rows=None, newest=True, log_end=None
    ) -> None:
        """Amortized done polling: the flags are sticky (a finished slot
        stays done until harvested), so fetching only every k-th read
        step's flags is exact, and the async copy started at dispatch
        has had a step to land before the host reads it. ``rows``: the
        ``(slot, row)`` pairs the flags speak of (a held step's; default
        every busy slot, for a step read where it was dispatched);
        ``newest``: whether these are the newest program's flags;
        ``log_end``: the cadence log's end at the step's dispatch."""
        self._steps_since_poll += 1
        if self._steps_since_poll < self.done_poll_interval:
            return
        self._steps_since_poll = 0
        done_host, moe_host = self.fetch(
            done, moe_stats or {}, what="done", newest=newest
        )
        self.stats.done_polls += 1
        registry = telemetry.get_metrics()
        share = moe_host.pop("paged_chunks_read_share", None)
        if share is not None:
            # the mean over the steps polled since the registry was cleared
            steps = registry.counter("attention/paged_chunks_read_steps")
            total = registry.counter("attention/paged_chunks_read_sum")
            steps.inc()
            total.inc(float(share))
            if steps.value:  # 0 while the registry is disabled
                registry.gauge("attention/paged_chunks_read_share").set(total.value / steps.value)
        if moe_host:
            from trlx_tpu.ops.moe import record_step_stats

            record_step_stats(moe_host)
        # occupancy timeseries: one gauge sample per paid done-poll
        # (the registry's ring is bounded; one host call per poll)
        # — the Perfetto counter track rides these samples
        registry.gauge("engine/slot_util").set(self.stats.slot_util)
        # again: the registry may have been cleared
        registry.gauge("engine/param_gb").set(self.stats.param_gb)
        self._publish_cache_gauges(self._cache_gb)
        t_done = telemetry.monotonic() if self.trace_requests else 0.0
        if rows is None:
            rows = list(self._busy_rows.items())
        if log_end is None:
            log_end = self._step_base + len(self._step_log)
        for slot, row in rows:
            if done_host[slot] and slot not in self._done_slots:
                self._done_slots.append(slot)
                if self.trace_requests:
                    # host-visible decode end: the harvest-wait stage
                    # (done → refill) starts here, a step after the
                    # flag was computed (k-1 more under amortized
                    # polling) — it is the host-observable bound.
                    marks = self._req_times.get(row)
                    if marks is not None:
                        marks["done"] = t_done
                        marks["done_step"] = log_end

    def fetch(
        self, *arrays, what: str = "group", newest: bool = False
    ) -> Tuple[np.ndarray, ...]:
        """The step loop's blocking device->host fetch, in one transfer
        event: the span ``engine/fetch`` (attr ``what``: ``tokens``,
        ``done``, or a harvested ``group``) is the host waiting on the
        device, and its wall accumulates in ``stats.host_blocked_ms``
        (forced: the counter stands with the tracer off). ``newest``
        says the arrays are outputs of the newest dispatched program,
        with nothing queued behind it: when the fetch returns the chip
        has drained, and the starved ledger's clock starts. A held
        step read behind the next step's dispatch is not, nor is a
        harvested group with a step dispatched behind its ``refill``
        (an older program's arrays): there the clock starts only if the
        step queued behind them is found ended too (:meth:`_ran_out`:
        the host has fallen a whole step behind the chip), so the steady
        loop reads 0. The tail read out with nothing dispatched since is
        the newest, so is a ``verify_step``'s, and so is a group
        harvested with nothing dispatched behind it (the pool ran
        empty: the engine knows, whatever the caller says)."""
        with telemetry.span("engine/fetch", force=True, what=what) as sp:
            host = jax.device_get(arrays)
        self.stats.host_blocked_ms += sp.duration_ms
        if what == "group":
            newest = self._last_refill == self._dispatches
        if self._drained_at is None and (newest or self._ran_out()):
            self._drained_at = sp.end
            if what != "group":  # a landing keeps its own part
                self._starved_part = "tap"
        return host

    def _ran_out(self) -> bool:
        """Whether the chip has nothing left to run: the held step is
        the newest dispatch and has ended (one non-blocking query)."""
        held = self._held
        return (
            held is not None
            and held.seq == self._dispatches
            and held.done.is_ready()
        )

    # ------------------------- the starved ledger ---------------------- #

    def mark_starved(self, part: Optional[str]) -> None:
        """The host loop enters ``part`` (one of ``STARVED_PARTS``; None:
        nobody's, where the engine holds no rows to be starved of). While
        the chip is drained, the time since the last mark goes to the
        part that was running; while it is fed this is one assignment
        and one look at the step in flight: found ended with nothing
        behind it, the chip is drained from here (when it ran out in the
        part that just ended nobody saw: a lower bound)."""
        if self._drained_at is not None:
            now = telemetry.monotonic()
            if self._starved_part is not None:
                self._episode[self._starved_part] += now - self._drained_at
            self._drained_at = now
        elif self._ran_out():
            self._drained_at = telemetry.monotonic()
        self._starved_part = part

    def _fed(self) -> None:
        """Called on entry to every dispatch of the loop: a drained chip
        is fed again, and the episode closes into ``stats``."""
        self._dispatches += 1
        if self._drained_at is None:
            return
        self.mark_starved(None)
        self._drained_at = None
        by, episode = self.stats.starved_by_ms, self._episode
        for part in STARVED_PARTS:
            by[part] += episode[part] * 1000.0
            episode[part] = 0.0

    # ------------------------- serving interface ----------------------- #

    @property
    def done_waiting(self) -> int:
        """Slots whose row has finished but that are held until their
        fixed-width harvest group fills."""
        return len(self._done_slots)

    @property
    def free_capacity(self) -> int:
        """Slots with neither an occupant nor a queued claim — how many
        more requests the serving scheduler may hand the engine without
        overcommitting the pool. Occupants are exactly ``_busy_rows``
        (which includes done-awaiting-harvest slots until the harvest
        pops them); counting ``_done_slots`` again would understate
        capacity and starve admission while a partial harvest group
        waits for peers."""
        return (
            self.num_slots
            - len(self._busy_rows)
            - len(self._queue)
        )

    def pump(self) -> List[Dict[str, Any]]:
        """One serving-loop iteration: harvest every ready fixed-width
        group, admit queued prompts into vacated slots, then advance
        decode one step. Returns the harvested groups (possibly empty).

        This is the scheduler-driven counterpart of :meth:`drive` — the
        serving tier interleaves QoS admission decisions between
        iterations instead of committing a whole phase's prompt set up
        front. Raises nothing on an idle pool (an empty pump is how the
        serving loop discovers it is drained).

        With ``prefill_chunk > 0`` and ``prefill_chunks_per_pump > 0``,
        one pump dispatches at most that many prefill-chunk forwards
        before advancing decode — a large admission burst spreads its
        prefill across pump iterations (Sarathi-style stall-free
        admission) instead of stalling every running slot for the whole
        burst."""
        groups = list(self._harvest_ready())
        if self.prefill_chunks_per_pump > 0:
            self._pump_admission(self.prefill_chunks_per_pump)
        else:
            if self._inflight_admission is None:
                self._apply_pending_push()
            self._admit()
        # a decode step is for the rows that decode: slots an unfinished
        # admission has reserved hold nothing to advance yet. With none
        # to step, what the last step left is read out (the tail)
        if self._seeded_rows():
            self._step_once()
        else:
            self._read_held()
        return groups
