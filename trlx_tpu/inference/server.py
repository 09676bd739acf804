"""Multi-tenant serving over the continuous-batching engine.

The request tier of the ROADMAP "millions of users" direction
(docs/serving.md): :class:`InferenceServer` is rebuilt on the
:mod:`trlx_tpu.serving` subsystem —

- **QoS scheduling**: every ``submit`` becomes a typed
  :class:`~trlx_tpu.serving.scheduler.Request` (tenant, priority, SLO
  class, deadline) in the :class:`~trlx_tpu.serving.scheduler.
  QoSScheduler`'s per-tenant queues; vacated decode slots are fed by
  priority-with-aging order under per-tenant token-bucket quotas, with
  SLO pressure read back from the ``serve/*`` latency histograms.
- **Cross-request prefix sharing**: with
  ``serving.prefix_cache_blocks > 0`` the engine carries a shared KV
  pool and the :class:`~trlx_tpu.serving.prefix_cache.PrefixBlockPool`
  maps common prompt prefixes (system prompts, few-shot headers) onto
  refcounted shared blocks — published once, gathered read-only by
  every later request with the same leading columns (bitwise-exact;
  docs/serving.md "Prefix sharing").
- **Streaming decode**: ``submit(..., stream=True)`` opens a bounded
  per-request token queue fed by the engine's per-decode-step tap —
  tokens arrive the step they exist, so TTFT decouples from
  harvest-group completion.
- The old padding waste is gone: partial final harvest groups pad with
  *placeholder* rows that are force-finished on admission (one decode
  step each), not decoded to their full token budget.

Request lifecycle: ``submit`` left-pads, types, and enqueues with the
scheduler (host); the serving pump moves scheduler picks into engine
slots as they vacate; ``flush``/``wait`` run the pump to completion;
results are retained until ``pop_result``/``wait`` hands them out.
A :class:`~trlx_tpu.telemetry.health.HealthMonitor` watches per-group
generation stats (non-finite logprobs/values trip ``nan-precursor``)
and the per-tenant SLO ratios (queue-wait p95 over the class budget
trips ``slo-breach``); the CI ``serving-smoke`` jobs assert clean runs
stay at zero events.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.serving.scheduler import DEFAULT_TENANT, tenant_metric_key

#: the per-request latency histograms every served request feeds
#: (docs/observability.md "Serving metrics") — the series QoS
#: scheduling gates on; the CI serving-smoke asserts these keys
SERVE_HISTOGRAMS = (
    "serve/queue_wait_ms",
    "serve/prefill_ms",
    "serve/ttft_ms",
    "serve/decode_per_token_ms",
    "serve/e2e_ms",
)

#: the starved ledger by part, an observation an iteration beside the
#: total ``serve/starved_ms`` (``other`` is the total less these)
STARVED_HISTOGRAMS = {
    part: f"serve/starved_ms[by={part}]"
    for part in ("tap", "admit", "land", "caller")
}

#: the parts of an iteration that ``host-stall`` names when its wall
#: grows: the first two from the engine's own accumulators
#: (``EngineStats.host_blocked_ms``, ``dispatch_ms``), the rest the
#: tracer's walls by span (``STALL_SPANS``)
STALL_SPANS = (
    "serve/schedule", "serve/land", "engine/route",
    "collect/admit", "collect/prefill", "collect/slot_recycle",
)
STALL_PARTS = ("engine/fetch", "engine/dispatch") + STALL_SPANS


def observe_request_metrics(
    registry,
    timing: Dict[str, float],
    tokens: int,
    tenant: Optional[str] = None,
) -> None:
    """Feed one completed request's engine timing decomposition
    (:meth:`~trlx_tpu.inference.engine.ContinuousBatchingEngine.
    pop_request_timing`) into the latency histograms: queue wait,
    prefill, time-to-first-token, per-token decode (``decode_ms`` over
    the generated token count), end-to-end. With ``tenant`` given, each
    observation ALSO lands in the tenant-labeled twin
    (``serve/queue_wait_ms[tenant=acme]``), so per-tenant SLOs are
    assertable — not just aggregates."""
    values = {
        "serve/queue_wait_ms": timing.get("queue_wait_ms", 0.0),
        "serve/prefill_ms": timing.get("prefill_ms", 0.0),
        "serve/ttft_ms": timing.get("ttft_ms", 0.0),
        "serve/decode_per_token_ms": (
            timing.get("decode_ms", 0.0) / max(1, int(tokens))
        ),
        "serve/e2e_ms": timing.get("e2e_ms", 0.0),
    }
    for key, value in values.items():
        registry.histogram(key).observe(value)
        if tenant is not None:
            registry.histogram(tenant_metric_key(key, tenant)).observe(
                value
            )
    registry.counter("serve/requests_completed").inc()
    if tenant is not None:
        registry.counter(
            tenant_metric_key("serve/requests_completed", tenant)
        ).inc()


class InferenceServer:
    """Submit/poll multi-tenant batched generation against a loaded
    policy.

    :param config: :class:`TRLConfig` (or its dict form) — ``model``
        selects the architecture/checkpoint conversion, ``train.mesh``
        the device mesh, ``method.gen_kwargs`` the generation
        parameters, ``train.rollout`` the engine geometry (slots /
        admit_width / harvest_width / block_size; the ``engine`` field
        is ignored — serving is always continuous), ``train.serving``
        the QoS/prefix/streaming section
        (:class:`~trlx_tpu.serving.ServingConfig`).
    :param checkpoint_dir: optional trainer checkpoint directory
        (``utils/checkpoint``): the policy params are restored from the
        saved train state (optimizer state is read but discarded).
    :param params: optional explicit policy param pytree (overrides
        ``checkpoint_dir``).
    :param tokenizer: optional tokenizer for string prompts / decoded
        results (falls back to ``model.tokenizer_path``).
    :param serving: optional dict overriding ``train.serving``.
    """

    def __init__(
        self,
        config: Union[TRLConfig, Dict[str, Any]],
        checkpoint_dir: Optional[str] = None,
        params=None,
        tokenizer=None,
        seed: int = 0,
        serving: Optional[Dict[str, Any]] = None,
    ):
        import jax
        import jax.numpy as jnp

        from trlx_tpu.inference import RolloutEngineConfig
        from trlx_tpu.inference.engine import ContinuousBatchingEngine
        from trlx_tpu.models.heads import CausalLMWithValueHead
        from trlx_tpu.ops.kv_cache import (
            SERVING_PREFILL_MIN_SKIP_SHARE,
            serving_prefill_chunk,
        )
        from trlx_tpu.ops.sampling import (
            GenerationConfig,
            validate_gen_config,
        )
        from trlx_tpu.parallel import make_mesh, make_partition_specs
        from trlx_tpu.serving import ServingConfig
        from trlx_tpu.serving.prefix_cache import PrefixBlockPool
        from trlx_tpu.serving.scheduler import build_scheduler
        from trlx_tpu.serving.streaming import StreamRouter
        from trlx_tpu.telemetry.health import HealthConfig, HealthMonitor
        from trlx_tpu.trainer.ppo_trainer import get_causal_arch
        from trlx_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        if not isinstance(config, TRLConfig):
            config = TRLConfig.from_dict(config)
        self.config = config
        train = config.train
        self.mesh = make_mesh(train.mesh)
        if dict(self.mesh.shape).get("pp", 1) > 1:
            raise NotImplementedError(
                "InferenceServer serves under plain GSPMD; drop the pp "
                "mesh axis (pipeline decode is a trainer-path feature)"
            )

        self.family, self.model_config, init_params = get_causal_arch(config)
        self.model = CausalLMWithValueHead(
            self.model_config, backbone_cls=self.family.backbone_cls
        )

        self.tokenizer = tokenizer
        if tokenizer is None and config.model.tokenizer_path:
            from transformers import AutoTokenizer

            self.tokenizer = AutoTokenizer.from_pretrained(
                config.model.tokenizer_path, local_files_only=True
            )

        gen_kwargs = dict(config.method.gen_kwargs)
        self.gen_config = GenerationConfig.from_dict(gen_kwargs)
        validate_gen_config(
            self.gen_config,
            getattr(self.model_config, "vocab_size", None),
            provided=set(gen_kwargs),
        )
        self.query_length = train.seq_length

        # --- params: explicit > checkpoint > converted > from-scratch ---
        rng = jax.random.PRNGKey(seed)
        rng, init_rng = jax.random.split(rng)
        if params is None:
            params = self.model.init(
                init_rng, jnp.zeros((1, 8), jnp.int32)
            )["params"]
            if init_params is not None:
                params["transformer"] = init_params  # converted backbone
            if checkpoint_dir is not None:
                from trlx_tpu.utils.checkpoint import load_checkpoint

                # restore the checkpoint as saved (no abstract spec —
                # serving must not need the training run's optimizer
                # layout) and keep only the policy params
                state, _meta = load_checkpoint(checkpoint_dir, None)
                saved = state["params"] if isinstance(state, dict) else (
                    state.params
                )
                flat_live = jax.tree_util.tree_structure(params)
                flat_saved = jax.tree_util.tree_structure(saved)
                if flat_live != flat_saved:
                    raise ValueError(
                        f"checkpoint under {checkpoint_dir} holds a "
                        "different param structure than model config "
                        f"{type(self.model_config).__name__} builds — "
                        "check model.model_arch/model_type against the "
                        "training run"
                    )
                params = saved

        from jax.sharding import NamedSharding, PartitionSpec as P

        specs = make_partition_specs(
            params, self.mesh, self.family.partition_rules
        )
        self.param_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s),
            specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        self.params = jax.device_put(params, self.param_shardings)
        # what the engine reads: matrices at the compute dtype, vectors as
        # stored (utils.served_params); `params` itself where the weights
        # are stored at that dtype already
        from trlx_tpu.utils import served_params

        self.served = served_params(
            self.params,
            getattr(self.model_config, "dtype", train.dtype),
            self.family.stored_width_leaves,
        )

        rollout = RolloutEngineConfig.from_dict(train.rollout)
        num_slots = rollout.slots or int(
            getattr(config.method, "chunk_size", 0) or train.batch_size
        )
        self.serving_config = ServingConfig.from_dict(
            serving if serving is not None else getattr(train, "serving", {})
        )

        def apply_fn(p, input_ids, attention_mask=None, position_ids=None,
                     cache=None, cache_index=None, last_only=False):
            return self.model.apply(
                {"params": p},
                input_ids,
                attention_mask=attention_mask,
                position_ids=position_ids,
                cache=cache,
                cache_index=cache_index,
                last_only=last_only,
            )

        import functools

        spec = rollout.spec_decode
        spec_on = spec is not None and spec.enabled
        # every running stream waits for whatever a pump iteration
        # dispatches, so where the user set no chunk the admission goes
        # through the engine's chunked prefill: a width derived from Q,
        # one chunk forward an iteration (all-pad chunks are not
        # computed), and a group that can skip fewer than half of its
        # chunks forwarded whole. An explicit rollout.prefill_chunk wins,
        # with the budget it came with and every group in chunks.
        prefill_chunk = rollout.prefill_chunk
        prefill_chunks_per_pump = rollout.prefill_chunks_per_pump
        prefill_min_skip_share = 0.0
        if not prefill_chunk:
            prefill_chunk = serving_prefill_chunk(self.query_length)
            prefill_chunks_per_pump = 1
            prefill_min_skip_share = SERVING_PREFILL_MIN_SKIP_SHARE
        self.engine = ContinuousBatchingEngine(
            apply_fn=apply_fn,
            init_cache_fn=functools.partial(
                self.family.init_cache, self.model_config
            ),
            gen_config=self.gen_config,
            query_length=self.query_length,
            vocab_size=self.model_config.vocab_size,
            num_slots=num_slots,
            admit_width=rollout.admit_width,
            harvest_width=rollout.harvest_width,
            block_size=rollout.block_size,
            mesh=self.mesh,
            param_shardings=self.param_shardings,
            with_values=True,
            prefix_pool_blocks=self.serving_config.prefix_cache_blocks,
            stream_taps=True,
            prefill_chunk=prefill_chunk,
            prefill_chunks_per_pump=prefill_chunks_per_pump,
            prefill_min_skip_share=prefill_min_skip_share,
            spec_max_draft=spec.max_draft if spec_on else 0,
            spec_min_accept_ewma=(
                spec.min_accept_ewma if spec_on else 0.0
            ),
        )
        # fold_in consumes rng without a dangling split chain (the
        # key-lineage engine's key-discard rule)
        phase_key = jax.random.fold_in(rng, 7)
        self.engine.start_phase(self.served, phase_key)
        # set-up pays for every admission program; no pump compiles
        self.engine.compile_admission_programs()

        from trlx_tpu import telemetry

        # span-ring capacity (train.telemetry.ring_size): per-request
        # traces multiply span volume; size the ring before traffic
        telemetry.configure_from_dict(getattr(train, "telemetry", None))
        self._registry = telemetry.get_metrics()
        self._registry.counter("serve/param_leaves_cast").inc(sum(
            a is not b for a, b in zip(
                jax.tree_util.tree_leaves(self.params),
                jax.tree_util.tree_leaves(self.served),
            )
        ))
        # request tracing (telemetry/request_trace.py): with the tracer
        # enabled the engine logs decode-step cadence and done marks so
        # every completed request emits a parented span chain; disabled
        # keeps the host loop's per-step cost at zero (NULL_SPAN contract)
        self.engine.trace_requests = telemetry.get_tracer().enabled
        self.scheduler = build_scheduler(
            self.serving_config, registry=self._registry
        )
        self.prefix_pool = (
            PrefixBlockPool(
                self.serving_config.prefix_cache_blocks,
                self.engine.block_size,
                self.engine.n_blocks,
            )
            if self.serving_config.prefix_cache_blocks > 0
            else None
        )
        if spec_on and spec.drafter == "trie" and self.engine.spec_max_draft:
            from trlx_tpu.serving.spec_drafter import TrieDrafter

            # rebind the engine's default per-row n-gram drafter to the
            # trie-backed one: the shared-prefix pool's published chains
            # become the global draft corpus (pool=None — sharing off —
            # keeps pure n-gram behavior)
            self.engine.spec_drafter = TrieDrafter(
                pool=self.prefix_pool,
                max_draft=self.engine.spec_max_draft,
                min_accept_ewma=spec.min_accept_ewma,
            )
        self._router = StreamRouter(
            maxlen=self.serving_config.stream_buffer
        )
        self.engine._admit_listener = self._on_admitted

        # generation-health watch: non-finite logprobs/values in a served
        # group trip nan-precursor, per-tenant queue-wait p95 over the
        # SLO budget trips slo-breach; zero events == healthy serving.
        # Always on; `train.health`'s per-detector tuning and `disable`
        # apply here as in a trainer (host-stall's ratio and min_ms)
        tuned = dict(train.health or {})
        self.health_monitor = HealthMonitor(HealthConfig.from_dict({
            "enabled": True,
            **{k: tuned[k] for k in ("detectors", "disable") if k in tuned},
        }))
        self._requests: Dict[int, Any] = {}  # request_id -> Request
        # trace-emission retention: Request refs (tenant/priority/trace
        # marks) kept until the row HARVESTS — pop_result may drop
        # _requests mid-flight, but an abandoned request's span chain
        # must still close when its row completes
        self._trace_reqs: Dict[int, Any] = {}
        self._plan_windows: Dict[int, Any] = {}  # rid -> (t0, t1)
        self._row_to_req: Dict[int, int] = {}  # engine row -> request_id
        self._req_row: Dict[int, int] = {}  # request_id -> engine row
        self._acquired: Dict[int, List[int]] = {}  # rid -> pool blocks
        self._published_by_row: Dict[int, List[int]] = {}
        self._streams: Dict[int, Any] = {}  # rid -> TokenStream
        self._results: Dict[int, Dict[str, Any]] = {}
        self._open: Dict[int, bool] = {}
        self._next_request = itertools.count()
        self.completion_order: List[int] = []
        self._groups_served = 0
        # the ledger as the last observing iteration left it, and when
        # the last iteration handed the loop back with rows in flight
        self._starved_seen = dict(self.engine.stats.starved_by_ms)
        self._returned_at: Optional[float] = None
        # host-stall (docs/observability.md "Host pauses"): an iteration's
        # wall against the running level of its own class (it met no
        # admission forward, a chunk's, a whole group's: each many times
        # the one before; met: dispatched it, or read a step that ran
        # behind it), and where the host stood at its entry
        self._stall_mark = telemetry.HostMark(STALL_SPANS)
        self._stall_series = tuple(
            self.health_monitor.timing_series(
                f"time/iter_ms[class={cls}]", STALL_PARTS
            )
            for cls in ("step", "admit", "admit_whole")
        )

    # ------------------------------ API -------------------------------- #

    @property
    def health_events(self) -> List[Any]:
        return list(self.health_monitor.events)

    def _encode(self, prompt) -> List[int]:
        if isinstance(prompt, str):
            if self.tokenizer is None:
                raise ValueError("string prompts require a tokenizer")
            return list(self.tokenizer.encode(prompt))
        return list(prompt)

    def _pad_prompt(self, toks: List[int], i: int):
        Q = self.query_length
        pad_id = self.gen_config.pad_token_id
        if not toks:
            raise ValueError(f"prompt {i} is empty")
        if len(toks) > Q:
            raise ValueError(
                f"prompt {i} has {len(toks)} tokens > seq_length={Q}"
            )
        ids = np.full((Q,), pad_id, np.int32)
        mask = np.zeros((Q,), np.int32)
        ids[Q - len(toks):] = toks  # left-pad, as the trainer does
        mask[Q - len(toks):] = 1
        return ids, mask

    def submit(
        self,
        prompts: Sequence[Any],
        tenant: str = DEFAULT_TENANT,
        priority: Optional[int] = None,
        slo_class: Optional[str] = None,
        deadline_ms: Optional[float] = None,
        stream: bool = False,
    ) -> List[int]:
        """Enqueue prompts (strings with a tokenizer, or token-id lists /
        arrays) with the QoS scheduler; returns request ids. Prompts
        longer than ``train.seq_length`` are refused (truncation would
        silently serve a different prompt).

        ``tenant``/``priority``/``slo_class``/``deadline_ms`` type the
        requests for admission (defaults inherit the tenant's
        ``train.serving.tenants`` entry); ``stream=True`` opens a
        per-request token stream (:meth:`stream`) fed per decode step.
        """
        from trlx_tpu import telemetry
        from trlx_tpu.serving.scheduler import Request
        from trlx_tpu.serving.streaming import TokenStream
        from trlx_tpu.telemetry.request_trace import mint_trace_id

        tenant_cfg = self.scheduler.tenant_config(tenant)
        prio = tenant_cfg.priority if priority is None else int(priority)
        slo = tenant_cfg.slo_class if slo_class is None else slo_class
        now = telemetry.monotonic()
        tracing = telemetry.get_tracer().enabled
        # build + validate the WHOLE batch before enqueueing anything:
        # a mid-batch refusal (over-long prompt, unadmittable cost)
        # must not orphan earlier requests whose ids the caller never
        # received
        reqs = []
        for i, p in enumerate(prompts):
            ids, mask = self._pad_prompt(self._encode(p), i)
            request_id = next(self._next_request)
            req = Request(
                request_id=request_id,
                tenant=tenant,
                prompt_ids=ids,
                prompt_mask=mask,
                priority=prio,
                slo_class=slo,
                max_tokens=self.engine.R,
                deadline=(
                    now + deadline_ms / 1000.0
                    if deadline_ms is not None
                    else None
                ),
                stream=bool(stream),
                cost=float(int(mask.sum()) + self.engine.R),
                submitted_at=now,
                trace_id=mint_trace_id(request_id),
            )
            self.scheduler.validate(req)
            reqs.append(req)
        rids = []
        for req in reqs:
            rid = req.request_id
            self.scheduler.submit(req)
            self._requests[rid] = req
            if tracing:
                self._trace_reqs[rid] = req
            self._open[rid] = True
            if stream:
                self._streams[rid] = TokenStream(
                    rid,
                    maxlen=self.serving_config.stream_buffer,
                    pump=self.step,
                )
            rids.append(rid)
        return rids

    def stream(self, request_id: int):
        """The :class:`~trlx_tpu.serving.streaming.TokenStream` iterator
        of a ``stream=True`` request — pulls tokens per decode step,
        pumping the serving loop as needed."""
        s = self._streams.get(request_id)
        if s is None:
            raise KeyError(
                f"request {request_id} was not submitted with stream=True"
            )
        return s

    # --------------------------- serving pump --------------------------- #

    def _on_admitted(self, rows: List[int]) -> None:
        """Engine admit listener: newly published prefix blocks become
        readable for later admission groups (the publishing prefill has
        been dispatched — device order makes its writes land first)."""
        if self.prefix_pool is None:
            return
        for row in rows:
            published = self._published_by_row.pop(row, None)
            if published:
                self.prefix_pool.mark_ready(published)

    def _engine_submit(self, batch) -> None:
        """Move scheduler picks into the engine's admission queue."""
        from trlx_tpu import telemetry
        from trlx_tpu.utils.retry import retry_call

        tracing = telemetry.get_tracer().enabled
        n = len(batch)
        Q = self.query_length
        ids = np.zeros((n, Q), np.int32)
        mask = np.zeros((n, Q), np.int32)
        shared_maps = publish_maps = None
        plans = []
        for i, req in enumerate(batch):
            ids[i] = req.prompt_ids
            mask[i] = req.prompt_mask
            if self.prefix_pool is not None:
                t_plan = telemetry.monotonic() if tracing else 0.0
                plan = self.prefix_pool.plan_admission(
                    req.prompt_ids, req.prompt_mask,
                    eligible_blocks=Q // self.engine.block_size,
                )
                if tracing:
                    # prefix-plan overlay span of the request's trace
                    self._plan_windows[req.request_id] = (
                        t_plan, telemetry.monotonic()
                    )
                plans.append(plan)
        if plans:
            shared_maps = np.stack([p.shared_map for p in plans])
            publish_maps = np.stack([p.publish_map for p in plans])
        # admission is host-side bookkeeping, but it sits on the serving
        # request path — a transient failure (the engine.admit injection
        # site models one) retries with bounded backoff instead of
        # bouncing the request (docs/resilience.md)
        try:
            rows = retry_call(
                lambda: self.engine.submit(
                    ids,
                    mask,
                    shared_maps=shared_maps,
                    publish_maps=publish_maps,
                    submit_times=[req.submitted_at for req in batch],
                ),
                describe="inference-server admission",
            )
        except Exception:
            # permanent admission failure: roll the plans back, or the
            # acquired refcounts and never-ready publish blocks leak —
            # pinned forever (unevictable) and breaking every later
            # same-prefix trie walk
            if self.prefix_pool is not None:
                for plan in plans:
                    if plan.acquired:
                        self.prefix_pool.abandon(plan.acquired)
            for req in batch:
                self._plan_windows.pop(req.request_id, None)
            raise
        for i, (row, req) in enumerate(zip(rows, batch)):
            self._row_to_req[row] = req.request_id
            self._req_row[req.request_id] = row
            if self.engine.spec_drafter is not None:
                # tenant-scoped accept-rate EWMA: one tenant's
                # unpredictable text degrades that tenant's drafting,
                # not everyone's
                self.engine.spec_drafter.set_tenant(row, req.tenant)
            if plans:
                if plans[i].acquired:
                    self._acquired[req.request_id] = plans[i].acquired
                if plans[i].published:
                    self._published_by_row[row] = plans[i].published
            if req.stream:
                s = self._streams.get(req.request_id)
                if s is not None:
                    self._router.attach(row, s)

    def _submit_placeholders(self, n: int) -> None:
        """Pad the engine queue with ``n`` release-on-admission rows so
        the final partial harvest group fills WITHOUT decoding dummy
        rollouts to their full token budget (each placeholder costs one
        decode step — the PR-8 padding waste, fixed)."""
        Q = self.query_length
        ids = np.full((n, Q), self.gen_config.pad_token_id, np.int32)
        mask = np.zeros((n, Q), np.int32)
        ids[:, Q - 1] = self.gen_config.pad_token_id
        mask[:, Q - 1] = 1
        self.engine.submit(ids, mask, release=True)

    def _schedule(self) -> int:
        """Feed the engine's admission queue from the scheduler, and pad
        a trailing partial harvest group with placeholders. The span
        ``serve/schedule`` opens only where there is such work; returns
        the requests handed to the engine."""
        from trlx_tpu import telemetry

        engine, scheduler = self.engine, self.scheduler
        free = engine.free_capacity
        feed = free > 0 and scheduler.has_work()
        Hw = engine.harvest_width
        if not feed and (scheduler.has_work() or not engine.pending % Hw):
            return 0
        admitted = 0
        with telemetry.span("serve/schedule") as sp:
            if feed:
                batch = scheduler.next_batch(free)
                if batch:
                    self._engine_submit(batch)
                    admitted = len(batch)
            # when the scheduler has nothing more to feed and the
            # in-flight rows cannot fill the last fixed-width harvest
            # group, pad with release-on-admission placeholders — a lone
            # streaming request (or a trailing partial group) drains
            # without waiting for traffic that may never come
            if not scheduler.has_work() and engine.pending % Hw:
                padded = Hw - engine.pending % Hw
                self._submit_placeholders(padded)
                sp.set(placeholders=padded)
            sp.set(admitted=admitted)
        return admitted

    def step(self) -> bool:
        """One serving iteration: feed the engine from the scheduler,
        let it harvest, admit and advance decode a step
        (:meth:`~trlx_tpu.inference.engine.ContinuousBatchingEngine.
        pump`), land the harvested groups. Returns whether anything
        progressed. A caller that owns the loop (an open-loop load
        generator, an RPC front end) calls this; ``flush``/``wait`` and
        a stream's iterator call it for everyone else.

        Measured from inside (docs/observability.md "The serving loop"):
        the span ``serve/step`` with ``serve/schedule``, ``engine/fetch``
        and ``serve/land`` beneath it, and for an iteration that did
        device work the histograms ``serve/pump_ms`` (it waited for a
        decode step and no admission prefill) or ``serve/admit_pump_ms``
        (the step it read had run behind an admission forward: the stall
        every running stream feels; since the loop reads one step behind,
        that is the iteration *after* the one that dispatched the forward),
        ``serve/step_host_ms`` (the wall less the time blocked in
        ``engine/fetch``), ``serve/slots_done_waiting``,
        ``serve/starved_ms`` with its ``[by=...]`` twins: how long the
        chip sat drained before this iteration fed it, by the part of
        the loop that held the host (the engine's starved ledger; this
        method marks ``admit``, ``land`` and ``caller``), and, where the
        iteration ran a decode step, ``serve/step_ahead``: 1.0 if the
        step was dispatched while the one before it was still unread,
        else 0.0 (a restart from an empty pipeline).

        The engine reads one step behind what it has dispatched: this
        iteration dispatches step n and then fetches, routes and polls
        step n-1, so the tokens and flags a caller sees are those of
        the step before the one now running, the fetch waits for n-1
        with n already queued, and no fetch of the steady loop drains
        the chip (the ledger reads 0 there; what it still shows is a
        real drain: a tail read out, a pool refilling from empty). A
        freed slot is seen, harvested and re-admitted one iteration
        later than its flag was computed. While a client streams, the
        engine fetches every step's tokens, so the walls are the
        device's; with no stream open only the ``done`` flags are."""
        from trlx_tpu import telemetry
        from trlx_tpu.telemetry.health import announce

        engine = self.engine
        stats = engine.stats
        forwards = stats.forwards
        steps, ahead = stats.decode_steps, stats.steps_ahead
        waited, wholes = engine.forwards_waited, engine.wholes_waited
        whole_forwards = stats.prefill_whole
        blocked_ms = stats.host_blocked_ms
        dispatch_ms = stats.dispatch_ms
        self._stall_mark.take()
        engine.mark_starved("admit")
        with telemetry.span("serve/step", force=True) as sp:
            self._stamp_caller(sp.start)
            admitted = self._schedule()
            # tap cost is per-step host fetches: only pay while someone
            # is actually streaming
            engine.token_sink = (
                self._router.on_tokens if self._router.active else None
            )
            busy_before = engine.pending
            groups = engine.pump()
            for group in groups:
                engine.mark_starved("land")
                with telemetry.span("serve/land"):
                    self._land_group(group)
            if groups:
                engine.mark_starved("other")
            sp.set(
                admitted=admitted,
                harvested=sum(len(g["rows"]) for g in groups),
            )
        if stats.forwards > forwards or stats.decode_steps > steps:
            wall_ms = sp.duration_ms
            registry = self._registry
            behind_forward = engine.forwards_waited > waited
            registry.histogram(
                "serve/admit_pump_ms" if behind_forward else "serve/pump_ms"
            ).observe(wall_ms)
            registry.histogram("serve/step_host_ms").observe(
                max(0.0, wall_ms - (stats.host_blocked_ms - blocked_ms))
            )
            registry.histogram("serve/slots_done_waiting").observe(
                engine.done_waiting
            )
            by = stats.starved_by_ms
            seen, self._starved_seen = self._starved_seen, dict(by)
            registry.histogram("serve/starved_ms").observe(
                sum(by[part] - seen[part] for part in by)
            )
            for part, name in STARVED_HISTOGRAMS.items():
                registry.histogram(name).observe(by[part] - seen[part])
            if stats.decode_steps > steps:
                registry.histogram("serve/step_ahead").observe(
                    float(stats.steps_ahead > ahead)
                )
            if (
                engine.wholes_waited > wholes
                or stats.prefill_whole > whole_forwards
            ):
                series = self._stall_series[2]
            else:
                series = self._stall_series[
                    behind_forward or stats.forwards > forwards
                ]
            if series is not None:
                series.values[0] = stats.host_blocked_ms - blocked_ms
                series.values[1] = stats.dispatch_ms - dispatch_ms
                self._stall_mark.fill(series, offset=2)
                event = self.health_monitor.observe_timing(
                    series, wall_ms, step=stats.decode_steps
                )
                if event is not None:
                    announce(event)
        # the caller's turn: starved time only while rows wait on it
        waiting = engine.pending > 0
        engine.mark_starved("caller" if waiting else None)
        self._returned_at = sp.end if waiting else None
        return bool(groups) or busy_before > 0

    def _stamp_caller(self, until: float) -> None:
        """The caller's turn, from the last iteration's return with rows
        in flight to this one's entry, as the span ``serve/caller``:
        stamped after the fact like a request's trace (no parent, no
        profiler annotation; in a device trace it is the time outside
        every ``trlx/serve/step``)."""
        since, self._returned_at = self._returned_at, None
        if since is None:
            return
        from trlx_tpu import telemetry
        from trlx_tpu.telemetry.request_trace import _stamp

        tracer = telemetry.get_tracer()
        if tracer.enabled:
            thread = threading.current_thread()
            tracer.record(_stamp(
                "serve/caller", since, until, thread.ident or 0,
                thread.name, {},
            ))

    def _pump_once(self) -> bool:
        """The name :meth:`step` had before it was public (callers
        outside the package still use it)."""
        return self.step()

    def _observe_group(self, lp, vals, mask) -> None:
        from trlx_tpu import telemetry

        m = mask.astype(bool)
        picked = lp[m] if m.any() else lp.ravel()
        row = {
            "health/logprob_mean": float(picked.mean()),
            "health/logprob_min": float(picked.min()),
            "health/value_mean": float(vals[m].mean() if m.any() else 0.0),
        }
        # per-tenant SLO watch: measured queue-wait p95 over the class
        # budget; a ratio > 1 trips the slo-breach detector
        row.update(self.scheduler.slo_ratio_rows())
        self.health_monitor.observe(row, step=self._groups_served)
        self._groups_served += 1
        # the host's counters stand at 0.0, not absent, after a cleared
        # registry and in a window in which nothing paused
        telemetry.touch_host_counters()

    def _land_group(self, group) -> None:
        engine = self.engine
        toks, mask, lp, vals = engine.fetch(
            group["tokens"], group["response_mask"],
            group["logprobs"], group["values"],
        )
        self._observe_group(lp, vals, mask)
        for j, row in enumerate(group["rows"]):
            record = engine.pop_request_record(row)
            timing = record["timing"] if record else None
            rid = self._row_to_req.pop(row, None)
            self._published_by_row.pop(row, None)
            # refcounts drop for EVERY harvested row with a plan — also
            # rows whose request was closed early (pop_result mid-
            # flight), which would otherwise pin pool blocks forever
            if rid is not None:
                acquired = self._acquired.pop(rid, None)
                if acquired and self.prefix_pool is not None:
                    self.prefix_pool.release(acquired)
            # the router entry is keyed by ROW and must go even for an
            # early-closed request (pop_result mid-flight) — a leaked
            # not-closed stream would keep the engine's token tap (two
            # extra device fetches per decode step) on forever
            stream = self._router.pop(row)
            if stream is not None:
                stream.close()
            length = int(mask[j].sum()) if rid is not None else 0
            if rid is None or not self._open.get(rid):
                # placeholder / already-closed row. An early-popped
                # request's row still decoded to harvest — its span
                # chain closes here too (status=abandoned), so trace
                # completeness covers every completed row
                self._finish_trace(
                    rid, record, stream, length, status="abandoned"
                )
                continue
            req = self._requests[rid]
            if timing is not None:
                observe_request_metrics(
                    self._registry, timing, length, tenant=req.tenant
                )
            out: Dict[str, Any] = {
                "tokens": toks[j, :length].tolist(),
                "logprobs": lp[j, :length].tolist(),
                "length": length,
                "tenant": req.tenant,
            }
            if self.tokenizer is not None:
                out["text"] = self.tokenizer.decode(
                    out["tokens"], skip_special_tokens=True
                )
            self._results[rid] = out
            self._open[rid] = False
            self.completion_order.append(rid)
            self._finish_trace(rid, record, stream, length)

    def _finish_trace(
        self, rid, record, stream, tokens: int, status: str = "ok"
    ) -> None:
        """Close one harvested request's distributed trace: turn the
        retained scheduler marks + the engine's popped record + the
        stream's delivery marks into the parented span chain
        (telemetry/request_trace.py). No-op for placeholder rows, for
        requests submitted while tracing was off, and when the tracer
        is disabled now."""
        req = self._trace_reqs.pop(rid, None) if rid is not None else None
        if req is None or record is None:
            if rid is not None:
                self._plan_windows.pop(rid, None)
            return
        from trlx_tpu import telemetry
        from trlx_tpu.telemetry.request_trace import emit_request_trace

        tracer = telemetry.get_tracer()
        if not tracer.enabled:
            self._plan_windows.pop(rid, None)
            return
        stream_window = None
        if stream is not None and stream.first_push_at is not None:
            stream_window = (
                stream.first_push_at,
                stream.closed_at or stream.first_push_at,
            )
        emit_request_trace(
            tracer,
            trace_id=req.trace_id,
            request_id=req.request_id,
            tenant=req.tenant,
            priority=req.priority,
            slo_class=req.slo_class,
            streamed=req.stream,
            tokens=tokens,
            marks=record["marks"],
            timing=record["timing"],
            delivered=telemetry.monotonic(),
            status=status,
            quota_blocked_at=req.quota_blocked_at,
            picked_at=req.picked_at or None,
            step_times=record.get("step_times"),
            step_epochs=record.get("step_epochs"),
            plan_window=self._plan_windows.pop(rid, None),
            stream_window=stream_window,
        )

    def flush(self) -> int:
        """Drive the serving loop until every submitted request has
        completed; returns the number of newly completed requests.
        Partial final harvest groups fill with release-on-admission
        placeholders (one decode step each) instead of fully-decoded
        dummy rows."""
        open_before = [r for r, o in self._open.items() if o]
        if not open_before:
            return 0
        while any(self._open.get(r) for r in open_before):
            progressed = self.step()
            if not progressed:
                if self.scheduler.has_work():
                    # quota-throttled tenants: wait for bucket refill
                    time.sleep(0.002)
                else:
                    raise RuntimeError(
                        "serving pump stalled with open requests but "
                        "nothing pending — request bookkeeping bug"
                    )
        return sum(
            1 for r in open_before if not self._open.get(r)
        )

    def poll(self, request_id: int) -> Optional[Dict[str, Any]]:
        """Completed result for ``request_id`` (None while in flight);
        the result stays claimable until :meth:`pop_result`."""
        return self._results.get(request_id)

    def pop_result(self, request_id: int) -> Optional[Dict[str, Any]]:
        # an in-flight streaming request closes its stream NOW (the tap
        # stops paying per-step fetches once no stream is live); the
        # row-keyed router entry itself is popped at harvest
        row = self._req_row.pop(request_id, None)
        if row is not None:
            self._router.close(row)
        self._open.pop(request_id, None)
        self._requests.pop(request_id, None)
        self._streams.pop(request_id, None)
        return self._results.pop(request_id, None)

    def wait(self, request_ids: Sequence[int]) -> Dict[int, Dict[str, Any]]:
        """Drive until every id in ``request_ids`` has a result; returns
        and pops them."""
        missing = [r for r in request_ids if r not in self._results]
        if missing:
            self.flush()
        still = [r for r in request_ids if r not in self._results]
        if still:
            raise RuntimeError(
                f"requests {still} did not complete — were they submitted?"
            )
        return {r: self.pop_result(r) for r in request_ids}

    def generate(self, prompts: Sequence[Any], **submit_kwargs
                 ) -> List[Dict[str, Any]]:
        """Blocking convenience: submit + wait, results in prompt order."""
        rids = self.submit(prompts, **submit_kwargs)
        done = self.wait(rids)
        return [done[r] for r in rids]

    def stats(self) -> Dict[str, float]:
        """Engine occupancy/throughput counters (cumulative this phase)
        plus scheduler and prefix-pool accounting."""
        out = self.engine.stats.to_dict()
        out["scheduler/admitted"] = float(self.scheduler.admitted)
        out["scheduler/pending"] = float(self.scheduler.pending)
        out["scheduler/throttled_rounds"] = float(
            self.scheduler.throttled_rounds
        )
        if self.prefix_pool is not None:
            out.update(self.prefix_pool.stats())
        return out

    def metrics(self) -> Dict[str, Any]:
        """The ``serve/*`` slice of the metrics-registry snapshot: the
        per-request latency histograms (summaries) and counters this
        process accumulated — aggregate AND tenant-labeled keys — and
        the histograms its engine observes (``engine/*``); beside them
        what the caller's tree held on the device and what the engine
        reads now (GB)."""
        from trlx_tpu.utils import tree_gb

        snap = self._registry.snapshot()
        out: Dict[str, Any] = {
            "param_gb_as_given": tree_gb(self.params),
            "param_gb_served": self.engine.stats.param_gb,
        }
        for section in ("counters", "gauges"):
            for name, value in snap.get(section, {}).items():
                if name.startswith("serve/"):
                    out[name] = value
        for name, summary in snap.get("histograms", {}).items():
            if name.startswith(("serve/", "engine/")):
                out[name] = summary
        return out
