"""Multi-tenant serving tier (trlx_tpu/serving/, docs/serving.md).

Three layers, cheapest first:

- host-only units (no jax): QoS scheduler (priority admission with
  aging, quota exhaustion/refill, deadline ordering, SLO pressure),
  refcounted prefix block pool (share/release, copy-on-divergence, no
  double free, LRU eviction), streaming queues, the `slo-breach`
  detector, per-tenant metric labeling;
- server-level (ONE module-scoped InferenceServer, no trainer build):
  streaming-before-harvest pin, the placeholder padding fix, per-tenant
  histogram keys;
- engine-level parity (acceptance): with prefix sharing enabled and
  real cross-request hits, per-request tokens/logprobs/values are
  BITWISE identical to the unshared engine on dp (tier-1) and mixed
  fsdp×tp (nightly) — the logical-view gather makes shared blocks
  exact, not approximate. The full multi-tenant e2e scenario runs as
  the nightly `slow` tier (per-PR CI covers it via the
  `serving-smoke` job's --mt-smoke step).
"""

import numpy as np
import pytest

from trlx_tpu.serving import ServingConfig
from trlx_tpu.serving.prefix_cache import DoubleFreeError, PrefixBlockPool
from trlx_tpu.serving.scheduler import (
    QoSScheduler,
    Request,
    SLOClass,
    TenantConfig,
    TokenBucket,
    tenant_metric_key,
)
from trlx_tpu.serving.streaming import StreamRouter, TokenStream
from trlx_tpu.telemetry.health import HealthConfig, HealthMonitor, without_timing
from trlx_tpu.telemetry.metrics import MetricsRegistry


DP_MESH = {"dp": -1, "fsdp": 1, "tp": 1}


# --------------------------- scheduler units --------------------------- #


def _req(rid, tenant="t", prio=0, cost=0.0, deadline=None, at=1.0):
    return Request(
        request_id=rid, tenant=tenant, prompt_ids=None, prompt_mask=None,
        priority=prio, cost=cost, deadline=deadline, submitted_at=at,
    )


def test_token_bucket_refill_and_exhaustion():
    b = TokenBucket(rate=10.0, burst=20.0)
    assert b.try_charge(20.0, now=0.0)
    assert not b.try_charge(1.0, now=0.0)  # empty
    assert not b.try_charge(11.0, now=1.0)  # refilled only 10
    assert b.try_charge(10.0, now=1.0)
    assert b.try_charge(20.0, now=100.0)  # capped at burst, not 990


def test_scheduler_priority_admission_order():
    """A high-priority request submitted AFTER low-priority ones is
    admitted ahead of them."""
    s = QoSScheduler(clock=lambda: 1.0)
    low = [s.submit(_req(i, "low", prio=0)) for i in range(3)]
    high = s.submit(_req(9, "high", prio=5))
    batch = s.next_batch(2, now=1.0)
    assert batch[0] is high
    assert batch[1] is low[0]  # then FIFO among equals


def test_scheduler_aging_prevents_starvation():
    """A request that waited long enough outranks a fresh higher-priority
    one: priority alone cannot starve the queue tail."""
    s = QoSScheduler(aging_half_ms=1000.0, clock=lambda: 11.0)
    old_low = s.submit(_req(1, "low", prio=0, at=1.0))  # 10s old
    fresh_high = s.submit(_req(2, "high", prio=5, at=11.0))
    batch = s.next_batch(1, now=11.0)
    # aging: 10_000ms / 1000ms = +10 points > priority 5
    assert batch == [old_low]
    assert s.next_batch(1, now=11.0) == [fresh_high]


def test_scheduler_quota_exhaustion_and_refill():
    """Quota-capped tenants are throttled (requests stay queued) but
    never starved: the bucket refills with time and they admit."""
    s = QoSScheduler(
        tenants={"metered": TenantConfig("metered", rate=10.0, burst=10.0)},
        clock=lambda: 0.0,
    )
    reqs = [s.submit(_req(i, "metered", cost=10.0, at=0.0)) for i in range(3)]
    assert s.next_batch(3, now=0.0) == [reqs[0]]  # burst covers one
    assert s.throttled_rounds >= 1
    assert s.next_batch(3, now=0.5) == []  # only 5 tokens refilled
    assert s.next_batch(3, now=1.0) == [reqs[1]]
    assert s.next_batch(3, now=2.0) == [reqs[2]]  # drained, not starved
    assert not s.has_work()


def test_scheduler_quota_never_bypassed_by_aging():
    s = QoSScheduler(
        tenants={"metered": TenantConfig("metered", rate=0.001, burst=1.0)},
        aging_half_ms=1.0,  # absurdly aggressive aging
        clock=lambda: 1000.0,
    )
    s.submit(_req(0, "metered", cost=1.0, at=0.0))  # drains the bucket
    s.submit(_req(1, "metered", cost=1.0, at=0.0))  # huge aging score
    s.submit(_req(2, "free", prio=0, at=1000.0))
    batch = s.next_batch(3, now=1000.0)
    # req 0 drains the bucket; req 1 is quota-blocked despite its giant
    # aged score; the unmetered tenant still admits this round
    assert [r.request_id for r in batch] == [0, 2]


def test_scheduler_unadmittable_cost_refused_at_submit():
    """A request whose cost exceeds the tenant's burst capacity could
    NEVER be admitted (the bucket level caps at burst) — it must refuse
    loudly at submit instead of hanging every later flush() forever."""
    s = QoSScheduler(
        tenants={"metered": TenantConfig("metered", rate=10.0, burst=10.0)},
        clock=lambda: 0.0,
    )
    with pytest.raises(ValueError, match="could never be admitted"):
        s.submit(_req(1, "metered", cost=10.5))
    assert not s.has_work()
    # at exactly burst it fits (strict comparison), eventually admitting
    s.submit(_req(2, "metered", cost=10.0))
    assert s.next_batch(1, now=0.0) != []


def test_scheduler_deadline_ordering():
    """Equal priority/tenant/age: earlier deadline wins; no deadline
    sorts last; final tie-break is submission order."""
    s = QoSScheduler(clock=lambda: 1.0)
    r_none = s.submit(_req(1, at=1.0))
    r_late = s.submit(_req(2, deadline=50.0, at=1.0))
    r_soon = s.submit(_req(3, deadline=5.0, at=1.0))
    batch = s.next_batch(3, now=1.0)
    assert [r.request_id for r in batch] == [3, 2, 1]


def test_scheduler_slo_pressure_reads_histograms():
    """A tenant whose measured queue-wait p95 approaches its budget gets
    boosted over an identical quiet tenant — the serve/* histograms
    feed back into admission."""
    registry = MetricsRegistry(enabled=True)
    hist = registry.histogram(
        tenant_metric_key("serve/queue_wait_ms", "pressured")
    )
    for _ in range(10):
        hist.observe(1900.0)  # ~0.95x the standard 2000ms budget
    s = QoSScheduler(clock=lambda: 1.0, registry=registry)
    quiet = s.submit(_req(1, "quiet", at=1.0))
    pressured = s.submit(_req(2, "pressured", at=1.0))
    batch = s.next_batch(2, now=1.0)
    assert batch[0] is pressured  # despite the later submission seq
    assert batch[1] is quiet
    ratios = s.slo_ratio_rows()
    key = tenant_metric_key("serve/slo_queue_wait_ratio", "pressured")
    assert 0.9 < ratios[key] < 1.0


def test_zero_rate_finite_burst_tenant_refused():
    """rate <= 0 with a finite burst means a drained bucket never
    refills — the tenant would hang forever, not throttle. Refused at
    config parse."""
    with pytest.raises(ValueError, match="never refill"):
        TenantConfig.from_dict("paused", {"rate": 0.0, "burst": 100.0})
    # unmetered (both unset/inf) stays fine
    TenantConfig.from_dict("free", {"priority": 1})


def test_serving_config_validation():
    with pytest.raises(ValueError, match="Unknown train.serving"):
        ServingConfig.from_dict({"tenant": {}})
    with pytest.raises(ValueError, match="serving.tenants"):
        TenantConfig.from_dict("x", {"priorty": 1})
    s = QoSScheduler()
    with pytest.raises(ValueError, match="slo_class"):
        s.submit(
            Request(request_id=1, tenant="t", prompt_ids=None,
                    prompt_mask=None, slo_class="platinum")
        )


# -------------------------- prefix pool units -------------------------- #


def _cols(*blocks):
    """Flatten per-block (ids, mask) pairs into column arrays."""
    ids = [t for b in blocks for t in b[0]]
    mask = [m for b in blocks for m in b[1]]
    return np.asarray(ids, np.int32), np.asarray(mask, np.int32)


B0 = ((1, 2), (1, 1))
B1 = ((3, 4), (1, 1))
B2 = ((9, 9), (1, 1))


def test_prefix_pool_share_and_release_refcounts():
    pool = PrefixBlockPool(4, block_size=2, n_blocks=4)
    a = pool.plan_admission(*_cols(B0, B1))
    assert list(a.publish_map[:2]) == a.published == a.acquired
    assert a.hit_blocks == 0
    pool.mark_ready(a.published)
    b = pool.plan_admission(*_cols(B0, B1))
    assert b.hit_blocks == 2 and b.published == []
    assert list(b.shared_map[:2]) == a.published  # same physical blocks
    assert list(b.publish_map[:2]) == [-1, -1]  # read-only sharing
    pool.release(a.acquired)
    pool.release(b.acquired)
    assert pool.stats()["prefix_pool/hit_rate"] == 0.5


def test_prefix_pool_double_free_raises():
    pool = PrefixBlockPool(2, block_size=2, n_blocks=2)
    a = pool.plan_admission(*_cols(B0))
    pool.release(a.acquired)
    with pytest.raises(DoubleFreeError):
        pool.release(a.acquired)


def test_prefix_pool_abandon_failed_admission():
    """A plan whose engine submit failed rolls back via abandon():
    never-ready publish blocks return to the free list (instead of
    staying pinned forever — not-ready nodes are unevictable) and the
    prefix stays publishable for the next request."""
    pool = PrefixBlockPool(2, block_size=2, n_blocks=2)
    a = pool.plan_admission(*_cols(B0, B1))
    assert pool.free_blocks == 0
    pool.abandon(a.acquired)  # submit failed; mark_ready never came
    assert pool.free_blocks == 2
    b = pool.plan_admission(*_cols(B0, B1))  # NOT stuck private
    assert len(b.published) == 2
    pool.mark_ready(b.published)
    # abandoning a plan that shared a still-live chain only drops the
    # refcount — the ready blocks stay cached for their other readers
    c = pool.plan_admission(*_cols(B0, B1))
    assert c.hit_blocks == 2
    pool.abandon(c.acquired)
    assert pool.free_blocks == 0
    d = pool.plan_admission(*_cols(B0, B1))
    assert d.hit_blocks == 2


def test_prefix_pool_cow_divergent_block():
    """Copy-on-divergent-write at block granularity: content diverging
    inside block 1 allocates a FRESH pool block — the published block
    is never mutated, and the original chain still matches."""
    pool = PrefixBlockPool(6, block_size=2, n_blocks=4)
    a = pool.plan_admission(*_cols(B0, B1))
    pool.mark_ready(a.published)
    b = pool.plan_admission(*_cols(B0, B2))  # diverges at block 1
    assert b.shared_map[0] == a.published[0]  # common prefix shared
    assert b.publish_map[1] not in a.published  # fresh block, no mutation
    pool.mark_ready(b.published)
    c = pool.plan_admission(*_cols(B0, B1))  # the ORIGINAL chain
    assert c.hit_blocks == 2
    assert list(c.shared_map[:2]) == a.published  # untouched by b


def test_prefix_pool_inflight_blocks_not_shared():
    """A block whose publisher has not been dispatched yet (not
    mark_ready) is unreadable — a concurrent same-prefix request stays
    private rather than waiting."""
    pool = PrefixBlockPool(4, block_size=2, n_blocks=2)
    pool.plan_admission(*_cols(B0))  # publisher, NOT marked ready
    b = pool.plan_admission(*_cols(B0))
    assert b.hit_blocks == 0
    assert list(b.shared_map) == [-1, -1]
    assert b.published == []


def test_prefix_pool_eviction_lru_refcount_zero_only():
    pool = PrefixBlockPool(2, block_size=2, n_blocks=2)
    a = pool.plan_admission(*_cols(B0, B1))
    pool.mark_ready(a.published)
    # pool full, every block referenced: a new chain cannot allocate
    c = pool.plan_admission(*_cols(B2))
    assert c.published == [] and c.shared_map[0] == -1
    pool.release(a.acquired)  # refcount 0 -> evictable
    d = pool.plan_admission(*_cols(B2))
    assert len(d.published) == 1
    assert pool.evictions >= 1
    # eviction is leaf-first: the chain TAIL (B1's block) was evicted,
    # the root block is still legitimately cached — replanning the old
    # chain hits block 0 but finds no stale hit for the evicted tail
    e = pool.plan_admission(*_cols(B0, B1))
    assert e.hit_blocks == 1
    assert e.shared_map[1] == -1 and e.published == []  # pool full


# ---------------------------- streaming units --------------------------- #


def test_token_stream_bounded_overflow_and_iter():
    s = TokenStream(1, maxlen=2)
    for t in (10, 11, 12):
        s.push(t)
    assert s.overflows == 1 and s.emitted == 3
    assert s.drain() == [11, 12]  # oldest dropped

    s2 = TokenStream(2, maxlen=8)
    pumped = []

    def pump():
        if pumped:
            s2.close()
        else:
            s2.push(7)
            pumped.append(1)

    s2._pump = pump
    assert next(s2) == 7  # pulled by pumping
    with pytest.raises(StopIteration):
        next(s2)  # pump closes; closed + drained ends the stream


def test_stream_router_routes_live_rows_only():
    r = StreamRouter(maxlen=8)
    a = TokenStream(0, maxlen=8)
    r.attach(0, a)
    r.attach(3, TokenStream(3, maxlen=8))
    r.on_tokens({0: 5, 3: 6, 7: 9})  # row 7 has no stream
    assert a.drain() == [5]
    assert r.get(3).drain() == [6]
    r.close(0)
    r.on_tokens({0: 8})  # closed stream drops
    assert a.drain() == []
    assert r.active == 1


# ------------------------- slo-breach detector -------------------------- #


def test_slo_breach_detector_trips_per_tenant():
    mon = HealthMonitor(HealthConfig.from_dict({"enabled": True}))
    key = tenant_metric_key("serve/slo_queue_wait_ratio", "acme")
    assert mon.observe({key: 0.8}) == []  # within budget
    events = mon.observe({key: 1.5})
    assert [e.detector for e in events] == ["slo-breach"]
    assert events[0].severity == "warning"
    assert events[0].series == key
    # a different tenant's breach is a separate series: also trips
    other = tenant_metric_key("serve/slo_queue_wait_ratio", "zeta")
    assert [e.detector for e in mon.observe({other: 2.0})] == ["slo-breach"]


def test_observe_request_metrics_tenant_labels():
    from trlx_tpu.inference.server import observe_request_metrics

    registry = MetricsRegistry(enabled=True)
    timing = {
        "queue_wait_ms": 4.0, "prefill_ms": 2.0, "ttft_ms": 6.0,
        "decode_ms": 30.0, "e2e_ms": 40.0,
    }
    observe_request_metrics(registry, timing, tokens=10, tenant="acme")
    snap = registry.snapshot()
    assert snap["histograms"]["serve/decode_per_token_ms"]["mean"] == 3.0
    assert (
        snap["histograms"]["serve/queue_wait_ms[tenant=acme]"]["count"] == 1
    )
    assert snap["counters"]["serve/requests_completed[tenant=acme]"] == 1
    # aggregate twin always fed
    assert snap["counters"]["serve/requests_completed"] == 1


# --------------------------- server fixture ----------------------------- #


def _build_server(mesh=None, slots=4, widths=2):
    from trlx_tpu.analysis import harness
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.inference.server import InferenceServer

    cfg = harness.tiny_config_dict("ppo", mesh=mesh)
    cfg["train"]["rollout"] = {
        "slots": slots, "admit_width": widths, "harvest_width": widths,
        "block_size": 4,
    }
    # generous CPU-tier SLO budgets: queue waits here include jit
    # compile walls, which would trip slo-breach on a healthy run
    cfg["train"]["serving"] = {
        "prefix_cache_blocks": 16,
        "slo_classes": {
            "interactive": {"queue_wait_budget_ms": 120000},
            "standard": {"queue_wait_budget_ms": 120000},
        },
    }
    return InferenceServer(TRLConfig.from_dict(cfg))


@pytest.fixture(scope="module")
def server():
    """ONE tiny server on the default audit mesh (mixed dp×fsdp×tp on
    8 host devices — widths round to the 4 data shards), shared by
    every engine-level test in this module."""
    return _build_server()


def _full_prompts(server, n, seed=0, prefix=(5, 6, 7, 8)):
    """Full-length prompts sharing a leading system prefix (equal
    lengths => identical padded leading columns => shareable)."""
    Q = server.query_length
    rng = np.random.default_rng(seed)
    return [
        list(prefix) + list(rng.integers(1, 30, Q - len(prefix)))
        for _ in range(n)
    ]


def test_streaming_first_token_before_harvest(server):
    """The streaming pin: the first streamed token exists strictly
    before the request's harvested result does, and the full streamed
    sequence equals the harvested tokens."""
    rid = server.submit(_full_prompts(server, 1), stream=True)[0]
    stream = server.stream(rid)
    first = next(stream)
    # the token arrived mid-decode: no harvested result yet
    assert server.poll(rid) is None
    streamed = [first] + list(stream)  # drains to close (pumping)
    server.flush()
    out = server.wait([rid])[rid]
    assert out["length"] >= 1
    assert streamed == out["tokens"]


def test_placeholder_padding_completes_and_releases(server):
    """3 requests into harvest_width=2 groups: the partial final group
    fills with release-on-admission placeholders, everything completes,
    and the placeholders are accounted (not full-budget decodes)."""
    before = server.engine.stats.released
    rids = server.submit(_full_prompts(server, 3, seed=3))
    server.flush()
    results = server.wait(rids)
    assert all(results[r]["length"] >= 1 for r in rids)
    assert server.engine.stats.released > before


def test_per_tenant_histograms_and_clean_health(server):
    res = server.generate(
        _full_prompts(server, 2, seed=5), tenant="acme"
    )
    assert all(r["length"] >= 1 for r in res)
    metrics = server.metrics()
    for base in (
        "serve/queue_wait_ms", "serve/ttft_ms", "serve/e2e_ms",
    ):
        key = tenant_metric_key(base, "acme")
        assert metrics[key]["count"] >= 2, key
    assert without_timing(server.health_events) == []


def test_metrics_say_what_the_server_holds(server):
    """bf16 arithmetic on float32 masters (the tiny config's dtypes): the
    engine reads the served copy and measures what it was handed,
    and the done poll publishes the gauge (docs/serving.md)."""
    import jax

    from trlx_tpu import telemetry
    from trlx_tpu.utils import tree_gb

    server.generate(_full_prompts(server, 2, seed=7))
    metrics = server.metrics()
    served = tree_gb(server.served)
    assert metrics["param_gb_served"] == server.engine.stats.param_gb == served
    assert served < metrics["param_gb_as_given"] == tree_gb(server.params) < 2 * served
    assert metrics["serve/param_leaves_cast"] > 0
    kinds = {(leaf.ndim >= 2, str(leaf.dtype))
             for leaf in jax.tree_util.tree_leaves(server.served["transformer"]["h_0"])}
    assert kinds == {(True, "bfloat16"), (False, "float32")}
    gauges = telemetry.get_metrics().snapshot()["gauges"]
    assert gauges["engine/param_gb"] == served


def test_submit_batch_atomic_on_refusal(server):
    """A mid-batch refusal enqueues NOTHING: the caller received no
    ids, so a partially-enqueued batch would decode orphan rows and
    burn quota for results nobody can claim."""
    ok = _full_prompts(server, 1, seed=11)[0]
    too_long = list(range(1, server.query_length + 2))
    before = server.scheduler.pending
    with pytest.raises(ValueError, match="tokens > seq_length"):
        server.submit([ok, too_long])
    assert server.scheduler.pending == before
    assert not any(server._open.values())


def test_early_pop_streaming_request_cleans_router(server):
    """pop_result on an in-flight streaming request closes its stream
    immediately (the per-step token tap stops paying the moment no
    stream is live) and the row-keyed router entry is reclaimed at
    harvest — no permanent tap leak."""
    rid = server.submit(_full_prompts(server, 1, seed=9), stream=True)[0]
    server._pump_once()  # admitted: the stream attached to its row
    assert server._router.active >= 1
    assert server.pop_result(rid) is None  # abandoned mid-flight
    assert server._router.active == 0  # tap disabled immediately
    other = server.submit(_full_prompts(server, 1, seed=10))
    server.flush()
    assert server.wait(other)[other[0]]["length"] >= 1
    assert server._router._streams == {}  # harvest reclaimed the entry


def test_prefix_sharing_hits_on_served_traffic(server):
    """Same-prefix requests across admission waves produce real shared
    reads (nonzero hit rate) on the serving path."""
    hits_before = server.engine.stats.prefix_hit_blocks
    server.generate(_full_prompts(server, 6, seed=7))
    assert server.engine.stats.prefix_hit_blocks > hits_before
    assert server.stats()["engine/prefix_hit_rate"] > 0


def test_request_traces_complete_and_sum_to_e2e(server):
    """Tentpole acceptance at the server: every completed request —
    streamed and non-streamed — emits ONE closed, root-parented span
    chain whose disjoint critical-path stages tile the root span
    exactly and (minus the post-harvest delivery stage) tie out to the
    request's serve/e2e_ms histogram observation within 5%. Padding
    placeholders emit NO chain — they are rows, not requests."""
    from trlx_tpu import telemetry
    from trlx_tpu.telemetry.request_trace import ROOT, STAGES

    with telemetry.scoped_tracer() as tr:
        rids = server.submit(_full_prompts(server, 2, seed=21))
        srid = server.submit(
            _full_prompts(server, 1, seed=22), stream=True
        )[0]
        streamed = list(server.stream(srid))
        server.flush()
        results = server.wait(rids + [srid])
        spans = tr.spans()
    assert all(results[r]["length"] >= 1 for r in rids + [srid])
    assert streamed == results[srid]["tokens"]
    roots = {
        s.attrs["request_id"]: s for s in spans if s.name == ROOT
    }
    # exactly one chain per request; placeholders contribute none
    assert sorted(roots) == sorted(rids + [srid])
    by_trace = {}
    for s in spans:
        tid = s.attrs.get("trace_id")
        if tid:
            by_trace.setdefault(tid, []).append(s)
    for rid, root in roots.items():
        chain = by_trace[root.attrs["trace_id"]]
        assert all(s.end >= s.start for s in chain)  # closed
        stages = [s for s in chain if s.name in STAGES]
        assert all(s.parent == root.index for s in stages)  # parented
        stage_sum = sum(s.duration_ms for s in stages)
        assert stage_sum == pytest.approx(root.duration_ms, rel=0.01)
        deliver = sum(
            s.duration_ms for s in stages if s.name == "serve/deliver"
        )
        # stage sum ≈ the serve/e2e_ms observation (carried as a root
        # attr so the tie-out needs no histogram join)
        assert stage_sum - deliver == pytest.approx(
            root.attrs["e2e_ms"], rel=0.05, abs=0.5
        )
        # decode cadence rode along (the bubble estimator's feed)
        decode = next(s for s in stages if s.name == "serve/decode")
        assert decode.attrs.get("steps", 0) >= 1
        assert len(decode.attrs["step_offsets_ms"]) == decode.attrs["steps"]
    # the streamed request additionally carries its delivery overlay
    s_chain = by_trace[roots[srid].attrs["trace_id"]]
    assert any(s.name == "serve/stream" for s in s_chain)
    assert roots[srid].attrs["stream"] is True


def test_request_trace_closes_for_early_popped_stream(server):
    """An abandoned request (pop_result mid-flight) still decodes to
    harvest — its span chain must close there too, flagged abandoned,
    or trace completeness silently excludes exactly the requests an
    operator most wants to see."""
    from trlx_tpu import telemetry
    from trlx_tpu.telemetry.request_trace import ROOT

    with telemetry.scoped_tracer() as tr:
        rid = server.submit(
            _full_prompts(server, 1, seed=23), stream=True
        )[0]
        server._pump_once()  # admitted
        assert server.pop_result(rid) is None  # abandoned mid-flight
        other = server.submit(_full_prompts(server, 1, seed=24))
        server.flush()
        server.wait(other)
        roots = {
            s.attrs["request_id"]: s
            for s in tr.spans()
            if s.name == ROOT
        }
    assert rid in roots and roots[rid].attrs["status"] == "abandoned"
    assert other[0] in roots and roots[other[0]].attrs["status"] == "ok"
    assert server._trace_reqs == {}  # retention reclaimed at harvest


# ----------------------- engine-level (run last) ------------------------ #


def test_released_placeholders_cost_one_decode_step(server):
    """The padding-waste fix, pinned at the engine: release-flagged rows
    are force-finished on admission — a full harvest group of them
    is done after ONE decode step instead of the R-step token budget
    (its flags are read behind the dispatch of a second, which rides the
    finished rows along: the loop reads one step behind)."""
    import jax

    eng = server.engine
    R, Hw = eng.R, eng.harvest_width
    assert R > 2  # the pin below is vacuous otherwise
    eng.start_phase(server.params, jax.random.PRNGKey(11))
    Q = eng.Q
    ids = np.full((Hw, Q), 0, np.int32)
    mask = np.zeros((Hw, Q), np.int32)
    mask[:, Q - 1] = 1
    eng.submit(ids, mask, release=True)
    groups = list(eng.drive(Hw))
    assert eng.stats.decode_steps == 2  # was R before the fix
    assert eng.stats.steps_ahead == 1
    assert eng.stats.released == Hw
    assert np.asarray(groups[0]["response_mask"]).sum() == 0


def _run_rounds(engine, params, ids, mask, pool):
    """Two admission rounds of ``num_slots`` rows; round 2 shares round
    1's published prefix blocks when a pool drives the maps."""
    import jax

    engine.start_phase(params, jax.random.PRNGKey(21))
    published_by_row = {}
    if pool is not None:
        engine._admit_listener = lambda rows: [
            pool.mark_ready(published_by_row.pop(r, ()))
            for r in rows
        ]
    got = {}
    Q, n = engine.Q, engine.num_slots
    for start in (0, n):
        sl = slice(start, start + n)
        if pool is not None:
            plans = [
                pool.plan_admission(
                    ids[i], mask[i],
                    eligible_blocks=Q // engine.block_size,
                )
                for i in range(start, start + n)
            ]
            rows = engine.submit(
                ids[sl], mask[sl],
                shared_maps=np.stack([p.shared_map for p in plans]),
                publish_maps=np.stack([p.publish_map for p in plans]),
            )
            for r, p in zip(rows, plans):
                if p.published:
                    published_by_row[r] = p.published
        else:
            engine.submit(ids[sl], mask[sl])
        for g in engine.drive(n):
            arrs = {
                k: np.asarray(g[k])
                for k in ("tokens", "response_mask", "logprobs", "values")
            }
            for j, r in enumerate(g["rows"]):
                got[r] = {k: v[j] for k, v in arrs.items()}
    engine._admit_listener = None
    return got


PARITY_MESHES = [
    # None = the default audit mesh: mixed dp×fsdp×tp on 8 host
    # devices — the STRONGER of the acceptance pins runs per-PR
    pytest.param(None, id="mixed_audit"),
    pytest.param(dict(DP_MESH), id="dp", marks=pytest.mark.slow),
]


@pytest.mark.parametrize("mesh", PARITY_MESHES)
def test_prefix_sharing_bitwise_parity(server, mesh):
    """Acceptance pin: with prefix sharing ENABLED and real
    cross-request hits, per-request tokens/logprobs/values are BITWISE
    identical to the unshared engine — the shared blocks hold the
    donor's bits, which equal the bits the reader's own prefill would
    compute, and the read side is a pure gather (no re-association)."""
    from trlx_tpu.inference.engine import ContinuousBatchingEngine

    if mesh is None:
        srv = server
    else:
        # pure dp: all 8 host devices on the data axis, so the slot
        # pool and widths round to 8 (nightly tier: a second full
        # server build)
        srv = _build_server(mesh=mesh, slots=8, widths=8)

    eng_shared = srv.engine  # prefix pool + stream taps enabled
    eng_plain = ContinuousBatchingEngine(
        apply_fn=eng_shared._apply_fn,
        init_cache_fn=eng_shared._init_cache_fn,
        gen_config=eng_shared.gen_config,
        query_length=eng_shared.Q,
        vocab_size=eng_shared.vocab_size,
        num_slots=eng_shared.num_slots,
        admit_width=eng_shared.admit_width,
        harvest_width=eng_shared.harvest_width,
        block_size=eng_shared.block_size,
        mesh=eng_shared.mesh,
        param_shardings=eng_shared._param_shardings,
        with_values=True,
    )
    n = 2 * eng_shared.num_slots
    prompts = np.asarray(
        _full_prompts(srv, n, seed=13), np.int32
    )
    mask_arr = np.ones_like(prompts)
    pool = PrefixBlockPool(
        16, eng_shared.block_size, eng_shared.n_blocks
    )
    plain = _run_rounds(eng_plain, srv.params, prompts, mask_arr, None)
    shared = _run_rounds(eng_shared, srv.params, prompts, mask_arr, pool)
    # sharing must actually have engaged (round 2 reads round 1's
    # published prefix blocks) or this test pins nothing
    assert eng_shared.stats.prefix_hit_blocks > 0
    assert set(plain) == set(shared) == set(range(n))
    for r in range(n):
        for key in ("tokens", "response_mask", "logprobs", "values"):
            np.testing.assert_array_equal(
                plain[r][key], shared[r][key], err_msg=f"row {r} {key}"
            )


@pytest.mark.slow
def test_multi_tenant_e2e_smoke():
    """The full multi-tenant scenario (priority ordering, quota
    throttle-no-starve, streamed TTFT below harvest TTFT, prefix hits,
    per-tenant keys, zero health events) — nightly tier; per-PR CI runs
    the same path via `python -m trlx_tpu.inference --mt-smoke`."""
    from trlx_tpu.inference.__main__ import multi_tenant_smoke

    assert multi_tenant_smoke() == 0
