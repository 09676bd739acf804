"""Runtime telemetry (trlx_tpu/telemetry) + engine 10 (--perf-audit).

Tracer units (nesting, exception safety, disabled-mode cost, ring
bounds, chrome export), the streamed-phase span-tree shape (epoch-1
dispatch spans strictly inside the collect span when phase_overlap is
on), the perf-budget gate's seeded/clean pair (the 40% drift trip per
the test_analysis_resources pattern — the sleep-injected end-to-end
trip runs on the nightly tier), profiler windows, and the satellites
(Clock/Logger monotonic source, visible wandb-init failure).
"""

import glob
import json
import os
import time

import numpy as np
import pytest

os.environ.setdefault("WANDB_DISABLED", "1")


# ----------------------------- tracer units ----------------------------- #


def _fresh_tracer(**kwargs):
    from trlx_tpu.telemetry import Tracer

    return Tracer(enabled=True, **kwargs)


def test_span_nesting_records_parent_depth_and_duration():
    tracer = _fresh_tracer()
    with tracer.span("outer", phase=3) as outer:
        with tracer.span("inner") as inner:
            time.sleep(0.005)
    assert inner.parent == outer.index
    assert inner.depth == 1 and outer.depth == 0
    assert inner.duration_ms >= 4.0
    # children close first but the whole chain is recorded
    names = [s.name for s in tracer.spans()]
    assert names == ["inner", "outer"]
    assert tracer.ancestors(inner) == [tracer.last("outer")]
    # timestamps nest: the inner window sits inside the outer one
    assert outer.start <= inner.start and inner.end <= outer.end
    # aggregate stats carry per-name percentiles
    stats = tracer.stats()
    assert stats["inner"]["count"] == 1
    assert stats["inner"]["p50_ms"] == pytest.approx(inner.duration_ms)


def test_span_exception_safe_close_and_stack_unwind():
    tracer = _fresh_tracer()
    with pytest.raises(ValueError, match="boom"):
        with tracer.span("failing"):
            raise ValueError("boom")  # the span must not swallow
    rec = tracer.last("failing")
    assert rec is not None and rec.status == "error"
    assert rec.end >= rec.start
    # the stack unwound: a follow-up span is a root again
    with tracer.span("after") as sp:
        pass
    assert sp.depth == 0 and sp.parent is None


def test_disabled_mode_returns_shared_null_span():
    from trlx_tpu.telemetry import NULL_SPAN

    tracer = _fresh_tracer()
    tracer.enabled = False
    s1 = tracer.span("x")
    s2 = tracer.span("y", attr=1)
    # one shared singleton — no allocation, no record, no stats
    assert s1 is NULL_SPAN and s2 is NULL_SPAN
    with s1:
        pass
    assert tracer.spans() == []
    assert s1.duration_ms == 0.0
    # forced spans still measure (phase stats stay correct) but are
    # NOT recorded while disabled
    with tracer.span("forced", force=True) as f:
        time.sleep(0.002)
    assert f.duration_ms >= 1.0
    assert tracer.spans() == []


def test_ring_buffer_bounds_and_drop_counter():
    tracer = _fresh_tracer(max_records=4)
    for i in range(7):
        with tracer.span(f"s{i}"):
            pass
    records = tracer.spans()
    assert len(records) == 4
    assert [s.name for s in records] == ["s3", "s4", "s5", "s6"]
    assert tracer.dropped == 3


def test_ring_size_env_override(monkeypatch):
    from trlx_tpu.telemetry.tracer import DEFAULT_RING_SIZE, env_ring_size

    monkeypatch.delenv("TRLX_TELEMETRY_RING", raising=False)
    assert env_ring_size() == DEFAULT_RING_SIZE
    monkeypatch.setenv("TRLX_TELEMETRY_RING", "128")
    assert env_ring_size() == 128
    # malformed/nonpositive values fall back — a typo must not kill the
    # run that was trying to observe itself
    monkeypatch.setenv("TRLX_TELEMETRY_RING", "bogus")
    assert env_ring_size() == DEFAULT_RING_SIZE
    monkeypatch.setenv("TRLX_TELEMETRY_RING", "0")
    assert env_ring_size() == DEFAULT_RING_SIZE


def test_configure_from_dict_ring_size(monkeypatch):
    from trlx_tpu import telemetry

    monkeypatch.delenv("TRLX_TELEMETRY_RING", raising=False)
    tracer = telemetry.get_tracer()
    prev = tracer._records.maxlen
    try:
        assert telemetry.configure_from_dict({"ring_size": 32}) is tracer
        assert tracer._records.maxlen == 32
        # an explicit env override outranks the YAML
        monkeypatch.setenv("TRLX_TELEMETRY_RING", "64")
        telemetry.configure_from_dict({"ring_size": 16})
        assert tracer._records.maxlen == 32
        # ...but a MALFORMED env value must not ALSO block the YAML —
        # validity decides precedence, not mere presence
        monkeypatch.setenv("TRLX_TELEMETRY_RING", "64k")
        telemetry.configure_from_dict({"ring_size": 48})
        assert tracer._records.maxlen == 48
        monkeypatch.delenv("TRLX_TELEMETRY_RING")
        with pytest.raises(ValueError, match="Unknown train.telemetry"):
            telemetry.configure_from_dict({"ringsize": 8})
        with pytest.raises(ValueError, match=">= 1"):
            telemetry.configure_from_dict({"ring_size": 0})
        # empty/None section: untouched
        telemetry.configure_from_dict(None)
        assert tracer._records.maxlen == 48
    finally:
        telemetry.configure(max_records=prev)


def test_tracer_record_external_spans():
    """Externally-stamped spans (the per-request trace path): explicit
    start/end, explicit parenting, no thread-stack participation, ring
    accounting like any other span."""
    from trlx_tpu.telemetry import Span

    tracer = _fresh_tracer(max_records=4)
    root = Span("serve/request")
    root.start, root.end = 5.0, 6.0
    ix = tracer.record(root)
    child = Span("serve/queue")
    child.start, child.end = 5.0, 5.5
    tracer.record(child, parent=ix)
    assert child.parent == ix
    assert tracer.ancestors(child) == [root]
    # the thread stack is untouched: a live context-manager span is
    # still a root
    with tracer.span("live") as sp:
        pass
    assert sp.parent is None
    # disabled tracer records nothing
    tracer.enabled = False
    ghost = Span("serve/request")
    ghost.start, ghost.end = 7.0, 8.0
    assert tracer.record(ghost) is None
    tracer.enabled = True
    assert len([s for s in tracer.spans() if s.name == "serve/request"]) == 1


def test_chrome_trace_export_roundtrip(tmp_path):
    from trlx_tpu.telemetry import chrome_trace_from_jsonl, export_chrome_jsonl

    tracer = _fresh_tracer()
    with tracer.span("phase/collect", rollouts=8):
        with tracer.span("collect/decode"):
            pass
    jsonl = str(tmp_path / "spans.jsonl")
    # 2 complete events + 2 metadata name events (process + one thread)
    assert export_chrome_jsonl(jsonl, tracer.spans()) == 4
    events = [json.loads(line) for line in open(jsonl) if line.strip()]
    complete = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {
        "phase/collect", "collect/decode",
    }
    for e in complete:
        assert e["dur"] >= 0 and "ts" in e
    # the array wrapper loads as plain JSON (chrome://tracing / Perfetto)
    wrapped = str(tmp_path / "trace.json")
    assert chrome_trace_from_jsonl(jsonl, wrapped) == 4
    doc = json.load(open(wrapped))
    assert len(doc["traceEvents"]) == 4


def test_chrome_trace_metadata_names_threads(tmp_path):
    """The exporter emits chrome `metadata` name events so Perfetto
    tracks carry REAL thread names (main loop vs background writer)
    instead of bare integer tids — and nothing when there are no
    spans."""
    import threading

    from trlx_tpu.telemetry import chrome_trace_events, export_chrome_jsonl

    tracer = _fresh_tracer()
    with tracer.span("phase/collect"):
        pass

    def worker():
        with tracer.span("writer/flush"):
            pass

    t = threading.Thread(target=worker, name="rollout-writer")
    t.start()
    t.join()

    events = chrome_trace_events(tracer.spans())
    meta = [e for e in events if e["ph"] == "M"]
    complete = [e for e in events if e["ph"] == "X"]
    # metadata precedes complete events: process_name + 2 thread_names
    assert [e["ph"] for e in events[: len(meta)]] == ["M"] * len(meta)
    assert len(complete) == 2
    proc = [e for e in meta if e["name"] == "process_name"]
    assert len(proc) == 1 and proc[0]["args"]["name"] == "trlx_tpu"
    thread_meta = {
        e["tid"]: e["args"]["name"]
        for e in meta
        if e["name"] == "thread_name"
    }
    writer_span = tracer.last("writer/flush")
    main_span = tracer.last("phase/collect")
    assert thread_meta[writer_span.thread_id] == "rollout-writer"
    assert thread_meta[main_span.thread_id] == threading.current_thread().name
    # every complete event's tid has a name event
    assert {e["tid"] for e in complete} <= set(thread_meta)
    # no spans -> no events at all (not a lone metadata header)
    assert chrome_trace_events([]) == []
    jsonl = str(tmp_path / "empty.jsonl")
    assert export_chrome_jsonl(jsonl, []) == 0
    assert not os.path.exists(jsonl)


def test_warn_on_span_drops_once(capsys):
    """Nonzero ring evictions warn exactly once on stderr and the count
    is returned for the bench payload — silent drops skew p50s."""
    from trlx_tpu import telemetry

    telemetry._drops_warned = False
    clean = _fresh_tracer(max_records=8)
    with clean.span("a"):
        pass
    assert telemetry.warn_on_span_drops(clean) == 0
    assert capsys.readouterr().err == ""

    tracer = _fresh_tracer(max_records=2)
    for i in range(5):
        with tracer.span(f"s{i}"):
            pass
    assert telemetry.warn_on_span_drops(tracer) == 3
    err = capsys.readouterr().err
    assert "dropped 3 spans" in err
    # second call still returns the count but stays quiet
    assert telemetry.warn_on_span_drops(tracer) == 3
    assert capsys.readouterr().err == ""
    telemetry._drops_warned = False


def test_scoped_tracer_isolates_and_restores_global_history():
    """Harness runs (the perf audit) must neither wipe nor leak into the
    embedding process's span history."""
    from trlx_tpu import telemetry

    outer = telemetry.get_tracer()
    outer_enabled = outer.enabled
    outer.enabled = True
    try:
        with telemetry.span("caller/own"):
            pass
        before = len(outer.spans())
        with telemetry.scoped_tracer() as inner:
            assert telemetry.get_tracer() is inner
            with telemetry.span("audit/phase"):
                pass
            inner.clear()  # the audit's own bookkeeping
            with telemetry.span("audit/measured"):
                pass
            assert [s.name for s in inner.spans()] == ["audit/measured"]
        # caller history untouched; audit spans did not leak
        assert telemetry.get_tracer() is outer
        assert len(outer.spans()) == before
        assert outer.last("audit/measured") is None
        assert outer.last("caller/own") is not None
    finally:
        outer.enabled = outer_enabled


def test_quantile_nearest_rank():
    from trlx_tpu.telemetry import quantile

    durs = sorted(float(x) for x in range(1, 101))
    assert quantile(durs, 0.5) == 51.0  # nearest-rank on 100 samples
    assert quantile(durs, 0.95) == 95.0
    assert quantile([], 0.5) == 0.0


# ----------------------- device metrics (CPU-safe) ----------------------- #


def test_device_metrics_degrade_to_empty_on_cpu():
    from trlx_tpu.telemetry import device_metrics

    stats = device_metrics.device_memory_stats()
    # CPU backends expose no allocator counters; every layer above must
    # degrade to empty dicts rather than raise
    if not stats:
        assert device_metrics.snapshot() == {}
        assert device_metrics.phase_memory_stats() == {}
    else:  # pragma: no cover - real accelerator
        snap = device_metrics.snapshot()
        assert "bytes_in_use" in snap


# ----------------------------- clock satellites -------------------------- #


def test_clock_and_spans_share_monotonic_source():
    from trlx_tpu import telemetry
    from trlx_tpu.utils import Clock

    t0 = telemetry.now()
    clock = Clock()
    time.sleep(0.002)
    ms = clock.tick()
    t1 = telemetry.now()
    # Clock deltas are bounded by the tracer clock read around them —
    # only true when both read the SAME monotonic source
    assert 0.0 < ms <= (t1 - t0) * 1000.0 + 1e-6


def test_logger_times_from_monotonic_and_warns_on_wandb_failure(
    monkeypatch, capsys
):
    import io
    import sys
    import types

    from trlx_tpu.utils.logging import Logger

    broken = types.ModuleType("wandb")

    def _raise(**kwargs):
        raise RuntimeError("no api key")

    broken.init = _raise
    monkeypatch.setitem(sys.modules, "wandb", broken)
    stream = io.StringIO()
    logger = Logger(use_wandb=True, stream=stream)
    err = capsys.readouterr().err
    assert "wandb init failed" in err and "RuntimeError" in err
    assert logger._wandb is None
    logger.log({"losses/total_loss": 1.0}, step=3)
    record = json.loads(stream.getvalue().splitlines()[-1])
    assert record["step"] == 3 and record["time"] >= 0.0
    logger.finish()


# -------------------- perf-budget gate (seeded/clean) -------------------- #


def _rows(collect=400.0, train=120.0, drain=1.0):
    from trlx_tpu.analysis.perf_audit import SpanBudgetRow

    return [
        SpanBudgetRow("phase/collect", 5, collect, collect * 1.2, collect * 5),
        SpanBudgetRow("phase/train", 5, train, train * 1.2, train * 5),
        SpanBudgetRow("train/drain", 5, drain, drain * 1.2, drain * 5),
    ]


def _budgets(tolerance_pct=20.0, abs_slack_ms=0.5, **rows_kwargs):
    from trlx_tpu.analysis.perf_audit import make_perf_budgets

    entry = make_perf_budgets(
        _rows(**rows_kwargs), platform="cpu", tolerance_pct=tolerance_pct
    )
    entry["abs_slack_ms"] = abs_slack_ms
    return {"perf_budgets": {"platforms": {"cpu": entry}}}


def _cpu_entry(budgets):
    return budgets["perf_budgets"]["platforms"]["cpu"]


def test_perf_regression_fires_on_seeded_40pct_slowdown():
    from trlx_tpu.analysis.perf_audit import check_perf_budgets

    budgets = _budgets(tolerance_pct=20.0)
    # seeded drift: the phase loop got 40% slower than the lockfile
    findings = check_perf_budgets(
        _rows(collect=400.0 * 1.4), budgets, platform="cpu"
    )
    assert [f.rule for f in findings] == ["perf-regression"]
    assert findings[0].subject == "phase/collect"
    assert findings[0].severity == "error"
    assert "+40.0%" in findings[0].message


def test_perf_budget_tolerance_absorbs_jitter_clean():
    from trlx_tpu.analysis.perf_audit import check_perf_budgets

    budgets = _budgets(tolerance_pct=20.0)
    # 10% jitter sits inside the 20% tolerance: clean
    assert check_perf_budgets(
        _rows(collect=400.0 * 1.1, train=120.0 * 1.1), budgets, platform="cpu"
    ) == []
    # tiny-span noise: a doubled sub-ms drain is absorbed by the
    # absolute slack floor (relative tolerance alone would flap)
    budgets = _budgets(tolerance_pct=20.0, abs_slack_ms=5.0)
    assert check_perf_budgets(
        _rows(drain=2.0), budgets, platform="cpu"
    ) == []


def test_perf_budget_per_span_tolerance_override():
    from trlx_tpu.analysis.perf_audit import check_perf_budgets

    budgets = _budgets(tolerance_pct=20.0)
    _cpu_entry(budgets)["spans"]["phase/collect"]["tolerance_pct"] = 60.0
    rows = _rows(collect=400.0 * 1.4)
    assert check_perf_budgets(rows, budgets, platform="cpu") == []
    # the override is span-scoped: train at +40% still trips
    rows = _rows(collect=400.0 * 1.4, train=120.0 * 1.4)
    findings = check_perf_budgets(rows, budgets, platform="cpu")
    assert [f.subject for f in findings] == ["phase/train"]


def test_perf_budget_missing_section_platform_mismatch_and_stale():
    from trlx_tpu.analysis.perf_audit import check_perf_budgets

    # no section at all: one actionable finding
    findings = check_perf_budgets(_rows(), {}, platform="cpu")
    assert len(findings) == 1 and "no perf_budgets section" in findings[0].message

    # an unlocked platform refuses comparison outright (wall-clock is
    # never compared across backends) and names the platforms that ARE
    # locked
    budgets = _budgets()
    findings = check_perf_budgets(_rows(), budgets, platform="tpu")
    assert len(findings) == 1 and "not comparable" in findings[0].message
    assert "'cpu'" in findings[0].message

    # missing entry for a measured gated span is an error
    budgets = _budgets()
    del _cpu_entry(budgets)["spans"]["phase/train"]
    findings = check_perf_budgets(_rows(), budgets, platform="cpu")
    assert [f.subject for f in findings] == ["phase/train"]
    assert "no committed perf budget" in findings[0].message

    # a locked entry that is not a gated span warns as stale
    budgets = _budgets()
    _cpu_entry(budgets)["spans"]["phase/legacy"] = {"p50_ms": 1.0}
    findings = check_perf_budgets(_rows(), budgets, platform="cpu")
    assert [f.severity for f in findings] == ["warning"]
    assert "phase/legacy" in findings[0].message


def test_merge_perf_budgets_preserves_reviewer_overrides():
    from trlx_tpu.analysis.perf_audit import (
        make_perf_budgets,
        merge_perf_budgets,
    )

    old = make_perf_budgets(_rows(), platform="cpu", tolerance_pct=300.0)
    old["abs_slack_ms"] = 7.0
    old["spans"]["phase/collect"]["tolerance_pct"] = 99.0
    new = make_perf_budgets(
        _rows(collect=500.0), platform="cpu", tolerance_pct=200.0
    )
    merged = merge_perf_budgets(new, old)
    assert merged["tolerance_pct"] == 300.0
    assert merged["abs_slack_ms"] == 7.0
    assert merged["spans"]["phase/collect"]["tolerance_pct"] == 99.0
    assert merged["spans"]["phase/collect"]["p50_ms"] == 500.0


def test_perf_platform_locks_coexist_and_do_not_cross_inherit():
    """A TPU relock and the CPU CI tripwire live side by side under
    perf_budgets.platforms: relocking one platform must neither touch
    the other's lock nor inherit its tolerance (carrying the CPU 300%
    tripwire onto a TPU lock would silently disable the tight hardware
    gate the relock exists to arm)."""
    from trlx_tpu.analysis.perf_audit import (
        check_perf_budgets,
        make_perf_budgets,
        upsert_perf_budgets,
    )

    budgets = _budgets(tolerance_pct=300.0)  # the cpu tripwire
    _cpu_entry(budgets)["spans"]["phase/collect"]["tolerance_pct"] = 99.0
    upsert_perf_budgets(
        budgets, make_perf_budgets(_rows(collect=40.0), platform="tpu")
    )
    platforms = budgets["perf_budgets"]["platforms"]
    # the tpu entry took the tight hardware default, not cpu's knobs
    assert platforms["tpu"]["tolerance_pct"] == 25.0
    assert "tolerance_pct" not in platforms["tpu"]["spans"]["phase/collect"]
    # the cpu lock (and its reviewer override) survived untouched
    assert platforms["cpu"]["tolerance_pct"] == 300.0
    assert platforms["cpu"]["spans"]["phase/collect"]["tolerance_pct"] == 99.0
    # and each platform gates against ITS entry
    assert check_perf_budgets(_rows(), budgets, platform="cpu") == []
    tripped = check_perf_budgets(
        _rows(collect=400.0), budgets, platform="tpu"
    )
    assert any(f.subject == "phase/collect" for f in tripped)


def test_perf_span_count_drift_warns():
    """Duplicated/renamed instrumentation halves per-fire p50s and would
    dodge the p50 gate — the per-phase count cross-check must warn."""
    from trlx_tpu.analysis.perf_audit import check_perf_budgets

    budgets = _budgets()  # counts locked at 5 over 5 phases (1/phase)
    rows = _rows()
    doubled = [
        type(r)(r.subject, 10 if r.subject == "phase/train" else r.count,
                r.p50_ms, r.p95_ms, r.total_ms)
        for r in rows
    ]
    findings = check_perf_budgets(
        doubled, budgets, platform="cpu", phases=5
    )
    assert [f.severity for f in findings] == ["warning"]
    assert findings[0].subject == "phase/train"
    assert "per phase" in findings[0].message
    # same per-phase rate at a different measured phase count is clean
    tripled = [
        type(r)(r.subject, r.count // 5 * 3, r.p50_ms, r.p95_ms, r.total_ms)
        for r in rows
    ]
    assert check_perf_budgets(
        tripled, budgets, platform="cpu", phases=3
    ) == []


def test_perf_relock_preserves_other_engine_sections(tmp_path):
    from trlx_tpu.analysis.perf_audit import (
        make_perf_budgets,
        upsert_perf_budgets,
    )
    from trlx_tpu.analysis.resource_audit import load_budgets, write_budgets

    path = str(tmp_path / "budgets.json")
    write_budgets(
        {
            "schema_version": 1,
            "mesh": {"dp": 2},
            "programs": {"ppo.train_step": {"peak_hbm_bytes": 123}},
            "compile_budgets": {"mesh": {"dp": 2}, "programs": {}},
        },
        path,
    )
    budgets = load_budgets(path)
    upsert_perf_budgets(budgets, make_perf_budgets(_rows(), platform="cpu"))
    write_budgets(budgets, path)
    again = load_budgets(path)
    # the perf section rides alongside engines 6-8's sections untouched
    assert again["programs"]["ppo.train_step"]["peak_hbm_bytes"] == 123
    assert "compile_budgets" in again
    entry = again["perf_budgets"]["platforms"]["cpu"]
    assert entry["spans"]["phase/collect"]["p50_ms"] == 400.0


def test_committed_lockfile_has_perf_section():
    """The shipped budgets.json must carry a perf_budgets section with
    every gated span — the CI job checks against THIS file."""
    from trlx_tpu.analysis.perf_audit import GATED_SPANS
    from trlx_tpu.analysis.resource_audit import (
        default_budgets_path,
        load_budgets,
    )

    budgets = load_budgets(default_budgets_path())
    entry = budgets["perf_budgets"]["platforms"]["cpu"]
    for name in GATED_SPANS:
        assert entry["spans"][name]["p50_ms"] > 0.0


# -------------------- streamed-phase span tree (live) -------------------- #


def _ancestor_indices(span, by_index):
    out = set()
    parent = span.parent
    while parent is not None and parent in by_index:
        out.add(parent)
        parent = by_index[parent].parent
    return out


@pytest.mark.slow
def test_streamed_phase_span_tree_shape():
    """One live streamed phase: with phase_overlap on, every epoch-1
    dispatch span must sit STRICTLY inside the phase/collect span (the
    overlap, visible in the trace), and the drain/residual spans inside
    phase/train after collection ended.

    Nightly tier: the trainer build + two phases cost ~30 s of compile
    (ROADMAP tier-1 budget note); the tier-1 canary for the live
    instrumentation is test_collect_span_clean_inside_enclosing_except
    (no model build) plus the phase-overlap suite, which runs the same
    instrumented code bitwise."""
    from trlx_tpu import telemetry
    from trlx_tpu.analysis.perf_audit import run_perf_phases

    tracer = telemetry.get_tracer()
    rows, records = run_perf_phases(phases=1, warmup=1)
    by_name = {}
    for s in records:
        by_name.setdefault(s.name, []).append(s)
    collect = by_name["phase/collect"][0]
    train = by_name["phase/train"][0]
    drain = by_name["train/drain"][0]
    dispatches = by_name["train/epoch1_dispatch"]
    # 24 rollouts / batch 8 = 3 epoch-1 minibatches, all dispatchable
    # during collection under the arrival-block plan
    assert len(dispatches) == 3
    by_index = {s.index: s for s in records}
    for d in dispatches:
        # strictly inside the collect window, and a descendant of it
        assert collect.start < d.start and d.end < collect.end
        assert collect.index in _ancestor_indices(d, by_index)
    # the train phase begins after collection and nests drain + residual
    assert train.start >= collect.end
    assert train.start <= drain.start and drain.end <= train.end
    residual = by_name["train/residual"][0]
    assert train.start <= residual.start and residual.end <= train.end
    # the measured rows cover the gated spans
    assert {r.subject for r in rows} >= {
        "phase/collect", "phase/train", "train/drain",
    }
    # chunk-level sub-spans landed inside collect as well
    for name in ("collect/prompt_draw", "collect/decode", "collect/score"):
        assert name in by_name
    assert tracer is telemetry.get_tracer()  # global tracer untouched


def test_collect_span_clean_inside_enclosing_except(monkeypatch):
    """make_experience called from inside an except handler (the retry
    path its docstring invites) must close a CLEAN collect span as
    status=ok — sys.exc_info() in a finally would see the enclosing
    handled exception and mislabel it (the PR-4 api.train hazard)."""
    from types import SimpleNamespace

    from trlx_tpu import telemetry
    from trlx_tpu.orchestrator.ppo_orchestrator import PPOOrchestrator

    tracer = telemetry.configure(enabled=True)
    tracer.clear()

    # a stub orchestrator whose collection loop is a no-op: collected
    # reaches num_rollouts immediately via a zero-rollout request
    orch = object.__new__(PPOOrchestrator)
    orch.trainer = SimpleNamespace(
        config=SimpleNamespace(method=SimpleNamespace()),
        mean_kl=0.0,
        logger=None,
        on_rollouts_landed=None,
    )
    orch._rollout_writer = None
    orch._loader = iter([])
    orch._dispatch_chunk = lambda: (
        SimpleNamespace(input_ids=[]), {}, None, None, 0.0
    )
    try:
        raise RuntimeError("outer handled failure")
    except RuntimeError:
        try:
            orch.make_experience(num_rollouts=0, iter_count=0)
        except Exception:
            pass  # stats math on zero rollouts may fail; span closed first
    span = tracer.last("phase/collect")
    assert span is not None and span.status == "ok"


@pytest.mark.slow
def test_perf_audit_end_to_end_sleep_injected_trip(tmp_path):
    """Full --perf-audit flow against its own lockfile: a clean relock
    passes, and a sleep-injected slowdown (the planted regression) trips
    perf-regression — the seeded/clean pair at the CLI-API level."""
    from trlx_tpu.analysis.perf_audit import audit_perf
    from trlx_tpu.analysis.resource_audit import load_budgets, write_budgets

    path = str(tmp_path / "budgets.json")
    span_log = str(tmp_path / "spans.jsonl")
    report, rows = audit_perf(
        budgets_path=path, update=True, phases=3, warmup=1,
        span_log=span_log,
    )
    assert report.findings == []
    assert os.path.exists(span_log)
    budgets = load_budgets(path)
    locked = budgets["perf_budgets"]["platforms"]["cpu"]["spans"]["phase/collect"]["p50_ms"]
    # tighten the relocked tolerance enough that the planted slowdown
    # must trip, but loose enough that shared-runner jitter between two
    # adjacent clean runs cannot (the sleep below is sized to clear the
    # bound by a wide margin)
    budgets["perf_budgets"]["platforms"]["cpu"]["tolerance_pct"] = 100.0
    budgets["perf_budgets"]["platforms"]["cpu"]["abs_slack_ms"] = 25.0
    write_budgets(budgets, path)

    clean_report, _ = audit_perf(budgets_path=path, phases=3, warmup=1)
    assert [f.rule for f in clean_report.findings if f.severity == "error"] == []

    # per-phase sleep far past the 100% + 25 ms bound: 3x the locked
    # collect p50 plus a hard floor
    slow_report, _ = audit_perf(
        budgets_path=path, phases=3, warmup=1,
        slowdown_ms=max(500.0, 3.0 * locked),
    )
    tripped = [f for f in slow_report.findings if f.rule == "perf-regression"]
    assert any(f.subject == "phase/collect" for f in tripped)


# ------------------------------ profiler -------------------------------- #


def test_phase_profiler_window_produces_loadable_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    from trlx_tpu.telemetry.profiler import PhaseProfiler

    prof = PhaseProfiler(str(tmp_path), target_phase=1)
    prof.on_phase_start(0)  # not the target: no trace
    assert not prof.active
    prof.on_phase_start(1)
    assert prof.active
    out = jax.jit(lambda a: a * 2)(jnp.ones((8, 8)))
    prof.on_phase_end(sync=out)
    assert prof.done and not prof.active
    artifacts = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert artifacts, "profile_phase window must dump an xplane trace"
    assert os.path.getsize(artifacts[0]) > 0
    # exactly one window per run: a later matching phase does not rearm
    prof.on_phase_start(1)
    assert not prof.active


def test_phase_profiler_close_is_crash_safe(tmp_path):
    from trlx_tpu.telemetry.profiler import PhaseProfiler

    prof = PhaseProfiler(str(tmp_path), target_phase=0)
    prof.on_phase_start(0)
    assert prof.active
    prof.close()  # exception epilogue: must stop the live trace
    assert not prof.active
    prof.close()  # idempotent


def test_profile_phase_keeps_streaming_eligible():
    """Turning the profiler on must not change the schedule it
    measures: profile_dir alone (a window over phase 0) and
    profile_phase both leave the streamed phase eligible. The gate
    reads only config/orch, so a stub trainer suffices — no model
    build."""
    from types import SimpleNamespace

    from trlx_tpu.analysis import harness
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    config = TRLConfig.from_dict(harness.tiny_config_dict("ppo"))
    stub = SimpleNamespace(config=config, orch=object())
    eligible = lambda: PPOTrainer._stream_eligible(stub, 0)  # noqa: E731
    assert eligible()
    from trlx_tpu.telemetry.profiler import PhaseProfiler

    config.train.profile_dir = "/tmp/prof"
    assert eligible()  # profile_dir alone: phase 0, same schedule
    alone = PhaseProfiler(config.train.profile_dir, config.train.profile_phase)
    assert alone.enabled and alone.target == 0
    config.train.profile_phase = 3
    assert eligible()  # windowed: streaming stays on
    assert PhaseProfiler("/tmp/prof", 3).target == 3
    assert not PhaseProfiler(None, None).enabled
