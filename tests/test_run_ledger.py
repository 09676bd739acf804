"""Metrics registry, run ledger + --compare/--watch, serving histograms,
counter-track export, flight-recorder metrics embedding (PR 12; the
utilization table it came with went with its last caller, PR 60).

All tier-1-cheap: pure host-side units — no trainer builds, no jit
compiles (the heaviest fixture is a FlightRecorder dict).
"""

import json
import os

import pytest

os.environ.setdefault("WANDB_DISABLED", "1")


# --------------------------- registry units ------------------------------ #


def _fresh_registry(**kwargs):
    from trlx_tpu.telemetry.metrics import MetricsRegistry

    return MetricsRegistry(enabled=True, **kwargs)


def test_counter_gauge_histogram_basics():
    reg = _fresh_registry()
    reg.counter("requests").inc()
    reg.counter("requests").inc(2)
    reg.gauge("slot_util").set(0.5)
    reg.gauge("slot_util").set(0.75)
    for v in (10.0, 20.0, 30.0, 40.0):
        reg.histogram("latency_ms").observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["requests"] == 3.0
    assert snap["gauges"]["slot_util"] == 0.75
    h = snap["histograms"]["latency_ms"]
    assert h["count"] == 4 and h["mean"] == 25.0
    assert h["min"] == 10.0 and h["max"] == 40.0
    assert h["p50"] in (20.0, 30.0)  # nearest-rank
    # gauges carry a timeseries on the shared clock (newest last)
    series = reg.gauge_series()
    assert [v for _, v in series["slot_util"]] == [0.5, 0.75]
    t0, t1 = series["slot_util"][0][0], series["slot_util"][1][0]
    assert t1 >= t0 > 0.0


def test_registry_type_conflict_raises():
    reg = _fresh_registry()
    reg.counter("x")
    with pytest.raises(TypeError, match="one name, one type"):
        reg.gauge("x")


def test_disabled_registry_is_shared_null_instrument():
    from trlx_tpu.telemetry.metrics import NULL_INSTRUMENT

    reg = _fresh_registry()
    reg.enabled = False
    c = reg.counter("a")
    g = reg.gauge("b")
    # one shared singleton — no allocation, no record, no stats
    assert c is NULL_INSTRUMENT and g is NULL_INSTRUMENT
    c.inc()
    g.set(5.0)
    reg.histogram("h").observe(1.0)
    reg.enabled = True
    snap = reg.snapshot()
    assert snap == {"counters": {}, "gauges": {}, "histograms": {}}
    # disabled absorb is a no-op too
    reg.enabled = False
    assert reg.absorb({"k": 1.0}) == 0


def test_absorb_sets_gauges_and_skips_non_numeric():
    reg = _fresh_registry()
    n = reg.absorb(
        {
            "async/learner_idle_ms": 12.5,
            "engine/slot_util": 0.9,
            "note": "a string",
            "flag": True,  # bools are not gauges
        }
    )
    assert n == 2
    snap = reg.snapshot()
    assert snap["gauges"] == {
        "async/learner_idle_ms": 12.5,
        "engine/slot_util": 0.9,
    }


def test_scoped_metrics_isolates_and_restores():
    from trlx_tpu import telemetry

    outer = telemetry.get_metrics()
    was_enabled = outer.enabled
    outer.enabled = True
    try:
        outer.counter("caller/own").inc()
        before = outer.snapshot()
        with telemetry.scoped_metrics() as inner:
            assert telemetry.get_metrics() is inner
            inner.counter("audit/thing").inc(7)
        assert telemetry.get_metrics() is outer
        assert outer.snapshot() == before
        assert "audit/thing" not in outer.snapshot()["counters"]
    finally:
        outer.enabled = was_enabled


def test_flatten_snapshot():
    from trlx_tpu.telemetry.metrics import flatten_snapshot

    flat = flatten_snapshot(
        {
            "counters": {"c": 2.0},
            "gauges": {"g": 0.5},
            "histograms": {"h": {"count": 3, "p50": 9.0}},
        }
    )
    assert flat == {"c": 2.0, "g": 0.5, "h/count": 3.0, "h/p50": 9.0}
    assert flatten_snapshot(None) == {}


# ------------------------ counter-track export --------------------------- #


def test_chrome_counter_events_and_jsonl_export(tmp_path):
    from trlx_tpu.telemetry import (
        chrome_counter_events,
        chrome_trace_from_jsonl,
        export_chrome_jsonl,
    )
    from trlx_tpu.telemetry.tracer import Tracer

    tracer = Tracer(enabled=True)
    with tracer.span("phase/collect"):
        pass
    series = {
        "mem/hbm_live": [(1.0, 100.0), (2.0, 250.0)],
        "engine/slot_util": [(1.5, 0.75)],
    }
    events = chrome_counter_events(series)
    assert [e["ph"] for e in events] == ["C", "C", "C"]
    # sorted by name, samples in order; ts in microseconds
    assert events[0]["name"] == "engine/slot_util"
    assert events[1]["name"] == "mem/hbm_live"
    assert events[1]["ts"] == 1.0e6 and events[1]["args"]["value"] == 100.0

    jsonl = str(tmp_path / "trace.jsonl")
    # 1 complete + 2 metadata + 3 counter events ride one file
    n = export_chrome_jsonl(jsonl, tracer.spans(), counters=series)
    lines = [json.loads(l) for l in open(jsonl) if l.strip()]
    assert len(lines) == n
    counter_lines = [e for e in lines if e["ph"] == "C"]
    assert {e["name"] for e in counter_lines} == set(series)
    # the array wrapper still loads the mixed stream
    wrapped = str(tmp_path / "trace.json")
    assert chrome_trace_from_jsonl(jsonl, wrapped) == n


def test_registry_gauge_series_feeds_counter_export():
    from trlx_tpu.telemetry import chrome_counter_events

    reg = _fresh_registry()
    reg.gauge("mem/hbm_live_bytes").set(2**20)
    reg.gauge("mem/hbm_live_bytes").set(2**21)
    reg.counter("not_a_gauge").inc()
    events = chrome_counter_events(reg.gauge_series())
    assert len(events) == 2
    assert all(e["name"] == "mem/hbm_live_bytes" for e in events)
    assert events[0]["args"]["value"] == 2**20


# ----------------------------- run ledger --------------------------------- #


def _manifest(run_id, value, p50, mfu):
    from trlx_tpu.telemetry.run_ledger import build_manifest

    return build_manifest(
        "bench",
        run_id=run_id,
        config={"train": {"seed": 1}},
        payload={"value": value},
        span_stats={
            "phase/collect": {"count": 5, "p50_ms": p50, "total_ms": 5 * p50}
        },
        metrics={"counters": {}, "gauges": {"slot_util": 0.8},
                 "histograms": {}},
        attribution=[{"program": "ppo.train_step", "mfu": mfu}],
        health_events={"kl-spike": 1},
    )


def test_ledger_append_compare_roundtrip(tmp_path):
    from trlx_tpu.telemetry import run_ledger as RL

    path = str(tmp_path / "ledger.jsonl")
    RL.append_manifest(_manifest("run_a", 160.0, 800.0, 0.28), path)
    RL.append_manifest(_manifest("run_b", 176.0, 700.0, 0.31), path)
    runs = RL.load_ledger(path)
    assert [r["run_id"] for r in runs] == ["run_a", "run_b"]
    # manifests self-identify
    assert runs[0]["schema_version"] == RL.SCHEMA_VERSION
    assert runs[0]["fingerprint"]
    assert runs[0]["health_events"] == {"kl-spike": 1}

    # resolution: run_id, back-references, bare index, ledger path
    assert RL.resolve_run("run_a", path)["payload"]["value"] == 160.0
    assert RL.resolve_run("~1", path)["run_id"] == "run_b"
    assert RL.resolve_run("prev", path)["run_id"] == "run_a"
    assert RL.resolve_run("last", path)["run_id"] == "run_b"
    assert RL.resolve_run("0", path)["run_id"] == "run_a"
    assert RL.resolve_run(path)["run_id"] == "run_b"
    with pytest.raises(ValueError, match="not found"):
        RL.resolve_run("nope", path)

    text = RL.compare_runs(
        RL.resolve_run("run_a", path), RL.resolve_run("run_b", path)
    )
    assert "run_a" in text and "run_b" in text
    # movers ranked by relative delta with signed percentages
    assert "value" in text and "+10.0%" in text
    assert "span/phase/collect_p50_ms" in text and "-12.5%" in text
    # attribution MFU section
    assert "ppo.train_step" in text and "0.28" in text and "0.31" in text


def test_ledger_skips_torn_lines_and_flags_mismatches(tmp_path):
    from trlx_tpu.telemetry import run_ledger as RL

    path = str(tmp_path / "ledger.jsonl")
    RL.append_manifest(_manifest("ok_run", 1.0, 10.0, 0.1), path)
    with open(path, "a") as fh:
        fh.write('{"torn": ')  # the run died mid-append
    runs = RL.load_ledger(path)
    assert len(runs) == 1 and runs[0]["run_id"] == "ok_run"

    a = _manifest("a", 1.0, 10.0, 0.1)
    b = _manifest("b", 1.0, 10.0, 0.1)
    b["fingerprint"] = "deadbeef0000"
    text = RL.compare_runs(a, b)
    assert "fingerprints differ" in text
    b2 = _manifest("b2", 1.0, 10.0, 0.1)
    b2["platform"] = {"backend": "tpu", "device_kind": "TPU v5 lite"}
    a["platform"] = {"backend": "cpu", "device_kind": "cpu"}
    assert "device kinds differ" in RL.compare_runs(a, b2)


def test_compare_cli_end_to_end(tmp_path, capsys):
    from trlx_tpu.telemetry import run_ledger as RL
    from trlx_tpu.telemetry.__main__ import main

    path = str(tmp_path / "ledger.jsonl")
    RL.append_manifest(_manifest("run_a", 100.0, 500.0, 0.2), path)
    RL.append_manifest(_manifest("run_b", 90.0, 600.0, 0.18), path)
    assert main(["--compare", "~2", "~1", "--ledger", path]) == 0
    out = capsys.readouterr().out
    assert "movers" in out and "run_a" in out and "run_b" in out
    # --json emits machine-readable deltas
    assert main(["--compare", "run_a", "run_b", "--ledger", path,
                 "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["run_a"] == "run_a"
    assert doc["deltas"]["value"] == {"a": 100.0, "b": 90.0}
    # unresolvable runs exit 2 with a message, not a traceback
    assert main(["--compare", "x", "y", "--ledger", path]) == 2


def test_watch_renders_live_phase_rows(tmp_path, capsys):
    from trlx_tpu.telemetry import run_ledger as RL
    from trlx_tpu.telemetry.__main__ import main

    run_dir = str(tmp_path / "run")
    writer = RL.PhaseLogWriter(run_dir)
    writer.append(
        {
            "phase": 0,
            "step": 4,
            "stats": {"losses/total_loss": 0.5},
            "spans": {"phase/collect": {"p50_ms": 120.0}},
            "memory": {},
            "events": [],
        }
    )
    writer.append(
        {
            "phase": 1,
            "step": 8,
            "stats": {"losses/total_loss": 0.4},
            "spans": {"phase/collect": {"p50_ms": 130.0}},
            "memory": {"peak_bytes_in_use": 3 * 2**30},
            "events": [{"detector": "kl-spike", "severity": "error"}],
        }
    )
    n = RL.watch(run_dir, follow=False)
    assert n == 2
    capsys.readouterr()  # drop the direct call's output
    assert main(["--watch", run_dir, "--no-follow"]) == 0
    out = capsys.readouterr().out
    lines = [l for l in out.splitlines() if l.strip()]
    assert len(lines) == 2
    assert "phase    0" in lines[0] and "total_loss=0.5" in lines[0]
    assert "collect=120ms" in lines[0]
    assert "events: kl-spike" in lines[1] and "hbm_peak=3.00G" in lines[1]
    # a missing run dir is exit 2, not a traceback
    assert main(["--watch", str(tmp_path / "nope"), "--no-follow"]) == 2


# --------------------------- serving histograms --------------------------- #


def test_serving_request_metrics_keys_and_math():
    from trlx_tpu.inference.server import (
        SERVE_HISTOGRAMS,
        observe_request_metrics,
    )

    reg = _fresh_registry()
    timing = {
        "queue_wait_ms": 5.0,
        "prefill_ms": 12.0,
        "ttft_ms": 17.0,
        "decode_ms": 96.0,
        "e2e_ms": 113.0,
    }
    observe_request_metrics(reg, timing, tokens=48)
    observe_request_metrics(reg, dict(timing, decode_ms=48.0), tokens=0)
    snap = reg.snapshot()
    for key in SERVE_HISTOGRAMS:
        assert snap["histograms"][key]["count"] == 2, key
    h = snap["histograms"]["serve/decode_per_token_ms"]
    # 96 ms / 48 tokens = 2 ms/token; zero tokens clamps the divisor
    assert h["min"] == pytest.approx(2.0)
    assert h["max"] == pytest.approx(48.0)
    assert snap["counters"]["serve/requests_completed"] == 2.0


def test_engine_request_timing_decomposition():
    """pop_request_timing math on a hand-built marks dict — the engine's
    host loop writes these marks; the decomposition must tie out."""
    from trlx_tpu.inference.engine import ContinuousBatchingEngine

    eng = object.__new__(ContinuousBatchingEngine)
    eng._req_times = {
        7: {
            "submitted": 10.0,
            "admitted": 10.2,
            "first_token": 10.5,
            "completed": 12.0,
        },
        8: {"submitted": 10.0},  # still decoding: no timing yet
    }
    t = eng.pop_request_timing(7)
    assert t["queue_wait_ms"] == pytest.approx(200.0)
    assert t["prefill_ms"] == pytest.approx(300.0)
    assert t["ttft_ms"] == pytest.approx(500.0)
    assert t["decode_ms"] == pytest.approx(1500.0)
    assert t["e2e_ms"] == pytest.approx(2000.0)
    assert 7 not in eng._req_times  # popped: one report per request
    assert eng.pop_request_timing(7) is None
    assert eng.pop_request_timing(8) is None
    assert eng.pop_request_timing(99) is None


# ------------------- flight recorder metrics embedding -------------------- #


def test_flight_record_embeds_metrics_and_inspect_renders(tmp_path):
    from trlx_tpu import telemetry
    from trlx_tpu.telemetry.flight_recorder import (
        FlightRecorder,
        inspect_dump,
        load_dump,
    )

    with telemetry.scoped_metrics() as reg:
        reg.gauge("engine/slot_util").set(0.85)
        reg.counter("serve/requests_completed").inc(6)
        reg.histogram("serve/ttft_ms").observe(42.0)
        recorder = FlightRecorder(
            capacity=4, directory=str(tmp_path), fingerprint="cafe01"
        )
        recorder.record_phase(
            0, step=1, stats_row={"losses/total_loss": 0.4}
        )
        path = recorder.dump("test-reason")
    payload = load_dump(path)
    rec = payload["phases"][-1]
    assert rec["metrics"]["gauges"]["engine/slot_util"] == 0.85
    assert rec["metrics"]["counters"]["serve/requests_completed"] == 6.0
    text = inspect_dump(payload)
    assert "metrics snapshot (final phase)" in text
    assert "engine/slot_util" in text
    assert "serve/ttft_ms" in text and "n=1" in text
