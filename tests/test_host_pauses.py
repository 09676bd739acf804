"""A pause of the host has a name (ISSUE 60): the collector's hook and its
counters, the span ``phase/begin``, and the ``host-stall`` detector over
the timing rows of the phase loop and the serving loop. CPU, tiny sizes;
every time here is a CPU time and stands under no device metric's name."""

import builtins
import gc
import time

import numpy as np
import pytest

from trlx_tpu import telemetry
from trlx_tpu.telemetry import health as health_mod
from trlx_tpu.telemetry.health import HealthConfig, HealthMonitor


@pytest.fixture
def scoped():
    """A fresh tracer and registry, the hook installed."""
    telemetry.watch_host()
    with telemetry.scoped_tracer() as tracer, telemetry.scoped_metrics() as registry:
        yield tracer, registry


def _counters(registry):
    return registry.snapshot()["counters"]


# ------------------------------- the hook -------------------------------- #


def test_full_collection_is_counted_and_is_a_span_under_the_open_one(scoped):
    tracer, registry = scoped
    with telemetry.span("phase/outer") as outer:
        gc.collect(2)
    counters = _counters(registry)
    assert counters["host/gc_pauses[gen=2]"] == 1.0
    assert counters["host/gc_pauses"] >= 1.0
    assert counters["host/gc_ms"] >= counters["host/gc_ms[gen=2]"] > 0.0
    longest = registry.snapshot()["gauges"]["host/gc_max_ms"]
    assert longest >= counters["host/gc_ms[gen=2]"]
    (full,) = [s for s in tracer.spans("host/gc") if s.attrs["generation"] == 2]
    assert full.parent == outer.index and full.depth == outer.depth + 1
    assert "collected" in full.attrs
    assert outer.start <= full.start and full.end <= outer.end
    # the span is the pause the counter took, to the hook's own few microseconds
    assert full.duration_ms == pytest.approx(counters["host/gc_ms[gen=2]"], abs=1.0)


def test_with_the_tracer_off_the_counters_advance_and_no_span_is_kept(scoped):
    tracer, registry = scoped
    tracer.enabled = False
    gc.collect(2)
    assert _counters(registry)["host/gc_pauses[gen=2]"] == 1.0
    assert tracer.spans() == [] and tracer.current() is None


def test_watch_host_twice_installs_one_hook():
    telemetry.watch_host()
    telemetry.watch_host()
    assert gc.callbacks.count(telemetry._on_gc) == 1


def test_the_longest_pause_survives_a_cleared_registry(scoped):
    _, registry = scoped
    gc.collect(2)
    longest = registry.snapshot()["gauges"]["host/gc_max_ms"]
    assert longest > 0.0
    gc.disable()  # (the test's, so that no collection falls between the two lines)
    try:
        registry.clear()
        assert registry.snapshot()["gauges"] == {}
    finally:
        gc.enable()
    gc.collect(0)  # the next collection of any generation sets it again
    assert registry.snapshot()["gauges"]["host/gc_max_ms"] >= longest


def test_touched_counters_read_zero_after_a_clear(scoped):
    _, registry = scoped
    registry.clear()
    telemetry.touch_host_counters()
    counters = _counters(registry)
    assert set(telemetry.HOST_COUNTERS) <= set(counters)
    assert counters["host/stalls"] == counters["host/stall_ms"] == 0.0
    assert "host/gc_max_ms" in registry.snapshot()["gauges"]


def test_a_raising_tracer_loses_the_span_and_nothing_else(scoped, monkeypatch):
    tracer, registry = scoped

    def refuse(*a, **k):
        raise RuntimeError("no spans today")

    monkeypatch.setattr(tracer, "span", refuse)
    monkeypatch.setattr(tracer, "record", refuse)
    before = telemetry._host.gc_ms
    gc.collect(2)
    assert _counters(registry)["host/gc_pauses[gen=2]"] == 1.0
    assert telemetry._host.gc_ms > before
    assert tracer.spans("host/gc") == []


@pytest.mark.parametrize("ms, kept", [(0.4, 0), (1.5, 1)])
def test_a_young_collection_is_stamped_only_where_it_lasted(scoped, monkeypatch, ms, kept):
    tracer, registry = scoped
    clock = iter([10.0, 10.0 + ms / 1e3])
    monkeypatch.setattr(telemetry, "monotonic", lambda: next(clock))
    telemetry._on_gc("start", {"generation": 0})
    telemetry._on_gc("stop", {"generation": 0, "collected": 3, "uncollectable": 0})
    assert _counters(registry)["host/gc_ms[gen=0]"] == pytest.approx(ms)
    spans = tracer.spans("host/gc")
    assert len(spans) == kept
    if kept:
        assert spans[0].attrs == {"generation": 0, "collected": 3}
        assert spans[0].duration_ms == pytest.approx(ms)


def test_a_collection_inside_the_tracers_own_lock_does_not_deadlock(scoped):
    """The collector runs wherever the thread stands, also inside the
    tracer's or the registry's locked sections: both locks are reentrant."""
    tracer, registry = scoped
    with tracer._lock, registry._lock:
        gc.collect(2)
    assert len(tracer.spans("host/gc")) == 1


def test_host_mark_reads_what_passed_between_take_and_fill(scoped):
    tracer, _ = scoped
    monitor = HealthMonitor(HealthConfig(enabled=True))
    series = monitor.timing_series("time/phase_ms", ("collect/score", "train/drain"))
    mark = telemetry.HostMark(series.parts, wall=("phase/collect", "phase/train"))
    with tracer.span("collect/score"):
        pass  # before the mark: not the phase's
    mark.take()
    with tracer.span("phase/collect"):
        with tracer.span("collect/score"):
            time.sleep(0.02)
        gc.collect(2)
    with tracer.span("phase/train"):
        pass
    wall = mark.fill(series)
    assert wall == pytest.approx(
        tracer.last("phase/collect").duration_ms + tracer.last("phase/train").duration_ms)
    assert series.values[0] == pytest.approx(tracer.last("collect/score").duration_ms)
    assert series.values[1] == 0.0
    assert series.gc_ms > 0.0 and series.compile_ms == 0.0
    assert 0.0 <= series.cpu_share < 1.0  # it slept
    row = series.row(wall)
    assert row["time/phase_ms"] == wall and row["time/collect/score_ms"] == series.values[0]
    assert {"time/gc_ms", "time/compile_ms", "time/cpu_share"} <= set(row)


# ------------------------------ the detector ------------------------------ #

PARTS = ("phase/begin", "collect/wait", "collect/score", "train/drain")


def _feed(monitor, series, wall, parts=(4.0, 2000.0, 20.0, 1000.0), gc_ms=0.0,
          compile_ms=0.0, cpu=0.5, phase=0):
    series.values[:] = parts
    series.gc_ms, series.compile_ms, series.cpu_share = gc_ms, compile_ms, cpu
    return monitor.observe_timing(series, wall, phase=phase)


def _warm(monitor, n=4, wall=3026.0):
    series = monitor.timing_series("time/phase_ms", PARTS)
    for phase in range(n):
        assert _feed(monitor, series, wall, phase=phase) is None
    return series


def test_host_stall_names_the_part_that_grew(scoped):
    _, registry = scoped
    monitor = HealthMonitor(HealthConfig(enabled=True))
    series = _warm(monitor, n=16)
    event = _feed(monitor, series, 4511.0, parts=(4.0, 3480.0, 25.0, 1000.0),
                  cpu=0.03, phase=17)
    assert event is not None and event.detector == "host-stall"
    assert event.severity == "warning" and event.phase == 17
    assert event.message == (
        "phase 17 4511 ms against 3026; collect/wait +1480; "
        "gc 0 ms, compile 0 ms, cpu_share 0.03")
    assert monitor.event_counts == {"host-stall": 1}
    counters = _counters(registry)
    assert counters["host/stalls"] == counters["host/stalls[by=collect/wait]"] == 1.0
    assert counters["host/stall_ms"] == pytest.approx(1485.0)
    assert counters["host/stall_ms[by=collect/wait]"] == pytest.approx(1485.0)


def test_host_stall_names_gc_where_the_collector_covers_the_excess(scoped):
    _, registry = scoped
    monitor = HealthMonitor(HealthConfig(enabled=True))
    series = _warm(monitor)
    event = _feed(monitor, series, 4000.0, parts=(900.0, 2040.0, 20.0, 1000.0), gc_ms=890.0)
    assert "gc +890 under phase/begin" in event.message
    assert _counters(registry)["host/stall_ms[by=gc]"] == pytest.approx(974.0)


def test_a_compile_is_counted_and_leaves_no_event(scoped):
    _, registry = scoped
    monitor = HealthMonitor(HealthConfig(enabled=True))
    series = _warm(monitor)
    assert _feed(monitor, series, 9000.0, compile_ms=5900.0) is None
    assert monitor.events == []
    assert _counters(registry)["host/stalls[by=compile]"] == 1.0


@pytest.mark.parametrize("case", ["steady", "small_pause", "before_warmup", "inside_cooldown",
                                  "disabled"])
def test_host_stall_stays_quiet(scoped, case):
    config = HealthConfig.from_dict({
        "enabled": True, "cooldown": 4,
        "disable": ["host-stall"] if case == "disabled" else [],
    })
    monitor = HealthMonitor(config)
    if case == "disabled":
        assert monitor.timing_series("time/phase_ms", PARTS) is None
        return
    if case == "before_warmup":
        series = monitor.timing_series("time/phase_ms", PARTS)
        # a first phase that compiled, two steady ones: the level is the least
        for wall in (40000.0, 3026.0, 3030.0):
            assert _feed(monitor, series, wall) is None
        assert series.level == 3026.0
        assert _feed(monitor, series, 4511.0) is not None  # armed from the fourth
        return
    series = _warm(monitor)
    if case == "steady":
        for wall in (3030.0, 3020.0, 3060.0, 3026.0 * 1.2):
            assert _feed(monitor, series, wall) is None
    elif case == "small_pause":
        # a 130 ms collection in a 3 s phase is in the counters, not an event
        assert _feed(monitor, series, 3156.0, gc_ms=130.0) is None
    else:
        assert _feed(monitor, series, 4511.0) is not None
        # inside the cooldown (this series' own observations) a trip is
        # counted, leaves no event and moves the level halfway to it
        assert _feed(monitor, series, 4511.0) is None
        assert series.level == pytest.approx(3026.0 * (1 + 0.25 * monitor._alpha) / 2 + 4511.0 / 2)
        assert _feed(monitor, series, 4511.0) is None  # and now it is how the series runs
        assert _counters(scoped[1])["host/stalls"] == 2.0
        for _ in range(3):
            assert _feed(monitor, series, 4511.0) is None
        assert _feed(monitor, series, 9000.0) is not None  # past the cooldown, a new stall
    assert len(monitor.events) == (2 if case == "inside_cooldown" else 0)


def test_a_short_step_trips_on_min_ms_not_on_the_ratio(scoped):
    monitor = HealthMonitor(HealthConfig(enabled=True))
    series = monitor.timing_series("time/iter_ms[class=step]", ("engine/dispatch",))
    for _ in range(4):
        series.values[0] = 0.3
        assert monitor.observe_timing(series, 12.0, step=1) is None
    series.values[0] = 0.3
    assert monitor.observe_timing(series, 40.0, step=5) is None  # 3x, yet under 50 ms
    series.values[0] = 75.3
    event = monitor.observe_timing(series, 87.0, step=6)
    assert event.message.startswith("iteration 6 87 ms against 18.79;")
    assert "engine/dispatch +75" in event.message and event.phase is None


def test_the_detectors_knobs_are_tunable_like_the_rest():
    config = HealthConfig.from_dict(
        {"enabled": True, "detectors": {"host-stall": {"ratio": 2.0, "min_ms": 5.0}}})
    spec = HealthMonitor(config)._specs["host-stall"]
    assert (spec["ratio"], spec["min_ms"], spec["warmup"], spec["severity"]) == (
        2.0, 5.0, 3, "warning")
    with pytest.raises(ValueError):
        HealthConfig.from_dict({"enabled": True, "detectors": {"host-stall": {"rato": 2}}})


# ------------------------------ the trainer ------------------------------- #


def _stub_trainer(tmp_path, health):
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.trainer import BaseRLTrainer

    class _Stub(BaseRLTrainer):
        learn = sample = save = load = None

    return _Stub(TRLConfig.from_dict({
        "model": {}, "method": {"name": "PPOConfig"},
        "train": {"health": dict(health, dump_dir=str(tmp_path))},
    }))


def test_a_stalled_phase_never_reaches_the_on_error_policy(scoped, tmp_path, capsys):
    tracer, _ = scoped
    trainer = _stub_trainer(tmp_path, {"enabled": True, "on_error": "abort"})
    walls = [0.002, 0.002, 0.002, 0.002, 0.09]
    for phase, wall in enumerate(walls):
        trainer.mark_phase_timing()
        with tracer.span("phase/begin"):
            pass
        with tracer.span("phase/collect"):
            with tracer.span("collect/score"):
                time.sleep(wall)
        with tracer.span("phase/train"):
            pass
        row = trainer.observe_phase_timing(phase)  # no HealthAbort, whatever the policy
        assert row["time/phase_ms"] >= wall * 1e3
    assert trainer.health_monitor.event_counts == {"host-stall": 1}
    assert trainer.flight_recorder.dumped == []
    err = capsys.readouterr().err
    assert "host-stall: phase 4 " in err and "collect/score +" in err
    assert [s.name for s in tracer.spans() if s.name.startswith("health/")] == ["health/host-stall"]
    # one row a mark
    assert trainer.observe_phase_timing(5) == {}


def test_with_health_off_the_trainer_builds_no_timing_row(scoped, tmp_path):
    trainer = _stub_trainer(tmp_path, {"enabled": False})
    trainer.mark_phase_timing()
    assert trainer._phase_timing is None and trainer.observe_phase_timing(0) == {}
    quiet = _stub_trainer(tmp_path, {"enabled": True, "disable": ["host-stall"]})
    quiet.mark_phase_timing()
    assert quiet._phase_timing is None and quiet.observe_phase_timing(0) == {}


@pytest.fixture(scope="module")
def toy_ppo():
    """A toy PPO trainer with its orchestrator, driven phase by phase as
    the benchmark's driver does; ``reward.sleep_s`` makes one phase's
    reward function slow."""
    from trlx_tpu.analysis import harness
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.orchestrator.ppo_orchestrator import PPOOrchestrator
    from trlx_tpu.pipeline.prompt_pipeline import PromptPipeline
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    cfg = harness.tiny_config_dict("ppo")
    cfg["method"].update(num_rollouts=16, chunk_size=8, ppo_epochs=2)
    # no cooldown: a phase this small stalls on a busy CPU of its own accord
    cfg["train"]["health"] = {"enabled": True, "cooldown": 0}
    config = TRLConfig.from_dict(cfg)
    trainer = PPOTrainer(config)

    def reward(samples, queries, response_gt=None):
        time.sleep(reward.sleep_s)
        return [(len(s) % 5) / 2.0 - 1.0 for s in samples]

    reward.sleep_s = 0.0
    rng = np.random.default_rng(0)
    prompts = [[int(x) for x in rng.integers(1, 28, size=4)] for _ in range(64)]
    orch = PPOOrchestrator(
        trainer, PromptPipeline(prompts, config.train.seq_length),
        reward_fn=reward, chunk_size=config.method.chunk_size)

    def one_phase(seed):
        trainer.buffer.clear_history()
        trainer.begin_streamed_phase(seed=seed)
        orch.make_experience(config.method.num_rollouts, 0)
        trainer.finish_streamed_phase()

    yield trainer, reward, one_phase
    orch.close(reraise=False)


def test_phase_begin_opens_once_a_phase_and_closes_before_the_first_dispatch(toy_ppo):
    trainer, _, one_phase = toy_ppo
    telemetry.watch_host()
    with telemetry.scoped_tracer() as tracer, telemetry.scoped_metrics() as registry:
        one_phase(seed=0)
        (begin,) = tracer.spans("phase/begin")
        (collect,) = tracer.spans("phase/collect")
        (train,) = tracer.spans("phase/train")
        first = min(tracer.spans("collect/dispatch"), key=lambda s: s.start)
        assert begin.parent is None and begin.end <= collect.start <= first.start
        assert collect.end <= train.start
        # the three tile what the program does of a streamed phase
        row = trainer.health_monitor._timing["time/phase_ms"]
        assert row.recent[-1] == pytest.approx(
            begin.duration_ms + collect.duration_ms + train.duration_ms)
        # touched once a phase: zeros, not absences
        assert {"host/gc_ms", "host/stalls", "host/stall_ms"} <= set(_counters(registry))


def test_a_slow_reward_function_is_named_as_collect_score(toy_ppo, capsys):
    trainer, reward, one_phase = toy_ppo
    monitor = trainer.health_monitor
    with telemetry.scoped_tracer(), telemetry.scoped_metrics() as registry:
        for seed in range(1, 6):  # steady phases behind the compiles
            one_phase(seed)
        before = monitor.event_counts.get("host-stall", 0)
        registry.clear()
        level = monitor._timing["time/phase_ms"].level
        reward.sleep_s = max(0.2, level / 1e3)  # two chunks: +2 x the level at least
        try:
            one_phase(seed=6)
        finally:
            reward.sleep_s = 0.0
        assert monitor.event_counts.get("host-stall", 0) == before + 1
        event = monitor.events[-1]
        assert "; collect/score +" in event.message and event.severity == "warning"
        counters = _counters(registry)
        assert counters["host/stall_ms[by=collect/score]"] == pytest.approx(
            event.value - event.baseline)
    assert "host-stall: phase" in capsys.readouterr().err


# ---------------------------- the serving loop ---------------------------- #


def _toy_server(health=None):
    from trlx_tpu.analysis import harness
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.inference.server import InferenceServer

    cfg = harness.tiny_config_dict("ppo")
    cfg["train"]["rollout"] = {
        "slots": 8, "admit_width": 4, "harvest_width": 4, "block_size": 4,
    }
    cfg["train"]["health"] = health or {}
    cfg["method"]["gen_kwargs"].update(max_new_tokens=24, min_new_tokens=24)
    return InferenceServer(TRLConfig.from_dict(cfg))


@pytest.fixture(scope="module")
def server():
    return _toy_server()


def _serve(server, n, seed, on_step=None):
    rng = np.random.default_rng(seed)
    prompts = [list(rng.integers(1, 30, server.query_length)) for _ in range(n)]
    rids = server.submit(prompts, stream=True)
    streams = [server.stream(r) for r in rids]
    while any(server.poll(r) is None for r in rids):
        server.step()
        if on_step is not None:
            on_step()
        for s in streams:
            s.drain()
    for r in rids:
        server.pop_result(r)


def test_a_slow_token_sink_is_named_as_engine_route_while_the_ledger_reads_zero(
        server, monkeypatch, capsys):
    """The stall is planted where the detector sees it whatever else the
    host is doing: three times the running level of the step class (80 ms
    at the least: beside busy neighbours an iteration's level reads
    hundreds of ms, and 80 over it trips nothing), in the first sink call
    past the eleventh that lies outside the cooldown of a stall the host
    made on its own (inside one a trip is counted and leaves no event)."""
    with telemetry.scoped_tracer() as tracer, telemetry.scoped_metrics() as registry:
        monkeypatch.setattr(server, "_registry", registry)
        _serve(server, 8, seed=0)  # programs built, levels warm
        before = len(server.health_events)
        steps = server._stall_series[0]  # iterations that met no admission forward
        sink, calls, planted_ms = server._router.on_tokens, [], []

        def slow(emitted):
            calls.append(None)
            if not planted_ms and len(calls) >= 12 and steps.count >= steps.quiet_until:
                planted_ms.append(max(80.0, 3.0 * steps.level))
                time.sleep(planted_ms[0] / 1e3)
            return sink(emitted)

        monkeypatch.setattr(server._router, "on_tokens", slow)
        registry.clear()
        _serve(server, 16, seed=1)  # two waves through the eight slots: room for the plant after a cooldown
        (stall_ms,) = planted_ms
        events = server.health_events[before:]
        assert {e.detector for e in events} == {"host-stall"}
        # (a busy CPU may stall an iteration of its own accord: the planted one is the sink's)
        (planted,) = [e for e in events if "; engine/route +" in e.message and e.value >= stall_ms]
        assert planted.phase is None
        counters = _counters(registry)
        assert counters["host/stalls[by=engine/route]"] >= 1.0
        if counters["host/stalls[by=engine/route]"] == 1.0:
            # the excess over the level is the sink's sleep, give or take what an iteration lies off its level
            assert counters["host/stall_ms[by=engine/route]"] == pytest.approx(
                stall_ms, abs=max(15.0, planted.baseline))
        # the stall ended inside the step in flight: no drained chip was seen
        starved = registry.snapshot()["histograms"]["serve/starved_ms"]
        assert starved["max"] < 40.0
        assert "health/host-stall" in {s.name for s in tracer.spans()}
    assert "host-stall: iteration" in capsys.readouterr().err


def test_a_server_takes_the_detectors_tuning_and_can_disable_it():
    tuned = _toy_server({"detectors": {"host-stall": {"min_ms": 5.0}}})
    assert tuned.health_monitor._specs["host-stall"]["min_ms"] == 5.0
    assert all(series is not None for series in tuned._stall_series)
    quiet = _toy_server({"disable": ["host-stall"]})
    assert quiet._stall_series == (None, None, None)
    with telemetry.scoped_tracer(), telemetry.scoped_metrics() as registry:
        quiet._registry = registry
        _serve(quiet, 4, seed=5)  # the loop runs without a series to judge
    assert quiet.health_monitor._timing == {}


def test_every_iteration_is_judged_in_the_class_of_what_it_waited_behind(server, monkeypatch):
    """Behind no admission forward, behind a chunk's, behind a whole group's:
    three levels, since each is many times the one before."""
    stats = server.engine.stats
    timing = server.health_monitor._timing
    count = lambda: {k.split("=")[1][:-1]: v.count for k, v in timing.items()}
    with telemetry.scoped_tracer(), telemetry.scoped_metrics() as registry:
        monkeypatch.setattr(server, "_registry", registry)
        before, wholes, chunks = count(), stats.prefill_whole, stats.prefill_chunks
        _serve(server, 12, seed=4)
        hist = registry.snapshot()["histograms"]
    seen = {k: v - before[k] for k, v in count().items()}
    assert set(seen) == {"step", "admit", "admit_whole"}
    # every iteration that did device work was judged once, in one class
    assert sum(seen.values()) == hist["serve/pump_ms"]["count"] + hist["serve/admit_pump_ms"]["count"]
    # a forward is met once or twice: where it is dispatched, where the step behind it is read
    whole, chunk = stats.prefill_whole - wholes, stats.prefill_chunks - chunks
    assert whole <= seen["admit_whole"] <= 2 * whole
    assert hist["serve/admit_pump_ms"]["count"] <= seen["admit"] + seen["admit_whole"] <= 2 * (whole + chunk)
    assert seen["step"] > 0


def test_an_iteration_that_trips_nothing_builds_no_event_and_sorts_nothing(server, monkeypatch):
    built, sorts, looked_up, in_check = [], [], [], []

    class CountedEvent(health_mod.HealthEvent):
        def __init__(self, *a, **k):
            built.append(None)
            super().__init__(*a, **k)

    real_sorted = builtins.sorted
    monkeypatch.setattr(health_mod, "HealthEvent", CountedEvent)
    # nothing trips here, however busy this CPU is
    monkeypatch.setitem(server.health_monitor._specs["host-stall"], "min_ms", 1e9)
    with telemetry.scoped_tracer(), telemetry.scoped_metrics() as registry:
        monkeypatch.setattr(server, "_registry", registry)
        _serve(server, 8, seed=2)
        steady = server.health_monitor._timing["time/iter_ms[class=step]"]
        seen = steady.count
        monkeypatch.setattr(builtins, "sorted", lambda *a, **k: sorts.append(None) or real_sorted(*a, **k))
        real_observe = server.health_monitor.observe_timing

        def counted(series, wall_ms, **k):
            lookups, sorted_before = len(registry._instruments), len(sorts)
            out = real_observe(series, wall_ms, **k)
            looked_up.append(len(registry._instruments) - lookups)
            in_check.append(len(sorts) - sorted_before)
            return out

        monkeypatch.setattr(server.health_monitor, "observe_timing", counted)
        _serve(server, 4, seed=3)
        monkeypatch.setattr(builtins, "sorted", real_sorted)
        assert steady.count > seen + 10  # every iteration that did device work was judged
    # (a harvested group's row still sorts its keys, once a group, outside the check)
    assert built == [] and set(in_check) == {0} and set(looked_up) == {0}
