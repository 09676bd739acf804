"""The serving loop says why the chip sat idle (ISSUE 43): the engine's
starved ledger, the spans that tile an iteration, and the histograms
``InferenceServer.step`` feeds from them.

Everything here runs on a fake clock that moves only where a test moves
it (a hook on the device fetch, the token sink, the landing, the
scheduler, the caller's turn), in units of 2**-10 s, so every expected
number is exact: no assertion compares two spans on the real clock.

Since ISSUE 44 the loop reads one step behind what it has dispatched, so
no fetch of a steady run drains the chip and the ledger reads 0 there
(``tests/test_serving_step_ahead.py`` holds that). What the ledger says
of a chip that *is* drained is pinned here as it was, on a loop made to
drain every step (``drained``: each step's outputs read out before the
next is dispatched, the order every step had before)."""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu import telemetry
from trlx_tpu.inference.engine import STARVED_PARTS
from trlx_tpu.inference.server import STARVED_HISTOGRAMS
from trlx_tpu.telemetry import metrics as metrics_mod
from trlx_tpu.telemetry import tracer as tracer_mod

U = 2.0 ** -10  # the clock's unit, s; U * 1000 is exact in a double
PROGRAMS = (
    "decode_step_jit", "verify_step_jit", "prefill_jit", "prefill_chunk_jit",
    "refill_jit", "release_jit",
)


class Clock:
    """``monotonic()`` that stands still but for ``advance`` and, with a
    ``tick``, a fixed cost a reading."""

    def __init__(self, tick: float = 0.0):
        self.t, self.tick = 1.0, tick

    def __call__(self) -> float:
        self.t += self.tick
        return self.t

    def advance(self, units: float) -> None:
        self.t += units * U


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(telemetry, "monotonic", c)
    monkeypatch.setattr(tracer_mod, "monotonic", c)
    monkeypatch.setattr(metrics_mod, "monotonic", c)
    return c


@pytest.fixture(scope="module")
def server():
    from trlx_tpu.analysis import harness
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.inference.server import InferenceServer

    cfg = harness.tiny_config_dict("ppo")
    cfg["train"]["rollout"] = {
        "slots": 8, "admit_width": 4, "harvest_width": 4, "block_size": 4,
    }
    cfg["train"]["serving"] = {
        "slo_classes": {"standard": {"queue_wait_budget_ms": 120000}},
    }
    return InferenceServer(TRLConfig.from_dict(cfg))


@pytest.fixture(autouse=True)
def settled(server):
    """Each test starts from a closed ledger: a run's tail drains its
    pool, and that episode stays open until the next dispatch."""
    server.engine._fed()
    server._starved_seen = dict(server.engine.stats.starved_by_ms)


def close(engine, clock, log):
    """Close the episode a run's tail left open, where the log ends."""
    log.append(("dispatch", clock.t, False))
    engine._fed()


@pytest.fixture(scope="module")
def bare_engine(server):
    """An engine with no stream tap (PPO's ``rollout.engine: continuous``
    shape), on the server's model."""
    base = server.engine
    return type(base)(
        apply_fn=base._apply_fn, init_cache_fn=base._init_cache_fn,
        gen_config=base.gen_config, query_length=base.Q,
        vocab_size=base.vocab_size, num_slots=8, admit_width=4,
        harvest_width=4, block_size=4, mesh=base.mesh,
        param_shardings=base._param_shardings, with_values=True,
    )


def drained(monkeypatch, engine):
    """Make ``engine`` read every step out before it dispatches the
    next: each step's fetch is then of the newest program and drains."""
    decode_once = engine._decode_once
    monkeypatch.setattr(
        engine, "_decode_once", lambda: (decode_once(), engine._read_held())
    )


def record(monkeypatch, engine, clock, device_units=0.0):
    """Log every fetch's return and every dispatch's entry of ``engine``
    with the clock's reading; a fetch holds the host ``device_units``.
    Whether the step in flight has ended is the real device's to say and
    not this clock's: here it never has."""
    log = []
    monkeypatch.setattr(engine, "_ran_out", lambda: False)
    real_get, real_fetch = jax.device_get, engine.fetch

    def device_get(x):
        clock.advance(device_units)
        return real_get(x)

    def fetch(*arrays, **kw):
        fed = engine._drained_at is None
        out = real_fetch(*arrays, **kw)
        # whether this fetch drained the chip is the engine's to say (a
        # group's is the newest program's where nothing follows its refill)
        log.append(("fetch", clock.t, fed and engine._drained_at is not None))
        return out

    real_mark = engine.mark_starved

    def mark_starved(part):
        fed = engine._drained_at is None
        real_mark(part)
        if fed and engine._drained_at is not None:
            # the step in flight was found ended here: as good as a fetch
            log.append(("fetch", clock.t, True))
        log.append(("mark", clock.t, part is None))

    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(engine, "fetch", fetch)
    monkeypatch.setattr(engine, "mark_starved", mark_starved)
    for name in PROGRAMS:
        program = getattr(engine, name)
        if program is None:
            continue

        def dispatch(*args, _program=program):
            log.append(("dispatch", clock.t, False))
            return _program(*args)

        monkeypatch.setattr(engine, name, dispatch)
    return log


def starved_ms_of(log) -> float:
    """The definition, from the log alone: from the return of a fetch
    that drained the chip (of the newest program's output) to the entry
    of the next dispatch, less the time the loop was nobody's (marked
    ``None``: the engine held no rows to be starved of)."""
    total, drained, nobodys = 0.0, None, False
    for kind, t, flag in log:
        if kind == "fetch" and flag and drained is None and not nobodys:
            drained = t
        elif kind == "mark" and flag and drained is not None:
            total, drained, nobodys = total + (t - drained), None, True
        elif kind == "mark" and not flag and nobodys:
            drained, nobodys = t, False
        elif kind == "dispatch":
            if drained is not None:
                total += t - drained
            drained, nobodys = None, False
    return total * 1000.0


def prompts_for(server, n, seed):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, 30, server.query_length)) for _ in range(n)]


def run_streamed(server, clock, n=6, seed=0, between=0.0, held=None):
    """``n`` streamed requests through ``step()`` to their results; the
    caller holds the loop ``between`` units after every iteration.
    Returns, an iteration, whether it ran a decode step."""
    stats = server.engine.stats
    rids = server.submit(prompts_for(server, n, seed), stream=True)
    streams = [server.stream(r) for r in rids]
    stepped = []
    while any(server.poll(r) is None for r in rids):
        steps = stats.decode_steps
        if held is not None:
            held.entered = steps
        assert server.step() or server.scheduler.has_work()
        stepped.append(stats.decode_steps > steps)
        for s in streams:
            s.drain()
        clock.advance(between)
    for r in rids:
        server.pop_result(r)
    assert server.engine.pending == 0
    return stepped


def ledger_delta(stats, before):
    return {p: stats.starved_by_ms[p] - before[p] for p in STARVED_PARTS}


# ------------------------------ the definition ---------------------------- #


@pytest.mark.parametrize("interval", [1, 2, 64])
def test_starved_is_the_distance_from_a_draining_fetch_to_the_next_dispatch(
    server, bare_engine, clock, monkeypatch, interval
):
    """No tap, every step read out before the next: the ``done`` flags
    fetched are the newest program's, so that fetch drains and the next
    dispatch feeds. A step that fetches nothing lets the host run ahead
    (every other step at an interval of 2, every step at 64) and adds
    nothing; a fetch of an older program's output adds nothing and
    changes no state."""
    engine = bare_engine
    monkeypatch.setattr(engine, "done_poll_interval", interval)
    engine.start_phase(server.params, jax.random.PRNGKey(interval))
    drained(monkeypatch, engine)
    log = record(monkeypatch, engine, clock, device_units=7)
    older = jnp.arange(3)
    # four pools' worth of rows: every pump has rows to step
    ids = np.asarray(prompts_for(server, 32, seed=interval), np.int32)
    engine.submit(ids, np.ones_like(ids))
    for _ in range(10):
        engine.pump()
        engine.fetch(older)  # an older output: not the newest program's
        clock.advance(4)
    stats = engine.stats
    assert stats.decode_steps == 10
    assert stats.done_polls == 10 // interval
    assert stats.starved_ms == starved_ms_of(log)
    # the device's 7 units a fetch lie before the drain, never in it;
    # the older fetch's 7 and the caller's 4 lie between drain and feed
    episodes = max(0, stats.done_polls - (10 % interval == 0))
    assert stats.starved_ms == episodes * 11 * U * 1000.0
    assert engine.stats.to_dict()["engine/starved_ms"] == round(
        stats.starved_ms, 3
    )
    if interval == 64:
        assert stats.starved_ms == 0.0 and stats.host_blocked_ms > 0.0


@pytest.mark.parametrize("interval", [1, 2, 64])
def test_a_step_in_flight_drains_nothing_and_the_tail_read_out_does(
    server, bare_engine, clock, monkeypatch, interval
):
    """The loop as it runs: step n is dispatched before step n-1's flags
    are fetched, so those are never the newest program's and the ledger
    stands at 0 whatever the host does in between. The tail read out
    with nothing dispatched since is the newest, and the time from there
    to the next dispatch is starved time."""
    engine = bare_engine
    monkeypatch.setattr(engine, "done_poll_interval", interval)
    engine.start_phase(server.params, jax.random.PRNGKey(interval))
    log = record(monkeypatch, engine, clock, device_units=7)
    ids = np.asarray(prompts_for(server, 32, seed=interval), np.int32)
    engine.submit(ids, np.ones_like(ids))
    for _ in range(10):
        engine.pump()
        clock.advance(4)
    stats = engine.stats
    assert (stats.decode_steps, stats.steps_ahead) == (10, 9)
    assert stats.done_polls == 9 // interval
    assert not any(newest for kind, _, newest in log if kind == "fetch")
    assert stats.starved_ms == starved_ms_of(log) == 0.0
    assert stats.host_blocked_ms > 0.0 or interval == 64
    engine._read_held()  # the tenth step, with nothing behind it
    clock.advance(5)
    engine.pump()
    assert (stats.decode_steps, stats.steps_ahead) == (11, 9)
    assert stats.done_polls == 10 // interval
    # at 64 the read fetched nothing: nobody saw the chip drain
    want = 0.0 if interval == 64 else 5 * U * 1000.0
    assert stats.starved_ms == starved_ms_of(log) == want


# ------------------------------- the parts -------------------------------- #


class Held:
    """Hold the host ``units[part]`` in each part's own code: the token
    sink, ``_land_group``, ``_schedule``. Counts the sink's calls and
    the landings entered with the chip drained, of which those that
    came after a decode step of their own iteration (``entered``: the
    step count the iteration began with)."""

    def __init__(self, monkeypatch, server, clock, units):
        self.sinks = self.lands_drained = self.lands_after_a_step = 0
        self.entered = 0
        engine = server.engine
        stats = engine.stats

        def slow(part, fn):
            def held(*args):
                self.sinks += part == "tap"
                self.lands_drained += (
                    part == "land" and engine._drained_at is not None
                )
                self.lands_after_a_step += (
                    part == "land" and stats.decode_steps > self.entered
                )
                clock.advance(units.get(part, 0))
                return fn(*args)
            return held

        router = server._router
        monkeypatch.setattr(router, "on_tokens", slow("tap", router.on_tokens))
        monkeypatch.setattr(
            server, "_land_group", slow("land", server._land_group)
        )
        monkeypatch.setattr(
            server, "_schedule", slow("admit", server._schedule)
        )


@pytest.mark.parametrize("part", ["tap", "admit", "land", "caller"])
def test_each_part_lands_under_its_name(server, clock, monkeypatch, part):
    """A slow token sink is ``tap``, a slow scheduler ``admit``, a slow
    ``_land_group`` ``land``, a caller that sits on the loop ``caller``:
    each to the unit, and nothing under any other name."""
    stats = server.engine.stats
    before = dict(stats.starved_by_ms)
    drained(monkeypatch, server.engine)
    log = record(monkeypatch, server.engine, clock)
    held = Held(monkeypatch, server, clock, {part: 3})
    between = 3 if part == "caller" else 0
    stepped = run_streamed(server, clock, between=between, held=held)
    if part == "caller":
        # an idle server between requests: the caller's time is nobody's
        clock.advance(1000)
        assert server.step() is False
        clock.advance(1000)
        stepped += run_streamed(server, clock, seed=1, between=between)
    close(server.engine, clock, log)
    got = ledger_delta(stats, before)
    times = {
        # the sink runs straight after the draining fetch, every time
        "tap": held.sinks,
        # the scheduler runs with the chip drained where the iteration
        # before ran a step
        "admit": sum(stepped[:-1]),
        # a landing in an iteration whose step drained the chip, or
        # behind the landing of a group harvested with nothing dispatched
        # after its refill (the pool ran empty: that fetch drains too,
        # inside the first landing of the tail)
        "land": held.lands_drained,
        "caller": sum(stepped),
    }[part]
    assert times > 0
    assert got[part] == times * 3 * U * 1000.0
    assert all(got[p] == 0.0 for p in STARVED_PARTS if p != part)
    assert sum(got.values()) == starved_ms_of(log)


def test_parts_sum_to_the_total_to_the_last_bit(server, clock, monkeypatch):
    """Every hook at once, the device included: the total is what the
    log of fetches and dispatches says, and the parts are all of it."""
    stats = server.engine.stats
    before, total0 = dict(stats.starved_by_ms), stats.starved_ms
    seen = dict(server._starved_seen)
    drained(monkeypatch, server.engine)
    log = record(monkeypatch, server.engine, clock, device_units=7)
    Held(monkeypatch, server, clock, {"tap": 3, "admit": 2, "land": 5})
    with telemetry.scoped_metrics() as reg:
        monkeypatch.setattr(server, "_registry", reg)
        run_streamed(server, clock, between=11)
    close(server.engine, clock, log)
    got = ledger_delta(stats, before)
    assert all(got[p] > 0.0 for p in ("tap", "admit", "land", "caller"))
    assert sum(got.values()) == starved_ms_of(log) == stats.starved_ms - total0
    # the histograms hold the same ledger, an observation an iteration
    # that did device work, as far as the last of them saw it (what a
    # harvest alone closes waits for the next)
    total = reg.histogram("serve/starved_ms")
    assert total.count == (
        reg.histogram("serve/pump_ms").count
        + reg.histogram("serve/admit_pump_ms").count
    )
    observed = {p: server._starved_seen[p] - seen[p] for p in STARVED_PARTS}
    assert total.sum == sum(observed.values()) <= sum(got.values())
    assert total.min >= 0.0
    for part, name in STARVED_HISTOGRAMS.items():
        twin = reg.histogram(name)
        assert (twin.count, twin.sum) == (total.count, observed[part])
    assert server.stats()["engine/starved_ms"] == round(stats.starved_ms, 3)


# -------------------------------- the spans ------------------------------- #


def test_children_tile_the_step_and_fetches_say_what(
    server, clock, monkeypatch
):
    """Over a streamed run the spans right under ``serve/step`` cover at
    least 95% of it (a reading of the clock costs a tick, so what lies
    between the children is not free); every ``engine/fetch`` says what
    it fetched; the caller's turn is a span of its own with no parent."""
    clock.tick = 2.0 ** -20
    record(monkeypatch, server.engine, clock, device_units=7)
    Held(monkeypatch, server, clock, {"tap": 3, "land": 5})
    next_batch = server.scheduler.next_batch
    monkeypatch.setattr(
        server.scheduler, "next_batch",
        lambda free: (clock.advance(2), next_batch(free))[1],
    )
    with telemetry.scoped_tracer() as tracer:
        stepped = run_streamed(server, clock, between=11)
        spans = tracer.spans()
    steps = {s.index: s for s in spans if s.name == "serve/step"}
    assert len(steps) == len(stepped)
    covered = {}
    for s in spans:
        if s.parent in steps:
            covered.setdefault(s.name, 0.0)
            covered[s.name] += s.duration_ms
    assert set(covered) == {
        "serve/schedule", "collect/slot_recycle", "collect/admit",
        "collect/prefill", "engine/dispatch", "engine/fetch", "engine/route",
        "serve/land",
    }
    wall = sum(s.duration_ms for s in steps.values())
    assert sum(covered.values()) >= 0.95 * wall
    fetches = [s for s in spans if s.name == "engine/fetch"]
    assert {s.attrs["what"] for s in fetches} == {"tokens", "done", "group"}
    by_index = {s.index: s for s in spans}
    assert all(
        by_index[s.parent].name == "serve/land"
        for s in fetches if s.attrs["what"] == "group"
    )
    dispatches = [s for s in spans if s.name == "engine/dispatch"]
    assert len(dispatches) == sum(stepped)
    assert {s.attrs["program"] for s in dispatches} == {"decode_step"}
    callers = [s for s in spans if s.name == "serve/caller"]
    # one a return with rows in flight; the first entry follows none
    assert len(callers) == len(stepped) - 1
    assert all(s.parent is None and s.duration_ms >= 11 * U * 1000.0
               for s in callers)
    assert all(s.thread_id == threading.get_ident() for s in callers)


def test_tracer_off_the_ledger_stands_and_the_new_spans_cost_nothing(
    server, clock, monkeypatch
):
    stats = server.engine.stats
    before, seen = dict(stats.starved_by_ms), dict(server._starved_seen)
    drained(monkeypatch, server.engine)
    log = record(monkeypatch, server.engine, clock, device_units=7)
    Held(monkeypatch, server, clock, {"tap": 3, "admit": 2, "land": 5})
    opened = []
    monkeypatch.setattr(tracer_mod, "_annotate", opened.append)
    off = telemetry.Tracer(enabled=False)
    with telemetry.scoped_tracer(off), telemetry.scoped_metrics() as reg:
        monkeypatch.setattr(server, "_registry", reg)
        assert telemetry.span("engine/dispatch") is telemetry.NULL_SPAN
        assert telemetry.span("engine/route") is telemetry.NULL_SPAN
        run_streamed(server, clock, between=11)
    assert off.spans() == [] and opened == []
    close(server.engine, clock, log)
    got = ledger_delta(stats, before)
    assert sum(got.values()) == starved_ms_of(log) > 0.0
    assert all(got[p] > 0.0 for p in ("tap", "admit", "land", "caller"))
    total = reg.histogram("serve/starved_ms")
    observed = sum(server._starved_seen[p] - seen[p] for p in STARVED_PARTS)
    assert total.count > 0 and total.sum == observed > 0.0
    for name in STARVED_HISTOGRAMS.values():
        assert reg.histogram(name).count == total.count


# ------------------------------- drive() ---------------------------------- #


def test_drive_keeps_the_ledger_and_observes_nothing(
    server, bare_engine, clock, monkeypatch
):
    """PPO's loop, read out step by step: the same ledger in
    ``EngineStats`` (no server marks its parts, so what follows a step's
    own bookkeeping is ``other``), no ``serve/*`` histogram."""
    engine = bare_engine
    engine.start_phase(server.params, jax.random.PRNGKey(5))
    drained(monkeypatch, engine)
    log = record(monkeypatch, engine, clock, device_units=7)
    admit = engine._admit
    monkeypatch.setattr(
        engine, "_admit", lambda: (clock.advance(2), admit())[1]
    )
    ids = np.asarray(prompts_for(server, 8, seed=5), np.int32)
    engine.submit(ids, np.ones_like(ids))
    with telemetry.scoped_metrics() as reg:
        for _ in engine.drive(8):
            clock.advance(4)
        snap = reg.snapshot()
    stats = engine.stats
    assert stats.starved_ms == starved_ms_of(log) > 0.0
    assert stats.starved_ms == stats.starved_by_ms["other"]
    assert not [n for n in snap["histograms"] if n.startswith("serve/")]
