"""The serving loop keeps one decode step in flight (ISSUE 44): step n is
dispatched before step n-1's tokens and flags are read, what is held is
read against the rows that stood at its dispatch, the tail is read out
with no further traffic, and every row's outputs are bit for bit those
of a loop that reads each step out before it dispatches the next (the
order every step had before: ``drained``).

The stub clock, the span recorder and the fixtures are those of
``test_serving_idle_ledger.py``; nothing compares two readings of the
real clock."""

import numpy as np
import pytest

import jax

from trlx_tpu import telemetry
from trlx_tpu.inference.engine import STARVED_PARTS
from trlx_tpu.inference.server import STARVED_HISTOGRAMS

from test_serving_idle_ledger import (  # noqa: F401  (fixtures)
    Held, bare_engine, clock, close, drained, ledger_delta, prompts_for,
    record, run_streamed, server, settled, starved_ms_of,
)

KEYS = ("tokens", "response_mask", "logprobs", "values")


def restarts_of(monkeypatch, engine):
    """Count the decode steps ``engine`` dispatches with nothing held."""
    seen = []
    decode_once = engine._decode_once

    def counted():
        seen.append(engine._held is None)
        return decode_once()

    monkeypatch.setattr(engine, "_decode_once", counted)
    return seen


# ------------------------------- the order -------------------------------- #


def test_step_n_is_dispatched_before_step_n_minus_1_is_fetched(
    server, clock, monkeypatch
):
    """In a steady run the ``engine/dispatch`` of step n is entered
    before the ``engine/fetch`` of step n-1's tokens returns (before it
    is entered, even), every step's tokens are fetched in the end, and
    the counters say so: ``engine/steps_ahead`` is the decode steps less
    the restarts from an empty pipeline, ``serve/step_ahead`` one
    observation an iteration that stepped."""
    clock.tick = 2.0 ** -20
    engine = server.engine
    stats = engine.stats
    steps0, ahead0 = stats.decode_steps, stats.steps_ahead
    record(monkeypatch, engine, clock, device_units=7)
    starts = restarts_of(monkeypatch, engine)
    with telemetry.scoped_tracer() as tracer, telemetry.scoped_metrics() as reg:
        monkeypatch.setattr(server, "_registry", reg)
        stepped = run_streamed(server, clock, n=12, between=3)
        spans = tracer.spans()
    dispatches = [s for s in spans if s.name == "engine/dispatch"]
    fetches = [
        s for s in spans
        if s.name == "engine/fetch" and s.attrs["what"] == "tokens"
    ]
    # outputs are read in the order of their dispatch, all of them
    assert len(dispatches) == len(fetches) == len(starts) == sum(stepped)
    assert engine._held is None
    ahead = 0
    for n in range(1, len(dispatches)):
        if starts[n]:  # step n-1 was read out as a tail before n
            assert fetches[n - 1].end <= dispatches[n].start
        else:
            assert dispatches[n].end <= fetches[n - 1].start
            ahead += 1
    assert starts[0] and sum(starts) < 0.2 * len(starts)
    assert stats.steps_ahead - ahead0 == ahead == len(starts) - sum(starts)
    assert server.stats()["engine/steps_ahead"] == float(stats.steps_ahead)
    assert stats.decode_steps - steps0 == len(starts)
    seen = reg.histogram("serve/step_ahead")
    assert (seen.count, seen.sum) == (len(starts), float(ahead))
    assert seen.min == 0.0 and seen.max == 1.0


def test_an_iteration_without_a_step_observes_no_step_ahead(server, clock):
    """An idle server's iteration runs no decode step: nothing observed."""
    with telemetry.scoped_metrics() as reg:
        assert server.step() is False
        assert "serve/step_ahead" not in reg.snapshot()["histograms"]


# -------------------------------- the rows -------------------------------- #


def serve_all(server, n, seed):
    """``n`` streamed requests at once (a multiple of the harvest width,
    so no placeholder takes a row) on a fresh phase; returns results and
    what each stream delivered, in request order, with what each stream
    held when it closed."""
    engine = server.engine
    engine.start_phase(server.params, jax.random.PRNGKey(seed))
    server._starved_seen = dict(engine.stats.starved_by_ms)  # a new ledger
    rids = server.submit(prompts_for(server, n, seed), stream=True)
    streams = {r: server.stream(r) for r in rids}
    delivered = {r: [] for r in rids}
    at_close = {}
    while any(server.poll(r) is None for r in rids):
        assert server.step()
        for r, s in streams.items():
            delivered[r].extend(s.drain())
            if s.closed and r not in at_close:
                at_close[r] = len(delivered[r])
    results = [server.pop_result(r) for r in rids]
    assert engine.pending == 0 and engine._held is None
    return results, [delivered[r] for r in rids], [at_close[r] for r in rids]


def test_pump_serves_the_rows_of_the_loop_that_read_each_step_out(
    server, monkeypatch
):
    """Sixteen requests over eight slots, so slots are harvested and
    re-admitted under running streams: every request's tokens and
    log-probabilities are bit for bit those of the drained order, and a
    stream holds all its request's tokens before it closes."""
    got, got_streams, got_at_close = serve_all(server, 16, seed=21)
    ahead = server.engine.stats.steps_ahead
    assert ahead > 0
    drained(monkeypatch, server.engine)
    want, _, _ = serve_all(server, 16, seed=21)
    assert server.engine.stats.steps_ahead == 0
    for g, w in zip(got, want):
        assert g["length"] == w["length"] and g["tokens"] == w["tokens"]
        assert g["logprobs"] == w["logprobs"]
    for res, streamed, at_close in zip(got, got_streams, got_at_close):
        assert streamed[: res["length"]] == res["tokens"]
        assert at_close >= res["length"]


def drive_all(server, engine, n, seed):
    engine.start_phase(server.params, jax.random.PRNGKey(seed))
    ids = np.asarray(prompts_for(server, n, seed), np.int32)
    engine.submit(ids, np.ones_like(ids))
    got = {}
    for group in engine.drive(n):
        arrs = {k: np.asarray(group[k]) for k in KEYS}
        for j, r in enumerate(group["rows"]):
            assert r not in got, "row harvested twice"
            got[r] = {k: v[j] for k, v in arrs.items()}
    assert set(got) == set(range(n))
    return got


@pytest.mark.parametrize("interval", [1, 3])
def test_drive_yields_the_rows_of_the_loop_that_read_each_step_out(
    server, bare_engine, monkeypatch, interval
):
    """``drive()`` (a trainer's collect loop): every submitted row is
    harvested once with tokens, mask, log-probabilities and values bit
    for bit the drained order's; only the steps ridden differ, a slot
    being held one step past its flag."""
    engine = bare_engine
    monkeypatch.setattr(engine, "done_poll_interval", interval)
    got = drive_all(server, engine, 24, seed=31)
    stats = engine.stats
    steps, ahead = stats.decode_steps, stats.steps_ahead
    assert ahead == steps - 1 and engine._held is None
    assert stats.completed == 24 and stats.recycles == 24
    drained(monkeypatch, engine)
    want = drive_all(server, engine, 24, seed=31)
    assert engine.stats.steps_ahead == 0
    for r in range(24):
        for key in KEYS:
            np.testing.assert_array_equal(
                got[r][key], want[r][key], err_msg=f"row {r} {key}"
            )
    # a finished slot is seen one step later: a harvest round is a step
    # longer at most, never shorter
    rounds = 24 // engine.num_slots
    assert 0 <= steps - engine.stats.decode_steps <= rounds * interval


# -------------------------------- the tail -------------------------------- #


def test_a_lone_streamed_request_finishes_with_no_further_traffic(server):
    """One request through an otherwise idle server: its stream's own
    iterator drives the loop to the close, the last step's outputs are
    read out though no step follows it, and nothing is left held."""
    engine = server.engine
    (rid,) = server.submit(prompts_for(server, 1, seed=41), stream=True)
    streamed = list(server.stream(rid))
    result = server.poll(rid)
    assert result is not None and server.stream(rid).closed
    assert 1 <= result["length"] <= engine.R
    assert streamed[: result["length"]] == result["tokens"]
    server.pop_result(rid)
    assert engine.pending == 0 and engine._held is None
    assert server.step() is False


def test_a_new_phase_drops_what_the_old_pool_left_in_flight(
    server, bare_engine
):
    engine = bare_engine
    engine.start_phase(server.params, jax.random.PRNGKey(43))
    ids = np.asarray(prompts_for(server, 8, seed=43), np.int32)
    engine.submit(ids, np.ones_like(ids))
    engine.pump()
    assert engine._held is not None
    engine.start_phase(server.params, jax.random.PRNGKey(44))
    assert engine._held is None and engine.stats.steps_ahead == 0


# ------------------- the routing table of the dispatch -------------------- #


def test_a_slot_recycled_between_dispatch_and_read_routes_nothing_to_the_new_row(
    server, monkeypatch
):
    """Between a step's dispatch and its read a harvest may recycle a
    slot and an admission hand it to a new row, while the old occupant
    still reads live (its budget spent) and done in the held outputs.
    Those speak of the row that stood at the dispatch: no token of it
    reaches the new row's stream (that its flag does not end the new row
    early is what the cases above hold, row for row)."""
    engine = server.engine
    engine.start_phase(server.params, jax.random.PRNGKey(51))
    server._starved_seen = dict(engine.stats.starved_by_ms)  # a new ledger
    sunk = {}

    def sink(emitted):
        for row, token in emitted.items():
            sunk.setdefault(row, []).append(token)

    stale = []  # (live, done) of an old occupant a read came upon
    read_step = engine._read_step

    def watched(held):
        for slot, row in held.rows:
            if engine._busy_rows.get(slot, row) != row:
                live, done = np.asarray(held.taps[1]), np.asarray(held.done)
                stale.append((bool(live[slot]), bool(done[slot])))
        read_step(held)

    monkeypatch.setattr(engine, "_read_step", watched)
    engine.token_sink = sink
    ids = np.asarray(prompts_for(server, 24, seed=51), np.int32)
    engine.submit(ids, np.ones_like(ids))
    rows = {}
    while engine.pending:
        for group in engine.pump():
            toks = np.asarray(group["tokens"])
            mask = np.asarray(group["response_mask"])
            for j, r in enumerate(group["rows"]):
                rows[r] = toks[j, : int(mask[j].sum())].tolist()
    engine.token_sink = None
    assert set(rows) == set(range(24))
    # the case arose, with an old occupant that still read live and done
    assert any(live and done for live, done in stale)
    for r, tokens in rows.items():
        # a row's stream is its own tokens (then, for one that spent its
        # budget, what its slot emits until the harvest: PERF.md section 7)
        assert sunk[r][: len(tokens)] == tokens, f"row {r}"
        assert len(tokens) >= 1


# ------------------------------ spec decode ------------------------------- #


@pytest.fixture(scope="module")
def drafting_engine(server):
    base = server.engine
    return type(base)(
        apply_fn=base._apply_fn, init_cache_fn=base._init_cache_fn,
        gen_config=base.gen_config, query_length=base.Q,
        vocab_size=base.vocab_size, num_slots=8, admit_width=4,
        harvest_width=4, block_size=4, mesh=base.mesh,
        param_shardings=base._param_shardings, with_values=True,
        spec_max_draft=3,
    )


def drive_drafted(server, engine, seed):
    """Cyclic prompts, so the n-gram drafter proposes from the first step."""
    engine.start_phase(server.params, jax.random.PRNGKey(seed))
    q = engine.Q
    ids = np.asarray(
        [([1 + i % 4, 2 + i % 4] * q)[:q] for i in range(16)], np.int32
    )
    engine.submit(ids, np.ones_like(ids))
    got = {}
    for group in engine.drive(16):
        arrs = {k: np.asarray(group[k]) for k in KEYS}
        for j, r in enumerate(group["rows"]):
            got[r] = {k: v[j] for k, v in arrs.items()}
    return got


def test_a_drafted_round_reads_what_is_held_first(
    server, drafting_engine, monkeypatch
):
    """A draft continues the tokens the host has seen and a drafted
    round needs the last acceptance: an engine that drafts reads what a
    draftless round left held before it drafts, ``verify_step`` finds
    nothing held, and the rows are the drained order's bit for bit."""
    engine = drafting_engine
    held_at = {"draft": [], "verify": []}
    draft_now, verify_once = engine._draft_now, engine._verify_once
    monkeypatch.setattr(engine, "_draft_now", lambda: (
        held_at["draft"].append(engine._held is not None), draft_now()
    )[1])
    monkeypatch.setattr(engine, "_verify_once", lambda draft, lens: (
        held_at["verify"].append(engine._held is not None),
        verify_once(draft, lens),
    )[1])
    got = drive_drafted(server, engine, seed=61)
    stats = engine.stats
    assert stats.spec_steps > 0 and stats.spec_drafted > 0
    assert stats.decode_steps > stats.spec_steps  # draftless rounds too
    assert held_at["verify"] and not any(held_at["verify"])
    assert held_at["draft"] and not any(held_at["draft"])
    assert stats.steps_ahead == 0 and engine._held is None
    spec = (stats.spec_steps, stats.spec_drafted, stats.spec_accepted)
    drained(monkeypatch, engine)
    want = drive_drafted(server, engine, seed=61)
    assert set(got) == set(want) == set(range(16))
    for r in range(16):
        for key in KEYS:
            np.testing.assert_array_equal(
                got[r][key], want[r][key], err_msg=f"row {r} {key}"
            )
    # the same rounds, drafts and acceptances: the synchronous order kept
    after = engine.stats
    assert spec == (after.spec_steps, after.spec_drafted, after.spec_accepted)


# ------------------------------- the ledger ------------------------------- #


def test_the_ledger_reads_zero_while_a_step_is_in_flight(
    server, clock, monkeypatch
):
    """Every part's hook holding the host, and the device a fetch: with
    a step queued behind every fetch nothing drains, the ledger stands
    still, and each histogram still takes its observation (0.0) on
    every iteration that did device work."""
    stats = server.engine.stats
    before = dict(stats.starved_by_ms)
    log = record(monkeypatch, server.engine, clock, device_units=7)
    held = Held(monkeypatch, server, clock, {"tap": 3, "admit": 2, "land": 5})
    with telemetry.scoped_metrics() as reg:
        monkeypatch.setattr(server, "_registry", reg)
        stepped = run_streamed(server, clock, between=11)
    assert held.sinks > 0 and sum(stepped) > 0
    assert all(v == 0.0 for v in ledger_delta(stats, before).values())
    assert starved_ms_of(log) == 0.0
    total = reg.histogram("serve/starved_ms")
    iterations = (
        reg.histogram("serve/pump_ms").count
        + reg.histogram("serve/admit_pump_ms").count
    )
    assert (total.count, total.sum) == (iterations, 0.0) and iterations > 0
    for name in STARVED_HISTOGRAMS.values():
        twin = reg.histogram(name)
        assert (twin.count, twin.sum) == (iterations, 0.0)


def test_a_host_a_whole_step_behind_shows_in_the_ledger(
    server, clock, monkeypatch
):
    """A real drain with a step in flight: the host comes back from a
    fetch and finds the step it had queued ended too, so the chip has
    nothing to run until the next dispatch. The ledger starts there,
    charges the parts as ever, and the parts sum to the total."""
    engine = server.engine
    stats = engine.stats
    before, seen = dict(stats.starved_by_ms), dict(server._starved_seen)
    log = record(monkeypatch, engine, clock, device_units=7)
    monkeypatch.setattr(
        engine, "_ran_out",
        lambda: engine._held is not None
        and engine._held.seq == engine._dispatches,
    )
    Held(monkeypatch, server, clock, {"tap": 3, "admit": 2, "land": 5})
    with telemetry.scoped_metrics() as reg:
        monkeypatch.setattr(server, "_registry", reg)
        run_streamed(server, clock, between=11)
    close(engine, clock, log)
    got = ledger_delta(stats, before)
    assert all(got[p] > 0.0 for p in ("tap", "admit", "land", "caller"))
    assert set(got) == set(STARVED_PARTS)
    assert sum(got.values()) == starved_ms_of(log)
    assert sum(got.values()) == stats.starved_ms - sum(before.values())
    total = reg.histogram("serve/starved_ms")
    observed = {p: server._starved_seen[p] - seen[p] for p in STARVED_PARTS}
    assert total.sum == sum(observed.values()) > 0.0
    for part, name in STARVED_HISTOGRAMS.items():
        twin = reg.histogram(name)
        assert (twin.count, twin.sum) == (total.count, observed[part])


def test_drive_in_flight_keeps_the_ledger_at_zero(
    server, bare_engine, clock, monkeypatch
):
    """PPO's loop as it runs: no fetch of a whole phase drains."""
    engine = bare_engine
    engine.start_phase(server.params, jax.random.PRNGKey(5))
    log = record(monkeypatch, engine, clock, device_units=7)
    ids = np.asarray(prompts_for(server, 8, seed=5), np.int32)
    engine.submit(ids, np.ones_like(ids))
    for _ in engine.drive(8):
        clock.advance(4)
    stats = engine.stats
    assert stats.steps_ahead == stats.decode_steps - 1 > 0
    assert stats.starved_ms == starved_ms_of(log) == 0.0
    assert stats.host_blocked_ms > 0.0
