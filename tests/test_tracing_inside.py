"""The program measured from inside (ISSUE 24): the tracer's spans in the
profiler trace, the serving loop's spans and histograms, the collect
loop's wait/detokenize split, the device scope names, and the compile
listener. CPU, tiny sizes; every time here is a CPU time and stands
under no device metric's name."""

import glob
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu import telemetry
from trlx_tpu.telemetry import tracer as tracer_mod


def _by_index(spans):
    return {s.index: s for s in spans}


# ------------------------------ one clock ------------------------------- #


def test_span_lands_in_profiler_trace_inside_its_parent(tmp_path):
    """A span opened under a profiler session is a host event
    ``trlx/<name>`` of the xplane, inside its parent's interval."""
    from jax.profiler import ProfileData

    with telemetry.scoped_tracer():
        jax.profiler.start_trace(str(tmp_path))
        try:
            with telemetry.span("phase/outer"):
                with telemetry.span("collect/inner"):
                    jax.jit(lambda x: x * 2)(jnp.ones(4)).block_until_ready()
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracer_mod.ANNOTATION_PREFIX):
                    start = int(ev.start_ns)
                    found[ev.name] = (start, start + int(ev.duration_ns))
    assert set(found) == {"trlx/phase/outer", "trlx/collect/inner"}
    outer, inner = found["trlx/phase/outer"], found["trlx/collect/inner"]
    assert outer[0] <= inner[0] and inner[1] <= outer[1]
    assert inner[1] > inner[0]


def test_unrecorded_spans_open_no_annotation(monkeypatch):
    """The disabled path stays what it was: ``NULL_SPAN`` opens nothing,
    nor does a forced-but-unrecorded span, nor a span stamped after the
    fact; a recorded span opens exactly one."""
    opened = []
    real = tracer_mod._annotate
    monkeypatch.setattr(
        tracer_mod, "_annotate", lambda name: opened.append(name) or real(name)
    )
    off = telemetry.Tracer(enabled=False)
    with off.span("serve/step") as sp:
        assert sp is telemetry.NULL_SPAN
    with off.span("serve/step", force=True) as sp:
        pass
    assert sp.duration_ms >= 0.0 and opened == []
    on = telemetry.Tracer(enabled=True)
    stamped = telemetry.Span("jit/compile")
    stamped.start, stamped.end = 1.0, 2.0
    on.record(stamped)
    assert opened == []
    with on.span("serve/step"):
        assert on.current().name == "serve/step"
    assert opened == ["serve/step"] and on.current() is None


# --------------------------- the serving loop ---------------------------- #


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


def _prompts(server, n, seed):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, 30, server.query_length)) for _ in range(n)]


def test_streamed_run_fills_the_serving_histograms(server, monkeypatch):
    """A streamed run through the public ``step()``: the four histograms
    fill, ``serve/admit_pump_ms`` only in iterations that dispatched a
    prefill (one forward each, a chunk or a whole group), the host's share never exceeds the iteration, an ordinary
    iteration stays inside its budget of spans, and a result carries the
    log-probabilities of its tokens."""
    with telemetry.scoped_tracer() as tracer, telemetry.scoped_metrics() as reg:
        monkeypatch.setattr(server, "_registry", reg)
        stats = server.engine.stats
        prefills0, forwards0 = stats.prefills, stats.prefill_chunks + stats.prefill_whole
        rids = server.submit(_prompts(server, 6, seed=0), stream=True)
        streams = [server.stream(r) for r in rids]
        iterations = 0
        while any(server.poll(r) is None for r in rids):
            assert server.step() or server.scheduler.has_work()
            iterations += 1
            for s in streams:
                s.drain()
        hist = reg.snapshot()["histograms"]
        spans = tracer.spans()
    results = [server.pop_result(r) for r in rids]

    pump, admit = hist["serve/pump_ms"], hist["serve/admit_pump_ms"]
    host, held = hist["serve/step_host_ms"], hist["serve/slots_done_waiting"]
    prefills = stats.prefills - prefills0
    assert prefills >= 2  # 6 requests at admit width 4
    # a server admits in chunks, one forward an iteration (PR 30): an
    # admission's iterations are its forwards, each the stall of its own
    forwards = stats.prefill_chunks + stats.prefill_whole - forwards0
    assert prefills <= admit["count"] == forwards
    assert pump["count"] >= 1
    # every iteration that did device work is in exactly one of the two
    assert pump["count"] + admit["count"] == host["count"] == held["count"]
    assert host["count"] <= iterations
    total = lambda h: h["mean"] * h["count"]
    assert total(host) <= total(pump) + total(admit)
    assert host["max"] <= max(pump["max"], admit["max"])
    assert 0 <= held["min"] and held["max"] <= server.engine.num_slots
    assert stats.host_blocked_ms > 0.0

    steps = [s for s in spans if s.name == "serve/step"]
    assert len(steps) == iterations
    children = {s.index: [] for s in steps}
    for s in spans:
        for a in tracer.ancestors(s):
            if a.index in children:
                children[a.index].append(s)
    admitting = [s for s in steps if s.attrs["admitted"]]
    assert admitting and all(
        any(c.name == "serve/schedule" for c in children[s.index])
        for s in admitting
    )
    landed = [s for s in steps if s.attrs["harvested"]]
    assert landed and all(
        any(c.name == "serve/land" for c in children[s.index]) for s in landed
    )
    for s in steps:
        names = [c.name for c in children[s.index]]
        blocked = sum(
            c.duration_ms for c in children[s.index] if c.name == "engine/fetch"
        )
        assert blocked <= s.duration_ms + 1e-6
        ordinary = not s.attrs["admitted"] and not s.attrs["harvested"] and not (
            {"collect/prefill", "collect/admit", "jit/compile"} & set(names)
        )
        if ordinary:
            # the budget: at most 6 spans an ordinary iteration (ISSUE
            # 24's 4, and ISSUE 43's dispatch and route)
            assert 1 + len(names) <= 6, names
            assert set(names) <= {
                "engine/dispatch", "engine/fetch", "engine/route"
            }

    for res in results:
        assert len(res["logprobs"]) == res["length"] == len(res["tokens"])
        assert all(np.isfinite(res["logprobs"])) and max(res["logprobs"]) <= 0.0


def test_pump_once_is_step(server):
    """The old private name still drives one iteration (the benchmark's
    load generator calls it)."""
    with telemetry.scoped_tracer() as tracer:
        assert server._pump_once() is False  # idle: nothing progressed
        assert [s.name for s in tracer.spans()] == ["serve/step"]


# --------------------------- the collect loop ---------------------------- #


def _collect_once(engine):
    from trlx_tpu.analysis import harness
    from trlx_tpu.orchestrator.ppo_orchestrator import PPOOrchestrator
    from trlx_tpu.pipeline.prompt_pipeline import PromptPipeline

    trainer = harness.build_trainer(
        "ppo", train_overrides={"rollout": {"engine": engine}}
    )
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(1, 30, 8)) for _ in range(16)]
    orch = PPOOrchestrator(
        trainer, PromptPipeline(prompts, trainer.query_length),
        reward_fn=lambda samples, queries, response_gt=None: [
            float(len(s)) for s in samples
        ],
        chunk_size=8,
    )
    with telemetry.scoped_tracer() as tracer:
        orch.make_experience(trainer.config.method.num_rollouts, 0)
        spans = tracer.spans()
    orch.close()
    return trainer, spans


@pytest.fixture(scope="module")
def fixed_collect():
    return _collect_once("fixed")


def _assert_wait_and_detokenize_nest(spans):
    by_index = _by_index(spans)
    decodes = [s for s in spans if s.name == "collect/decode"]
    assert decodes
    for name in ("collect/wait", "collect/detokenize"):
        inner = [s for s in spans if s.name == name]
        assert len(inner) == len(decodes)
        for s in inner:
            parent = by_index[s.parent]
            assert parent.name == "collect/decode"
            assert parent.start <= s.start and s.end <= parent.end
    for d in decodes:
        kids = [s for s in spans if s.parent == d.index]
        assert [k.name for k in sorted(kids, key=lambda k: k.start)] == [
            "collect/wait", "collect/detokenize",
        ]
        assert sum(k.duration_ms for k in kids) <= d.duration_ms + 1e-6


def test_collect_decode_splits_into_wait_and_detokenize_fixed(fixed_collect):
    _assert_wait_and_detokenize_nest(fixed_collect[1])


def test_collect_decode_splits_into_wait_and_detokenize_continuous():
    _, spans = _collect_once("continuous")
    _assert_wait_and_detokenize_nest(spans)
    # the engine's own step loop reports where the host was blocked
    assert any(s.name == "engine/fetch" for s in spans)


# ------------------------- stable device names --------------------------- #


def _scoped(text, name):
    """Whether a lowered module's op names hold the scope ``name`` as a
    path component (``jvp(loss)``, ``transpose(jvp(loss))`` count)."""
    return re.search(r'loc\("[^"]*(?<![\w])%s(?![\w])[^"]*"' % re.escape(name), text)


def test_lowered_programs_carry_the_scope_names(fixed_collect, server):
    """The contract a device-trace reader keys on
    (docs/observability.md "Device scope names"), and the module names
    the benchmark's readers match stay what they were."""
    from trlx_tpu.analysis import harness

    trainer = fixed_collect[0]
    B, Q = trainer.config.train.batch_size, trainer.query_length
    prompt = jax.ShapeDtypeStruct((B, Q), jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = harness._sds(trainer.state.params)
    R = trainer.gen_config.max_new_tokens
    resp = jax.ShapeDtypeStruct((B, R), jnp.int32)
    mb = harness._ppo_minibatch_sds(trainer)
    stacked = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((2,) + x.shape, x.dtype), mb
    )
    engine = server.engine
    programs = {
        "jit_sampler": (
            trainer._sample_jit.lower(params, prompt, prompt, key),
            ("prefill", "decode_step"),
        ),
        "jit__ref_logprobs": (
            trainer._score_ref_jit.lower(
                harness._sds(trainer.ref_params), params,
                prompt, prompt, resp, resp,
            ),
            ("ref_score",),
        ),
        "jit_train_step": (
            trainer._train_step_jit.lower(harness._sds(trainer.state), mb),
            ("train_step", "policy_forward", "loss", "optimizer"),
        ),
        "jit_train_phase": (
            trainer._train_phase_jit.lower(
                harness._sds(trainer.state), stacked
            ),
            ("train_phase", "train_step", "policy_forward", "loss", "optimizer"),
        ),
        "jit_decode_step": (
            engine.decode_step_jit.lower(
                harness._sds(engine._params), harness._sds(engine._state)
            ),
            ("decode_step",),
        ),
    }
    for module, (lowered, scopes) in programs.items():
        text = lowered.as_text(debug_info=True)
        assert re.search(r"module @%s\b" % module, text), module
        for scope in scopes:
            assert _scoped(text, scope), (module, scope)


# ------------------------- which step recompiled -------------------------- #


def test_compile_lands_as_span_under_the_open_span_and_counts():
    with telemetry.scoped_tracer() as tracer, telemetry.scoped_metrics() as reg:
        telemetry.watch_compiles()
        telemetry.watch_compiles()  # installed once: no double counting
        x = jnp.arange(7.0)
        jax.block_until_ready(x)
        before = reg.snapshot()["counters"].get("jit/compiles", 0.0)
        with telemetry.span("serve/step") as step:
            # a program no other test builds: compiled here and now
            jax.jit(lambda v: jnp.tanh(v) * 1.2345 + 7.0)(x).block_until_ready()
        counters = reg.snapshot()["counters"]
        compiles = [s for s in tracer.spans("jit/compile") if s.parent == step.index]
    assert counters["jit/compiles"] - before == len(compiles) == 1
    assert counters["jit/compile_s"] > 0.0
    (c,) = compiles
    assert c.depth == step.depth + 1
    assert step.start <= c.start + 1e-3 and c.end <= step.end + 1e-3
    assert c.duration_ms > 0.0
