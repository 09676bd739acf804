"""Driver ``serve``: open-loop load against ``InferenceServer``.

The server has no thread and no public single step, so the generator is
the serving loop: it submits every request that is due, runs one
``_pump_once`` (one admission round and at most one decode step), reads
what each open stream received, and repeats. A request's clock starts
when it was *due*, not when the loop got round to submitting it; how late
the loop ran is reported as ``loadgen_lag_p95_ms``. After the window no
request is submitted and the loop drains for ``drain_limit_s``; a request
still open then has failed and misses every latency.

A traffic file with ``"driver": "serve"`` gives: ``seq_length``,
``prompt_lengths``, ``max_new_tokens``, ``slots``, ``admit_width``,
``harvest_width``, ``arrivals`` (``process``, ``knee_per_s``, ``load``[,
``cv``]), ``drain_limit_s``, ``warmup_requests``, ``trace_seconds``.
Three more keys are optional, for a mix in which a seed would otherwise
change the work (``loadgen``'s docstring): ``min_new_tokens`` (1 where
absent), the program's own ``gen_kwargs`` key, below which it draws no
EOS, so a mix that sets it to its ``max_new_tokens`` gets requests that
run to their budget whatever the seeded head says; ``weights_seed``,
which makes the served parameters in ``--seed``'s place, one model for
every run of the cell; and ``order_seed``, which orders the prompt lengths
in ``--seed``'s place, the same lengths at the same instants in every run.

A traced run offers the cell's whole window, like any other run, and
every histogram, counter and client clock is read over all of it. Only
the profiler is held to a slice: it opens ``trace_seconds`` before the
window's end, when the slots have long filled, and the device trace is
cut to the span ``bench/steady`` from there to the window's end (the
profiler itself is stopped after the drain, so that writing the trace
stalls no request).
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import checks, harness, loadgen
from benchmark.arithmetic import model_shape

N_CHECK_ROWS = 4


def build_config(cell: Dict[str, Any]):
    from trlx_tpu.data.configs import TRLConfig

    cf, t = cell["config_file"], cell["traffic_file"]
    eos = cf["vocab_size"] - 1
    scratch = os.path.join(harness.REPO, ".bench_trace", "program_out")
    return TRLConfig.from_dict({
        "model": {"model_type": cf["model_type"], "model_arch": harness.arch_of(cf)},
        "train": {
            "seq_length": t["seq_length"],
            "batch_size": t["admit_width"],
            "epochs": 1,
            "total_steps": 1,
            "checkpoint_dir": os.path.join(scratch, "ckpt"),
            "mesh": dict(cell["mesh"]),
            "dtype": cf["run"]["dtype"],
            "param_dtype": cf["run"]["param_dtype"],
            "rollout": {
                "slots": t["slots"], "admit_width": t["admit_width"],
                "harvest_width": t["harvest_width"],
            },
            # one SLO class whose budget cannot refuse or trip inside a run
            "serving": {"slo_classes": {"standard": {"queue_wait_budget_ms": 3600000}}},
        },
        "method": {
            "name": "PPOConfig",
            "gen_kwargs": {
                "max_new_tokens": t["max_new_tokens"],
                "min_new_tokens": t.get("min_new_tokens", 1),
                "top_k": 0,
                "do_sample": True,
                "eos_token_id": eos,
                "pad_token_id": eos,
            },
        },
    })


def seeded_params(config, seed: int):
    """The served parameters, made on the device in one jitted call from
    the seed, in the type they are served in (``param_dtype``)."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.heads import CausalLMWithValueHead
    from trlx_tpu.trainer.ppo_trainer import get_causal_arch

    family, model_config, _ = get_causal_arch(config)
    model = CausalLMWithValueHead(model_config, backbone_cls=family.backbone_cls)
    key = jax.random.PRNGKey(loadgen.program_seed(seed))
    return jax.jit(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"])(key)


class Client:
    """One open streamed request as its client sees it."""

    __slots__ = ("rid", "due", "submitted", "stream", "token_times", "prompt_len", "surplus")

    def __init__(self, rid, due, submitted, stream, prompt_len):
        self.rid, self.due, self.submitted, self.stream = rid, due, submitted, stream
        self.token_times: List[float] = []
        self.prompt_len = prompt_len
        self.surplus = 0  # tokens streamed past the budget the client asked for


class TraceSlice:
    """The profiler over the last ``trace_seconds`` of the window: opened
    by the loop's first tick at or past ``start_s``, the span
    ``bench/steady`` closed by the first at or past ``end_s``. ``probe()``
    (the engine's occupancy and step counters) is read at both ends, so
    the slice's own mean batch stands beside its device times."""

    SPAN = "steady"

    def __init__(self, profiler: harness.ProfilerWindow, start_s: float, end_s: float, probe):
        self.profiler, self.start_s, self.end_s, self.probe = profiler, start_s, end_s, probe
        self.span = None
        self.opened = False
        self.at_open = self.at_close = None

    def __call__(self, t: float) -> None:
        import jax

        if not self.opened and t >= self.start_s:
            self.profiler.start()
            self.opened = True
            self.span = jax.profiler.TraceAnnotation(harness.SPAN_PREFIX + self.SPAN)
            self.span.__enter__()
            self.at_open = self.probe()
        elif self.span is not None and t >= self.end_s:
            self.at_close = self.probe()
            self.span.__exit__(None, None, None)
            self.span = None

    def mean_batch(self):
        """Occupied slots per decode step inside the slice."""
        if self.at_close is None or self.at_close[1] == self.at_open[1]:
            return None
        return (self.at_close[0] - self.at_open[0]) / (self.at_close[1] - self.at_open[1])

    def stop(self):
        if not self.opened:
            return None
        self(float("inf"))
        return self.profiler.stop()


def drive(server, prompts, due_times, seconds: float, drain_limit_s: float,
          spans: harness.Spans, budget: int, tick=None) -> Dict[str, Any]:
    """The open loop. Times are seconds from the window's start. A client
    reads ``budget`` tokens and no more: the engine's tap keeps emitting
    for a slot that has spent its budget until its harvest group fills
    (counted as ``surplus``; PERF.md, Open questions). ``tick(t)`` is
    called once an iteration, between pumps."""
    t0 = time.perf_counter()
    now = lambda: time.perf_counter() - t0
    open_: Dict[int, Client] = {}
    done: List[Client] = []
    results: Dict[int, Dict[str, Any]] = {}
    nxt, n = 0, len(prompts)
    refused = 0
    while True:
        t = now()
        if tick is not None:
            tick(t)
        if nxt < n and due_times[nxt] <= t:
            with spans.span("submit"):
                while nxt < n and due_times[nxt] <= t:
                    try:
                        (rid,) = server.submit([prompts[nxt]], stream=True)
                    except Exception as e:  # refused: a failed request
                        refused += 1
                        print(f"request {nxt} refused: {type(e).__name__}: {e}", flush=True)
                    else:
                        open_[rid] = Client(rid, due_times[nxt], now(), server.stream(rid),
                                            len(prompts[nxt]))
                    nxt += 1
        with spans.span("pump"):
            progressed = server._pump_once()
        t = now()
        for rid in list(open_):
            c = open_[rid]
            got = c.stream.drain()
            if got:
                keep = min(len(got), budget - len(c.token_times))
                c.token_times.extend([t] * keep)
                c.surplus += len(got) - keep
            if c.stream.closed and server.poll(rid) is not None:
                results[rid] = server.pop_result(rid)
                done.append(open_.pop(rid))
        if nxt >= n and not open_:
            break
        if t > seconds + drain_limit_s:
            break
        if not progressed and nxt < n:
            time.sleep(max(0.0, min(0.001, due_times[nxt] - now())))
    return {"done": done, "unfinished": list(open_.values()), "results": results,
            "refused": refused, "ended_s": now()}


def client_side(out: Dict[str, Any], window_s: float) -> Dict[str, Any]:
    """What the clients saw: time to first token from the due time, every
    gap between consecutive tokens of a request, how late the generator
    submitted, and the output tokens that reached a client inside the
    window (of every request, finished by then or not: all the work of
    the window over all its time; whole requests would count in steps of
    128 tokens, 0.65% of a window each)."""
    done = out["done"]
    everyone = done + out["unfinished"]
    return {
        "ttft": [c.token_times[0] - c.due for c in done if c.token_times],
        "gaps": [b - a for c in done for a, b in zip(c.token_times, c.token_times[1:])],
        "lag": [c.submitted - c.due for c in everyone],
        "in_window": [c for c in done if c.token_times and c.token_times[-1] <= window_s],
        "tokens_in_window": sum(1 for c in everyone for x in c.token_times if x <= window_s),
    }


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_start: float, device: Dict[str, Any]) -> Dict[str, Any]:
    from trlx_tpu import telemetry
    from trlx_tpu.inference.server import InferenceServer

    cf, t = cell["config_file"], cell["traffic_file"]
    compiles = harness.CompileCounter().install()
    spans = harness.Spans()
    config = build_config(cell)
    t_imported = time.time()
    params = seeded_params(config, t.get("weights_seed", seed))
    server = InferenceServer(config, params=params, seed=loadgen.program_seed(seed))
    t_built = time.time()
    vocab, budget = cf["vocab_size"], t["max_new_tokens"]
    eos, least = vocab - 1, config.method.gen_kwargs["min_new_tokens"]  # as build_config read it from the mix

    window_s = float(seconds)
    due = loadgen.arrival_times(t["arrivals"], window_s, t["traffic_seed"])
    n_warm = int(t["warmup_requests"])
    prompts = loadgen.draw_prompts(
        t["prompt_lengths"], len(due) + n_warm, vocab, t["traffic_seed"], seed,
        order_seed=t.get("order_seed"),
    )
    # warm-up: the cell's own widths, streamed, with a partial last group so
    # the placeholder (release) program is built too
    warm = drive(server, prompts[:n_warm], np.zeros(n_warm), 0.0, 3600.0, spans, budget)
    if warm["unfinished"] or warm["refused"]:
        raise RuntimeError("warm-up requests did not complete")
    print(f"note setup: to_imports={t_imported - t_start:.1f}s server={t_built - t_imported:.1f}s "
          f"warmup={time.time() - t_built:.1f}s compiles={compiles.count} "
          f"compile_s={compiles.seconds:.1f}", flush=True)
    spans.clear()
    telemetry.get_metrics().clear()
    est = server.engine.stats
    occ0, steps0 = est.occupancy_sum, est.decode_steps
    tracing = TraceSlice(
        harness.ProfilerWindow(cell["name"]),
        max(0.0, window_s - float(t["trace_seconds"])), window_s,
        lambda: (est.occupancy_sum, est.decode_steps),
    ) if trace else None
    setup_s = time.time() - t_start
    mark = compiles.mark()

    out = drive(server, prompts[n_warm:], due, window_s, float(t["drain_limit_s"]), spans,
                budget, tick=tracing)
    xplane = tracing.stop() if tracing else None
    compiled_in_window = compiles.mark()[0] - mark[0]
    steps = est.decode_steps - steps0
    slot_util = (est.occupancy_sum - occ0) / (steps * t["slots"]) if steps else 0.0

    done, unfinished = out["done"], out["unfinished"]
    attempted = len(due)
    failed = len(unfinished) + out["refused"]
    seen = client_side(out, window_s)
    ttft, gaps, lag, in_window = seen["ttft"], seen["gaps"], seen["lag"], seen["in_window"]
    tokens_in_window = seen["tokens_in_window"]

    # ---------------- outside the window: what decides `correct` ---------------- #
    memory_peak = harness.memory_peak_bytes()  # the program's own: read before the reference runs
    log = harness.CheckLog()
    ok = True
    bad_len = bad_tok = 0
    for c in done:
        res = out["results"][c.rid]
        toks = res["tokens"]
        stopped = bool(toks) and toks[-1] == eos
        if not (res["length"] == budget or (stopped and least <= res["length"] < budget)):
            bad_len += 1
        if len(c.token_times) != res["length"]:
            bad_len += 1
        if any(not 0 <= int(x) < vocab for x in toks):
            bad_tok += 1
    ok &= log.line("accounting.requests_off_budget", bad_len,
                   f"== 0 (each returned {budget} tokens or stopped on EOS after {least} or more; "
                   "streamed as many as returned)", bad_len == 0)
    ok &= log.line("accounting.requests_with_token_outside_vocab", bad_tok, "== 0", bad_tok == 0)
    ok &= log.line("accounting.compiles_in_window", compiled_in_window, "== 0",
                   compiled_in_window == 0)
    ok &= reference_check(cell, server, seed, log)
    events = [e.to_dict().get("detector") for e in server.health_events]
    print(f"note health_events (not part of correct): {events}", flush=True)

    mean_prompt = float(np.mean([c.prompt_len for c in done])) if done else 0.0
    metrics_now = server.metrics()
    scalars = harness.registry_scalars()  # the registry was cleared at the window's start
    pct = lambda xs, q: loadgen.percentile(xs, q) * 1e3 if xs else None
    med_gap = float(np.median(gaps)) if gaps else 0.0
    record = {
        "kind": "serve", "cell": cell, "device": device, "spans": spans,
        "tracer_stats": {}, "phases": 1, "window_s": window_s, "setup_s": setup_s,
        "compile_s_setup": mark[1], "xplane": xplane, "trace_clip": TraceSlice.SPAN,
        "chips": cell["chips"],
        "shape": model_shape(cell["family"], cf), "flops": (0.0, 0.0),
        "kv_cache_dtype": harness.kv_dtype_of(cf, t["seq_length"] + budget),
        "state_dtype": cf["run"].get("state_dtype"), "memory_peak_bytes": memory_peak,
        "histograms": {k: v for k, v in metrics_now.items() if isinstance(v, dict)},
        "counters": scalars["counters"], "gauges": scalars["gauges"],
        "engine_slot_util_pct": 100.0 * slot_util,
        "loadgen_lag_p95_ms": pct(lag, 95),
        "serve_ttft_p50_ms": pct(ttft, 50),
        "serve_ttft_p95_ms": pct(ttft, 95),
        "serve_itl_p50_ms": pct(gaps, 50),
        "serve_itl_p99_ms": pct(gaps, 99),
        # the share of gaps with something else between two decode steps
        # (an admission prefill): over twice the median gap
        "serve_long_gap_pct": 100.0 * float(np.mean(np.asarray(gaps) > 2 * med_gap)) if gaps else None,
        "decode": {
            # the traced slice's own mean batch where there is one
            "batch": (tracing and tracing.mean_batch()) or slot_util * t["slots"],
            "mean_context": mean_prompt + budget / 2.0,
        },
        "decode_steps": steps,
    }
    end_to_end = {
        "serve_itl_p95_ms": {"value": pct(gaps, 95) or 0.0, "unit": "ms"},
        "serve_tokens_per_s": {"value": tokens_in_window / window_s, "unit": "tokens/s"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    print(f"note serve: requests={attempted} done={len(done)} in_window={len(in_window)} "
          f"ttft_p50/p90/p95_ms={pct(ttft, 50)}/{pct(ttft, 90)}/{pct(ttft, 95)} "
          f"itl_p50/p95/p99_ms={pct(gaps, 50)}/{pct(gaps, 95)}/{pct(gaps, 99)} "
          f"long_gap_pct={record['serve_long_gap_pct']} "
          f"lag_p95_ms={record['loadgen_lag_p95_ms']} ended_s={out['ended_s']:.2f} "
          f"stream_tokens_past_budget={sum(c.surplus for c in done)}", flush=True)
    return {"correct": bool(ok), "attempted": attempted, "failed": failed,
            "end_to_end": end_to_end, "record": record, "checks": log}


def reference_check(cell: Dict[str, Any], server, seed: int, log: harness.CheckLog) -> bool:
    """One more harvest group through the idle server (admission prefill,
    then decode through the paged cache); for ``N_CHECK_ROWS`` of its
    requests the log-probabilities the engine recorded for the tokens it
    drew against the float32 reference's on the served parameters. A
    result carries no log-probabilities, so the groups are read as the
    server lands them (PERF.md, Open questions)."""
    import jax
    import jax.numpy as jnp

    cf, t = cell["config_file"], cell["traffic_file"]
    Q, width = t["seq_length"], t["harvest_width"]
    prompts = loadgen.draw_prompts(
        t["prompt_lengths"], width, cf["vocab_size"], t["traffic_seed"], seed + 1
    )
    landed = []
    land = server._land_group

    def tap(group):
        landed.append((dict(server._row_to_req), group))
        return land(group)

    server._land_group = tap
    try:
        rids = server.submit(prompts)
        server.wait(rids)
    finally:
        server._land_group = land
    found = {}
    for row_to_req, group in landed:
        fetched = {k: np.asarray(jax.device_get(group[k]))
                   for k in ("tokens", "response_mask", "logprobs")}
        for j, row in enumerate(group["rows"]):
            rid = row_to_req.get(row)
            if rid in rids[:N_CHECK_ROWS]:
                found[rid] = {k: v[j] for k, v in fetched.items()}
    if len(found) != N_CHECK_ROWS:
        return log.line("reference.requests_read_back", len(found), f"== {N_CHECK_ROWS}", False)
    padded = [server._pad_prompt(p, i) for i, p in enumerate(prompts[:N_CHECK_ROWS])]
    ids = np.stack([p[0] for p in padded])
    mask = np.stack([p[1] for p in padded])
    stack = lambda k: np.stack([found[r][k] for r in rids[:N_CHECK_ROWS]])
    r_ids, r_mask, r_lp = stack("tokens"), stack("response_mask"), stack("logprobs")
    full_ids = np.concatenate([ids, r_ids], axis=1)
    full_mask = np.concatenate([mask, r_mask], axis=1)
    one = jax.devices()[0]
    params = jax.device_put(server.params, one)
    ref = checks.reference_logits(
        cell["family"], cf, params["transformer"],
        jax.device_put(jnp.asarray(full_ids), one), jax.device_put(jnp.asarray(full_mask), one), Q,
    )
    tol = checks.tolerances_of(cf, harness.kv_dtype_of(cf, Q + t["max_new_tokens"]), cell["root"])
    return checks.compare_with_reference(log, "reference", ref, r_ids, r_mask, r_lp, None, tol)
