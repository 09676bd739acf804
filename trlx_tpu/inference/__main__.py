"""Serving smoke: ``python -m trlx_tpu.inference --smoke``.

The CI ``serving-smoke`` job's entry point (code_quality.yml): build the
tiny harness policy, save a real trainer checkpoint, load it through
:class:`~trlx_tpu.inference.server.InferenceServer` (no trainer in the
serving process path), submit a prompt batch, and assert every request
completes with zero health events. Prints one JSON line with the
completion lengths and the engine's occupancy stats so the job log shows
what the engine actually did.

The smokes run on whatever platform the environment gives jax (the chip on
a TPU host); CI and the tests export ``JAX_PLATFORMS=cpu`` and an 8-device
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` themselves.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile


def serving_smoke(mesh=None, n_prompts: int = 6) -> int:
    import numpy as np

    from trlx_tpu.analysis import harness
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.inference.server import InferenceServer
    from trlx_tpu.telemetry.health import without_timing
    from trlx_tpu.utils.checkpoint import save_checkpoint

    # a real checkpoint round-trip: the smoke must exercise the same
    # load path a served production policy takes
    cfg = harness.tiny_config_dict("ppo", mesh=mesh)
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    trainer = PPOTrainer(TRLConfig.from_dict(cfg))
    ckpt = tempfile.mkdtemp(prefix="serving_smoke_ckpt_")
    save_checkpoint(ckpt, trainer.state, metadata={}, step=1)
    del trainer

    scfg = harness.tiny_config_dict("ppo", mesh=mesh)
    scfg["train"]["rollout"] = {
        "slots": 4, "admit_width": 2, "harvest_width": 2, "block_size": 4,
    }
    # CPU-tier SLO budgets: queue waits here include jit COMPILE walls
    # (seconds), which production latency never pays — a tight default
    # budget would trip slo-breach on a perfectly healthy run
    scfg["train"]["serving"] = {
        "slo_classes": {"standard": {"queue_wait_budget_ms": 120000}},
    }
    server = InferenceServer(TRLConfig.from_dict(scfg), checkpoint_dir=ckpt)

    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(1, 30, int(rng.integers(2, 8))))
        for _ in range(n_prompts)
    ]
    ids = server.submit(prompts)
    results = server.wait(ids)

    failures = []
    for rid in ids:
        out = results.get(rid)
        if out is None or out["length"] < 1:
            failures.append(rid)
    # a stalled host is the machine's doing, not the run's health
    events = without_timing(server.health_events)
    record = {
        "completed": len(ids) - len(failures),
        "submitted": len(ids),
        "lengths": [results[r]["length"] for r in ids if r in results],
        "health_events": [ev.to_dict() for ev in events],
        # per-request latency histograms (docs/observability.md,
        # "Serving metrics"): queue wait / prefill / TTFT / per-token
        # decode / e2e summaries — the CI job asserts these keys exist
        # with nonzero counts in the JSON artifact
        "serving_metrics": server.metrics(),
        **server.stats(),
    }
    print(json.dumps(record))
    if failures:
        print(f"serving-smoke FAIL: requests {failures} incomplete",
              file=sys.stderr)
        return 1
    if events:
        print(f"serving-smoke FAIL: {len(events)} health events on a "
              "clean run", file=sys.stderr)
        return 1
    from trlx_tpu import telemetry
    from trlx_tpu.inference.server import SERVE_HISTOGRAMS

    if telemetry.get_metrics().enabled:
        missing = [
            k for k in SERVE_HISTOGRAMS
            if not record["serving_metrics"].get(k, {}).get("count")
        ]
        if missing:
            print(f"serving-smoke FAIL: request-latency histograms "
                  f"{missing} missing/empty", file=sys.stderr)
            return 1
    else:
        # TRLX_TELEMETRY=0 (or non-rank-0): histograms are legitimately
        # absent — telemetry off is the operator's choice, not a wiring
        # regression; the completion/health gates above still hold
        print("serving-smoke: metrics registry disabled — skipping "
              "request-latency key check", file=sys.stderr)
    # run-ledger recording (docs/observability.md "Run ledger"): with
    # $TRLX_RUN_LEDGER set, each smoke appends a manifest — the CI
    # perf-budget job records two and diffs them via --compare
    if os.environ.get("TRLX_RUN_LEDGER"):
        from trlx_tpu.telemetry.run_ledger import (
            append_manifest,
            build_manifest,
            numeric_payload,
        )

        append_manifest(
            build_manifest("serving-smoke", payload=numeric_payload(record))
        )
    print("serving-smoke PASS: all requests completed, zero health events",
          file=sys.stderr)
    return 0


def multi_tenant_smoke(mesh=None, span_log=None) -> int:
    """The serving-tier QoS smoke (docs/serving.md; CI serving-smoke
    job, multi-tenant step). One CPU run must demonstrate:

    - **priority admission**: a high-priority tenant's requests,
      submitted AFTER a low-priority tenant's, complete strictly ahead
      of them (the slot pool is smaller than the request count, so
      ordering is a scheduling decision, not an accident);
    - **quota without starvation**: the low-priority tenant is
      token-bucket-throttled (observable throttled rounds) yet every
      one of its requests still completes;
    - **streamed TTFT < wait-for-harvest TTFT**: the first streamed
      token of a ``stream=True`` request arrives strictly before the
      same request's harvested result exists;
    - **prefix sharing**: a shared system-prompt prefix across tenants
      yields a nonzero ``engine/prefix_hit_rate``;
    - **per-tenant metrics**: ``serve/*[tenant=...]`` histogram keys
      land in the artifact with nonzero counts;
    - **request tracing**: every completed request emitted a closed
      ``serve/request`` span chain and the span ring dropped NOTHING
      (an evicting ring silently truncates traces — the assert is the
      capacity canary for telemetry.ring_size);
    - **chunked prefill**: the scenario runs with
      ``rollout.prefill_chunk`` enabled and a per-pump chunk budget
      (``prefill_chunks_per_pump`` — Sarathi-style stall-free
      admission), and must report ``engine/prefill_chunks > 0`` while
      staying bitwise-served (the parity contract is pinned in
      tests/test_chunked_prefill.py; here the gate is that the chunked
      serving path carries real multi-tenant traffic cleanly);
    - **speculative decoding**: the scenario serves through the
      trie-drafted spec path (``rollout.spec_decode`` with the
      ``drafter: trie`` wired to the shared-prefix pool), must report
      ``engine/spec_accept_rate > 0``, and a spec-off rerun over the
      same prompts must reproduce every served row bitwise (the verify
      step's acceptance contract, end to end);
    - **zero health events** on this clean run.

    ``span_log`` exports the whole span stream (phase + request spans
    and counter tracks, one Perfetto JSONL) — the CI job feeds it to
    ``python -m trlx_tpu.telemetry --trace-report``.
    """
    import numpy as np

    from trlx_tpu import telemetry
    from trlx_tpu.analysis import harness
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.inference.server import InferenceServer
    from trlx_tpu.telemetry.health import without_timing

    scfg = harness.tiny_config_dict("ppo", mesh=mesh)
    # near-greedy decode with a longer budget: random-init generation
    # falls into short loops the trie/n-gram drafter locks onto, so the
    # spec path sees real acceptance
    scfg["method"]["gen_kwargs"].update(
        {"temperature": 0.05, "max_new_tokens": 16, "min_new_tokens": 8}
    )
    scfg["train"]["rollout"] = {
        # serving ignores the trainer-side engine choice, but
        # spec_decode's config validation pins it to "continuous"
        "engine": "continuous",
        "slots": 4, "admit_width": 2, "harvest_width": 2, "block_size": 4,
        # chunked prefill, serving tier: admission prefill runs as
        # need-gated prompt-column chunks, at most one chunk forward
        # per pump (stall-free admission under bursts)
        "prefill_chunk": 4, "prefill_chunks_per_pump": 1,
        # speculative decoding through the shared-prefix trie drafter
        # (docs/inference.md "Speculative decoding")
        "spec_decode": {"enabled": True, "max_draft": 4, "drafter": "trie"},
    }
    serving_cfg = {
        "prefix_cache_blocks": 16,
        # generous CPU-tier budgets (queue waits include compile
        # walls); the slo-breach detector is unit-tested with tight
        # budgets in tests/test_serving.py
        "slo_classes": {
            "interactive": {"queue_wait_budget_ms": 120000},
            "standard": {"queue_wait_budget_ms": 120000},
        },
        "tenants": {
            "gold": {"priority": 10, "slo_class": "interactive"},
            # burst covers ONE request's cost (Q + R tokens), the
            # rate refills roughly two requests/second: bronze is
            # throttled to a trickle but never starves
            "bronze": {
                "priority": 0, "rate": 60.0, "burst": 26.0,
                "slo_class": "standard",
            },
        },
    }
    server = InferenceServer(TRLConfig.from_dict(scfg), serving=serving_cfg)
    Q, R = server.query_length, server.engine.R
    rng = np.random.default_rng(0)
    system_prefix = [5, 6, 7, 8]  # shared across BOTH tenants
    def make_prompts(n):
        # cyclic two-token tails: every suffix recurs, so the drafter
        # has n-gram matches from the first decode step
        out = []
        for _ in range(n):
            a, b = (int(x) for x in rng.integers(1, 30, 2))
            tail = list(np.tile([a, b], Q))[: Q - len(system_prefix)]
            out.append(system_prefix + tail)
        return out

    bronze_prompts = make_prompts(4)
    gold_prompts = make_prompts(4)
    stream_prompts = make_prompts(1)
    # low-priority bronze submits FIRST; gold afterwards — priority
    # admission must still serve gold ahead of bronze
    bronze = server.submit(bronze_prompts, tenant="bronze")
    gold = server.submit(gold_prompts, tenant="gold")
    stream_rid = server.submit(
        stream_prompts, tenant="gold", stream=True
    )[0]

    # streamed TTFT: pull the first token through the stream iterator
    # (it pumps the serving loop); wait-for-harvest TTFT: keep pumping
    # until the SAME request's harvested result exists
    t0 = telemetry.monotonic()
    first_token = next(server.stream(stream_rid))
    ttft_stream_ms = (telemetry.monotonic() - t0) * 1000.0
    result_at_first_token = server.poll(stream_rid)
    while server.poll(stream_rid) is None:
        server.step()
    ttft_harvest_ms = (telemetry.monotonic() - t0) * 1000.0

    server.flush()
    # engine rows are allocated in admission-feed order: the scheduler's
    # decision trail (captured before wait() pops the bookkeeping)
    admit_pos = dict(server._req_row)
    results = server.wait(bronze + gold + [stream_rid])

    order = server.completion_order
    rank = {rid: i for i, rid in enumerate(order)}
    gold_ranks = [rank[r] for r in gold + [stream_rid]]
    bronze_ranks = [rank[r] for r in bronze]
    gold_rows = [admit_pos[r] for r in gold + [stream_rid]]
    bronze_rows = [admit_pos[r] for r in bronze]
    stats = server.stats()
    metrics = server.metrics()
    # a stalled host is the machine's doing, not the run's health
    events = without_timing(server.health_events)

    tracer = telemetry.get_tracer()
    request_spans = (
        [s for s in tracer.spans() if s.name == "serve/request"]
        if tracer.enabled
        else []
    )

    # spec-off rerun: the same config with spec_decode disabled, the
    # same prompts in the same submission order (=> identical draw
    # positions => identical per-row keys), so every served row must be
    # BITWISE what the one-token loop produces — the verify step's
    # acceptance contract, exercised end-to-end through real
    # multi-tenant traffic
    import copy

    scfg_off = copy.deepcopy(scfg)
    scfg_off["train"]["rollout"].pop("spec_decode")
    server_off = InferenceServer(
        TRLConfig.from_dict(scfg_off), serving=serving_cfg
    )
    off_bronze = server_off.submit(bronze_prompts, tenant="bronze")
    off_gold = server_off.submit(gold_prompts, tenant="gold")
    off_stream = server_off.submit(stream_prompts, tenant="gold")
    results_off = server_off.wait(off_bronze + off_gold + off_stream)
    spec_parity = all(
        results[a]["tokens"] == results_off[b]["tokens"]
        for a, b in zip(
            bronze + gold + [stream_rid],
            off_bronze + off_gold + off_stream,
        )
    )

    record = {
        "spec_drafter": type(server.engine.spec_drafter).__name__,
        "spec_off_row_parity": bool(spec_parity),
        "completion_order_tenants": [
            "gold" if r in set(gold + [stream_rid]) else "bronze"
            for r in order
        ],
        "gold_ranks": gold_ranks,
        "bronze_ranks": bronze_ranks,
        "gold_admission_rows": gold_rows,
        "bronze_admission_rows": bronze_rows,
        "first_streamed_token": int(first_token),
        "ttft_stream_ms": round(ttft_stream_ms, 3),
        "ttft_harvest_ms": round(ttft_harvest_ms, 3),
        "scheduler_throttled_rounds": stats["scheduler/throttled_rounds"],
        "prefix_hit_rate": stats["engine/prefix_hit_rate"],
        "prefix_blocks_saved": stats["engine/prefix_blocks_saved"],
        "prefill_chunks": stats["engine/prefill_chunks"],
        "prefill_cols_skipped": stats["engine/prefill_cols_skipped"],
        "prefill_flops_saved": stats["engine/prefill_flops_saved"],
        "released_placeholders": stats["engine/released"],
        "request_spans": len(request_spans),
        "spans_dropped": int(tracer.dropped),
        "health_events": [ev.to_dict() for ev in events],
        "serving_metrics": metrics,
        # the full engine/scheduler counter row (engine/prefix_hit_rate,
        # engine/released, scheduler/*) — the CI job asserts on these
        # keys in the artifact, same as the single-tenant smoke
        **stats,
    }
    print(json.dumps(record))
    if span_log and tracer.enabled:
        n_events = telemetry.export_chrome_jsonl(
            span_log,
            tracer.spans(),
            counters=telemetry.get_metrics().gauge_series(),
        )
        print(
            f"mt-smoke: exported {n_events} trace events to {span_log}",
            file=sys.stderr,
        )

    failures = []
    if len(results) != 9 or any(
        results[r]["length"] < 1 for r in results
    ):
        failures.append("not every request completed")
    if max(gold_rows) > min(bronze_rows):
        failures.append(
            "priority inversion: a bronze request was ADMITTED before "
            "the last gold request despite submitting earlier with "
            "lower priority"
        )
    if sorted(gold_ranks[:4]) != list(range(4)):
        failures.append(
            "the first completions were not the first gold wave"
        )
    # single-process CPU smoke: these are host-side scheduler/engine
    # counters (never device collectives), so branching cannot desync
    if stats["scheduler/throttled_rounds"] < 1:  # tpu-lint: disable=host-branch
        failures.append("bronze quota never throttled")
    if result_at_first_token is not None:
        failures.append("harvest completed before the first streamed token")
    if not ttft_stream_ms < ttft_harvest_ms:
        failures.append(
            f"streamed TTFT {ttft_stream_ms:.1f}ms not below "
            f"wait-for-harvest TTFT {ttft_harvest_ms:.1f}ms"
        )
    if not stats["engine/prefix_hit_rate"] > 0:  # tpu-lint: disable=host-branch
        failures.append("prefix sharing produced zero hits")
    if not stats["engine/prefill_chunks"] > 0:  # tpu-lint: disable=host-branch
        failures.append(
            "chunked prefill never ran (engine/prefill_chunks == 0) "
            "despite rollout.prefill_chunk being set"
        )
    if not stats["engine/spec_accept_rate"] > 0:  # tpu-lint: disable=host-branch
        failures.append(
            "spec decode accepted nothing (engine/spec_accept_rate == 0) "
            "despite rollout.spec_decode being enabled"
        )
    if not spec_parity:
        failures.append(
            "spec-on served rows are not bitwise-identical to the "
            "spec-off rerun"
        )
    events_off = without_timing(server_off.health_events)
    if events_off:
        failures.append(
            f"{len(events_off)} health events on the "
            "spec-off rerun"
        )
    if telemetry.get_metrics().enabled:
        for tenant in ("gold", "bronze"):
            key = f"serve/queue_wait_ms[tenant={tenant}]"
            if not metrics.get(key, {}).get("count"):
                failures.append(f"missing per-tenant histogram {key}")
    if tracer.enabled:
        # trace completeness + capacity canary: one closed request-span
        # chain per completed request, zero ring evictions (a dropped
        # span truncates a trace silently — raise telemetry.ring_size)
        if len(request_spans) < len(results):
            failures.append(
                f"request tracing incomplete: {len(request_spans)} "
                f"serve/request spans for {len(results)} completed "
                "requests"
            )
        if telemetry.warn_on_span_drops(tracer):
            failures.append(
                f"span ring dropped {tracer.dropped} spans — raise "
                "telemetry.ring_size / TRLX_TELEMETRY_RING"
            )
    if events:
        failures.append(f"{len(events)} health events on a clean run")
    if failures:
        for f in failures:
            print(f"mt-smoke FAIL: {f}", file=sys.stderr)
        return 1
    print(
        "mt-smoke PASS: priority ordering, quota-throttle-no-starve, "
        f"streamed TTFT {ttft_stream_ms:.0f}ms < harvest "
        f"{ttft_harvest_ms:.0f}ms, prefix hit rate "
        f"{stats['engine/prefix_hit_rate']:.2f}, "
        f"{stats['engine/prefill_chunks']:.0f} prefill chunks "
        f"({stats['engine/prefill_cols_skipped']:.0f} cols skipped), "
        f"spec accept rate {stats['engine/spec_accept_rate']:.2f} "
        "(bitwise vs spec-off), zero health events",
        file=sys.stderr,
    )
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m trlx_tpu.inference",
        description="continuous-batching serving utilities",
    )
    parser.add_argument(
        "--smoke", action="store_true",
        help="run the serving smoke: checkpoint round-trip through "
        "InferenceServer, assert completions + zero health events",
    )
    parser.add_argument(
        "--mt-smoke", action="store_true",
        help="run the multi-tenant QoS smoke: priority ordering, "
        "quota throttling without starvation, streamed TTFT below "
        "harvest TTFT, nonzero prefix-sharing hit rate, per-tenant "
        "serve/* histograms, complete request traces with zero span "
        "drops, zero health events",
    )
    parser.add_argument(
        "--span-log", metavar="PATH", default=None,
        help="with --mt-smoke: export the run's span stream (phase + "
        "per-request spans + counter tracks) as Perfetto JSONL — the "
        "input of `python -m trlx_tpu.telemetry --trace-report`",
    )
    args = parser.parse_args(argv)
    if args.smoke:
        return serving_smoke()
    if args.mt_smoke:
        return multi_tenant_smoke(span_log=args.span_log)
    parser.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
