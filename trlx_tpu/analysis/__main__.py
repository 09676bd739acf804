"""CLI: ``python -m trlx_tpu.analysis [--strict] [--json] ...``.

Exit status: 0 when clean; 1 when findings remain (``--strict`` counts
warnings too, plain mode only errors). Designed for CI on CPU-only
runners — the jaxpr audit forces an 8-virtual-device CPU platform before
JAX initializes so collective/sharding structure is real.

Besides the rule engines there are report modes: ``--sanitize
<trainer>`` (eqn-level non-finite replay), ``--resources`` (static
peak-HBM / collective / FLOP budgets per traced program), ``--compile-
audit`` (runtime compile counting), ``--perf-audit`` (measured
per-span wall-clock over the instrumented phase loop), and
``--lockstep`` (N simulated controller processes diffing per-host
dispatch logs), ``--hlo-audit`` (AOT-compiled post-SPMD HLO vs
jaxpr intent), and ``--races`` (host-concurrency lockset lint +
deterministic-schedule interleaving engine) — the budgeted modes gated
against the committed
``analysis/budgets.json`` with ``--update-budgets`` relocking each
engine's own section. JSON output
carries a top-level ``schema_version`` and deterministic ordering so CI
artifacts diff cleanly.
"""

from __future__ import annotations

import argparse
import os
import sys


def _force_cpu_platform() -> None:
    """Make the audit runnable on any host, before jax first initializes."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["XLA_FLAGS"] = flags


def _emit_smoke(summary, format_smoke_text, as_json: bool) -> int:
    """Shared tail of every ``--*-smoke`` mode: print the summary (JSON
    or text) and map ``passed`` to the exit code — one place to fix the
    contract instead of one copy per smoke."""
    import json as _json

    if as_json:
        print(_json.dumps(summary, default=str))
    else:
        print(format_smoke_text(summary))
    return 0 if summary["passed"] else 1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m trlx_tpu.analysis",
        description="jaxpr + AST static analysis for the TPU port. A CPU "
        "tool by design: every mode that builds programs forces "
        "JAX_PLATFORMS=cpu and an 8-device virtual mesh unless the "
        "environment already sets them, and never touches an accelerator.",
    )
    parser.add_argument(
        "--engine",
        choices=(
            "all", "jaxpr", "ast", "nanflow", "collective", "donation",
            "compile", "prng",
        ),
        default="all",
        help="which engine(s) to run (default: all; `compile` here is "
        "the static retrace-risk rules — the runtime trace-count "
        "harness is --compile-audit)",
    )
    parser.add_argument(
        "--compile-audit",
        action="store_true",
        help="instead of the rule engines: run each trainer's canonical "
        "short loop with a compilation hook, attribute every XLA "
        "compile to its jitted callable, gate counts against the "
        "compile_budgets section of analysis/budgets.json, and diff "
        "step-0 vs step-k jaxprs on any steady-state retrace "
        "(--update-budgets relocks the counts)",
    )
    parser.add_argument(
        "--lockstep",
        action="store_true",
        help="instead of the rule engines: simulate each trainer's "
        "canonical loop as N controller processes (threads with "
        "per-thread jax.process_index/process_count and rank-0 gates), "
        "record every jitted/collective-bearing dispatch per host, diff "
        "the logs (any divergence is a future multi-host deadlock, "
        "localized to ordinal + file:line + guarding branch), and gate "
        "host-0 dispatch fingerprints against the lockstep_budgets "
        "section of analysis/budgets.json (--update-budgets relocks)",
    )
    parser.add_argument(
        "--hosts",
        type=int,
        default=2,
        help="with --lockstep: number of simulated controller processes "
        "(default 2)",
    )
    parser.add_argument(
        "--plant-divergence",
        action="store_true",
        help="with --lockstep: plant one rank-0-only dispatch at the end "
        "of the loop — self-check that the simulator localizes exactly "
        "this hazard (budget gating is skipped; exit must be 1)",
    )
    parser.add_argument(
        "--races",
        action="store_true",
        help="instead of the rule engines: host-concurrency race audit — "
        "static thread-entry-point inventory + attribute-level lockset "
        "walk (unguarded-shared-write, lock-order-cycle, "
        "signal-unsafe-handler, atomicity-split), then a deterministic "
        "cooperative scheduler running the real async-writer, engine "
        "drive/weight-push, and TokenStream paths under N seeded "
        "interleavings asserting the repo's cross-thread invariants "
        "(schedule-invariant-violation names the replayable seed)",
    )
    parser.add_argument(
        "--schedules",
        type=int,
        default=6,
        help="with --races: seeded interleavings explored per scenario "
        "(default 6; nightly sweeps pass more)",
    )
    parser.add_argument(
        "--race-seed",
        type=int,
        default=None,
        metavar="SEED",
        help="with --races: replay exactly this one schedule seed per "
        "scenario instead of the 0..N-1 sweep (reproduce a reported "
        "schedule-invariant-violation)",
    )
    parser.add_argument(
        "--race-scenarios",
        metavar="NAMES",
        default=None,
        help="with --races: comma-separated subset of dynamic scenarios "
        "(writer-rows,stream-close,engine-push; default: all)",
    )
    parser.add_argument(
        "--plant-race",
        action="store_true",
        help="with --races: plant a deliberate unguarded counter through "
        "BOTH halves — the lockset walk must name "
        "unguarded-shared-write at the planted file:line and the "
        "scheduler must find a violating schedule; exit must be 1",
    )
    parser.add_argument(
        "--hlo-audit",
        action="store_true",
        help="instead of the rule engines: AOT-compile every traced "
        "program with its real in_shardings, parse the optimized "
        "post-SPMD HLO + buffer-assignment stats, diff the emitted "
        "collectives/dtypes/peak against jaxpr intent and the "
        "hlo_budgets section of analysis/budgets.json, and sweep the "
        "known-miscompile registry (--update-budgets relocks)",
    )
    parser.add_argument(
        "--plant-hazard",
        action="store_true",
        help="with --hlo-audit: compile a seeded eager concat of "
        "committed-sharded arrays — self-check that the audit trips "
        "both spmd-concat-hazard (at the planted line) and "
        "lowering-collective-drift (on the minted replica-axis "
        "all-reduce); budget gating is skipped; exit must be 1",
    )
    parser.add_argument(
        "--no-mesh-matrix",
        action="store_true",
        help="with --hlo-audit: compile only the audit-mesh program set, "
        "skipping the train-step compiles on the rest of the "
        "collective-divergence mesh matrix (faster; less coverage)",
    )
    parser.add_argument(
        "--resume-audit",
        action="store_true",
        help="instead of the rule engines: checkpoint/resume "
        "state-coverage audit — statically classify every mutable "
        "attribute on the trainer-reachable surface as "
        "checkpoint-carried / config-reconstructed / allowlisted "
        "ephemeral (resume-state-gap on anything else), run a "
        "kill/resume differ per trainer (checkpoint at a phase "
        "boundary, rebuild + restore, one more phase vs an "
        "uninterrupted twin, deep-compare the full live attribute "
        "trees: resume-divergence), and gate the checkpoint schema "
        "against the state_manifest section of analysis/budgets.json "
        "(ckpt-schema-drift; --update-budgets relocks)",
    )
    parser.add_argument(
        "--plant-gap",
        action="store_true",
        help="with --resume-audit: plant an uncheckpointed counter "
        "threaded into the sampling schedule — self-check that the "
        "static half names resume-state-gap at the planted file:line "
        "AND the differ names the divergent attribute path; schema "
        "gating is skipped; exit must be 1",
    )
    parser.add_argument(
        "--resources",
        action="store_true",
        help="instead of the rule engines: compute static peak-HBM / "
        "collective-bytes / FLOP budgets per traced program and gate "
        "them against the committed analysis/budgets.json contract",
    )
    parser.add_argument(
        "--perf-audit",
        action="store_true",
        help="instead of the rule engines: run the instrumented streamed "
        "phase loop (telemetry spans, docs/observability.md), measure "
        "per-span p50/p95 wall-clock, and gate the stable phase spans "
        "against the perf_budgets section of analysis/budgets.json "
        "(--update-budgets relocks; --span-log exports the trace)",
    )
    parser.add_argument(
        "--span-log",
        metavar="PATH",
        default=None,
        help="with --perf-audit: write the audited run's span stream to "
        "PATH as Perfetto/chrome-tracing JSONL",
    )
    parser.add_argument(
        "--perf-phases",
        type=int,
        default=5,
        help="with --perf-audit: measured phases per run (default 5; "
        "p50 over these gates the lockfile)",
    )
    parser.add_argument(
        "--plant-slowdown",
        type=float,
        default=0.0,
        metavar="MS",
        help="with --perf-audit: inject MS milliseconds of host-side "
        "sleep into every measured phase — self-check that a planted "
        "regression trips the perf-regression gate",
    )
    parser.add_argument(
        "--health-smoke",
        action="store_true",
        help="instead of the rule engines: planted-anomaly self-check "
        "for the run-health detectors (docs/observability.md) — clean "
        "streamed phases must stay quiet, then a poisoned embedding "
        "table must trip kl-spike + entropy-collapse and write a "
        "flight dump parseable by `python -m trlx_tpu.telemetry "
        "--inspect`; exit 1 when any leg fails",
    )
    parser.add_argument(
        "--chaos-smoke",
        action="store_true",
        help="instead of the rule engines: injected-failure self-check "
        "for the resilience layer (docs/resilience.md) — a clean "
        "supervised run must stay quiet; a transient checkpoint-I/O "
        "error must recover via bounded backoff; a structure mismatch "
        "must refuse fast; a SIGTERM at phase k must drain to an "
        "emergency checkpoint and auto-resume bitwise-identically; an "
        "engine-path failure must degrade to the fixed sampler with a "
        "health event; a disk-full rollout log must degrade to "
        "synchronous writes with zero row loss; exit 1 when any "
        "scenario fails",
    )
    parser.add_argument(
        "--chaos-workdir",
        metavar="DIR",
        default=None,
        help="with --chaos-smoke: scratch/artifact directory for the "
        "scenarios' checkpoints and logs (default: a temp dir)",
    )
    parser.add_argument(
        "--chaos-scenarios",
        metavar="NAMES",
        default=None,
        help="with --chaos-smoke: comma-separated subset of scenarios "
        "to run (default: all)",
    )
    parser.add_argument(
        "--async-smoke",
        action="store_true",
        help="instead of the rule engines: self-check for the "
        "asynchronous actor–learner path (docs/async_pipeline.md) — a "
        "staleness_window=0 async phase must be bitwise-identical to "
        "the serial same-plan phase with zero weight pushes, and a "
        "planted dead actor (engine.admit chaos) must surface an "
        "actor-dead health event and recover via the resilience "
        "supervisor with no hang; exit 1 when any scenario fails",
    )
    parser.add_argument(
        "--async-workdir",
        metavar="DIR",
        default=None,
        help="with --async-smoke: scratch directory for the scenarios' "
        "checkpoints (default: a temp dir)",
    )
    parser.add_argument(
        "--async-scenarios",
        metavar="NAMES",
        default=None,
        help="with --async-smoke: comma-separated subset of scenarios "
        "to run (default: all)",
    )
    parser.add_argument(
        "--health-dump-dir",
        metavar="DIR",
        default=None,
        help="with --health-smoke: directory for the flight-dump "
        "artifact (default: a temp dir; CI passes an upload path)",
    )
    parser.add_argument(
        "--update-budgets",
        action="store_true",
        help="with --resources / --compile-audit / --perf-audit / "
        "--hlo-audit: "
        "regenerate that engine's section of the budget lockfile from "
        "the current run instead of checking against it (review the "
        "diff!); each engine's relock preserves the others' entries",
    )
    parser.add_argument(
        "--budgets",
        metavar="PATH",
        default=None,
        help="budget contract file for --resources "
        "(default: trlx_tpu/analysis/budgets.json)",
    )
    parser.add_argument(
        "--sanitize",
        metavar="TRAINER",
        default=None,
        help="instead of the rule engines: replay TRAINER's train step "
        "eqn-by-eqn on concrete values and report the first non-finite "
        "equation (ppo|ilql|grpo|seq2seq)",
    )
    parser.add_argument(
        "--mesh",
        default=None,
        help="mesh axis sizes for --sanitize / --resources, e.g. "
        "dp=2,fsdp=2,tp=2 (default: the audit mesh)",
    )
    parser.add_argument(
        "--plant-nan",
        action="store_true",
        help="poison one param leaf with NaN before --sanitize — "
        "self-check that the replay detects and attributes it",
    )
    parser.add_argument(
        "--streamed",
        action="store_true",
        help="with --sanitize: replay the overlapped phase's streamed "
        "epoch-1 step — the minibatch is gathered from the streaming "
        "buffer after chunked dynamic_update_slice landings, the way "
        "the streamed dispatcher produces it",
    )
    parser.add_argument(
        "--engine-step",
        action="store_true",
        help="with --sanitize ppo: replay the continuous-batching "
        "engine's decode_step, then the speculative verify_step "
        "(docs/inference.md) on a concretely prefilled slot pool "
        "instead of the train step",
    )
    parser.add_argument(
        "--paths",
        nargs="*",
        default=None,
        help="files/dirs for the AST lint (default: the trlx_tpu package)",
    )
    parser.add_argument(
        "--trainers",
        default=None,
        help="comma-separated trainer kinds for the jaxpr audit "
        "(default: ppo,ilql,grpo,seq2seq)",
    )
    parser.add_argument(
        "--strict",
        action="store_true",
        help="fail on any finding, warnings included",
    )
    parser.add_argument(
        "--json", action="store_true", help="machine-readable output"
    )
    parser.add_argument(
        "--list-rules", action="store_true", help="print the rule catalogue"
    )
    args = parser.parse_args(argv)

    if args.list_rules:
        from trlx_tpu.analysis.registry import all_rules

        for rule in all_rules():
            print(f"{rule.id:18} [{rule.engine}/{rule.severity}] "
                  f"{rule.description}")
        return 0

    mesh = None
    if args.mesh:
        mesh = {
            k.strip(): int(v)
            for k, v in (kv.split("=") for kv in args.mesh.split(","))
        }
    trainers = (
        [t.strip() for t in args.trainers.split(",") if t.strip()]
        if args.trainers
        else None
    )

    if args.hlo_audit or args.plant_hazard:
        _force_cpu_platform()
        from trlx_tpu.analysis.hlo_audit import audit_hlo, format_hlo_text

        report, result = audit_hlo(
            kinds=trainers,
            mesh=mesh,
            budgets_path=args.budgets,
            update=args.update_budgets,
            matrix=not args.no_mesh_matrix,
            plant=args.plant_hazard,
        )
        if args.json:
            report.resources = result.to_rows()
            print(report.to_json())
        else:
            print(format_hlo_text(result))
            if args.update_budgets and not report.findings:
                print(
                    "hlo budgets written — review and commit the "
                    "lockfile diff"
                )
            if report.findings:
                print(report.format_text())
        if args.update_budgets:
            # findings here mean the update was REFUSED (rule findings
            # on the tree, or a cross-mesh partial relock) and nothing
            # was written
            return 1 if report.findings else 0
        return report.exit_code(strict=args.strict)

    if args.resume_audit or args.plant_gap:
        _force_cpu_platform()
        from trlx_tpu.analysis.state_audit import (
            audit_resume_state,
            format_state_text,
        )

        report, result = audit_resume_state(
            kinds=trainers,
            mesh=mesh,
            budgets_path=args.budgets,
            update=args.update_budgets,
            plant_gap=args.plant_gap,
        )
        if args.json:
            print(report.to_json())
        else:
            print(format_state_text(result))
            if args.update_budgets and not report.findings:
                print(
                    "state manifest written — review and commit the "
                    "lockfile diff"
                )
            if report.findings:
                print(report.format_text())
        if args.update_budgets:
            # findings here mean the update was REFUSED (gap/divergence
            # findings on the tree, or a cross-mesh partial relock) and
            # nothing trustworthy was written
            return 1 if report.findings else 0
        return report.exit_code(strict=args.strict)

    if args.races or args.plant_race:
        _force_cpu_platform()
        from trlx_tpu.analysis.concurrency import (
            audit_races,
            format_races_text,
        )

        scenarios = (
            [s.strip() for s in args.race_scenarios.split(",") if s.strip()]
            if args.race_scenarios
            else None
        )
        report, result = audit_races(
            paths=args.paths,
            schedules=args.schedules,
            plant=args.plant_race,
            seed=args.race_seed,
            scenarios=scenarios,
        )
        if args.json:
            print(report.to_json())
        else:
            print(format_races_text(result))
            if report.findings:
                print(report.format_text())
        return report.exit_code(strict=args.strict)

    if args.lockstep:
        _force_cpu_platform()
        from trlx_tpu.analysis.lockstep import (
            audit_lockstep,
            format_lockstep_text,
        )

        report, results = audit_lockstep(
            kinds=trainers,
            hosts=args.hosts,
            mesh=mesh,
            budgets_path=args.budgets,
            update=args.update_budgets,
            plant=args.plant_divergence,
        )
        if args.json:
            report.resources = [r.to_row() for r in results]
            print(report.to_json())
        else:
            print(format_lockstep_text(results))
            if args.update_budgets and not report.findings:
                print(
                    "lockstep budgets written — review and commit the "
                    "lockfile diff"
                )
            if report.findings:
                print(report.format_text())
        if args.update_budgets:
            # findings here mean the update was REFUSED (diverging
            # schedule, or cross-mesh/hosts partial relock) and nothing
            # was written
            return 1 if report.findings else 0
        return report.exit_code(strict=args.strict)

    if args.compile_audit:
        _force_cpu_platform()
        from trlx_tpu.analysis.compile_audit import (
            audit_compiles,
            format_compile_text,
        )

        report, result = audit_compiles(
            kinds=trainers,
            mesh=mesh,
            budgets_path=args.budgets,
            update=args.update_budgets,
        )
        if args.json:
            report.resources = result.to_rows()
            print(report.to_json())
        else:
            print(format_compile_text(result))
            if args.update_budgets and not report.findings:
                print(
                    "compile budgets written — review and commit the "
                    "lockfile diff"
                )
            if report.findings:
                print(report.format_text())
        if args.update_budgets:
            # findings here mean the update was REFUSED (cross-mesh
            # partial relock) and nothing was written
            return 1 if report.findings else 0
        return report.exit_code(strict=args.strict)

    if args.chaos_smoke:
        _force_cpu_platform()
        from trlx_tpu.analysis.chaos_smoke import (
            format_smoke_text,
            run_chaos_smoke,
        )

        only = (
            [s.strip() for s in args.chaos_scenarios.split(",") if s.strip()]
            if args.chaos_scenarios
            else None
        )
        summary = run_chaos_smoke(workdir=args.chaos_workdir, only=only)
        return _emit_smoke(summary, format_smoke_text, args.json)

    if args.async_smoke:
        _force_cpu_platform()
        from trlx_tpu.analysis.async_smoke import (
            format_smoke_text,
            run_async_smoke,
        )

        only = (
            [s.strip() for s in args.async_scenarios.split(",") if s.strip()]
            if args.async_scenarios
            else None
        )
        summary = run_async_smoke(workdir=args.async_workdir, only=only)
        return _emit_smoke(summary, format_smoke_text, args.json)

    if args.health_smoke:
        _force_cpu_platform()
        from trlx_tpu.analysis.health_smoke import (
            format_smoke_text,
            run_health_smoke,
        )

        summary = run_health_smoke(dump_dir=args.health_dump_dir)
        return _emit_smoke(summary, format_smoke_text, args.json)

    if args.perf_audit:
        _force_cpu_platform()
        from trlx_tpu.analysis.perf_audit import audit_perf, format_perf_text

        report, rows = audit_perf(
            budgets_path=args.budgets,
            update=args.update_budgets,
            phases=args.perf_phases,
            slowdown_ms=args.plant_slowdown,
            span_log=args.span_log,
        )
        if args.json:
            print(report.to_json())
        else:
            print(format_perf_text(rows))
            if args.update_budgets and not report.findings:
                print(
                    "perf budgets written — review and commit the "
                    "lockfile diff"
                )
            if report.findings:
                print(report.format_text())
        if args.update_budgets:
            return 1 if report.findings else 0
        return report.exit_code(strict=args.strict)

    if args.resources:
        _force_cpu_platform()
        from trlx_tpu.analysis.resource_audit import (
            audit_resources,
            default_budgets_path,
            format_resources_text,
        )

        report, resources = audit_resources(
            kinds=trainers,
            mesh=mesh,
            budgets_path=args.budgets,
            update=args.update_budgets,
        )
        if args.json:
            print(report.to_json())
        else:
            print(format_resources_text(resources))
            if args.update_budgets and not report.findings:
                print(
                    f"budgets written to "
                    f"{args.budgets or default_budgets_path()} — review "
                    "and commit the diff"
                )
            if report.findings:
                print(report.format_text())
        if args.update_budgets:
            # findings here mean the update was REFUSED (mesh-mixing
            # partial relock) and nothing was written
            return 1 if report.findings else 0
        return report.exit_code(strict=args.strict)

    if args.sanitize:
        _force_cpu_platform()
        from trlx_tpu.analysis.sanitizer import (
            sanitize_engine_step,
            sanitize_trainer,
        )

        if args.engine_step:
            result = sanitize_engine_step(
                args.sanitize, mesh=mesh, plant=args.plant_nan
            )
        else:
            result = sanitize_trainer(
                args.sanitize, mesh=mesh, plant=args.plant_nan,
                streamed=args.streamed,
            )
        report = result.to_report()
        print(report.to_json() if args.json else result.format_text())
        return report.exit_code(strict=args.strict)

    if args.engine in (
        "all", "jaxpr", "nanflow", "collective", "donation", "prng",
    ):
        _force_cpu_platform()

    from trlx_tpu.analysis import run

    report = run(engine=args.engine, paths=args.paths, trainers=trainers)
    print(report.to_json() if args.json else report.format_text())
    return report.exit_code(strict=args.strict)


if __name__ == "__main__":
    sys.exit(main())
