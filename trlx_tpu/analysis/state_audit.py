"""Checkpoint/resume state-coverage auditor (engine 15).

Proves the kill/resume parity contract (docs/resilience.md) over the
WHOLE mutable host-state surface, not just the params pytree the PR-9
canaries pin. Two halves, same shape as engines 11/13/14:

**Static half** — reuse engine 14's attribute-level class collector to
inventory every attribute written outside ``__init__`` on the classes
reachable from a trainer (trainer, orchestrator, rollout buffer,
continuous engine, QoS scheduler, prefix pool, drafters, health
monitor), then require each one to be exactly one of:

- **carried** — referenced inside a checkpoint-carry method
  (``state_dict``/``save``/``host_state_dict``/…) of the class or a
  base class, so it rides the checkpoint;
- **carried-via** — serialized field-by-field by ANOTHER class's carry
  method (declared in :data:`CARRIED_VIA`, e.g. ``_SeriesState`` inside
  ``HealthMonitor.state_dict``);
- **phase-reset** — reassigned wholesale by the class's declared
  phase-boundary reset method (:data:`PHASE_RESET_METHODS`), so it is
  dead at every checkpointable boundary;
- **reconstructed** — written only by ``_build_*``/``_setup_*``/
  ``_rebuild_*`` derivation methods that recompute it from config on
  restore;
- **ephemeral** — allowlisted in :data:`EPHEMERAL_CONTRACTS` with a
  written justification (telemetry counters, caches whose loss is
  parity-inert).

Anything else is a ``resume-state-gap`` error at its first write site.
A contract entry naming a dead attribute is ``stale-state-contract``.

**Dynamic half** — a generalized kill/resume differ: run each trainer's
canonical harness pass to a phase boundary, ``save()``, rebuild the
trainer from scratch, ``load()``, then run BOTH the resumed trainer and
the uninterrupted twin one more identically-seeded pass and deep-compare
the full live attribute trees (arrays by content hash). Any diverging
path is a ``resume-divergence`` error naming the owning attribute path
and both values. The same run fingerprints the checkpoint schema (state
pytree leaf shapes/dtypes + host-metadata key paths) and locks it into
the ``state_manifest`` section of ``analysis/budgets.json``
(``ckpt-schema-drift``; relock via ``--update-budgets`` with the usual
foreign-section-preserving merge).

``--plant-gap`` is the self-test: a planted uncheckpointed counter
threaded into the sampling schedule must be named by BOTH halves.
"""

from __future__ import annotations

import ast
import hashlib
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from trlx_tpu.analysis.ast_lint import collect_py_files
from trlx_tpu.analysis.concurrency import _ClassInfo, _collect_class
from trlx_tpu.analysis.findings import (
    Finding,
    Report,
    filter_suppressed,
)
from trlx_tpu.analysis.registry import ENGINE_STATE, get_rule

__all__ = [
    "audit_resume_state",
    "classify_surface",
    "lint_resume_state",
    "run_resume_differ",
    "format_state_text",
    "RESUME_SURFACE",
    "EPHEMERAL_CONTRACTS",
]

_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPO_ROOT = os.path.dirname(_PKG_ROOT)

#: the modules that together hold every object reachable from a live
#: trainer whose mutable host state the resume contract covers
RESUME_SURFACE = [
    "trlx_tpu/trainer/__init__.py",
    "trlx_tpu/trainer/ppo_trainer.py",
    "trlx_tpu/trainer/ilql_trainer.py",
    "trlx_tpu/trainer/grpo_trainer.py",
    "trlx_tpu/trainer/seq2seq_ppo_trainer.py",
    "trlx_tpu/orchestrator/__init__.py",
    "trlx_tpu/orchestrator/ppo_orchestrator.py",
    "trlx_tpu/orchestrator/offline_orchestrator.py",
    "trlx_tpu/inference/engine.py",
    "trlx_tpu/pipeline/ppo_buffer.py",
    "trlx_tpu/serving/scheduler.py",
    "trlx_tpu/serving/prefix_cache.py",
    "trlx_tpu/serving/spec_drafter.py",
    "trlx_tpu/telemetry/health.py",
]

#: methods whose body participates in the checkpoint-carry contract: a
#: ``self.X`` reference inside any of them (on the class or a base)
#: classifies X as carried
CARRY_METHODS = frozenset({
    "state_dict",
    "load_state_dict",
    "host_state_dict",
    "load_host_state_dict",
    "_save_metadata",
    "save",
    "load",
})

_INIT_METHODS = frozenset({"__init__", "__post_init__"})

#: method-name prefixes that mark deterministic reconstruction: these
#: derive their writes from config/static model structure, and restore
#: reruns them (jitted programs, shardings, parsed configs)
_REBUILD_PREFIXES = ("_build", "_setup", "_rebuild")

#: per-class phase-boundary reset methods: state written there is
#: reinitialized from the method's arguments at every phase start, so it
#: is dead at the inter-phase boundaries where checkpoints happen
PHASE_RESET_METHODS: Dict[str, Set[str]] = {
    # start_phase() reassigns the whole slot/queue/draft state from the
    # pushed params + phase key (docs/inference.md "phase lifecycle")
    "ContinuousBatchingEngine": {"start_phase"},
    # begin_stream() re-creates the landing store for the next phase;
    # clear_history() is the on-policy refresh that empties the staged
    # chunks before each re-collect — experience is re-gathered from
    # the carried rng/prompt-stream position, never restored (the PR-9
    # parity canary pins exactly this flow)
    "PPORolloutBuffer": {"begin_stream", "clear_history"},
    # reset() drops row histories at each phase boundary (EWMAs are
    # deliberately NOT written there — they must be carried instead)
    "NGramDrafter": {"reset"},
    "TrieDrafter": {"reset"},
    # reset_rollout_phase() re-arms the per-phase RNG cursor pair
    "PPOTrainer": {"reset_rollout_phase"},
}

#: attrs serialized field-by-field by another class's carry method —
#: the owning class has no state_dict of its own, but the state rides
#: the checkpoint anyway
CARRIED_VIA: Dict[Tuple[str, str], str] = {
    ("_SeriesState", attr): (
        "HealthMonitor.state_dict serializes every series "
        "field-by-field ({count, mean, var, window, flat_run})"
    )
    for attr in ("count", "mean", "var", "window", "flat_run")
}
CARRIED_VIA[("TokenBucket", "level")] = (
    "QoSScheduler.state_dict carries every bucket's level"
)

#: the ephemeral allowlist: (class, attr) -> written justification.
#: Every entry asserts that LOSING the attribute across kill/resume
#: cannot change any token, update, or schedule decision.
EPHEMERAL_CONTRACTS: Dict[Tuple[str, str], str] = {
    # ---- BaseRLTrainer ------------------------------------------------ #
    ("BaseRLTrainer", "_last_samples"): (
        "eval-time decoded sample cache for the logger; re-filled by "
        "the next evaluate() and never read by the train schedule"
    ),
    ("BaseRLTrainer", "eval_pipeline"): (
        "wiring performed by the driver (add_eval_pipeline) before "
        "learn(); a resumed run re-wires it the same way it was first "
        "wired — it is an input, not evolving state"
    ),
    ("BaseRLTrainer", "_phase_log"): (
        "run_dir --watch JSONL writer handle (run_ledger.py); an "
        "append-only sink whose rows are already on disk — reopened "
        "in append mode on rebuild"
    ),
    ("BaseRLTrainer", "_phase_timing"): (
        "host-stall's timing series and the mark taken at a phase's "
        "start (telemetry/health.py): wall-clock levels of this host, "
        "warmed up again in three phases; never read by the schedule"
    ),
    ("HealthMonitor", "_timing"): (
        "host-stall's running levels of phase and iteration walls: "
        "wall-clock of the host the run is on, kept out of state_dict "
        "on purpose (a resumed run is on another host and warms up "
        "again in three observations); no token or update reads it"
    ),
    # ---- PPOTrainer --------------------------------------------------- #
    ("PPOTrainer", "_behavior_params"): (
        "phase-scoped behavior-policy snapshot: begin_streamed_phase "
        "re-captures it from the (checkpoint-carried) params at every "
        "phase start; dead at phase boundaries"
    ),
    ("PPOTrainer", "_stream"): (
        "phase-scoped streaming handle created by begin_streamed_phase "
        "and closed by finish_streamed_phase; the preemption contract "
        "drains it before any checkpoint"
    ),
    ("PPOTrainer", "_health_phase"): (
        "phase-scoped health-row accumulator, re-armed by "
        "begin_streamed_phase; observations it fed the monitor are "
        "carried inside health_monitor's state_dict"
    ),
    ("PPOTrainer", "_last_stream_seed"): (
        "debug echo of the last begin_streamed_phase seed; never read "
        "by the schedule"
    ),
    ("PPOTrainer", "_last_overlap_stats"): (
        "telemetry: overlap timing of the finished phase, logger-only"
    ),
    ("PPOTrainer", "_last_phase_mean_kl"): (
        "telemetry echo of the phase KL already carried as mean_kl; "
        "logger/monitor display only"
    ),
    ("PPOTrainer", "_phase_index"): (
        "display counter for flight records; learn() renumbers from "
        "the carried state.step on resume, and no seed or schedule "
        "derives from it"
    ),
    ("PPOTrainer", "_epoch0"): (
        "derived at learn() entry from the carried state.step "
        "(resume fast-forward); recomputed identically on restore"
    ),
    ("PPOTrainer", "_final_stats"): (
        "logger summary of the finished run; never read by training"
    ),
    ("PPOTrainer", "_phase_profiler"): (
        "wall-clock phase profiler (host timing only — timings are "
        "not reproducible across runs by definition)"
    ),
    ("PPOTrainer", "logger"): (
        "run-scoped logger handle re-opened by learn(); sink, not state"
    ),
    ("PPOTrainer", "_rollout_params_cache"): (
        "memoized rollout-dtype cast keyed by the CARRIED "
        "state.params' identity; a cold cache recomputes the identical "
        "cast on first use after restore"
    ),
    ("PPOTrainer", "_bound_min_prompts"): (
        "prompt-budget binding performed by the driver before learn() "
        "(bind_prompt_budget); re-performed identically on rebuild"
    ),
    ("PPOTrainer", "gen_config"): (
        "rebound by bind_prompt_budget from config + tokenizer "
        "defaults; config-derived, not evolving"
    ),
    # ---- ILQLTrainer -------------------------------------------------- #
    ("ILQLTrainer", "_rollout_bundle_cache"): (
        "memoized rollout-dtype cast keyed by the CARRIED state "
        "params/target identity; recomputed identically on first use "
        "after restore"
    ),
    ("ILQLTrainer", "_chunk_index"): (
        "display counter for flight records; renumbered from the "
        "carried state.step on resume, feeds no seed"
    ),
    ("ILQLTrainer", "_final_stats"): (
        "logger summary of the finished run; never read by training"
    ),
    ("ILQLTrainer", "logger"): (
        "run-scoped logger handle re-opened by learn(); sink, not state"
    ),
    # ---- orchestrators ------------------------------------------------ #
    ("PPOOrchestrator", "_engine_error"): (
        "transient engine-failure capture consumed (re-raised) by the "
        "same collect phase that set it; never outlives a phase"
    ),
    ("PPOOrchestrator", "_rollout_writer"): (
        "background JSONL writer handle; close() is lifecycle, the "
        "rows already written are on disk"
    ),
    ("OfflineOrchestrator", "trainer"): (
        "back-reference wired once by the driver at construction time"
    ),
    # ---- continuous engine (non-phase-reset attrs) -------------------- #
    ("ContinuousBatchingEngine", "_chunk_flops"): (
        "memoized FLOP cost per chunk shape (pure function of config); "
        "refilled on first use after rebuild"
    ),
    ("ContinuousBatchingEngine", "_latent_pinned_share"): (
        "gauge value read off the fresh state's arrays by init_state (how "
        "the latent pools lie on the device: a function of the config and "
        "the backend); read again by the next init_state, feeds no token"
    ),
    ("ContinuousBatchingEngine", "_block_bitcast_share"): (
        "gauge value read off the held cache's shapes by _make_state (a "
        "function of the config); read again by the next, feeds no token"
    ),
    # ---- QoS scheduler ------------------------------------------------ #
    ("QoSScheduler", "_queues"): (
        "in-flight request queues: the preemption contract drains the "
        "serving tier at phase boundaries, so queues are empty at "
        "every checkpointable point (clients re-submit after a kill)"
    ),
    ("QoSScheduler", "tenants"): (
        "default-tenant auto-registration cache; an unknown tenant "
        "re-registers with identical defaults on first touch"
    ),
    # ---- prefix pool -------------------------------------------------- #
    ("PrefixBlockPool", "_free"): (
        "device KV block freelist: the KV pool itself is not "
        "checkpointed, so block ids cannot meaningfully survive a "
        "restart; a cold pool only costs recomputed prefixes "
        "(performance), never changes a sampled token — sharing is "
        "parity-exact by construction (docs/inference.md)"
    ),
    ("PrefixBlockPool", "_nodes"): (
        "radix-trie node index over the uncheckpointed KV pool; see "
        "_free — cold-start cost only"
    ),
    ("PrefixBlockPool", "_root"): (
        "radix-trie root over the uncheckpointed KV pool; see _free"
    ),
    ("PrefixBlockPool", "_tick"): (
        "LRU recency clock for eviction order inside one process "
        "lifetime; eviction changes which prefixes are RECOMPUTED, "
        "never their values — parity-inert by the verify-exact "
        "sharing contract"
    ),
    ("PrefixBlockPool", "hits"): "telemetry counter (stats() row only)",
    ("PrefixBlockPool", "misses"): "telemetry counter (stats() row only)",
    ("PrefixBlockPool", "evictions"): (
        "telemetry counter (stats() row only)"
    ),
    # ---- drafters (telemetry only — EWMAs/probes are carried) --------- #
    ("NGramDrafter", "drafts"): "telemetry counter (stats() row only)",
    ("NGramDrafter", "draft_hits"): (
        "telemetry counter (stats() row only)"
    ),
    ("NGramDrafter", "degraded_draws"): (
        "telemetry counter (stats() row only)"
    ),
    ("TrieDrafter", "drafts"): "telemetry counter (stats() row only)",
    ("TrieDrafter", "draft_hits"): (
        "telemetry counter (stats() row only)"
    ),
    ("TrieDrafter", "trie_hits"): "telemetry counter (stats() row only)",
}

# attrs the DIFFER skips on top of the ephemeral contracts: identity /
# handle objects that can never compare equal across two processes yet
# carry no schedule state (the static half still classifies them)
_DIFFER_SKIP_ATTRS: Set[str] = {
    "logger",
    "flight_recorder",
    "_phase_log",
    "_phase_profiler",
    "_stream",
    "pool",  # TrieDrafter's pool back-reference (pool itself visited)
    # per-request wall-clock stamps for the latency histograms: real
    # time can never compare across two processes (statically they are
    # phase-reset — start_phase reassigns them every phase)
    "_req_times",
}


# ------------------------------ static half ------------------------------ #

@dataclass
class AttrClassification:
    """Where one mutable attribute landed in the resume taxonomy."""

    cls: str
    attr: str
    file: str
    line: int
    category: str  # carried|carried-via|phase-reset|reconstructed|ephemeral
    detail: str = ""


@dataclass
class _SurfaceClass:
    info: _ClassInfo
    bases: List[str]
    #: attrs referenced as ``self.X`` inside carry-method bodies
    carried_refs: Set[str]
    #: every attr the class assigns anywhere (incl. __init__) — the
    #: liveness set for stale-contract checks
    all_attrs: Set[str]


def _self_attr_refs(fn: ast.AST) -> Set[str]:
    """Every ``self.X`` referenced (read or written) inside ``fn``."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"
        ):
            out.add(node.attr)
    return out


def _collect_surface(
    paths: Sequence[str],
) -> Dict[str, _SurfaceClass]:
    """Parse ``paths`` into the per-class write/carry maps."""
    classes: Dict[str, _SurfaceClass] = {}
    for path in collect_py_files(paths):
        try:
            with open(path, encoding="utf-8") as fh:
                tree = ast.parse(fh.read())
        except (OSError, SyntaxError):
            continue
        rel = os.path.relpath(os.path.abspath(path), _REPO_ROOT)
        if not rel.startswith(".."):
            report_path = rel
        else:
            report_path = path
        for node in tree.body:
            if not isinstance(node, ast.ClassDef):
                continue
            info = _collect_class(node, report_path)
            bases = []
            for b in node.bases:
                try:
                    bases.append(ast.unparse(b).split("[")[0])
                except Exception:  # pragma: no cover - malformed base
                    continue
            carried: Set[str] = set()
            for name, fn in info.methods.items():
                if name in CARRY_METHODS:
                    carried |= _self_attr_refs(fn)
            all_attrs = {w.attr for w in info.writes}
            classes[node.name] = _SurfaceClass(
                info=info,
                bases=bases,
                carried_refs=carried,
                all_attrs=all_attrs,
            )
    return classes


def _base_chain(
    name: str, classes: Dict[str, _SurfaceClass]
) -> List[str]:
    """``name`` plus every (transitively) resolvable base class, MRO-ish
    order, restricted to classes found on the surface."""
    out: List[str] = []
    stack = [name]
    while stack:
        cur = stack.pop(0)
        if cur in out or cur not in classes:
            continue
        out.append(cur)
        stack.extend(classes[cur].bases)
    return out


def classify_surface(
    paths: Optional[Sequence[str]] = None,
    extra_contracts: Optional[Dict[Tuple[str, str], str]] = None,
) -> Tuple[List[AttrClassification], List[Finding]]:
    """The static half: classify every post-init mutated attribute on
    the surface; unclassifiable attrs become ``resume-state-gap``
    findings, contract entries naming dead attrs become
    ``stale-state-contract``."""
    if paths is None:
        paths = [os.path.join(_REPO_ROOT, p) for p in RESUME_SURFACE]
    contracts = dict(EPHEMERAL_CONTRACTS)
    contracts.update(extra_contracts or {})
    gap_rule = get_rule("resume-state-gap")
    stale_rule = get_rule("stale-state-contract")
    classes = _collect_surface(paths)
    classified: List[AttrClassification] = []
    findings: List[Finding] = []

    for name in sorted(classes):
        sc = classes[name]
        chain = _base_chain(name, classes)
        carried: Set[str] = set()
        phase_reset_methods: Set[str] = set()
        for cname in chain:
            carried |= classes[cname].carried_refs
            phase_reset_methods |= PHASE_RESET_METHODS.get(cname, set())
        # attr -> ordered write sites outside init/carry methods
        post_writes: Dict[str, List] = {}
        for w in sc.info.writes:
            if w.method in _INIT_METHODS or w.method in CARRY_METHODS:
                continue
            post_writes.setdefault(w.attr, []).append(w)
        for attr in sorted(post_writes):
            writes = post_writes[attr]
            first = min(writes, key=lambda w: w.line)
            site = AttrClassification(
                cls=name,
                attr=attr,
                file=sc.info.file,
                line=first.line,
                category="",
            )
            contract_key = next(
                (
                    (cname, attr)
                    for cname in chain
                    if (cname, attr) in contracts
                ),
                None,
            )
            carried_via = next(
                (
                    (cname, attr)
                    for cname in chain
                    if (cname, attr) in CARRIED_VIA
                ),
                None,
            )
            if attr in carried:
                site.category = "carried"
            elif carried_via is not None:
                site.category = "carried-via"
                site.detail = CARRIED_VIA[carried_via]
            elif any(w.method in phase_reset_methods for w in writes):
                site.category = "phase-reset"
                site.detail = ",".join(
                    sorted(phase_reset_methods & {w.method for w in writes})
                )
            elif all(
                w.method.startswith(_REBUILD_PREFIXES) for w in writes
            ):
                site.category = "reconstructed"
                site.detail = ",".join(sorted({w.method for w in writes}))
            elif contract_key is not None:
                site.category = "ephemeral"
                site.detail = contracts[contract_key]
            else:
                methods = sorted({w.method for w in writes})
                findings.append(
                    Finding(
                        rule=gap_rule.id,
                        message=(
                            f"`{name}.{attr}` is mutated inside the "
                            f"phase loop (in {', '.join(methods)}) but "
                            "is neither checkpoint-carried, "
                            "reconstructed from config, nor "
                            "allowlisted ephemeral — a resumed run "
                            "silently resets it. Carry it via "
                            "state_dict()/host_state_dict(), or add "
                            "an EPHEMERAL_CONTRACTS entry in "
                            "trlx_tpu/analysis/state_audit.py with a "
                            "written justification that losing it "
                            "cannot change any token or update"
                        ),
                        severity=gap_rule.severity,
                        file=sc.info.file,
                        line=first.line,
                        subject=f"{name}.{attr}",
                        engine=ENGINE_STATE,
                    )
                )
                continue
            classified.append(site)

    # stale contracts: entries naming classes/attrs that no longer exist
    shipped = {
        key
        for key in contracts
        if key in EPHEMERAL_CONTRACTS or (extra_contracts or {}).get(key)
    }
    for (cname, attr) in sorted(shipped):
        sc = classes.get(cname)
        if sc is None:
            # the class lives outside the scanned paths (tests scan tmp
            # trees): only flag when the default surface was scanned
            if paths is not None and any(
                os.path.abspath(p).startswith(_PKG_ROOT)
                for p in paths
            ):
                findings.append(
                    Finding(
                        rule=stale_rule.id,
                        message=(
                            f"ephemeral allowlist names class `{cname}` "
                            "which no longer exists on the resume "
                            "surface — prune or rename the entry"
                        ),
                        severity=stale_rule.severity,
                        subject=f"{cname}.{attr}",
                        engine=ENGINE_STATE,
                    )
                )
            continue
        if attr not in sc.all_attrs:
            findings.append(
                Finding(
                    rule=stale_rule.id,
                    message=(
                        f"ephemeral allowlist entry `{cname}.{attr}` "
                        "names an attribute the class never writes — "
                        "the justification covers nothing; prune or "
                        "rename the entry"
                    ),
                    severity=stale_rule.severity,
                    file=sc.info.file,
                    line=sc.info.line,
                    subject=f"{cname}.{attr}",
                    engine=ENGINE_STATE,
                )
            )
    return classified, findings


def lint_resume_state(
    paths: Optional[Sequence[str]] = None,
    extra_contracts: Optional[Dict[Tuple[str, str], str]] = None,
) -> List[Finding]:
    """Findings-only wrapper over :func:`classify_surface` (test entry)."""
    _, findings = classify_surface(paths, extra_contracts)
    return findings


# ------------------------------ dynamic half ----------------------------- #

_OPAQUE_MODULE_PREFIXES = (
    "jaxlib",
    "orbax",
    "threading",
    "logging",
    "concurrent",
)


def _value_digest(value: Any) -> Optional[str]:
    """A comparable scalar rendering of ``value``, or None when the
    value is opaque (callables, meshes, shardings, jitted programs) and
    must not participate in the diff."""
    import numpy as np

    if value is None or isinstance(value, (bool, int, str, bytes)):
        return repr(value)
    if isinstance(value, float):
        # repr round-trips doubles exactly — bitwise parity, readable
        return repr(value)
    if hasattr(value, "shape") and hasattr(value, "dtype"):
        # arrays first: jax.Array's type lives in jaxlib, which the
        # opaque filter below would otherwise swallow
        try:
            import jax

            host = np.asarray(jax.device_get(value))
        except Exception:
            return None
        digest = hashlib.sha1(host.tobytes()).hexdigest()[:16]
        return f"{host.dtype}{list(host.shape)}:{digest}"
    if callable(value):
        return None
    mod = type(value).__module__ or ""
    if mod.startswith(_OPAQUE_MODULE_PREFIXES):
        return None
    return None


def _snapshot_into(
    value: Any,
    path: str,
    out: Dict[str, str],
    seen: Set[int],
    depth: int = 0,
) -> None:
    """Flatten the live attribute tree under ``value`` into
    ``out[path] = digest`` rows, recursing into containers and
    trlx_tpu-owned objects only."""
    if depth > 12:
        return
    digest = _value_digest(value)
    if digest is not None:
        out[path] = digest
        return
    if id(value) in seen:
        return
    seen.add(id(value))
    if isinstance(value, dict):
        for k in sorted(value, key=repr):
            _snapshot_into(
                value[k], f"{path}[{k!r}]", out, seen, depth + 1
            )
        return
    if isinstance(value, (list, tuple)) or type(value).__name__ == "deque":
        for i, item in enumerate(value):
            _snapshot_into(item, f"{path}[{i}]", out, seen, depth + 1)
        return
    if isinstance(value, (set, frozenset)):
        out[path] = repr(sorted(repr(v) for v in value))
        return
    mod = type(value).__module__ or ""
    if mod.startswith("trlx_tpu") or type(value).__name__ in (
        "_SeriesState",
    ):
        cls = type(value).__name__
        attrs: Dict[str, Any] = {}
        if hasattr(value, "__dict__"):
            attrs.update(vars(value))
        for slot in getattr(type(value), "__slots__", ()) or ():
            if hasattr(value, slot):
                attrs[slot] = getattr(value, slot)
        for attr in sorted(attrs):
            if attr in _DIFFER_SKIP_ATTRS:
                continue
            if _is_contracted(cls, attr):
                continue
            _snapshot_into(
                attrs[attr], f"{path}.{attr}", out, seen, depth + 1
            )
    # anything else (foreign objects, modules, locks) is opaque: skip


def _is_contracted(cls: str, attr: str) -> bool:
    """True when (cls-or-base, attr) carries an ephemeral contract —
    resolved by name only (the differ has no AST at hand), so every
    class in the contract table matches itself and its subclasses via
    the live MRO."""
    probe = _CONTRACT_CLASS_INDEX.get(attr)
    if not probe:
        return False
    return cls in probe or any(
        base in probe for base in _LIVE_BASES.get(cls, ())
    )


#: attr -> {classes allowlisting it} (derived once from the contracts)
_CONTRACT_CLASS_INDEX: Dict[str, Set[str]] = {}
for (_cls, _attr), _ in EPHEMERAL_CONTRACTS.items():
    _CONTRACT_CLASS_INDEX.setdefault(_attr, set()).add(_cls)

#: live base-name map filled lazily by the differ (subclass -> bases)
_LIVE_BASES: Dict[str, Tuple[str, ...]] = {}


def _register_live_bases(obj: Any) -> None:
    for klass in type(obj).__mro__:
        _LIVE_BASES.setdefault(
            klass.__name__,
            tuple(b.__name__ for b in klass.__mro__[1:]),
        )


def snapshot_host_state(trainer: Any) -> Dict[str, str]:
    """The full flattened live attribute tree of ``trainer`` (and every
    reachable trlx_tpu object), arrays digested by content."""
    _register_live_bases(trainer)
    out: Dict[str, str] = {}
    _snapshot_into(trainer, "trainer", out, set())
    return out


class PlantedScheduleState:
    """The ``--plant-gap`` payload: an uncheckpointed draw counter that
    the planted canonical pass folds into its sampling seed — exactly
    the bug class the auditor exists to catch."""

    def __init__(self) -> None:
        self.draws = 0


def _one_pass(trainer: Any, kind: str, step_seed: int) -> None:
    """One canonical phase at the harness shapes — mirrors the loop the
    compile/lockstep engines drive (rollout -> stepwise update -> fused
    phase -> behavior snapshot -> engine mini-phase) so all engines gate
    the same dispatch order."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trlx_tpu.analysis import harness
    from trlx_tpu.parallel.mesh import batch_sharding

    planted = getattr(trainer, "_planted_schedule", None)
    if planted is not None:
        # the planted gap: an uncheckpointed counter feeding the seed
        planted.draws += 1
        step_seed = step_seed + planted.draws

    batch_sh = getattr(trainer, "_batch_sh", None) or batch_sharding(
        trainer.mesh
    )
    B = trainer.config.train.batch_size
    Q = trainer.query_length
    prompt_ids = jnp.ones((B, Q), jnp.int32)
    prompt_mask = jnp.ones((B, Q), jnp.int32)
    trainer.sample(prompt_ids, prompt_mask)
    mb = harness.concrete_minibatch(trainer, kind, seed=step_seed)
    mb = jax.device_put(mb, batch_sh)
    trainer.state, _ = trainer._train_step_jit(trainer.state, mb)
    if kind == "ilql":
        return
    stacked = jax.tree_util.tree_map(
        lambda a, b: jnp.stack([a, b]),
        harness.concrete_minibatch(trainer, kind, seed=step_seed),
        harness.concrete_minibatch(trainer, kind, seed=step_seed + 17),
    )
    stacked = jax.device_put(stacked, trainer._stacked_batch_sh)
    trainer.state, _ = trainer._train_phase_jit(trainer.state, stacked)
    trainer._behavior_snapshot_jit(trainer.state.params)
    if kind == "ppo":
        engine = trainer.rollout_engine_obj
        rng = np.random.default_rng(step_seed)
        n = engine.harvest_width
        eng_ids = rng.integers(1, 30, (n, Q)).astype(np.int32)
        engine.start_phase(
            trainer.rollout_params(),
            jax.random.fold_in(jax.random.PRNGKey(0), step_seed),
        )
        engine.submit(eng_ids, np.ones((n, Q), np.int32))
        for _group in engine.drive(n):
            pass


@dataclass
class DifferRun:
    """One trainer kind's kill/resume differ outcome."""

    kind: str
    compared_paths: int = 0
    divergences: List[Tuple[str, str, str]] = field(
        default_factory=list
    )  # (path, resumed, twin)
    manifest: Dict[str, Any] = field(default_factory=dict)
    mesh: Dict[str, int] = field(default_factory=dict)


def trainer_manifest(trainer: Any) -> Dict[str, Any]:
    """Checkpoint schema fingerprint: every state-pytree leaf's
    shape/dtype plus the host-metadata key paths."""
    import jax

    leaves: Dict[str, str] = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(trainer.state)
    for keypath, leaf in flat:
        key = jax.tree_util.keystr(keypath)
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            leaves[key] = f"{leaf.dtype}{list(leaf.shape)}"
        else:
            leaves[key] = type(leaf).__name__
    meta_keys: List[str] = []

    def _walk_meta(value: Any, prefix: str) -> None:
        if isinstance(value, dict):
            if not value:
                meta_keys.append(f"{prefix}{{}}")
            for k in sorted(value):
                _walk_meta(value[k], f"{prefix}.{k}" if prefix else str(k))
        else:
            meta_keys.append(prefix)

    _walk_meta(trainer._save_metadata(), "")
    return {"state": leaves, "metadata": sorted(meta_keys)}


def run_resume_differ(
    kind: str,
    mesh: Optional[Dict[str, int]] = None,
    plant_gap: bool = False,
    workdir: Optional[str] = None,
) -> DifferRun:
    """Kill/resume differ for one trainer kind.

    Phase 0 runs on trainer A, which then checkpoints. Trainer B is
    built from scratch (a new process's rebuild) and restores. Both run
    an identically-seeded phase 1; any surviving state A carries that B
    lost shows up as a diverging attribute path.
    """
    import shutil
    import tempfile

    from trlx_tpu.analysis import harness

    own_dir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix=f"resume_audit_{kind}_")
    ckpt_dir = os.path.join(workdir, "ckpt")
    run = DifferRun(kind=kind)
    try:
        overrides = {
            "checkpoint_dir": ckpt_dir,
            "async_checkpoint": False,
        }
        twin = harness.build_trainer(
            kind, mesh, train_overrides=overrides
        )
        run.mesh = {k: int(v) for k, v in twin.mesh.shape.items()}
        if plant_gap:
            twin._planted_schedule = PlantedScheduleState()
        _one_pass(twin, kind, 0)
        twin.save(ckpt_dir)

        resumed = harness.build_trainer(
            kind, mesh, train_overrides=overrides
        )
        if plant_gap:
            resumed._planted_schedule = PlantedScheduleState()
        resumed.load(ckpt_dir)

        _one_pass(twin, kind, 1)
        _one_pass(resumed, kind, 1)

        run.manifest = trainer_manifest(twin)
        snap_twin = snapshot_host_state(twin)
        snap_resumed = snapshot_host_state(resumed)
        run.compared_paths = len(set(snap_twin) | set(snap_resumed))
        for path in sorted(set(snap_twin) | set(snap_resumed)):
            a = snap_twin.get(path, "<absent>")
            b = snap_resumed.get(path, "<absent>")
            if a != b:
                run.divergences.append((path, b, a))
    finally:
        if own_dir:
            shutil.rmtree(workdir, ignore_errors=True)
    return run


def divergence_findings(run: DifferRun) -> List[Finding]:
    rule = get_rule("resume-divergence")
    findings: List[Finding] = []
    for path, resumed, twin in run.divergences:
        findings.append(
            Finding(
                rule=rule.id,
                message=(
                    f"[{run.kind}] `{path}` diverged after "
                    f"checkpoint/rebuild/restore + one phase: resumed="
                    f"{resumed} vs uninterrupted={twin} — the state at "
                    "this path did not survive kill/resume. Carry it "
                    "in the owner's state_dict()/host_state_dict(), "
                    "or (only if losing it provably cannot change a "
                    "token or update) add an EPHEMERAL_CONTRACTS "
                    "entry in trlx_tpu/analysis/state_audit.py"
                ),
                severity=rule.severity,
                subject=f"{run.kind}:{path}",
                engine=ENGINE_STATE,
            )
        )
    return findings


# ------------------------------- manifest -------------------------------- #

def make_state_manifest(
    runs: Sequence[DifferRun], mesh: Dict[str, int]
) -> Dict[str, Any]:
    return {
        "mesh": {k: int(v) for k, v in sorted(mesh.items())},
        "trainers": {
            run.kind: run.manifest
            for run in sorted(runs, key=lambda r: r.kind)
        },
    }


def check_state_manifest(
    runs: Sequence[DifferRun],
    budgets: Dict,
    mesh: Dict[str, int],
    budgets_path: Optional[str] = None,
) -> List[Finding]:
    """Gate the observed checkpoint schema against the committed lock."""
    rule = get_rule("ckpt-schema-drift")
    stale_rule = get_rule("stale-state-contract")
    where = os.path.basename(budgets_path or "budgets.json")
    section = budgets.get("state_manifest")
    if section is None:
        return [
            Finding(
                rule=rule.id,
                message=(
                    f"{where} has no state_manifest section — lock the "
                    "checkpoint schema with --resume-audit "
                    "--update-budgets and commit the diff"
                ),
                severity=rule.severity,
                subject="state_manifest",
                engine=ENGINE_STATE,
            )
        ]
    findings: List[Finding] = []
    locked_mesh = section.get("mesh")
    current_mesh = {k: int(v) for k, v in sorted(mesh.items())}
    if locked_mesh is not None and locked_mesh != current_mesh:
        return [
            Finding(
                rule=rule.id,
                message=(
                    f"state manifest in {where} was locked for mesh "
                    f"{locked_mesh} but the audit ran on {current_mesh} "
                    "— schemas are not comparable; rerun on the locked "
                    "mesh or --update-budgets"
                ),
                severity=rule.severity,
                subject="state_manifest",
                engine=ENGINE_STATE,
            )
        ]
    locked_trainers = section.get("trainers", {})
    for run in runs:
        locked = locked_trainers.get(run.kind)
        if locked is None:
            findings.append(
                Finding(
                    rule=rule.id,
                    message=(
                        f"no committed state manifest for trainer "
                        f"`{run.kind}` — lock it with --resume-audit "
                        "--update-budgets and review the diff"
                    ),
                    severity=rule.severity,
                    subject=f"state_manifest:{run.kind}",
                    engine=ENGINE_STATE,
                )
            )
            continue
        locked_state = locked.get("state", {})
        current_state = run.manifest.get("state", {})
        for key in sorted(set(locked_state) - set(current_state)):
            findings.append(
                Finding(
                    rule=rule.id,
                    message=(
                        f"[{run.kind}] checkpoint leaf `{key}` vanished "
                        f"from the save pytree (locked "
                        f"{locked_state[key]}) — existing checkpoints "
                        "would restore without it; if the removal is "
                        "intended, relock with --update-budgets and "
                        "explain the diff"
                    ),
                    severity=rule.severity,
                    subject=f"{run.kind}:{key}",
                    engine=ENGINE_STATE,
                )
            )
        for key in sorted(set(current_state) - set(locked_state)):
            findings.append(
                Finding(
                    rule=rule.id,
                    message=(
                        f"[{run.kind}] new checkpoint leaf `{key}` "
                        f"({current_state[key]}) is not in the locked "
                        "manifest — relock additively with "
                        "--resume-audit --update-budgets"
                    ),
                    severity=rule.severity,
                    subject=f"{run.kind}:{key}",
                    engine=ENGINE_STATE,
                )
            )
        for key in sorted(set(current_state) & set(locked_state)):
            if current_state[key] != locked_state[key]:
                findings.append(
                    Finding(
                        rule=rule.id,
                        message=(
                            f"[{run.kind}] checkpoint leaf `{key}` "
                            f"changed {locked_state[key]} -> "
                            f"{current_state[key]} — every checkpoint "
                            "on disk restores with the old "
                            "shape/dtype; relock with --update-budgets "
                            "only alongside a migration story"
                        ),
                        severity=rule.severity,
                        subject=f"{run.kind}:{key}",
                        engine=ENGINE_STATE,
                    )
                )
        locked_meta = set(locked.get("metadata", []))
        current_meta = set(run.manifest.get("metadata", []))
        for key in sorted(locked_meta - current_meta):
            findings.append(
                Finding(
                    rule=rule.id,
                    message=(
                        f"[{run.kind}] host-metadata key `{key}` "
                        "vanished from _save_metadata() — resume "
                        "silently loses it; relock with "
                        "--update-budgets if intended"
                    ),
                    severity=rule.severity,
                    subject=f"{run.kind}:{key}",
                    engine=ENGINE_STATE,
                )
            )
        for key in sorted(current_meta - locked_meta):
            findings.append(
                Finding(
                    rule=rule.id,
                    message=(
                        f"[{run.kind}] new host-metadata key `{key}` "
                        "is not in the locked manifest — relock "
                        "additively with --update-budgets"
                    ),
                    severity=rule.severity,
                    subject=f"{run.kind}:{key}",
                    engine=ENGINE_STATE,
                )
            )
    # stale manifest entries: locked trainer kinds that no longer exist
    from trlx_tpu.analysis import harness

    for stale in sorted(set(locked_trainers) - set(harness.TRAINER_KINDS)):
        findings.append(
            Finding(
                rule=stale_rule.id,
                message=(
                    f"state manifest names trainer kind `{stale}` which "
                    "is not a registered harness kind — prune it with "
                    "--resume-audit --update-budgets"
                ),
                severity=stale_rule.severity,
                subject=f"state_manifest:{stale}",
                engine=ENGINE_STATE,
            )
        )
    return findings


# ------------------------------ planted gap ------------------------------ #

# NOTE: test_analysis_state.py and the CI planted-gap step grep for the
# exact localization "planted_resume_gap.py:18" — the line of the first
# uncarried mutation below (`self.draws += 1`). Keep the layout stable.
_PLANT_SOURCE = '''\
"""Planted resume gap (generated by --plant-gap; never shipped)."""


class PlantedSampler:
    """A sampler whose schedule depends on an uncheckpointed counter."""

    def __init__(self, seed):
        self.seed = seed
        self.draws = 0

    def state_dict(self):
        return {"seed": self.seed}

    def load_state_dict(self, state):
        self.seed = state["seed"]

    def next_seed(self):
        self.draws += 1
        return self.seed + self.draws
'''

_PLANT_FILE = "planted_resume_gap.py"
_PLANT_LINE = 18


def plant_gap_paths(workdir: str) -> List[str]:
    """Write the planted source into ``workdir`` and return the scan
    paths (planted file only — the shipped surface is audited by the
    normal run; the plant proves detection, not the tree)."""
    path = os.path.join(workdir, _PLANT_FILE)
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(_PLANT_SOURCE)
    return [path]


# ------------------------------ entry point ------------------------------ #

@dataclass
class StateAuditResult:
    """The ``--resume-audit`` payload next to the findings report."""

    mesh: Dict[str, int] = field(default_factory=dict)
    classified: List[AttrClassification] = field(default_factory=list)
    runs: List[DifferRun] = field(default_factory=list)

    def to_json(self) -> Dict[str, Any]:
        by_category: Dict[str, int] = {}
        for c in self.classified:
            by_category[c.category] = by_category.get(c.category, 0) + 1
        return {
            "mesh": self.mesh,
            "classified_attrs": len(self.classified),
            "by_category": dict(sorted(by_category.items())),
            "differ": [
                {
                    "kind": r.kind,
                    "compared_paths": r.compared_paths,
                    "divergences": len(r.divergences),
                }
                for r in self.runs
            ],
        }


def audit_resume_state(
    kinds: Optional[Sequence[str]] = None,
    mesh: Optional[Dict[str, int]] = None,
    budgets_path: Optional[str] = None,
    update: bool = False,
    plant_gap: bool = False,
    static_paths: Optional[Sequence[str]] = None,
) -> Tuple[Report, StateAuditResult]:
    """The ``--resume-audit`` entry point.

    Static classification first (no jax), then the per-kind kill/resume
    differ, then the schema gate against (or with ``update=True`` a
    relock of) the ``state_manifest`` section of analysis/budgets.json.
    """
    import tempfile

    from trlx_tpu.analysis import harness
    from trlx_tpu.analysis.resource_audit import (
        default_budgets_path,
        load_budgets,
        write_budgets,
    )

    path = budgets_path or default_budgets_path()
    report = Report()
    result = StateAuditResult()

    # ---- static half ---- #
    classified, static_findings = classify_surface(paths=static_paths)
    result.classified = classified
    if plant_gap:
        with tempfile.TemporaryDirectory(
            prefix="resume_plant_"
        ) as plantdir:
            _, plant_findings = classify_surface(
                paths=plant_gap_paths(plantdir)
            )
            static_findings += plant_findings
    report.covered += [
        f"state:{c.cls}.{c.attr}[{c.category}]" for c in classified
    ]

    # ---- dynamic half ---- #
    dyn_findings: List[Finding] = []
    for kind in kinds or harness.TRAINER_KINDS:
        # plant only on the cheapest trainer: one planted divergence
        # proves the differ end-to-end; planting everywhere just
        # multiplies identical findings
        plant_here = plant_gap and kind == (kinds or ("ilql",))[0]
        run = run_resume_differ(kind, mesh, plant_gap=plant_here)
        result.runs.append(run)
        dyn_findings += divergence_findings(run)
        report.covered += [
            f"differ:{kind}:{run.compared_paths} paths"
        ]
        for key in run.manifest.get("state", {}):
            report.covered.append(f"manifest:{kind}:{key}")
        for key in run.manifest.get("metadata", []):
            report.covered.append(f"manifest-meta:{kind}:{key}")
        result.mesh = run.mesh or result.mesh

    # ---- schema lock ---- #
    if update:
        try:
            budgets = load_budgets(path)
        except (OSError, ValueError):
            budgets = {}
        partial = kinds is not None
        section = make_state_manifest(result.runs, result.mesh)
        old_section = budgets.get("state_manifest") or {}
        if partial and old_section.get("mesh") not in (
            None,
            section["mesh"],
        ):
            rule = get_rule("ckpt-schema-drift")
            report.extend([
                Finding(
                    rule=rule.id,
                    message=(
                        "refusing --update-budgets: the state manifest "
                        f"is locked for mesh {old_section.get('mesh')} "
                        f"but this --trainers subset ran on "
                        f"{section['mesh']} — rerun without --trainers "
                        "or on the locked mesh"
                    ),
                    severity=rule.severity,
                    subject="state_manifest",
                    engine=ENGINE_STATE,
                )
            ])
            return report, result
        # unsuppressed gaps/divergences refuse the relock BEFORE any
        # write: a manifest locked over a broken tree would certify
        # the breakage
        kept_f, suppressed = filter_suppressed(
            static_findings + dyn_findings
        )
        report.extend(kept_f)
        report.suppressed += suppressed
        if report.findings:
            return report, result
        if partial:
            kept = {
                k: dict(v)
                for k, v in old_section.get("trainers", {}).items()
                if k not in set(kinds or ())
            }
            kept.update(section["trainers"])
            section["trainers"] = {k: kept[k] for k in sorted(kept)}
        budgets["state_manifest"] = section
        write_budgets(budgets, path)
        return report, result

    try:
        budgets = load_budgets(path)
    except (OSError, ValueError) as e:
        rule = get_rule("ckpt-schema-drift")
        static_findings.append(
            Finding(
                rule=rule.id,
                message=(
                    f"cannot load budget contract {path}: {e} — "
                    "generate it with --resume-audit --update-budgets"
                ),
                severity=rule.severity,
                subject="state_manifest",
                engine=ENGINE_STATE,
            )
        )
        budgets = {}
    manifest_findings: List[Finding] = []
    if budgets:
        manifest_findings = check_state_manifest(
            result.runs, budgets, result.mesh, path
        )
    kept, suppressed = filter_suppressed(
        static_findings + dyn_findings + manifest_findings
    )
    report.extend(kept)
    report.suppressed += suppressed
    return report, result


def format_state_text(result: StateAuditResult) -> str:
    by_category: Dict[str, int] = {}
    for c in result.classified:
        by_category[c.category] = by_category.get(c.category, 0) + 1
    lines = [
        f"resume surface: {len(result.classified)} classified "
        "mutable attrs "
        + " ".join(
            f"{k}={v}" for k, v in sorted(by_category.items())
        )
    ]
    for run in result.runs:
        lines.append(
            f"{run.kind:8} differ: {run.compared_paths} live paths "
            f"compared, {len(run.divergences)} divergence(s); "
            f"{len(run.manifest.get('state', {}))} state leaves + "
            f"{len(run.manifest.get('metadata', []))} metadata keys "
            "fingerprinted"
        )
    return "\n".join(lines)
