"""Compile-stability audit: runtime trace counting + jaxpr drift.

Engine 8 of ``trlx_tpu.analysis``. Silent recompilation is the dominant
un-instrumented TPU perf killer in a pjit training loop: one
shape-varying call site (a buffer resized to an arbitrary capacity, a
host scalar rehashing the jit cache key) recompiles the whole train step
mid-run, costs minutes of XLA time at real shapes, and shows up nowhere
— not in loss curves, not in the other engines. Three complementary
checks:

- **trace-count harness** (``python -m trlx_tpu.analysis
  --compile-audit``): runs each trainer's canonical short loop on the
  CPU audit mesh with a compilation hook installed (the
  ``jax_log_compiles`` log stream, which names the jitted callable per
  *actual backend compile* — cache hits are silent), attributes every
  compile to its callable, and gates per-callable counts against the
  ``compile_budgets`` section of ``analysis/budgets.json`` (rule
  ``compile-count-regression``; relock via ``--update-budgets``). Every
  driven callable is invoked again with steady-state inputs after its
  first compile — a compile observed in that window is an
  ``unexpected-retrace``.
- **jaxpr drift**: the same program is traced at step 0 and at step k
  and the canonicalized equation lists are diffed; the first divergent
  equation (shape, dtype/weak_type, or static-arg provenance) ships
  inside the retrace finding, so the report names the *cause* of the
  recompilation, not just the count.
- **AST retrace-risk rules** (rule ``retrace-risk``, also in ``--engine
  all``): untraced trainer/orchestrator loop code feeding a ``*_jit``
  call site values derived from ``len()`` / ``.item()`` / ``int(...)``
  (each distinct value is a fresh cache key), passing non-literal
  expressions in ``static_argnums`` positions, and jit-traced functions
  closing over module globals that other functions mutate (the traced
  value is baked at compile time; mutation silently uses stale data or
  retraces).

The counts are *contracts*: deterministic for a given (config, mesh,
jax version). The harness runs real compiles, so it lives behind its own
CLI flag (and CI job) rather than inside ``--engine all``.
"""

from __future__ import annotations

import hashlib
import logging
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from trlx_tpu.analysis.findings import Finding, Report, filter_suppressed
from trlx_tpu.analysis.registry import get_rule

# loggers that carry the compile/trace records we count: pxla logs
# "Compiling jit(<name>) with global shapes and types [...]" once per
# actual backend compile; dispatch logs the trace/compile timings
_JAX_COMPILE_LOGGERS = (
    "jax._src.interpreters.pxla",
    "jax._src.dispatch",
)

_COMPILING_RE = re.compile(r"^Compiling jit\(([^\s)]+)\) with global shapes and types (.*)$", re.S)
_TRACING_RE = re.compile(r"^Finished tracing \+ transforming ([^\s]+) for pjit in ([0-9.eE+-]+) sec")
_COMPILED_RE = re.compile(r"^Finished XLA compilation of jit\(([^\s)]+)\) in ([0-9.eE+-]+) sec")


@dataclass
class CompileEvent:
    """One actual backend compilation, as logged by pxla."""

    name: str  # the jitted callable's __name__
    arg_spec: str  # abstract arg shapes/dtypes at the compiling call
    steady: bool  # fired after the harness declared steady state


class CompileMonitor:
    """Context manager counting actual XLA compiles per callable name.

    Uses the ``jax_log_compiles`` record stream at DEBUG level (the
    records are emitted regardless of the config flag; the flag only
    raises their priority), so nothing is printed and no jax internals
    are patched. A compile cache hit emits nothing — counts are *real*
    compiles, exactly what a retrace audit must see.
    """

    def __init__(self) -> None:
        self.events: List[CompileEvent] = []
        self.trace_seconds = 0.0
        self.compile_seconds = 0.0
        self._steady = False
        self._handler: Optional[logging.Handler] = None
        self._saved_levels: Dict[str, int] = {}
        self._saved_propagate: Dict[str, bool] = {}

    # ------------------------------ phases ------------------------------ #

    def mark_steady(self) -> None:
        """Everything after this point is a steady-state repeat: any
        compile recorded from here on is an unexpected retrace."""
        self._steady = True

    def mark_warmup(self) -> None:
        self._steady = False

    def counts(self, steady_only: bool = False) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.events:
            if steady_only and not e.steady:
                continue
            out[e.name] = out.get(e.name, 0) + 1
        return out

    # ---------------------------- log plumbing --------------------------- #

    def _emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:
            return
        m = _COMPILING_RE.match(msg)
        if m:
            self.events.append(
                CompileEvent(
                    name=m.group(1),
                    arg_spec=m.group(2).strip(),
                    steady=self._steady,
                )
            )
            return
        m = _TRACING_RE.match(msg)
        if m:
            self.trace_seconds += float(m.group(2))
            return
        m = _COMPILED_RE.match(msg)
        if m:
            self.compile_seconds += float(m.group(2))

    def __enter__(self) -> "CompileMonitor":
        monitor = self

        class _Handler(logging.Handler):
            def emit(self, record: logging.LogRecord) -> None:
                monitor._emit(record)

        self._handler = _Handler(level=logging.DEBUG)
        for name in _JAX_COMPILE_LOGGERS:
            lg = logging.getLogger(name)
            self._saved_levels[name] = lg.level
            # the records are emitted at DEBUG unless jax_log_compiles is
            # set; open the logger without touching global jax config
            if lg.level == 0 or lg.level > logging.DEBUG:
                lg.setLevel(logging.DEBUG)
            # opening the logger at DEBUG would otherwise spray every
            # compile record through the root handler — keep the stream
            # private to this monitor while it is attached
            self._saved_propagate[name] = lg.propagate
            lg.propagate = False
            lg.addHandler(self._handler)
        return self

    def __exit__(self, *exc) -> None:
        for name in _JAX_COMPILE_LOGGERS:
            lg = logging.getLogger(name)
            if self._handler is not None:
                lg.removeHandler(self._handler)
            lg.setLevel(self._saved_levels.get(name, 0))
            lg.propagate = self._saved_propagate.get(name, True)
        self._handler = None


# ------------------------------ jaxpr drift ------------------------------ #

def canonical_eqns(closed_jaxpr, _depth: int = 0) -> List[str]:
    """Canonicalized equation lines of a (closed) jaxpr: variables renamed
    to serial ids, avals printed with weak_type, static params sorted —
    two traces of the same program produce identical lists iff nothing
    that feeds the compile cache key changed.

    Call-like sub-jaxprs (pjit, remat, scan/cond bodies, custom_*) are
    INLINED as indented lines, not summarized: the drift diff must both
    detect an inner-equation change (a same-length summary like
    ``<jaxpr:3eqns>`` would hash identically) and *name* the divergent
    inner equation — a traced ``jax.jit`` wrapper is a single outer pjit
    eqn, so without inlining every real divergence would be reported as
    the whole train step."""
    inner = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    names: Dict[int, str] = {}
    pad = "  " * _depth

    def ref(v) -> str:
        if hasattr(v, "val"):  # Literal
            return f"lit({v.val!r})"
        if id(v) not in names:
            names[id(v)] = f"v{len(names)}"
        return names[id(v)]

    def aval_str(v) -> str:
        aval = getattr(v, "aval", None)
        if aval is None:
            return "?"
        weak = getattr(aval, "weak_type", False)
        return f"{aval.str_short()}{'~w' if weak else ''}"

    def is_jaxpr(val) -> bool:
        return hasattr(val, "jaxpr") or hasattr(val, "eqns")

    def param_str(params: Dict, sub_lines: List[str]) -> str:
        parts = []
        for k in sorted(params):
            val = params[k]
            if is_jaxpr(val):
                parts.append(f"{k}=<jaxpr>")
                sub_lines.extend(canonical_eqns(val, _depth + 1))
            elif isinstance(val, (list, tuple)) and any(
                is_jaxpr(x) for x in val
            ):
                parts.append(f"{k}=<jaxprs:{len(val)}>")
                for x in val:
                    if is_jaxpr(x):
                        sub_lines.extend(canonical_eqns(x, _depth + 1))
            else:
                parts.append(f"{k}={val!r}")
        return ",".join(parts)

    for v in list(inner.constvars) + list(inner.invars):
        ref(v)
    lines = [
        pad
        + "in "
        + " ".join(f"{ref(v)}:{aval_str(v)}" for v in inner.invars)
    ]
    for eqn in inner.eqns:
        ins = " ".join(f"{ref(v)}:{aval_str(v)}" for v in eqn.invars)
        outs = " ".join(f"{ref(v)}:{aval_str(v)}" for v in eqn.outvars)
        sub_lines: List[str] = []
        params = param_str(eqn.params, sub_lines)
        lines.append(f"{pad}{eqn.primitive.name}[{params}] {ins} -> {outs}")
        lines.extend(sub_lines)
    return lines


def jaxpr_fingerprint(closed_jaxpr) -> str:
    digest = hashlib.sha256()
    for line in canonical_eqns(closed_jaxpr):
        digest.update(line.encode())
    return digest.hexdigest()[:16]


@dataclass
class JaxprDrift:
    """First divergence between two traces of one program."""

    eqn_index: int  # -1: different eqn counts with a common prefix
    before: str
    after: str
    cause: str  # "shape" | "dtype" | "weak_type" | "static-args" | "structure"

    def describe(self) -> str:
        where = (
            "program input signature diverged"
            if self.eqn_index < 0
            else f"first divergent eqn #{self.eqn_index}"
        )
        before, after = _focus_divergence(self.before, self.after)
        return (
            f"{where} [{self.cause}]: "
            f"step-0 `{before}` vs step-k `{after}`"
        )


def _focus_divergence(
    before: str, after: str, width: int = 160
) -> Tuple[str, str]:
    """Window both lines around their first differing character — a train
    step's input-signature line holds hundreds of avals, and the finding
    must show the drifting operand, not the whole state tree."""
    if max(len(before), len(after)) <= width:
        return before, after
    i = 0
    for i, (b, a) in enumerate(zip(before, after)):
        if b != a:
            break
    start = max(0, i - width // 4)

    def clip(s: str) -> str:
        end = start + width
        head = "..." if start else ""
        tail = "..." if end < len(s) else ""
        return f"{head}{s[start:end]}{tail}"

    return clip(before), clip(after)


def _classify_drift(before: str, after: str) -> str:
    """Name what changed between two canonical eqn lines."""
    aval_re = re.compile(r"v\d+:([a-z0-9_]+)\[([\d,]*)\](~w)?")
    b, a = aval_re.findall(before), aval_re.findall(after)
    if len(b) == len(a) and b != a:
        for (bd, bs, bw), (ad, as_, aw) in zip(b, a):
            if bs != as_:
                return "shape"
            if bd != ad:
                return "dtype"
            if bw != aw:
                return "weak_type"
    b_head, a_head = before.split(" ", 1)[0], after.split(" ", 1)[0]
    if b_head.split("[")[0] != a_head.split("[")[0]:
        return "structure"
    if b_head != a_head:
        return "static-args"
    return "structure"


def diff_jaxprs(before_jaxpr, after_jaxpr) -> Optional[JaxprDrift]:
    """Diff two traces of the same program; ``None`` when identical."""
    before = canonical_eqns(before_jaxpr)
    after = canonical_eqns(after_jaxpr)
    if before == after:
        return None
    for i, (b, a) in enumerate(zip(before, after)):
        if b != a:
            return JaxprDrift(
                eqn_index=i - 1,  # line 0 is the input signature
                before=b,
                after=a,
                # a line-0 divergence is the program input signature
                # itself changing — classify it like any other aval diff
                cause=_classify_drift(b, a),
            )
    # one trace is a strict prefix of the other
    longer = before if len(before) > len(after) else after
    i = min(len(before), len(after))
    return JaxprDrift(
        eqn_index=i - 1,
        before=before[i] if len(before) > len(after) else "<absent>",
        after="<absent>" if len(before) > len(after) else longer[i],
        cause="structure",
    )


# --------------------------- the canonical loop --------------------------- #

@dataclass
class DrivenProgram:
    """One jitted callable exercised by the canonical loop."""

    subject: str  # "ppo.train_step"
    log_name: str  # the name pxla logs compiles under
    def_site: Optional[Tuple[str, int]]
    compiles: int = 0
    steady_compiles: int = 0
    drift: Optional[JaxprDrift] = None
    trace0_fingerprint: str = ""
    tracek_fingerprint: str = ""


def _log_name(fn) -> str:
    inner = getattr(fn, "__wrapped__", fn)
    return getattr(inner, "__name__", "<unnamed>")


def _sds_args(args) -> Any:
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            getattr(x, "shape", ()), getattr(x, "dtype", None),
            weak_type=bool(getattr(x, "weak_type", False)),
        ),
        args,
    )


def drive_trainer(
    kind: str,
    mesh: Optional[Dict[str, int]] = None,
    monitor: Optional[CompileMonitor] = None,
    steps: int = 2,
    instrument=None,
    train_overrides: Optional[Dict] = None,
) -> Tuple[List[DrivenProgram], CompileMonitor, Dict[str, int]]:
    """Run ``kind``'s canonical short loop under a compile monitor.

    The loop mirrors production dispatch order (rollout → stepwise update
    → fused phase → behavior snapshot) at the harness shapes. Every
    jitted callable is invoked at least twice with steady-state inputs;
    after the warmup pass the monitor is flipped to steady, so *any*
    compile in the second pass is an unexpected retrace. The train step's
    inputs are signature-captured at step 0 and step k, and re-traced at
    the end (tracing is compile-free) for the drift diff.

    ``instrument``, when given, is called with the freshly built trainer
    before any program runs — the lockstep simulator (engine 11) uses it
    to wrap every ``*_jit`` attribute with a dispatch recorder, so both
    engines share ONE canonical loop instead of drifting copies.
    ``train_overrides`` forwards to the harness config (same reason).
    """
    import jax

    from trlx_tpu.analysis import harness

    own_monitor = monitor is None
    monitor = monitor or CompileMonitor()
    mesh_shape: Dict[str, int] = {}

    def run_loop() -> List[DrivenProgram]:
        import jax.numpy as jnp

        from trlx_tpu.parallel.mesh import batch_sharding

        nonlocal mesh_shape
        trainer = harness.build_trainer(
            kind, mesh, train_overrides=train_overrides
        )
        if instrument is not None:
            instrument(trainer)
        mesh_shape.update(
            {k: int(v) for k, v in trainer.mesh.shape.items()}
        )
        batch_sh = getattr(trainer, "_batch_sh", None) or batch_sharding(
            trainer.mesh
        )
        B = trainer.config.train.batch_size
        Q = trainer.query_length
        prompt_ids = jnp.ones((B, Q), jnp.int32)
        prompt_mask = jnp.ones((B, Q), jnp.int32)

        driven: List[DrivenProgram] = []

        def register(subject: str, fn) -> DrivenProgram:
            d = DrivenProgram(
                subject=subject,
                log_name=_log_name(fn),
                def_site=harness.callable_def_site(fn),
            )
            driven.append(d)
            return d

        d_rollout = register(f"{kind}.rollout", trainer._sample_jit)
        d_step = register(f"{kind}.train_step", trainer._train_step_jit)
        if kind != "ilql":
            d_phase = register(
                f"{kind}.train_phase", trainer._train_phase_jit
            )
            d_snap = register(
                f"{kind}.behavior_snapshot", trainer._behavior_snapshot_jit
            )
        engine = None
        if kind == "ppo":
            # the continuous-batching engine's programs (docs/inference.md)
            # join the canonical loop: one mini slot-admission phase per
            # pass — a retrace on the second pass means the engine's
            # jitted shapes are not steady (e.g. per-phase state
            # reallocation changed a shape)
            engine = trainer.rollout_engine_obj
            register(f"{kind}.engine_prefill", engine.prefill_jit)
            register(f"{kind}.engine_decode_step", engine.decode_step_jit)
            register(f"{kind}.engine_refill", engine.refill_jit)

        step_args: List[Any] = []  # captured (state, mb) signatures

        def one_pass(step_seed: int) -> None:
            # rollout: the sampler consumes (params, prompts, key); the
            # key changes per call exactly as trainer.sample() does it
            trainer.sample(prompt_ids, prompt_mask)
            # stepwise update: fresh minibatch VALUES, stable shapes
            mb = harness.concrete_minibatch(trainer, kind, seed=step_seed)
            mb = jax.device_put(mb, batch_sh)
            step_args.append(_sds_args((trainer.state, mb)))
            trainer.state, _stats = trainer._train_step_jit(
                trainer.state, mb
            )
            if kind == "ilql":
                return
            # fused phase over 2 stacked minibatches + phase snapshot
            stacked = jax.tree_util.tree_map(
                lambda a, b: jnp.stack([a, b]),
                harness.concrete_minibatch(trainer, kind, seed=step_seed),
                harness.concrete_minibatch(
                    trainer, kind, seed=step_seed + 17
                ),
            )
            stacked = jax.device_put(stacked, trainer._stacked_batch_sh)
            trainer.state, _ = trainer._train_phase_jit(
                trainer.state, stacked
            )
            trainer._behavior_snapshot_jit(trainer.state.params)
            if engine is not None:
                # one harvest group through the slot-admission loop:
                # fresh prompt VALUES per pass, stable shapes
                import numpy as _np

                rng = _np.random.default_rng(step_seed)
                n = engine.harvest_width
                eng_ids = rng.integers(1, 30, (n, Q)).astype(_np.int32)
                engine.start_phase(
                    trainer.rollout_params(),
                    jax.random.fold_in(
                        jax.random.PRNGKey(0), step_seed
                    ),
                )
                engine.submit(eng_ids, _np.ones((n, Q), _np.int32))
                for _group in engine.drive(n):
                    pass

        one_pass(0)
        monitor.mark_steady()
        for s in range(1, max(2, steps)):
            one_pass(s)

        # attribute counts; drift-trace the step program at step 0 vs k
        warm = monitor.counts(steady_only=False)
        steady = monitor.counts(steady_only=True)
        for d in driven:
            d.compiles = warm.get(d.log_name, 0)
            d.steady_compiles = steady.get(d.log_name, 0)
        state0, mb0 = step_args[0]
        statek, mbk = step_args[-1]
        j0 = jax.make_jaxpr(trainer._train_step_jit)(state0, mb0)
        jk = jax.make_jaxpr(trainer._train_step_jit)(statek, mbk)
        d_step.trace0_fingerprint = jaxpr_fingerprint(j0)
        d_step.tracek_fingerprint = jaxpr_fingerprint(jk)
        d_step.drift = diff_jaxprs(j0, jk)
        return driven

    if own_monitor:
        with monitor:
            driven = run_loop()
    else:
        driven = run_loop()
    return driven, monitor, mesh_shape


# ------------------------------- budgets --------------------------------- #

def make_compile_budgets(
    driven: Sequence[DrivenProgram], mesh: Dict[str, int]
) -> Dict:
    return {
        "mesh": {k: int(v) for k, v in sorted(mesh.items())},
        "programs": {
            d.subject: {"compiles": d.compiles}
            for d in sorted(driven, key=lambda d: d.subject)
        },
    }


def check_compile_budgets(
    driven: Sequence[DrivenProgram],
    budgets: Dict,
    mesh: Optional[Dict[str, int]] = None,
    budgets_path: Optional[str] = None,
) -> List[Finding]:
    """Gate observed compile counts against the committed contract."""
    rule = get_rule("compile-count-regression")
    findings: List[Finding] = []
    where = os.path.basename(budgets_path or "budgets.json")
    section = budgets.get("compile_budgets")
    if section is None:
        return [
            Finding(
                rule=rule.id,
                message=(
                    f"{where} has no compile_budgets section — lock the "
                    "compile counts with --compile-audit --update-budgets "
                    "and commit the diff"
                ),
                severity=rule.severity,
                subject="compile_budgets",
                engine="compile",
            )
        ]
    locked_mesh = section.get("mesh")
    if mesh is not None and locked_mesh is not None:
        current = {k: int(v) for k, v in sorted(mesh.items())}
        locked = {k: int(v) for k, v in sorted(locked_mesh.items())}
        if locked != current:
            return [
                Finding(
                    rule=rule.id,
                    message=(
                        f"compile budgets in {where} were locked for mesh "
                        f"{locked_mesh} but the audit ran on {current} — "
                        "counts are not comparable; rerun on the locked "
                        "mesh or --update-budgets"
                    ),
                    severity=rule.severity,
                    subject="compile_budgets",
                    engine="compile",
                )
            ]
    programs = section.get("programs", {})
    for d in driven:
        file, line = d.def_site or (None, None)
        entry = programs.get(d.subject)
        if entry is None:
            findings.append(
                Finding(
                    rule=rule.id,
                    message=(
                        f"no committed compile budget for driven program "
                        f"`{d.subject}` ({d.compiles} compile(s) observed) "
                        "— run --compile-audit --update-budgets and review "
                        "the lockfile diff"
                    ),
                    severity=rule.severity,
                    file=file,
                    line=line,
                    subject=d.subject,
                    engine="compile",
                )
            )
            continue
        locked_n = int(entry.get("compiles", 0))
        if d.compiles > locked_n:
            findings.append(
                Finding(
                    rule=rule.id,
                    message=(
                        f"`{d.subject}` compiled {d.compiles}× over the "
                        f"canonical loop, past the committed {locked_n}× — "
                        "each extra compile is minutes of XLA time at real "
                        "shapes; if intended, relock with --compile-audit "
                        "--update-budgets and explain the diff"
                    ),
                    severity=rule.severity,
                    file=file,
                    line=line,
                    subject=d.subject,
                    engine="compile",
                )
            )
    driven_kinds = {d.subject.split(".")[0] for d in driven}
    current_subjects = {d.subject for d in driven}
    for stale in sorted(set(programs) - current_subjects):
        if stale.split(".")[0] in driven_kinds:
            findings.append(
                Finding(
                    rule=rule.id,
                    message=(
                        f"compile budget entry `{stale}` no longer matches "
                        "any driven program — prune it with "
                        "--compile-audit --update-budgets"
                    ),
                    severity="warning",
                    subject=stale,
                    engine="compile",
                )
            )
    return findings


def retrace_findings(driven: Sequence[DrivenProgram]) -> List[Finding]:
    """unexpected-retrace findings for steady-window compiles, with the
    jaxpr drift attached when the step-0/step-k traces disagree."""
    rule = get_rule("unexpected-retrace")
    findings: List[Finding] = []
    for d in driven:
        if not d.steady_compiles:
            continue
        if d.drift is not None:
            cause = f"; jaxpr drift: {d.drift.describe()}"
        elif d.trace0_fingerprint and (
            d.trace0_fingerprint == d.tracek_fingerprint
        ):
            cause = (
                "; traced program is IDENTICAL at step 0 and step k — the "
                "retrace came from cache-key churn outside the jaxpr "
                "(rebuilt callable identity, non-hashable static args)"
            )
        else:
            cause = ""
        file, line = d.def_site or (None, None)
        findings.append(
            Finding(
                rule=rule.id,
                message=(
                    f"`{d.subject}` recompiled {d.steady_compiles}× during "
                    "the steady-state repeat of the canonical loop — a "
                    "shape-/dtype-varying call site retraces this program "
                    f"every step at real shapes{cause}"
                ),
                severity=rule.severity,
                file=file,
                line=line,
                subject=d.subject,
                engine="compile",
            )
        )
    return findings


# --------------------------- AST retrace risks ---------------------------- #

_HOST_VARYING_CALLS = ("len", "int")


def _expr_retrace_risk(node) -> Optional[str]:
    """Why an argument expression fed to a jitted call risks retraces;
    ``None`` when it looks safe."""
    import ast

    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            func = sub.func
            if (
                isinstance(func, ast.Name)
                and func.id in _HOST_VARYING_CALLS
                and sub.args
                and not isinstance(sub.args[0], ast.Constant)
            ):
                return (
                    f"derives a Python scalar via {func.id}() — every "
                    "distinct value is a fresh jit cache key (weak-typed "
                    "scalar), so the callable recompiles per value"
                )
            if isinstance(func, ast.Attribute) and func.attr == "item":
                return (
                    "derives a Python scalar via .item() — a per-step "
                    "device value becomes a fresh jit cache key each step"
                )
    return None


def lint_retrace_risk(paths: Sequence[str]) -> Tuple[List[Finding], List[str], int]:
    """AST pass over untraced (host-loop) code: per-step-varying host
    scalars fed to ``*_jit`` call sites, non-literal static args, and
    traced closures over mutated module globals."""
    import ast

    from trlx_tpu.analysis.ast_lint import (
        _FunctionIndex,
        _ImportAliases,
        _is_trace_entry,
        _transitively_traced,
        collect_py_files,
    )

    rule = get_rule("retrace-risk")
    files = collect_py_files(paths)

    findings: List[Finding] = []
    n_suppressed = 0
    for path in files:
        try:
            with open(path, encoding="utf-8") as fh:
                source = fh.read()
            tree = ast.parse(source, filename=path)
        except (OSError, SyntaxError):
            continue
        aliases = _ImportAliases()
        aliases.visit(tree)
        index = _FunctionIndex(aliases)
        index.visit(tree)
        traced = _transitively_traced(index)

        # names bound by `g = jax.jit(f, static_argnums=...)` and the
        # positions of their static args
        static_positions: Dict[str, Set[int]] = {}
        mutated_globals: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Global):
                mutated_globals.update(node.names)
            if not isinstance(node, ast.Assign):
                continue
            value = node.value
            if not (
                isinstance(value, ast.Call)
                and _is_trace_entry(value.func, aliases)
            ):
                continue
            positions: Set[int] = set()
            for kw in value.keywords:
                if kw.arg == "static_argnums" and isinstance(
                    kw.value, (ast.Tuple, ast.Constant)
                ):
                    elts = (
                        kw.value.elts
                        if isinstance(kw.value, ast.Tuple)
                        else [kw.value]
                    )
                    positions = {
                        e.value
                        for e in elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, int)
                    }
            if positions:
                for target in node.targets:
                    name = None
                    if isinstance(target, ast.Name):
                        name = target.id
                    elif isinstance(target, ast.Attribute):
                        name = target.attr
                    if name:
                        static_positions[name] = positions

        def add(node, message: str, subject: str) -> None:
            findings.append(
                Finding(
                    rule=rule.id,
                    message=message,
                    severity=rule.severity,
                    file=path,
                    line=getattr(node, "lineno", None),
                    subject=subject,
                    engine="compile",
                )
            )

        # (1)+(2): jitted call sites in untraced functions
        for fname in sorted(set(index.defs) - traced):
            for fnode in index.defs.get(fname, ()):
                # one-hop taint: locals assigned from a host-varying
                # derivation (`n = len(batch)`) carry the risk to the
                # call site that consumes them
                tainted: Dict[str, str] = {}
                for sub in ast.walk(fnode):
                    if not (
                        isinstance(sub, ast.Assign)
                        and len(sub.targets) == 1
                        and isinstance(sub.targets[0], ast.Name)
                    ):
                        continue
                    why = _expr_retrace_risk(sub.value)
                    if why is not None:
                        tainted[sub.targets[0].id] = why
                for node in ast.walk(fnode):
                    if not isinstance(node, ast.Call):
                        continue
                    callee = None
                    if isinstance(node.func, ast.Attribute):
                        callee = node.func.attr
                    elif isinstance(node.func, ast.Name):
                        callee = node.func.id
                    if callee is None:
                        continue
                    is_jit_site = callee.endswith("_jit") or (
                        callee in static_positions
                    )
                    if not is_jit_site:
                        continue
                    for pos, arg in enumerate(node.args):
                        why = _expr_retrace_risk(arg)
                        if why is None and isinstance(arg, ast.Name):
                            why = tainted.get(arg.id)
                        if why is not None:
                            add(
                                arg,
                                f"jitted call site `{callee}(...)` arg "
                                f"{pos} {why}; pass a device array or a "
                                "step-invariant scalar",
                                f"{fname}()",
                            )
                        elif pos in static_positions.get(
                            callee, set()
                        ) and not isinstance(arg, ast.Constant):
                            if not (
                                isinstance(arg, ast.Attribute)
                                and "config" in ast.dump(arg)
                            ):
                                add(
                                    arg,
                                    f"static arg {pos} of `{callee}(...)` "
                                    "is a non-literal expression — every "
                                    "distinct (or unhashable) value "
                                    "recompiles the callable",
                                    f"{fname}()",
                                )

        # (3): traced functions reading module globals that something
        # mutates via `global X`
        if mutated_globals:
            for fname in sorted(traced):
                for fnode in index.defs.get(fname, ()):
                    assigned_here = {
                        t.id
                        for sub in ast.walk(fnode)
                        if isinstance(sub, ast.Assign)
                        for t in sub.targets
                        if isinstance(t, ast.Name)
                    }
                    for node in ast.walk(fnode):
                        if (
                            isinstance(node, ast.Name)
                            and isinstance(node.ctx, ast.Load)
                            and node.id in mutated_globals
                            and node.id not in assigned_here
                        ):
                            add(
                                node,
                                f"traced function closes over module "
                                f"global `{node.id}` that other code "
                                "mutates — the traced value is baked at "
                                "compile time; mutations are silently "
                                "ignored (or force retraces via static "
                                "hashing)",
                                f"{fname}()",
                            )
                            break

    kept, n_suppressed = filter_suppressed(findings)
    return kept, files, n_suppressed


# ----------------------------- orchestration ------------------------------ #

@dataclass
class CompileAuditResult:
    driven: List[DrivenProgram] = field(default_factory=list)
    mesh: Dict[str, int] = field(default_factory=dict)
    trace_seconds: float = 0.0
    compile_seconds: float = 0.0
    unattributed: Dict[str, int] = field(default_factory=dict)

    def to_rows(self) -> List[Dict]:
        return [
            {
                "subject": d.subject,
                "compiles": d.compiles,
                "steady_compiles": d.steady_compiles,
                "trace_fingerprint_step0": d.trace0_fingerprint,
                "trace_fingerprint_stepk": d.tracek_fingerprint,
                "drift": d.drift.describe() if d.drift else None,
            }
            for d in sorted(self.driven, key=lambda d: d.subject)
        ]


def audit_compiles(
    kinds: Optional[Sequence[str]] = None,
    mesh: Optional[Dict[str, int]] = None,
    budgets_path: Optional[str] = None,
    update: bool = False,
    steps: int = 2,
) -> Tuple[Report, CompileAuditResult]:
    """The ``--compile-audit`` entry point: drive every trainer's
    canonical loop under one monitor, then gate counts against (or with
    ``update=True`` relock) the ``compile_budgets`` section of
    ``analysis/budgets.json``. Also runs the AST retrace-risk rules so
    the CI job covers the static half of the engine."""
    from trlx_tpu.analysis import harness
    from trlx_tpu.analysis.resource_audit import (
        default_budgets_path,
        load_budgets,
        write_budgets,
    )

    path = budgets_path or default_budgets_path()
    result = CompileAuditResult()
    report = Report()
    all_driven: List[DrivenProgram] = []
    for kind in kinds or harness.TRAINER_KINDS:
        with CompileMonitor() as monitor:
            driven, _, mesh_shape = drive_trainer(
                kind, mesh, monitor=monitor, steps=steps
            )
        all_driven.extend(driven)
        result.mesh = mesh_shape or result.mesh
        result.trace_seconds += monitor.trace_seconds
        result.compile_seconds += monitor.compile_seconds
        named = {d.log_name for d in driven}
        for name, n in monitor.counts().items():
            if name not in named:
                result.unattributed[name] = (
                    result.unattributed.get(name, 0) + n
                )
    result.driven = all_driven
    report.covered += [f"compile:{d.subject}" for d in all_driven]

    findings = retrace_findings(all_driven)
    if update:
        try:
            budgets = load_budgets(path)
        except (OSError, ValueError):
            budgets = {}
        partial = kinds is not None
        section = make_compile_budgets(all_driven, result.mesh)
        old_section = budgets.get("compile_budgets") or {}
        if partial and old_section.get("mesh") not in (
            None, section["mesh"]
        ):
            rule = get_rule("compile-count-regression")
            report.extend([
                Finding(
                    rule=rule.id,
                    message=(
                        "refusing --update-budgets: the compile lockfile "
                        f"is for mesh {old_section.get('mesh')} but this "
                        f"--trainers subset ran on {section['mesh']} — "
                        "rerun without --trainers or on the locked mesh"
                    ),
                    severity=rule.severity,
                    subject="compile_budgets",
                    engine="compile",
                )
            ])
            return report, result
        if partial:
            kept = {
                s: dict(e)
                for s, e in old_section.get("programs", {}).items()
                if s.split(".")[0] not in {k for k in (kinds or ())}
            }
            kept.update(section["programs"])
            section["programs"] = {s: kept[s] for s in sorted(kept)}
        budgets["compile_budgets"] = section
        write_budgets(budgets, path)
        return report, result

    ast_findings, ast_covered, ast_suppressed = lint_retrace_risk(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
    )
    report.covered += [f"retrace-risk:{len(ast_covered)} files"]
    try:
        budgets = load_budgets(path)
    except (OSError, ValueError) as e:
        rule = get_rule("compile-count-regression")
        findings.append(
            Finding(
                rule=rule.id,
                message=(
                    f"cannot load budget contract {path}: {e} — generate "
                    "it with --compile-audit --update-budgets"
                ),
                severity=rule.severity,
                subject="compile_budgets",
                engine="compile",
            )
        )
        budgets = {}
    if budgets:
        findings += check_compile_budgets(
            all_driven, budgets, result.mesh, path
        )
    kept, suppressed = filter_suppressed(findings)
    report.extend(kept + ast_findings)
    report.suppressed += suppressed + ast_suppressed
    return report, result


def format_compile_text(result: CompileAuditResult) -> str:
    lines = [
        f"{'program':28} {'compiles':>9} {'steady':>7}  fingerprint(step0->k)"
    ]
    for row in result.to_rows():
        fp = row["trace_fingerprint_step0"]
        fpk = row["trace_fingerprint_stepk"]
        fps = f"{fp}->{fpk}" if fp or fpk else "-"
        lines.append(
            f"{row['subject']:28} {row['compiles']:>9} "
            f"{row['steady_compiles']:>7}  {fps}"
        )
        if row["drift"]:
            lines.append(f"  drift: {row['drift']}")
    lines.append(
        f"total: {result.compile_seconds:.1f}s XLA compile, "
        f"{result.trace_seconds:.1f}s trace"
        + (
            f"; unattributed compiles: {result.unattributed}"
            if result.unattributed
            else ""
        )
    )
    return "\n".join(lines)
