"""Donation-safety checker: use-after-donate, wasted and leaking aliases.

Engine 7 of ``trlx_tpu.analysis``. Buffer donation is the TPU port's
memory contract (the ``donation`` rule already *requires* it for train
steps) — but donation done wrong fails silently, off-device, or only on
real hardware. Three rules close the gap, riding the PR-1/PR-2 traced
programs plus an AST pass over the untraced trainer/orchestrator loops:

- ``use-after-donate`` (AST, host code): a pytree read after being passed
  to a donating jitted callable without rebinding the result first. The
  donating callables are *discovered per module* from
  ``jax.jit(..., donate_argnums=...)`` assignments, so the rule tracks
  the repo's own step functions without a hand-kept list. The walk is
  linear per function (loop-carried flows are not modeled); false
  positives silence with ``# tpu-lint: disable=use-after-donate``.
- ``donation-ignored`` (jaxpr): a donated input with no shape/dtype-
  matching output — XLA cannot reuse the buffer and only warns at
  runtime; the donation promise silently buys nothing.
- ``alias-escape`` (jaxpr): a program output that IS a non-donated input
  (pjit input-forwarding) — the caller receives an alias of a buffer it
  does not own, the exact PR-3 behavior-snapshot hazard: a later
  donating step invalidates every holder of the forwarded output.

Jaxpr findings anchor to the traced callable's ``def`` line (the
harness's ``def_site``), so inline suppression works there too.
"""

from __future__ import annotations

import ast
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from trlx_tpu.analysis.findings import Finding, filter_suppressed
from trlx_tpu.analysis.jaxpr_audit import is_jit_eqn
from trlx_tpu.analysis.registry import get_rule

# ----------------------------- jaxpr rules ------------------------------- #

def _donating_pjit(closed_jaxpr):
    """(inner jaxpr, donated mask) of a traced jitted callable, or
    (outer jaxpr, all-False) when no pjit wrapper is present."""
    outer = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    pjit_eqns = [e for e in outer.eqns if is_jit_eqn(e)]
    if len(outer.eqns) == 1 and pjit_eqns:
        eqn = pjit_eqns[0]
        inner = eqn.params["jaxpr"].jaxpr
        donated = list(eqn.params.get("donated_invars", ()))
        donated += [False] * (len(inner.invars) - len(donated))
        return inner, donated
    return outer, [False] * len(outer.invars)


def _path_label(input_paths: Optional[Sequence[str]], i: int) -> str:
    if input_paths and i < len(input_paths):
        return input_paths[i]
    return f"input[{i}]"


def check_donation_ignored(
    closed_jaxpr,
    subject: str,
    input_paths: Optional[Sequence[str]] = None,
    def_site: Optional[Tuple[str, int]] = None,
) -> List[Finding]:
    """Donated inputs XLA cannot reuse: no output shares their
    shape+dtype (aliasing requires an exact buffer match)."""
    rule = get_rule("donation-ignored")
    inner, donated = _donating_pjit(closed_jaxpr)
    if not any(donated):
        return []
    out_pool: Dict[Tuple, int] = {}
    for v in inner.outvars:
        if hasattr(v, "val"):
            continue
        key = (tuple(v.aval.shape), str(v.aval.dtype))
        out_pool[key] = out_pool.get(key, 0) + 1
    findings: List[Finding] = []
    file, line = def_site or (None, None)
    for i, (v, don) in enumerate(zip(inner.invars, donated)):
        if not don:
            continue
        key = (tuple(v.aval.shape), str(v.aval.dtype))
        if out_pool.get(key, 0) > 0:
            out_pool[key] -= 1
            continue
        findings.append(
            Finding(
                rule=rule.id,
                message=(
                    f"donated buffer `{_path_label(input_paths, i)}` "
                    f"(shape {tuple(v.aval.shape)}, {v.aval.dtype}) has no "
                    "same-shape/dtype output to reuse it — XLA ignores the "
                    "donation (silent HBM waste it only warns about at "
                    "runtime); stop donating this argument or return an "
                    "updated value for it"
                ),
                severity=rule.severity,
                file=file,
                line=line,
                subject=subject,
                engine="donation",
            )
        )
    return findings


def check_alias_escape(
    closed_jaxpr,
    subject: str,
    input_paths: Optional[Sequence[str]] = None,
    def_site: Optional[Tuple[str, int]] = None,
) -> List[Finding]:
    """Outputs that ARE non-donated inputs: jit forwards the caller's
    buffer instead of materializing a fresh one (forwarding a *donated*
    input is intended aliasing and allowed). A pass-through output shows
    in the traced program in one of two ways, and both are read: the jit
    call's body returns one of its own inputs (this jax), or the outer
    jaxpr returns its own input and the value never enters the jit call
    (the ones before hoisted it out). Either way it is the caller's
    buffer, returned."""
    rule = get_rule("alias-escape")
    outer = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    donated_by_var: Dict[int, bool] = {}
    forwards: Dict[int, Any] = {}  # a jit call's output -> the operand its body returns
    for eqn in outer.eqns:
        if not is_jit_eqn(eqn):
            continue
        for v, don in zip(eqn.invars, eqn.params.get("donated_invars", ())):
            if not hasattr(v, "val"):
                donated_by_var[id(v)] = donated_by_var.get(id(v), False) or don
        body = eqn.params["jaxpr"].jaxpr
        operand = {id(b): v for b, v in zip(body.invars, eqn.invars)}
        for out, returned in zip(eqn.outvars, body.outvars):
            if id(returned) in operand:
                forwards[id(out)] = operand[id(returned)]
    in_index = {id(v): i for i, v in enumerate(outer.invars)}
    findings: List[Finding] = []
    file, line = def_site or (None, None)
    for o, v in enumerate(outer.outvars):
        v = forwards.get(id(v), v)
        if hasattr(v, "val"):
            continue
        i = in_index.get(id(v))
        if i is None or donated_by_var.get(id(v), False):
            continue
        findings.append(
            Finding(
                rule=rule.id,
                message=(
                    f"output {o} of `{subject}` is input "
                    f"`{_path_label(input_paths, i)}` forwarded unchanged — "
                    "the caller receives an ALIAS of a buffer it does not "
                    "own; a later donating step invalidates every holder "
                    "(the PR-3 snapshot hazard). Copy the leaf "
                    "(e.g. `x + 0`/`jnp.copy`) or donate the argument"
                ),
                severity=rule.severity,
                file=file,
                line=line,
                subject=subject,
                engine="donation",
            )
        )
    return findings


def audit_traced_programs(programs: Iterable[Any]):
    """Jaxpr-side donation rules over harness TracedPrograms; returns a
    :class:`~trlx_tpu.analysis.findings.Report`."""
    from trlx_tpu.analysis.findings import Report

    report = Report()
    findings: List[Finding] = []
    for traced in programs:
        report.covered.append(f"donation:{traced.subject}")
        findings += check_donation_ignored(
            traced.closed_jaxpr,
            traced.subject,
            traced.input_paths,
            traced.def_site,
        )
        findings += check_alias_escape(
            traced.closed_jaxpr,
            traced.subject,
            traced.input_paths,
            traced.def_site,
        )
    kept, suppressed = filter_suppressed(findings)
    report.extend(kept)
    report.suppressed += suppressed
    return report


# --------------------------- use-after-donate ---------------------------- #

def _dotted(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _donate_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """donate_argnums of a ``jit`` call as the source spells it
    (``jax.jit``, ``jit``), or None when absent."""
    func = _dotted(call.func)
    if func is None or func.split(".")[-1] != "jit":
        return None
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        value = kw.value
        if isinstance(value, ast.Constant) and isinstance(value.value, int):
            return (value.value,)
        if isinstance(value, (ast.Tuple, ast.List)):
            out = []
            for elt in value.elts:
                if isinstance(elt, ast.Constant) and isinstance(
                    elt.value, int
                ):
                    out.append(elt.value)
            return tuple(out)
    return None


class _DonatingCallables(ast.NodeVisitor):
    """Discover `<name> = jax.jit(fn, donate_argnums=...)` bindings; the
    bound name (attribute or local) is a donating callable."""

    def __init__(self) -> None:
        self.callables: Dict[str, Tuple[int, ...]] = {}

    def visit_Assign(self, node: ast.Assign) -> None:
        if isinstance(node.value, ast.Call):
            positions = _donate_positions(node.value)
            if positions:
                for target in node.targets:
                    name = None
                    if isinstance(target, ast.Attribute):
                        name = target.attr
                    elif isinstance(target, ast.Name):
                        name = target.id
                    if name:
                        self.callables[name] = positions
        self.generic_visit(node)


def _maximal_reads(node: ast.AST) -> List[Tuple[str, ast.AST]]:
    """Maximal dotted-name reads in an expression: `self.state.params`
    yields once, not its sub-chains."""
    reads: List[Tuple[str, ast.AST]] = []

    def walk(n: ast.AST) -> None:
        if isinstance(n, (ast.Attribute, ast.Name)):
            name = _dotted(n)
            if name is not None:
                reads.append((name, n))
                return  # do not descend into the chain's own .value
        for child in ast.iter_child_nodes(n):
            walk(child)

    walk(node)
    return reads


class _UseAfterDonateLinter:
    """Linear, per-function scan: a donating call kills its donated arg
    expressions; a read of a killed expression (or a field of it) before
    a rebinding assignment is a finding."""

    def __init__(
        self, path: str, subject: str, donating: Dict[str, Tuple[int, ...]]
    ) -> None:
        self.path = path
        self.subject = subject
        self.donating = donating
        self.dead: Dict[str, Tuple[int, str]] = {}  # expr -> (line, callee)
        self.findings: List[Finding] = []

    def _flag(self, expr: str, node: ast.AST) -> None:
        line, callee = self.dead[expr if expr in self.dead else next(
            d for d in self.dead
            if expr.startswith(d + ".") or d.startswith(expr + ".")
        )]
        rule = get_rule("use-after-donate")
        self.findings.append(
            Finding(
                rule=rule.id,
                message=(
                    f"`{_dotted(node) or expr}` is read after being donated "
                    f"to `{callee}` (line {line}) — the buffer was freed/"
                    "reused by XLA; rebind the call's result (e.g. "
                    f"`{expr}, ... = self.{callee}({expr}, ...)`) before "
                    "reading it"
                ),
                severity=rule.severity,
                file=self.path,
                line=getattr(node, "lineno", None),
                subject=self.subject,
                engine="donation",
            )
        )

    def _is_dead(self, name: str) -> bool:
        return any(
            name == d or name.startswith(d + ".") or d.startswith(name + ".")
            for d in self.dead
        )

    def _donations_in(self, node: ast.AST):
        """(donated expr, callee, arg node) triples for donating calls
        anywhere inside ``node``."""
        out = []
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            func = _dotted(sub.func)
            if func is None:
                continue
            callee = func.split(".")[-1]
            positions = self.donating.get(callee)
            if not positions:
                continue
            for pos in positions:
                if pos < len(sub.args):
                    expr = _dotted(sub.args[pos])
                    if expr:
                        out.append((expr, callee, sub.args[pos]))
        return out

    def _check_reads(self, node: ast.AST, exclude: Set[int]) -> None:
        for name, read_node in _maximal_reads(node):
            if id(read_node) in exclude:
                continue
            if isinstance(getattr(read_node, "ctx", None), ast.Store):
                continue
            if self._is_dead(name):
                self._flag(name, read_node)

    def _apply_targets(self, targets: Iterable[ast.AST]) -> None:
        for target in targets:
            elts = (
                target.elts
                if isinstance(target, (ast.Tuple, ast.List))
                else [target]
            )
            for elt in elts:
                name = _dotted(elt)
                if name:
                    for d in list(self.dead):
                        if d == name or d.startswith(name + "."):
                            del self.dead[d]

    def _header(self, stmt) -> List[ast.AST]:
        """The expressions a compound statement evaluates BEFORE its body
        — only donations here may kill state ahead of the body scan (a
        donation inside the body applies at its own statement; applying
        it early would flag body reads that precede it)."""
        if isinstance(stmt, (ast.If, ast.While)):
            return [stmt.test]
        if isinstance(stmt, ast.For):
            return [stmt.iter]
        if isinstance(stmt, ast.With):
            return [item.context_expr for item in stmt.items]
        return []

    def scan_block(self, stmts: Sequence[ast.stmt]) -> None:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue  # nested defs have their own donation lifetimes
            # compound statements: handle only the header expressions
            # here, then scan each body in order (shared kill-state — a
            # branch that donates poisons the fall-through, conservatively)
            if isinstance(stmt, (ast.If, ast.While, ast.For, ast.With)):
                donations = []
                for header in self._header(stmt):
                    donations += self._donations_in(header)
                exclude = {id(n) for _, _, n in donations}
                for header in self._header(stmt):
                    self._check_reads(header, exclude)
                self._apply_donations(donations)
                if isinstance(stmt, ast.For):
                    self._apply_targets([stmt.target])
                self.scan_block(stmt.body)
                self.scan_block(getattr(stmt, "orelse", []))
            elif isinstance(stmt, ast.Try):
                self.scan_block(stmt.body)
                for handler in stmt.handlers:
                    self.scan_block(handler.body)
                self.scan_block(stmt.orelse)
                self.scan_block(stmt.finalbody)
            else:
                donations = self._donations_in(stmt)
                exclude = {id(n) for _, _, n in donations}
                self._check_reads(stmt, exclude)
                self._apply_donations(donations)
                if isinstance(stmt, ast.Assign):
                    self._apply_targets(stmt.targets)
                elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
                    self._apply_targets([stmt.target])

    def _apply_donations(self, donations) -> None:
        for expr, callee, node in donations:
            self.dead[expr] = (getattr(node, "lineno", 0), callee)


def check_use_after_donate_source(
    source: str, path: str
) -> Tuple[List[Finding], int]:
    """Lint one module; returns (kept findings, suppressed count)."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError:
        return [], 0  # ast_lint already reports unparseable files
    discovery = _DonatingCallables()
    discovery.visit(tree)
    if not discovery.callables:
        return [], 0
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            linter = _UseAfterDonateLinter(
                path, f"{node.name}()", discovery.callables
            )
            linter.scan_block(node.body)
            findings.extend(linter.findings)
    return filter_suppressed(findings, {path: source.splitlines()})


def lint_paths(paths: Iterable[str]):
    """use-after-donate over Python files / trees; returns a Report."""
    from trlx_tpu.analysis.ast_lint import collect_py_files
    from trlx_tpu.analysis.findings import Report

    files = collect_py_files(paths)
    report = Report()
    for f in files:
        try:
            with open(f, encoding="utf-8") as fh:
                source = fh.read()
        except OSError:
            continue
        found, suppressed = check_use_after_donate_source(source, f)
        report.extend(found)
        report.suppressed += suppressed
    report.covered.append(f"donation:host[{len(files)} files]")
    return report


def audit_all(
    kinds: Optional[Sequence[str]] = None,
    paths: Optional[Sequence[str]] = None,
    programs=None,
):
    """Full donation engine: jaxpr rules over traced programs + the AST
    use-after-donate pass; returns a merged Report."""
    from trlx_tpu.analysis import harness
    from trlx_tpu.analysis.findings import Report

    report = Report()
    sub = audit_traced_programs(
        programs if programs is not None else harness.trace_all(kinds)
    )
    report.extend(sub.findings)
    report.covered += sub.covered
    report.suppressed += sub.suppressed
    default_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    host = lint_paths(paths or [default_root])
    report.extend(host.findings)
    report.covered += host.covered
    report.suppressed += host.suppressed
    return report
