"""Engine 14: host-concurrency race auditor (``--races``).

Two halves behind the PR-1 registry/CLI/suppression stack
(docs/static_analysis.md, "Engine 14"):

**Static half** — a whole-repo thread-entry-point inventory (every
``threading.Thread(target=...)``, registered signal handler, and the
curated cross-thread entry points like ``push_weights`` and the
``TokenStream`` producer/consumer pair), an attribute-level shared-state
map per class, and a lockset walk over host code:

- ``unguarded-shared-write`` (error): an attribute mutated from >= 2
  thread roots with no common lock held on every mutation path;
- ``lock-order-cycle`` (error): inconsistent acquisition order across
  the discovered locks (the ABBA deadlock shape);
- ``signal-unsafe-handler`` (error): a SIGTERM/SIGINT handler doing
  anything beyond an async-signal-safe flag set;
- ``atomicity-split`` (warning): check-then-act on shared state outside
  the lock that guards it.

Classes with a *written single-thread contract* (their docstring states
which thread owns them and why) are allowlisted in
:data:`SINGLE_THREAD_CONTRACTS` — the allowlist is code, so growing it
is a reviewable diff.

**Dynamic half** — a deterministic cooperative scheduler
(:class:`DeterministicScheduler`) that runs the REAL async-writer,
engine drive/harvest + weight-push, and TokenStream produce/consume
paths under N seeded thread interleavings. Production code is
instrumented with ``sched_points.yield_point`` at every lock/queue/
shared-attribute touch; the scheduler serializes execution to exactly
one runnable thread at a time and picks the next one from a seeded RNG,
so every schedule is a pure function of its seed. The invariants the
repo already claims are asserted under every explored schedule:

- zero lost writer rows (PR-3 flush contract),
- no torn ``TokenStream`` close-vs-push handoff (every accepted token
  is consumed, in order),
- ``staleness_window=0`` bitwise parity with zero weight pushes, and
  version-column monotonicity of the stream store under mid-phase
  pushes (PR-11 contract).

The first violating schedule is reported as rule
``schedule-invariant-violation`` with its seed — replay it exactly with
``--races --race-seed <seed>``. ``--plant-race`` seeds a deliberate
unguarded counter through BOTH halves: the lockset walk must name
``unguarded-shared-write`` at the planted file:line and the scheduler
must find (and name) a violating schedule.
"""

from __future__ import annotations

import ast
import collections
import functools
import json
import os
import random
import tempfile
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

from trlx_tpu.analysis.ast_lint import collect_py_files
from trlx_tpu.analysis.findings import (
    Finding,
    Report,
    SEVERITY_ERROR,
    SEVERITY_WARNING,
    filter_suppressed,
)
from trlx_tpu.analysis.registry import ENGINE_CONCURRENCY
from trlx_tpu.utils import sched_points

# ------------------------------------------------------------------ #
# curated concurrency model
# ------------------------------------------------------------------ #

#: classes whose state is intentionally unlocked because exactly one
#: thread owns it — each entry is a WRITTEN contract, reviewed like
#: code. An unlocked shared write inside one of these is not a finding;
#: moving a class off this list (because a second thread now touches
#: it) makes the engine light up, which is the point.
SINGLE_THREAD_CONTRACTS: Dict[str, str] = {
    # drive-thread confined: every counter is mutated by the thread
    # running drive()/the serving pump; absorbers read at phase
    # boundaries after drive() returned on that same thread
    # (inference/engine.py, EngineStats docstring).
    "EngineStats": "drive/pump-thread confined; read at phase boundaries",
    # the routing table is mutated only by the serving loop (attach at
    # submit, close/pop at harvest); cross-thread traffic goes through
    # the per-stream lock inside TokenStream (serving/streaming.py).
    "StreamRouter": "serving-loop confined; TokenStream carries the lock",
    # rank-0/main-thread metrics registry: gauges are set and absorbed
    # from the trainer's host loop (telemetry contract).
    "MetricsRegistry": "main-thread metrics registry (rank-0 host loop)",
    # the scheduler itself: its mutable maps are guarded by _cv's lock;
    # scheduled threads only touch them inside _cv (this module).
    "DeterministicScheduler": "all state guarded by the _cv condition",
}

#: methods known to be entered from a thread other than the owning
#: object's main/drive thread — the engine cannot discover these from
#: Thread(target=...) because the caller lives in ANOTHER repo layer
#: (the learner loop, a serving driver, a consumer iterator).
CROSS_THREAD_ENTRYPOINTS: Dict[str, Dict[str, str]] = {
    # PipelineRL-style in-flight update: the learner thread stages
    # weights and polls staleness while the drive thread decodes
    "ContinuousBatchingEngine": {
        "push_weights": "learner",
        "min_inflight_version": "learner",
    },
    # driver-thread + consumer-thread deployment (streaming.py docstring)
    "TokenStream": {
        "push": "producer",
        "close": "producer",
        "__next__": "consumer",
        "drain": "consumer",
    },
}

#: attribute names that look like locks when assigned from these calls
_LOCK_FACTORIES = {"Lock", "RLock", "Condition", "Semaphore",
                   "BoundedSemaphore"}

#: method names on an attribute that mutate the underlying container
_MUTATOR_METHODS = {
    "append", "appendleft", "add", "extend", "insert", "pop", "popleft",
    "remove", "discard", "clear", "update", "setdefault", "put",
    "put_nowait",
}


# ------------------------------------------------------------------ #
# static half
# ------------------------------------------------------------------ #


def _dotted(node: ast.AST) -> str:
    """'a.b.c' for Name/Attribute chains, '' otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _self_attr(node: ast.AST) -> Optional[str]:
    """'x' when node is exactly ``self.x``."""
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


@dataclass
class _Access:
    """One write/read of ``self.<attr>`` inside a method."""

    attr: str
    line: int
    method: str
    held: frozenset  # lock attrs held at this point
    kind: str  # "write" | "read"


@dataclass
class _ClassInfo:
    name: str
    file: str
    line: int
    methods: Dict[str, ast.FunctionDef] = field(default_factory=dict)
    lock_attrs: Set[str] = field(default_factory=set)
    writes: List[_Access] = field(default_factory=list)
    # method -> set of intra-class methods it calls
    calls: Dict[str, Set[str]] = field(default_factory=dict)
    # (caller, callee, locks held at the call site)
    call_edges: List[Tuple[str, str, frozenset]] = field(
        default_factory=list
    )
    # thread roots discovered in this class: method -> root label
    roots: Dict[str, str] = field(default_factory=dict)
    # thread targets spawned more than once (a loop, or two creation
    # sites): the method races against ITSELF
    multi_spawn: Set[str] = field(default_factory=set)
    # (held_lock, acquired_lock, line) nested-acquisition edges
    lock_edges: List[Tuple[str, str, int]] = field(default_factory=list)
    # candidate atomicity splits: (line, attr, acting_line)
    splits: List[Tuple[int, str, str]] = field(default_factory=list)


class _MethodWalker(ast.NodeVisitor):
    """Walk one method body propagating the held-lock set. Intra-class
    calls are recorded for the reachability pass; the held set is
    propagated into callees by :func:`_propagate_locksets`."""

    def __init__(self, info: _ClassInfo, method: str):
        self.info = info
        self.method = method
        self.held: frozenset = frozenset()
        self._loop_depth = 0

    def visit_For(self, node: ast.For) -> None:
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    def visit_While(self, node: ast.While) -> None:
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    # -- lock acquisition -------------------------------------------- #

    def _acquired_lock(self, item: ast.withitem) -> Optional[str]:
        ctx = item.context_expr
        # with self._lock:
        attr = _self_attr(ctx)
        if attr is not None and attr in self.info.lock_attrs:
            return attr
        # with sched_points.guard(self._lock, "tag"):
        if isinstance(ctx, ast.Call) and _dotted(ctx.func).endswith("guard"):
            if ctx.args:
                attr = _self_attr(ctx.args[0])
                if attr is not None and attr in self.info.lock_attrs:
                    return attr
        return None

    def visit_With(self, node: ast.With) -> None:
        acquired = [
            a for a in (self._acquired_lock(i) for i in node.items)
            if a is not None
        ]
        for a in acquired:
            for h in self.held:
                self.info.lock_edges.append((h, a, node.lineno))
        prev = self.held
        self.held = self.held | frozenset(acquired)
        for item in node.items:
            self.visit(item.context_expr)
        for stmt in node.body:
            self.visit(stmt)
        self.held = prev

    # -- writes ------------------------------------------------------- #

    def _record_write(self, attr: str, line: int) -> None:
        self.info.writes.append(
            _Access(attr, line, self.method, self.held, "write")
        )

    def visit_Assign(self, node: ast.Assign) -> None:
        for tgt in node.targets:
            for sub in ast.walk(tgt):
                attr = _self_attr(sub)
                if attr is not None:
                    self._record_write(attr, node.lineno)
        self.visit(node.value)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        attr = _self_attr(node.target)
        if attr is not None:
            self._record_write(attr, node.lineno)
        self.visit(node.value)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            attr = _self_attr(node.target)
            if attr is not None:
                self._record_write(attr, node.lineno)
            self.visit(node.value)

    def visit_Call(self, node: ast.Call) -> None:
        # self._buf.append(x): a mutation of self._buf
        if isinstance(node.func, ast.Attribute):
            recv = _self_attr(node.func.value)
            if recv is not None and node.func.attr in _MUTATOR_METHODS:
                self._record_write(recv, node.lineno)
            # self.helper(...): intra-class call edge
            if (
                recv is None
                and _self_attr(node.func) is not None
            ):
                self.info.calls.setdefault(self.method, set()).add(
                    node.func.attr
                )
                self.info.call_edges.append(
                    (self.method, node.func.attr, self.held)
                )
        # threading.Thread(target=self._run, ...)
        if _dotted(node.func).endswith("Thread"):
            for kw in node.keywords:
                if kw.arg == "target":
                    tgt = _self_attr(kw.value)
                    if tgt is not None:
                        if tgt in self.info.roots or self._loop_depth:
                            # spawned twice (or in a loop): the target
                            # method races against itself
                            self.info.multi_spawn.add(tgt)
                        self.info.roots[tgt] = f"thread:{tgt}"
        self.generic_visit(node)

    # -- check-then-act ------------------------------------------------ #

    def visit_If(self, node: ast.If) -> None:
        self._scan_split(node)
        self.visit(node.test)
        for stmt in node.body:
            self.visit(stmt)
        for stmt in node.orelse:
            self.visit(stmt)

    def _scan_split(self, node: ast.If) -> None:
        # intra-class: test reads self.X outside any lock, body acts on
        # class state (a write or an intra-class call) — resolved
        # against the guarded-attribute map in a later pass
        if self.held:
            return
        tested = sorted({
            a for sub in ast.walk(node.test)
            if (a := _self_attr(sub)) is not None
        })
        if not tested:
            return
        acts = False
        for stmt in node.body:
            for sub in ast.walk(stmt):
                if isinstance(sub, (ast.Assign, ast.AugAssign)):
                    tgts = (
                        sub.targets
                        if isinstance(sub, ast.Assign)
                        else [sub.target]
                    )
                    if any(
                        _self_attr(t2) is not None
                        for t in tgts for t2 in ast.walk(t)
                    ):
                        acts = True
                if isinstance(sub, ast.Call) and isinstance(
                    sub.func, ast.Attribute
                ):
                    if _self_attr(sub.func) is not None or (
                        _self_attr(sub.func.value) is not None
                        and sub.func.attr in _MUTATOR_METHODS
                    ):
                        acts = True
        if acts:
            for attr in tested:
                self.info.splits.append((node.lineno, attr, self.method))


def _collect_class(node: ast.ClassDef, path: str) -> _ClassInfo:
    info = _ClassInfo(node.name, path, node.lineno)
    for item in node.body:
        if isinstance(item, ast.FunctionDef):
            info.methods[item.name] = item
    # pass 1: lock attributes (any method may create one, __init__ usual)
    for m in info.methods.values():
        for sub in ast.walk(m):
            if isinstance(sub, ast.Assign) and isinstance(
                sub.value, ast.Call
            ):
                callee = _dotted(sub.value.func)
                if callee.split(".")[-1] in _LOCK_FACTORIES:
                    for tgt in sub.targets:
                        attr = _self_attr(tgt)
                        if attr is not None:
                            info.lock_attrs.add(attr)
    # pass 2: per-method lockset walk
    for name, m in info.methods.items():
        walker = _MethodWalker(info, name)
        for stmt in m.body:
            walker.visit(stmt)
    # curated cross-thread entry points
    for meth, label in CROSS_THREAD_ENTRYPOINTS.get(node.name, {}).items():
        if meth in info.methods:
            info.roots[meth] = label
    return info


def _find_signal_handlers(
    tree: ast.Module, path: str
) -> List[Tuple[str, Optional[str], int]]:
    """(handler_name, class_name, line) for every ``signal.signal(sig,
    h)`` registration whose handler is resolvable (``self.m`` or a
    plain name)."""
    out: List[Tuple[str, Optional[str], int]] = []

    def scan(node: ast.AST, cls: Optional[str]) -> None:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) and _dotted(sub.func) in (
                "signal.signal", "signal"
            ):
                if len(sub.args) >= 2:
                    h = sub.args[1]
                    attr = _self_attr(h)
                    if attr is not None:
                        out.append((attr, cls, sub.lineno))
                    elif isinstance(h, ast.Name):
                        out.append((h.id, None, sub.lineno))

    for item in tree.body:
        if isinstance(item, ast.ClassDef):
            scan(item, item.name)
        else:
            scan(item, None)
    return out


def _handler_violations(fn: ast.FunctionDef) -> List[Tuple[int, str]]:
    """Lines where a registered handler exceeds the async-signal-safe
    contract: anything beyond plain flag assignments / pass / docstring
    / bare return."""
    bad: List[Tuple[int, str]] = []
    body = list(fn.body)
    if (
        body
        and isinstance(body[0], ast.Expr)
        and isinstance(body[0].value, ast.Constant)
        and isinstance(body[0].value.value, str)
    ):
        body = body[1:]  # docstring
    for stmt in body:
        if isinstance(stmt, ast.Pass):
            continue
        if isinstance(stmt, ast.Return) and stmt.value is None:
            continue
        if isinstance(stmt, ast.Assign):
            simple_target = all(
                _self_attr(t) is not None or isinstance(t, ast.Name)
                for t in stmt.targets
            )
            simple_value = isinstance(
                stmt.value, (ast.Name, ast.Constant, ast.Attribute)
            )
            if simple_target and simple_value:
                continue
            bad.append((stmt.lineno, "non-trivial assignment"))
            continue
        kind = type(stmt).__name__
        desc = kind
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Call):
            desc = f"call to {_dotted(stmt.value.func) or 'expression'}()"
        elif isinstance(stmt, ast.If):
            desc = "branch (handlers must not branch on shared state)"
        bad.append((stmt.lineno, desc))
    return bad


def _internal_only(info: _ClassInfo) -> Set[str]:
    """Underscore-private methods only ever entered through an
    intra-class call (no explicit thread/signal/curated root): they run
    on their callers' threads and inherit their callers' locks."""
    called: Set[str] = set()
    for caller, callee, _held in info.call_edges:
        if caller != "__init__":
            called.add(callee)
    return {
        m for m in info.methods
        if m.startswith("_")
        and m != "__init__"
        and m not in info.roots
        and m in called
    }


def _inherited_held(info: _ClassInfo) -> Dict[str, frozenset]:
    """Locks guaranteed held on ENTRY to each internal-only method: the
    intersection over every call site of (site's held set | the
    caller's own inherited set), to a fixed point."""
    internal = _internal_only(info)
    edges_in: Dict[str, List[Tuple[str, frozenset]]] = (
        collections.defaultdict(list)
    )
    for caller, callee, held in info.call_edges:
        if caller != "__init__":
            edges_in[callee].append((caller, held))
    inherited: Dict[str, frozenset] = {
        m: frozenset() for m in info.methods
    }
    changed = True
    while changed:
        changed = False
        for m in internal:
            sets = [
                held | inherited.get(caller, frozenset())
                for caller, held in edges_in[m]
            ]
            new = sets[0]
            for s in sets[1:]:
                new = new & s
            if new != inherited[m]:
                inherited[m] = frozenset(new)
                changed = True
    return inherited


def _propagate_roots(info: _ClassInfo) -> Dict[str, Set[str]]:
    """Per-method set of thread roots that can reach it intra-class.
    Methods without an explicit root are entered from 'main' — except
    internal-only helpers, which run on their callers' threads;
    discovered thread/signal/curated targets carry their own root and
    are NOT also counted as main entries."""
    method_roots: Dict[str, Set[str]] = {
        m: set() for m in info.methods
    }
    internal = _internal_only(info)

    def reach(entry: str, label: str) -> None:
        seen: Set[str] = set()
        stack = [entry]
        while stack:
            m = stack.pop()
            if m in seen or m not in info.methods:
                continue
            seen.add(m)
            method_roots[m].add(label)
            stack.extend(info.calls.get(m, ()))

    for m in info.methods:
        label = info.roots.get(m)
        if label is None and m != "__init__" and m not in internal:
            label = "main"
        if label is not None and m != "__init__":
            reach(m, label)
    for m in info.multi_spawn:
        # a second spawn of the same target is a second root
        reach(m, f"thread:{m}#2")
    return method_roots


def _guarded_attrs(
    info: _ClassInfo, inherited: Dict[str, frozenset]
) -> Dict[str, Set[str]]:
    """attr -> set of locks held at EVERY non-__init__ write (empty set
    when any write is unlocked; attrs only written in __init__ are
    absent). A write's effective held set includes the locks its
    internal-only method inherits from every caller."""
    per_attr: Dict[str, List[frozenset]] = collections.defaultdict(list)
    for acc in info.writes:
        if acc.method == "__init__":
            continue
        per_attr[acc.attr].append(
            acc.held | inherited.get(acc.method, frozenset())
        )
    out: Dict[str, Set[str]] = {}
    for attr, heldsets in per_attr.items():
        common = set(heldsets[0])
        for h in heldsets[1:]:
            common &= h
        out[attr] = common
    return out


def _analyze_class(info: _ClassInfo) -> List[Finding]:
    findings: List[Finding] = []
    method_roots = _propagate_roots(info)
    inherited = _inherited_held(info)
    guarded = _guarded_attrs(info, inherited)
    allowlisted = info.name in SINGLE_THREAD_CONTRACTS

    # ---- unguarded-shared-write ------------------------------------- #
    per_attr: Dict[str, List[_Access]] = collections.defaultdict(list)
    for acc in info.writes:
        if acc.method == "__init__":
            # construction happens-before any thread start
            continue
        per_attr[acc.attr].append(acc)
    for attr, accs in sorted(per_attr.items()):
        if attr in info.lock_attrs:
            continue
        roots: Set[str] = set()
        for acc in accs:
            roots |= method_roots.get(acc.method, set())
        if len(roots) < 2:
            continue
        # async-signal flag exemption: a lock in a handler would
        # deadlock; handler hygiene is signal-unsafe-handler's job
        if roots <= {"main", "signal"}:
            continue
        common = guarded.get(attr, set())
        if common:
            continue
        if allowlisted:
            continue
        first = min(
            (a for a in accs if not a.held), default=accs[0],
            key=lambda a: a.line,
        )
        findings.append(Finding(
            rule="unguarded-shared-write",
            severity=SEVERITY_ERROR,
            message=(
                f"{info.name}.{attr} is mutated from thread roots "
                f"{{{', '.join(sorted(roots))}}} with no common lock on "
                "every write path — guard every mutation with one lock "
                "or add a written single-thread contract"
            ),
            file=info.file,
            line=first.line,
            subject=f"{info.name}.{attr}",
            engine=ENGINE_CONCURRENCY,
        ))

    # ---- atomicity-split -------------------------------------------- #
    multi_rooted = any(
        len(r) >= 2 or (r and r != {"main"})
        for r in method_roots.values()
    )
    if multi_rooted and not allowlisted:
        for line, attr, method in sorted(set(info.splits)):
            locks = guarded.get(attr)
            if not locks:
                continue  # attr is not lock-guarded; nothing to split
            if inherited.get(method):
                continue  # the caller holds the lock around this method
            roots = method_roots.get(method, set())
            if not roots:
                continue
            findings.append(Finding(
                rule="atomicity-split",
                severity=SEVERITY_WARNING,
                message=(
                    f"{info.name}.{method} checks "
                    f"{info.name}.{attr} outside "
                    f"{'/'.join(sorted(locks))} and then acts on class "
                    "state — the check and the act must share one "
                    "critical section"
                ),
                file=info.file,
                line=line,
                subject=f"{info.name}.{method}",
                engine=ENGINE_CONCURRENCY,
            ))
    return findings


def _cross_object_splits(tree: ast.Module, path: str) -> List[Finding]:
    """The exact shape of the PR-13 torn handoff: ``if [not] x.closed:``
    guarding a mutation call on the same object — closed-ness must be
    decided inside the object's own lock, not at the call site."""
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.If):
            continue
        test = node.test
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            test = test.operand
        if not (
            isinstance(test, ast.Attribute) and test.attr == "closed"
        ):
            continue
        recv = _dotted(test.value)
        if not recv or recv == "self":
            continue
        for sub in ast.walk(node):
            if (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Attribute)
                and _dotted(sub.func.value) == recv
                and sub.func.attr in _MUTATOR_METHODS | {"push", "close"}
            ):
                findings.append(Finding(
                    rule="atomicity-split",
                    severity=SEVERITY_WARNING,
                    message=(
                        f"check-then-act on {recv}.closed: the closed "
                        f"check and {recv}.{sub.func.attr}(...) are two "
                        "critical sections — let the object decide "
                        "closed-ness inside its own lock"
                    ),
                    file=path,
                    line=node.lineno,
                    subject=recv,
                    engine=ENGINE_CONCURRENCY,
                ))
                break
    return findings


@dataclass
class StaticRaceResult:
    """Inventory + findings of the lockset walk."""

    files: List[str] = field(default_factory=list)
    classes: List[str] = field(default_factory=list)  # "Class@file"
    thread_roots: List[str] = field(default_factory=list)
    signal_handlers: List[str] = field(default_factory=list)
    locks: List[str] = field(default_factory=list)  # "Class._lock"
    shared_attrs: List[str] = field(default_factory=list)
    allowlisted: List[str] = field(default_factory=list)
    findings: List[Finding] = field(default_factory=list)


def lint_races(paths: Sequence[str]) -> StaticRaceResult:
    """Run the static half over ``paths`` (files or directory trees)."""
    result = StaticRaceResult()
    lock_edges: List[Tuple[str, str, str, int]] = []  # a, b, file, line
    for path in collect_py_files(paths):
        try:
            with open(path, encoding="utf-8") as fh:
                source = fh.read()
            tree = ast.parse(source)
        except (OSError, SyntaxError):
            continue
        result.files.append(path)
        result.findings.extend(_cross_object_splits(tree, path))
        handlers = _find_signal_handlers(tree, path)
        handler_names = {(h, cls) for h, cls, _ in handlers}
        for node in tree.body:
            if not isinstance(node, ast.ClassDef):
                continue
            info = _collect_class(node, path)
            result.classes.append(f"{node.name}@{os.path.basename(path)}")
            for m, label in sorted(info.roots.items()):
                result.thread_roots.append(
                    f"{node.name}.{m} [{label}] ({os.path.basename(path)})"
                )
            for lk in sorted(info.lock_attrs):
                result.locks.append(f"{node.name}.{lk}")
            for a, b, line in info.lock_edges:
                lock_edges.append(
                    (f"{node.name}.{a}", f"{node.name}.{b}", path, line)
                )
            # signal handlers found as self.X registrations
            for hname, cls, _hline in handlers:
                if cls == node.name and hname in info.methods:
                    info.roots.setdefault(hname, "signal")
            if node.name in SINGLE_THREAD_CONTRACTS:
                result.allowlisted.append(
                    f"{node.name}: {SINGLE_THREAD_CONTRACTS[node.name]}"
                )
            method_roots = _propagate_roots(info)
            for acc in info.writes:
                roots: Set[str] = set()
                roots |= method_roots.get(acc.method, set())
                if acc.method != "__init__" and len(roots) >= 2:
                    entry = f"{node.name}.{acc.attr}"
                    if entry not in result.shared_attrs:
                        result.shared_attrs.append(entry)
            result.findings.extend(_analyze_class(info))
            # handler-body hygiene for handlers that are methods here
            for hname, cls, hline in handlers:
                if cls == node.name and hname in info.methods:
                    result.signal_handlers.append(
                        f"{node.name}.{hname} ({os.path.basename(path)})"
                    )
                    for line, what in _handler_violations(
                        info.methods[hname]
                    ):
                        result.findings.append(Finding(
                            rule="signal-unsafe-handler",
                            severity=SEVERITY_ERROR,
                            message=(
                                f"signal handler {node.name}.{hname} "
                                f"does more than set a flag: {what} — "
                                "handlers run between arbitrary "
                                "bytecodes; do the work at the poll "
                                "site"
                            ),
                            file=path,
                            line=line,
                            subject=f"{node.name}.{hname}",
                            engine=ENGINE_CONCURRENCY,
                        ))
        # module-level handlers (plain functions)
        for hname, cls, hline in handlers:
            if cls is None:
                fn = next(
                    (
                        n for n in tree.body
                        if isinstance(n, ast.FunctionDef)
                        and n.name == hname
                    ),
                    None,
                )
                if fn is None:
                    continue
                result.signal_handlers.append(
                    f"{hname} ({os.path.basename(path)})"
                )
                for line, what in _handler_violations(fn):
                    result.findings.append(Finding(
                        rule="signal-unsafe-handler",
                        severity=SEVERITY_ERROR,
                        message=(
                            f"signal handler {hname} does more than "
                            f"set a flag: {what}"
                        ),
                        file=path,
                        line=line,
                        subject=hname,
                        engine=ENGINE_CONCURRENCY,
                    ))
    # ---- lock-order-cycle (global over discovered locks) ------------- #
    graph: Dict[str, Set[str]] = collections.defaultdict(set)
    where: Dict[Tuple[str, str], Tuple[str, int]] = {}
    for a, b, path, line in lock_edges:
        graph[a].add(b)
        where.setdefault((a, b), (path, line))
    for a, b, path, line in lock_edges:
        # a->b recorded; a path from b back to a closes the cycle
        stack, seen = [b], set()
        while stack:
            n = stack.pop()
            if n == a:
                result.findings.append(Finding(
                    rule="lock-order-cycle",
                    severity=SEVERITY_ERROR,
                    message=(
                        f"inconsistent lock order: {a} is acquired "
                        f"while holding {b} elsewhere, and {b} while "
                        f"holding {a} here — pick one global order"
                    ),
                    file=path,
                    line=line,
                    subject=f"{a}<->{b}",
                    engine=ENGINE_CONCURRENCY,
                ))
                break
            if n in seen:
                continue
            seen.add(n)
            stack.extend(graph.get(n, ()))
    return result


# ------------------------------------------------------------------ #
# dynamic half: deterministic cooperative scheduler
# ------------------------------------------------------------------ #


class ScheduleViolation(Exception):
    """An invariant failed under one explored interleaving."""


class ScheduleWedged(Exception):
    """The harness itself stalled (a blocking call the instrumentation
    missed) — a harness bug, not a product finding."""


class DeterministicScheduler:
    """Serialize N threads to one-at-a-time execution with a seeded
    pick at every yield point — every schedule is a pure function of
    its seed, so the first violating one replays exactly.

    Threads created by the scenario use :meth:`spawn`; threads created
    *inside* instrumented product code (the writer daemon) are adopted
    via ``sched_points.announce_thread`` or by name prefix at their
    first yield. All mutable state is guarded by ``_cv``'s lock
    (dogfooding: the engine's own lockset walk analyzes this class).
    """

    #: product-created thread names auto-adopted at their first yield
    ADOPT_PREFIXES = ("rollout-jsonl-writer",)

    def __init__(self, seed: int, max_decisions: int = 50_000):
        self.seed = int(seed)
        self.rng = random.Random(self.seed)
        self.max_decisions = max_decisions
        self._cv = threading.Condition(threading.Lock())
        self._parked: Dict[str, threading.Event] = {}
        self._alive: Dict[str, threading.Thread] = {}
        self._names: Dict[int, str] = {}  # thread ident -> name
        self._errors: List[Tuple[str, BaseException]] = []
        self._started = False
        self._pending: List[Tuple[str, Callable[[], None]]] = []
        self.trace: List[Tuple[str, str]] = []
        self.decisions: List[str] = []
        self.yield_counts: collections.Counter = collections.Counter()

    # -- scenario-facing API ------------------------------------------ #

    def spawn(self, name: str, fn: Callable[[], None]) -> None:
        """Register a scenario thread; it starts parked and only runs
        when picked."""
        self._pending.append((name, fn))

    def run(self) -> None:
        """Drive every spawned/adopted thread to completion under one
        seeded schedule. Re-raises the first scenario-thread exception
        (ScheduleViolation included)."""
        sched_points.install(self._hook, self._announce)
        try:
            threads = []
            for name, fn in self._pending:
                t = threading.Thread(
                    target=self._wrap(name, fn), name=name, daemon=True
                )
                threads.append((name, t))
            with self._cv:
                for name, t in threads:
                    self._alive[name] = t
            for name, t in threads:
                t.start()
                with self._cv:
                    self._names[t.ident] = name
            self._schedule_loop()
            for _name, t in threads:
                t.join(timeout=10)
        finally:
            sched_points.uninstall()
        if self._errors:
            raise self._errors[0][1]

    # -- hooks (run on scheduled threads) ------------------------------ #

    def _wrap(self, name: str, fn: Callable[[], None]):
        def runner() -> None:
            self._park(name, "spawn")
            try:
                fn()
            except BaseException as e:
                with self._cv:
                    self._errors.append((name, e))
            finally:
                with self._cv:
                    self._alive.pop(name, None)
                    self._names.pop(threading.get_ident(), None)
                    self._cv.notify_all()

        return runner

    def _announce(self, thread: threading.Thread) -> None:
        with self._cv:
            if thread.name not in self._alive:
                self._alive[thread.name] = thread
                if thread.ident is not None:
                    self._names[thread.ident] = thread.name
                self._cv.notify_all()

    def _hook(self, tag: str) -> None:
        ident = threading.get_ident()
        with self._cv:
            name = self._names.get(ident)
            if name is None:
                cur = threading.current_thread()
                if cur.name.startswith(self.ADOPT_PREFIXES):
                    name = cur.name
                    self._names[ident] = name
                    self._alive.setdefault(name, cur)
                else:
                    return  # not a scheduled thread (harness, pytest, …)
        self._park(name, tag)

    def _park(self, name: str, tag: str) -> None:
        ev = threading.Event()
        with self._cv:
            self.trace.append((name, tag))
            self.yield_counts[tag] += 1
            self._parked[name] = ev
            self._cv.notify_all()
        if not ev.wait(timeout=30):
            raise ScheduleWedged(
                f"thread {name} never rescheduled after {tag} "
                f"(seed {self.seed})"
            )

    # -- the schedule loop (harness thread) ---------------------------- #

    def _runnable(self) -> Optional[List[str]]:
        """Sorted parked names when every live thread is parked; None
        while some thread is still running. Must hold _cv."""
        for name, t in list(self._alive.items()):
            if not t.is_alive() and name not in self._parked:
                # adopted thread exited without a final yield
                del self._alive[name]
        if not self._alive:
            return []
        if all(
            n in self._parked or not t.is_alive()
            for n, t in self._alive.items()
        ):
            return sorted(self._parked)
        return None

    def _schedule_loop(self) -> None:
        import time

        while True:
            with self._cv:
                candidates = self._runnable()
                # short-poll wait: adopted threads (the writer daemon)
                # exit without notifying, so re-check _runnable — which
                # prunes dead threads — every few ms instead of camping
                # on one long cv.wait
                deadline = time.monotonic() + 30
                while candidates is None:
                    if time.monotonic() > deadline:
                        running = [
                            n for n, t in self._alive.items()
                            if n not in self._parked and t.is_alive()
                        ]
                        raise ScheduleWedged(
                            f"schedule stalled: {running} running but "
                            f"never yielded (seed {self.seed})"
                        )
                    self._cv.wait(timeout=0.02)
                    candidates = self._runnable()
                if not candidates:
                    return  # all threads finished
                pick = candidates[self.rng.randrange(len(candidates))]
                self.decisions.append(pick)
                if len(self.decisions) > self.max_decisions:
                    raise ScheduleWedged(
                        f"schedule exceeded {self.max_decisions} "
                        f"decisions (seed {self.seed}) — livelock?"
                    )
                ev = self._parked.pop(pick)
            ev.set()


# ------------------------------------------------------------------ #
# scenarios: the real code paths under seeded interleavings
# ------------------------------------------------------------------ #


def _scenario_writer(sched: DeterministicScheduler, workdir: str) -> None:
    """Two producers submit to the REAL BackgroundJSONLWriter while its
    daemon thread drains; invariant: zero lost rows, per-producer order
    preserved, no pending error."""
    from trlx_tpu.utils.async_writer import BackgroundJSONLWriter

    path = os.path.join(workdir, f"rows_{sched.seed}.jsonl")
    writer = BackgroundJSONLWriter(maxsize=2)
    rows_per = 3
    done = [False, False]

    def producer(k: int) -> None:
        for i in range(rows_per):
            writer.submit(path, [{"producer": k, "i": i}])
        done[k] = True

    def closer() -> None:
        while not all(done):
            sched_points.yield_point("closer.wait")
        writer.close()

    sched.spawn("producer-a", lambda: producer(0))
    sched.spawn("producer-b", lambda: producer(1))
    sched.spawn("closer", closer)
    sched.run()

    with open(path, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    if len(rows) != 2 * rows_per:
        raise ScheduleViolation(
            f"writer lost rows: {len(rows)}/{2 * rows_per} on disk "
            f"(seed {sched.seed})"
        )
    for k in (0, 1):
        seq = [r["i"] for r in rows if r["producer"] == k]
        if seq != sorted(seq):
            raise ScheduleViolation(
                f"writer reordered producer {k}'s rows: {seq} "
                f"(seed {sched.seed})"
            )


def _scenario_stream(sched: DeterministicScheduler, workdir: str) -> None:
    """Producer pushes then closes a REAL TokenStream while a consumer
    iterates; invariant: every accepted token is consumed, in order —
    the torn close-vs-push handoff loses exactly one."""
    from trlx_tpu.serving.streaming import TokenStream

    stream = TokenStream(1, maxlen=64, pump=lambda: True)
    accepted: List[int] = []
    consumed: List[int] = []
    n_tokens = 6

    def producer() -> None:
        for tok in range(n_tokens):
            if stream.push(tok):
                accepted.append(tok)
        stream.close()

    def consumer() -> None:
        for tok in stream:
            consumed.append(tok)

    sched.spawn("producer", producer)
    sched.spawn("consumer", consumer)
    sched.run()

    if consumed != accepted:
        raise ScheduleViolation(
            f"torn stream handoff: accepted {accepted} but consumed "
            f"{consumed} (seed {sched.seed})"
        )
    if len(accepted) + stream.dropped_after_close != n_tokens:
        raise ScheduleViolation(
            f"stream accounting broke: {len(accepted)} accepted + "
            f"{stream.dropped_after_close} dropped != {n_tokens} "
            f"(seed {sched.seed})"
        )


_ENGINE_ROWS = 8


@functools.lru_cache(maxsize=None)
def _tiny_engine_parts():
    """Trainer-free tiny float32 engine (the test_chunked_prefill
    recipe); compiled once per process — every schedule reuses the
    jitted programs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trlx_tpu.inference.engine import ContinuousBatchingEngine
    from trlx_tpu.models.gpt2 import GPT2Config, init_cache
    from trlx_tpu.models.heads import CausalLMWithValueHead
    from trlx_tpu.ops.sampling import GenerationConfig

    Q, R, vocab, eos = 16, 8, 64, 63
    cfg = GPT2Config(
        vocab_size=vocab, n_positions=64, n_embd=32, n_layer=2,
        n_head=2, dtype="float32",
    )
    model = CausalLMWithValueHead(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]

    def apply_fn(p, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None, last_only=False):
        return model.apply(
            {"params": p}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache,
            cache_index=cache_index, last_only=last_only,
        )

    engine = ContinuousBatchingEngine(
        apply_fn=apply_fn,
        init_cache_fn=functools.partial(init_cache, cfg),
        gen_config=GenerationConfig(
            max_new_tokens=R, min_new_tokens=1, eos_token_id=eos,
            pad_token_id=eos, do_sample=True,
        ),
        query_length=Q,
        vocab_size=vocab,
        num_slots=4,
        admit_width=2,
        harvest_width=2,
        block_size=4,
    )
    rng = np.random.default_rng(7)
    ids = rng.integers(1, 30, (_ENGINE_ROWS, Q)).astype(np.int32)
    mask = np.ones_like(ids)
    return engine, params, ids, mask


def _drive_collect(engine, params, ids, mask, on_group=None):
    """start_phase + submit + drive; returns {row: (tokens, version)}."""
    import jax
    import numpy as np

    engine.start_phase(params, jax.random.PRNGKey(5))
    engine.submit(ids, mask)
    out: Dict[int, Tuple[Any, int]] = {}
    for group in engine.drive(_ENGINE_ROWS):
        toks = np.asarray(group["tokens"])
        for j, r in enumerate(group["rows"]):
            out[r] = (toks[j].tolist(), group["versions"][j])
        if on_group is not None:
            on_group(group)
    return out


@functools.lru_cache(maxsize=None)
def _engine_baseline() -> str:
    """Serial (unscheduled) drive of the tiny engine — the bitwise
    reference every interleaving is compared against."""
    engine, params, ids, mask = _tiny_engine_parts()
    return json.dumps(_drive_collect(engine, params, ids, mask))


def _scenario_engine(sched: DeterministicScheduler, workdir: str) -> None:
    """The REAL drive/harvest loop + learner-thread weight pushes at the
    safe point, landing each harvest group into the REAL stream store.

    Invariants across every interleaving:

    - staleness_window=0: the guard admits ZERO pushes and the harvested
      tokens are bitwise identical to the serial baseline;
    - version-column monotonicity: the stream store's version column is
      non-decreasing in draw order (rows admitted later never carry an
      older behavior version);
    - no torn stream-store rows: every landed row's version column entry
      equals the version the engine harvested it under.
    """
    import numpy as np

    from trlx_tpu.data.ppo_types import PPORolloutBatch
    from trlx_tpu.pipeline.ppo_buffer import PPORolloutBuffer
    from trlx_tpu.trainer.async_rl import guard_allows

    engine, params, ids, mask = _tiny_engine_parts()
    baseline = json.loads(_engine_baseline())
    # split the seeded schedule between the two legs deterministically
    window = 0 if sched.seed % 2 == 0 else 1

    buffer = PPORolloutBuffer()
    buffer.begin_stream(_ENGINE_ROWS)
    landed: List[Tuple[int, int]] = []  # (row, engine version)
    state = {"done": False, "out": None}

    def on_group(group) -> None:
        batch = PPORolloutBatch(
            query_tokens=group["query_tokens"],
            query_mask=group["query_mask"],
            response_tokens=group["tokens"],
            response_mask=group["response_mask"],
            logprobs=group["logprobs"],
            values=group["values"],
            rewards=group["values"] * 0,
        )
        buffer.push(batch, versions=group["versions"])
        landed.extend(zip(group["rows"], group["versions"]))

    def driver() -> None:
        state["out"] = _drive_collect(engine, params, ids, mask, on_group)
        state["done"] = True

    def pusher() -> None:
        learner_version = 0
        while not state["done"]:
            sched_points.yield_point("pusher.poll")
            mv = engine.min_inflight_version()
            if mv is None:
                continue  # nothing in flight to refresh
            if guard_allows(learner_version, mv, window):
                learner_version += 1
                # same params, bumped version: token bits must not move
                engine.push_weights(params, version=learner_version)

    sched.spawn("driver", driver)
    sched.spawn("pusher", pusher)
    sched.run()

    out = state["out"]
    if window == 0:
        if engine.stats.weight_pushes != 0:
            raise ScheduleViolation(
                f"W=0 guard admitted {engine.stats.weight_pushes} "
                f"push(es) (seed {sched.seed})"
            )
        if json.dumps(out) != json.dumps(baseline):
            raise ScheduleViolation(
                f"W=0 parity broke: interleaved tokens differ from the "
                f"serial baseline (seed {sched.seed})"
            )
    else:
        # params are identical across versions, so bits still match
        for row, (toks, _v) in out.items():
            if toks != baseline[str(row)][0]:
                raise ScheduleViolation(
                    f"row {row} tokens changed under same-params pushes "
                    f"(seed {sched.seed})"
                )
    # version-column monotonicity in draw order + no torn rows
    import numpy as np  # noqa: F811

    col = buffer.row_versions(np.arange(len(landed)))
    by_push = [v for _r, v in landed]
    if list(col) != by_push:
        raise ScheduleViolation(
            f"torn stream-store row: version column {list(col)} != "
            f"engine-harvested versions {by_push} (seed {sched.seed})"
        )
    draw_order = sorted(landed)
    versions_by_draw = [v for _r, v in draw_order]
    if versions_by_draw != sorted(versions_by_draw):
        raise ScheduleViolation(
            f"version column not admission-monotone: {versions_by_draw} "
            f"(seed {sched.seed})"
        )


# ---- planted race ------------------------------------------------- #

#: the deliberately racy class --plant-race feeds BOTH halves: no lock,
#: two thread roots, read-modify-write through a yield point
_PLANT_SOURCE = '''\
"""Planted unguarded counter (engine-14 self-check; never imported)."""

import threading


class PlantedCounter:
    """Two worker threads bump `count` with no lock."""

    def __init__(self):
        self.count = 0
        self._threads = []

    def start(self):
        for _ in range(2):
            t = threading.Thread(target=self._work)
            self._threads.append(t)
            t.start()

    def _work(self):
        for _ in range(3):
            tmp = self.count
            self.count = tmp + 1
'''


def _plant_static(workdir: str) -> Tuple[List[Finding], str]:
    path = os.path.join(workdir, "planted_race.py")
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(_PLANT_SOURCE)
    result = lint_races([path])
    return result.findings, path


class _PlantedCounter:
    """Runtime twin of the planted source: the read-modify-write is
    split by a yield point, so the scheduler can interleave the two
    increments and lose an update."""

    def __init__(self) -> None:
        self.count = 0

    def bump(self) -> None:
        sched_points.yield_point("plant.read")
        tmp = self.count
        sched_points.yield_point("plant.write")
        self.count = tmp + 1


def _scenario_plant(sched: DeterministicScheduler, workdir: str) -> None:
    counter = _PlantedCounter()
    per_thread = 3

    def worker() -> None:
        for _ in range(per_thread):
            counter.bump()

    sched.spawn("bump-a", worker)
    sched.spawn("bump-b", worker)
    sched.run()
    if counter.count != 2 * per_thread:
        raise ScheduleViolation(
            f"lost update: count={counter.count} after "
            f"{2 * per_thread} increments (seed {sched.seed})"
        )


# ------------------------------------------------------------------ #
# orchestration
# ------------------------------------------------------------------ #

SCENARIOS: Tuple[Tuple[str, Callable], ...] = (
    ("writer-rows", _scenario_writer),
    ("stream-close", _scenario_stream),
    ("engine-push", _scenario_engine),
)


@dataclass
class ScenarioResult:
    name: str
    schedules: int
    passed: bool
    violating_seed: Optional[int] = None
    violation: str = ""
    decisions: int = 0
    yield_tags: Dict[str, int] = field(default_factory=dict)
    trace_tail: List[str] = field(default_factory=list)


@dataclass
class RaceAuditResult:
    static: StaticRaceResult
    scenarios: List[ScenarioResult] = field(default_factory=list)
    schedules: int = 0
    seed_base: int = 0
    planted: bool = False


def _run_one_schedule(
    name: str, fn: Callable, seed: int, workdir: str
) -> Tuple[Optional[ScheduleViolation], DeterministicScheduler]:
    sched = DeterministicScheduler(seed)
    try:
        fn(sched, workdir)
        return None, sched
    except ScheduleViolation as v:
        return v, sched


def run_scenario(
    name: str,
    schedules: int,
    seed_base: int = 0,
    workdir: Optional[str] = None,
    fn: Optional[Callable] = None,
) -> ScenarioResult:
    """Explore ``schedules`` seeded interleavings of one scenario; stop
    at the first violation (its seed replays it exactly)."""
    if fn is None:
        fn = dict(SCENARIOS)[name]
    own_tmp = workdir is None
    tmp = workdir or tempfile.mkdtemp(prefix="race_audit_")
    result = ScenarioResult(name=name, schedules=0, passed=True)
    tags: collections.Counter = collections.Counter()
    try:
        for i in range(schedules):
            seed = seed_base + i
            violation, sched = _run_one_schedule(name, fn, seed, tmp)
            result.schedules += 1
            result.decisions += len(sched.decisions)
            tags.update(sched.yield_counts)
            if violation is not None:
                result.passed = False
                result.violating_seed = seed
                result.violation = str(violation)
                result.trace_tail = [
                    f"{t}:{tag}" for t, tag in sched.trace[-12:]
                ]
                break
    finally:
        result.yield_tags = dict(sorted(tags.items()))
        if own_tmp:
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)
    return result


def audit_races(
    paths: Optional[Sequence[str]] = None,
    schedules: int = 6,
    plant: bool = False,
    seed: Optional[int] = None,
    scenarios: Optional[Sequence[str]] = None,
) -> Tuple[Report, RaceAuditResult]:
    """Run engine 14: the lockset walk, then the interleaving sweep.

    :param schedules: seeded interleavings explored per scenario.
    :param plant: seed the deliberate unguarded counter through BOTH
        halves (self-check; exit must be 1).
    :param seed: replay exactly this one seed per scenario instead of
        the 0..schedules-1 sweep.
    """
    default_root = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    static = lint_races(list(paths) if paths else [default_root])
    report = Report()
    result = RaceAuditResult(
        static=static,
        schedules=1 if seed is not None else schedules,
        seed_base=seed if seed is not None else 0,
        planted=plant,
    )

    with tempfile.TemporaryDirectory(prefix="race_audit_") as tmp:
        if plant:
            planted_findings, planted_path = _plant_static(tmp)
            static.findings.extend(planted_findings)
            static.files.append(planted_path)

        wanted = list(SCENARIOS)
        if plant:
            wanted.append(("planted-counter", _scenario_plant))
        if scenarios:
            keep = set(scenarios)
            wanted = [(n, f) for n, f in wanted if n in keep]

        for name, fn in wanted:
            if seed is not None:
                sr = run_scenario(
                    name, 1, seed_base=seed, workdir=tmp, fn=fn
                )
            elif name == "planted-counter":
                # the self-check must FIND a violating schedule: widen
                # the sweep until one loses an update (deterministic —
                # the seed sequence is fixed)
                sr = run_scenario(
                    name, max(schedules, 64), workdir=tmp, fn=fn
                )
                if sr.passed:
                    sr.passed = False
                    sr.violation = (
                        "planted race never violated — scheduler is not "
                        "interleaving (harness bug)"
                    )
            else:
                sr = run_scenario(name, schedules, workdir=tmp, fn=fn)
            result.scenarios.append(sr)
            if not sr.passed:
                static.findings.append(Finding(
                    rule="schedule-invariant-violation",
                    severity=SEVERITY_ERROR,
                    message=(
                        f"scenario {sr.name}: {sr.violation or 'failed'}"
                        + (
                            f" — replay with --races --race-seed "
                            f"{sr.violating_seed}"
                            if sr.violating_seed is not None
                            else ""
                        )
                    ),
                    file="trlx_tpu/analysis/concurrency.py",
                    line=1,
                    subject=f"schedule:{sr.name}",
                    engine=ENGINE_CONCURRENCY,
                ))

    kept, n_suppressed = filter_suppressed(static.findings)
    report.extend(kept)
    report.suppressed += n_suppressed
    # coverage: every analyzed file, class, lock, root, shared attr and
    # every explored (scenario, seed) schedule is a subject
    report.covered += [f"file:{os.path.basename(f)}" for f in static.files]
    report.covered += [f"class:{c}" for c in static.classes]
    report.covered += [f"root:{r}" for r in static.thread_roots]
    report.covered += [f"lock:{lk}" for lk in static.locks]
    report.covered += [f"shared:{s}" for s in static.shared_attrs]
    report.covered += [f"handler:{h}" for h in static.signal_handlers]
    for sr in result.scenarios:
        base = result.seed_base
        report.covered += [
            f"schedule:{sr.name}[seed={base + i}]"
            for i in range(sr.schedules)
        ]
    return report, result


def format_races_text(result: RaceAuditResult) -> str:
    s = result.static
    lines = [
        "host-concurrency race audit (engine 14)",
        f"  static: {len(s.files)} files, {len(s.classes)} classes, "
        f"{len(s.locks)} locks, {len(s.thread_roots)} thread roots, "
        f"{len(s.signal_handlers)} signal handlers, "
        f"{len(s.shared_attrs)} shared attrs",
    ]
    if s.allowlisted:
        lines.append("  single-thread contracts:")
        for entry in s.allowlisted:
            lines.append(f"    - {entry}")
    lines.append(
        f"  dynamic: {result.schedules} schedule(s)/scenario"
        + (" [planted]" if result.planted else "")
    )
    for sr in result.scenarios:
        status = "ok" if sr.passed else "VIOLATION"
        lines.append(
            f"    {sr.name:16} {status}  schedules={sr.schedules} "
            f"decisions={sr.decisions} "
            f"yield-tags={len(sr.yield_tags)}"
        )
        if not sr.passed:
            lines.append(f"      {sr.violation}")
            if sr.violating_seed is not None:
                lines.append(
                    f"      replay: python -m trlx_tpu.analysis --races "
                    f"--race-seed {sr.violating_seed}"
                )
            for t in sr.trace_tail:
                lines.append(f"        {t}")
    return "\n".join(lines)
