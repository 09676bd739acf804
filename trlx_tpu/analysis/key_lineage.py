"""PRNG key-lineage: dataflow over traced jaxprs + host split-chain walk.

Engine 9 of ``trlx_tpu.analysis``. PPO's statistical correctness rests
on key discipline nothing else checks: key reuse silently *correlates*
rollouts (two draws from one key explore identical trajectories), a
dropped split repeats the "fresh" subkeys on the next call, and a
hard-coded seed pins every run of a sampling path to one trajectory set
— none of which is visible in loss curves. Three rules:

- ``key-reuse`` (jaxpr + host AST): one key consumed by two or more
  random primitives (draw / split / fold_in) without an intervening
  derivation. The jaxpr dataflow tracks key identity through
  ``random_wrap``/``random_unwrap`` (raw uint32[2] chains), call
  boundaries (pjit/remat/custom_*), and ``scan``: a key passed as a
  scan *constant* and consumed in the body is flagged — the body
  reuses it every iteration. ``cond`` branches are exclusive, so
  per-branch consumptions do not add up.
- ``key-discard`` (host AST): a ``jax.random.split`` whose output is
  never consumed, or a split of a persistent chain (``self.rng``)
  that does not rebind the chain — ``_, key = split(self.rng)``
  re-derives the identical key on every call.
- ``fixed-seed`` (host AST): a literal seed at a
  ``PRNGKey``/``jax.random.key``/``default_rng``/``set_seed`` call
  site in training-path code (trainer/pipeline/orchestrator/ops).
  Seeds come from config so runs differ on purpose.

Key-derivation semantics intentionally mirror jax's own: ``split`` and
``fold_in`` outputs are fresh lineages; slicing/indexing a split result
is selection, not reuse.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from trlx_tpu.analysis.findings import Finding, Report, filter_suppressed
from trlx_tpu.analysis.jaxpr_audit import called_jaxpr
from trlx_tpu.analysis.registry import get_rule

# primitives that CONSUME a key's randomness (a second consumption of the
# same lineage is reuse). random_seed mints a key from an int — creation,
# not consumption.
KEY_CONSUMERS = {
    "random_bits",
    "random_split",
    "random_fold_in",
    "random_gamma",
    "threefry2x32",
}

# identity-preserving wrappers: out is the SAME lineage as in
_KEY_IDENTITY = {"random_wrap", "random_unwrap", "convert_element_type"}


def _is_key_aval(aval) -> bool:
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return False
    s = str(dtype)
    return s.startswith("key<") or "prng" in s.lower()


def _is_raw_key_aval(aval) -> bool:
    """uint32[..., 2]: the raw threefry key layout trainers thread."""
    dtype = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", None)
    return (
        dtype is not None
        and str(dtype) == "uint32"
        and shape is not None
        and len(shape) >= 1
        and shape[-1] == 2
    )


@dataclass
class _Site:
    primitive: str
    canonical: int
    label: str
    file: Optional[str]
    line: Optional[int]
    repeats: bool  # a loop-invariant key consumed inside a scan body:
    # the SAME lineage is consumed once per iteration


class _KeyFlow:
    """One program's key-lineage walk."""

    def __init__(self, subject: str, repo_root: str):
        self.subject = subject
        self.repo_root = repo_root
        self._next = 0
        self.labels: Dict[int, str] = {}
        # canonical id -> consumption sites, in program order
        self.consumers: Dict[int, List[_Site]] = {}

    def fresh(self, label: str = "") -> int:
        self._next += 1
        self.labels[self._next] = label
        return self._next

    # -------------------------- the jaxpr walk -------------------------- #

    def run(self, closed_jaxpr, input_paths: Optional[Sequence[str]] = None):
        inner = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
        env: Dict[Any, int] = {}
        for i, v in enumerate(inner.invars):
            if _is_key_aval(v.aval) or _is_raw_key_aval(v.aval):
                label = (
                    input_paths[i]
                    if input_paths and i < len(input_paths)
                    else f"input[{i}]"
                )
                env[v] = self.fresh(label)
        self._walk(inner, env, repeat_ids=set())
        return self

    def _loc(self, eqn) -> Tuple[Optional[str], Optional[int]]:
        from trlx_tpu.analysis.jaxpr_audit import _repo_frame

        frame = _repo_frame(eqn, self.repo_root)
        if frame is None:
            return None, None
        return frame.file_name, frame.start_line

    def _consume(self, eqn, canonical: int, repeats: bool) -> None:
        file, line = self._loc(eqn)
        self.consumers.setdefault(canonical, []).append(
            _Site(
                primitive=eqn.primitive.name,
                canonical=canonical,
                label=self.labels.get(canonical, ""),
                file=file,
                line=line,
                repeats=repeats,
            )
        )

    def _walk(
        self, jaxpr, env: Dict[Any, int], repeat_ids: Set[int]
    ) -> None:
        def canon(v) -> Optional[int]:
            if hasattr(v, "val"):  # Literal
                return None
            return env.get(v)

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name

            if name in KEY_CONSUMERS:
                for v in eqn.invars:
                    c = canon(v)
                    if c is not None:
                        self._consume(eqn, c, repeats=c in repeat_ids)
                # split/fold_in outputs are FRESH lineages
                for out in eqn.outvars:
                    if _is_key_aval(out.aval) or _is_raw_key_aval(out.aval):
                        env[out] = self.fresh(f"derived@{name}")
                continue

            if name in _KEY_IDENTITY:
                src = canon(eqn.invars[0]) if eqn.invars else None
                if src is not None and eqn.outvars:
                    env[eqn.outvars[0]] = src
                continue

            closed = called_jaxpr(eqn)
            if closed is not None:
                sub = getattr(closed, "jaxpr", closed)
                sub_env: Dict[Any, int] = {}
                for outer, inner_v in zip(eqn.invars, sub.invars):
                    c = canon(outer)
                    if c is not None:
                        sub_env[inner_v] = c
                self._walk(sub, sub_env, repeat_ids)
                for outer_out, inner_out in zip(eqn.outvars, sub.outvars):
                    if not hasattr(inner_out, "val"):
                        c = sub_env.get(inner_out)
                        if c is not None:
                            env[outer_out] = c
                continue

            if name == "scan":
                closed = eqn.params.get("jaxpr")
                if closed is not None:
                    sub = getattr(closed, "jaxpr", closed)
                    n_consts = eqn.params.get("num_consts", 0)
                    sub_env = {}
                    # consts are loop-invariant: the SAME lineage enters
                    # every iteration — one consumption in the body
                    # repeats per step (marked via repeat_ids and
                    # upgraded to reuse by findings())
                    body_repeats = set(repeat_ids)
                    for outer, inner_v in zip(
                        eqn.invars[:n_consts], sub.invars[:n_consts]
                    ):
                        c = canon(outer)
                        if c is not None:
                            sub_env[inner_v] = c
                            body_repeats.add(c)
                    # carry/xs keys are per-iteration values: fresh, and
                    # NOT repeating (the carry advances each step)
                    for inner_v in sub.invars[n_consts:]:
                        if _is_key_aval(inner_v.aval) or _is_raw_key_aval(
                            inner_v.aval
                        ):
                            sub_env[inner_v] = self.fresh("scan-carry")
                    self._walk(sub, sub_env, body_repeats)
                continue

            if name == "cond":
                branches = eqn.params.get("branches", ())
                # branches are exclusive: consumptions must not add up
                # across them — each runs against a snapshot, and the
                # heaviest branch's counts are kept
                base = {
                    c: list(sites) for c, sites in self.consumers.items()
                }
                best = base
                best_total = sum(len(s) for s in base.values())
                for closed in branches:
                    sub = getattr(closed, "jaxpr", closed)
                    self.consumers = {
                        c: list(sites) for c, sites in base.items()
                    }
                    sub_env = {}
                    for outer, inner_v in zip(eqn.invars[1:], sub.invars):
                        c = canon(outer)
                        if c is not None:
                            sub_env[inner_v] = c
                    self._walk(sub, sub_env, repeat_ids)
                    total = sum(len(s) for s in self.consumers.values())
                    if total > best_total:
                        best, best_total = self.consumers, total
                self.consumers = best
                continue

            # anything else producing a key-typed output (slice/squeeze/
            # gather of a split result, stacking, ...) is SELECTION of a
            # fresh lineage, not reuse
            for out in eqn.outvars:
                if hasattr(out, "val"):
                    continue
                if _is_key_aval(out.aval) or _is_raw_key_aval(out.aval):
                    env[out] = self.fresh(f"selected@{name}")

    # ----------------------------- findings ----------------------------- #

    def findings(self) -> List[Finding]:
        rule = get_rule("key-reuse")
        out: List[Finding] = []
        for canonical, sites in sorted(self.consumers.items()):
            effective = len(sites) + sum(1 for s in sites if s.repeats)
            if effective < 2:
                continue
            label = self.labels.get(canonical, "") or "key"
            offender = sites[1] if len(sites) > 1 else sites[0]
            ops = ", ".join(
                s.primitive + (" (per scan iteration)" if s.repeats else "")
                for s in sites
            )
            out.append(
                Finding(
                    rule=rule.id,
                    message=(
                        f"key `{label}` is consumed by {len(sites)} random "
                        f"primitive(s) [{ops}] without an intervening "
                        "split/fold_in — draws from one key are perfectly "
                        "correlated; split first and consume the subkeys"
                    ),
                    severity=rule.severity,
                    file=_relpath(offender.file),
                    line=offender.line,
                    subject=self.subject,
                    engine="prng",
                )
            )
        return out


def _relpath(path: Optional[str]) -> Optional[str]:
    if path is None:
        return None
    from trlx_tpu.analysis.jaxpr_audit import default_repo_root

    root = default_repo_root()
    if root in path:
        return path.split(root, 1)[1].lstrip("/")
    return path


def analyze_key_flow(
    closed_jaxpr,
    subject: str = "program",
    input_paths: Optional[Sequence[str]] = None,
) -> List[Finding]:
    """key-reuse findings of one traced program."""
    from trlx_tpu.analysis.jaxpr_audit import default_repo_root

    flow = _KeyFlow(subject, default_repo_root())
    flow.run(closed_jaxpr, input_paths)
    return flow.findings()


# ----------------------------- host AST walk ------------------------------ #

# jax.random draw functions whose first argument consumes a key
_DRAW_FNS = {
    "normal", "uniform", "bits", "categorical", "bernoulli", "gumbel",
    "choice", "permutation", "randint", "truncated_normal", "exponential",
    "laplace", "poisson", "gamma", "beta", "dirichlet", "cauchy",
}

# calls whose literal first argument is a seed
_SEED_FNS = {"PRNGKey", "key", "default_rng", "seed", "set_seed"}

# training-path directories for the fixed-seed rule (tests and the
# analysis harness use fixed seeds deliberately)
_TRAINING_PATH_DIRS = ("trainer", "pipeline", "orchestrator", "ops", "models")
_TRAINING_PATH_FILES = ("api.py",)


def _name_of(node: ast.AST) -> Optional[str]:
    """Textual form of a chain-able reference: `x` or `self.x`."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name):
        return f"{node.value.id}.{node.attr}"
    return None


def _is_split_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    dotted = []
    func = node.func
    while isinstance(func, ast.Attribute):
        dotted.append(func.attr)
        func = func.value
    if isinstance(func, ast.Name):
        dotted.append(func.id)
    dotted.reverse()
    return bool(dotted) and dotted[-1] in ("split", "fold_in") and (
        len(dotted) == 1 or dotted[-2] in ("random",)
    )


class _ChainWalker(ast.NodeVisitor):
    """Ordered statement walk of one host function: split-chain discipline
    and key consumption counting."""

    def __init__(self, path: str, subject: str) -> None:
        self.path = path
        self.subject = subject
        self.findings: List[Finding] = []
        # key name -> number of consumptions since last (re)bind
        self.consumed: Dict[str, int] = {}
        # split-result names never read (candidate discards)
        self.unread_splits: Dict[str, ast.AST] = {}

    def _add(self, rule_id: str, node: ast.AST, message: str) -> None:
        rule = get_rule(rule_id)
        self.findings.append(
            Finding(
                rule=rule.id,
                message=message,
                severity=rule.severity,
                file=self.path,
                line=getattr(node, "lineno", None),
                subject=self.subject,
                engine="prng",
            )
        )

    # ----------------------------- binding ----------------------------- #

    def _bind_targets(self, targets: Sequence[ast.AST]) -> List[str]:
        names: List[str] = []
        for t in targets:
            elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
            for e in elts:
                n = _name_of(e)
                if n:
                    names.append(n)
        return names

    def visit_Assign(self, node: ast.Assign) -> None:
        # reads on the RHS happen before the bind
        self.generic_visit(node)
        bound = self._bind_targets(node.targets)
        for n in bound:
            self.consumed.pop(n, None)
            self.unread_splits.pop(n, None)
        if _is_split_call(node.value) and node.value.args:
            src = _name_of(node.value.args[0])
            for n in bound:
                # locals only: attribute targets (self.rng) are the
                # persistent chain advancing — read by the NEXT call —
                # and `_` is the idiomatic spelled-out discard handled
                # by the chain-advance check below
                if "." not in n and n != "_":
                    self.unread_splits[n] = node
            # splitting a persistent chain must advance it: self.rng
            # (or any *.rng/_rng attribute) has to be among the targets
            if (
                src
                and "." in src
                and src.split(".", 1)[1].lstrip("_") in ("rng", "key")
                and src not in bound
            ):
                self._add(
                    "key-discard",
                    node,
                    f"split of persistent chain `{src}` does not rebind "
                    f"it — the next call replays the same subkeys; write "
                    f"`{src}, key = jax.random.split({src})`",
                )

    def visit_Expr(self, node: ast.Expr) -> None:
        if _is_split_call(node.value):
            self._add(
                "key-discard",
                node,
                "jax.random.split result is discarded — the derived "
                "subkeys are lost and the source chain did not advance",
            )
        self.generic_visit(node)

    # --------------------------- consumption ---------------------------- #

    def _consume(self, name: str, node: ast.AST, how: str) -> None:
        self.consumed[name] = self.consumed.get(name, 0) + 1
        if self.consumed[name] == 2:
            self._add(
                "key-reuse",
                node,
                f"host key `{name}` is consumed twice without a fresh "
                f"split ({how}) — the two draws are perfectly correlated",
            )

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        dotted: List[str] = []
        f = func
        while isinstance(f, ast.Attribute):
            dotted.append(f.attr)
            f = f.value
        if isinstance(f, ast.Name):
            dotted.append(f.id)
        dotted.reverse()
        leaf = dotted[-1] if dotted else None

        if leaf in _DRAW_FNS and len(dotted) >= 2 and dotted[-2] == "random":
            if node.args:
                n = _name_of(node.args[0])
                if n:
                    self._consume(n, node, f"jax.random.{leaf}")
        elif leaf and (leaf.endswith("_jit") or leaf in ("sample",)):
            for arg in node.args:
                n = _name_of(arg)
                if n and (
                    n in self.consumed
                    or n.split(".")[-1] in ("key", "rng", "subkey")
                ):
                    self._consume(n, arg, f"passed to {leaf}()")
        self.generic_visit(node)

    # ANY Load-context read of a split result counts as consumption —
    # subscripts (`keys[0]`), returns, tuple packing, f-strings — not
    # just call arguments; key-discard is only the *never read at all*
    # case (the `visit_Assign` re-add happens after its RHS walk, so a
    # fresh split's own statement cannot clear its entry)
    def visit_Name(self, node: ast.Name) -> None:
        if isinstance(node.ctx, ast.Load):
            self.unread_splits.pop(node.id, None)
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if isinstance(node.ctx, ast.Load):
            n = _name_of(node)
            if n:
                self.unread_splits.pop(n, None)
        self.generic_visit(node)

    def visit_FunctionDef(self, node) -> None:
        return  # nested defs walk under their own classification

    visit_AsyncFunctionDef = visit_FunctionDef


def _is_training_path(path: str) -> bool:
    parts = os.path.normpath(path).split(os.sep)
    if parts[-1] in _TRAINING_PATH_FILES:
        return True
    return any(d in parts for d in _TRAINING_PATH_DIRS)


class _SeedLinter(ast.NodeVisitor):
    """fixed-seed: literal seeds at RNG constructor call sites."""

    def __init__(self, path: str, subject: str) -> None:
        self.path = path
        self.subject = subject
        self.findings: List[Finding] = []

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        leaf = None
        if isinstance(func, ast.Attribute):
            leaf = func.attr
        elif isinstance(func, ast.Name):
            leaf = func.id
        if (
            leaf in _SEED_FNS
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, int)
        ):
            rule = get_rule("fixed-seed")
            self.findings.append(
                Finding(
                    rule=rule.id,
                    message=(
                        f"literal seed {node.args[0].value!r} at "
                        f"{leaf}(...) in training-path code — every run "
                        "replays the same randomness; take the seed from "
                        "train.seed/config"
                    ),
                    severity=rule.severity,
                    file=self.path,
                    line=node.lineno,
                    subject=self.subject,
                    engine="prng",
                )
            )
        self.generic_visit(node)


def lint_key_chains(
    paths: Sequence[str],
) -> Tuple[List[Finding], List[str], int]:
    """Host-side walk: split-chain discipline in untraced functions and
    literal seeds in training-path modules."""
    from trlx_tpu.analysis.ast_lint import (
        _FunctionIndex,
        _ImportAliases,
        _transitively_traced,
        collect_py_files,
    )

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

        file_findings: List[Finding] = []
        for fname in sorted(set(index.defs) - traced):
            for fnode in index.defs.get(fname, ()):
                walker = _ChainWalker(path, f"{fname}()")
                for stmt in fnode.body:
                    walker.visit(stmt)
                for name, node in walker.unread_splits.items():
                    walker._add(
                        "key-discard",
                        node,
                        f"split result `{name}` is never consumed — "
                        "either dead randomness or a chain that was "
                        "meant to advance",
                    )
                file_findings.extend(walker.findings)

        if _is_training_path(path):
            seeds = _SeedLinter(path, os.path.basename(path))
            seeds.visit(tree)
            file_findings.extend(seeds.findings)

        kept, suppressed = filter_suppressed(
            file_findings, {path: source.splitlines()}
        )
        findings.extend(kept)
        n_suppressed += suppressed
    return findings, files, n_suppressed


# ----------------------------- orchestration ------------------------------ #

def analyze_trainers(
    kinds: Optional[Sequence[str]] = None,
    paths: Optional[Sequence[str]] = None,
    programs=None,
) -> Report:
    """The engine entry: key-reuse dataflow over every traced trainer
    program that consumes a key, plus the host chain/seed walk."""
    from trlx_tpu.analysis import harness

    report = Report()
    if programs is None:
        programs = list(harness.trace_all(kinds))
    jaxpr_findings: List[Finding] = []
    for traced in programs:
        flow_findings = analyze_key_flow(
            traced.closed_jaxpr, traced.subject, traced.input_paths
        )
        jaxpr_findings.extend(flow_findings)
        report.covered.append(f"prng:{traced.subject}")
    kept, suppressed = filter_suppressed(jaxpr_findings)
    report.extend(kept)
    report.suppressed += suppressed

    default_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ast_findings, files, ast_suppressed = lint_key_chains(
        paths or [default_root]
    )
    report.extend(ast_findings)
    report.covered.append(f"prng-host:{len(files)} files")
    report.suppressed += ast_suppressed
    return report
