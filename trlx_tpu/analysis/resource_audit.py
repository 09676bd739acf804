"""Resource audit: static HBM / collective / FLOP budgets per program.

Engine 6 of ``trlx_tpu.analysis``. Nothing else in the stack says
*statically* how much memory, interconnect traffic, or compute a jitted
program needs — regressions surface as OOMs or slow benches on real
hardware. This engine derives three numbers from every traced jaxpr
(recursing pjit / scan / cond / remat sub-jaxprs) and gates them against
a committed contract file, ``analysis/budgets.json``:

- **peak live HBM bytes** (per device): a liveness walk over the program.
  Non-donated inputs are pinned for the whole program (the caller owns
  them); donated inputs die at their last use — donation IS in-place
  reuse, so a donating step's peak excludes the double-buffer. Input
  bytes divide by their sharding divisor (total / per-device shard
  elements, from the trainer's declared ``in_shardings``); divisors
  propagate through shape-preserving eqns, everything else is counted
  replicated (a deterministic upper bound).
- **collective cost model**: per-(primitive, mesh axes) counts and bytes
  moved per device, with standard ring factors over the operand bytes —
  psum ``2(n-1)/n``, all_gather ``(n-1)×`` (its operand is the
  pre-gather shard), reduce_scatter/all_to_all ``(n-1)/n``, ppermute
  ``1`` hop — where ``n`` is the product of the named axes' sizes.
  Collectives inside ``scan`` bodies multiply by the trip count.
- **FLOP estimate**: ``dot_general`` / ``conv_general_dilated`` exact
  MAC counting (2 FLOPs/MAC), scan bodies multiplied by length, cond
  branches at the max.

The numbers are *contracts, not measurements*: deterministic for a given
(config, mesh, jax version), monotone under buffer growth, and cheap
(tracing only — no compilation). ``--update-budgets`` regenerates the
lockfile; CI fails on unexplained growth (rules ``hbm-over-budget``,
``collective-bytes-regression``), turning perf/memory regressions into
reviewable diffs of ``budgets.json``.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass, field
from typing import (
    Any, Dict, Iterator, List, Optional, Sequence, Set, Tuple,
)

from trlx_tpu.analysis.findings import Finding, Report
from trlx_tpu.analysis.jaxpr_audit import is_jit_eqn
from trlx_tpu.analysis.registry import get_rule

BUDGETS_SCHEMA_VERSION = 1
DEFAULT_TOLERANCE_PCT = 5.0

# collectives the cost model prices; axis_index moves no payload
COSTED_COLLECTIVES = {
    "psum", "psum2", "pmax", "pmin", "psum_invariant", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter", "ppermute",
}


def default_budgets_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "budgets.json")


# ------------------------------- bytes ---------------------------------- #

def _aval_bytes(aval, divisor: int = 1) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = math.prod(int(s) for s in shape) if shape else 1
    return (n * dtype.itemsize) // max(1, divisor)


def _is_literal(v) -> bool:
    return hasattr(v, "val")  # jax.core.Literal


def _is_drop(v) -> bool:
    return type(v).__name__ == "DropVar"


def _sub_jaxprs_of(eqn) -> Iterator[Any]:
    from trlx_tpu.analysis.jaxpr_audit import _sub_jaxprs

    for sub in _sub_jaxprs(eqn):
        yield getattr(sub, "jaxpr", sub)  # open a ClosedJaxpr


# --------------------------- peak-HBM liveness --------------------------- #

def peak_live_bytes(
    jaxpr,
    input_divisors: Optional[Sequence[int]] = None,
    donated: Optional[Sequence[bool]] = None,
) -> int:
    """Peak simultaneously-live bytes of one (open) jaxpr.

    Liveness: a value is born when its eqn executes and dies after its
    last consumer. Non-donated inputs and program outputs are pinned for
    the whole program (caller-owned / escaping buffers); donated inputs
    die at their last use, which is exactly XLA's in-place reuse. Each
    sub-jaxpr contributes its internal overhead (its own peak beyond its
    boundary values) as a transient at its eqn — parent-level lifetimes
    already cover the boundary.
    """
    eqns = list(jaxpr.eqns)
    div: Dict[Any, int] = {}
    if input_divisors:
        for v, d in zip(jaxpr.invars, input_divisors):
            if d and d > 1:
                div[v] = int(d)

    def vb(v) -> int:
        if _is_literal(v) or _is_drop(v):
            return 0
        return _aval_bytes(v.aval, div.get(v, 1))

    last_use: Dict[Any, int] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not _is_literal(v):
                last_use[v] = i

    end = len(eqns)
    outset = {v for v in jaxpr.outvars if not _is_literal(v)}
    for v in outset:
        last_use[v] = end

    inputs = list(jaxpr.constvars) + list(jaxpr.invars)
    donated_mask = [False] * len(jaxpr.constvars) + list(
        donated if donated is not None else [False] * len(jaxpr.invars)
    )
    donated_mask += [False] * (len(inputs) - len(donated_mask))
    current = 0
    for v, don in zip(inputs, donated_mask):
        current += vb(v)
        if v in outset:
            continue
        if not don:
            last_use[v] = end  # caller keeps the buffer alive throughout
        elif v not in last_use:
            last_use[v] = -1  # unused donated input: reusable immediately
    peak = current
    for v, don in zip(inputs, donated_mask):
        if last_use.get(v) == -1:
            current -= vb(v)

    for i, eqn in enumerate(eqns):
        # propagate sharding divisors through shape-preserving eqns so a
        # cast/elementwise image of a sharded input stays per-device
        if len(eqn.outvars) == 1 and not _is_drop(eqn.outvars[0]):
            out_shape = getattr(eqn.outvars[0].aval, "shape", None)
            best = 1
            for v in eqn.invars:
                if (
                    not _is_literal(v)
                    and v in div
                    and getattr(v.aval, "shape", None) == out_shape
                ):
                    best = max(best, div[v])
            if best > 1:
                div[eqn.outvars[0]] = best

        inner_extra = 0
        for sub in _sub_jaxprs_of(eqn):
            sub_div = None
            if len(sub.invars) == len(eqn.invars):
                sub_div = [
                    1 if _is_literal(v) else div.get(v, 1)
                    for v in eqn.invars
                ]
            sub_peak = peak_live_bytes(
                sub, sub_div, [True] * len(sub.invars)
            )
            boundary = sum(
                _aval_bytes(v.aval, (sub_div or [1] * len(sub.invars))[k])
                for k, v in enumerate(sub.invars)
            ) + sum(
                0 if _is_literal(v) else _aval_bytes(v.aval)
                for v in sub.outvars
            )
            inner_extra = max(inner_extra, max(0, sub_peak - boundary))

        outs = [v for v in eqn.outvars if not _is_drop(v)]
        for v in outs:
            if v not in last_use and v not in outset:
                last_use[v] = i  # produced and never consumed
        current += sum(vb(v) for v in outs)
        peak = max(peak, current + inner_extra)
        released = set()
        for v in list(eqn.invars) + outs:
            if _is_literal(v) or v in released:
                continue
            if last_use.get(v, end) == i:
                current -= vb(v)
                released.add(v)
    return peak


# ----------------------------- FLOP counting ----------------------------- #

def count_flops(jaxpr) -> int:
    """Matmul/conv FLOPs of a jaxpr (2 FLOPs per MAC), scan bodies
    multiplied by trip count, cond branches at the max, while bodies
    counted once (trip count is data-dependent)."""
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            rhs = eqn.invars[1].aval.shape
            batch = math.prod(int(lhs[i]) for i in lb) if lb else 1
            contract = math.prod(int(lhs[i]) for i in lc) if lc else 1
            m = math.prod(
                int(s) for i, s in enumerate(lhs) if i not in set(lb) | set(lc)
            )
            n = math.prod(
                int(s) for i, s in enumerate(rhs) if i not in set(rb) | set(rc)
            )
            total += 2 * batch * m * n * contract
        elif name == "conv_general_dilated":
            out = eqn.outvars[0].aval.shape
            rhs = eqn.invars[1].aval.shape
            groups = int(eqn.params.get("feature_group_count", 1))
            # per output element: one MAC per kernel element of its group
            kernel_macs = math.prod(int(s) for s in rhs) // max(
                1, int(out[1]) if len(out) > 1 else 1
            )
            total += 2 * math.prod(int(s) for s in out) * max(
                1, kernel_macs // max(1, groups)
            )
        elif name == "scan":
            body = eqn.params["jaxpr"]
            total += int(eqn.params.get("length", 1)) * count_flops(
                getattr(body, "jaxpr", body)
            )
        elif name == "cond":
            total += max(
                (
                    count_flops(getattr(b, "jaxpr", b))
                    for b in eqn.params["branches"]
                ),
                default=0,
            )
        else:
            for sub in _sub_jaxprs_of(eqn):
                total += count_flops(sub)
    return total


# --------------------------- collective model ---------------------------- #

def _moved_bytes(prim: str, payload: int, n: int) -> int:
    """Bytes one device moves for a collective over ``n`` participants,
    where ``payload`` is the operand (invar) bytes — standard ring
    algorithms; n == 1 moves nothing. Note the operand-size asymmetry:
    psum/reduce_scatter/all_to_all operate on full-size inputs, so the
    ring factor is fractional, while all_gather's operand is the
    PRE-gather shard — each device moves (n-1) shards to assemble the
    n-shard output."""
    if n <= 1:
        return 0
    if prim in ("psum", "psum2", "pmax", "pmin", "psum_invariant"):
        return int(2 * (n - 1) / n * payload)
    if prim == "all_gather":
        return (n - 1) * payload
    if prim in ("reduce_scatter", "all_to_all"):
        return int((n - 1) / n * payload)
    # ppermute / pbroadcast: one payload hop
    return payload


def collective_costs(
    jaxpr, axis_sizes: Dict[str, int], _mult: int = 1
) -> Dict[str, Dict[str, int]]:
    """Per-(primitive, axes) collective counts and modeled bytes moved,
    recursing sub-jaxprs; scan bodies multiply by trip count."""
    from trlx_tpu.analysis.jaxpr_audit import _axis_names_of

    costs: Dict[str, Dict[str, int]] = {}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COSTED_COLLECTIVES:
            axes = tuple(_axis_names_of(eqn))
            n = math.prod(int(axis_sizes.get(a, 1)) for a in axes) if axes else 1
            payload = sum(
                _aval_bytes(v.aval)
                for v in eqn.invars
                if not _is_literal(v)
            )
            key = f"{name}[{','.join(axes)}]"
            entry = costs.setdefault(key, {"count": 0, "bytes": 0})
            entry["count"] += _mult
            entry["bytes"] += _mult * _moved_bytes(name, payload, n)
            continue
        mult = _mult
        if name == "scan":
            mult = _mult * int(eqn.params.get("length", 1))
        for sub in _sub_jaxprs_of(eqn):
            for key, sub_entry in collective_costs(
                sub, axis_sizes, mult
            ).items():
                entry = costs.setdefault(key, {"count": 0, "bytes": 0})
                entry["count"] += sub_entry["count"]
                entry["bytes"] += sub_entry["bytes"]
    return costs


# ------------------------------ per program ------------------------------ #

@dataclass
class ProgramResources:
    subject: str
    peak_hbm_bytes: int
    input_bytes: int
    donated_bytes: int
    output_bytes: int
    flops: int
    collectives: Dict[str, Dict[str, int]] = field(default_factory=dict)
    # (file, line) of the traced callable's def — budget findings anchor
    # here so `# tpu-lint: disable=hbm-over-budget` on the def line
    # works; not serialized (machine-local paths would churn the report)
    def_site: Optional[Tuple[str, int]] = None

    @property
    def collective_bytes(self) -> int:
        return sum(e["bytes"] for e in self.collectives.values())

    @property
    def collective_count(self) -> int:
        return sum(e["count"] for e in self.collectives.values())

    def to_dict(self) -> Dict:
        return {
            "subject": self.subject,
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "input_bytes": self.input_bytes,
            "donated_bytes": self.donated_bytes,
            "output_bytes": self.output_bytes,
            "flops": self.flops,
            "collective_bytes": self.collective_bytes,
            "collective_count": self.collective_count,
            "collectives": {
                k: dict(self.collectives[k]) for k in sorted(self.collectives)
            },
        }


def analyze_closed_jaxpr(
    closed_jaxpr,
    subject: str,
    axis_sizes: Optional[Dict[str, int]] = None,
    input_divisors: Optional[Sequence[int]] = None,
) -> ProgramResources:
    """Resources of one traced program (``jax.make_jaxpr`` output).

    When the program is a jitted callable, the outer jaxpr holds a single
    ``jit`` eqn: the analysis uses its ``donated_invars`` and recurses its
    body; a bare (un-jitted) jaxpr is analyzed directly, undonated.
    """
    outer = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    axis_sizes = axis_sizes or {}
    target, donated, divisors = outer, None, input_divisors
    pjit_eqns = [e for e in outer.eqns if is_jit_eqn(e)]
    if len(outer.eqns) == 1 and pjit_eqns:
        eqn = pjit_eqns[0]
        target = eqn.params["jaxpr"].jaxpr
        donated = list(eqn.params.get("donated_invars", ()))
        # the outer jaxpr forwards its invars to the pjit 1:1; on an
        # arity mismatch (e.g. hoisted closure consts becoming extra
        # inner invars) the outer divisors do not align — fall back to
        # replicated rather than zip them against the wrong values
        if input_divisors and len(target.invars) == len(input_divisors):
            divisors = list(input_divisors)
        else:
            divisors = None

    divisors = list(divisors or [1] * len(target.invars))
    donated_list = list(donated or [False] * len(target.invars))
    donated_list += [False] * (len(target.invars) - len(donated_list))
    input_bytes = sum(
        _aval_bytes(v.aval, d) for v, d in zip(target.invars, divisors)
    )
    donated_bytes = sum(
        _aval_bytes(v.aval, d)
        for v, d, don in zip(target.invars, divisors, donated_list)
        if don
    )
    output_bytes = sum(
        0 if _is_literal(v) else _aval_bytes(v.aval) for v in target.outvars
    )
    return ProgramResources(
        subject=subject,
        peak_hbm_bytes=peak_live_bytes(target, divisors, donated_list),
        input_bytes=input_bytes,
        donated_bytes=donated_bytes,
        output_bytes=output_bytes,
        flops=count_flops(target),
        collectives=collective_costs(target, axis_sizes),
    )


def analyze_traced_program(traced) -> ProgramResources:
    """Resources of a harness :class:`TracedProgram`."""
    res = analyze_closed_jaxpr(
        traced.closed_jaxpr,
        traced.subject,
        axis_sizes=traced.mesh_shape or {},
        input_divisors=traced.input_divisors,
    )
    res.def_site = traced.def_site
    return res


def trainer_step_resources(trainer, kind: str = "ppo") -> ProgramResources:
    """Static resources of a LIVE trainer's jitted train step — tracing
    only (no compilation): the budget numbers at the real workload shape,
    for ``telemetry/device_metrics.py`` to set beside measured stats."""
    import jax

    from trlx_tpu.analysis import harness
    from trlx_tpu.parallel.mesh import batch_sharding

    state_sds = harness._sds(trainer.state)
    mb = (
        harness._ilql_minibatch_sds(trainer)
        if kind == "ilql"
        else harness._ppo_minibatch_sds(trainer)
    )
    closed = jax.make_jaxpr(trainer._train_step_jit)(state_sds, mb)
    divisors = harness.flat_sharding_divisors(
        (state_sds, mb),
        (trainer.state_shardings, batch_sharding(trainer.mesh)),
    )
    return analyze_closed_jaxpr(
        closed,
        f"{kind}.train_step",
        axis_sizes={k: int(v) for k, v in trainer.mesh.shape.items()},
        input_divisors=divisors,
    )


# ------------------------------- budgets --------------------------------- #

def make_budgets(
    resources: Sequence[ProgramResources],
    mesh: Dict[str, int],
    tolerance_pct: float = DEFAULT_TOLERANCE_PCT,
) -> Dict:
    return {
        "schema_version": BUDGETS_SCHEMA_VERSION,
        "mesh": {k: int(v) for k, v in sorted(mesh.items())},
        "tolerance_pct": tolerance_pct,
        "programs": {
            r.subject: {
                "peak_hbm_bytes": r.peak_hbm_bytes,
                "collective_bytes": r.collective_bytes,
                "collective_count": r.collective_count,
                "flops": r.flops,
            }
            for r in sorted(resources, key=lambda r: r.subject)
        },
    }


def merge_budgets(
    budgets: Dict,
    existing: Dict,
    partial: bool,
    traced_kinds: Set[str],
) -> Dict:
    """Fold a freshly-generated ``budgets`` dict into the ``existing``
    lockfile: the file-level and per-entry ``tolerance_pct`` overrides a
    reviewer committed survive regeneration, a *partial* update (a
    ``--trainers`` subset trace) keeps the untraced kinds' entries
    instead of silently dropping them from the contract, and foreign
    top-level sections owned by OTHER engines (``compile_budgets``,
    engine 8; ``perf_budgets``, engine 10; anything future) pass through
    untouched — a resource relock must never wipe another engine's
    contract out of the shared lockfile."""
    own_keys = {"schema_version", "mesh", "tolerance_pct", "programs"}
    for key, val in existing.items():
        if key not in own_keys:
            budgets[key] = val
    if "tolerance_pct" in existing:
        budgets["tolerance_pct"] = existing["tolerance_pct"]
    old_programs = existing.get("programs", {})
    if partial:
        kept = {
            s: dict(e)
            for s, e in old_programs.items()
            if s.split(".")[0] not in traced_kinds
        }
        kept.update(budgets["programs"])
        budgets["programs"] = {s: kept[s] for s in sorted(kept)}
    for s, entry in budgets["programs"].items():
        old = old_programs.get(s)
        if old and "tolerance_pct" in old and "tolerance_pct" not in entry:
            entry["tolerance_pct"] = old["tolerance_pct"]
    return budgets


def load_budgets(path: str) -> Dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def write_budgets(budgets: Dict, path: str) -> None:
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(budgets, fh, indent=2, sort_keys=True)
        fh.write("\n")


def check_budgets(
    resources: Sequence[ProgramResources],
    budgets: Dict,
    mesh: Optional[Dict[str, int]] = None,
    budgets_path: Optional[str] = None,
) -> List[Finding]:
    """Gate current resources against the committed contract.

    Growth past a program's tolerance (entry-level ``tolerance_pct``
    override, else the file-level default) is a finding; so is a traced
    program with no committed entry, a stale entry for a kind that was
    traced, and a mesh mismatch (the numbers are only comparable on the
    mesh they were locked for).
    """
    hbm_rule = get_rule("hbm-over-budget")
    coll_rule = get_rule("collective-bytes-regression")
    findings: List[Finding] = []
    where = budgets_path or default_budgets_path()

    locked_mesh = budgets.get("mesh")
    if mesh is not None and locked_mesh is not None:
        current = {k: int(v) for k, v in sorted(mesh.items())}
        locked = {k: int(v) for k, v in sorted(locked_mesh.items())}
        if locked != current:
            return [
                Finding(
                    rule=hbm_rule.id,
                    message=(
                        f"budgets in {os.path.basename(where)} were locked "
                        f"for mesh {locked_mesh}, but the audit ran on "
                        f"{current} — the numbers are not comparable; rerun "
                        "with the locked mesh or --update-budgets"
                    ),
                    severity=hbm_rule.severity,
                    subject="budgets",
                    engine="resource",
                )
            ]

    default_tol = float(budgets.get("tolerance_pct", DEFAULT_TOLERANCE_PCT))
    programs = budgets.get("programs", {})
    for r in resources:
        # anchor at the traced callable's def line so inline
        # `# tpu-lint: disable=` directives apply to budget findings too
        file, line = r.def_site or (None, None)
        entry = programs.get(r.subject)
        if entry is None:
            findings.append(
                Finding(
                    rule=hbm_rule.id,
                    message=(
                        f"no committed budget for traced program "
                        f"`{r.subject}` (peak {r.peak_hbm_bytes} B, "
                        f"{r.collective_bytes} collective B) — run "
                        "--update-budgets and review the lockfile diff"
                    ),
                    severity=hbm_rule.severity,
                    file=file,
                    line=line,
                    subject=r.subject,
                    engine="resource",
                )
            )
            continue
        tol = 1.0 + float(entry.get("tolerance_pct", default_tol)) / 100.0
        locked_hbm = int(entry.get("peak_hbm_bytes", 0))
        if r.peak_hbm_bytes > locked_hbm * tol:
            growth = (
                100.0 * (r.peak_hbm_bytes - locked_hbm) / locked_hbm
                if locked_hbm
                else float("inf")
            )
            findings.append(
                Finding(
                    rule=hbm_rule.id,
                    message=(
                        f"static peak HBM of `{r.subject}` grew to "
                        f"{r.peak_hbm_bytes} B per device, "
                        f"{growth:+.1f}% over the committed "
                        f"{locked_hbm} B (tolerance "
                        f"{entry.get('tolerance_pct', default_tol)}%) — if "
                        "intended, regenerate with --update-budgets and "
                        "explain the growth in the lockfile diff"
                    ),
                    severity=hbm_rule.severity,
                    file=file,
                    line=line,
                    subject=r.subject,
                    engine="resource",
                )
            )
        locked_coll = int(entry.get("collective_bytes", 0))
        cur_coll = r.collective_bytes
        over = cur_coll > locked_coll * tol
        if locked_coll == 0:
            over = cur_coll > 0
        if over:
            findings.append(
                Finding(
                    rule=coll_rule.id,
                    message=(
                        f"modeled collective traffic of `{r.subject}` grew "
                        f"to {cur_coll} B/device over "
                        f"{r.collective_count} op(s), past the committed "
                        f"{locked_coll} B — an extra/larger collective is "
                        "a scaling regression on real slices; if intended, "
                        "regenerate with --update-budgets"
                    ),
                    severity=coll_rule.severity,
                    file=file,
                    line=line,
                    subject=r.subject,
                    engine="resource",
                )
            )

    traced_kinds = {r.subject.split(".")[0] for r in resources}
    current_subjects = {r.subject for r in resources}
    for stale in sorted(set(programs) - current_subjects):
        if stale.split(".")[0] in traced_kinds:
            findings.append(
                Finding(
                    rule=hbm_rule.id,
                    message=(
                        f"budget entry `{stale}` no longer matches any "
                        "traced program — prune it with --update-budgets"
                    ),
                    severity="warning",
                    subject=stale,
                    engine="resource",
                )
            )
    return findings


# ----------------------------- orchestration ----------------------------- #

def collect_resources(
    kinds: Optional[Sequence[str]] = None,
    mesh: Optional[Dict[str, int]] = None,
    programs=None,
) -> Tuple[List[ProgramResources], Dict[str, int]]:
    """Trace the trainer programs (or reuse ``programs``) and size them;
    returns (resources, resolved mesh axis sizes)."""
    from trlx_tpu.analysis import harness

    if programs is None:
        programs = list(harness.trace_all(kinds, mesh))
    resources = [analyze_traced_program(t) for t in programs]
    mesh_shape: Dict[str, int] = {}
    for t in programs:
        if t.mesh_shape:
            mesh_shape = dict(t.mesh_shape)
            break
    return resources, mesh_shape


def audit_resources(
    kinds: Optional[Sequence[str]] = None,
    mesh: Optional[Dict[str, int]] = None,
    budgets_path: Optional[str] = None,
    update: bool = False,
    programs=None,
) -> Tuple[Report, List[ProgramResources]]:
    """The ``--resources`` entry point: trace, size, and either regenerate
    the lockfile (``update=True``) or gate against it."""
    from trlx_tpu.analysis.findings import filter_suppressed

    path = budgets_path or default_budgets_path()
    resources, mesh_shape = collect_resources(kinds, mesh, programs)
    report = Report()
    report.covered += [f"resource:{r.subject}" for r in resources]
    report.resources = [r.to_dict() for r in resources]
    if update:
        budgets = make_budgets(resources, mesh_shape)
        try:
            existing = load_budgets(path)
        except (OSError, ValueError):
            existing = None
        if existing is not None:
            partial = kinds is not None
            locked_mesh = existing.get("mesh")
            # lockfile-sourced ints, not device values — normalized
            # OUTSIDE the branch so the host-branch lint can see this
            # condition never reads device state
            locked_norm = (
                {k: int(v) for k, v in sorted(locked_mesh.items())}
                if locked_mesh is not None
                else None
            )
            if (
                partial
                and locked_norm is not None
                and locked_norm != budgets["mesh"]
            ):
                # a subset trace on a different mesh cannot merge: the
                # kept entries would be locked for another topology
                rule = get_rule("hbm-over-budget")
                report.extend([
                    Finding(
                        rule=rule.id,
                        message=(
                            f"refusing --update-budgets: the lockfile is "
                            f"for mesh {locked_mesh} but this --trainers "
                            f"subset traced on {budgets['mesh']} — a "
                            "partial update would mix topologies; rerun "
                            "without --trainers (full relock) or on the "
                            "locked mesh"
                        ),
                        severity=rule.severity,
                        subject="budgets",
                        engine="resource",
                    )
                ])
                return report, resources
            budgets = merge_budgets(
                budgets,
                existing,
                partial,
                {r.subject.split(".")[0] for r in resources},
            )
        write_budgets(budgets, path)
        return report, resources
    try:
        budgets = load_budgets(path)
    except (OSError, ValueError) as e:
        rule = get_rule("hbm-over-budget")
        report.extend([
            Finding(
                rule=rule.id,
                message=(
                    f"cannot load budget contract {path}: {e} — generate "
                    "it with --update-budgets and commit the file"
                ),
                severity=rule.severity,
                subject="budgets",
                engine="resource",
            )
        ])
        return report, resources
    kept, suppressed = filter_suppressed(
        check_budgets(resources, budgets, mesh_shape, path)
    )
    report.extend(kept)
    report.suppressed += suppressed
    return report, resources


def format_resources_text(resources: Sequence[ProgramResources]) -> str:
    lines = [
        f"{'program':28} {'peak HBM/dev':>14} {'collective B':>13} "
        f"{'colls':>6} {'GFLOP':>10}"
    ]
    for r in sorted(resources, key=lambda r: r.subject):
        lines.append(
            f"{r.subject:28} {r.peak_hbm_bytes:>14,} "
            f"{r.collective_bytes:>13,} {r.collective_count:>6} "
            f"{r.flops / 1e9:>10.3f}"
        )
    return "\n".join(lines)
