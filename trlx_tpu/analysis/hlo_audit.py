"""Compiled-HLO lowering audit: gate what XLA emitted against jaxpr intent.

Engine 13 of ``trlx_tpu.analysis``. Every other engine reasons at the
jaxpr level, but the repo's two worst correctness bugs lived *below* it:
XLA's SPMD partitioner mis-lowering an eager sharded ``jnp.concatenate``
into a replica-axis SUM (PR 2 — NaN divergence on fsdp×tp), and the
still-quarantined pp cached-decode ``jnp.stack`` miscompile
(``tools/pp_miscompile_repro.py``). Both are invisible to jaxpr rules by
construction: the jaxpr is *intent*; the optimized post-SPMD module is
what the TPU runs. This engine AOT-lowers and compiles every traced
program from the harness (``jit_fn.lower(*example_args).compile()`` on
the CPU audit mesh, with the trainers' real ``in_shardings``), parses
``compiled.as_text()`` + ``memory_analysis()``, and gates the artifact:

- ``lowering-collective-drift`` (error) — three sub-checks: (a) any
  all-reduce whose metadata attributes to a ``concatenate``/``stack`` op
  (a concat must never lower to a cross-replica reduction — the exact
  PR-2 signature, caught with no lockfile needed); (b) every *explicit*
  jaxpr collective (engine 5's sequence) must survive into the compiled
  module as its HLO counterpart; (c) the per-program collective profile
  (``kind[axes]|dtype`` → count) must match the committed ``hlo_budgets``
  lockfile exactly — an inserted, dropped, or re-axised collective is a
  lowering change that needs human review, not a silent drive-by.
- ``hlo-dtype-upcast`` (warning) — non-scalar f32 tensors minted from
  bf16 inputs by ``convert`` in the optimized module, outside the
  curated allowlist (softmax/layernorm/loss accumulation own their f32).
- ``hlo-memory-drift`` (error) — the compiled buffer-assignment peak
  (temp + args + outputs − donation aliasing) vs the per-program
  ``hlo_budgets`` entry, with engine-7-style tolerance.
- ``spmd-concat-hazard`` (error) — the jaxpr-side tripwire for the PR-2
  class, replacing the ROADMAP "watch for eager multi-operand
  concat/stack of committed-sharded arrays" human obligation: a
  multi-operand ``concatenate`` eqn whose operands taint back to
  committed-sharded program inputs, on a mesh with a spare size>1 axis,
  outside the blessed ``spmd_stack``/``concat_cols`` helpers (which
  build via ``dynamic_update_slice`` and never emit ``concatenate``).

Plus a **known-miscompile registry** (:data:`KNOWN_MISCOMPILES`): the
quarantined lowerings are pinned as *expected-divergence* entries keyed
to the jaxlib versions they were verified broken on. A fixing jaxlib
bump mechanically flips the entry to a stale-quarantine finding telling
the builder which workaround to retire — no human re-running repros
after version bumps. ``--plant-hazard`` is the engine's self-check: it
compiles a seeded eager sharded concat and must trip BOTH
``spmd-concat-hazard`` (at the planted line) and
``lowering-collective-drift`` (on the minted replica-axis all-reduce).
"""

from __future__ import annotations

import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from trlx_tpu.analysis.findings import (
    SEVERITY_WARNING,
    Finding,
    Report,
    filter_suppressed,
)
from trlx_tpu.analysis.registry import get_rule

# Mesh axis order of every repo mesh (parallel/mesh.py::make_mesh builds
# the device ndarray row-major over exactly these axes from the flat
# jax.devices() list) — lets the parser map the flat device ids in HLO
# replica_groups back to named mesh axes.
MESH_AXIS_ORDER = ("dp", "fsdp", "tp", "sp", "pp", "ep")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# HLO collective opcodes audited, with async -start forms folded into
# their sync spelling (-done carries no groups and is skipped).
_COLLECTIVE_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)


# ----------------------------- HLO parsing ------------------------------ #

@dataclass
class HloCollective:
    """One collective instruction of an optimized post-SPMD module."""

    kind: str                      # canonical opcode, e.g. "all-reduce"
    dtype: str                     # element type of the (first) result
    elems: int                     # element count across the result tuple
    bytes: int                     # payload bytes across the result tuple
    groups: Optional[List[List[int]]] = None   # expanded replica_groups
    pairs: Optional[List[Tuple[int, int]]] = None  # collective-permute
    to_apply: str = ""             # reduction computation name, if any
    op_name: str = ""              # metadata op_name (jaxpr provenance)
    source_file: str = ""
    source_line: int = 0

    def axes(self, mesh_shape: Optional[Dict[str, int]]) -> Tuple[str, ...]:
        return infer_collective_axes(self, mesh_shape)


_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\w+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start)?\("
)
_EXPLICIT_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^=]*?\})\}")
_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?"
)
_PAIRS_RE = re.compile(r"source_target_pairs=\{([^}]*(?:\},\{[^}]*)*)\}")
_TO_APPLY_RE = re.compile(r"to_apply=%?([\w.\-]+)")
_METADATA_RE = re.compile(r"metadata=\{([^}]*)\}")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_SOURCE_FILE_RE = re.compile(r'source_file="([^"]*)"')
_SOURCE_LINE_RE = re.compile(r"source_line=(\d+)")


def _parse_shape(shape_text: str) -> Tuple[str, int, int]:
    """(first dtype, total elements, total bytes) of a shape or a tuple
    of shapes, e.g. ``f32[32,32]{1,0}`` or ``(f32[32,32], f32[32])``."""
    dtype, elems, total = "", 0, 0
    for m in _SHAPE_RE.finditer(shape_text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        dtype = dtype or dt
        elems += n
        total += n * _DTYPE_BYTES[dt]
    return dtype, elems, total


def expand_replica_groups(line: str) -> Optional[List[List[int]]]:
    """Expanded replica groups of one HLO instruction line, handling the
    explicit ``{{0,1},{2,3}}`` form and both iota forms
    ``[g,s]<=[dims]`` / ``[g,s]<=[dims]T(perm)``."""
    m = _EXPLICIT_GROUPS_RE.search(line)
    if m:
        return [
            [int(d) for d in grp.split(",") if d.strip()]
            for grp in re.findall(r"\{([^{}]*)\}", m.group(1) + "}")
            if grp.strip()
        ]
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        perm = (
            [int(p) for p in m.group(4).split(",")]
            if m.group(4)
            else list(range(len(dims)))
        )
        total = 1
        for d in dims:
            total *= d
        # iota(total) reshaped to dims, transposed by perm, flattened,
        # then chunked into groups — the HLO IotaReplicaGroupList spec
        import numpy as np

        flat = (
            np.arange(total).reshape(dims).transpose(perm).reshape(-1)
        )
        if n_groups * group_size != total:
            return None
        return flat.reshape(n_groups, group_size).tolist()
    return None


def _parse_pairs(line: str) -> Optional[List[Tuple[int, int]]]:
    m = _PAIRS_RE.search(line)
    if not m:
        return None
    return [
        (int(a), int(b))
        for a, b in re.findall(r"\{(\d+),(\d+)\}", "{" + m.group(1) + "}")
    ]


def parse_hlo_collectives(hlo_text: str) -> List[HloCollective]:
    """All collective instructions of an optimized module, in text order
    (async ``-start`` forms folded; ``-done`` carries no new info)."""
    out: List[HloCollective] = []
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        dtype, elems, nbytes = _parse_shape(m.group(1))
        meta = _METADATA_RE.search(line)
        meta_text = meta.group(1) if meta else ""
        op_name_m = _OP_NAME_RE.search(meta_text)
        src_file_m = _SOURCE_FILE_RE.search(meta_text)
        src_line_m = _SOURCE_LINE_RE.search(meta_text)
        to_apply_m = _TO_APPLY_RE.search(line)
        out.append(
            HloCollective(
                kind=m.group(2),
                dtype=dtype,
                elems=elems,
                bytes=nbytes,
                groups=expand_replica_groups(line),
                pairs=_parse_pairs(line),
                to_apply=to_apply_m.group(1) if to_apply_m else "",
                op_name=op_name_m.group(1) if op_name_m else "",
                source_file=src_file_m.group(1) if src_file_m else "",
                source_line=int(src_line_m.group(1)) if src_line_m else 0,
            )
        )
    return out


def _device_coords(dev: int, sizes: Sequence[int]) -> Tuple[int, ...]:
    coords = []
    for s in reversed(sizes):
        coords.append(dev % s)
        dev //= s
    return tuple(reversed(coords))


def infer_collective_axes(
    c: HloCollective, mesh_shape: Optional[Dict[str, int]]
) -> Tuple[str, ...]:
    """Named mesh axes a collective's groups span (device ids map back
    to mesh coordinates row-major over :data:`MESH_AXIS_ORDER` — how
    ``make_mesh`` lays the flat device list out)."""
    if not mesh_shape:
        return ("?",)
    names = [a for a in MESH_AXIS_ORDER if a in mesh_shape]
    sizes = [int(mesh_shape[a]) for a in names]
    varying: Set[str] = set()
    if c.groups:
        for group in c.groups:
            coords = [_device_coords(d, sizes) for d in group]
            for i, name in enumerate(names):
                if len({co[i] for co in coords}) > 1:
                    varying.add(name)
    elif c.pairs:
        for src, dst in c.pairs:
            a, b = _device_coords(src, sizes), _device_coords(dst, sizes)
            for i, name in enumerate(names):
                if a[i] != b[i]:
                    varying.add(name)
    else:
        # no groups attribute => the collective spans all devices
        varying = {n for n, s in zip(names, sizes) if s > 1}
    if not varying:
        return ("self",)
    return tuple(sorted(varying))


def collective_profile(
    collectives: Sequence[HloCollective],
    mesh_shape: Optional[Dict[str, int]],
) -> Dict[str, int]:
    """Count collectives keyed ``kind[axes]|dtype`` — the locked shape
    of a program's compiled collective schedule. Counts (not sequences):
    XLA reorders freely, but minting, dropping, or re-axising a
    collective changes a key."""
    profile: Dict[str, int] = {}
    for c in collectives:
        key = f"{c.kind}[{','.join(c.axes(mesh_shape))}]|{c.dtype}"
        profile[key] = profile.get(key, 0) + 1
    return profile


# -------------------------- dtype-upcast scan --------------------------- #

# f32 compute legitimately minted from bf16 in the optimized module —
# mirrors jaxpr_audit.PRECISION_ALLOWLIST but keys on HLO metadata
# op_name (the jaxpr-provenance path XLA threads through optimization).
HLO_UPCAST_ALLOWLIST = (
    r"softmax", r"log_softmax", r"logsumexp", r"layer_norm", r"layernorm",
    r"rms_norm", r"norm/", r"loss", r"entropy", r"kl", r"logprob",
    r"cross_entropy", r"attention_weights", r"reduce_sum", r"reduce_mean",
    r"/mean", r"/sum", r"/var", r"gae", r"returns", r"advantage",
    r"cumsum", r"cumlogsumexp", r"global_norm", r"clip_by_global_norm",
    r"adam", r"optimizer", r"whiten", r"/dot_general",
    # f32 attention-score path: logits/weights compute in f32
    # (preferred_element_type) and cast back — numerics by design
    r"attn/", r"attention/",
    # LM heads mint f32 logits for stable softmax/log-softmax
    r"logits",
    # T5 RMSNorm scopes (`ln_self`/`ln_cross`/`ln_mlp`) accumulate f32
    r"/ln_",
)

# source files whose converts are f32-by-design end to end — the HLO
# twin of jaxpr_audit.PRECISION_ALLOWLIST's whole-file entries, keyed on
# the metadata source_file suffix (op_name scopes vary with AD/fusion,
# the authoring file does not)
HLO_UPCAST_SOURCE_ALLOWLIST = (
    "ops/ppo_math.py",        # loss + GAE math is f32 by contract
    "ops/ilql_math.py",       # loss math is f32 by contract
    "parallel/collectives.py",  # whitening/logprob reductions
    "trainer/common.py",      # optimizer moment upcasts
    "ops/attention.py",       # f32 softmax accumulation contract
    "ops/flash_attention.py",
    "ops/ring_attention.py",
    "models/t5.py",           # T5 consumes f32 directly by parity contract
    "models/heads.py",        # MLPHead fc2 computes in f32
)
_UPCAST_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*f32\[([0-9,]+)\](?:\{[^}]*\})?\s+"
    r"convert\([^)]*\)"
)


@dataclass
class DtypeUpcast:
    shape: str
    op_name: str
    source_file: str
    source_line: int


def extract_dtype_upcasts(hlo_text: str) -> List[DtypeUpcast]:
    """Non-scalar (rank≥2) f32 ``convert`` results in an optimized
    module, outside :data:`HLO_UPCAST_ALLOWLIST`. Scalars and vectors
    are reduction/accumulator plumbing (every all-reduce region converts
    its bf16 operands) — only activation-rank tensors double HBM
    traffic, which is what the bf16 compute contract protects.

    Converts with no ``op_name`` metadata are skipped: those are
    compiler-minted fusion/rematerialization plumbing (the clean tree
    carries ~15k of them, all at loop-carried scan shapes) that can
    neither be attributed to source nor curated through the allowlist —
    the rule audits *authored* f32 compute that survived into the
    optimized module. Repeated instances of the same authored convert
    (per-layer scans, AD transposes) are deduplicated to one report."""
    out: List[DtypeUpcast] = []
    seen: Set[Tuple[str, str, str, int]] = set()
    allow = re.compile("|".join(HLO_UPCAST_ALLOWLIST))
    for line in hlo_text.splitlines():
        m = _UPCAST_RE.match(line)
        if m is None or "bf16[" not in line:
            continue
        dims = m.group(1)
        if dims.count(",") < 1:  # rank < 2
            continue
        meta = _METADATA_RE.search(line)
        meta_text = meta.group(1) if meta else ""
        op_name_m = _OP_NAME_RE.search(meta_text)
        op_name = op_name_m.group(1) if op_name_m else ""
        if not op_name:  # unattributable compiler plumbing
            continue
        if allow.search(op_name):
            continue
        src_file_m = _SOURCE_FILE_RE.search(meta_text)
        src_line_m = _SOURCE_LINE_RE.search(meta_text)
        source_file = src_file_m.group(1) if src_file_m else ""
        source_line = int(src_line_m.group(1)) if src_line_m else 0
        if source_file.endswith(HLO_UPCAST_SOURCE_ALLOWLIST):
            continue
        key = (f"f32[{dims}]", op_name, source_file, source_line)
        if key in seen:
            continue
        seen.add(key)
        out.append(
            DtypeUpcast(
                shape=f"f32[{dims}]",
                op_name=op_name,
                source_file=source_file,
                source_line=source_line,
            )
        )
    return out


# --------------------------- compiled program --------------------------- #

@dataclass
class CompiledProgram:
    """One AOT-compiled traced program plus its parsed ground truth."""

    subject: str
    mesh_label: str
    mesh_shape: Optional[Dict[str, int]]
    collectives: List[HloCollective] = field(default_factory=list)
    profile: Dict[str, int] = field(default_factory=dict)
    collective_bytes: int = 0
    upcasts: List[DtypeUpcast] = field(default_factory=list)
    # buffer-assignment stats from compiled.memory_analysis()
    temp_bytes: int = 0
    argument_bytes: int = 0
    output_bytes: int = 0
    alias_bytes: int = 0
    def_site: Optional[Tuple[str, int]] = None
    explicit_intent: List[Tuple[str, Tuple[str, ...], str]] = field(
        default_factory=list
    )

    @property
    def peak_bytes(self) -> int:
        """Live-at-entry + temporaries − donation aliasing: the
        compiled counterpart of engine 7's static peak."""
        return max(
            0,
            self.temp_bytes + self.argument_bytes + self.output_bytes
            - self.alias_bytes,
        )

    def budget_entry(self) -> Dict:
        return {
            "collectives": {k: self.profile[k] for k in sorted(self.profile)},
            "collective_bytes": int(self.collective_bytes),
            "peak_bytes": int(self.peak_bytes),
            "temp_bytes": int(self.temp_bytes),
            "argument_bytes": int(self.argument_bytes),
            "output_bytes": int(self.output_bytes),
            "alias_bytes": int(self.alias_bytes),
        }


def _mesh_label(mesh_shape: Optional[Dict[str, int]]) -> str:
    if not mesh_shape:
        return "?"
    return (
        "/".join(
            f"{k}={v}" for k, v in sorted(mesh_shape.items()) if int(v) != 1
        )
        or "single-axis"
    )


def compile_program(program) -> CompiledProgram:
    """AOT-lower and compile one harness program; parse the optimized
    module and buffer-assignment stats into a :class:`CompiledProgram`."""
    lowered = program.jit_fn.lower(*program.example_args)
    compiled = lowered.compile()
    hlo_text = compiled.as_text()
    cp = CompiledProgram(
        subject=program.subject,
        mesh_label=_mesh_label(program.mesh_shape),
        mesh_shape=program.mesh_shape,
        collectives=parse_hlo_collectives(hlo_text),
        upcasts=extract_dtype_upcasts(hlo_text),
        def_site=program.def_site,
    )
    cp.profile = collective_profile(cp.collectives, cp.mesh_shape)
    cp.collective_bytes = sum(c.bytes for c in cp.collectives)
    try:
        mem = compiled.memory_analysis()
        cp.temp_bytes = int(getattr(mem, "temp_size_in_bytes", 0))
        cp.argument_bytes = int(getattr(mem, "argument_size_in_bytes", 0))
        cp.output_bytes = int(getattr(mem, "output_size_in_bytes", 0))
        cp.alias_bytes = int(getattr(mem, "alias_size_in_bytes", 0))
    except Exception:
        pass
    from trlx_tpu.analysis.collective_trace import collective_sequence

    cp.explicit_intent = collective_sequence(program.closed_jaxpr)
    return cp


# --------------------- lowering-collective-drift rule ------------------- #

# jaxpr collective primitive -> the HLO opcode GSPMD lowers it to
_PRIM_TO_HLO = {
    "psum": "all-reduce",
    "psum2": "all-reduce",
    "psum_invariant": "all-reduce",
    "pmax": "all-reduce",
    "pmin": "all-reduce",
    "all_gather": "all-gather",
    "reduce_scatter": "reduce-scatter",
    "ppermute": "collective-permute",
    "pbroadcast": "collective-permute",
    "all_to_all": "all-to-all",
}

_CONCAT_OP_RE = re.compile(r"(?:^|/)(concatenate|stack)(?:\[|$|/)")

# JAX-library scopes whose internal concatenates legitimately lower to
# a zero-pad + all-reduce(add) shard combine: threefry bit generation
# (`_uniform`/`_gumbel`/`_normal` concat the two u32 output halves of
# replicated PRNG state, and the partitioner recombines by summing
# disjoint nonzero shards — a correct partial-value lowering, verified
# concretely by the sanitizer replays). The PR-2 signature is an
# all-reduce minted from a *repo-authored* concat of committed-sharded
# data, whose op scope never crosses these private jax.random frames.
_CONCAT_EXEMPT_OPS = re.compile(
    r"jit\(_uniform\)|jit\(_gumbel\)|jit\(_normal\)|threefry|random_bits"
)


def concat_minted_collectives(
    collectives: Sequence[HloCollective],
) -> List[HloCollective]:
    """All-reduces whose jaxpr provenance is a ``concatenate``/``stack``
    op — outside the jax.random bit-gen scopes above, a concat must
    never lower to a cross-replica reduction, so any hit is the PR-2
    replica-sum signature regardless of lockfiles."""
    return [
        c
        for c in collectives
        if c.kind == "all-reduce"
        and _CONCAT_OP_RE.search(c.op_name)
        and not _CONCAT_EXEMPT_OPS.search(c.op_name)
    ]


def check_lowering_drift(
    cp: CompiledProgram,
    locked_entry: Optional[Dict],
    budgets_where: str = "budgets.json",
) -> List[Finding]:
    """The three ``lowering-collective-drift`` sub-checks for one
    compiled program (concat-minted sums, explicit-intent survival,
    locked-profile equality)."""
    rule = get_rule("lowering-collective-drift")
    findings: List[Finding] = []
    file, line = cp.def_site or (None, None)

    for c in concat_minted_collectives(cp.collectives):
        axes = ",".join(c.axes(cp.mesh_shape))
        findings.append(
            Finding(
                rule=rule.id,
                message=(
                    f"XLA lowered a concatenate/stack in `{cp.subject}` "
                    f"to a replica-axis all-reduce over [{axes}] "
                    f"({c.dtype}, {c.elems} elems, reduction "
                    f"`{c.to_apply}`, op {c.op_name!r}) — the PR-2 "
                    "sharded-concat miscompile signature; route the "
                    "concat through spmd_stack/concat_cols "
                    "(dynamic_update_slice never mis-lowers)"
                ),
                severity=rule.severity,
                file=file,
                line=line,
                subject=cp.subject,
                engine="hlo",
            )
        )

    # explicit jaxpr collectives must survive lowering as their HLO kind
    compiled_kinds = {c.kind for c in cp.collectives}
    for prim, axes, _detail in cp.explicit_intent:
        want = _PRIM_TO_HLO.get(prim)
        if want is None:
            continue
        if want not in compiled_kinds:
            findings.append(
                Finding(
                    rule=rule.id,
                    message=(
                        f"jaxpr of `{cp.subject}` names an explicit "
                        f"`{prim}` over {list(axes)} but the optimized "
                        f"module contains no {want} — XLA dropped or "
                        "rewrote a collective the program author wrote"
                    ),
                    severity=rule.severity,
                    file=file,
                    line=line,
                    subject=cp.subject,
                    engine="hlo",
                )
            )

    if locked_entry is not None:
        locked = {
            k: int(v)
            for k, v in (locked_entry.get("collectives") or {}).items()
        }
        if locked != cp.profile:
            drift = []
            for key in sorted(set(locked) | set(cp.profile)):
                a, b = locked.get(key, 0), cp.profile.get(key, 0)
                if a != b:
                    drift.append(f"{key}: {a} -> {b}")
            findings.append(
                Finding(
                    rule=rule.id,
                    message=(
                        f"compiled collective profile of `{cp.subject}` "
                        f"drifted from {budgets_where}: "
                        + "; ".join(drift)
                        + " — XLA inserted/dropped/re-axised a "
                        "collective; review the lowering and relock "
                        "with --hlo-audit --update-budgets"
                    ),
                    severity=rule.severity,
                    file=file,
                    line=line,
                    subject=cp.subject,
                    engine="hlo",
                )
            )
    return findings


def check_dtype_upcasts(cp: CompiledProgram) -> List[Finding]:
    rule = get_rule("hlo-dtype-upcast")
    findings: List[Finding] = []
    file, line = cp.def_site or (None, None)
    for u in cp.upcasts:
        where = (
            f" (from {os.path.basename(u.source_file)}:{u.source_line})"
            if u.source_file
            else ""
        )
        findings.append(
            Finding(
                rule=rule.id,
                message=(
                    f"optimized module of `{cp.subject}` mints "
                    f"{u.shape} from bf16 at op {u.op_name!r}{where} — "
                    "f32 compute outside the softmax/layernorm/loss "
                    "allowlist doubles that tensor's HBM traffic; cast "
                    "back to the compute dtype or extend "
                    "HLO_UPCAST_ALLOWLIST with a justification"
                ),
                severity=rule.severity,
                file=file,
                line=line,
                subject=cp.subject,
                engine="hlo",
            )
        )
    return findings


def check_memory_drift(
    cp: CompiledProgram,
    locked_entry: Optional[Dict],
    tolerance_pct: float,
    budgets_where: str = "budgets.json",
) -> List[Finding]:
    rule = get_rule("hlo-memory-drift")
    file, line = cp.def_site or (None, None)
    if locked_entry is None:
        return [
            Finding(
                rule=rule.id,
                message=(
                    f"no committed hlo budget for `{cp.subject}` "
                    f"(compiled peak {cp.peak_bytes} B observed) — run "
                    "--hlo-audit --update-budgets and review the "
                    "lockfile diff"
                ),
                severity=rule.severity,
                file=file,
                line=line,
                subject=cp.subject,
                engine="hlo",
            )
        ]
    locked_peak = int(locked_entry.get("peak_bytes", 0))
    tol = float(locked_entry.get("tolerance_pct", tolerance_pct))
    if locked_peak and cp.peak_bytes > locked_peak * (1 + tol / 100.0):
        pct = 100.0 * (cp.peak_bytes - locked_peak) / locked_peak
        return [
            Finding(
                rule=rule.id,
                message=(
                    f"compiled buffer-assignment peak of `{cp.subject}` "
                    f"grew {pct:.1f}% past {budgets_where} "
                    f"({locked_peak} -> {cp.peak_bytes} B, tolerance "
                    f"{tol:g}%) — a lowering or fusion change regressed "
                    "live memory; review, then relock with "
                    "--hlo-audit --update-budgets"
                ),
                severity=rule.severity,
                file=file,
                line=line,
                subject=cp.subject,
                engine="hlo",
            )
        ]
    return []


# ------------------------- spmd-concat-hazard --------------------------- #

# helpers blessed to assemble sharded arrays (both build their result
# with dynamic_update_slice and never emit a `concatenate` eqn — seeing
# one attributed to them would itself be news)
BLESSED_CONCAT_HELPERS = ("spmd_stack", "concat_cols")


def check_concat_hazard(program, repo_root: Optional[str] = None) -> List[Finding]:
    """Jaxpr walk for the PR-2 hazard *class*: a multi-operand
    ``concatenate`` **along a mesh-split dimension** whose operands
    taint back to committed-sharded program inputs (``input_divisors``
    > 1), on a mesh that actually distributes (some axis size > 1),
    outside the blessed helpers. Concatenating along a *replicated*
    dimension of sharded operands (e.g. ``[query; response]`` along the
    sequence axis of batch-sharded rollout tensors) lowers to a local
    per-shard concat and is benign — only the along-the-split shape
    forces the partitioner reshard that GSPMD has twice mis-lowered
    into a replica-axis SUM in this repo's history. Taint carries the
    set of candidate split dimensions per value (seeded from
    ``input_sharded_dims`` when the harness recorded them, else every
    dimension of a sharded input) and propagates as a union — crude
    across reshapes/transposes, but the hazard shape in practice
    concatenates program inputs directly."""
    from jax._src.core import Literal

    from trlx_tpu.analysis.jaxpr_audit import (
        _repo_frame,
        _sub_jaxprs,
        default_repo_root,
        frame_function,
    )

    rule = get_rule("spmd-concat-hazard")
    repo_root = repo_root or default_repo_root()
    findings: List[Finding] = []
    mesh_shape = program.mesh_shape or {}
    if not any(v > 1 for v in mesh_shape.values()):
        return findings  # single-device mesh cannot mis-partition
    divisors = program.input_divisors or []
    sharded_dims = getattr(program, "input_sharded_dims", None)

    def _rank(v) -> int:
        return len(getattr(getattr(v, "aval", None), "shape", ()) or ())

    def _shift(dims: frozenset, src_rank: int, dst_rank: int) -> frozenset:
        """Re-index taint dims across a rank change by trailing
        alignment: a scan/loop body slicing the stacked leading axis
        (or a squeeze/broadcast of it) keeps the trailing layout, so
        the batch axis that was dim 1 of ``(n_mb, batch, seq)`` is dim
        0 of the ``(batch, seq)`` slice. Wrong for transposes — the
        hazard shape in practice never reorders the split axis."""
        delta = src_rank - dst_rank
        if delta == 0:
            return dims
        return frozenset(
            d - delta for d in dims if 0 <= d - delta < max(dst_rank, 1)
        )

    def walk(jaxpr, tainted: Dict[Any, frozenset]) -> bool:
        """Returns True when any outvar of ``jaxpr`` is tainted."""
        for eqn in jaxpr.eqns:
            hot_in = [
                v
                for v in eqn.invars
                if not isinstance(v, Literal) and v in tainted
            ]
            in_dims = frozenset().union(*(tainted[v] for v in hot_in))
            in_taint = bool(in_dims)
            if eqn.primitive.name == "concatenate":
                dim = int(eqn.params.get("dimension", 0))
                operands = [
                    v
                    for v in eqn.invars
                    if not isinstance(v, Literal)
                ]
                hot = [
                    v
                    for v in operands
                    if dim in tainted.get(v, frozenset())
                ]
                if len(operands) >= 2 and len(hot) >= 2:
                    frame = _repo_frame(eqn, repo_root)
                    fn_name = frame_function(frame) if frame else ""
                    if fn_name not in BLESSED_CONCAT_HELPERS:
                        file = frame.file_name if frame else None
                        line = frame.start_line if frame else None
                        findings.append(
                            Finding(
                                rule=rule.id,
                                message=(
                                    "eager multi-operand concatenate of "
                                    "committed-sharded operands in "
                                    f"`{program.subject}` on mesh "
                                    f"{_mesh_label(mesh_shape)} — the "
                                    "PR-2 miscompile class (XLA's SPMD "
                                    "partitioner has minted a "
                                    "replica-axis SUM from this shape); "
                                    "assemble via spmd_stack/concat_cols "
                                    "instead"
                                ),
                                severity=rule.severity,
                                file=file,
                                line=line,
                                subject=program.subject,
                                engine="hlo",
                            )
                        )
            # conservative taint propagation, recursing into sub-jaxprs
            # with the eqn-level taint mapped onto their invars
            for sub in _sub_jaxprs(eqn):
                inner = getattr(sub, "jaxpr", sub)
                sub_taint: Dict[Any, frozenset] = {}
                n = min(len(inner.invars), len(eqn.invars))
                for sv, ov in zip(inner.invars[-n:], eqn.invars[-n:]):
                    if not isinstance(ov, Literal) and ov in tainted:
                        dims = _shift(tainted[ov], _rank(ov), _rank(sv))
                        if dims:
                            sub_taint[sv] = dims
                if not sub_taint and in_taint:
                    sub_taint = {sv: in_dims for sv in inner.invars}
                walk(inner, sub_taint)
            if in_taint:
                for ov in eqn.outvars:
                    dims = frozenset().union(
                        *(
                            _shift(tainted[v], _rank(v), _rank(ov))
                            for v in hot_in
                        )
                    )
                    if dims:
                        tainted[ov] = tainted.get(ov, frozenset()) | dims
        return any(v in tainted for v in jaxpr.outvars)

    jaxpr = program.closed_jaxpr.jaxpr
    seed: Dict[Any, frozenset] = {}
    for i, v in enumerate(jaxpr.invars):
        if i < len(divisors) and divisors[i] > 1:
            if sharded_dims is not None and i < len(sharded_dims):
                dims = frozenset(sharded_dims[i])
            else:
                # harness predates per-dim recording: treat every
                # dimension as a candidate split (conservative)
                ndim = len(getattr(getattr(v, "aval", None), "shape", ()) or ())
                dims = frozenset(range(max(ndim, 1)))
            if dims:
                seed[v] = dims
    if not seed:
        return findings
    walk(jaxpr, seed)
    return findings


# ----------------------- known-miscompile registry ---------------------- #

@dataclass(frozen=True)
class KnownMiscompile:
    """One quarantined XLA lowering bug, pinned as expected divergence.

    ``verified_broken`` is the set of jaxlib versions the repro was
    confirmed on; a jaxlib outside the set flips the entry to a
    stale-quarantine finding (the mechanical "re-run the repro after a
    bump" that used to be a human ROADMAP obligation)."""

    id: str
    description: str
    repro: str               # command that prints REPRODUCED/FIXED UPSTREAM
    verified_broken: Tuple[str, ...]
    retire: str              # what to dismantle when fixed upstream


KNOWN_MISCOMPILES: Tuple[KnownMiscompile, ...] = (
    KnownMiscompile(
        id="sharded-concat-replica-sum",
        description=(
            "eager multi-operand concatenate of committed-sharded arrays "
            "on a mesh with a spare size>1 axis mis-lowers into a "
            "replica-axis SUM (PR 2)"
        ),
        repro="python -m trlx_tpu.analysis --plant-hazard",
        verified_broken=("0.4.36",),
        retire=(
            "spmd_stack/concat_cols quarantine helpers "
            "(parallel/pipeline.py, ops/sampling.py) and this registry "
            "entry"
        ),
    ),
    KnownMiscompile(
        id="pp-cached-decode-stack",
        description=(
            "pp cached-decode jnp.stack of per-stage KV rows miscompiles "
            "under pipeline-parallel SPMD (quarantined behind spmd_stack)"
        ),
        repro="python tools/pp_miscompile_repro.py",
        verified_broken=("0.4.36",),
        retire="spmd_stack quarantine in parallel/pipeline.py",
    ),
    KnownMiscompile(
        id="multihost-sync-barrier-abort",
        description=(
            "multi-process CPU sync barrier aborts at init "
            "(quarantines the multi-controller integration tests)"
        ),
        repro="python tools/multiprocess_probe.py",
        verified_broken=("0.4.36",),
        retire=(
            "the simulated-host lockstep fallback note in "
            "docs/multihost.md and the skipped integration tests"
        ),
    ),
)


def check_known_miscompiles(
    jaxlib_version: Optional[str] = None,
    probe: bool = True,
) -> Tuple[List[Finding], List[str]]:
    """Registry sweep: report each entry's status. On the verified
    jaxlib the entries are *expected* divergence (covered, no finding);
    a jaxlib outside an entry's verified set yields a stale-quarantine
    warning naming the repro to run and the workaround to retire. For
    ``sharded-concat-replica-sum`` the audit additionally live-probes
    the lowering (compile a seeded concat, look for the minted
    all-reduce) so the flip is detected even with no version bump."""
    if jaxlib_version is None:
        import jaxlib

        jaxlib_version = jaxlib.__version__
    rule = get_rule("lowering-collective-drift")
    findings: List[Finding] = []
    covered: List[str] = []
    for entry in KNOWN_MISCOMPILES:
        covered.append(f"known-miscompile:{entry.id}")
        stale_reason = None
        if jaxlib_version not in entry.verified_broken:
            stale_reason = (
                f"jaxlib {jaxlib_version} is outside the verified-broken "
                f"set {list(entry.verified_broken)}"
            )
        elif entry.id == "sharded-concat-replica-sum" and probe:
            if not _probe_concat_miscompile():
                stale_reason = (
                    f"the live probe no longer reproduces on jaxlib "
                    f"{jaxlib_version}"
                )
        if stale_reason:
            findings.append(
                Finding(
                    rule=rule.id,
                    message=(
                        f"known-miscompile `{entry.id}` may be FIXED "
                        f"UPSTREAM: {stale_reason} — run `{entry.repro}` "
                        "and, if it prints FIXED UPSTREAM, retire "
                        f"{entry.retire}, then update verified_broken"
                    ),
                    severity=SEVERITY_WARNING,
                    subject=f"known-miscompile:{entry.id}",
                    engine="hlo",
                )
            )
    return findings, covered


def _probe_concat_miscompile() -> bool:
    """Compile the minimal PR-2 shape and return True when the minted
    replica-axis all-reduce is still present (i.e. still broken)."""
    try:
        program = plant_hazard_program()
        cp = compile_program(program)
        return bool(concat_minted_collectives(cp.collectives))
    except Exception:
        # a probe that cannot run must not mask real findings — treat
        # as still-broken (the CI upstream-probe job runs the full repro)
        return True


# ------------------------------ the plant ------------------------------- #

def plant_hazard_program():
    """The ``--plant-hazard`` self-check subject: an eager two-operand
    concat of batch-committed rows on the audit mesh (spare tp axis) —
    the minimal PR-2 shape. Running the full rule set over it must trip
    ``spmd-concat-hazard`` at the concat's line below AND
    ``lowering-collective-drift`` on the compiled replica-sum."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from trlx_tpu.analysis import harness

    mesh = harness.audit_mesh()
    row = NamedSharding(mesh, P(("dp", "fsdp"), None))

    def planted_eager_concat(a, b):
        return jnp.concatenate([a, b], axis=0)

    fn = jax.jit(planted_eager_concat, in_shardings=(row, row))
    sds = jax.ShapeDtypeStruct((8, 6), jnp.int32)
    closed = jax.make_jaxpr(fn)(sds, sds)
    return harness.TracedProgram(
        subject="plant.eager_concat",
        closed_jaxpr=closed,
        mesh_axes=set(mesh.axis_names),
        mesh_shape={k: int(v) for k, v in mesh.shape.items()},
        input_divisors=harness.flat_sharding_divisors(
            ((sds, sds),), ((row, row),)
        ),
        input_sharded_dims=harness.flat_sharded_dims(
            ((sds, sds),), ((row, row),)
        ),
        def_site=harness.callable_def_site(planted_eager_concat),
        jit_fn=fn,
        example_args=(sds, sds),
    )


# ------------------------------- budgets -------------------------------- #

def make_hlo_budgets(
    compiled: Sequence[CompiledProgram],
    mesh: Dict[str, int],
    tolerance_pct: float,
) -> Dict:
    audit_label = _mesh_label(mesh)
    return {
        "mesh": {k: int(v) for k, v in sorted(mesh.items())},
        "tolerance_pct": float(tolerance_pct),
        "programs": {
            _budget_key(cp, audit_label): cp.budget_entry()
            for cp in sorted(compiled, key=lambda c: (c.subject, c.mesh_label))
        },
    }


def _budget_key(cp: CompiledProgram, audit_label: str) -> str:
    """Programs compiled on the audit mesh key by bare subject; the
    mesh-matrix train-step extras carry their mesh label so cross-mesh
    entries never collide (and partial relocks can tell them apart)."""
    if cp.mesh_label == audit_label:
        return cp.subject
    return f"{cp.subject}@{cp.mesh_label}"


# ------------------------------ entry point ----------------------------- #

@dataclass
class HloAuditResult:
    mesh: Dict[str, int] = field(default_factory=dict)
    compiled: List[CompiledProgram] = field(default_factory=list)
    compile_seconds: float = 0.0
    registry_status: List[str] = field(default_factory=list)

    def to_rows(self) -> List[Dict]:
        audit_label = _mesh_label(self.mesh)
        return [
            {
                "subject": _budget_key(cp, audit_label),
                "collectives": sum(cp.profile.values()),
                "collective_bytes": cp.collective_bytes,
                "peak_bytes": cp.peak_bytes,
                "upcasts": len(cp.upcasts),
            }
            for cp in sorted(
                self.compiled, key=lambda c: (c.subject, c.mesh_label)
            )
        ]


def audit_hlo(
    kinds: Optional[Sequence[str]] = None,
    mesh: Optional[Dict[str, int]] = None,
    budgets_path: Optional[str] = None,
    update: bool = False,
    matrix: bool = True,
    plant: bool = False,
    programs: Optional[Sequence[Any]] = None,
    registry_probe: bool = True,
) -> Tuple[Report, HloAuditResult]:
    """The ``--hlo-audit`` entry point: compile every harness program
    (plus the train step on the rest of engine 5's mesh matrix — the
    PR-2 bug only mis-lowered on meshes with a spare axis), run the four
    rules, and gate (or with ``update=True`` relock) the ``hlo_budgets``
    section of ``analysis/budgets.json``. ``plant=True`` swaps the
    program set for the seeded eager concat and must produce findings
    from both ``spmd-concat-hazard`` and ``lowering-collective-drift``.
    """
    import time

    from trlx_tpu.analysis import harness
    from trlx_tpu.analysis.collective_trace import MESH_MATRIX
    from trlx_tpu.analysis.resource_audit import (
        DEFAULT_TOLERANCE_PCT,
        default_budgets_path,
        load_budgets,
        write_budgets,
    )

    path = budgets_path or default_budgets_path()
    where = os.path.basename(path)
    report = Report()
    result = HloAuditResult()
    rule_drift = get_rule("lowering-collective-drift")

    if programs is not None and programs:
        # injected subjects (tests): the run's mesh is theirs
        audit_mesh = {
            k: int(v)
            for k, v in (list(programs)[0].mesh_shape or {}).items()
        }
    else:
        audit_mesh = {
            k: int(v)
            for k, v in harness.audit_mesh().shape.items()
        }
    result.mesh = audit_mesh
    audit_label = _mesh_label(audit_mesh)

    if programs is not None:
        programs = list(programs)
    elif plant:
        programs = [plant_hazard_program()]
    else:
        programs = []
        for kind in kinds or harness.TRAINER_KINDS:
            programs.extend(harness.trace_trainer(kind, mesh))
        if matrix and mesh is None:
            for kind in kinds or harness.TRAINER_KINDS:
                for matrix_mesh in MESH_MATRIX:
                    shaped = harness.trace_train_step_program(
                        kind, matrix_mesh
                    )
                    if _mesh_label(shaped.mesh_shape) == audit_label:
                        continue  # the audit mesh is matrix row 4
                    programs.append(shaped)

    findings: List[Finding] = []
    t0 = time.monotonic()
    for program in programs:
        label = _mesh_label(program.mesh_shape)
        if program.jit_fn is None:
            continue
        try:
            cp = compile_program(program)
        except Exception as e:
            findings.append(
                Finding(
                    rule=rule_drift.id,
                    message=(
                        f"failed to AOT-compile `{program.subject}` on "
                        f"mesh {label}: {type(e).__name__}: {e} — the "
                        "compiled artifact cannot be audited"
                    ),
                    severity=rule_drift.severity,
                    subject=program.subject,
                    engine="hlo",
                )
            )
            continue
        result.compiled.append(cp)
        findings.extend(check_dtype_upcasts(cp))
        findings.extend(check_concat_hazard(program))
        report.covered += [
            f"hlo:{program.subject}[{label}]:{facet}"
            for facet in ("collectives", "dtypes", "memory", "intent")
        ] + [
            f"hlo:{program.subject}[{label}]",
            f"hazard:{program.subject}[{label}]",
        ]
    result.compile_seconds = time.monotonic() - t0

    if update:
        if findings:
            kept, suppressed = filter_suppressed(findings)
            report.extend(kept)
            report.suppressed += suppressed
            if report.findings:
                return report, result  # REFUSED: fix findings first
        try:
            budgets = load_budgets(path)
        except (OSError, ValueError):
            budgets = {}
        partial = kinds is not None
        section = make_hlo_budgets(
            result.compiled, result.mesh, DEFAULT_TOLERANCE_PCT
        )
        old_section = budgets.get("hlo_budgets") or {}
        if partial and old_section.get("mesh") not in (
            None, section["mesh"]
        ):
            report.extend([
                Finding(
                    rule=rule_drift.id,
                    message=(
                        "refusing --update-budgets: the hlo lockfile is "
                        f"for mesh {old_section.get('mesh')} but this "
                        f"--trainers subset ran on {section['mesh']} — "
                        "rerun without --trainers or on the locked mesh"
                    ),
                    severity=rule_drift.severity,
                    subject="hlo_budgets",
                    engine="hlo",
                )
            ])
            return report, result
        if partial:
            kept_entries = {
                s: dict(e)
                for s, e in old_section.get("programs", {}).items()
                if s.split(".")[0] not in set(kinds or ())
            }
            kept_entries.update(section["programs"])
            section["programs"] = {
                s: kept_entries[s] for s in sorted(kept_entries)
            }
        budgets["hlo_budgets"] = section
        write_budgets(budgets, path)
        return report, result

    try:
        budgets = load_budgets(path)
    except (OSError, ValueError) as e:
        budgets = {}
        if not plant:
            findings.append(
                Finding(
                    rule=rule_drift.id,
                    message=(
                        f"cannot load budget contract {path}: {e} — "
                        "generate it with --hlo-audit --update-budgets"
                    ),
                    severity=rule_drift.severity,
                    subject="hlo_budgets",
                    engine="hlo",
                )
            )
    section = budgets.get("hlo_budgets")
    if section is None and budgets and not plant:
        findings.append(
            Finding(
                rule=rule_drift.id,
                message=(
                    f"{where} has no hlo_budgets section — lock the "
                    "compiled contract with --hlo-audit --update-budgets "
                    "and commit the diff"
                ),
                severity=rule_drift.severity,
                subject="hlo_budgets",
                engine="hlo",
            )
        )
    locked_mesh = (section or {}).get("mesh")
    mesh_comparable = locked_mesh is None or {
        k: int(v) for k, v in sorted(locked_mesh.items())
    } == {k: int(v) for k, v in sorted(result.mesh.items())}
    if section is not None and not mesh_comparable and not plant:
        findings.append(
            Finding(
                rule=rule_drift.id,
                message=(
                    f"hlo budgets in {where} were locked for mesh "
                    f"{locked_mesh} but the audit ran on {result.mesh} "
                    "— compiled profiles are not comparable; rerun on "
                    "the locked mesh or --update-budgets"
                ),
                severity=rule_drift.severity,
                subject="hlo_budgets",
                engine="hlo",
            )
        )
    tol = float(
        (section or {}).get("tolerance_pct", DEFAULT_TOLERANCE_PCT)
    )
    locked_programs = (section or {}).get("programs", {})
    for cp in result.compiled:
        key = _budget_key(cp, audit_label)
        entry = (
            locked_programs.get(key)
            if section is not None and mesh_comparable and not plant
            else None
        )
        findings.extend(check_lowering_drift(cp, entry, where))
        if not plant:
            findings.extend(check_memory_drift(cp, entry, tol, where))

    if not plant and registry_probe:
        registry_findings, registry_covered = check_known_miscompiles()
        findings.extend(registry_findings)
        report.covered += registry_covered
        import jaxlib

        for entry in KNOWN_MISCOMPILES:
            status = (
                "expected-divergence"
                if jaxlib.__version__ in entry.verified_broken
                else "STALE?"
            )
            result.registry_status.append(f"{entry.id}: {status}")

    kept, suppressed = filter_suppressed(findings)
    report.extend(kept)
    report.suppressed += suppressed
    return report, result


# ------------------------------ rendering ------------------------------- #

def format_hlo_text(result: HloAuditResult) -> str:
    lines = [
        f"{'program':44} {'colls':>5} {'coll MB':>8} {'peak MB':>8} "
        f"{'upcasts':>7}"
    ]
    for row in result.to_rows():
        lines.append(
            f"{row['subject']:44} {row['collectives']:>5} "
            f"{row['collective_bytes'] / 2**20:>8.3f} "
            f"{row['peak_bytes'] / 2**20:>8.3f} {row['upcasts']:>7}"
        )
    for status in result.registry_status:
        lines.append(f"known-miscompile {status}")
    lines.append(
        f"total: {len(result.compiled)} program(s) compiled in "
        f"{result.compile_seconds:.1f}s on mesh {result.mesh}"
    )
    return "\n".join(lines)
