"""Jaxpr audit: dtype / collective / donation / precision invariants.

Engine 1 of ``trlx_tpu.analysis``. The TPU port's core invariants are
*visible in jaxprs*: the trainers' step and rollout programs are traced
abstractly (``jax.make_jaxpr`` on the jitted callables, CPU mesh, tiny
configs — see ``harness.py``) and the closed jaxpr is walked recursively
through every sub-jaxpr (pjit / shard_map / scan / cond / custom_*):

- ``fp64``: no float64 aval anywhere.
- ``collective-axis``: every named collective (``psum``/``all_gather``/
  ``ppermute``/``reduce_scatter``/...) references an axis of the trainer
  mesh (``parallel/mesh.py`` constants).
- ``donation``: the train-step pjit donates all of its state buffers.
- ``precision-leak``: no bf16/f16 -> f32 ``convert_element_type`` of an
  activation-rank (ndim >= 3) tensor whose source is repo forward code;
  loss/optimizer reduction sites are allow-listed
  (:data:`PRECISION_ALLOWLIST`).
- ``partition-spec``: every registered model family's partition rules
  produce mesh-valid specs for its param tree (axis exists, dim
  divisible) — via ``parallel/partition.py``'s registration-time
  validation.

Rule functions take explicit inputs (jaxpr, axis names, ...) so golden
tests can seed violations without building trainers.
"""

from __future__ import annotations

import os
from typing import Any, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

from trlx_tpu.analysis.findings import Finding
from trlx_tpu.analysis.registry import get_rule

# Primitives that reference a named mesh axis. (psum lowers as psum2 in
# recent JAX; keep both spellings.)
COLLECTIVE_PRIMS = {
    "psum", "psum2", "pmax", "pmin", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter", "axis_index",
    "psum_invariant",
}

# (file suffix, function name) pairs allowed to upcast bf16 activations to
# f32: loss math, logprob/entropy reductions, optimizer moment math. A None
# function matches the whole file. Extend here (with a comment saying why)
# rather than sprinkling inline suppressions over kernel code.
PRECISION_ALLOWLIST: Sequence[Tuple[str, Optional[str]]] = (
    ("ops/ppo_math.py", None),  # loss + GAE math is f32 by contract
    ("ops/ilql_math.py", None),  # loss math is f32 by contract
    ("parallel/collectives.py", None),  # whitening/logprob reductions
    ("trainer/common.py", None),  # optimizer moment upcasts
    ("", "_policy_entropy"),  # entropy reduction consumes f32 logits
    ("", "chunk_logprobs"),  # chunked CE upcasts one logits chunk at a time
    # f32 softmax accumulation: attention logits/weights compute in f32
    # (preferred_element_type) and cast back — numerics by design
    ("ops/attention.py", "dot_product_attention"),
    ("ops/flash_attention.py", None),  # same f32-accumulation contract
    ("ops/ring_attention.py", None),  # same f32-accumulation contract
    # T5 consumes f32 directly by parity contract: RMSNorm accumulates
    # f32, rel-pos bias feeds attention at f32, logits are f32 (the
    # seq2seq trainer refuses rollout_param_cast for exactly this)
    ("models/t5.py", None),
    # MLPHead fc2 computes in f32 (value clipping is sensitive to bf16
    # rounding; see utils.ROLLOUT_CAST_EXCLUDE)
    ("models/heads.py", "__call__"),
    # flax nn.LayerNorm accumulates its moments in f32 and casts back
    # (standard stable-norm numerics); flax registers its frames for
    # traceback exclusion, so the converts attribute to the repo call line
    ("models/gpt2.py", "__call__"),
    # AD transpose of the embed tables' compute-dtype downcast: the bf16
    # cotangent upcasts to f32 so gradients accumulate in the param dtype
    ("models/gpt2.py", "embed"),
)


def _sub_jaxprs(eqn) -> Iterator[Any]:
    for value in eqn.params.values():
        candidates = value if isinstance(value, (list, tuple)) else (value,)
        for v in candidates:
            if hasattr(v, "eqns"):
                yield v
            elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                yield v.jaxpr


def iter_eqns(jaxpr) -> Iterator[Any]:
    """All equations of a (closed) jaxpr, recursing into sub-jaxprs."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


# Call-like primitives besides the jit call, and the params key that
# holds the sub-program each one enters.
_CALL_JAXPR_KEYS = {
    "closed_call": "call_jaxpr",
    "core_call": "call_jaxpr",
    "remat": "jaxpr",
    "remat2": "jaxpr",
    "checkpoint": "jaxpr",
    "custom_jvp_call": "call_jaxpr",
    "custom_vjp_call": "call_jaxpr",
    "custom_vjp_call_jaxpr": "fun_jaxpr",
}


def is_jit_eqn(eqn) -> bool:
    """Whether ``eqn`` calls a jitted sub-program (``params["jaxpr"]``,
    ``donated_invars``): the primitive is named ``jit`` by the jax this
    repo runs on and ``pjit`` by the ones before it."""
    return eqn.primitive.name in ("jit", "pjit")


def called_jaxpr(eqn):
    """The (closed) jaxpr a call-like equation enters with its operands
    mapped 1:1 (a jit call, ``closed_call``, remat, ``custom_jvp``/
    ``custom_vjp``), or None."""
    key = "jaxpr" if is_jit_eqn(eqn) else _CALL_JAXPR_KEYS.get(eqn.primitive.name)
    return None if key is None else eqn.params.get(key)


def user_frames(eqn) -> Iterator[Any]:
    """The frames of ``eqn``'s traceback outside jax and the standard
    library, innermost first: the one place the engines read a
    ``source_info``. Nothing is caught here: when jax's own reader moves
    or takes something else, the audit raises. An audit that cannot see
    where an equation came from drops every located finding, which
    reads as clean."""
    source_info = getattr(eqn, "source_info", None)
    if source_info is None:
        return iter(())
    from jax._src import source_info_util

    return source_info_util.user_frames(source_info.traceback)


def frame_function(frame) -> str:
    """The function a frame ran in, as its ``def`` spells it: this jax
    reports the qualified name (``GPT2Model.embed``, ``f.<locals>.g``),
    the ones before it the bare one. Allow-lists name the bare one."""
    return frame.function_name.rsplit(".", 1)[-1]


def _repo_frame(eqn, repo_root: str, innermost_only: bool = False):
    """A traceback frame pointing into this repo, or None.

    ``innermost_only`` returns a frame only when the *innermost* user
    frame is repo code — i.e. the repo source itself wrote the op. A
    convert emitted inside flax/optax (e.g. LayerNorm's f32 accumulation)
    has a library file as its innermost frame even though repo lines sit
    above it in the stack; those libraries own their numerics.
    """
    for frame in user_frames(eqn):
        if repo_root in frame.file_name:
            return frame
        if innermost_only:
            return None
    return None


def _loc(eqn, repo_root: str) -> Tuple[Optional[str], Optional[int]]:
    frame = _repo_frame(eqn, repo_root)
    if frame is None:
        return None, None
    return frame.file_name, frame.start_line


def default_repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------ fp64 rule ------------------------------- #

def check_no_fp64(jaxpr, subject: str, repo_root: Optional[str] = None) -> List[Finding]:
    import numpy as np

    repo_root = repo_root or default_repo_root()
    rule = get_rule("fp64")
    findings: List[Finding] = []
    for eqn in iter_eqns(jaxpr):
        for var in list(eqn.outvars) + list(eqn.invars):
            aval = getattr(var, "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and dtype == np.float64:
                file, line = _loc(eqn, repo_root)
                findings.append(
                    Finding(
                        rule=rule.id,
                        message=f"float64 value in `{eqn.primitive.name}` "
                        f"(shape {getattr(aval, 'shape', '?')}) — TPUs "
                        "have no f64 units",
                        severity=rule.severity,
                        file=file,
                        line=line,
                        subject=subject,
                        engine="jaxpr",
                    )
                )
                break  # one finding per eqn is enough
    return findings


# -------------------------- collective-axis rule ------------------------ #

def _axis_names_of(eqn) -> Iterable[str]:
    for key in ("axes", "axis_name", "axis"):
        if key in eqn.params:
            value = eqn.params[key]
            names = value if isinstance(value, (list, tuple)) else (value,)
            for n in names:
                if isinstance(n, str):
                    yield n
            return


def check_collective_axes(
    jaxpr, mesh_axes: Set[str], subject: str, repo_root: Optional[str] = None
) -> List[Finding]:
    repo_root = repo_root or default_repo_root()
    rule = get_rule("collective-axis")
    findings: List[Finding] = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name not in COLLECTIVE_PRIMS:
            continue
        for axis in _axis_names_of(eqn):
            if axis not in mesh_axes:
                file, line = _loc(eqn, repo_root)
                findings.append(
                    Finding(
                        rule=rule.id,
                        message=f"collective `{eqn.primitive.name}` names "
                        f"axis {axis!r}, not a mesh axis "
                        f"({sorted(mesh_axes)})",
                        severity=rule.severity,
                        file=file,
                        line=line,
                        subject=subject,
                        engine="jaxpr",
                    )
                )
    return findings


# ----------------------------- donation rule ---------------------------- #

def check_donation(
    closed_jaxpr, n_state_leaves: int, subject: str
) -> List[Finding]:
    """The traced callable's outer pjit must donate its first
    ``n_state_leaves`` flat inputs (the train-state buffers)."""
    rule = get_rule("donation")
    inner = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    pjit_eqns = [e for e in inner.eqns if is_jit_eqn(e)]
    if not pjit_eqns:
        return [
            Finding(
                rule=rule.id,
                message="no jit equation found — the step function is "
                "not jitted at all",
                severity=rule.severity,
                subject=subject,
                engine="jaxpr",
            )
        ]
    eqn = pjit_eqns[0]
    donated = eqn.params.get("donated_invars", ())
    missing = [
        i for i in range(min(n_state_leaves, len(donated))) if not donated[i]
    ]
    if len(donated) < n_state_leaves or missing:
        return [
            Finding(
                rule=rule.id,
                message=f"train step donates "
                f"{sum(bool(d) for d in donated)} of {n_state_leaves} "
                f"state buffers (first undonated flat index: "
                f"{missing[0] if missing else len(donated)}) — pass "
                "donate_argnums for the state argument",
                severity=rule.severity,
                subject=subject,
                engine="jaxpr",
            )
        ]
    return []


# -------------------------- precision-leak rule ------------------------- #

def check_precision_leak(
    jaxpr,
    subject: str,
    repo_root: Optional[str] = None,
    allowlist: Sequence[Tuple[str, Optional[str]]] = PRECISION_ALLOWLIST,
    min_rank: int = 3,
) -> List[Finding]:
    """bf16/f16 -> f32 converts of activation-rank tensors traced from repo
    forward code. Converts with no repo frame (jax/optax internals) and
    allow-listed sites are fine; everything else is a leak report."""
    import numpy as np

    repo_root = repo_root or default_repo_root()
    rule = get_rule("precision-leak")
    findings: List[Finding] = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        new_dtype = eqn.params.get("new_dtype")
        if new_dtype is None or np.dtype(new_dtype) != np.float32:
            continue
        aval = getattr(eqn.invars[0], "aval", None)
        src_dtype = getattr(aval, "dtype", None)
        if src_dtype is None or str(src_dtype) not in ("bfloat16", "float16"):
            continue
        if len(getattr(aval, "shape", ())) < min_rank:
            continue
        frame = _repo_frame(eqn, repo_root, innermost_only=True)
        if frame is None:
            continue  # jax/flax/optax internals own their precision story
        rel = frame.file_name
        if repo_root in rel:
            rel = rel.split(repo_root, 1)[1].lstrip(os.sep)
        allowed = False
        for file_suffix, func in allowlist:
            if file_suffix and not rel.endswith(file_suffix):
                continue
            if func is not None and frame_function(frame) != func:
                continue
            allowed = True
            break
        if allowed:
            continue
        findings.append(
            Finding(
                rule=rule.id,
                message=f"{src_dtype}->f32 upcast of a rank-"
                f"{len(aval.shape)} tensor (shape {aval.shape}) in "
                f"`{frame.function_name}` — doubles its HBM traffic; "
                "allow-list the site if the upcast is a loss/optimizer "
                "reduction",
                severity=rule.severity,
                file=frame.file_name,
                line=frame.start_line,
                subject=subject,
                engine="jaxpr",
            )
        )
    return findings


# -------------------------- partition-spec rule ------------------------- #

# (family name, tiny arch overrides) — small dims chosen divisible by the
# audit mesh (tp=2 when >= 4 devices) so the check exercises rule matching,
# not toy-shape artifacts.
FAMILY_TINY_ARCH = {
    "gpt2": {
        "vocab_size": 32, "n_positions": 16, "n_embd": 32, "n_layer": 2,
        "n_head": 2,
    },
    "gptj": {
        "vocab_size": 32, "n_positions": 16, "n_embd": 32, "n_layer": 2,
        "n_head": 2, "rotary_dim": 8,
    },
    "gpt_neo": {
        "vocab_size": 32, "max_position_embeddings": 16, "hidden_size": 32,
        "num_layers": 2, "num_heads": 2,
        "attention_layers": ["global", "local"],
    },
    "gpt_neox": {
        "vocab_size": 32, "max_position_embeddings": 16, "hidden_size": 32,
        "num_hidden_layers": 2, "num_attention_heads": 2,
    },
    "t5": {
        "vocab_size": 32, "d_model": 32, "d_kv": 8, "d_ff": 64,
        "num_layers": 2, "num_decoder_layers": 2, "num_heads": 4,
        "relative_attention_num_buckets": 8,
        "relative_attention_max_distance": 16,
        "feed_forward_proj": "gated-gelu", "tie_word_embeddings": False,
    },
    "gpt2_moe": {
        "vocab_size": 32, "n_positions": 16, "n_embd": 32, "n_layer": 2,
        "n_head": 2, "n_experts": 2,
    },
}


def check_partition_specs(
    mesh, families: Optional[Sequence[str]] = None
) -> Tuple[List[Finding], List[str]]:
    """Validate every registered family's partition rules against ``mesh``
    for a representative param tree; returns (findings, covered subjects)."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.registry import get_model_family
    from trlx_tpu.parallel.partition import (
        PartitionRuleError,
        make_partition_specs,
    )

    rule = get_rule("partition-spec")
    findings: List[Finding] = []
    covered: List[str] = []
    for name in families or sorted(FAMILY_TINY_ARCH):
        family = get_model_family(name)
        arch = family.config_cls.from_dict(dict(FAMILY_TINY_ARCH[name]))
        module = family.backbone_cls(arch)
        if family.is_seq2seq:
            shapes = jax.eval_shape(
                lambda m=module: m.init(
                    jax.random.PRNGKey(0),
                    jnp.zeros((1, 8), jnp.int32),
                    decoder_input_ids=jnp.zeros((1, 2), jnp.int32),
                )
            )["params"]
        else:
            shapes = jax.eval_shape(
                lambda m=module: m.init(
                    jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
                )
            )["params"]
        subject = f"partition:{name}"
        covered.append(subject)
        try:
            make_partition_specs(
                shapes, mesh, family.partition_rules, validate=True
            )
        except PartitionRuleError as e:
            findings.append(
                Finding(
                    rule=rule.id,
                    message=str(e),
                    severity=rule.severity,
                    subject=subject,
                    engine="jaxpr",
                )
            )
    return findings, covered


# ------------------------------ orchestration --------------------------- #

def audit_program(
    closed_jaxpr,
    subject: str,
    mesh_axes: Set[str],
    n_donated_state_leaves: Optional[int] = None,
    repo_root: Optional[str] = None,
) -> List[Finding]:
    """Run every per-program jaxpr rule on one traced program."""
    findings = []
    findings += check_no_fp64(closed_jaxpr, subject, repo_root)
    findings += check_collective_axes(
        closed_jaxpr, mesh_axes, subject, repo_root
    )
    if n_donated_state_leaves is not None:
        findings += check_donation(
            closed_jaxpr, n_donated_state_leaves, subject
        )
    findings += check_precision_leak(closed_jaxpr, subject, repo_root)
    # one report per (rule, site, program): scan/vmap bodies repeat the
    # same source eqn once per unrolled context
    seen = set()
    unique = []
    for f in findings:
        key = (f.rule, f.file, f.line, f.subject, f.file is None and f.message)
        if key not in seen:
            seen.add(key)
            unique.append(f)
    return unique


def audit_trainers(kinds: Optional[Sequence[str]] = None, programs=None):
    """Trace all trainer programs via the harness and audit them
    (``programs``: pre-traced :class:`~trlx_tpu.analysis.harness.
    TracedProgram` list, so callers running several jaxpr engines trace
    once).

    Returns a :class:`~trlx_tpu.analysis.findings.Report`.
    """
    from trlx_tpu.analysis import harness
    from trlx_tpu.analysis.findings import Report, filter_suppressed

    report = Report()
    mesh_findings: List[Finding] = []
    for traced in programs if programs is not None else harness.trace_all(kinds):
        report.covered.append(traced.subject)
        mesh_findings += audit_program(
            traced.closed_jaxpr,
            traced.subject,
            traced.mesh_axes,
            traced.n_donated_state_leaves,
        )
    spec_findings, spec_covered = check_partition_specs(harness.audit_mesh())
    mesh_findings += spec_findings
    report.covered += spec_covered
    kept, suppressed = filter_suppressed(mesh_findings)
    report.extend(kept)
    report.suppressed += suppressed
    return report
