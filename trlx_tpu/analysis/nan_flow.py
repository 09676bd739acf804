"""NaN-source dataflow: guard-dominance analysis over traced jaxprs.

Engine 3 of ``trlx_tpu.analysis``. The fsdp/tp PPO divergence (ROADMAP
"Open items") is a *numeric* failure: some equation produced the first
NaN/Inf, and some unguarded op upstream made it possible. This engine
walks every traced program's jaxpr in dataflow order, tracking per-value
facts a guard establishes —

- ``lo``/``hi``: statically known bounds (``clamp``, ``max(x, c)``,
  interval arithmetic through ``add``/``sub``/``mul``/``exp``/...);
- ``pos``/``nonzero``: strict positivity (``x**2 + eps``, softmax
  denominators whose max element is provably included);
- ``neg_inf_mask``: the value may hold ``-inf``/huge-negative fill
  written by a ``where``-style mask (so ``exp`` of it can be exactly 0);

— and flags ops that can mint a NaN/Inf when their operands lack the
matching guard:

- ``nan-unguarded``: ``div`` by a possibly-zero denominator, ``log``/
  ``rsqrt`` of a possibly-nonpositive operand, ``sqrt``/non-integer
  ``pow`` of a possibly-negative operand, ``exp`` of an operand with no
  static upper bound (overflow to inf — the classic unclipped PPO
  ratio).
- ``where-grad-trap``: the same unguarded op, but its output feeds a
  ``select_n`` — the ``where(mask, f(x), 0)`` pattern whose *backward*
  pass evaluates ``f'(x)`` on the masked lane and multiplies the
  inf/NaN by a zero cotangent, producing NaN gradients even though the
  forward value is masked (guard the *input*, not the output).
- ``inf-mask-softmax``: a softmax-style denominator (sum of ``exp``)
  built from a ``-inf``-masked input — a fully-masked row divides 0/0.

Attribution mirrors the precision-leak rule: a finding is reported only
when the op's *innermost* traced frame is repo code (jax/flax/optax own
their internal numerics — ``jax.nn.softmax`` guards itself). Intentional
sites are curated in :data:`NAN_ALLOWLIST`, not inline-suppressed, so
kernel code stays clean and each exemption carries its justification.

Two softmax structural patterns are recognized (interval facts alone
cannot prove them):

- ``x - max(x)`` (same operand, possibly through ``stop_gradient``) is
  bounded above by 0, so its ``exp`` cannot overflow;
- ``sum(exp(x - max(x)))`` includes the max element, so it is >= 1 —
  a valid ``log``/``div`` guard — *unless* the input was -inf-masked.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple

from trlx_tpu.analysis.findings import Finding
from trlx_tpu.analysis.jaxpr_audit import (
    _repo_frame, called_jaxpr, frame_function, is_jit_eqn,
)
from trlx_tpu.analysis.registry import get_rule

# (file suffix, function name) pairs allowed to run the flagged op
# unguarded; None matches the whole file. Every entry documents why the
# site cannot actually mint a NaN (a dynamic invariant the dataflow
# cannot see). Extend here rather than suppressing inline in kernels.
NAN_ALLOWLIST: Sequence[Tuple[str, Optional[str]]] = (
    # online-softmax kernels: exp(s - m) where m is the *running* row max
    # carried through the scan — dynamically s - m <= 0, but the carry
    # enters the body jaxpr with no static facts
    ("ops/flash_attention.py", None),
    ("ops/ring_attention.py", None),
    # decode-time top-p/min-length filtering fills logits with -inf by
    # design; the sampler always leaves at least one finite logit (the
    # top-1 survives any top-p threshold, and eos suppression only masks
    # one column)
    ("ops/sampling.py", None),
    # causal self-attention softmax over -1e9/-inf-masked logits: every
    # live query row sees at least its own position (the causal band
    # includes the diagonal), so the denominator keeps one exp(0) term;
    # fully-padded rows produce garbage that response_forward's
    # position slicing and the loss masks never read
    ("ops/attention.py", "dot_product_attention"),
)

_BIG_NEG = -1e8  # mask fills at or below this count as "-inf-like"


@dataclass(frozen=True)
class Fact:
    """Statically-known properties of one jaxpr value (NaN-free unless
    a flagged op mints one — facts describe the *intended* range)."""

    lo: Optional[float] = None  # x >= lo elementwise
    hi: Optional[float] = None  # x <= hi elementwise
    pos: bool = False  # x > 0 strictly
    nonzero: bool = False
    neg_inf_mask: bool = False  # may hold a -inf-like mask fill

    @property
    def nonneg(self) -> bool:
        return self.pos or (self.lo is not None and self.lo >= 0)

    def meet(self, other: "Fact") -> "Fact":
        """Facts that hold for a value that may be either input."""
        lo = None
        if self.lo is not None and other.lo is not None:
            lo = min(self.lo, other.lo)
        hi = None
        if self.hi is not None and other.hi is not None:
            hi = max(self.hi, other.hi)
        return Fact(
            lo=lo,
            hi=hi,
            pos=self.pos and other.pos,
            nonzero=self.nonzero and other.nonzero,
            neg_inf_mask=self.neg_inf_mask or other.neg_inf_mask,
        )


TOP = Fact()


def _const_fact(value) -> Fact:
    import numpy as np

    try:
        arr = np.asarray(value)
    except Exception:
        return TOP
    if arr.dtype.kind not in "fiub" and arr.dtype.name not in (
        "bfloat16", "float16"  # ml_dtypes report numpy kind 'V'
    ):
        return TOP
    if arr.size == 0 or arr.size > 1 << 22:
        return TOP
    arr64 = arr.astype(np.float64)
    if np.isnan(arr64).any():
        return Fact(neg_inf_mask=False)
    lo = float(arr64.min())
    hi = float(arr64.max())
    return Fact(
        lo=lo if math.isfinite(lo) else None,
        hi=hi if math.isfinite(hi) else None,
        pos=lo > 0,
        nonzero=bool((arr64 != 0).all()),
        neg_inf_mask=lo <= _BIG_NEG,
    )


def _add(a: Fact, b: Fact) -> Fact:
    lo = a.lo + b.lo if a.lo is not None and b.lo is not None else None
    hi = a.hi + b.hi if a.hi is not None and b.hi is not None else None
    return Fact(
        lo=lo,
        hi=hi,
        # pos + nonneg stays strictly positive (the classic `x**2 + eps`)
        pos=(a.pos and b.nonneg) or (b.pos and a.nonneg) or bool(lo and lo > 0),
        nonzero=bool(lo is not None and lo > 0) or bool(hi is not None and hi < 0),
        neg_inf_mask=a.neg_inf_mask or b.neg_inf_mask,
    )


def _sub(a: Fact, b: Fact) -> Fact:
    return _add(a, Fact(
        lo=-b.hi if b.hi is not None else None,
        hi=-b.lo if b.lo is not None else None,
        pos=False,
        neg_inf_mask=b.neg_inf_mask,
    ))


def _mul(a: Fact, b: Fact) -> Fact:
    lo = hi = None
    if None not in (a.lo, a.hi, b.lo, b.hi):
        prods = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
        lo, hi = min(prods), max(prods)
    return Fact(
        lo=0.0 if (a.nonneg and b.nonneg and lo is None) else lo,
        hi=hi,
        pos=a.pos and b.pos,
        nonzero=a.nonzero and b.nonzero,
        neg_inf_mask=a.neg_inf_mask or b.neg_inf_mask,
    )


_IDENTITY_PRIMS = {
    "broadcast_in_dim", "reshape", "transpose", "squeeze", "expand_dims",
    "slice", "dynamic_slice", "rev", "copy", "stop_gradient",
    "reduce_precision", "sharding_constraint", "device_put", "gather",
    "reduce_max", "reduce_min", "cumsum", "sort", "pad",
    "optimization_barrier", "convert_element_type", "real", "tile",
}


def _is_int_const(fact: Fact) -> bool:
    return (
        fact.lo is not None
        and fact.hi is not None
        and fact.lo == fact.hi
        and float(fact.lo).is_integer()
    )


class _Analyzer:
    """One program's dataflow walk; collects findings."""

    def __init__(self, subject: str, repo_root: str,
                 allowlist: Sequence[Tuple[str, Optional[str]]]):
        self.subject = subject
        self.repo_root = repo_root
        self.allowlist = allowlist
        self.findings: List[Finding] = []

    # ----------------------------- helpers ------------------------------ #

    def _read(self, env: Dict, var) -> Fact:
        from jax._src.core import Literal

        if isinstance(var, Literal):
            return _const_fact(var.val)
        return env.get(var, TOP)

    def _source_of(self, producers: Dict, var):
        """The eqn that produced ``var`` at this jaxpr level, or None."""
        return producers.get(id(var))

    def _is_max_shift(self, eqn, env: Dict, producers: Dict) -> bool:
        """``sub(x, reduce_max(x))`` (through stop_gradient/broadcast) —
        bounded above by 0."""
        if eqn.primitive.name != "sub":
            return False
        x, m = eqn.invars
        m_eqn = self._source_of(producers, m)
        # peel broadcast/reshape/stop_gradient wrappers around the max
        seen = 0
        while m_eqn is not None and seen < 6:
            name = m_eqn.primitive.name
            if name == "reduce_max":
                root = m_eqn.invars[0]
                return root is x or self._same_origin(root, x, producers)
            if name in _IDENTITY_PRIMS or name == "custom_jvp_call":
                m_eqn = self._source_of(producers, m_eqn.invars[0])
                seen += 1
                continue
            if name == "max":
                # jax.nn.softmax emits max(-inf, reduce_max(x)) — a no-op
                # floor; peel through the non-literal operand
                from jax._src.core import Literal

                operands = [
                    v for v in m_eqn.invars if not isinstance(v, Literal)
                ]
                if len(operands) == 1:
                    m_eqn = self._source_of(producers, operands[0])
                    seen += 1
                    continue
            return False
        return False

    def _same_origin(self, a, b, producers, depth: int = 4) -> bool:
        """Whether two vars trace to one producer through identity prims."""
        def root(v):
            for _ in range(depth):
                e = self._source_of(producers, v)
                if e is None or e.primitive.name not in _IDENTITY_PRIMS:
                    return v
                v = e.invars[0]
            return v

        return root(a) is root(b)

    def _library_owned(self, eqn) -> bool:
        """Whether the innermost non-jax raw frame is third-party code
        (optax/flax register traceback exclusions, so their internals
        *attribute* to the repo call line — but they still own the
        numerics of ops they wrote, e.g. adamw's eps-guarded div)."""
        source_info = getattr(eqn, "source_info", None)
        tb = getattr(source_info, "traceback", None)
        if tb is None:
            return False
        for frame in tb.frames:  # a traceback without `frames` raises
            fn = frame.file_name
            if "/jax/" in fn or "/jaxlib/" in fn:
                continue  # jax machinery is transparent
            return self.repo_root not in fn
        return False

    def _report(self, eqn, rule_id: str, message: str) -> None:
        frame = _repo_frame(eqn, self.repo_root, innermost_only=True)
        if frame is None:
            return  # library-internal numerics guard themselves
        if self._library_owned(eqn):
            return  # optax/flax wrote the op; they own its guards
        rel = frame.file_name
        if self.repo_root in rel:
            rel = rel.split(self.repo_root, 1)[1].lstrip("/")
        for file_suffix, func in self.allowlist:
            if file_suffix and not rel.endswith(file_suffix):
                continue
            if func is not None and frame_function(frame) != func:
                continue
            return  # curated: the site's invariant is documented
        rule = get_rule(rule_id)
        self.findings.append(
            Finding(
                rule=rule.id,
                message=message,
                severity=rule.severity,
                file=frame.file_name,
                line=frame.start_line,
                subject=self.subject,
                engine="nanflow",
            )
        )

    # ------------------------------ walk -------------------------------- #

    def walk(self, jaxpr, consts: Sequence[Any],
             in_facts: Sequence[Fact]) -> List[Fact]:
        env: Dict = {}
        producers: Dict[int, Any] = {}
        consumers: Dict[int, List] = {}
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                consumers.setdefault(id(v), []).append(eqn)

        for var, val in zip(jaxpr.constvars, consts):
            env[var] = _const_fact(val)
        for var, fact in zip(jaxpr.invars, in_facts):
            env[var] = fact

        for eqn in jaxpr.eqns:
            facts = [self._read(env, v) for v in eqn.invars]
            outs = self._transfer(eqn, facts, env, producers)
            self._check(eqn, facts, env, producers, consumers)
            for v, f in zip(eqn.outvars, outs):
                env[v] = f
                producers[id(v)] = eqn
        return [self._read(env, v) for v in jaxpr.outvars]

    def _sub_jaxpr_facts(self, eqn, facts: List[Fact]) -> Optional[List[Fact]]:
        """Recurse into call-like sub-jaxprs with mapped input facts;
        returns the sub-program's output facts where they map 1:1 onto
        the eqn's outputs (pjit-wrapped helpers like ``jnp.clip`` /
        ``jnp.where`` must not erase the guard they establish)."""
        name = eqn.primitive.name
        params = eqn.params
        closed = called_jaxpr(eqn)
        if closed is not None:
            inner = getattr(closed, "jaxpr", closed)
            return self.walk(inner, getattr(closed, "consts", ()), facts)
        if name == "scan":
            closed = params["jaxpr"]
            inner = getattr(closed, "jaxpr", closed)
            n_consts = params.get("num_consts", 0)
            n_carry = params.get("num_carry", 0)
            # consts keep their facts; carry iterates to an unknown fixed
            # point -> TOP; xs facts hold per-slice (bounds are elementwise)
            body_facts = (
                facts[:n_consts]
                + [TOP] * n_carry
                + facts[n_consts + n_carry:]
            )
            self.walk(inner, getattr(closed, "consts", ()), body_facts)
            return None  # outs went through the unknown carry
        if name == "while":
            for key in ("cond_jaxpr", "body_jaxpr"):
                closed = params[key]
                inner = getattr(closed, "jaxpr", closed)
                self.walk(inner, getattr(closed, "consts", ()),
                          [TOP] * len(inner.invars))
            return None
        if name == "cond":
            branch_outs = []
            for closed in params.get("branches", ()):
                inner = getattr(closed, "jaxpr", closed)
                branch_outs.append(
                    self.walk(inner, getattr(closed, "consts", ()), facts[1:])
                )
            if branch_outs and all(
                len(o) == len(branch_outs[0]) for o in branch_outs
            ):
                met = branch_outs[0]
                for outs in branch_outs[1:]:
                    met = [a.meet(b) for a, b in zip(met, outs)]
                return met
            return None
        if name == "shard_map":
            inner = params.get("jaxpr")
            if inner is not None:
                inner = getattr(inner, "jaxpr", inner)
                return self.walk(inner, (), facts)
        return None

    def _transfer(self, eqn, facts: List[Fact], env, producers) -> List[Fact]:
        name = eqn.primitive.name
        n_out = len(eqn.outvars)

        if called_jaxpr(eqn) is not None or name in (
                "scan", "while", "cond", "shard_map"):
            sub_out = self._sub_jaxpr_facts(eqn, facts)
            if sub_out is not None and len(sub_out) == n_out:
                return sub_out
            return [TOP] * n_out

        if name in _IDENTITY_PRIMS:
            if name == "convert_element_type":
                # int casts of bool masks etc. keep facts
                return [facts[0]]
            return [facts[0] if facts else TOP] * n_out
        if name == "concatenate":
            out = facts[0]
            for f in facts[1:]:
                out = out.meet(f)
            return [out]
        if name == "add":
            return [_add(facts[0], facts[1])]
        if name == "sub":
            if self._is_max_shift(eqn, env, producers):
                out = replace(_sub(facts[0], facts[1]), hi=0.0)
                return [out]
            return [_sub(facts[0], facts[1])]
        if name == "mul":
            a, b = eqn.invars
            if a is b:  # x * x
                sq = _mul(facts[0], facts[1])
                return [replace(sq, lo=max(0.0, sq.lo or 0.0))]
            return [_mul(facts[0], facts[1])]
        if name == "neg":
            f = facts[0]
            return [Fact(
                lo=-f.hi if f.hi is not None else None,
                hi=-f.lo if f.lo is not None else None,
                nonzero=f.nonzero,
            )]
        if name == "abs":
            f = facts[0]
            hi = None
            if f.lo is not None and f.hi is not None:
                hi = max(abs(f.lo), abs(f.hi))
            return [Fact(lo=0.0, hi=hi, pos=f.nonzero or f.pos,
                         nonzero=f.nonzero)]
        if name in ("max", "pmax"):
            a, b = facts[0], facts[1]
            los = [x for x in (a.lo, b.lo) if x is not None]
            hi = None
            if a.hi is not None and b.hi is not None:
                hi = max(a.hi, b.hi)
            lo = max(los) if los else None
            return [Fact(lo=lo, hi=hi, pos=a.pos or b.pos or bool(lo and lo > 0),
                         nonzero=bool(lo is not None and lo > 0))]
        if name in ("min", "pmin"):
            a, b = facts[0], facts[1]
            his = [x for x in (a.hi, b.hi) if x is not None]
            lo = None
            if a.lo is not None and b.lo is not None:
                lo = min(a.lo, b.lo)
            return [Fact(lo=lo, hi=min(his) if his else None,
                         pos=a.pos and b.pos,
                         neg_inf_mask=a.neg_inf_mask or b.neg_inf_mask)]
        if name == "clamp":  # clamp(lo, x, hi)
            lo_f, x_f, hi_f = facts
            return [Fact(lo=lo_f.lo, hi=hi_f.hi,
                         pos=lo_f.pos, nonzero=lo_f.pos)]
        if name == "exp":
            f = facts[0]
            hi = math.exp(min(f.hi, 700.0)) if f.hi is not None else None
            # exp(x) > 0 unless x can be a -inf-like mask fill (exp -> 0)
            return [Fact(lo=0.0, hi=hi, pos=not f.neg_inf_mask,
                         nonzero=not f.neg_inf_mask,
                         neg_inf_mask=f.neg_inf_mask)]
        if name == "logistic":
            return [Fact(lo=0.0, hi=1.0, pos=not facts[0].neg_inf_mask)]
        if name == "tanh":
            return [Fact(lo=-1.0, hi=1.0)]
        if name == "erf":
            return [Fact(lo=-1.0, hi=1.0)]
        if name == "log":
            f = facts[0]
            lo = math.log(f.lo) if f.lo is not None and f.lo > 0 else None
            return [Fact(
                lo=lo,
                hi=math.log(f.hi) if f.hi and f.hi > 0 else None,
                pos=bool(lo is not None and lo > 0),
                nonzero=bool(lo is not None and lo > 0)
                or bool(f.hi is not None and f.hi < 1),
            )]
        if name == "sqrt":
            f = facts[0]
            return [Fact(lo=0.0, pos=f.pos, nonzero=f.pos,
                         hi=math.sqrt(f.hi) if f.hi and f.hi >= 0 else None)]
        if name == "rsqrt":
            return [Fact(lo=0.0, pos=facts[0].pos, nonzero=facts[0].pos)]
        if name == "integer_pow":
            y = eqn.params.get("y", 1)
            f = facts[0]
            if y < 0:
                # x**-k is a division: inf at 0, and magnitude bounds
                # invert — no sound facts without a nonzero guarantee
                return [Fact(lo=0.0 if y % 2 == 0 else None,
                             pos=f.pos, nonzero=f.nonzero)]
            if y % 2 == 0:
                hi = None
                if f.lo is not None and f.hi is not None:
                    hi = max(abs(f.lo), abs(f.hi)) ** y
                return [Fact(lo=0.0, hi=hi, pos=f.nonzero, nonzero=f.nonzero)]
            return [TOP]
        if name == "div":
            a, b = facts[0], facts[1]
            out_pos = a.pos and b.pos
            hi = None
            if a.hi is not None and b.lo is not None and b.lo > 0:
                if a.hi >= 0:
                    # positive numerators are largest over the smallest
                    # denominator
                    hi = a.hi / b.lo
                elif b.hi is not None:
                    # negative numerators are largest (closest to 0) over
                    # the LARGEST denominator
                    hi = a.hi / b.hi
                else:
                    hi = 0.0  # a.hi < 0, denominator unbounded above
            lo = 0.0 if (a.nonneg and b.pos) else None
            return [Fact(lo=lo, hi=hi, pos=out_pos, nonzero=a.nonzero and b.nonzero)]
        if name == "reduce_sum":
            f = facts[0]
            # sum(exp(x - max(x))) includes the max element -> >= 1;
            # matched here so softmax denominators count as guards
            src = self._source_of(producers, eqn.invars[0])
            if (
                src is not None
                and src.primitive.name == "exp"
                and f.pos
                and self._source_of(producers, src.invars[0]) is not None
                and self._is_max_shift(
                    self._source_of(producers, src.invars[0]), env, producers
                )
            ):
                return [Fact(lo=1.0, pos=True, nonzero=True)]
            return [Fact(
                lo=0.0 if f.nonneg else None,
                pos=f.pos,
                neg_inf_mask=f.neg_inf_mask,
            )]
        if name in ("reduce_prod",):
            f = facts[0]
            return [Fact(pos=f.pos, nonzero=f.nonzero)]
        if name == "select_n":
            # select_n(pred, case0, case1, ...): value is one of the cases
            out = facts[1]
            for f in facts[2:]:
                out = out.meet(f)
            return [out]
        if name == "pow":
            base, expo = facts[0], facts[1]
            if base.pos:
                hi = None
                if (
                    base.hi is not None
                    and 0 < base.hi <= 1
                    and expo.lo is not None
                    and expo.lo >= 0
                ):
                    # c^x for c in (0,1], x >= x_lo: bounded by c^x_lo
                    # (adamw's bias correction 1 - b^count needs this)
                    hi = base.hi ** expo.lo
                return [Fact(lo=0.0, hi=hi, pos=True, nonzero=True)]
            return [TOP]
        if name in ("dot_general",):
            return [TOP]
        if name in ("sign",):
            return [Fact(lo=-1.0, hi=1.0)]
        if name in ("cos", "sin"):
            return [Fact(lo=-1.0, hi=1.0)]
        if name in ("iota",):
            return [Fact(lo=0.0)]
        if name in ("argmax", "argmin"):
            return [Fact(lo=0.0)]
        if name in ("and", "or", "not", "xor", "eq", "ne", "lt", "le",
                    "gt", "ge", "is_finite"):
            return [Fact(lo=0.0, hi=1.0)]
        if name == "one_hot":
            return [Fact(lo=0.0, hi=1.0)]
        if name in ("psum", "psum2", "all_gather", "reduce_scatter",
                    "all_to_all", "ppermute", "pbroadcast"):
            f = facts[0] if facts else TOP
            return [Fact(lo=0.0 if f.nonneg else None, pos=f.pos,
                         neg_inf_mask=f.neg_inf_mask)] * n_out
        return [TOP] * n_out

    # ----------------------------- checks ------------------------------- #

    def _emit(self, eqn, consumers, kind: str, detail: str) -> None:
        """Pick the rule id: the where-grad-trap variant when the risky
        op's output feeds a select_n at this jaxpr level."""
        def _is_select(c) -> bool:
            # jnp.where arrives as a jit call named `_where` wrapping select_n
            return c.primitive.name == "select_n" or (
                is_jit_eqn(c) and c.params.get("name") == "_where"
            )

        feeds_select = any(
            _is_select(c)
            for v in eqn.outvars
            for c in consumers.get(id(v), ())
        )
        if feeds_select:
            self._report(
                eqn, "where-grad-trap",
                f"{detail} — and its output feeds a `where`/`select`: the "
                "backward pass still evaluates the non-total op on masked "
                "lanes and multiplies inf by a zero cotangent (NaN grads); "
                "guard the op's *input* instead",
            )
        else:
            self._report(eqn, "nan-unguarded", detail)

    def _check(self, eqn, facts: List[Fact], env, producers, consumers) -> None:
        import numpy as np

        name = eqn.primitive.name
        if name == "div":
            aval = getattr(eqn.outvars[0], "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is None or np.dtype(dtype).kind != "f":
                return
            den = facts[1]
            if den.pos or den.nonzero:
                return
            if den.neg_inf_mask or (
                den.nonneg and self._den_is_masked_softmax(eqn, producers)
            ):
                self._report(
                    eqn, "inf-mask-softmax",
                    "softmax denominator built from a -inf-masked input: a "
                    "fully-masked row sums exp() to 0 and divides 0/0; "
                    "re-select the output or keep one unmasked column",
                )
                return
            self._emit(
                eqn, consumers,
                "div",
                "`div` by a denominator not proven nonzero — guard with "
                "`+eps`, `maximum(x, eps)`, or a `where` on the input",
            )
        elif name in ("log", "log1p"):
            f = facts[0]
            floor = -1.0 if name == "log1p" else 0.0
            if f.lo is not None and f.lo > floor:
                return
            if f.pos and name == "log":
                return
            self._emit(
                eqn, consumers, name,
                f"`{name}` of an operand not proven > {floor:g} — NaN on "
                "the masked/zero lane; guard the input with `+eps` or "
                "`maximum`",
            )
        elif name == "rsqrt":
            f = facts[0]
            if f.pos:
                return
            self._emit(
                eqn, consumers, name,
                "`rsqrt` of an operand not proven positive — inf at 0, NaN "
                "below; guard with `+eps` (eps-free rsqrt is the classic "
                "norm/whitening divergence)",
            )
        elif name == "sqrt":
            f = facts[0]
            if f.nonneg:
                return
            self._emit(
                eqn, consumers, name,
                "`sqrt` of an operand not proven >= 0 — NaN on negative "
                "inputs; guard with `maximum(x, 0)` or square the operand",
            )
        elif name in ("exp", "exp2"):
            f = facts[0]
            aval = getattr(eqn.outvars[0], "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is None or np.dtype(dtype).kind != "f":
                return
            # overflow guard: any finite static upper bound below the f32
            # overflow threshold (~88.7; bf16 shares the f32 exponent)
            if f.hi is not None and f.hi <= 80.0:
                return
            self._emit(
                eqn, consumers, name,
                f"`{name}` of an operand with no static upper bound — "
                "overflows to inf (the unclipped-ratio PPO trap); clamp "
                "the exponent (e.g. `clip(log_ratio, -c, c)`) or subtract "
                "a rowwise max first",
            )
        elif name == "pow":
            base, expo = facts[0], facts[1]
            if base.nonneg or _is_int_const(expo):
                return
            self._emit(
                eqn, consumers, name,
                "`pow` with a possibly-negative base and non-integer "
                "exponent — NaN; guard the base or use an integer power",
            )
        elif name == "integer_pow":
            y = eqn.params.get("y", 1)
            if y >= 0:
                return
            f = facts[0]
            aval = getattr(eqn.outvars[0], "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is None or np.dtype(dtype).kind != "f":
                return
            if f.nonzero or f.pos:
                return
            self._emit(
                eqn, consumers, name,
                f"`x**{y}` (a reciprocal power) of an operand not proven "
                "nonzero — inf at 0; guard the base with `+eps` or "
                "`maximum`",
            )

    def _den_is_masked_softmax(self, eqn, producers) -> bool:
        """div denominator = reduce_sum(exp(masked)) where the exp input
        carries a -inf-like fill."""
        src = self._source_of(producers, eqn.invars[1])
        hops = 0
        while src is not None and hops < 4:
            n = src.primitive.name
            if n == "reduce_sum":
                inner = self._source_of(producers, src.invars[0])
                return bool(inner is not None and inner.primitive.name == "exp")
            if n in _IDENTITY_PRIMS or n == "add":
                src = self._source_of(producers, src.invars[0])
                hops += 1
                continue
            return False
        return False


def analyze_program(
    closed_jaxpr,
    subject: str,
    repo_root: Optional[str] = None,
    allowlist: Sequence[Tuple[str, Optional[str]]] = NAN_ALLOWLIST,
    in_facts: Optional[Sequence[Fact]] = None,
) -> List[Finding]:
    """Run the NaN-source dataflow on one traced program."""
    from trlx_tpu.analysis.jaxpr_audit import default_repo_root

    repo_root = repo_root or default_repo_root()
    inner = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    consts = getattr(closed_jaxpr, "consts", ())
    analyzer = _Analyzer(subject, repo_root, allowlist)
    facts = list(in_facts or [])
    facts = facts[:len(inner.invars)]
    facts += [TOP] * (len(inner.invars) - len(facts))
    analyzer.walk(inner, consts, facts)
    # one report per (rule, site): scan bodies and vmapped lanes repeat
    # the same source eqn in several trace contexts
    seen = set()
    unique: List[Finding] = []
    for f in analyzer.findings:
        key = (f.rule, f.file, f.line)
        if key not in seen:
            seen.add(key)
            unique.append(f)
    return unique


def input_facts(paths: Sequence[str]) -> List[Fact]:
    """Data-contract facts per program input, derived from its keypath:
    masks and done flags are 0/1, behavior logprobs are <= 0, token ids /
    step counters / Adam second moments are nonnegative. These are the
    invariants the trainers' input pipelines maintain — seeding them at
    the program boundary is what lets guards like ``sum(mask) >= ...``
    and ``sqrt(nu)`` prove out."""
    facts: List[Fact] = []
    for path in paths:
        p = path.lower()
        if "mask" in p or "dones" in p:
            facts.append(Fact(lo=0.0, hi=1.0))
        elif "logprob" in p:
            facts.append(Fact(hi=0.0))
        elif (
            "tokens" in p or "input_ids" in p or "_ixs" in p
            or p.endswith(".step") or ".count" in p or p.endswith("count")
        ):
            facts.append(Fact(lo=0.0))
        elif ".nu" in p:  # Adam second moment: EMA of squares
            facts.append(Fact(lo=0.0))
        else:
            facts.append(TOP)
    return facts


def analyze_trainers(kinds=None, programs=None):
    """NaN-flow over the harness's traced trainer programs; returns a
    :class:`~trlx_tpu.analysis.findings.Report`."""
    from trlx_tpu.analysis import harness
    from trlx_tpu.analysis.findings import Report, filter_suppressed

    report = Report()
    findings: List[Finding] = []
    for traced in programs if programs is not None else harness.trace_all(kinds):
        report.covered.append(f"nanflow:{traced.subject}")
        facts = (
            input_facts(traced.input_paths)
            if getattr(traced, "input_paths", None)
            else None
        )
        findings += analyze_program(
            traced.closed_jaxpr, traced.subject, in_facts=facts
        )
    kept, suppressed = filter_suppressed(findings)
    report.extend(kept)
    report.suppressed += suppressed
    return report
