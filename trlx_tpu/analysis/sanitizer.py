"""Sanitizer replay: execute a captured step jaxpr eqn-by-eqn, checking
every intermediate for NaN/Inf.

Engine 5 of ``trlx_tpu.analysis`` — the dynamic complement of the
NaN-flow dataflow. ``python -m trlx_tpu.analysis --sanitize ppo`` builds
the tiny harness trainer (optionally on an explicit ``--mesh``, e.g. the
diverging ``dp=2,fsdp=2,tp=2`` repro), captures its jitted train step as
a jaxpr over the *concrete* trainer state and a plausible rollout batch,
and replays it equation by equation:

- call-like eqns (pjit / remat / custom_vjp / scan / cond) are entered
  recursively, so the first offending equation is an actual primitive
  with source provenance, not "the pjit";
- ``scan`` is re-executed as a Python loop over its body jaxpr, so a NaN
  minted at iteration k of the fused PPO phase is attributed to the body
  equation (and the report says which iteration);
- every output is checked with ``isfinite``; the first non-finite
  equation stops the replay and is reported with its primitive, shapes,
  repo source frame, the parameter paths of any top-level inputs it
  consumed, and the trainer's mesh spec.

Integer/bool outputs are exempt (masks legitimately hold sentinel
values), as are inputs that were already non-finite before the eqn ran —
the report names the *minting* equation, not the propagation.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from trlx_tpu.analysis.findings import Finding, Report
from trlx_tpu.analysis.jaxpr_audit import called_jaxpr
from trlx_tpu.analysis.registry import get_rule


@dataclass
class Offence:
    """The first equation whose output went non-finite."""

    primitive: str
    kind: str  # "nan" | "inf"
    subject: str
    file: Optional[str] = None
    line: Optional[int] = None
    out_shape: str = ""
    iteration: Optional[int] = None  # scan iteration, when inside one
    input_paths: List[str] = field(default_factory=list)
    eqn_str: str = ""

    def describe(self) -> str:
        loc = f"{self.file}:{self.line}" if self.file else "<no repo frame>"
        it = f" (scan iteration {self.iteration})" if self.iteration is not None else ""
        paths = (
            f"; consumes program inputs: {', '.join(self.input_paths)}"
            if self.input_paths
            else ""
        )
        return (
            f"first non-finite intermediate ({self.kind}) minted by "
            f"`{self.primitive}` -> {self.out_shape} at {loc}{it}{paths}"
        )


class _Replayer:
    def __init__(self, repo_root: str, subject: str):
        self.repo_root = repo_root
        self.subject = subject
        self.offence: Optional[Offence] = None
        self._scan_iter: Optional[int] = None

    # --------------------------- value checks --------------------------- #

    def _nonfinite_kind(self, val) -> Optional[str]:
        import numpy as np

        dtype = getattr(val, "dtype", None)
        if dtype is None:
            # plain Python scalars (jaxpr Literals like -inf mask fills)
            if isinstance(val, float):
                import math

                if math.isnan(val):
                    return "nan"
                if math.isinf(val):
                    return "inf"
            return None
        try:
            np_dtype = np.dtype(dtype)
        except TypeError:
            # extended dtypes (typed PRNG keys, `key<fry>`) have no
            # numpy interpretation and no finiteness to check — the
            # engine decode replay's per-row fold_in mints these
            return None
        if np_dtype.kind != "f" and np_dtype.name not in (
            "bfloat16", "float16"  # ml_dtypes report numpy kind 'V'
        ):
            return None
        arr = np.asarray(val)
        if np_dtype.kind != "f":
            arr = arr.astype(np.float32)
        if np.isnan(arr).any():
            return "nan"
        if np.isinf(arr).any():
            return "inf"
        return None

    def _record(self, eqn, invals, outvals, input_names: Dict[int, str]) -> None:
        import numpy as np

        kinds = [self._nonfinite_kind(v) for v in outvals]
        bad = next((k for k in kinds if k), None)
        if bad is None:
            return
        # A NaN is never intentional: record it wherever it first appears
        # (for a poisoned program input, that is its first consumer — the
        # localization the operator wants). An inf *can* be intentional
        # (-inf mask fills, -1e9 biases), so only an inf minted from
        # all-finite inputs counts — genuine overflow, not propagation.
        if bad == "inf" and any(self._nonfinite_kind(v) for v in invals):
            return
        from trlx_tpu.analysis.jaxpr_audit import _repo_frame

        frame = _repo_frame(eqn, self.repo_root)
        shapes = ", ".join(
            str(getattr(v, "shape", "?")) for v in outvals[:3]
        )
        paths = [
            input_names[id(v)]
            for v in eqn.invars
            if id(v) in input_names
        ]
        self.offence = Offence(
            primitive=eqn.primitive.name,
            kind=bad,
            subject=self.subject,
            file=frame.file_name if frame else None,
            line=frame.start_line if frame else None,
            out_shape=shapes,
            iteration=self._scan_iter,
            input_paths=paths,
            eqn_str=str(eqn)[:200],
        )

    # ----------------------------- replay ------------------------------- #

    def replay(
        self,
        jaxpr,
        consts: Sequence[Any],
        args: Sequence[Any],
        input_names: Optional[Dict[int, str]] = None,
        arg_names: Optional[Sequence[Optional[str]]] = None,
    ) -> List[Any]:
        """Evaluate ``jaxpr`` eqn-by-eqn; stops recording at the first
        offence but keeps evaluating (outputs still needed upstream).

        ``arg_names`` labels this jaxpr's invars (parameter paths for the
        top-level call; propagated through call-like eqns)."""
        from jax._src.core import Literal

        env: Dict = {}
        names: Dict[int, str] = dict(input_names or {})

        def read(v):
            return v.val if isinstance(v, Literal) else env[v]

        for var, val in zip(jaxpr.constvars, consts):
            env[var] = val
        for i, (var, val) in enumerate(zip(jaxpr.invars, args)):
            env[var] = val
            if arg_names and i < len(arg_names) and arg_names[i]:
                names[id(var)] = arg_names[i]

        for eqn in jaxpr.eqns:
            invals = [read(v) for v in eqn.invars]
            outvals = self._eval_eqn(eqn, invals, names)
            if not isinstance(outvals, (list, tuple)):
                outvals = [outvals]
            if self.offence is None:
                self._record(eqn, invals, outvals, names)
            for var, val in zip(eqn.outvars, outvals):
                env[var] = val
        return [read(v) for v in jaxpr.outvars]

    def _eval_eqn(self, eqn, invals, names: Dict[int, str]):
        name = eqn.primitive.name
        closed = called_jaxpr(eqn)
        if closed is not None:
            inner = getattr(closed, "jaxpr", closed)
            consts = getattr(closed, "consts", ())
            inner_names = [names.get(id(v)) for v in eqn.invars]
            return self.replay(inner, consts, invals, arg_names=inner_names)
        if name == "scan":
            return self._eval_scan(eqn, invals, names)
        if name == "cond":
            import numpy as np

            branches = eqn.params.get("branches")
            if branches is not None:
                index = int(np.asarray(invals[0]))
                closed = branches[index]
                inner = getattr(closed, "jaxpr", closed)
                return self.replay(
                    inner, getattr(closed, "consts", ()), invals[1:]
                )
        # everything else: execute the primitive whole (impl rules run
        # eagerly outside any trace)
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        out = eqn.primitive.bind(*subfuns, *invals, **bind_params)
        return out

    def _eval_scan(self, eqn, invals, names: Dict[int, str]):
        """Python-loop a scan so each iteration replays the body jaxpr."""
        import jax.numpy as jnp

        params = eqn.params
        closed = params["jaxpr"]
        inner = getattr(closed, "jaxpr", closed)
        consts_vals = getattr(closed, "consts", ())
        n_consts = params.get("num_consts", 0)
        n_carry = params.get("num_carry", 0)
        length = params.get("length")
        reverse = params.get("reverse", False)

        consts = list(invals[:n_consts])
        carry = list(invals[n_consts:n_consts + n_carry])
        xs = list(invals[n_consts + n_carry:])
        if length is None:
            length = xs[0].shape[0] if xs else 0

        const_names = [names.get(id(v)) for v in eqn.invars[:n_consts]]
        ys_acc: List[List[Any]] = []
        order = range(length - 1, -1, -1) if reverse else range(length)
        outer_iter = self._scan_iter
        for i in order:
            slices = [x[i] for x in xs]
            self._scan_iter = i
            outs = self.replay(
                inner,
                consts_vals,
                consts + carry + slices,
                arg_names=const_names + [None] * (n_carry + len(slices)),
            )
            carry = list(outs[:n_carry])
            ys_acc.append(list(outs[n_carry:]))
        self._scan_iter = outer_iter
        if reverse:
            ys_acc.reverse()
        ys = [
            jnp.stack([row[j] for row in ys_acc])
            for j in range(len(ys_acc[0]))
        ] if ys_acc and ys_acc[0] else []
        return carry + ys


@dataclass
class SanitizeResult:
    subject: str
    mesh: Dict[str, int]
    n_eqns_checked: int
    offence: Optional[Offence]

    @property
    def clean(self) -> bool:
        return self.offence is None

    def to_report(self) -> Report:
        report = Report()
        report.covered.append(f"sanitize:{self.subject}")
        if self.offence is not None:
            rule = get_rule("sanitizer-nonfinite")
            report.extend([
                Finding(
                    rule=rule.id,
                    message=self.offence.describe()
                    + f"; mesh={self.mesh}",
                    severity=rule.severity,
                    file=_relpath(self.offence.file),
                    line=self.offence.line,
                    subject=self.subject,
                    engine="sanitizer",
                )
            ])
        return report

    def format_text(self) -> str:
        head = f"sanitize[{self.subject}] mesh={self.mesh}"
        if self.clean:
            return f"{head}: clean — all intermediates finite"
        return f"{head}:\n  {self.offence.describe()}"


def _relpath(path: Optional[str]) -> Optional[str]:
    if path is None:
        return None
    from trlx_tpu.analysis.jaxpr_audit import default_repo_root

    root = default_repo_root()
    if root in path:
        return path.split(root, 1)[1].lstrip("/")
    return path


def _flat_input_names(state, mb) -> List[str]:
    """Flat keypath labels for the (state, minibatch) argument tree, in
    the order make_jaxpr flattens them."""
    from trlx_tpu.analysis.harness import flat_input_paths

    return flat_input_paths(state, mb, prefixes=("state", "batch"))


def sanitize_jaxpr(
    closed_jaxpr,
    args: Sequence[Any],
    subject: str = "program",
    mesh: Optional[Dict[str, int]] = None,
    repo_root: Optional[str] = None,
    arg_names: Optional[Sequence[Optional[str]]] = None,
) -> SanitizeResult:
    """Replay a captured (closed) jaxpr on concrete ``args``."""
    from trlx_tpu.analysis.jaxpr_audit import default_repo_root, iter_eqns

    inner = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    replayer = _Replayer(repo_root or default_repo_root(), subject)
    replayer.replay(
        inner, getattr(closed_jaxpr, "consts", ()), list(args),
        arg_names=list(arg_names or []),
    )
    n = sum(1 for _ in iter_eqns(closed_jaxpr))
    return SanitizeResult(
        subject=subject,
        mesh=dict(mesh or {}),
        n_eqns_checked=n,
        offence=replayer.offence,
    )


def plant_nan(state):
    """Poison one parameter leaf (NaN at flat index 0) so the replay has
    a deterministic first-NaN to localize — the CLI's ``--plant-nan``
    self-check that the sanitizer actually detects and attributes."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(state.params)
    first = leaves[0]
    poisoned = first.at[(0,) * first.ndim].set(jnp.nan)
    params = jax.tree_util.tree_unflatten(treedef, [poisoned] + leaves[1:])
    # .replace keeps every other field (ILQL's state carries
    # target_q_params beyond the common params/opt_state/step)
    return state.replace(params=params)


def sanitize_engine_step(
    kind: str,
    mesh: Optional[Dict[str, int]] = None,
    plant: bool = False,
    seed: int = 0,
) -> SanitizeResult:
    """Replay the continuous-batching engine's ``decode_step``, then
    its speculative ``verify_step`` (``trlx_tpu/inference/engine.py``),
    eqn-by-eqn on a concretely prefilled slot pool.

    The state is produced the way production produces it — a real
    ``start_phase`` + admission prefill over random prompts — so a
    NaN minted anywhere in the decode path (paged-cache gather, per-row
    bias, token selection, value head) is localized to its first
    offending equation exactly like ``--sanitize``'s train-step replay.
    The verify replay runs the multi-token drafted pass
    (docs/inference.md "Speculative decoding") on a separately built
    spec-enabled engine with every slot carrying a full-width random
    draft — acceptance is irrelevant to the replay; rejected columns
    still exercise the OOB-sentinel write and masked-softmax paths
    where a NaN would mint. ``plant`` poisons one param leaf first
    (the CLI self-check; the decode replay finds it and short-circuits).
    """
    import numpy as np

    import jax

    from trlx_tpu.analysis import harness
    from trlx_tpu.analysis.harness import flat_input_paths

    if kind != "ppo":
        raise ValueError(
            "--engine-step replays the causal continuous-batching "
            f"engine via the ppo trainer; got {kind!r}"
        )
    trainer = harness.build_trainer(kind, mesh)
    params = trainer.state.params
    if plant:
        params = plant_nan(trainer.state).params
    engine = trainer.rollout_engine_obj
    rng = np.random.default_rng(seed)
    A, Q = engine.admit_width, engine.Q
    vocab = getattr(trainer.model_config, "vocab_size", 32)
    ids = rng.integers(1, max(2, vocab - 2), (A, Q)).astype(np.int32)
    mask = np.ones((A, Q), np.int32)
    engine.start_phase(params, jax.random.PRNGKey(seed))
    engine.submit(ids, mask)
    engine._admit()  # concrete prefill — the replay's input state
    state = engine._state

    closed = jax.make_jaxpr(engine.decode_step_jit)(params, state)
    args = jax.tree_util.tree_leaves((params, state))
    names = flat_input_paths(params, state, prefixes=("params", "state"))
    mesh_shape = {k: int(v) for k, v in trainer.mesh.shape.items()}
    decode_result = sanitize_jaxpr(
        closed,
        args,
        subject=f"{kind}.engine_decode_step"
        + (".planted" if plant else ""),
        mesh=mesh_shape,
        arg_names=names,
    )
    if decode_result.offence is not None:
        return decode_result

    import jax.numpy as jnp

    from trlx_tpu.inference.engine import ContinuousBatchingEngine

    spec_engine = ContinuousBatchingEngine(
        apply_fn=engine._apply_fn,
        init_cache_fn=engine._init_cache_fn,
        gen_config=engine.gen_config,
        query_length=engine.Q,
        vocab_size=engine.vocab_size,
        num_slots=engine.num_slots,
        admit_width=engine.admit_width,
        harvest_width=engine.harvest_width,
        block_size=engine.block_size,
        mesh=engine.mesh,
        param_shardings=engine._param_shardings,
        cache_sharding=engine._cache_sharding,
        with_values=engine.with_values,
        spec_max_draft=4,
    )
    if spec_engine.verify_step_jit is None:
        return decode_result
    spec_engine.start_phase(params, jax.random.PRNGKey(seed))
    spec_engine.submit(ids, mask)
    spec_engine._admit()
    B, D = spec_engine.num_slots, spec_engine.spec_max_draft
    draft = jnp.asarray(
        rng.integers(1, max(2, vocab - 2), (B, D)).astype(np.int32)
    )
    lens = jnp.full((B,), D, jnp.int32)
    verify_args_tree = (params, spec_engine._state, draft, lens)
    closed_v = jax.make_jaxpr(spec_engine.verify_step_jit)(
        *verify_args_tree
    )
    verify_result = sanitize_jaxpr(
        closed_v,
        jax.tree_util.tree_leaves(verify_args_tree),
        subject=f"{kind}.engine_verify_step",
        mesh=mesh_shape,
        arg_names=flat_input_paths(
            *verify_args_tree,
            prefixes=("params", "state", "draft", "draft_len"),
        ),
    )
    if verify_result.offence is not None:
        return verify_result
    return SanitizeResult(
        subject=f"{kind}.engine_decode_step+engine_verify_step",
        mesh=mesh_shape,
        n_eqns_checked=(
            decode_result.n_eqns_checked + verify_result.n_eqns_checked
        ),
        offence=None,
    )


def sanitize_trainer(
    kind: str,
    mesh: Optional[Dict[str, int]] = None,
    plant: bool = False,
    seed: int = 0,
    streamed: bool = False,
) -> SanitizeResult:
    """Build the tiny harness trainer, capture its train-step jaxpr over
    concrete (state, batch), and replay eqn-by-eqn.

    ``streamed=True`` replays the *streamed* epoch-1 step of the
    overlapped collect→train phase (docs/async_pipeline.md): the
    minibatch is produced the way the streamed dispatcher produces it —
    rollout rows land chunk-by-chunk in the streaming buffer
    (``dynamic_update_slice`` writes, the SPMD-safe path) and the
    replayed step consumes the first plan minibatch gathered from the
    partially-identical store — so sharded-store corruption of the class
    the PR-2 concat bug belonged to shows up as the replay's first
    non-finite equation."""
    import jax

    from trlx_tpu.analysis import harness

    trainer = harness.build_trainer(kind, mesh)
    state = trainer.state
    if plant:
        state = plant_nan(state)
    mb = harness.concrete_minibatch(trainer, kind, seed=seed)
    subject = f"{kind}.train_step"
    if streamed:
        if kind == "ilql":
            raise ValueError(
                "--streamed replays the PPO-family streamed phase; ILQL "
                "has no streamed collect→train path"
            )
        from trlx_tpu.pipeline.ppo_buffer import make_stream_plan

        B = trainer.config.train.batch_size
        plan = make_stream_plan(
            B, B, trainer.config.method.ppo_epochs, seed
        )
        trainer.buffer.clear_history()
        trainer.buffer.begin_stream(plan.total)
        half = max(B // 2, 1)
        trainer.buffer.push(jax.tree_util.tree_map(lambda x: x[:half], mb))
        if half < B:
            trainer.buffer.push(
                jax.tree_util.tree_map(lambda x: x[half:], mb)
            )
        mb = trainer.buffer.gather(
            plan.epoch1[0], sharding=trainer._batch_sh
        )
        subject = f"{kind}.streamed_step"
    closed = jax.make_jaxpr(trainer._train_step_jit)(state, mb)
    args = jax.tree_util.tree_leaves((state, mb))
    names = _flat_input_names(state, mb)
    mesh_shape = {k: int(v) for k, v in trainer.mesh.shape.items()}
    return sanitize_jaxpr(
        closed,
        args,
        subject=subject + (".planted" if plant else ""),
        mesh=mesh_shape,
        arg_names=names,
    )
