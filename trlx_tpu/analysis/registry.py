"""Rule registry for the static-analysis pass.

Every enforceable invariant is a registered :class:`Rule` with a stable id
(the id is what ``# tpu-lint: disable=<id>`` names). Engines look their
rules up here so the CLI can list, select, and document them uniformly;
adding a rule means registering it and implementing its check in the
owning engine (see docs/static_analysis.md, "Adding a rule").
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

from trlx_tpu.analysis.findings import SEVERITY_ERROR, SEVERITY_WARNING

ENGINE_JAXPR = "jaxpr"
ENGINE_AST = "ast"
ENGINE_NANFLOW = "nanflow"
ENGINE_COLLECTIVE = "collective"
ENGINE_SANITIZER = "sanitizer"
ENGINE_RESOURCE = "resource"
ENGINE_DONATION = "donation"
ENGINE_COMPILE = "compile"
ENGINE_PRNG = "prng"
ENGINE_PERF = "perf"
ENGINE_LOCKSTEP = "lockstep"
ENGINE_HLO = "hlo"
ENGINE_CONCURRENCY = "concurrency"
ENGINE_STATE = "state"


@dataclass(frozen=True)
class Rule:
    id: str
    engine: str
    description: str
    severity: str = SEVERITY_ERROR
    rationale: str = ""


_RULES: Dict[str, Rule] = {}


def register_rule(rule: Rule) -> Rule:
    if rule.id in _RULES:
        raise ValueError(f"duplicate rule id {rule.id!r}")
    _RULES[rule.id] = rule
    return rule


def get_rule(rule_id: str) -> Rule:
    if rule_id not in _RULES:
        raise KeyError(
            f"unknown rule {rule_id!r}; registered: {sorted(_RULES)}"
        )
    return _RULES[rule_id]


def all_rules(engine: str = "") -> List[Rule]:
    rules = sorted(_RULES.values(), key=lambda r: (r.engine, r.id))
    if engine:
        rules = [r for r in rules if r.engine == engine]
    return rules


# --------------------------- jaxpr-audit rules --------------------------- #

register_rule(Rule(
    "fp64",
    ENGINE_JAXPR,
    "no float64 value anywhere in a traced program",
    SEVERITY_ERROR,
    "TPUs have no f64 units; an f64 leaf silently doubles memory and "
    "falls back to slow emulation (the reference's torch code never "
    "promotes, so any f64 here is an accident).",
))
register_rule(Rule(
    "collective-axis",
    ENGINE_JAXPR,
    "every collective (psum/all_gather/ppermute/reduce_scatter/...) names "
    "an axis of the trainer mesh",
    SEVERITY_ERROR,
    "A collective over an unknown axis either fails at compile on the "
    "real slice topology or — worse — silently reduces over nothing.",
))
register_rule(Rule(
    "donation",
    ENGINE_JAXPR,
    "train steps donate their input state buffers",
    SEVERITY_ERROR,
    "Without donation the optimizer state + params are double-buffered "
    "through every update — the difference between fitting and OOM at "
    "the 20B stretch shapes.",
))
register_rule(Rule(
    "precision-leak",
    ENGINE_JAXPR,
    "no unexpected bf16->f32 convert of an activation-rank tensor inside "
    "the compute-dtype forward (loss/optimizer reductions are allow-listed)",
    SEVERITY_WARNING,
    "A stray f32 upcast of a [B, T, D] tensor doubles that tensor's HBM "
    "traffic and defeats the bf16 compute contract (PAPER.md: policy "
    "loaded in bfloat16).",
))
register_rule(Rule(
    "partition-spec",
    ENGINE_JAXPR,
    "every PartitionSpec produced by a family's partition rules is valid "
    "on the mesh (axis exists, dim divisible)",
    SEVERITY_ERROR,
    "An invalid spec either crashes at jit time on the real topology or "
    "silently replicates a tensor that was meant to shard.",
))

# --------------------------- NaN-dataflow rules -------------------------- #

register_rule(Rule(
    "nan-unguarded",
    ENGINE_NANFLOW,
    "every op that can mint a NaN/Inf (div, log, rsqrt, sqrt, exp "
    "overflow, fractional pow) has its operand dominated by a guard "
    "(+eps, clip/maximum, where on the input)",
    SEVERITY_ERROR,
    "The fsdp/tp PPO divergence is exactly this class: one unguarded "
    "op (unclipped exp(log_ratio), eps-free rsqrt) mints the first "
    "NaN and the optimizer propagates it everywhere within a step.",
))
register_rule(Rule(
    "where-grad-trap",
    ENGINE_NANFLOW,
    "no unguarded non-total op whose output is masked by where/select — "
    "the backward pass evaluates it on masked lanes anyway",
    SEVERITY_ERROR,
    "grad(where(mask, f(x), 0)) evaluates f'(x) on every lane and "
    "multiplies inf by the zero cotangent: 0*inf = NaN gradients while "
    "the forward value looks fine. The guard must sit on f's input.",
))
register_rule(Rule(
    "inf-mask-softmax",
    ENGINE_NANFLOW,
    "no softmax denominator built from a -inf-masked input without a "
    "row-liveness guarantee",
    SEVERITY_WARNING,
    "where(mask, s, -inf) into softmax divides 0/0 on a fully-masked "
    "row. Causal self-attention rows always see themselves; anything "
    "else (padding-only rows, cross-attention) needs a re-select.",
))

# ------------------------ collective-sequence rules ----------------------- #

register_rule(Rule(
    "collective-divergence",
    ENGINE_COLLECTIVE,
    "a trainer's linearized collective sequence (psum/all_gather/"
    "reduce_scatter/ppermute + axes) is identical across the mesh "
    "matrix up to axis renaming",
    SEVERITY_ERROR,
    "Distributed RLHF correctness hinges on all workers executing the "
    "same collective schedule (LlamaRL): a topology-dependent psum "
    "order deadlocks or silently mismatches reductions on the slice.",
))
register_rule(Rule(
    "host-branch",
    ENGINE_AST,
    "no host Python branch on device-derived values (float(x) of a "
    "fetched stat, step_stats[...]) in multi-host trainer loop code",
    SEVERITY_WARNING,
    "A branch on a per-host value can take different arms on "
    "different hosts; the next collective then hangs or reduces "
    "mismatched programs. Branch on config/step counters, or "
    "all-gather the scalar first.",
))

# ----------------------------- sanitizer rule ----------------------------- #

register_rule(Rule(
    "sanitizer-nonfinite",
    ENGINE_SANITIZER,
    "eqn-level replay of a captured step jaxpr finds no equation whose "
    "output is the program's first NaN/Inf",
    SEVERITY_ERROR,
    "Replaying the step eqn-by-eqn turns 'PPO diverges on fsdp/tp' "
    "into 'this equation, this source line, this param path minted "
    "the first NaN' — a one-command localization instead of printf.",
))

# --------------------------- resource-audit rules ------------------------ #

register_rule(Rule(
    "hbm-over-budget",
    ENGINE_RESOURCE,
    "a traced program's statically-computed peak live HBM (per device, "
    "sharding- and donation-aware) stays within its committed budget in "
    "analysis/budgets.json (+ tolerance)",
    SEVERITY_ERROR,
    "Memory regressions today surface as OOMs on real hardware (LlamaRL "
    "makes per-component memory budgets a first-class design input). The "
    "lockfile turns every peak-HBM change into a reviewable diff: grow "
    "the budget deliberately with --update-budgets, never by accident.",
))
register_rule(Rule(
    "collective-bytes-regression",
    ENGINE_RESOURCE,
    "a traced program's modeled collective traffic (bytes moved per "
    "device across psum/all_gather/ppermute/all_to_all, attributed to "
    "mesh axes) stays within its committed budget in analysis/budgets.json",
    SEVERITY_ERROR,
    "Interconnect bytes are the scaling ceiling for multi-slice RLHF: an "
    "accidental extra all_gather costs nothing on the CPU test mesh and "
    "everything on a real slice. Regressions must be explained in the "
    "budget-lockfile diff.",
))

# ----------------------------- donation rules ---------------------------- #

register_rule(Rule(
    "use-after-donate",
    ENGINE_DONATION,
    "host code never reads a pytree after passing it to a donating jitted "
    "step without rebinding the result first",
    SEVERITY_ERROR,
    "A donated buffer is freed/aliased by XLA the moment the step is "
    "dispatched; the host-side reference silently reads garbage (or "
    "crashes) — the exact hazard class PR 3's snapshot logic hit, caught "
    "then only by hand-audit.",
))
register_rule(Rule(
    "donation-ignored",
    ENGINE_DONATION,
    "every donated input buffer has a same-shape/dtype output that can "
    "actually reuse it",
    SEVERITY_WARNING,
    "A donated buffer XLA cannot reuse (no shape/dtype-matching output) "
    "is silent memory waste the runtime only warns about on real "
    "hardware — the donation promise is a lie and peak HBM is higher "
    "than the step's budget claims.",
))
register_rule(Rule(
    "alias-escape",
    ENGINE_DONATION,
    "no traced program returns a non-donated input leaf unchanged — the "
    "output would alias the caller's buffer instead of owning fresh "
    "memory",
    SEVERITY_ERROR,
    "pjit input-forwarding aliases the returned array onto the input "
    "buffer; if any later program donates that buffer, every holder of "
    "the forwarded output reads reused memory (the PR-3 behavior-"
    "snapshot hazard: copy per leaf, or donate explicitly).",
))

# ------------------------- compile-stability rules ----------------------- #

register_rule(Rule(
    "unexpected-retrace",
    ENGINE_COMPILE,
    "no jitted callable recompiles on a steady-state repeat call of the "
    "trainer's canonical loop (same logical step, stable shapes)",
    SEVERITY_ERROR,
    "Silent recompilation is the dominant un-instrumented TPU perf "
    "killer: one shape-varying call site recompiles the whole train "
    "step mid-run (~minutes at real shapes) and nothing in the loss "
    "curves shows it. The finding ships the jaxpr drift — the first "
    "divergent equation (shape / dtype / weak_type / static-arg) — so "
    "the cause lands in the report, not just the count.",
))
register_rule(Rule(
    "compile-count-regression",
    ENGINE_COMPILE,
    "per-callable compile counts over the canonical short loop stay "
    "within the committed compile_budgets entries in "
    "analysis/budgets.json",
    SEVERITY_ERROR,
    "The compile-count lockfile turns every new compile into a "
    "reviewable diff: grow a budget deliberately with "
    "--compile-audit --update-budgets, never by accident. A count "
    "regression on the CPU audit mesh is minutes of XLA time at the "
    "real shapes.",
))
register_rule(Rule(
    "retrace-risk",
    ENGINE_COMPILE,
    "no jitted call site in an untraced trainer/orchestrator loop is fed "
    "a per-step-varying host scalar (len()/.item()/int() of device "
    "values) or a non-literal static argument",
    SEVERITY_WARNING,
    "A Python scalar derived from len()/.item()/int() re-hashes the jit "
    "cache key every time its value changes: the call site compiles per "
    "distinct value, and the retrace harness only catches the ones the "
    "canonical loop happens to exercise. Pass device arrays, or keep "
    "host scalars step-invariant.",
))

# --------------------------- PRNG-lineage rules -------------------------- #

register_rule(Rule(
    "key-reuse",
    ENGINE_PRNG,
    "no PRNG key is consumed by more than one random primitive "
    "(draw/split/fold_in) — every reuse must go through a fresh "
    "split/fold_in derivation",
    SEVERITY_ERROR,
    "Key reuse silently correlates samples: two rollouts drawn from one "
    "key explore identical trajectories and PPO's gradient variance "
    "estimates are wrong with no visible symptom in loss curves — the "
    "failure mode RLHF pipelines are least likely to catch.",
))
register_rule(Rule(
    "key-discard",
    ENGINE_PRNG,
    "every jax.random.split advances its chain: the result is consumed "
    "and the source chain variable (self.rng) is rebound",
    SEVERITY_WARNING,
    "A split whose output is dropped (or whose source chain is not "
    "rebound) repeats the same subkeys at the next call — delayed key "
    "reuse. `_, key = split(self.rng)` is the classic spelling: every "
    "subsequent call re-derives the identical key.",
))
register_rule(Rule(
    "fixed-seed",
    ENGINE_PRNG,
    "no literal seed reaches training-path randomness outside tests "
    "(PRNGKey(0)/key(42)/default_rng(7) in trainer/pipeline/orchestrator "
    "code must come from config)",
    SEVERITY_WARNING,
    "A hard-coded seed pins every run of a sampling path to one "
    "trajectory set: sweeps silently share rollouts, and restarts "
    "replay the same 'random' experience. Seeds belong to "
    "train.seed/config so runs are reproducible on purpose.",
))

# -------------------------- measured-perf rules -------------------------- #

register_rule(Rule(
    "perf-regression",
    ENGINE_PERF,
    "measured per-span wall-clock (p50 over the instrumented phase loop) "
    "stays within the committed perf_budgets section of "
    "analysis/budgets.json (+ per-span tolerance)",
    SEVERITY_ERROR,
    "Faithful throughput drifted 167 -> 162 samples/s/chip across five "
    "bench rounds and only a manual diff caught it: nothing gated "
    "*measured* time. The span lockfile turns wall-clock drift into a "
    "failing job — relock deliberately with --perf-audit "
    "--update-budgets, never by accident.",
))

# ------------------------ multi-controller lockstep ---------------------- #

register_rule(Rule(
    "lockstep-divergence",
    ENGINE_LOCKSTEP,
    "N simulated controller processes running a trainer's canonical host "
    "loop dispatch the SAME jitted/collective-bearing programs in the "
    "same order with the same arg signatures and collective schedules",
    SEVERITY_ERROR,
    "In multi-controller JAX every host drives its own Python loop; a "
    "dispatch present on one host and absent (or different) on another "
    "— a rank-0-gated jit call, a host-local branch — leaves the other "
    "hosts blocked inside the program's first collective forever. The "
    "simulator catches the deadlock before any multi-host hardware "
    "exists, localized to the first diverging ordinal and call site.",
))
register_rule(Rule(
    "dispatch-sequence-drift",
    ENGINE_LOCKSTEP,
    "a trainer's host-0 dispatch-sequence fingerprint over the canonical "
    "loop matches the committed lockstep_budgets section of "
    "analysis/budgets.json",
    SEVERITY_ERROR,
    "The dispatch schedule is the multi-host contract: reordering it, "
    "adding a program, or changing a shape signature silently changes "
    "what every direction-1 component (launcher, per-host restart, "
    "cross-slice push) must replay identically. The lockfile turns "
    "every schedule change into a reviewable diff — relock with "
    "--lockstep --update-budgets, never by accident.",
))

# -------------------- compiled-HLO audit (engine 13) --------------------- #

register_rule(Rule(
    "lowering-collective-drift",
    ENGINE_HLO,
    "the collectives XLA actually emitted for a program (optimized "
    "post-SPMD HLO) match jaxpr intent and the committed hlo_budgets "
    "profile: no concat-minted replica-axis all-reduce, no dropped "
    "explicit collective, no inserted/dropped/re-axised profile key",
    SEVERITY_ERROR,
    "The jaxpr is intent; the compiled module is what the TPU runs. "
    "Both of this repo's worst correctness bugs were XLA's SPMD "
    "partitioner rewriting collectives below the jaxpr (the PR-2 "
    "sharded-concat replica-SUM, the quarantined pp cached-decode "
    "stack) — drift at this layer is invisible to every jaxpr-level "
    "engine and NaNs the run at scale.",
))
register_rule(Rule(
    "hlo-dtype-upcast",
    ENGINE_HLO,
    "no non-scalar f32 tensor minted from bf16 inputs by the optimized "
    "module outside the softmax/layernorm/loss accumulation allowlist",
    SEVERITY_WARNING,
    "XLA may legally widen compute during optimization; an activation-"
    "rank f32 tensor the source never wrote doubles HBM traffic and "
    "defeats the bf16 compute contract (PAPER.md: policy in bfloat16) "
    "— and the jaxpr-level precision-leak rule cannot see compiler-"
    "minted converts.",
))
register_rule(Rule(
    "hlo-memory-drift",
    ENGINE_HLO,
    "each program's compiled buffer-assignment peak (temp + args + "
    "outputs - donation aliasing) stays within tolerance of the "
    "committed hlo_budgets entry",
    SEVERITY_ERROR,
    "Engine 7's static peak is a model; XLA's buffer assignment is the "
    "allocation the device makes. A fusion or layout change can "
    "regress real live memory while the static number holds — the "
    "lockfile turns that silent regression into a reviewable diff.",
))
register_rule(Rule(
    "spmd-concat-hazard",
    ENGINE_HLO,
    "no eager multi-operand concatenate of committed-sharded operands "
    "on a multi-device mesh outside the blessed spmd_stack/concat_cols "
    "helpers",
    SEVERITY_ERROR,
    "XLA's SPMD partitioner has twice mis-lowered exactly this shape "
    "into a replica-axis SUM (PR 2; the quarantined pp cached-decode "
    "stack). The dynamic_update_slice spelling in the blessed helpers "
    "is the sanctioned route — this rule automates the ROADMAP 'watch "
    "for new eager concat/stack' human obligation.",
))

# -------------------- host-concurrency lint (engine 12) ------------------- #

register_rule(Rule(
    "rank-gated-dispatch",
    ENGINE_AST,
    "no jitted or collective-bearing call is reachable only under a "
    "process_index()/is_main_process rank gate in host-loop code",
    SEVERITY_ERROR,
    "A dispatch inside `if is_main_process():` runs a collective-bearing "
    "program on host 0 only; the other hosts never enter it and the "
    "collective blocks until the job is killed. Rank-gate host I/O "
    "(logging, checkpoint writes), never device dispatch.",
))
register_rule(Rule(
    "nondet-host-order",
    ENGINE_AST,
    "no iteration over set()/un-sorted os.listdir()/glob feeds a jitted "
    "or collective-bearing call in host-loop code",
    SEVERITY_ERROR,
    "set/listdir/glob order is process-local: two hosts walking the "
    "same logical collection dispatch the same programs in DIFFERENT "
    "orders, and order is exactly what multi-controller lockstep "
    "requires. Wrap the iterable in sorted(...).",
))
register_rule(Rule(
    "host-time-in-dispatch",
    ENGINE_AST,
    "no wall-clock (time.time/monotonic/datetime.now) or host random "
    "value steers a branch that guards a jitted or collective-bearing "
    "call in host-loop code",
    SEVERITY_WARNING,
    "Host clocks and host RNG are per-process: a deadline or sampled "
    "branch flips arms at different moments on different hosts, so one "
    "host dispatches a program its peers skip — the next collective "
    "hangs. Derive the decision from step counters or broadcast it "
    "from rank 0 (distributed.broadcast_host_value).",
))
register_rule(Rule(
    "unsynced-host-io",
    ENGINE_AST,
    "no value read from a per-host file (open/read/np.load/json.load) "
    "feeds a jitted or collective-bearing call's arguments in host-loop "
    "code",
    SEVERITY_WARNING,
    "Per-host reads of 'the same' file can observe different snapshots "
    "(checkpoint-in-progress, node-local cache); a shape or value "
    "difference re-hashes the jit cache key or mismatches the "
    "collective's operands across hosts. Read on rank 0 and broadcast, "
    "or route through the checkpoint layer's synchronized restore.",
))

# ------------------- host-concurrency races (engine 14) ------------------ #

register_rule(Rule(
    "unguarded-shared-write",
    ENGINE_CONCURRENCY,
    "every attribute mutated from two or more thread roots is guarded by "
    "a common lock on every mutation path (or the owning class carries a "
    "written single-thread contract)",
    SEVERITY_ERROR,
    "The host side is concurrent now — writer thread, drive loop, "
    "weight-push caller, stream pump, signal handlers — and a shared "
    "counter or reference mutated from two roots without one lock is a "
    "data race: torn under free-threading, and a lost update even under "
    "the GIL when the mutation is a read-modify-write.",
))
register_rule(Rule(
    "lock-order-cycle",
    ENGINE_CONCURRENCY,
    "the discovered locks are acquired in one consistent global order "
    "(no path acquires A then B while another acquires B then A)",
    SEVERITY_ERROR,
    "Inconsistent acquisition order is the classic ABBA deadlock: each "
    "thread holds one lock and blocks forever on the other. The cycle "
    "only bites under load on real hardware, where it presents as a "
    "hung slice, not a stack trace.",
))
register_rule(Rule(
    "signal-unsafe-handler",
    ENGINE_CONCURRENCY,
    "SIGTERM/SIGINT handlers do nothing beyond async-signal-safe flag "
    "sets (one attribute/global assignment; no I/O, no allocation-heavy "
    "calls, no locks)",
    SEVERITY_ERROR,
    "A Python signal handler runs between arbitrary bytecodes of the "
    "interrupted thread. print() there can deadlock on the stdout "
    "buffer lock the main thread already holds; anything beyond "
    "setting a flag races the drain that the preemption contract says "
    "happens at phase boundaries.",
))
register_rule(Rule(
    "atomicity-split",
    ENGINE_CONCURRENCY,
    "no check-then-act on shared state outside the lock that guards "
    "that state (the check and the act must sit in one critical "
    "section)",
    SEVERITY_WARNING,
    "`if not stream.closed: stream.push(tok)` is two critical sections: "
    "a close between them loses the token even though both halves are "
    "individually locked. TOCTOU on shared state is invisible to "
    "single-schedule tests — every parity pin in the suite runs one "
    "lucky interleaving.",
))
register_rule(Rule(
    "schedule-invariant-violation",
    ENGINE_CONCURRENCY,
    "the repo's claimed concurrency invariants (version-column "
    "monotonicity, no torn stream rows, staleness_window=0 bitwise "
    "parity, zero lost writer rows) hold under every explored "
    "deterministic thread interleaving",
    SEVERITY_ERROR,
    "Static locksets prove guarding, not semantics. The cooperative "
    "scheduler runs the REAL writer/drive/push/pump code under seeded "
    "interleavings and replays the first violating schedule by seed — "
    "a race gate the 13 jaxpr/HLO-level engines cannot provide.",
))

# ---------------- checkpoint/resume state coverage (engine 15) ----------- #

register_rule(Rule(
    "resume-state-gap",
    ENGINE_STATE,
    "every mutable attribute written inside the phase loop on an object "
    "reachable from a trainer is checkpoint-carried, deterministically "
    "reconstructed from config on restore, or explicitly allowlisted "
    "ephemeral with a written justification",
    SEVERITY_ERROR,
    "Kill/resume parity is the repo's fault-tolerance contract (PR 9's "
    "supervisor + emergency checkpoints), but host state grew past the "
    "save() metadata: an accept-EWMA, token-bucket level, or RNG key "
    "that feeds the sampling schedule and is silently reset on restore "
    "makes a resumed run diverge from the uninterrupted one — exactly "
    "the failure the parity canaries were written to forbid.",
))
register_rule(Rule(
    "stale-state-contract",
    ENGINE_STATE,
    "every ephemeral-allowlist entry and state-manifest key names an "
    "attribute that still exists in the code",
    SEVERITY_WARNING,
    "A contract naming a dead attribute is worse than no contract: the "
    "attribute was renamed or removed, the justification no longer "
    "covers anything, and the next writer inherits a green audit that "
    "is vacuously true. Stale entries must be pruned or renamed so the "
    "allowlist stays a live inventory, not a fossil record.",
))
register_rule(Rule(
    "ckpt-schema-drift",
    ENGINE_STATE,
    "each trainer's checkpoint key-set and per-leaf shape/dtype "
    "fingerprint matches the locked state_manifest section of "
    "analysis/budgets.json",
    SEVERITY_ERROR,
    "A key that vanishes from the save pytree is a resume gap the "
    "static classifier cannot see (the state_dict method still "
    "exists), and a shape/dtype change breaks restore of every "
    "checkpoint already on disk. Locking the schema makes either "
    "drift a reviewed, additive relock instead of a silent break.",
))
register_rule(Rule(
    "resume-divergence",
    ENGINE_STATE,
    "after checkpoint -> rebuild -> restore, one more phase of the "
    "resumed trainer leaves every live host attribute bitwise equal to "
    "an uninterrupted twin's (outside the allowlisted ephemeral set)",
    SEVERITY_ERROR,
    "The dynamic half of the contract: static classification proves an "
    "attribute is carried, only the differ proves it is carried "
    "*correctly* (right tensor, right dtype, restored before first "
    "use). Any diverging attribute path is a real parity break that "
    "the params-only canaries would miss.",
))

# ---------------------------- AST-lint rules ----------------------------- #

register_rule(Rule(
    "host-item",
    ENGINE_AST,
    "no .item() inside jit-decorated/traced functions",
    SEVERITY_ERROR,
    ".item() blocks on a device->host transfer; inside traced code it "
    "either fails to trace or forces a blocking sync per call.",
))
register_rule(Rule(
    "host-scalar-cast",
    ENGINE_AST,
    "no float()/int() of a non-literal inside traced functions",
    SEVERITY_ERROR,
    "float(x) on a tracer is a ConcretizationTypeError at best and a "
    "hidden host sync at worst; use x.astype(...) / jnp casts.",
))
register_rule(Rule(
    "host-transfer",
    ENGINE_AST,
    "no jax.device_get / np.asarray / np.array inside traced functions",
    SEVERITY_ERROR,
    "Explicit host transfers inside traced code serialize the step "
    "pipeline (OPPO in PAPERS.md: overlap wins evaporate under hidden "
    "host syncs).",
))
register_rule(Rule(
    "py-random",
    ENGINE_AST,
    "no Python random module inside traced functions",
    SEVERITY_ERROR,
    "Host RNG inside traced code bakes one sample into the compiled "
    "program — every execution replays the same 'random' number; use "
    "jax.random with explicit keys.",
))
register_rule(Rule(
    "np-in-ops",
    ENGINE_AST,
    "ops/ kernels use jnp, not np, inside any function",
    SEVERITY_ERROR,
    "ops/ modules are kernel code whose functions run under trace; "
    "np.* on a tracer escapes to host or fails. Module-level np "
    "constants are fine.",
))
