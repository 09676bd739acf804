"""General utilities: seeding, timing, tree ops, top-k masking.

TPU-native re-design of the reference's ``trlx/utils/__init__.py`` (172 LoC:
set_seed :15-22, Clock :63-101, topk_mask :107-116, tree_map/to_device
:132-150, filter_non_scalars :153-164, get_git_tag :167-172). Host-side
helpers stay Python; anything that runs on device is pure jax.numpy so it can
live inside jitted programs.
"""

from __future__ import annotations

import math
import os
import random
import subprocess
from typing import Any, Dict, Iterable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the repo's single timing source (telemetry/tracer.py): every reported
# duration — Clock ticks, Logger timestamps, spans, the perf lockfile —
# shares this monotonic clock, so numbers are comparable and immune to
# wall-clock steps (NTP adjustments skewed time.time() deltas)
from trlx_tpu.telemetry.tracer import monotonic


def set_seed(seed: int) -> jax.Array:
    """Seed host-side RNGs and return the root JAX PRNG key.

    Unlike the reference (which seeds torch/cuda globals), JAX randomness is
    explicit: the returned key threads through the framework as part of the
    train state.
    """
    random.seed(seed)
    np.random.seed(seed)
    return jax.random.PRNGKey(seed)


def flatten(xs: Iterable[Iterable[Any]]) -> List[Any]:
    """Flatten one level of nesting."""
    return [item for sub in xs for item in sub]


def chunk(xs: List[Any], chunk_size: int) -> List[List[Any]]:
    """Split ``xs`` into chunks of at most ``chunk_size``."""
    return [xs[i : i + chunk_size] for i in range(0, len(xs), chunk_size)]


def safe_mkdir(path: str) -> None:
    os.makedirs(path, exist_ok=True)


def significant(x: float, ndigits: int = 2) -> float:
    """Round ``x`` to ``ndigits`` significant figures (for log readability)."""
    if x == 0 or not math.isfinite(x):
        return x
    return round(x, ndigits - int(math.floor(math.log10(abs(x)))) - 1)


class Clock:
    """Wall-clock timer that tracks total time and samples processed.

    Mirrors the reference Clock's API (tick returns ms since last tick;
    get_stat reports time-per-1000-samples) so trainer timing stats keep the
    same meaning. Reads the tracer's monotonic clock — one timebase for
    Clock ticks and span durations.
    """

    def __init__(self):
        self.start = monotonic()
        self.total_time = 0.0
        self.total_samples = 0

    def tick(self, samples: int = 0) -> float:
        end = monotonic()
        delta = end - self.start
        self.start = end
        if samples != 0:
            self.total_time += delta
            self.total_samples += samples
        return delta * 1000.0

    def get_stat(self, n_samp: int = 1000, reset: bool = False) -> float:
        stat = 0.0
        if self.total_samples > 0:
            stat = self.total_time * n_samp / self.total_samples
        if reset:
            self.total_time = 0.0
            self.total_samples = 0
        return stat


def topk_mask(xs: jax.Array, k: int) -> jax.Array:
    """Set all elements outside the top-k of the last axis to -inf.

    Device-side (jit-safe) equivalent of the reference's topk_mask; used by
    top-k sampling in the jitted decode loop and ILQL generation.
    """
    if k >= xs.shape[-1]:
        return xs
    kth = jax.lax.top_k(xs, k)[0][..., -1:]
    return jnp.where(xs < kth, jnp.full_like(xs, -jnp.inf), xs)


def sentiment_score(sentiments: Iterable[Any]) -> "jax.Array":
    """Extract the positive-class score from HF sentiment-pipeline outputs
    (reference `trlx/utils/__init__.py:122-129`): each entry is a list of
    ``{"label", "score"}`` dicts; returns the POSITIVE scores as an array."""
    import jax.numpy as jnp

    scores = []
    for entry in sentiments:
        by_label = {d["label"]: d["score"] for d in entry}
        if "POSITIVE" in by_label:
            scores.append(by_label["POSITIVE"])
        else:
            # generic 2-class heads: positive is the highest label name
            # (LABEL_1 > LABEL_0) — pipeline output order is score-sorted,
            # so never index by position
            scores.append(by_label[max(by_label)])
    return jnp.asarray(scores, jnp.float32)


def tree_map(f, tree: Any) -> Any:
    """Apply ``f`` to every leaf of a pytree (dict/list/tuple/array)."""
    return jax.tree_util.tree_map(f, tree)


def to_device(tree: Any, device=None) -> Any:
    """Move a pytree of arrays onto a device (default: first local device)."""
    return jax.device_put(tree, device)


# f32-consuming leaves excluded from the rollout-phase compute-dtype cast:
# value/Q-head final layers (MLPHead "fc2" computes in f32 — value clipping
# is sensitive to bf16 rounding) and MoE router logits.
ROLLOUT_CAST_EXCLUDE = ("router", "fc2")


def compute_dtype_cast(params: Any, compute_dtype) -> Any:
    """Cast float param leaves to the compute dtype for the rollout phase.

    Decode re-reads every parameter once per generated token; f32 masters
    double that HBM traffic vs the compute dtype. Bit-identical outputs:
    causal-family ops already cast params to the compute dtype per use
    (embedding adds round per-table first), and leaves whose path matches
    :data:`ROLLOUT_CAST_EXCLUDE` — the ones genuinely consumed at f32 —
    keep their storage dtype. Jit with param shardings in/out so the copy
    lands sharded like the masters (`train.rollout_param_cast`)."""
    cdtype = jnp.dtype(compute_dtype)

    def cast(path, leaf):
        keys = "/".join(str(getattr(p, "key", p)) for p in path)
        if any(ex in keys for ex in ROLLOUT_CAST_EXCLUDE):
            return leaf
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf.astype(cdtype)
        return leaf

    return jax.tree_util.tree_map_with_path(cast, params)


def cast_is_exact(path, leaf, compute_dtype, keep: Iterable[str] = ()) -> bool:
    """Whether storing ``leaf`` at the compute dtype leaves every result as
    it was: a float leaf of rank >= 2, wider than the compute dtype, whose
    path holds none of :data:`ROLLOUT_CAST_EXCLUDE` nor of ``keep`` (a
    family's ``ModelFamily.stored_width_leaves``). Every program first uses
    such a leaf through a cast to the compute dtype (a gather from an
    embedding table commutes with it), so the stored copy is the value
    each product already saw. Vectors stay as stored: LayerNorm and RMSNorm
    apply ``scale`` / ``bias`` at f32, so :func:`compute_dtype_cast`, which
    rounds them, is exact only on the initialisers' ones and zeros
    (tests/test_served_params.py reads each family's programs for what
    they do with every leaf; ROADMAP.md Queue 1 item 2(c): one rule)."""
    keys = "/".join(str(getattr(p, "key", p)) for p in path)
    return (
        jnp.issubdtype(leaf.dtype, jnp.floating)
        and leaf.ndim >= 2
        and leaf.dtype.itemsize > jnp.dtype(compute_dtype).itemsize
        and not any(ex in keys for ex in (*ROLLOUT_CAST_EXCLUDE, *keep))
    )


def served_params(params: Any, compute_dtype, keep: Iterable[str] = ()) -> Any:
    """The tree a server hands its engine: every leaf :func:`cast_is_exact`
    names stored at the compute dtype and sharded like the leaf, every
    other leaf the caller's own array. A step under a server is one program
    a token, so a cast left inside it is repeated every token. Decided on
    the host: where no leaf qualifies (weights stored at the compute dtype,
    a tree a trainer already cast) the tree comes back as the same object
    and nothing is dispatched. Else one jitted cast a leaf, which is one
    small program a distinct shape (7 in a 24-layer model; 3 ms each from
    a warm compile cache on the v5e)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    picks = [cast_is_exact(path, leaf, compute_dtype, keep) for path, leaf in flat]
    if not any(picks):
        return params
    cast = jax.jit(lambda leaf: leaf.astype(compute_dtype))
    return treedef.unflatten(
        [cast(leaf) if pick else leaf for (_, leaf), pick in zip(flat, picks)]
    )


def tree_gb(tree: Any) -> float:
    """Bytes a tree of arrays holds, in GB, from shapes and dtypes."""
    return sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
    ) / 1e9


def filter_non_scalars(xs: Dict[str, Any]) -> Dict[str, float]:
    """Keep only entries castable to float — used before metric logging."""
    ys = {}
    for k, v in xs.items():
        try:
            ys[k] = float(v)
        except (TypeError, ValueError):
            continue
    return ys


def get_git_tag() -> str:
    """Return `(short-hash, commit-date)` of HEAD for run naming."""
    try:
        output = subprocess.check_output(
            "git log --format='%h/%as' -n1".split(),
            stderr=subprocess.DEVNULL,
        )
        branch = subprocess.check_output(
            "git rev-parse --abbrev-ref HEAD".split(),
            stderr=subprocess.DEVNULL,
        )
        return f"{branch.decode()[:-1]}/{output.decode()[1:-2]}"
    except (subprocess.CalledProcessError, FileNotFoundError):
        return "unknown"


def rampup_decay_schedule(
    rampup_steps: int, decay_steps: int, init_lr: float, target_lr: float
):
    """Linear warmup then exponential decay, as an optax-compatible schedule.

    Replaces the reference's LambdaLR `rampup_decay`.
    """

    def schedule(step):
        step = jnp.asarray(step, jnp.float32)
        warm = target_lr * jnp.minimum(step / jnp.maximum(rampup_steps, 1), 1.0)
        decay_frac = jnp.maximum(step - rampup_steps, 0.0) / jnp.maximum(
            decay_steps, 1
        )
        decayed = target_lr * jnp.power(
            jnp.asarray(init_lr / target_lr, jnp.float32), jnp.minimum(decay_frac, 1.0)
        )
        return jnp.where(step < rampup_steps, warm, jnp.maximum(decayed, init_lr))

    return schedule


def infinite_loader(loader) -> Iterable:
    """Cycle a loader forever (prompt draws in rollout collection).

    ``loader`` is either a reusable iterable or a ``factory(epoch) ->
    iterable`` (lets pipelines reshuffle per pass). Raises instead of
    spinning if an iteration yields nothing.
    """
    epoch = 0
    while True:
        it = loader(epoch) if callable(loader) else loader
        yielded = False
        for item in it:
            yielded = True
            yield item
        if not yielded:
            raise ValueError("infinite_loader: underlying loader is empty")
        epoch += 1
