"""Orbax checkpointing of train-state pytrees + host metadata.

TPU-native replacement for ``accelerator.save_state/load_state``
(`accelerate_base_model.py:144-146`, SURVEY §5.4). Checkpoints are managed
by ``ocp.CheckpointManager``: each save lands in a step-numbered directory
and the previous checkpoint is garbage-collected only *after* the new one
commits — a crash mid-write (sync or async) always leaves the last good
checkpoint restorable. State (sharded arrays, written/restored per-shard
with no host gather) and host metadata (KL controller, the reference's Ray
`state.json` analogue, `accelerate_base_model.py:232-240`) are one
composite checkpoint, committed atomically.

``async_save=True`` returns once device arrays are snapshotted to host
buffers; the write proceeds on Orbax's background thread (SURVEY §5.4
"Orbax async checkpointing"). :func:`wait_for_checkpoints` joins in-flight
writes and surfaces background write errors.

Failure taxonomy (docs/resilience.md): save/load failures are classified
by :func:`classify_checkpoint_error` into *transient* (flaky filesystem
— retried with bounded backoff via `utils/retry.py`) and *permanent*
(train-state structure mismatch, wrong path — refused fast with the
actionable :func:`_structure_mismatch_error` translation). Both paths
carry the ``checkpoint.save`` / ``checkpoint.load`` fault-injection
sites (resilience/chaos.py), which is how the ``--chaos-smoke``
self-check proves a transient error recovers and a permanent one does
not retry.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Optional, Tuple

from trlx_tpu.resilience import chaos
from trlx_tpu.utils.retry import classify_io_error, retry_call


def _orbax():
    """``orbax.checkpoint``, imported by the first save or load and not
    with this module: the trainers import this module at their top, a
    serving process imports a trainer module for its architecture table
    and never writes a checkpoint, and the import is seconds of every
    such process's set-up (about half of importing
    ``trainer/ppo_trainer.py``)."""
    import orbax.checkpoint as ocp

    return ocp


# One manager per directory: managers own background threads, per-directory
# step bookkeeping, and (multi-host) coordination state. Async is always
# enabled at the manager level; a *sync* save simply joins the write before
# returning — so a directory never has two managers with divergent GC state.
_managers: Dict[str, Any] = {}  # directory -> ocp.CheckpointManager


def _manager(directory: str):
    if directory not in _managers:
        ocp = _orbax()
        _managers[directory] = ocp.CheckpointManager(
            directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=2,
                enable_async_checkpointing=True,
            ),
        )
    return _managers[directory]


def save_checkpoint(
    directory: str,
    state: Any,
    metadata: Optional[Dict[str, Any]] = None,
    async_save: bool = False,
    step: Optional[int] = None,
) -> None:
    """Save state + metadata as one atomically-committed checkpoint under
    ``directory/<step>/``; the previous checkpoint survives until the new
    one commits."""
    ocp = _orbax()
    directory = os.path.abspath(directory)
    os.makedirs(directory, exist_ok=True)
    mgr = _manager(directory)
    if step is None:
        step = (mgr.latest_step() or 0) + 1
    # Stamp the save wall-clock so load_checkpoint can prefer the newest
    # *timeline* over the highest step number: a crash between the new
    # save's commit and stale-step GC below can leave a higher-numbered
    # step from a previous run alongside this one.
    args = ocp.args.Composite(
        state=ocp.args.StandardSave(state),
        host_state=ocp.args.JsonSave(
            dict(metadata or {}, _saved_at=time.time())
        ),
    )
    # A fresh run reusing a directory from a longer previous run: steps
    # beyond the one being written belong to the stale timeline and must go
    # (retention GC keeps latest-by-step and would otherwise delete this
    # run's checkpoint; resume would restore the old run via latest_step()).
    # Keep the newest stale step until the new save commits so a crash in
    # between never leaves the directory with zero restorable checkpoints.
    stale = sorted(s for s in mgr.all_steps() if s > int(step))
    for s in stale[:-1]:
        mgr.delete(s)

    def _attempt() -> None:
        chaos.check("checkpoint.save", step=int(step))
        try:
            mgr.save(int(step), args=args, force=True)
        except ocp.checkpoint_manager.StepAlreadyExistsError:
            # same-step re-save (incl. a retry after a partially-failed
            # attempt): replace that step's checkpoint
            mgr.delete(int(step))
            mgr.save(int(step), args=args, force=True)
        if stale or not async_save:
            # join the write when the caller needs durability now (sync
            # save) or stale-step GC must wait on the commit; a
            # background failure surfaces here, inside the retry scope
            mgr.wait_until_finished()

    # transient filesystem errors retry with bounded backoff; anything
    # else (wrong path, serialization bug) still fails fast
    retry_call(
        _attempt,
        classify=classify_io_error,
        describe=f"checkpoint save to {directory}",
    )
    if stale:
        mgr.delete(stale[-1])  # new step committed -> stale can go


def wait_for_checkpoints() -> None:
    """Block until in-flight async checkpoint writes have committed
    (re-raises background write errors)."""
    for mgr in _managers.values():
        mgr.wait_until_finished()


def has_checkpoint(directory: str) -> bool:
    """True when ``directory`` holds a restorable checkpoint (managed
    step-numbered layout or the legacy ``state`` + sidecar layout)."""
    directory = os.path.abspath(directory)
    if os.path.isdir(os.path.join(directory, "state")):
        return True  # legacy layout
    if not os.path.isdir(directory):
        return False
    return any(name.isdigit() for name in os.listdir(directory))


_MISMATCH_HINTS = (
    # structure-mismatch phrasings from orbax's StandardRestore stack; keep
    # these NARROW — broad words ("shape", "different") appear in unrelated
    # IO/topology failures that must surface untranslated
    "structure", "mismatch", "not match", "treedef",
)


def _structure_mismatch_error(directory: str, e: Exception) -> Optional[ValueError]:
    """Map Orbax's deep structure-mismatch failures to an actionable error.

    The optimizer-state layout is configuration-dependent: a frozen-mask
    run (``model.num_layers_unfrozen``) stores moments only for the
    trainable slice (``optax.masked``), and ``train.adam_moment_dtype``
    changes the moment dtype — checkpoints written under one layout do not
    restore into another, and Orbax surfaces that as an opaque error deep
    in its restore stack."""
    text = f"{type(e).__name__}: {e}".lower()
    if not any(h in text for h in _MISMATCH_HINTS):
        return None
    if isinstance(e, OSError):
        # an I/O error whose strerror happens to contain a hint word is
        # still an I/O error — never translate it into a layout remedy
        return None
    return ValueError(
        f"checkpoint under {directory} does not match the current "
        "train-state structure. This likely means the optimizer-state "
        "layout changed between the run that wrote the checkpoint and this "
        "configuration — e.g. `model.num_layers_unfrozen` (frozen-mask "
        "runs store moments only for the trainable slice) or "
        "`train.adam_moment_dtype` differs. Frozen-mask layout changes are "
        "not restorable: restore with the original configuration, or "
        "restart the run fresh with a new checkpoint dir. If neither key "
        f"changed, the underlying error was: {type(e).__name__}: {e}"
    )


def classify_checkpoint_error(e: Exception) -> str:
    """Transient-vs-permanent taxonomy for checkpoint I/O failures
    (docs/resilience.md). A structure mismatch is permanent no matter
    how orbax typed it — retrying a layout disagreement only delays the
    actionable error; everything else follows the shared host-I/O
    taxonomy (OSError family transient, deterministic Python errors
    permanent)."""
    if _structure_mismatch_error("", e) is not None:
        return "permanent"
    return classify_io_error(e)


def load_checkpoint(
    directory: str, abstract_state: Any
) -> Tuple[Any, Dict[str, Any]]:
    """Restore into the shapes/shardings of ``abstract_state`` (obtain via
    ``jax.eval_shape`` + shardings, or pass a live state of the right
    spec). Reads the managed layout and the legacy state-dir + sidecar.
    A checkpoint whose train-state structure does not match
    ``abstract_state`` (e.g. a different freezing mask or moment dtype)
    raises a :class:`ValueError` naming the config keys instead of Orbax's
    opaque internal mismatch error."""
    ocp = _orbax()
    wait_for_checkpoints()
    directory = os.path.abspath(directory)
    mgr = _manager(directory)
    step = mgr.latest_step()
    legacy_state = os.path.join(directory, "state")
    if step is None and os.path.isdir(legacy_state):
        # legacy layout only — once managed steps exist they are newer
        # (an upgraded run keeps saving next to the old 'state' dir)
        with ocp.StandardCheckpointer() as ckptr:

            def _restore_legacy():
                chaos.check("checkpoint.load")
                return ckptr.restore(legacy_state, abstract_state)

            try:
                # transient I/O retries with backoff; a structure
                # mismatch is permanent and refuses on the first attempt
                state = retry_call(
                    _restore_legacy,
                    classify=classify_checkpoint_error,
                    describe=f"checkpoint restore from {legacy_state}",
                )
            except Exception as e:  # noqa: BLE001 — orbax raises many types
                wrapped = _structure_mismatch_error(directory, e)
                if wrapped is None:
                    raise
                raise wrapped from e
        metadata: Dict[str, Any] = {}
        legacy_json = os.path.join(directory, "host_state.json")
        if os.path.exists(legacy_json):
            with open(legacy_json) as f:
                metadata = json.load(f)
        return state, metadata
    if step is None:
        raise FileNotFoundError(f"no checkpoint found under {directory}")
    # Prefer the newest checkpoint by commit wall-clock, not step number:
    # after a crash in save_checkpoint's commit->GC window, a stale
    # higher-numbered step from a previous run can coexist with the newer
    # save. Unstamped (legacy) steps sort by step number alone.
    steps = sorted(mgr.all_steps())
    if len(steps) > 1:

        def _saved_at(s: int) -> float:
            try:
                meta = mgr.restore(
                    s, args=ocp.args.Composite(host_state=ocp.args.JsonRestore())
                )["host_state"]
                return float((meta or {}).get("_saved_at", 0.0))
            except Exception:
                return 0.0

        step = max(steps, key=lambda s: (_saved_at(s), s))
    def _restore():
        chaos.check("checkpoint.load")
        return mgr.restore(
            step,
            args=ocp.args.Composite(
                state=ocp.args.StandardRestore(abstract_state),
                host_state=ocp.args.JsonRestore(),
            ),
        )

    try:
        # the transient/permanent split (classify_checkpoint_error): a
        # flaky filesystem read retries with bounded backoff, a
        # structure mismatch refuses on the first attempt
        restored = retry_call(
            _restore,
            classify=classify_checkpoint_error,
            describe=f"checkpoint restore from {directory}",
        )
    except Exception as e:  # noqa: BLE001 — orbax raises many types
        wrapped = _structure_mismatch_error(directory, e)
        if wrapped is None:
            raise
        raise wrapped from e
    metadata = dict(restored["host_state"] or {})
    metadata.pop("_saved_at", None)
    return restored["state"], metadata
