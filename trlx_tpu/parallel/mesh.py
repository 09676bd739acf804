"""Device mesh construction and axis conventions.

The TPU-native replacement for the reference's distributed substrate
(HF Accelerate -> torch.distributed/NCCL/DeepSpeed; SURVEY §2.9). All
parallelism in this framework is expressed as sharding over one
``jax.sharding.Mesh`` with three named axes:

- ``dp``   — pure data parallel: params replicated, batch sharded
             (reference: Accelerate DDP, `accelerate_base_model.py:38`).
- ``fsdp`` — ZeRO-style fully-sharded data parallel: batch sharded *and*
             params/optimizer state sharded (reference: DeepSpeed ZeRO
             stages, `configs/deepspeed_configs/default_configs.yml`).
- ``tp``   — tensor parallel: hidden/head dimensions sharded (reference has
             only dormant scaffolding for this, `ppo_models.py:310-312`).

Gradient sync, global statistics, and param gathers all become XLA
collectives over ICI inserted automatically by GSPMD from these shardings —
there is no explicit NCCL-equivalent call-site in the framework.
"""

from __future__ import annotations

import contextvars
import functools
from typing import Callable, Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_DP = "dp"
AXIS_FSDP = "fsdp"
AXIS_TP = "tp"
AXIS_SP = "sp"  # sequence/context parallel (ring attention, ops/ring_attention.py)
AXIS_PP = "pp"  # pipeline parallel (GPipe microbatching, parallel/pipeline.py)
AXIS_EP = "ep"  # expert parallel (switch MoE routing, parallel/moe.py)
# Batch axes: data is sharded over both dp and fsdp mesh axes.
BATCH_AXES = (AXIS_DP, AXIS_FSDP)


def make_mesh(
    mesh_config: Optional[Dict[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a ``Mesh`` from ``{"dp": -1, "fsdp": 1, "tp": 1}`` axis sizes.

    Exactly one axis may be -1, meaning "all remaining devices". Multi-host
    TPU slices work transparently: ``jax.devices()`` enumerates the global
    device set after ``jax.distributed.initialize``.
    """
    mesh_config = dict(mesh_config or {})
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)

    sizes = {
        AXIS_DP: mesh_config.get(AXIS_DP, -1),
        AXIS_FSDP: mesh_config.get(AXIS_FSDP, 1),
        AXIS_TP: mesh_config.get(AXIS_TP, 1),
        AXIS_SP: mesh_config.get(AXIS_SP, 1),
        AXIS_PP: mesh_config.get(AXIS_PP, 1),
        AXIS_EP: mesh_config.get(AXIS_EP, 1),
    }
    unknown = set(mesh_config) - set(sizes)
    if unknown:
        raise ValueError(f"Unknown mesh axes: {sorted(unknown)}")

    wildcard = [k for k, v in sizes.items() if v == -1]
    if len(wildcard) > 1:
        raise ValueError(f"At most one mesh axis may be -1, got {wildcard}")
    fixed = int(np.prod([v for v in sizes.values() if v != -1]))
    if wildcard:
        if n % fixed != 0:
            raise ValueError(
                f"{n} devices not divisible by fixed axes product {fixed}"
            )
        sizes[wildcard[0]] = n // fixed
    elif fixed != n:
        raise ValueError(f"Mesh {sizes} needs {fixed} devices, have {n}")

    shape = (
        sizes[AXIS_DP], sizes[AXIS_FSDP], sizes[AXIS_TP], sizes[AXIS_SP],
        sizes[AXIS_PP], sizes[AXIS_EP],
    )
    device_array = np.asarray(devices).reshape(shape)
    return Mesh(
        device_array, (AXIS_DP, AXIS_FSDP, AXIS_TP, AXIS_SP, AXIS_PP, AXIS_EP)
    )


_PROGRAM_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "trlx_tpu_program_mesh", default=None
)


def program_mesh() -> Optional[Mesh]:
    """The mesh of the program jax is tracing right now, as declared by
    :func:`traced_on`; ``None`` outside one."""
    return _PROGRAM_MESH.get()


def traced_on(mesh: Mesh, fn: Callable) -> Callable:
    """``fn``, for ``jax.jit``, with ``mesh`` declared as the mesh its
    program runs on for as long as jax traces it.

    Model code is mesh-agnostic — GSPMD partitions it from the shardings at
    the jit boundary — and stays so. The one op that must know its mesh is
    a Mosaic kernel: XLA will not partition it ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map", jax
    0.9.0), and a ``shard_map`` needs the mesh at trace time, deep inside
    the model. Whoever builds a jitted program knows its mesh; wrapping the
    traced function here hands it down without threading it through every
    module (``ops/attention.py`` reads :func:`program_mesh`). Scoped to
    the trace, so two programs on two meshes — the learner's and an actor
    subset's — never see each other's."""

    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        token = _PROGRAM_MESH.set(mesh)
        try:
            return fn(*args, **kwargs)
        finally:
            _PROGRAM_MESH.reset(token)

    return scoped


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for [B, ...] data arrays: batch split over dp x fsdp."""
    return NamedSharding(mesh, P(BATCH_AXES))


def stacked_batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for [n_mb, B, ...] stacked-minibatch arrays: the scan axis is
    replicated, the batch axis splits over dp x fsdp (each scan slice then
    matches :func:`batch_sharding`)."""
    return NamedSharding(mesh, P(None, BATCH_AXES))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def local_batch_size(mesh: Mesh, global_batch_size: int) -> int:
    """Per-shard batch size; validates divisibility (reference computes
    global batch via WORLD_SIZE, `trlx.py:44`)."""
    n = mesh.shape[AXIS_DP] * mesh.shape[AXIS_FSDP]
    if global_batch_size % n != 0:
        raise ValueError(
            f"global batch {global_batch_size} not divisible by {n} data shards"
        )
    return global_batch_size // n
