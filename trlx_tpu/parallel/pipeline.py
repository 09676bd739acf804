"""Pipeline parallelism: GPipe-style microbatched stage execution.

Beyond the reference (SURVEY §2.9: pipeline parallel "NO ... not required
for parity; optional") — provided as a first-class mesh primitive so deep
models can shard *layers* over a ``pp`` axis when tensor parallelism alone
runs out of headroom. TPU-native design: every pp device runs the same
compiled program inside ``shard_map``; activations hop to the next stage via
``ppermute`` over ICI each tick, and the schedule (GPipe: S + M - 1 ticks
for S stages x M microbatches; interleaved: v·S + M - 1 cheaper ticks) is a
``lax.fori_loop`` with masked writes — no host control flow.

The primitive is deliberately model-agnostic: ``stage_fn(stage_params, h)
-> h`` with shape-preserving activations, stage params stacked on a leading
[S] axis (sharded over ``pp``). Autodiff works through the schedule
(``ppermute`` transposes to the inverse permutation), so this composes with
training, not just inference.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def spmd_stack(*xs):
    """``jnp.stack(xs, axis=0)`` built from ``dynamic_update_slice`` writes.

    XLA's SPMD partitioner mis-lowers a ``concatenate``/``stack`` whose
    output feeds a ``shard_map`` with a ``P("pp")`` in_spec on any mesh
    with a second size>1 axis: each stage reads wrong slices of the
    stacked operand (jit-only; eager is exact). Same compiler-bug family
    as the sharded rollout-concat replica-sum
    (``data/ppo_types.py::concat_rollouts``); minimal standalone repro +
    the workaround A/B in ``tools/pp_miscompile_repro.py``. Every
    stage-stacking path MUST build its [S]-leading arrays through this
    helper, never ``jnp.stack``/``jnp.concatenate``."""
    first = xs[0]
    buf = jnp.zeros((len(xs),) + first.shape, first.dtype)
    for i, x in enumerate(xs):
        buf = jax.lax.dynamic_update_slice(
            buf, x.astype(first.dtype)[None], (i,) + (0,) * first.ndim
        )
    return buf


def stack_stage_params(params_list):
    """Stack per-stage param pytrees on a leading [S] axis (shard over pp)."""
    return jax.tree_util.tree_map(spmd_stack, *params_list)


def stack_stage_params_interleaved(chunk_trees, stages: int, virtual: int):
    """[v*S] per-chunk param trees -> leaves [S, v, ...]: chunk
    ``c = lap*S + d`` goes to device d, lap ``lap`` (round-robin layer
    placement for the interleaved schedule)."""
    device_trees = []
    for d in range(stages):
        laps = [chunk_trees[lap * stages + d] for lap in range(virtual)]
        device_trees.append(jax.tree_util.tree_map(spmd_stack, *laps))
    return stack_stage_params(device_trees)


def pipeline_span_layer_units(S: int, M: int, L: int, v: int = 1) -> int:
    """Schedule span in single-layer compute units (layer cost = 1).

    GPipe (v=1): ``(S + M - 1)`` ticks of ``L/S`` layers. Interleaved
    (v>1): ``(v*S + M - 1)`` ticks of ``L/(v*S)`` layers — the fill/drain
    bubble shrinks by ~v because each tick is v× cheaper while the steady
    term stays M*L/S. Per-device efficiency: ``M / (S + (M-1)/v)`` vs
    GPipe's ``M / (S + M - 1)``."""
    chunk = L // (S * v)
    return (v * S + M - 1) * chunk


def pipeline_apply(
    stage_fn: Callable,
    stacked_params,
    x: jax.Array,  # [B, ...] activations entering stage 0
    mesh: Mesh,
    axis_name: str = "pp",
    num_microbatches: int = 2,
    batch_axes=("dp", "fsdp"),
    aux=None,
    virtual_stages: int = 1,
    capture_stage: int = None,
    capture_only: bool = False,
) -> jax.Array:
    """Run ``x`` through S pipeline stages with M microbatches.

    ``stacked_params`` leaves are [S, ...] (stage-major) with S equal to the
    ``pp`` axis size (one stage per device); stage s applies
    ``stage_fn(params[s], h)``. ``num_microbatches`` must divide the
    *per-batch-shard* size ``x.shape[0] / (dp*fsdp)``. Returns activations
    after the last stage, with the same sharding as ``x``.

    ``aux`` (optional): a pytree of batch-leading [B, ...] arrays carried
    alongside the activations — e.g. an attention bias. Each stage receives
    the microbatch slice matching the activations it is processing, as a
    third argument: ``stage_fn(params, h, aux_mb)``. Unlike ``h``, aux does
    not travel over the wire (every device holds its batch shard).

    ``virtual_stages=v > 1`` runs the interleaved schedule: stacked_params
    leaves are [S, v, L/(S·v)-chunk, ...] (chunk c = ℓ·S + d lives on
    device d, lap ℓ — `stack_stage_params_interleaved`) and the span drops
    from ``(S+M-1)`` ticks of L/S layers to ``(v·S+M-1)`` ticks of
    L/(v·S) layers (:func:`pipeline_span_layer_units`). Differentiable
    like the GPipe path (the backward is the mirrored schedule). Requires
    ``M <= S`` and is train-only (no cache support).
    """
    # One schedule implementation: the cache-less path is the cached path
    # with an empty cache pytree, and the interleaved schedule is the same
    # tick with lap-indexed chunk params (round-3 reviews: hand-synced
    # copies of the pipeline tick invite silent divergence).
    if aux is None:
        def adapted(p, h, _aux, _cache, _idx):
            return stage_fn(p, h), {}
    else:
        def adapted(p, h, aux_m, _cache, _idx):
            return stage_fn(p, h, aux_m), {}

    res = pipeline_apply_cached(
        adapted, stacked_params, x, {}, 0, mesh,
        axis_name=axis_name, num_microbatches=num_microbatches,
        batch_axes=batch_axes, aux=aux, virtual_stages=virtual_stages,
        capture_stage=capture_stage, capture_only=capture_only,
    )
    if capture_stage is None:
        return res[0]
    return res[0], res[2]  # (out — INVALID if capture_only, capture)


def pipeline_apply_cached(
    stage_fn: Callable,
    stacked_params,
    x: jax.Array,  # [B, T, ...] activations entering stage 0
    cache,  # leaves [L, B, C, ...]: layer-major KV buffers, L sharded over pp
    cache_index,
    mesh: Mesh,
    axis_name: str = "pp",
    num_microbatches: int = 2,
    batch_axes=("dp", "fsdp"),
    aux=None,
    virtual_stages: int = 1,
    capture_stage: int = None,
    capture_only: bool = False,
    static_cache=None,
    capture_all: bool = False,
):
    """The pipeline schedule — one implementation for all three uses:
    cache-less train forward (via :func:`pipeline_apply`), rollout decode
    with STAGE-RESIDENT KV caches, and the interleaved train schedule
    (``virtual_stages > 1``, cache-less only).

    ``capture_all=True`` (v=1, cache-less): EVERY device additionally
    saves the activation entering its own stage for each microbatch and
    the schedule returns it as a third output shaped ``[S, M, B/M, ...]``
    sharded ``P(pp, None, batch)`` — the residuals of the rematerialized
    pipeline backward (:func:`pipeline_apply_remat`), which stores only
    stage INPUTS instead of letting autodiff save every layer's
    internals across the whole schedule.

    ``static_cache`` (optional): a READ-ONLY stage-resident tree with the
    same layer-major ``[L, B, ...]`` layout and ``P(pp, batch)`` sharding
    as ``cache`` — e.g. precomputed seq2seq cross-attention K/V. It is
    microbatch-sliced like the cache and handed to ``stage_fn`` as an
    extra argument before ``cache_index`` (signature becomes
    ``stage_fn(params, h, aux_mb, cache_mb, static_mb, cache_index)``)
    but never written back.

    ``capture_stage=k`` additionally collects the activation ENTERING stage
    k for every microbatch (the hydra shared-trunk branch point — the
    boundary between stage k-1 and k) and returns it as a third output
    ``[B, ...]`` shaped like ``x``. v=1 only. With ``capture_only=True``
    the schedule stops after tick ``k + M - 1`` (the last microbatch's
    arrival at stage k) — the first output is then INVALID (stages >= k
    never ran to completion); callers take only the capture.

    ``cache`` leaves are layer-major ``[L, B, C, ...]`` sharded ``P(pp,
    batch_axes)`` — each device permanently holds the KV buffers of its own
    stage's ``L/S`` layers (plus its dp/fsdp batch shard), so a pp mesh
    shards rollout *memory and compute* instead of replicating the full
    model per device. Each tick, the active stage reads/writes only the
    microbatch rows it is processing; writes at inactive (bubble) ticks are
    masked back to the old values.

    ``stage_fn(stage_params, h, aux_mb, stage_cache_mb, cache_index) ->
    (h, new_stage_cache_mb)`` where ``stage_cache_mb`` leaves are
    ``[L/S, b_mb, C, ...]``.

    Interleaved tick math (v > 1): microbatch m enters chunk 0 at tick m
    and advances one chunk per tick, so chunk c of m runs at tick m + c on
    device c mod S. With M <= S each device sees at most one live (m, c)
    per tick (m ≡ t - d (mod S) has one solution in [0, M)), every
    activation is consumed the tick after it arrives, and the single ring
    wire buffer suffices; the lap (= c // S) selects which of the device's
    v param chunks runs. The v = 1 indexing (m = t - idx, no mod) also
    covers M > S, which the mod form cannot — hence the branch.

    Returns ``(out, new_cache)`` with the same shardings as ``(x, cache)``.
    """
    S = mesh.shape[axis_name]
    M = num_microbatches
    v = virtual_stages
    if capture_all:
        if capture_stage is not None or v > 1:
            raise NotImplementedError(
                "capture_all (remat residuals) is v=1 and exclusive with "
                "capture_stage"
            )
        if jax.tree_util.tree_leaves(cache):
            raise NotImplementedError(
                "capture_all is for the cache-less train schedule"
            )
    if capture_stage is not None:
        if v > 1:
            raise NotImplementedError(
                "capture_stage (hydra branch point) is not available on "
                "the interleaved schedule: the stage boundary is not a "
                "single device's input there"
            )
        if not (0 <= capture_stage < S):
            raise ValueError(
                f"capture_stage={capture_stage} outside [0, {S})"
            )
    if v > 1:
        if M > S:
            raise ValueError(
                f"interleaved schedule requires num_microbatches <= pp "
                f"stages ({M} > {S}): with M > S two microbatches collide "
                f"on one device in the same tick; drop virtual_stages or "
                f"microbatches"
            )
        if jax.tree_util.tree_leaves(cache):
            raise NotImplementedError(
                "interleaved schedule is train-only: the stage-resident "
                "KV cache layout is contiguous stage-major (v=1)"
            )
        for leaf in jax.tree_util.tree_leaves(stacked_params):
            if leaf.shape[0] != S or leaf.shape[1] != v:
                raise ValueError(
                    f"interleaved stage params must be [S={S}, v={v}, ...]; "
                    f"got leaf {leaf.shape}"
                )
    else:
        for leaf in jax.tree_util.tree_leaves(stacked_params):
            if leaf.shape[0] != S:
                raise ValueError(
                    f"stacked stage params have leading dim {leaf.shape[0]} "
                    f"but the {axis_name!r} axis has {S} devices (one stage "
                    f"per device); extra stages would be silently dropped"
                )
    for leaf in jax.tree_util.tree_leaves((cache, static_cache)):
        if leaf.shape[0] % S:
            raise ValueError(
                f"cache layer dim {leaf.shape[0]} must divide pp={S}"
            )
    # mesh.shape is host metadata, not a tracer; the int() is trace-static
    n_batch_shards = int(np.prod([mesh.shape[a] for a in batch_axes]))  # tpu-lint: disable=host-scalar-cast
    B_local = x.shape[0] // n_batch_shards
    if x.shape[0] % n_batch_shards or B_local % M:
        raise ValueError(
            f"batch {x.shape[0]} must divide into {n_batch_shards} shards of "
            f"{M} microbatches"
        )

    def local(params, x, cache, static, cache_index, aux):
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        idx = jax.lax.axis_index(axis_name)
        n = jax.lax.psum(1, axis_name)
        b = x.shape[0]
        bm = b // M
        mbs = x.reshape((M, bm) + x.shape[1:]).astype(x.dtype)
        aux_mbs = jax.tree_util.tree_map(
            lambda a: a.reshape((M, a.shape[0] // M) + a.shape[1:]), aux
        )

        perm = [(i, (i + 1) % n) for i in range(n)]
        pp_zero = (0.0 * jax.lax.axis_index(axis_name)).astype(x.dtype)
        buf0 = jnp.zeros_like(mbs[0]) + pp_zero
        outs0 = jnp.zeros_like(mbs) + pp_zero

        want_caps = capture_stage is not None or capture_all

        def tick(t, carry):
            # caps rides the carry only when a capture is requested — the
            # hot paths (train forward, per-token decode) carry no dead
            # buffer
            if want_caps:
                buf, outs, cache, caps = carry
            else:
                (buf, outs, cache), caps = carry, None
            if v > 1:
                m = (t - idx) % n
                c = t - m  # chunk index; c ≡ idx (mod n) by construction
                lap = jnp.clip(c // n, 0, v - 1)
                active = jnp.logical_and(
                    m < M, jnp.logical_and(c >= 0, c < v * n)
                )
                is_first = c == 0
                is_last = c == v * n - 1
                chunk_params = jax.tree_util.tree_map(
                    lambda p: jax.lax.dynamic_index_in_dim(
                        p, lap, axis=0, keepdims=False
                    ),
                    params,
                )
            else:
                m = t - idx
                active = jnp.logical_and(m >= 0, m < M)
                is_first = idx == 0
                is_last = idx == n - 1
                chunk_params = params
            m_c = jnp.clip(m, 0, M - 1)
            h_in = jnp.where(is_first, mbs[m_c], buf)
            if capture_all:
                # every device saves its own stage's input (remat residual)
                caps = jnp.where(active, caps.at[m_c].set(h_in), caps)
            elif capture_stage is not None:
                # the activation ENTERING stage k (the hydra branch point)
                caps = jnp.where(
                    jnp.logical_and(active, idx == capture_stage),
                    caps.at[m_c].set(h_in),
                    caps,
                )
            aux_m = jax.tree_util.tree_map(lambda a: a[m_c], aux_mbs)
            mb_slice = lambda c_: jax.lax.dynamic_slice_in_dim(
                c_, m_c * bm, bm, axis=1
            )
            old_mb = jax.tree_util.tree_map(mb_slice, cache)
            if static_cache is None:
                h_out, new_mb = stage_fn(
                    chunk_params, h_in, aux_m, old_mb, cache_index
                )
            else:
                static_mb = jax.tree_util.tree_map(mb_slice, static)
                h_out, new_mb = stage_fn(
                    chunk_params, h_in, aux_m, old_mb, static_mb, cache_index
                )
            # bubble ticks compute on garbage: mask their cache writes
            new_mb = jax.tree_util.tree_map(
                lambda nk, ok: jnp.where(active, nk.astype(ok.dtype), ok),
                new_mb, old_mb,
            )
            cache = jax.tree_util.tree_map(
                lambda c_, nk: jax.lax.dynamic_update_slice_in_dim(
                    c_, nk, m_c * bm, axis=1
                ),
                cache, new_mb,
            )
            outs = jnp.where(
                jnp.logical_and(active, is_last),
                outs.at[m_c].set(h_out),
                outs,
            )
            wire = jnp.where(active, h_out, buf * 0.0)
            buf = jax.lax.ppermute(wire, axis_name, perm)
            if not want_caps:
                return buf, outs, cache
            return buf, outs, cache, caps

        n_ticks = v * S + M - 1
        if capture_stage is not None and capture_only:
            # last microbatch reaches stage k at tick k + M - 1
            n_ticks = capture_stage + M
        if not want_caps:
            _, outs, cache = jax.lax.fori_loop(
                0, n_ticks, tick, (buf0, outs0, cache)
            )
        else:
            caps0 = jnp.zeros_like(mbs) + pp_zero
            _, outs, cache, caps = jax.lax.fori_loop(
                0, n_ticks, tick, (buf0, outs0, cache, caps0)
            )
        outs = jnp.where(idx == n - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, axis_name)
        if not want_caps:
            return outs.reshape(x.shape), cache
        if capture_all:
            # per-device stage residuals: [1, M, bm, ...] -> global
            # [S, M, B/M, ...] under P(pp, None, batch)
            return outs.reshape(x.shape), cache, caps[None]
        caps = jnp.where(idx == capture_stage, caps, jnp.zeros_like(caps))
        caps = jax.lax.psum(caps, axis_name)
        return outs.reshape(x.shape), cache, caps.reshape(x.shape)

    from jax import shard_map

    # Stage params enter shard_map sharded over pp ONLY: each device holds
    # its stage's L/S layers *fully materialized* for the loop's duration —
    # any fsdp sharding on these params is all-gathered at this boundary.
    # That is a deliberate memory/simplicity trade: keeping fsdp inside the
    # loop would need a per-layer all_gather in the stage scan (gather one
    # layer, compute, free) to avoid holding the gathered stage anyway.
    # So pp here shards *compute and params across stages*; combine with
    # fsdp to shard the *other* stages' memory, not the resident stage's.
    param_specs = jax.tree_util.tree_map(
        lambda _: P(axis_name), stacked_params
    )
    x_spec = P(batch_axes)
    cache_specs = jax.tree_util.tree_map(
        lambda _: P(axis_name, batch_axes), cache
    )
    aux_specs = jax.tree_util.tree_map(lambda _: P(batch_axes), aux)
    if capture_all:
        out_specs = (x_spec, cache_specs, P(axis_name, None, batch_axes))
    elif capture_stage is not None:
        out_specs = (x_spec, cache_specs, x_spec)
    else:
        out_specs = (x_spec, cache_specs)
    static_specs = jax.tree_util.tree_map(
        lambda _: P(axis_name, batch_axes), static_cache
    )
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs, x_spec, cache_specs, static_specs, P(), aux_specs),
        out_specs=out_specs,
    )(stacked_params, x, cache, static_cache, cache_index, aux)


def _partition_inexact(tree):
    """Split a pytree into (inexact, other) halves with ``None`` sentinels.

    The remat backward differentiates through the stage recompute; int/bool
    leaves (rotary position_ids in aux, gpt_neo's local-band flags in the
    stage tree) have no cotangent — ``jax.vjp`` hands back float0 arrays
    that neither accumulate nor pass a dtype cast. They are carried to the
    recompute via closure instead and get float0 zeros at the custom_vjp
    boundary."""
    inexact = lambda x: jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact)
    fpart = jax.tree_util.tree_map(lambda x: x if inexact(x) else None, tree)
    opart = jax.tree_util.tree_map(lambda x: None if inexact(x) else x, tree)
    return fpart, opart


def _combine_inexact(fpart, opart):
    """Inverse of :func:`_partition_inexact` (None sentinels as leaves)."""
    return jax.tree_util.tree_map(
        lambda f, o: o if f is None else f,
        fpart,
        opart,
        is_leaf=lambda x: x is None,
    )


def _insert_float0(cotangents_f, primals):
    """Fill a partitioned cotangent tree back to the primal structure,
    with float0 zeros (the required custom_vjp cotangent for non-inexact
    primal inputs) at the ``None`` positions."""
    return jax.tree_util.tree_map(
        lambda c, p: np.zeros(np.shape(p), jax.dtypes.float0)
        if c is None
        else c,
        cotangents_f,
        primals,
        is_leaf=lambda x: x is None,
    )


def pipeline_apply_remat(
    stage_fn: Callable,
    stacked_params,
    x: jax.Array,
    mesh: Mesh,
    axis_name: str = "pp",
    num_microbatches: int = 2,
    batch_axes=("dp", "fsdp"),
    aux=None,
) -> jax.Array:
    """:func:`pipeline_apply` with a REMATERIALIZED, hand-scheduled
    backward (the memory half of 1F1B — the part that matters; the bubble
    spans of GPipe-fwd+bwd and 1F1B are equal at 2(S+M-1) ticks).

    Autodiff through the fori_loop schedule saves every tick's stage
    internals (all L/S layers' activations per microbatch) for the whole
    span. Here the forward saves ONLY each stage's input activation per
    microbatch (``capture_all``), and the custom backward re-runs the
    mirrored schedule: at each reverse tick the active device RECOMPUTES
    its stage forward from the saved input under ``jax.vjp`` and applies
    the arriving cotangent — param grads accumulate per stage, activation
    cotangents hop backward over the inverse ``ppermute`` ring, aux
    cotangents (shared bias tensors) accumulate across stages via psum.
    Peak residual memory drops from O(span · per-layer internals) to
    O(M stage inputs) per device + one stage's recompute working set.

    v=1, cache-less, train-schedule only. Non-inexact leaves (int32
    rotary position_ids in aux, gpt_neo's bool band flags in the stage
    tree) ride to the recompute via closure and receive float0
    cotangents at the custom_vjp boundary (round 5). Gradient parity vs
    the autodiffed schedule is pinned in
    ``tests/test_pipeline_parallel.py`` and, per causal family,
    ``tests/test_pp_integration.py::
    test_pp_remat_matches_autodiff_nonfloat_leaves``.
    """
    S = mesh.shape[axis_name]
    M = num_microbatches
    aux_dict = {} if aux is None else aux
    has_aux = bool(jax.tree_util.tree_leaves(aux_dict))
    x_dtype = x.dtype  # static metadata only — bwd must not touch outer tracers

    def call_stage(p, h, a):
        return stage_fn(p, h, a) if has_aux else stage_fn(p, h)

    def fwd_schedule(params, xx, a, capture):
        def adapted(p, h, aux_m, _cache, _idx):
            return call_stage(p, h, aux_m), {}

        return pipeline_apply_cached(
            adapted, params, xx, {}, 0, mesh,
            axis_name=axis_name, num_microbatches=M,
            batch_axes=batch_axes, aux=a if has_aux else None,
            capture_all=capture,
        )

    @jax.custom_vjp
    def run(params, xx, a):
        return fwd_schedule(params, xx, a, capture=False)[0]

    def run_fwd(params, xx, a):
        out, _, saves = fwd_schedule(params, xx, a, capture=True)
        return out, (params, saves, a)

    def run_bwd(res, g):
        params, saves, a = res

        def local_bwd(params, saves, a, g):
            params = jax.tree_util.tree_map(lambda p: p[0], params)
            saves = saves[0]  # [M, bm, ...] — this stage's inputs
            idx = jax.lax.axis_index(axis_name)
            n = jax.lax.psum(1, axis_name)
            b = g.shape[0]
            bm = b // M
            g_mbs = g.reshape((M, bm) + g.shape[1:]).astype(g.dtype)
            aux_mbs = jax.tree_util.tree_map(
                lambda t: t.reshape((M, t.shape[0] // M) + t.shape[1:]), a
            )
            # differentiate only the inexact leaves — int/bool leaves
            # (rotary position_ids, gpt_neo band flags) ride to the
            # recompute via closure and take no cotangent
            params_f, params_o = _partition_inexact(params)
            aux_f, aux_o = _partition_inexact(aux_mbs)
            inv_perm = [(i, (i - 1) % n) for i in range(n)]
            pp_zero = (0.0 * idx).astype(g.dtype)
            buf0 = jnp.zeros_like(g_mbs[0]) + pp_zero
            dxs0 = jnp.zeros_like(g_mbs) + pp_zero
            # accumulator inits derive from the data (0*value keeps every
            # varying-axis annotation: params vary over pp, aux over the
            # batch axes + pp via the idx marker) — synthesized zeros are
            # axis-invariant and shard_map rejects the loop carry
            dp0 = jax.tree_util.tree_map(
                lambda p: (0.0 * p).astype(
                    jnp.promote_types(p.dtype, jnp.float32)
                ),
                params_f,
            )
            da0 = jax.tree_util.tree_map(
                lambda t: (0.0 * t).astype(
                    jnp.promote_types(t.dtype, jnp.float32)
                )
                + (0.0 * idx),
                aux_f,
            )

            def tick(r, carry):
                buf, dxs, dparams, daux = carry
                # stage idx handled microbatch m forward at tick m + idx;
                # its cotangent arrives in mirrored order at r = m + (n-1-idx)
                m = r - (n - 1 - idx)
                active = jnp.logical_and(m >= 0, m < M)
                m_c = jnp.clip(m, 0, M - 1)
                gbar = jnp.where(idx == n - 1, g_mbs[m_c], buf)
                aux_m_f = jax.tree_util.tree_map(lambda t: t[m_c], aux_f)
                aux_m_o = jax.tree_util.tree_map(lambda t: t[m_c], aux_o)
                h_in = saves[m_c]
                _, vjp_fn = jax.vjp(
                    lambda pf, h, af: call_stage(
                        _combine_inexact(pf, params_o),
                        h,
                        _combine_inexact(af, aux_m_o),
                    ),
                    params_f,
                    h_in,
                    aux_m_f,
                )
                dp, dh, da = vjp_fn(gbar.astype(g.dtype))
                # where, not multiply-by-flag: a nan computed on a bubble
                # tick's garbage must not poison the accumulator (0*nan)
                dparams = jax.tree_util.tree_map(
                    lambda acc, d: acc
                    + jnp.where(active, d.astype(acc.dtype), 0.0),
                    dparams, dp,
                )
                daux = jax.tree_util.tree_map(
                    lambda acc, d: acc.at[m_c].add(
                        jnp.where(active, d.astype(acc.dtype), 0.0)
                    ),
                    daux, da,
                )
                dxs = jnp.where(
                    jnp.logical_and(active, idx == 0),
                    dxs.at[m_c].set(dh.astype(dxs.dtype)),
                    dxs,
                )
                wire = jnp.where(active, dh.astype(buf.dtype), buf * 0.0)
                buf = jax.lax.ppermute(wire, axis_name, inv_perm)
                return buf, dxs, dparams, daux

            _, dxs, dparams, daux = jax.lax.fori_loop(
                0, S + M - 1, tick, (buf0, dxs0, dp0, da0)
            )
            # each data shard saw only its rows of every microbatch, and
            # dparams is still the WHOLE batch's sum: this shard_map tracks
            # which axes a value varies over, the stage parameters do not
            # vary over the batch axes, and `jax.vjp` therefore hands back
            # their cotangent already summed over those axes (the transpose
            # of the broadcast it inserted). A psum here would count every
            # shard's rows once more for each data shard.
            dxs = jnp.where(idx == 0, dxs, jnp.zeros_like(dxs))
            dxs = jax.lax.psum(dxs, axis_name)
            # aux is shared by every stage: total cotangent sums over pp
            daux = jax.lax.psum(daux, axis_name)
            a_f_full, _ = _partition_inexact(a)
            daux = jax.tree_util.tree_map(
                lambda t, orig: t.reshape((t.shape[0] * t.shape[1],) + t.shape[2:])
                .astype(orig.dtype),
                daux, a_f_full,
            )
            dparams = jax.tree_util.tree_map(
                lambda d, p: d[None].astype(p.dtype), dparams, params_f
            )
            return dparams, dxs.reshape(g.shape), daux

        from jax import shard_map

        param_specs = jax.tree_util.tree_map(lambda _: P(axis_name), params)
        x_spec = P(batch_axes)
        aux_specs = jax.tree_util.tree_map(lambda _: P(batch_axes), a)
        # cotangent outputs exist only for the inexact leaves; the int/bool
        # leaves get float0 zeros outside the shard_map
        params_f_outer, _ = _partition_inexact(params)
        aux_f_outer, _ = _partition_inexact(a)
        dparams, dx, daux = shard_map(
            local_bwd,
            mesh=mesh,
            in_specs=(
                param_specs, P(axis_name, None, batch_axes), aux_specs, x_spec
            ),
            out_specs=(
                jax.tree_util.tree_map(lambda _: P(axis_name), params_f_outer),
                x_spec,
                jax.tree_util.tree_map(lambda _: P(batch_axes), aux_f_outer),
            ),
        )(params, saves, a, g)
        return (
            _insert_float0(dparams, params),
            dx.astype(x_dtype),
            _insert_float0(daux, a),
        )

    run.defvjp(run_fwd, run_bwd)
    return run(stacked_params, x, aux_dict)
