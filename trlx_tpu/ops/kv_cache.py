"""The KV cache: how it is stored, written, classified and handed to
attention. The one module that knows; it imports nothing from the models,
the inference engine, the serving tier or the trainers.

    ops/kv_cache.py  <-  ops/attention.py  <-  models/*  <-  inference/engine.py

A cache is a tuple over layers of plain dicts (the engine donates them,
``models/pp_runner.py`` and the analysis harness trace them, checkpoints do
not hold them). Which storage a layer's dict is, :func:`cache_kind` reads
from its keys and ranks — the one place that does:

- **dense** (:func:`kv_buffers`): ``{"k", "v"}`` ``[B, C, H, Dh]`` in the
  compute dtype; what every family's ``init_cache`` allocates and every
  prefill writes. :func:`dense_write_read` writes a call's rows at
  ``cache_index`` and returns the buffers to attend over.
- **folded** (:func:`decode_kv_layout`): the dense cache with heads folded
  into the minor axis, ``[B, C, H*Dh]``; the layout the fixed sampler's
  decode loop carries, **layer-major** where a layer's buffer is small
  enough for the compiler to stage (:func:`staged_by_the_compiler`): one
  array a kind for all layers (``[L, B, C, H*Dh]``), which a model hands
  layer by layer through :func:`layer_cache` / :func:`with_layer_cache`
  (the layer's index rides under ``"layer"``; a dict without it is one
  layer's own buffers: a larger layer's, the pp stage scan's call). Its
  in-place write and single read are attention
  math and live in ``ops/attention.py::_decode_read``.
- **paged** (:func:`init_paged_cache`): the dense pools plus
  ``"block_tables"``; the continuous-batching engine's cache.
  :func:`paged_write_read` writes through the tables and returns either the
  logical view or the pools as stored.

Each composes with **int8** storage (``"k_scale"``/``"v_scale"`` present:
values quantised per (position, head) by :func:`quantize_kv`, bf16 scales;
``kv_cache_dtype``, resolved by :func:`resolve_kv_cache_dtype`), and a paged
cache may carry a **shared-prefix overlay** (``"shared_tables"`` present).
``ops/attention.py::decode_attention`` picks the read from the kind.

A fourth kind holds no keys at all:

- **state** (:func:`state_buffers`): ``{"ssm_state", "conv_tail"}``, what a
  state-space layer (``ops/ssm.py``) keeps of a sequence whatever its
  length: ``[B, H, P, N]`` in ``state_dtype`` and the last ``K - 1`` inputs
  of its convolution. No capacity axis, no block table, no int8 form; it
  never reaches ``decode_attention``. A model that mixes the kinds
  (:func:`hybrid_cache`) hands the engine a tuple whose layers differ, and
  everything that walks a cache asks :func:`cache_kind` layer by layer.

And a layer of keys may keep a **tail** beside them (:func:`tail_buffers`):
rows kept a slot under keys that start with ``tail_`` (a convolution's last
inputs, a shifted value's source; ``ops/cca.py``), in ``state_dtype``, with
no capacity axis. ``cache_kind(...).tail`` names a layer's by-slot keys (a
state layer's: all of them), which is the one answer the engine's walks
use: those keys are taken and set back by slot, the rest are pools. The
tail never reaches ``decode_attention``: the model hands it the layer
without (:func:`split_tail`).

A **latent** layer (:func:`latent_buffers`) keeps one row a position and
no values: ``{"k": [B, C, 1, W]}`` with no ``"v"`` beside it, ``W`` a
compressed key-value latent and the one rotated key part all heads share
(``ops/attention.py::decode_attention`` with ``latent``: the values are the
row's leading columns, or what an up-projection makes of them). It is
written, paged, scattered by block (one head: ``H`` = 1) and read as
stored like any pool of keys; every write and read here skips the absent
``"v"``. ``cache_kind(...).latent`` says so. No int8 form, no shared-prefix
pool (both refused by name where they would be built). Whoever holds such
a pool across programs holds its rows padded to whole lanes
(:func:`hold_pool`), or every program copies the pool in and out; a pool
may therefore be **wider than the rows written to it**, the write pads a
row with zeros and the reads take a row's own columns.

The pools are sized by **KV heads**: a family with fewer KV heads than
query heads (grouped-query attention) allocates and reads ``H_kv`` of them,
and the reads in ``ops/attention.py`` map query head ``h`` to KV head
``h // (H_q / H_kv)``. Whoever holds a paged pool across programs holds a
head that is several whole lane rows wide (256, 512) **as those rows**
(:func:`hold_pool`: ``[B, C, H * Dh // 128, 128]``, the same bytes), or
every admission re-tiles the pool around its block write; the writes and
reads here take such a head's own rows.

Paging
------

vLLM-style paging adapted to the TPU/GSPMD substrate: physical storage
keeps the fixed sampler's ``[B, capacity, heads, head_dim]`` per-layer
buffers (so the batch axis shards over dp×fsdp exactly like the fixed
cache, and an ``sp`` mesh axis shards the capacity axis), while a per-slot
**block table** indirects logical token positions through fixed-size
blocks:

- physical layout: capacity = ``n_blocks * block_size`` contiguous
  positions per slot; block ``j`` of slot ``b`` is positions
  ``[j*bs, (j+1)*bs)`` of ``pool[b]``;
- ``block_tables[b, j]`` maps *logical* block ``j`` to a *physical*
  block index inside slot ``b``'s region. Writes and reads both resolve
  through the table, so a recycled slot can be handed a permuted table
  (the engine rotates tables on recycle — the indirection is exercised,
  not decorative);
- a call with more than one position (prefill, the verify step) reads
  the slot's **logical view** — a per-position gather back into logical
  order, materialised — so attention over it is the computation the
  fixed cache runs, term for term in the same order;
- an admission call is a **group's**: the engine hands the model the
  pools whole with the group's fresh tables and, under ``"slot_ids"``,
  the pool row of each of the call's rows. Its columns are scattered at
  (slot, physical position) where they lie and its view is gathered from
  there; no slice of the group's rows, no merge back (on the v5e the two
  were 16 of a 39.6 ms chunk forward of pythia-1.4b; PERF.md §6, PR 42).
  Where the columns are whole blocks (:func:`writes_whole_blocks`) they
  go in a block a window, a sixteenth of the scatter's indices for the
  same bytes (PERF.md §6, PR 49);
- the decode step (one position a slot, a floating pool) reads the pool
  **as stored**: attention is a sum over positions, so it runs in
  physical order with the bias re-indexed (:func:`stored_order_bias`)
  and nothing is gathered or copied. The terms are the same, their
  order within a slot is rotated with its table, so a float32 sum may
  differ in its last bits; ``rollout.engine: continuous`` stays per-row
  token-identical to the fixed sampler (tests/test_inference_engine.py).
  On the v5e the gathered view cost 1.36 ms a layer of a 32 x 640 x 16 x
  128 pool (the compiler converted it to float32, whole, on top of the
  gather); the stored read costs 0.31 (PERF.md §5-§6, PR 28).

``kv_cache_dtype`` is honored exactly as in the dense cache: ``int8``
stores quantized values + per-(position, head) bf16 scales and dequantizes
on read — the same absmax/127 quantizer, so int8 paged and int8 dense
caches hold identical bits per logical position. An int8 pool is always
read through the dequantised logical view (its ``[B, C, H, 1]`` scales are
no layout to read in place).

Why per-slot block regions instead of one global pool: a single shared
pool would put every slot's blocks behind one un-sharded physical axis,
breaking the dp×fsdp batch sharding that keeps decode local to each data
shard. Per-slot regions keep GSPMD layouts identical to the fixed cache;
the paging machinery (tables, block-granular recycling) is unchanged,
only the allocator's arena is per-slot.

**Cross-request prefix sharing** (the serving tier,
:mod:`trlx_tpu.serving`): when the engine is built with
``prefix_pool_blocks > 0`` each layer additionally carries

- ``shared_k`` / ``shared_v`` (+ int8 scales) — a *replicated* flat pool
  of ``prefix_pool_blocks * block_size`` positions holding published
  prefix KV (replicated like the params: system prompts are small and
  every data shard reads them, so the pool is a broadcast structure, not
  a batch-sharded one — the per-slot regions' sharding story is
  untouched);
- ``shared_tables[b, j]`` — logical block ``j`` of slot ``b`` READS from
  shared-pool block ``shared_tables[b, j]`` when ``>= 0`` (else from the
  slot's private region through ``block_tables``);
- ``publish_tables[b, j]`` — prefill WRITES logical block ``j``'s K/V
  into shared-pool block ``publish_tables[b, j]`` when ``>= 0`` (the
  donor request publishing a new prefix).

Sharing semantics are exact, not approximate: a shared block's bits are
the donor prefill's bits, which equal the bits the reader's own prefill
computes for the same leading padded columns (causal attention — column
``j``'s K/V depends only on columns ``<= j``; same program shape, same
params, same columns ⇒ same bits), and the read side is a gather — a
permutation that re-associates nothing. Private writes to shared
columns are dropped (the region's leading blocks stay unwritten — the
``engine/prefix_blocks_saved`` accounting), writes during decode land at
positions ``>= Q`` which are never shared, so a shared block is
immutable after publication — copy-on-first-divergent-write degenerates
to "the first divergent block is private from admission", enforced
host-side by :class:`trlx_tpu.serving.prefix_cache.PrefixBlockPool`
(which only maps *fully-covered* leading blocks and allocates a fresh
pool block on any content divergence instead of mutating a published
one).
"""

from __future__ import annotations

import numbers
import warnings
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.telemetry import get_metrics

# KV cache: tuple over layers of {"k": [B, C, H, Dh], "v": [B, C, H, Dh]}
Cache = Tuple[Dict[str, jax.Array], ...]


# ----------------------------- storage dtype ----------------------------- #

VALID_KV_CACHE_DTYPES = ("bfloat16", "int8", "auto")


def validate_kv_cache_dtype(value: str) -> None:
    """Shared __post_init__ validation for every causal family config."""
    if value not in VALID_KV_CACHE_DTYPES:
        raise ValueError(
            f"kv_cache_dtype={value!r} is not supported (choose one of "
            f"{VALID_KV_CACHE_DTYPES}) — an unrecognized value would "
            "otherwise silently fall back to bf16 buffers"
        )


# The int8 KV cache's capacity ceiling under ``kv_cache_dtype="auto"``: the
# largest capacity at which the int8 read has been measured on the chip.
# The fixed sampler decodes from :func:`decode_kv_layout`, where the int8
# read runs within ~1.7x of its bytes' time at capacity 512 (cell
# ``ppo-gpt2m-longgen``, PERF.md §5-§6, PR 25); nothing beyond 512 is on
# record, so the threshold stays where the benchmark's configurations state
# it until a long-context cell measures past it. The paged engine reads a
# floating pool as stored since PR 28; its int8 pool is still gathered and
# dequantised whole at every step, so int8 under ``rollout.engine:
# continuous`` is the slow choice at any capacity until that read exists.
INT8_KV_MAX_CAPACITY = 512


def resolve_kv_cache_dtype(kv_cache_dtype: str, capacity: int) -> str:
    """Resolve ``"auto"`` by cache capacity and warn when an explicit
    ``"int8"`` is forced past the capacity it is measured to — a
    long-context config must not silently take an unmeasured read."""
    if kv_cache_dtype == "auto":
        return "int8" if capacity <= INT8_KV_MAX_CAPACITY else "bfloat16"
    if kv_cache_dtype == "int8" and capacity > INT8_KV_MAX_CAPACITY:
        warnings.warn(
            f"kv_cache_dtype='int8' with a {capacity}-token cache: the fused "
            f"int8 read is measured only up to capacity "
            f"{INT8_KV_MAX_CAPACITY} (PERF.md, cell ppo-gpt2m-longgen) and "
            "nothing is on record beyond it; the paged engine's int8 read "
            "still gathers and dequantises the whole view a step. Set "
            "kv_cache_dtype='auto' to take int8 only where it is measured, "
            "or 'bfloat16' to silence this"
        )
    return kv_cache_dtype


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization over the head dim: per (batch, token,
    head) absmax/127 scale. Returns (int8 values, scale[..., :1])."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(scale, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


# ------------------------------ dense, folded ---------------------------- #


def kv_buffers(
    n_layer: int,
    batch_size: int,
    capacity: int,
    n_kv_head: int,
    head_dim: int,
    dtype,
    kv_cache_dtype: str = "bfloat16",
) -> Cache:
    """Per-layer fixed-capacity KV buffers, shared by every causal family,
    ``n_kv_head`` heads wide (the query heads where every head has its own).
    ``"int8"`` stores int8 values + per (token, head) bf16 scales — ~half
    the HBM traffic of a bf16 cache (every write and read here handles
    both); ``"auto"`` picks int8 only up to the capacity it is measured
    to."""
    shape = (batch_size, capacity, n_kv_head, head_dim)
    kv_cache_dtype = resolve_kv_cache_dtype(kv_cache_dtype, capacity)
    if kv_cache_dtype == "int8":
        sshape = (batch_size, capacity, n_kv_head, 1)
        return tuple(
            {
                "k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.bfloat16),
                "v_scale": jnp.zeros(sshape, jnp.bfloat16),
            }
            for _ in range(n_layer)
        )
    if kv_cache_dtype != "bfloat16":
        raise ValueError(
            f"kv_cache_dtype={kv_cache_dtype!r} is not supported (choose "
            "'bfloat16' or 'int8') — an unrecognized value would otherwise "
            "silently fall back to bf16 buffers"
        )
    return tuple(
        {"k": jnp.zeros(shape, jnp.dtype(dtype)),
         "v": jnp.zeros(shape, jnp.dtype(dtype))}
        for _ in range(n_layer)
    )


def latent_buffers(
    n_layer: int,
    batch_size: int,
    capacity: int,
    width: int,
    dtype,
    kv_cache_dtype: str = "bfloat16",
) -> Cache:
    """Per-layer latent buffers: one row of ``width`` values a position
    under ``"k"`` (``[B, C, 1, width]``: one head, so every pool operation
    takes it as it takes keys) and no ``"v"``. A latent row is what a
    family's attention compressed keys *and* values into; quantising it
    per row would share one scale between a normed latent and a rotated
    key part, which nothing here has measured: int8 is refused by name."""
    if kv_cache_dtype != "bfloat16":
        raise ValueError(
            f"kv_cache_dtype={kv_cache_dtype!r} is not built for a latent "
            "cache (one row a position, no values): choose 'bfloat16'"
        )
    shape = (batch_size, capacity, 1, width)
    return tuple({"k": jnp.zeros(shape, jnp.dtype(dtype))} for _ in range(n_layer))


# A lane row of the TPU's vector memory: the minor axis of a device buffer
# is tiled by it.
LANES = 128


def held_row_width(layer_kv) -> int:
    """The width at which one layer's pool of rows is held by whoever keeps
    it across programs (the engine's state): a **latent** pool whose row is
    no whole number of lanes, the next whole number of them (576 -> 640);
    every other layer, and a latent row of whole lanes, the width it has.
    Decided on what the layer is, nothing else. What was compiled and seen,
    one ``DeepseekV3Attention`` sublayer's decode step and a chunk of an
    admission for a described v5e:2x2 at the seventh cell's widths (bf16,
    64 slots x 1536 positions, the pool donated; jax 0.9.0, libtpu 0.0.34;
    ``tests/test_tpu_compile.py``; PERF.md section 6, PR 57):

    - ``[64, 1536, 1, 576]``: the runtime lays it **position-minor**
      (``{1,3,2,0:T(8,128)(2,1)}``: 576 is no multiple of 128 lanes and 1536
      is; ``[64, 1536, 576]`` and ``[1, 64, 1536, 576]`` alike), the in-place
      write and both absorbed products compute on it row-minor
      (``{3,1,0,2}``), so the program copies the pool on entry and again
      before its result: two copies of 113 MB a layer in every program that
      runs the model, ten a decode step at five layers (0.97 of a 7.93 s
      slice, ledger PR 56);
    - ``[64, 1536, 1, 640]``: the runtime lays it row-minor itself
      (``{3,1,2,0:T(8,128)(2,1)}``, positions second-minor), the write is in
      place on the parameter and no pool-shaped ``copy`` or ``transpose`` is
      left in ``ENTRY``, with the reads taking ``[..., :576]`` of the rows
      viewed ``[B, K, W]`` (taken of the four-axis pool, before the head
      axis is dropped, the slice brought one position-minor copy back);
    - the layout pinned on the 576-wide pool instead
      (``jax.experimental.layout.Format(Layout(major_to_minor=(2, 0, 1, 3)),
      sharding)`` on the argument and the result of every program, what
      ISSUE 57 asked for): compiled cold it has no copy either and ran 22.4
      against 26.0 ms a step on the chip, **and it does not survive the
      persistent compile cache**: an executable that jax loads from it hands
      back arrays labelled with the runtime's default layout whatever it was
      compiled to return (the bytes are as compiled: a device-to-host read
      is right), so the next program, whose ``in_shardings`` hold the
      ``Format``, refuses its own state (``Layout passed to jit does not
      match``); every warm start of a server died there. A pool of whole
      lanes needs no layout of its own, so nothing is pinned.

    The padding is what the pinned layout pays too (a device row is whole
    lanes either way): a ninth more than the logical bytes
    ``cache/latent_gb`` counts.

    The same rule for a pool of **keys and values whose head is several
    whole lane rows** (``Dh % 128 == 0 and Dh > 128``; :func:`lane_rows`):
    it is held with each head as its ``Dh // 128`` rows of one lane row,
    ``[slots, capacity, H * Dh // 128, 128]``, the same bytes with no
    padding either way. The runtime tiles a pool's last two axes ``(H,
    Dh)``; a head of 256 takes two tiles side by side, and merging positions
    into heads for the block write (:func:`_scatter_blocks`) re-tiles the
    whole pool on entry and again before the result. One full-attention
    sublayer of qwen3-next at its cell's widths (bf16, 128 slots x 1024
    positions, 2 KV heads of 256 under 16 query heads, blocks of 16, the
    pool donated; the same compiler; PERF.md section 6, PR 63), a chunk of
    an admission (8 rows x 128 columns), a whole one (8 x 512) and the
    decode step:

    - ``[128, 1024, 2, 256]`` (``T(2,128)(2,1)``): the block view ``[128,
      64, 32, 256]`` is ``T(8,128)(2,1)``, so each admission program holds a
      ``reshape`` of the whole pool in and one out, for K and for V (134 MB
      of temporaries; 0.59 ms each on the chip, 4.7 of a 42.5 ms chunk
      forward at the cell's two such layers); the step, which writes by
      position and reads as stored, has none;
    - ``[128, 1024, 4, 128]`` (``T(4,128)(2,1)``): the block view ``[128,
      64, 64, 128]`` is a bitcast as it is at zaya's ``[.., 2, 128]`` and
      pythia's ``[.., 16, 128]``, and no program of the three returns
      anything pool-sized but the two in-place writes;
    - ``[128, 1024, 512]`` (heads folded into the row, ``T(8,128)(2,1)``):
      a bitcast too, and a second rank beside every other pool's four.

    An int8 pool keeps its heads (a scale is a head's). A head narrower than
    a lane row (gpt2's 64) is this rule's other half and is not built:
    :func:`lane_rows` is where it would go (ROADMAP.md Queue 1 item 8)."""
    if "k" not in layer_kv:
        return 0
    kind, width = cache_kind(layer_kv), layer_kv["k"].shape[-1]
    if kind.latent:
        return -(-width // LANES) * LANES
    return width // lane_rows(layer_kv)


def block_view_is_bitcast(layer_kv) -> bool:
    """Whether one layer's pool of keys, at the shape it has, is viewed by
    blocks (:func:`_scatter_blocks`: positions merged into heads) without
    moving: its row is one lane row or less, so the merge lies above whole
    tiles (:func:`held_row_width` has what was compiled). The shape alone."""
    return layer_kv["k"].shape[-1] <= LANES


def lane_rows(layer_kv) -> int:
    """The rows of one lane row each that a head of this layer's pool of
    keys and values is held as: ``Dh // 128`` for a floating pool whose
    head is more than one whole lane row, 1 for everything else (a head of
    one lane row or less, a head that fills no whole number of them, a
    latent row, an int8 pool, a state layer, the sampler's folded rows) and
    for a pool already held so. A head *narrower* than a lane row would be
    held by the same rule and is not: this is where it would go."""
    kind = cache_kind(layer_kv)
    if kind.layout not in (DENSE, PAGED) or kind.latent or kind.quantized:
        return 1
    width = layer_kv["k"].shape[-1]
    return 1 if block_view_is_bitcast(layer_kv) or width % LANES else width // LANES


def _zero_padded(rows: jax.Array, width: int) -> jax.Array:
    pad = width - rows.shape[-1]
    if pad == 0:
        return rows
    return jnp.pad(rows, ((0, 0),) * (rows.ndim - 1) + ((0, pad),))


def hold_pool(layer_kv):
    """One layer's cache dict as its holder keeps it, its pools' rows at
    :func:`held_row_width`: a latent pool's zero-padded to it (576-value
    rows, 640 wide), a pool of keys and values whose head is several lane
    rows with each head as those rows (``[S, C, 2, 256]`` held ``[S, C, 4,
    128]``: the same bytes, row ``kv * J + j`` of a position is columns
    ``[128 j, 128 (j + 1))`` of head ``kv``), every other key and every
    other kind as it is. Nothing is pinned: the held shape's default layout
    is the one the programs compute on. Zeros: a padded column is never read
    (the reads take a row's own columns), and a row is written whole, its
    padding with it. A write takes the call's rows to the pool's shape
    (:func:`_as_held`) and never the pool to the rows'; the gathered view
    comes back in the call's own heads (:func:`paged_write_read`) and the
    read of the pool as stored takes a head's lane rows where they lie
    (``ops/attention.py::decode_attention``, ``path=paged``)."""
    width = held_row_width(layer_kv)
    if "k" not in layer_kv or width == layer_kv["k"].shape[-1]:
        return layer_kv
    if cache_kind(layer_kv).latent:
        return dict(layer_kv, k=_zero_padded(layer_kv["k"], width))
    S, C, H, Dh = layer_kv["k"].shape
    held = (S, C, H * Dh // width, width)
    return dict(layer_kv, k=layer_kv["k"].reshape(held), v=layer_kv["v"].reshape(held))


def _as_held(rows: jax.Array, pool: jax.Array) -> jax.Array:
    """A call's rows ``[B, T, H, Dh]`` in the shape ``pool`` ``[.., H * J,
    Dh // J]`` holds a position in (:func:`hold_pool`: a head as its ``J``
    lane rows): a reshape of the call's rows, which where the pool holds
    whole heads is no operation at all."""
    if rows.shape[2] * rows.shape[3] != pool.shape[-2] * pool.shape[-1] or pool.shape[-2] % rows.shape[2]:
        raise ValueError(
            f"rows of {rows.shape[2]} heads of {rows.shape[3]} do not fill a pool "
            f"that holds {pool.shape[-2]} rows of {pool.shape[-1]} a position"
        )
    return rows.reshape(rows.shape[:2] + pool.shape[-2:])


# The largest folded buffer of one layer that a loop carrying it on its own
# would have staged: compiled for a described v5e (jax 0.9.0, libtpu 0.0.34;
# gpt2-medium's sampler, 24 layers) the memory-space assignment puts buffers
# of 32.5 and 33.5 MB (int8) and of 36.7 and 55.1 MB (bf16) into ``S(1)``,
# runs the step's one-row write there and copies the whole buffer back to
# the loop's carry every step; buffers of 64.2 and 73.4 MB are written in
# HBM and only prefetched for their read, and one of 134 MB is left alone
# (PERF.md section 6, PR 50)
STAGED_LAYER_BYTES = 60 * 10**6


def staged_by_the_compiler(layer_kv) -> bool:
    """Whether a decode loop that carried this layer's ``k`` buffer on its
    own would see it staged through ``S(1)`` and written back whole: its
    bytes, whatever its dtype or layout (:data:`STAGED_LAYER_BYTES`)."""
    k = layer_kv["k"]
    return k.size * k.dtype.itemsize <= STAGED_LAYER_BYTES


def decode_kv_layout(cache):
    """The dense cache in the layout the decode loop carries: heads folded
    into the minor axis — ``k``/``v`` ``[..., C, H, Dh] -> [..., C, H*Dh]``,
    int8 scales ``[..., C, H, 1] -> [..., H, C]`` — and a tuple of layers
    whose buffers the compiler would stage (:func:`staged_by_the_compiler`)
    folded into **one dict, layer-major** (``[L, B, C, H*Dh]``, scales
    ``[L, B, H, C]``): the carry. A tuple of larger layers stays a tuple of
    folded dicts.

    Why another layout: on the TPU a ``[B, C, H, Dh]`` buffer is tiled over
    its two minor axes, and ``Dh = 64`` fills half of a 128-lane row — the
    compiler pads it, so a gpt2-sized buffer takes (and every decode step
    reads) twice its bytes; the ``[B, C, H, 1]`` scales take 128x theirs.
    Folded, both are lane-dense. Why one array for all layers: a loop that
    carries a layer's 33.5 MB int8 buffer on its own invites the compiler's
    memory-space assignment to stage it through ``S(1)``, write the step's
    one row there and write the whole buffer back to the carry, 32 buffers
    a step of gpt2-medium's 48 (1.58 of a 6.15 ms step on the v5e); a
    0.8 GB array cannot be staged, so a row written at ``(l, 0, index, 0)``
    is an in-place write by construction. A 73 MB layer is written in HBM
    as it is and prefetched for its read, which the carry would cost it
    (4% of such a step), so it stays on its own (PERF.md §6, PR 50). The
    prefill writes the ``kv_buffers`` layout and the sampler converts once,
    before its loop (``ops/sampling.py::make_sampler``); each layer is
    written to its place in the preallocated carry in turn.
    ``cache`` is one layer's dict, a tuple of them, or the pp sampler's
    layer-major dict (leading ``L`` axis), which folds as a dict.
    """
    if not isinstance(cache, dict):
        if not staged_by_the_compiler(cache[0]):
            return tuple(decode_kv_layout(layer) for layer in cache)
        carry = {}
        for l, layer in enumerate(cache):
            for name, a in decode_kv_layout(layer).items():
                if l == 0:
                    # uninitialised: every layer is written below, and
                    # zeros cost a pass over the carry
                    carry[name] = jax.lax.empty((len(cache),) + a.shape, a.dtype)
                carry[name] = jax.lax.dynamic_update_slice(
                    carry[name], a[None], (l,) + (0,) * a.ndim
                )
        return carry
    out = {}
    for name, a in cache.items():
        if name.endswith("_scale"):
            out[name] = jnp.swapaxes(a[..., 0], -1, -2)
        else:
            out[name] = a.reshape(*a.shape[:-2], a.shape[-2] * a.shape[-1])
    return out


# the key under which :func:`layer_cache` names the layer a call is for
LAYER = "layer"


def layer_cache(cache, i: int):
    """Layer ``i``'s cache dict from what a model was handed: ``None``, a
    tuple over layers (prefill, the engine, the fixed sampler's larger
    layers, every path but one), or the fixed sampler's layer-major carry (:func:`decode_kv_layout` of a tuple:
    one dict for all layers), which goes to the layer whole with ``i``
    under ``"layer"`` — what :func:`cache_kind` reads as ``.layer``."""
    if cache is None:
        return None
    if isinstance(cache, dict):
        return {**cache, LAYER: i}
    return cache[i]


# the key under which :func:`written_to_index` carries the sampler's promise
WRITTEN_TO_INDEX = "written_to_index"


def written_to_index(cache, first_index: int):
    """A cache in :func:`decode_kv_layout` (the carry, or a tuple of folded
    layers) with its caller's promise, under ``"written_to_index"`` in every
    dict a layer is handed: positions past a call's ``cache_index`` hold
    nothing this call may read, and the first call comes at ``first_index``.
    What a traced ``cache_index`` cannot show a layer and the fixed
    sampler's loop knows of itself (its prefill fills ``[0, Q)``, step ``t``
    writes ``Q + t``): ``ops/attention.py::_decode_read`` then reads the
    leading :func:`decode_read_widths` positions and no more. A static key
    for one call, like ``"layer"`` and ``"first_block"``: the read returns
    arrays alone, and the caller takes the key off the carry its model hands
    back before its loop carries it. A caller that makes no promise (the pp
    stage scan, anything that is not that loop) has the whole capacity
    read."""
    if isinstance(cache, dict):
        return {**cache, WRITTEN_TO_INDEX: first_index}
    return tuple({**layer, WRITTEN_TO_INDEX: first_index} for layer in cache)


# the lane tile of the decode read's scores ([B, H, C], C minor)
READ_WIDTH_TILE = 128


def decode_read_widths(capacity: int, first_index: Optional[int]) -> Tuple[int, ...]:
    """The widths the decode read of a ``capacity``-position buffer takes
    under a promise that its first call comes at ``first_index``
    (:func:`written_to_index`; ``None``: no promise, the capacity alone),
    ascending and ending at ``capacity``; a call at ``cache_index`` ``p``
    reads the smallest that is ``>= p + 1``. Derived: the multiples of the
    scores' lane tile inside ``(first_index, capacity)``, then the capacity;
    where that is more than four, every ``ceil(n / 4)``-th counted back from
    the capacity. (64, 512): 128, 256, 384, 512; (512, 560): 560 alone;
    (512, 2560): 1024, 1536, 2048, 2560."""
    if first_index is None:
        return (capacity,)
    tile = READ_WIDTH_TILE
    widths = [*range((first_index // tile + 1) * tile, capacity, tile), capacity]
    stride = -(-len(widths) // 4)
    return tuple(widths[(len(widths) - 1) % stride :: stride])


def with_layer_cache(cache, i: int, new_kv):
    """What the model was handed with layer ``i``'s result put back: the
    tuple with entry ``i`` replaced, or the carry as the layer's read
    returned it (written in place at ``i``, every other layer untouched),
    under what else the model was handed with it (the sampler's promise,
    :func:`written_to_index`, is for every layer of the call)."""
    if cache is None:
        return None
    if isinstance(cache, dict):
        return {**cache, **new_kv}
    return (*cache[:i], new_kv, *cache[i + 1:])


# one value until a check can tell another from it: the benchmark's
# comparison reads a bfloat16 state as it reads float32 (PERF.md section 7 (20))
VALID_STATE_DTYPES = ("float32",)


def _state_dtype(state_dtype: str):
    if state_dtype not in VALID_STATE_DTYPES:
        raise ValueError(
            f"state_dtype={state_dtype!r} is not supported (choose one of "
            f"{VALID_STATE_DTYPES})"
        )
    return jnp.dtype(state_dtype)


def state_buffers(
    batch_size: int,
    n_head: int,
    head_dim: int,
    d_state: int,
    conv_width: int,
    conv_channels: int,
    state_dtype: str = "float32",
) -> Dict[str, jax.Array]:
    """One state-space layer's memory of ``batch_size`` sequences
    (``ops/ssm.py``): the state ``[B, H, P, N]`` and the convolution's last
    ``conv_width - 1`` inputs ``[B, K - 1, C]``, both in ``state_dtype``,
    zeros (what a sequence starts from)."""
    dt = _state_dtype(state_dtype)
    return {
        "ssm_state": jnp.zeros((batch_size, n_head, head_dim, d_state), dt),
        "conv_tail": jnp.zeros((batch_size, conv_width - 1, conv_channels), dt),
    }


def hybrid_cache(
    layer_types,
    batch_size: int,
    capacity: int,
    *,
    n_kv_head: Optional[int] = None,
    head_dim: Optional[int] = None,
    dtype,
    kv_cache_dtype: str,
    state: Dict[str, int],
    state_dtype: str = "float32",
    keys: Tuple[str, ...] = ("attention",),
    latent_width: Optional[int] = None,
) -> Cache:
    """The cache of a model whose layers differ: :func:`kv_buffers` for an
    entry of ``layer_types`` that ``keys`` names (the caller says which of
    its family's layer kinds hold keys), :func:`state_buffers` (with the
    sizes in ``state``) for any other. With ``latent_width`` the layers
    ``keys`` names keep one latent row of that width a position and no
    values (:func:`latent_buffers`), so one sequence holds a matrix state
    and a latent row in the same cache and :func:`cache_kind` answers for
    each layer by itself; a caller gives either that width or ``n_kv_head``
    and ``head_dim``, and one that gives both or neither is refused (a
    forgotten size would be a buffer of no width). ``int8`` has no state
    form and a step that reads one layer in ten through it gains nothing:
    with a state layer it is refused by name (``auto`` could resolve to
    it)."""
    stateful = sorted(set(layer_types) - set(keys))
    if stateful and kv_cache_dtype != "bfloat16":
        raise ValueError(
            f"kv_cache_dtype={kv_cache_dtype!r} with a state layer "
            f"({stateful}) is not built: a "
            "state has no int8 form; choose 'bfloat16'"
        )
    sized = (n_kv_head is not None, head_dim is not None)
    if any(sized) != all(sized) or (latent_width is not None) == all(sized):
        raise ValueError(
            "hybrid_cache takes either latent_width (a latent row a position) or "
            f"n_kv_head and head_dim (keys and values), got latent_width={latent_width!r}, "
            f"n_kv_head={n_kv_head!r}, head_dim={head_dim!r}"
        )

    def holds_keys():
        if latent_width is not None:
            return latent_buffers(1, batch_size, capacity, latent_width, dtype, kv_cache_dtype)[0]
        return kv_buffers(1, batch_size, capacity, n_kv_head, head_dim, dtype, kv_cache_dtype)[0]

    return tuple(
        holds_keys() if kind in keys else state_buffers(batch_size, state_dtype=state_dtype, **state)
        for kind in layer_types
    )


TAIL_PREFIX = "tail_"


def tail_buffers(batch_size: int, rows: Dict[str, Tuple[int, ...]],
                 state_dtype: str = "float32") -> Dict[str, jax.Array]:
    """The by-slot rows a layer of keys keeps beside them: ``rows`` maps a
    name to the shape one sequence holds (``{"z": (K - 1, C)}``); each
    comes back under ``tail_<name>`` as ``[B, *shape]`` zeros in
    ``state_dtype`` (what a sequence starts from)."""
    dt = _state_dtype(state_dtype)
    return {
        TAIL_PREFIX + name: jnp.zeros((batch_size,) + tuple(shape), dt)
        for name, shape in rows.items()
    }


def split_tail(cache_kv: Dict[str, jax.Array]):
    """``(the layer without its tail, the tail)``: what ``decode_attention``
    is handed, and what the model's own step reads and writes."""
    tail = cache_kind(cache_kv).tail
    return (
        {k: v for k, v in cache_kv.items() if k not in tail},
        {k: cache_kv[k] for k in tail},
    )


DENSE, FOLDED, PAGED, STATE = "dense", "folded", "paged", "state"


class CacheKind(NamedTuple):
    """What storage one layer's cache dict is (:func:`cache_kind`)."""

    layout: str  # DENSE | FOLDED | PAGED | STATE
    quantized: bool  # int8 values + bf16 scales
    shared: bool  # a paged cache with a shared-prefix overlay
    rows: bool = False  # a paged call over a group's rows of the whole pool
    # the keys kept a slot, not a position: every key of a state layer, the
    # ``tail_*`` keys of a layer of keys that keeps a tail, else none
    tail: Tuple[str, ...] = ()
    # a folded cache that is the layer-major carry of all layers: the layer
    # this call writes and reads (``None``: the dict is one layer's own)
    layer: Optional[int] = None
    # one row a position under ``"k"`` and no ``"v"`` (:func:`latent_buffers`)
    latent: bool = False
    # the first index of the caller's promise that nothing past a call's
    # ``cache_index`` is read (:func:`written_to_index`; ``None``: no promise)
    written_to_index: Optional[int] = None


def cache_kind(cache_kv: Dict[str, jax.Array]) -> CacheKind:
    """Classify one layer's cache dict, at trace time, from the keys it
    carries and the rank of ``k`` — every reader of "which storage is
    this" asks here. ``"layer"`` marks the fixed sampler's carry
    (:func:`layer_cache`): folded, all layers in one array a kind, and
    rank 4 like a dense buffer, which is why a key says it and no rank.
    ``"slot_ids"`` beside ``"block_tables"`` marks an admission call: the
    pools are whole (``num_slots`` rows), the tables and the call's K/V are
    the group's (``A`` rows), and row ``i`` of the call lives in pool row
    ``slot_ids[i]`` (:func:`paged_write_read`).
    ``tail`` names the keys that live by slot; a layer of ``"k"`` without
    ``"v"`` is ``latent``. (``"first_block"`` beside
    ``"slot_ids"`` is a caller's promise about one call,
    :func:`starting_at_block`; it changes no layer's kind.
    ``"written_to_index"`` is the fixed sampler's about one call of its
    loop, :func:`written_to_index`, reported as it was given.)"""
    if "ssm_state" in cache_kv:
        return CacheKind(STATE, False, False, tail=tuple(sorted(cache_kv)))
    layer = cache_kv.get(LAYER)
    if "block_tables" in cache_kv:
        layout = PAGED
    elif layer is not None or cache_kv["k"].ndim == 3:
        layout = FOLDED
    else:
        layout = DENSE
    return CacheKind(
        layout,
        "k_scale" in cache_kv,
        "shared_tables" in cache_kv,
        layout == PAGED and "slot_ids" in cache_kv,
        tuple(sorted(k for k in cache_kv if k.startswith(TAIL_PREFIX))),
        layer,
        "v" not in cache_kv,
        cache_kv.get(WRITTEN_TO_INDEX),
    )


def dense_write_read(cache_kv, k, v, cache_index, dtype, view_len: int = 0):
    """Write this call's K/V into the dense capacity buffers at
    ``cache_index``; returns ``(k, v, new_kv)`` — the full buffers to attend
    over and the updated cache dict. The dense half of the generic arm of
    ``ops/attention.py::decode_attention`` (the fixed sampler's prefill,
    T5, an sp-sharded cache; :func:`paged_write_read` is the paged half).
    The one-token steps do not come here — the fixed sampler's write in
    place and read the stored buffers once, in :func:`decode_kv_layout`.

    - floating: ``{"k", "v"}`` in the compute dtype;
    - int8: quantize the new slice, store value+scale, dequantize the
      whole buffer for attention. On the chip the convert+mul does NOT
      fold into the attention matmuls' operand read for a one-token
      query: v5e traces showed each read of a ``[64, 512, 16, 64]`` int8
      buffer at 252 us where its bytes take 41 (PERF.md §5-§6, PR 23-25)
      — the reason the decode loop has its own read.

    ``view_len`` (static, ``0`` = all) narrows the RETURNED attention view
    to the leading ``view_len`` positions — ``decode_attention`` derives it
    from the attention bias width (``ops/attention.py::causal_dispatch``:
    mask width == view width). Full capacity is byte-identical to the
    unnarrowed program; writes always resolve at full capacity.
    """
    at = (0, cache_index, 0, 0)
    capacity = cache_kv["k"].shape[1]
    narrow = 0 < view_len < capacity
    if cache_kind(cache_kv).quantized:
        k_q, k_s = quantize_kv(k)
        v_q, v_s = quantize_kv(v)
        new_kv = {
            "k": jax.lax.dynamic_update_slice(cache_kv["k"], k_q, at),
            "v": jax.lax.dynamic_update_slice(cache_kv["v"], v_q, at),
            "k_scale": jax.lax.dynamic_update_slice(
                cache_kv["k_scale"], k_s, at
            ),
            "v_scale": jax.lax.dynamic_update_slice(
                cache_kv["v_scale"], v_s, at
            ),
        }
        k_read = new_kv["k"][:, :view_len] if narrow else new_kv["k"]
        v_read = new_kv["v"][:, :view_len] if narrow else new_kv["v"]
        k_s_read = new_kv["k_scale"][:, :view_len] if narrow else new_kv["k_scale"]
        v_s_read = new_kv["v_scale"][:, :view_len] if narrow else new_kv["v_scale"]
        k = k_read.astype(dtype) * k_s_read.astype(dtype)
        v = v_read.astype(dtype) * v_s_read.astype(dtype)
        return k, v, new_kv
    k = jax.lax.dynamic_update_slice(cache_kv["k"], k, at)
    v = jax.lax.dynamic_update_slice(cache_kv["v"], v, at)
    new_kv = {"k": k, "v": v}
    if narrow:
        return k[:, :view_len], v[:, :view_len], new_kv
    return k, v, new_kv


# --------------------------------- paged --------------------------------- #


def choose_block_size(capacity: int, requested: int) -> int:
    """Largest divisor of ``capacity`` that is <= ``requested``.

    The logical view must be exactly ``capacity`` wide: a non-dividing
    block size would pad the view with tail positions whose masked-out
    (but present) slots change the softmax reduction shape — breaking
    bitwise parity with the fixed cache.
    """
    if capacity < 1:
        raise ValueError(f"cache capacity must be >= 1, got {capacity}")
    bs = max(1, min(int(requested), capacity))
    while capacity % bs:
        bs -= 1
    return bs


def choose_prefill_chunk(
    query_length: int, requested: int, block_size: int
) -> int:
    """Effective chunked-prefill width for ``rollout.prefill_chunk``.

    The chunk must tile the prompt columns exactly (divide Q — a ragged
    tail chunk would need its own program shape) and should align to the
    paged-KV block size so a pool-covered shared block is never split
    across a run/skip boundary. Returns the largest divisor of ``Q`` that
    is ``<= requested`` and a ``block_size`` multiple; when no aligned
    divisor exists (block size does not divide Q — e.g. the block was
    auto-shrunk against a capacity Q+R that Q does not share factors
    with), falls back to the largest plain divisor — chunk-skip decisions
    are column-granular, so correctness never depends on alignment, only
    the shared-skip efficiency does. ``requested <= 0`` disables chunking
    (the monolithic prefill).
    """
    if requested <= 0:
        return 0
    hi = min(int(requested), int(query_length))
    fallback = 1
    for w in range(hi, 0, -1):
        if query_length % w:
            continue
        if w % block_size == 0:
            return w
        if fallback == 1:
            fallback = w
    return fallback


#: what a serving pump asks of ``choose_prefill_chunk`` where the user set
#: no ``rollout.prefill_chunk``: this share of the prompt columns a chunk
#: forward. Pinned from one sweep on a v5e in ``serve-pythia1b4-chat``
#: (Q 512; PERF.md section 6, PR 30: Q // 8, Q // 4, Q // 2).
SERVING_PREFILL_CHUNK_DIVISOR = 4


def serving_prefill_chunk(query_length: int) -> int:
    """The chunk width an ``InferenceServer`` requests for its engine
    when ``rollout.prefill_chunk`` is unset: a constant rule of Q (the
    engine rounds it with :func:`choose_prefill_chunk`). Under a serving
    pump every running stream waits for whatever an iteration
    dispatches, so an admission goes in chunks of this width, one
    forward an iteration, and all-pad leading chunks are never computed;
    the trainer's collect loop has no stream to stall and keeps the
    width it is configured with."""
    return max(1, int(query_length) // SERVING_PREFILL_CHUNK_DIVISOR)


#: with the chunk a server derived itself: a group that can skip less
#: than this share of its chunks (3 or 4 forwards of 4) is forwarded
#: whole, as one monolithic ``prefill`` (``ContinuousBatchingEngine``'s
#: ``prefill_min_skip_share``). Chunking pays through the columns it
#: skips; such a group skips little, would hold the admission path for
#: 3-4 iterations with the requests behind it waiting
#: (``serve_queue_wait_p95_ms`` 29 -> 131 and ``serve_ttft_p95_ms`` up in
#: three of four runs of ``serve-olmoe1b7b-chat`` with every group in
#: chunks; PERF.md section 6, PR 30) and would pay what a forward costs
#: whatever its columns each time (the gather of the group's view; the
#: slice of the group and the merge back went with PR 42, so the share is
#: due a new sweep). About one group in five of chat traffic (a prompt over
#: Q // 2), under 2% of the gaps between tokens: the p99 gap, not the
#: p95, keeps the whole forward's length. (The benchmark's
#: ``moe_gmm_prefill_roofline`` reads that program's grouped
#: multiplication, 32768 rows: with 0 here it has nothing to read.)
SERVING_PREFILL_MIN_SKIP_SHARE = 0.5


def identity_block_tables(n_slots: int, n_blocks: int) -> jax.Array:
    """[B, n_blocks] int32 identity mapping (fresh slots)."""
    return jnp.broadcast_to(
        jnp.arange(n_blocks, dtype=jnp.int32)[None, :], (n_slots, n_blocks)
    )


def rotate_block_table(table, turns: int):
    """Rotate one slot's table by ``turns`` blocks (host or device array).

    The engine hands a recycled slot a rotated table so physical block
    reuse order differs from logical order — block-table indirection is
    exercised on every recycle, and a table-resolution bug shows up as a
    parity break instead of lying dormant behind identity tables.
    """
    n = table.shape[-1]
    k = int(turns) % n
    if k == 0:
        return table
    return jnp.concatenate([table[..., k:], table[..., :k]], axis=-1)


def init_paged_cache(
    n_layer: int,
    n_slots: int,
    capacity: int,
    n_kv_head: int,
    head_dim: int,
    dtype,
    kv_cache_dtype: str = "bfloat16",
    block_size: int = 16,
) -> Tuple[Dict[str, jax.Array], ...]:
    """Per-layer paged KV buffers + shared block tables.

    Layer dicts carry the physical pools under the dense cache's key
    names ("k"/"v" [+ scales]) plus "block_tables" — the presence of
    that key is what :func:`cache_kind` calls paged, so every causal
    family decodes through the paged cache with no model changes.
    """
    bs = choose_block_size(capacity, block_size)
    n_blocks = capacity // bs
    tables = identity_block_tables(n_slots, n_blocks)
    layers = kv_buffers(
        n_layer, n_slots, capacity, n_kv_head, head_dim, dtype, kv_cache_dtype
    )
    # per-layer table copies: donated-state programs must not see one
    # buffer behind several arguments (XLA double-donation refusal)
    return tuple(
        dict(layer, block_tables=jnp.array(tables)) for layer in layers
    )


def empty_share_tables(n_slots: int, n_blocks: int) -> jax.Array:
    """[B, n_blocks] int32 all ``-1`` — no block shared/published."""
    return jnp.full((n_slots, n_blocks), -1, jnp.int32)


def init_shared_pool(
    pool_blocks: int,
    block_size: int,
    n_head: int,
    head_dim: int,
    dtype,
    kv_cache_dtype: str = "bfloat16",
) -> Dict[str, jax.Array]:
    """Per-layer shared-prefix pool buffers: ``pool_blocks * block_size``
    flat positions in the private regions' storage layout (int8 pools
    carry scales exactly like the int8 dense cache)."""
    if pool_blocks < 1:
        raise ValueError(
            f"prefix pool needs >= 1 block, got {pool_blocks}"
        )
    n_pos = pool_blocks * block_size
    shape = (n_pos, n_head, head_dim)
    if kv_cache_dtype == "int8":
        sshape = (n_pos, n_head, 1)
        return {
            "shared_k": jnp.zeros(shape, jnp.int8),
            "shared_v": jnp.zeros(shape, jnp.int8),
            "shared_k_scale": jnp.zeros(sshape, jnp.bfloat16),
            "shared_v_scale": jnp.zeros(sshape, jnp.bfloat16),
        }
    return {
        "shared_k": jnp.zeros(shape, jnp.dtype(dtype)),
        "shared_v": jnp.zeros(shape, jnp.dtype(dtype)),
    }


#: cache-dict keys that belong to the shared-prefix pool (global, never
#: sliced/merged along the slot axis) vs the per-slot share metadata
SHARED_POOL_KEYS = (
    "shared_k", "shared_v", "shared_k_scale", "shared_v_scale",
)
SHARE_TABLE_KEYS = ("shared_tables", "publish_tables")


def physical_positions(
    block_tables: jax.Array,  # [B, n_blocks] int32
    positions: jax.Array,  # [B, T] logical positions (may be >= capacity)
    capacity: int,
) -> jax.Array:
    """[B, T] physical positions; out-of-range logical positions map to
    ``capacity`` (out of bounds), which scatters DROP — the engine uses
    position >= capacity as the "discard this write" sentinel for
    finished/inactive slots."""
    n_blocks = block_tables.shape[-1]
    bs = capacity // n_blocks
    pos = jnp.asarray(positions, jnp.int32)
    blk = jnp.clip(pos // bs, 0, n_blocks - 1)
    phys_blk = jnp.take_along_axis(block_tables, blk, axis=1)
    phys = phys_blk * bs + pos % bs
    # preserve OOB-ness: the table gather above CLIPS, so a position past
    # capacity would otherwise alias the last block and corrupt it
    return jnp.where((pos >= 0) & (pos < capacity), phys, capacity)


def logical_view_index(block_tables: jax.Array, capacity: int) -> jax.Array:
    """[B, capacity] gather index: physical position of each logical
    position (the read-side permutation)."""
    n_blocks = block_tables.shape[-1]
    bs = capacity // n_blocks
    offs = jnp.arange(bs, dtype=jnp.int32)[None, None, :]
    phys = block_tables[:, :, None] * bs + offs  # [B, n_blocks, bs]
    return phys.reshape(block_tables.shape[0], capacity)


def reads_as_stored(cache_kv: Dict[str, jax.Array], k: jax.Array,
                    cache_index, view_len: int = 0) -> bool:
    """Whether this call can attend over the pools in the order they are
    stored (:func:`paged_write_read` with ``as_stored=True``): one new
    position a slot at a per-slot or scalar ``cache_index``, a floating
    pool read at full width, and no shared-prefix overlay. Decided on what
    the call shows, at trace time; everything else reads the logical
    view (a group's call does: its bias is the group's, the pool every
    slot's)."""
    kind = cache_kind(cache_kv)
    capacity = cache_kv["k"].shape[1]
    return (
        k.shape[1] == 1
        and jnp.ndim(cache_index) <= 1
        and not kind.quantized
        and not kind.shared
        and not kind.rows
        and not 0 < view_len < capacity
    )


def stored_order_bias(
    block_tables: jax.Array,  # [B, n_blocks] int32, a permutation a slot
    bias: jax.Array,  # [B or 1, heads or 1, Q, capacity] over LOGICAL positions
) -> jax.Array:
    """``bias`` re-indexed over PHYSICAL positions: column ``p`` of slot
    ``b`` takes the bias of the logical position stored at ``p``. Whole
    blocks move, so each physical block selects its logical block's
    ``block_size`` columns by comparison with the table: ``n_blocks**2 *
    block_size`` selects a slot in one fused pass, exact, and no gather (on
    the v5e a gather of these 32 x 640 floats took 148 us a layer, more
    than the read of K it served; PERF.md §6, PR 28)."""
    n_slots, n_blocks = block_tables.shape
    capacity = bias.shape[-1]
    lead = (n_slots,) + bias.shape[1:-1]
    blocks = jnp.broadcast_to(bias, lead + (capacity,)).reshape(
        lead + (1, n_blocks, capacity // n_blocks)
    )  # [B, heads, Q, 1, logical block, column]
    # holds[b, p, j]: physical block p of slot b holds logical block j
    holds = (
        block_tables[:, None, :]
        == jnp.arange(n_blocks, dtype=block_tables.dtype)[None, :, None]
    ).reshape((n_slots,) + (1,) * (len(lead) - 1) + (n_blocks, n_blocks, 1))
    stored = jnp.sum(jnp.where(holds, blocks, 0), axis=-2)
    return stored.reshape(lead + (capacity,))


# The least a position of a pool holds, K or V, for the read by live chunks
# to be taken (:func:`reads_live_chunks` has what was measured either side)
LIVE_POSITION_BYTES = 4096


def reads_live_chunks(layer_kv: Dict[str, jax.Array], head_size: int) -> bool:
    """Whether the decode step's read of this layer's pool as stored
    (:func:`reads_as_stored`) takes the chunks a live position lies in
    (``ops/paged_live_read.py``) and not the whole pool: a paged pool of
    keys **and** values (a latent pool keeps the absorbed read), floating
    bfloat16 (int8 and an overlay read the logical view anyway), whose
    stored row is the call's whole head and whole lane rows (``head_size %
    128 == 0`` and the pool's row as wide: a head narrower than a lane row
    fills half of every tile and is copied as the whole of it; a head held
    as several lane rows, :func:`hold_pool`, keeps
    ``ops/attention.py::_lane_rows_read``), and **whose position is at
    least** ``LIVE_POSITION_BYTES``. ``head_size``: the width of a
    head as the layer's model makes it, which a held pool no longer shows.
    Decided on what the layer is; read by ``decode_attention`` for its
    dispatch and by the engine, which counts the chunks read only where
    some layer takes them.

    Where the line on a position's bytes fell, and why (one TPU v5e, a
    layer's write and read in a chain of 12, microseconds a layer; the
    whole XLA read against the kernel at the share of chunks live that the
    cell's traffic gives, with every slot live, and with every chunk live;
    PERF.md section 6, PR 67): the whole read runs at its bytes whatever
    is live, the kernel pays a copy and two small products a chunk, so it
    gains what it skips and loses what a thin position makes of a chunk:

    - 4 KiB a position (pythia, OLMoE: ``[32, 640, 16, 128]``): 309
      whole; 56 at 0.12 of the chunks, 232 at 0.61, 370 at 1.0. Taken: it
      wins up to four fifths of the chunks live, and a serving pool that
      full has no slot to admit into;
    - 2 KiB (granite: ``[32, 640, 8, 128]``): 138 whole; 48 at 0.15, 162 at
      0.61, 261 at 1.0. Not taken: it loses from under half the chunks, a
      step of 22 ms holds 70% of its slots, and its one layer is 0.6% of it;
    - 512 B (zaya ``[32, 1024, 2, 128]``, nemotron ``[64, 1024, 2, 128]``):
      74 and 138 whole; 101 at 0.36 and 143 at 0.25, 253 and 516 at 1.0.
      Not taken at any share its traffic shows: a chunk of 128 positions is
      64 KB and half a microsecond of loop whatever it holds."""
    if "k" not in layer_kv:  # a state layer
        return False
    kind = cache_kind(layer_kv)
    pool = layer_kv["k"]
    return (
        kind.layout == PAGED
        and not (kind.latent or kind.quantized or kind.shared or kind.rows)
        and pool.dtype == jnp.bfloat16
        and head_size % LANES == 0
        and pool.shape[-1] == head_size
        and pool.shape[-2] * head_size * pool.dtype.itemsize >= LIVE_POSITION_BYTES
        and live_chunk_positions(layer_kv) > 0
    )


# The most a chunk of one pool holds, K or V: two such buffers and a slot's
# float32 scores are what the kernel keeps in VMEM (pythia's 128 positions
# x 16 heads x 128: 0.5 MB a buffer, 0.66 MB of scores)
LIVE_CHUNK_BYTES = 512 * 1024
# and the most positions, however thin a position is: what is skipped is
# skipped a chunk at a time, so a chunk of a whole slot skips nothing
LIVE_CHUNK_POSITIONS = 128


def live_chunk_positions(layer_kv: Dict[str, jax.Array]) -> int:
    """The positions a chunk of this layer's paged pool holds for the read
    of its live chunks: whole blocks (a table moves whole blocks, so that is
    the grain at which a slot's live positions lie together), a divisor of
    the capacity, as many as ``LIVE_CHUNK_POSITIONS`` and
    ``LIVE_CHUNK_BYTES`` of one pool allow, and a chunk's pool rows
    (positions x KV heads) whole lane rows, which is how wide the scores of
    a chunk are. Computed from the shape; 0 where no width is all of it."""
    _, capacity, heads, width = layer_kv["k"].shape
    n_blocks = layer_kv["block_tables"].shape[-1]
    block = capacity // n_blocks
    position_bytes = heads * width * layer_kv["k"].dtype.itemsize
    for blocks in range(n_blocks, 0, -1):
        chunk = blocks * block
        if (
            n_blocks % blocks == 0
            and chunk <= LIVE_CHUNK_POSITIONS
            and chunk * position_bytes <= LIVE_CHUNK_BYTES
            and (chunk * heads) % LANES == 0
        ):
            return chunk
    return 0


class LiveChunks(NamedTuple):
    """:func:`live_chunks`: which chunks of a pool a decode step reads."""

    slots: jax.Array  # [B] int32: the slots with a live chunk, in order, then zeros
    n_slots: jax.Array  # [1] int32: how many
    counts: jax.Array  # [B] int32: a slot's live chunks
    chunks: jax.Array  # [B, n_chunks] int32: which, in order, then zeros
    share: jax.Array  # float32: live chunks / all chunks


def live_chunks(bias: jax.Array, cache_index, chunk: int, floor: float) -> LiveChunks:
    """Each slot's chunks of ``chunk`` physical positions in which some
    position can carry a weight, from the two things a decode call shows:
    ``bias`` ``[B, 1, 1, C]`` in **stored** order (:func:`stored_order_bias`;
    a position at or under ``floor``, the callers' ``NEG_INF / 2``, weighs
    exactly 0 in a float32 softmax beside any live one) and ``cache_index``
    (``[B]`` or a scalar: a slot at or past the capacity is the engine's
    row that is not live, whose write is dropped and whose output nobody
    reads; it has no live chunk whatever mask its last request left).
    Lists are in stored order, compacted by a prefix sum (no sort), int32
    for a kernel's scalar prefetch. The same for every layer of a step whose
    layers share a mask and tables."""
    B, capacity = bias.shape[0], bias.shape[-1]
    n = capacity // chunk
    above = (bias[:, 0, 0] > floor).reshape(B, n, chunk)
    live = jnp.any(above, axis=-1) & (
        jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (B,)) < capacity
    )[:, None]

    def compact(flags):
        """The indices at which ``flags`` ``[..., n]`` holds, in order,
        then zeros, and how many."""
        width = flags.shape[-1]
        at = jnp.cumsum(flags, axis=-1, dtype=jnp.int32) - 1
        ids = jnp.arange(width, dtype=jnp.int32)
        lands = flags[..., :, None] & (at[..., :, None] == ids)  # [..., from, to]
        return (
            jnp.sum(jnp.where(lands, ids[:, None], 0), axis=-2, dtype=jnp.int32),
            jnp.sum(flags, axis=-1, dtype=jnp.int32),
        )

    chunks, counts = compact(live)
    slots, n_slots = compact(counts > 0)
    return LiveChunks(
        slots, n_slots[None], counts, chunks,
        jnp.sum(counts).astype(jnp.float32) / (B * n),
    )


def starting_at_block(cache: Cache, first_block) -> Cache:
    """A group's cache (:func:`cache_kind` ``.rows``) with the caller's
    promise, under ``"first_block"`` beside ``"slot_ids"``, that the call's
    first column is the first of logical block ``first_block``: what a
    traced ``cache_index`` (chunk ``c`` at ``c * W``) cannot show by itself
    and :func:`writes_whole_blocks` needs to hear from the caller that
    knows. A caller makes it only where its width is whole blocks; a
    declared call of any other width is refused at trace time."""
    first_block = jnp.asarray(first_block, jnp.int32)
    return tuple(
        dict(layer, first_block=first_block) if cache_kind(layer).rows else layer
        for layer in cache
    )


def writes_whole_blocks(cache_kv: Dict[str, jax.Array], k: jax.Array,
                        cache_index) -> bool:
    """Whether this call's columns are whole blocks of the pool, so
    :func:`paged_write_read` writes them a block a window and not a
    position a window. Decided on what the call shows, at trace time, in
    the manner of :func:`reads_as_stored`:

    - ``T`` columns from a scalar ``cache_index`` (not the verify step's
      ``[B, T]`` matrix, not the decode step's per-slot vector) with ``T``
      a multiple of the block size, **and** the first column the first of a
      block: a Python integer shows that itself (a whole forward's ``0``),
      a traced scalar cannot, and counts only where the caller declared it
      (:func:`starting_at_block`, the engine's chunk forwards);
    - a floating pool without a shared-prefix overlay: an int8 pool writes
      four buffers and a shared region drops and publishes by position.

    Everything else keeps the by-position write. A declared call whose
    width is not whole blocks is a caller's error, refused by name."""
    return _first_whole_block(cache_kv, k, cache_index) is not None


def _first_whole_block(cache_kv, k, cache_index):
    """The call's first logical block where :func:`writes_whole_blocks`
    holds (a Python ``cache_index`` on a block's boundary shows it, a
    traced scalar's is the caller's declared ``"first_block"``), else
    ``None``."""
    kind = cache_kind(cache_kv)
    if kind.layout != PAGED:
        return None
    block_size = cache_kv["k"].shape[1] // cache_kv["block_tables"].shape[-1]
    whole = k.shape[1] % block_size == 0
    if "first_block" in cache_kv and not whole:
        raise ValueError(
            f"a call declared to start at a block (starting_at_block) writes "
            f"whole blocks: {k.shape[1]} columns are no multiple of the block "
            f"size {block_size}"
        )
    if not whole or kind.quantized or kind.shared:
        return None
    if isinstance(cache_index, numbers.Integral):
        return cache_index // block_size if cache_index % block_size == 0 else None
    if cache_index.ndim == 0:
        return cache_kv.get("first_block")
    return None


def _in_heads(view: jax.Array, rows: jax.Array) -> jax.Array:
    """A gathered view ``[B, view, H * J, Dh // J]`` in the heads of the
    call's ``rows`` ``[B, T, H, Dh]``: :func:`_as_held` undone, on what was
    gathered."""
    return view.reshape(view.shape[:2] + rows.shape[2:])


def _pool_rows(pool: jax.Array, slot_ids) -> jax.Array:
    """[B, 1] the pool row each of a call's rows lives in: its own where
    the call spans every slot, ``slot_ids`` for a group's call."""
    if slot_ids is None:
        return jnp.arange(pool.shape[0], dtype=jnp.int32)[:, None]
    return jnp.asarray(slot_ids, jnp.int32)[:, None]


def _gather_logical(pool: jax.Array, view_idx: jax.Array, slot_ids=None) -> jax.Array:
    """Gather the call's rows of ``pool`` [num_slots, cap, ...] into logical
    order, [B, view, ...]: one gather of (row, physical position) pairs."""
    return pool[_pool_rows(pool, slot_ids), view_idx]


def _scatter_rows(
    pool: jax.Array, phys: jax.Array, rows: jax.Array, slot_ids=None
) -> jax.Array:
    """Scatter ``rows`` [B, T, ...] into ``pool`` [num_slots, cap, ...] at
    physical positions ``phys`` [B, T] of the call's rows; an out-of-bounds
    position or row (a group's dummy, ``slot_ids == num_slots``) drops (jax
    scatter semantics — the discard sentinel relies on this)."""
    return pool.at[_pool_rows(pool, slot_ids), phys].set(
        _as_held(rows, pool).astype(pool.dtype), mode="drop"
    )


def physical_blocks(
    block_tables: jax.Array,  # [B, n_blocks] int32
    first_block,  # scalar: the call's first logical block
    n: int,  # the blocks the call writes
) -> jax.Array:
    """[B, n] the physical block of each of the call's ``n`` logical blocks
    from ``first_block``; a logical block out of range maps to ``n_blocks``
    (out of bounds), which :func:`_scatter_blocks` drops, as
    :func:`physical_positions` does a position."""
    n_blocks = block_tables.shape[-1]
    logical = jnp.broadcast_to(
        jnp.asarray(first_block, jnp.int32) + jnp.arange(n, dtype=jnp.int32),
        (block_tables.shape[0], n),
    )
    phys = jnp.take_along_axis(
        block_tables, jnp.clip(logical, 0, n_blocks - 1), axis=1
    )
    return jnp.where((logical >= 0) & (logical < n_blocks), phys, n_blocks)


def _scatter_blocks(
    pool: jax.Array, phys_blk: jax.Array, rows: jax.Array, slot_ids=None
) -> jax.Array:
    """Scatter ``rows`` [B, T, H, Dh], ``T`` a whole number of blocks, into
    ``pool`` [num_slots, cap, H, Dh] as ``B x T // bs`` windows, one a
    block, at the physical blocks ``phys_blk`` [B, T // bs] of the call's
    rows; an out-of-bounds block or row drops as in :func:`_scatter_rows`.
    A block is contiguous in the pool, so the pool is viewed ``[num_slots,
    n_blocks, bs * H, Dh]``, positions folded into heads: a split of a
    major axis and a merge above the minor one, which moves no data
    whatever the head count (the view ``[..., bs, H, Dh]`` does for two
    heads: the compiler re-tiles the whole pool; PERF.md section 6, PR 49)
    **where a head is one lane row or less** (:func:`block_view_is_bitcast`).
    A head of 256 is two tiles side by side and the merge re-tiles the pool
    both ways, which is why its holder keeps such a pool with each head as
    its lane rows (:func:`hold_pool`; what was compiled, with jax 0.9.0 and
    libtpu 0.0.34, is in :func:`held_row_width`): the pool's own ``H`` and
    ``Dh`` are then ``H * J`` and ``128``, and the call's rows are taken to
    that shape (:func:`_as_held`), never the pool to the rows'."""
    S, cap, H = pool.shape[:3]
    B, n = phys_blk.shape
    bs = rows.shape[1] // n
    blocks = pool.reshape((S, cap // bs, bs * H) + pool.shape[3:])
    windows = _as_held(rows, pool).astype(pool.dtype).reshape((B, n, bs * H) + pool.shape[3:])
    return (
        blocks.at[_pool_rows(pool, slot_ids), phys_blk]
        .set(windows, mode="drop")
        .reshape(pool.shape)
    )


def _publish_rows(
    pool: jax.Array, pub_pos: jax.Array, rows: jax.Array
) -> jax.Array:
    """Scatter ``rows`` [B, T, ...] into the flat shared pool
    [pool_positions, ...] at ``pub_pos`` [B, T]; OOB (== pool size)
    drops — rows without a publish assignment write nowhere. The host
    pool allocator guarantees distinct rows never publish to the same
    block, so the scatter is collision-free."""
    idx = pub_pos.reshape(-1)
    rows = _as_held(rows, pool)
    flat = rows.reshape((-1,) + rows.shape[2:])
    return pool.at[idx].set(flat.astype(pool.dtype), mode="drop")


def _shared_gather(
    shared_tables: jax.Array,  # [B, n_blocks] int32, -1 = private
    pool: jax.Array,  # [pool_positions, H, ...] shared values
    capacity: int,
    view_len: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Per logical position, the shared-pool value (garbage where the
    block is private) and the [B, capacity] bool mask of shared
    positions — the read-side overlay inputs. ``view_len > 0`` narrows
    the overlay to the leading ``view_len`` logical positions (the
    chunked prefill's prompt-region view — shared prefix blocks all live
    there, so the narrowed overlay gathers strictly less)."""
    n_blocks = shared_tables.shape[-1]
    bs = capacity // n_blocks
    width = view_len if 0 < view_len < capacity else capacity
    cols = jnp.arange(width, dtype=jnp.int32)
    sh_blk = jnp.take(shared_tables, cols // bs, axis=1)  # [B, capacity]
    sh_pos = sh_blk * bs + cols[None, :] % bs
    safe = jnp.clip(sh_pos, 0, pool.shape[0] - 1)
    return pool[safe], sh_blk >= 0


def paged_write_read(
    cache_kv: Dict[str, jax.Array],
    k: jax.Array,  # [B, T, H, Dh] new keys (compute dtype)
    v: Optional[jax.Array],  # None for a latent layer, which keeps none
    cache_index,  # scalar/[B] logical base position, or [B, T] per column
    dtype,
    view_len: int = 0,
    as_stored: bool = False,
) -> Tuple[jax.Array, Optional[jax.Array], Dict[str, jax.Array]]:
    """Paged counterpart of :func:`dense_write_read`: write the new K/V
    rows through the block table, then return the buffers to attend over
    (plus the updated cache dict).

    The call's rows are every slot's (``k`` has the pool's ``num_slots``
    rows: the decode and verify steps) or a **group's** (``cache_kv``
    carries ``"slot_ids"`` ``[A]``, ``"block_tables"`` and the share maps
    are the group's ``[A, n_blocks]``, the pools are whole: the engine's
    admission programs, ``cache_kind(...).rows``). Either way a row is
    addressed inside the pool by (pool row, physical position): nothing
    is sliced out of the pool for a group and nothing merged back, and a
    group's dummy row (``slot_ids == num_slots``) is out of bounds, so its
    writes drop like a position at ``capacity``. The pools come back whole,
    the tables and ``slot_ids`` as they were handed in.

    The write is one scatter a pool, in place in the donated pool
    whatever its dtype, in one of two forms, chosen from what the call
    shows (:func:`writes_whole_blocks`; counted a traced call site in
    ``kv_cache/write_path{path=blocks|positions}``):

    - **by block**: a call whose ``T`` columns are whole blocks from a
      block's first column, into a floating pool without a shared-prefix
      overlay (the engine's admission forwards: a whole prompt from a
      Python ``0``, a chunk from a traced ``c * W`` that the engine
      declares with :func:`starting_at_block`). The ``T // bs`` logical
      blocks go through the table to physical blocks and each lands as
      one window ``[bs * H, Dh]`` of the pool viewed by blocks
      (:func:`_scatter_blocks`): ``A x T // bs`` index pairs. A block is
      contiguous in the pool, so the same bytes land in the same places;
    - **by position** (:func:`_scatter_rows`): everything else. ``T``
      columns at ``(row, phys)``, ``A x T`` index pairs. The decode step's
      one position a slot, the verify step's per-column targets, an int8
      pool (four buffers) and a shared-prefix group (whose writes drop and
      publish by position).

    The chip takes a scatter by the index, not by the byte: on the v5e
    ~75 ns a window whatever it holds, so 32 rows into an 84 MB pool take
    microseconds, and a chunk's 8 x 128 columns of 16 heads x 128 took
    77 us by position (a whole prompt's 8 x 512: 310 us) where their 4 MB
    need 5; by block a sixteenth of the indices (PERF.md §6, PR 28, PR 42
    and PR 49). What is returned to attend over comes in two forms, and
    the form is most of a decode step's cost:

    - the **logical view** (default): one gather of (row, physical
      position) pairs from the pool into logical order, ``[B, view_len,
      H, Dh]``, materialised, so the caller's bias and causal structure
      apply as they are. Prefill, chunked prefill, the verify step, int8
      pools (dequantised after the gather) and shared-prefix reads
      (overlaid on it);
    - ``as_stored=True`` (callers check :func:`reads_as_stored`): the
      updated pools themselves, in physical order, with nothing gathered
      or copied. Attention is a sum over positions, so it may run in any
      order if the bias follows: the caller re-indexes its bias with
      :func:`stored_order_bias`. The engine's one-token decode step reads
      this way (``ops/attention.py::decode_attention``, ``path=paged``).

    ``cache_index`` may be per-slot (the continuous engine's rows sit at
    different depths), scalar (broadcast), or a full [B, T] per-column
    position matrix — the speculative verify step's drafted window,
    where each row writes only its first ``draft_len + 1`` columns and
    parks the rest at ``capacity`` (the same OOB-drop sentinel idle
    slots use, applied per column instead of per row). int8 pools
    quantize on write and dequantize the gathered view — same bits as
    the dense int8 path per logical position.

    A pool may be held in another shape than the call's rows
    (:func:`hold_pool`: a latent row padded to whole lanes, a head of
    several lane rows as those rows). The writes take the rows to the
    pool's shape, the logical view comes back in the call's own heads
    (merged on what was gathered, :func:`_in_heads`), and ``as_stored``
    returns the pools as they are held: the caller reads a head's lane rows
    where they lie (``ops/attention.py::_lane_rows_read``).

    ``view_len > 0`` narrows the returned logical view (and the shared
    overlay) to the leading ``view_len`` positions — chunk-granular
    reads for the chunked prefill, whose prompt-chunk queries never
    attend the decode region. Writes are NEVER narrowed: positions
    resolve through the table at full capacity regardless.
    """
    if as_stored and not reads_as_stored(cache_kv, k, cache_index, view_len):
        raise ValueError(
            "as_stored serves one position a slot into a floating pool read "
            "at full width, without a shared-prefix overlay (reads_as_stored)"
        )
    kind = cache_kind(cache_kv)
    B, T = k.shape[0], k.shape[1]
    capacity = cache_kv["k"].shape[1]
    tables = cache_kv["block_tables"]
    slot_ids = cache_kv["slot_ids"] if kind.rows else None
    first_block = _first_whole_block(cache_kv, k, cache_index)
    by_block = first_block is not None
    get_metrics().counter(
        "kv_cache/write_path{path=%s}" % ("blocks" if by_block else "positions")
    ).inc()
    if by_block:
        phys_blk = physical_blocks(
            tables, first_block, T * tables.shape[-1] // capacity
        )
    else:
        idx = jnp.asarray(cache_index, jnp.int32)
        if idx.ndim == 2:
            # per-column targets: the caller names every column's logical
            # position directly (OOB columns drop per element)
            positions = idx
        else:
            base = jnp.broadcast_to(idx, (B,))
            positions = base[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        phys = physical_positions(tables, positions, capacity)
    view = logical_view_index(tables, capacity)
    if 0 < view_len < capacity:
        view = view[:, :view_len]

    sharing = kind.shared
    pub_pos = None
    if sharing:
        shared_tables = cache_kv["shared_tables"]
        publish_tables = cache_kv["publish_tables"]
        n_blocks = shared_tables.shape[-1]
        bs = capacity // n_blocks
        pool_size = cache_kv["shared_k"].shape[0]
        col_blk = jnp.clip(positions // bs, 0, n_blocks - 1)
        in_range = (positions >= 0) & (positions < capacity)
        # private writes to shared columns drop: the pool serves those
        # reads and the region's leading blocks stay unwritten (the
        # engine/prefix_blocks_saved accounting)
        shared_at = (
            jnp.take_along_axis(shared_tables, col_blk, axis=1) >= 0
        )
        phys = jnp.where(shared_at & in_range, capacity, phys)
        # publish: the donor's prefix columns scatter into the pool (a
        # reader mapped to the same blocks in the SAME call gathers the
        # just-written bits — identical to what it computed in-flight)
        pub_blk = jnp.take_along_axis(publish_tables, col_blk, axis=1)
        pub_pos = jnp.where(
            (pub_blk >= 0) & in_range,
            pub_blk * bs + positions % bs,
            pool_size,
        )

    def scatter(key, rows):
        if by_block:
            return _scatter_blocks(cache_kv[key], phys_blk, rows, slot_ids)
        return _scatter_rows(cache_kv[key], phys, rows, slot_ids)

    def logical(key):
        return _gather_logical(new_kv[key], view, slot_ids)

    def overlay(full, pool_key, scale_key=None):
        if not sharing:
            return full
        pool_vals, mask = _shared_gather(
            cache_kv["shared_tables"], new_kv[pool_key], capacity,
            view_len=view_len,
        )
        vals = pool_vals.astype(dtype)
        if scale_key is not None:
            scales, _ = _shared_gather(
                cache_kv["shared_tables"], new_kv[scale_key], capacity,
                view_len=view_len,
            )
            vals = vals * scales.astype(dtype)
        return jnp.where(mask[..., None, None], vals, full)

    def carry(new_kv):
        """Thread the share metadata (+ updated pools) through so the
        next step's cache dict keeps the full layout."""
        new_kv["block_tables"] = tables
        if kind.rows:
            new_kv["slot_ids"] = slot_ids
        if sharing:
            new_kv["shared_tables"] = cache_kv["shared_tables"]
            new_kv["publish_tables"] = cache_kv["publish_tables"]
        return new_kv

    if kind.latent:
        # one pool a layer and no values: the caller's ``v`` is None and so
        # is what comes back for it
        if v is not None or sharing:
            raise ValueError(
                "a latent layer (one row a position, no values) takes v=None "
                "and no shared-prefix overlay"
            )
        # a pool may be held wider than its rows (hold_pool): what comes
        # back to attend over is at the pool's width, stored or gathered
        new_kv = carry({"k": scatter("k", _zero_padded(k, cache_kv["k"].shape[-1]))})
        return (new_kv["k"] if as_stored else logical("k")), None, new_kv

    if kind.quantized:
        k_q, k_s = quantize_kv(k)
        v_q, v_s = quantize_kv(v)
        new_kv = carry({
            "k": scatter("k", k_q),
            "v": scatter("v", v_q),
            "k_scale": scatter("k_scale", k_s),
            "v_scale": scatter("v_scale", v_s),
        })
        if sharing:
            new_kv["shared_k"] = _publish_rows(
                cache_kv["shared_k"], pub_pos, k_q
            )
            new_kv["shared_v"] = _publish_rows(
                cache_kv["shared_v"], pub_pos, v_q
            )
            new_kv["shared_k_scale"] = _publish_rows(
                cache_kv["shared_k_scale"], pub_pos, k_s
            )
            new_kv["shared_v_scale"] = _publish_rows(
                cache_kv["shared_v_scale"], pub_pos, v_s
            )
        k_full = logical("k").astype(dtype) * (
            logical("k_scale").astype(dtype)
        )
        v_full = logical("v").astype(dtype) * (
            logical("v_scale").astype(dtype)
        )
        k_full = overlay(k_full, "shared_k", "shared_k_scale")
        v_full = overlay(v_full, "shared_v", "shared_v_scale")
        return k_full, v_full, new_kv

    new_kv = carry({
        "k": scatter("k", k),
        "v": scatter("v", v),
    })
    if as_stored:
        return new_kv["k"], new_kv["v"], new_kv
    if sharing:
        new_kv["shared_k"] = _publish_rows(cache_kv["shared_k"], pub_pos, k)
        new_kv["shared_v"] = _publish_rows(cache_kv["shared_v"], pub_pos, v)
    # a head held as several lane rows (hold_pool) is merged on the gathered
    # view, the group's rows and no more, never on the pool
    k_full = _in_heads(overlay(logical("k"), "shared_k"), k)
    v_full = _in_heads(overlay(logical("v"), "shared_v"), v)
    return k_full, v_full, new_kv
