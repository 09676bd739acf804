"""Compiles for a TPU that is described, not attached: what the chip's own
compiler makes of the main path's layers at the sizes the benchmark serves.
Nothing runs, so nothing here is a time or a result (PERF.md has those).

The topology is described inside a fixture and never at import: one worker
loads the TPU's library, and only when a test of this file starts. Keep
every such compile in this one file."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_paged_decode_layer_holds_no_copy_of_its_pool(one_chip, monkeypatch):
    """One layer of the serving cells' decode step (pythia-1.4b and
    OLMoE-1B-7B: 32 slots x 640 positions x 16 heads of 128, bf16, blocks of
    16): write one row a slot, attend over the pool. Read through the
    logical view this compiled to a bf16 gather and a float32 convert of the
    whole pool, for K and for V: 168 MB of temporaries a layer and 1.4 ms a
    layer on the chip (PERF.md §6, PR 28). Read as stored it needs none.
    This is the whole read, which a program on one device leaves to the
    read by live chunks since PR 67 (the next test) and a program on several
    devices keeps: taken here as such a program takes it."""
    from trlx_tpu.ops import attention

    monkeypatch.setattr(attention, "_reads_live_chunks", lambda *call: False)

    def decode_attention(*call):  # a function of this test's: jax keeps traces by function
        return attention.decode_attention(*call)

    B, C, H, Dh, n_blocks = 32, 640, 16, 128, 40

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((B, C, H, Dh), jnp.bfloat16)
    cache = {"k": pool, "v": pool, "block_tables": sds((B, n_blocks), jnp.int32)}
    row = sds((B, 1, H, Dh), jnp.bfloat16)
    compiled = (
        jax.jit(decode_attention, donate_argnums=(3,))
        .lower(row, row, row, cache, sds((B,), jnp.int32), sds((B, 1, 1, C), jnp.float32))
        .compile()
    )
    pool_bytes = B * C * H * Dh * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8
    # no operation of the entry computation returns a pool in float32
    # (fused converts live inside a fusion and return scores or outputs)
    entry = compiled.as_text().split("\nENTRY ", 1)[1]
    assert not re.search(r"= f32\[(%d,%d|%d),%d,%d\]" % (B, C, B * C, H, Dh), entry)


@pytest.mark.parametrize(
    "B,C,H,H_kv", [(32, 640, 16, 16), (32, 1024, 8, 2)], ids=["pythia-olmoe", "zaya"]
)
def test_paged_decode_layer_reads_live_chunks_in_one_kernel_over_the_pool_as_stored(
    one_chip, monkeypatch, B, C, H, H_kv
):
    """The same layer where ``decode_attention`` takes the read by live
    chunks (``ops/paged_live_read.py``; PERF.md §6, PR 67), at pythia's and
    OLMoE's pool, and the kernel at zaya's (8 query over 2 KV heads: a
    position of 512 bytes, which the dispatch leaves to the whole read,
    ``kv_cache.py::reads_live_chunks``; sent there here so that grouped
    heads stay compiled for the chip): one Mosaic call, whose K and V
    operands are the pools themselves, written in place by the scatter
    before it and bitcast to rows ``[C * H_kv, Dh]``. The entry computation
    holds no operation that returns anything pool-sized but the two fused
    scatters (no ``copy``, ``copy-start``, ``slice``, ``convert``,
    ``transpose``: XLA's whole read moved a pool a layer through ``S(1)``,
    2.4 of pythia's 12.35 ms step), and the temporaries are the chunk lists
    and a bias a pool row, not a pool."""
    from trlx_tpu.ops import attention, kv_cache

    # the kernel is interpreted off the TPU, and this process's backend is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kv_cache, "LIVE_POSITION_BYTES", 0)
    Dh, n_blocks = 128, C // 16

    def decode_attention(*call):  # a function of this test's: jax keeps traces by function
        return attention.decode_attention(*call)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((B, C, H_kv, Dh), jnp.bfloat16)
    cache = {"k": pool, "v": pool, "block_tables": sds((B, n_blocks), jnp.int32)}
    compiled = (
        jax.jit(decode_attention, donate_argnums=(3,))
        .lower(
            sds((B, 1, H, Dh), jnp.bfloat16),
            *[sds((B, 1, H_kv, Dh), jnp.bfloat16)] * 2,
            cache, sds((B,), jnp.int32), sds((B, 1, 1, C), jnp.float32),
        )
        .compile()
    )
    pool_bytes = B * C * H_kv * Dh * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8
    entry = compiled.as_text().split("\nENTRY ", 1)[1]
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", entry)) == 1
    pool_sized = re.findall(
        r"= \w+\[(?:%d,%d,%d|%d,%d|%d,%d),%d\]\S* ([\w-]+)\("
        % (B, C, H_kv, B * C, H_kv, B, C * H_kv, Dh),
        entry,
    )
    # the two pools, each written in place by one fused scatter and handed
    # to the kernel as rows: no copy, copy-start, slice, convert, transpose
    assert sorted(pool_sized) == ["bitcast", "bitcast", "fusion", "fusion", "parameter", "parameter"]


def _updates_attention_grad(one_chip, monkeypatch, B, T, H, Dh):
    """``jax.grad`` of a layer's uncached causal self-attention under a
    ``[B, 1, 1, T]`` padding bias, compiled for the described chip."""
    from trlx_tpu.ops.attention import dot_product_attention

    # the rule reads the backend at trace time and this process's is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, bias):
        out = dot_product_attention(q, k, v, bias, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    x = sds((B, T, H, Dh), jnp.bfloat16)
    return (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        .lower(x, x, x, sds((B, 1, 1, T), jnp.float32))
        .compile()
    )


@pytest.mark.parametrize(
    "B,T,H,Dh",
    [(16, 560, 16, 64), (16, 512, 16, 64), (8, 1000, 16, 128), (8, 944, 16, 128)],
    ids=["tldr-update", "longgen-update", "one-tile-limit-Dh128", "widest-chunk-Dh128"],
)
def test_the_updates_attention_compiles_to_the_kernels_and_no_scores(
    one_chip, monkeypatch, B, T, H, Dh
):
    """A layer's uncached causal self-attention at the PPO cells' update
    shapes (minibatch 16, gpt2-medium's 16 heads of 64, T 560 and 512),
    forward and backward: on the TPU's path Mosaic takes the one-tile
    kernels ``fitted_block`` chooses (the third case is the largest tile the
    rule can hand it, just under ``LONG_SEQ``, at heads of 128, and the
    fourth the most rows a loop iteration takes over the most keys, 320 of
    944 with the last chunk overlapping: both have to fit the kernels'
    VMEM), two custom calls (the forward and the one backward
    kernel of a tile that covers both axes), and no ``[B, H, T, T]`` array
    is left in the program, which on the XLA path holds the float32 scores
    (321 MB a layer at T 560) forward and backward (PERF.md §6, PR 37)."""
    text = _updates_attention_grad(one_chip, monkeypatch, B, T, H, Dh).as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text)) == 2
    assert not re.search(r"\[%d,%d,%d,%d\]" % (B, H, T, T), text)


def test_the_updates_kernels_stay_a_loop_in_the_serialized_program(
    one_chip, monkeypatch
):
    """The same program at tldr's shape, serialized as the compile cache
    holds it before compression: Mosaic unrolls a chunk's ``[rows, T]``
    passes, so the kernels' code grows with the rows a loop iteration takes,
    and 24 layers of it are most of ``jit_train_step``'s cache entry, which
    two checkouts' warm runs have to share the chip machine's 192 MiB with
    (PERF.md §7 (24)). As compiled here: five chunks of 112 rows 0.92 MB,
    two of 288 (``_row_chunk(560)``) 1.16 MB, the whole tile as straight
    code 1.40 MB; an edit that unrolls the loop, or a cap that takes the
    whole tile, fails here and not in a cell's ``setup_s``."""
    from jax.experimental.serialize_executable import serialize

    compiled = _updates_attention_grad(one_chip, monkeypatch, 16, 560, 16, 64)
    assert len(serialize(compiled)[0]) < 1.25e6


@pytest.mark.parametrize("program,calls", [("forward", 1), ("grad", 2)])
@pytest.mark.parametrize(
    "B,T,H,Dh",
    [(16, 560, 16, 64), (16, 512, 16, 64), (8, 1000, 16, 128)],
    ids=["tldr-update", "longgen-update", "one-tile-limit-Dh128"],
)
def test_a_block_as_the_model_writes_it_holds_no_copy_of_q_k_v_or_o(
    one_chip, monkeypatch, B, T, H, Dh, program, calls
):
    """An attention block as ``models/gpt2.py`` writes it (the fused
    ``[B, T, 3 * H * Dh]`` projection, ``split``, the reshape to
    ``[B, T, H, Dh]``, ``dot_product_attention(causal=True)``, the reshape
    back, the output projection), forward and ``jax.grad``: the kernels read
    and write ``[B, T, H * Dh]``, which is those reshapes' own array, so the
    compiled entry holds the custom calls (the forward's; with the one
    backward kernel under ``grad``) and no ``copy`` or ``transpose`` of a
    q-sized array between the projections and them (before PR 59: q, k, v
    and ``do`` in, ``o``, ``dq``, ``dk``, ``dv`` out, eight a layer, 11.1 of
    tldr's 173.7 ms step). The block and not the bare call: a ``[B, T, H,
    Dh]`` entry parameter is laid position-minor by the compiler and copied
    whatever the kernels read."""
    from trlx_tpu.ops.attention import dot_product_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    d = H * Dh

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def block(x, w_qkv, w_out, bias):
        q, k, v = (
            a.reshape(B, T, H, Dh) for a in jnp.split(x @ w_qkv, 3, axis=-1)
        )
        out = dot_product_attention(q, k, v, bias, causal=True)
        return out.reshape(B, T, d) @ w_out

    def loss(*args):
        return jnp.sum(block(*args).astype(jnp.float32) ** 2)

    fn = block if program == "forward" else jax.grad(loss, argnums=(0, 1, 2))
    text = (
        jax.jit(fn)
        .lower(
            sds((B, T, d), jnp.bfloat16), sds((d, 3 * d), jnp.bfloat16),
            sds((d, d), jnp.bfloat16), sds((B, 1, 1, T), jnp.float32),
        )
        .compile()
        .as_text()
    )
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text)) == calls
    moved = [
        line.strip()[:120]
        for line in text.split("\nENTRY ", 1)[1].split("\n")
        for m in [re.search(r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(", line)]
        if m and np.prod([int(n) for n in m.group(1).split(",")]) == B * T * d
    ]
    assert not moved, moved


def test_cca_decode_layer_steps_its_tail_and_holds_no_copy_of_its_pool(one_chip):
    """One CCA attention sublayer of the ``serve-zaya1-8b-reason`` decode step
    at published widths (32 slots x 1024 positions x 2 KV heads of 128, bf16
    pool, float32 tail; 8 query heads a latent of 1024): the mix from the
    tail, one row a slot written in place, the grouped read of the pool as
    stored. The chip's compiler takes it, and nothing pool-sized is copied
    or converted beside the 2 KB a row of tail."""
    from trlx_tpu.models.zaya import ZayaAttention, ZayaConfig, init_zaya_cache

    cfg = ZayaConfig(num_hidden_layers=1, dtype="bfloat16", param_dtype="bfloat16")
    B, C, n_blocks = 32, 1024, 64
    module = ZayaAttention(cfg)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    on_chip = lambda tree: jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)
    layer = jax.eval_shape(lambda: dict(init_zaya_cache(cfg, B, C)[0], block_tables=jnp.zeros((B, n_blocks), jnp.int32)))
    args = (sds((B, 1, cfg.hidden_size), jnp.bfloat16), sds((B, 1, 1, C), jnp.float32), sds((B, 1), jnp.int32),
            sds((B, 1), jnp.float32), sds((B,), jnp.bool_))
    index = sds((B,), jnp.int32)
    params = jax.eval_shape(lambda *a: module.init(jax.random.PRNGKey(0), *a, False), *args, layer, index)

    def step(params, x, bias, pos, mask, fresh, layer, index):
        return module.apply(params, x, bias, pos, mask, fresh, layer, index, False)

    compiled = jax.jit(step, donate_argnums=(6,)).lower(on_chip(params), *args, on_chip(layer), index).compile()
    pool_bytes = B * C * 2 * 128 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 4
    entry = compiled.as_text().split("\nENTRY ", 1)[1]
    assert not re.search(r"= f32\[(%d,%d|%d),2,128\]" % (B, C, B * C), entry)


def _kda_mixer(one_chip, B, T):
    """``ops/delta.py::kda_mix`` between its projections at Ling-3.0-flash's
    published widths (32 heads of 128 keys and 128 values, conv 4, the gate
    bounded at -5), on ``B`` rows of ``T`` columns with the layer's state
    donated: ``(compiled, the optimised text)``."""
    from trlx_tpu.ops import delta

    H, D = 32, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def mix(qkv, g_raw, b_raw, conv_weight, dt_bias, A_log, mask, layer):
        return delta.kda_mix(qkv, g_raw, b_raw, conv_weight=conv_weight, dt_bias=dt_bias, A_log=A_log, n_heads=H,
                             key_dim=D, value_dim=D, lower_bound=-5.0, chunk=64, mask=mask, cache_layer=layer)

    layer = {"ssm_state": sds((B, H, D, D), jnp.float32), "conv_tail": sds((B, 3, 3 * H * D), jnp.float32)}
    args = (sds((B, T, 3 * H * D), jnp.bfloat16), sds((B, T, H * D), jnp.bfloat16), sds((B, T, H), jnp.bfloat16),
            sds((4, 3 * H * D), jnp.float32), sds((H * D,), jnp.bfloat16), sds((H,), jnp.bfloat16),
            sds((B, T), jnp.float32), layer)
    compiled = jax.jit(mix, donate_argnums=(7,)).lower(*args).compile()
    return compiled, compiled.as_text()


def test_kda_decode_layer_steps_its_state_in_two_passes_and_in_place(one_chip):
    """One KDA layer of the ``serve-ling3flash-reason1k`` decode step: 256
    slots of a ``[32, 128, 128]`` float32 state, 537 MB. The chip's compiler
    takes the vector decay's step; the state is touched by two operations,
    one that reads it out under ``k`` and ``q`` (both reads in one pass) and
    one that reads and writes it, and nothing state-sized is kept beside
    the donated buffer."""
    B = 256
    compiled, text = _kda_mixer(one_chip, B, 1)
    state_bytes = B * 32 * 128 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < state_bytes // 8
    entry = text.split("\nENTRY ", 1)[1]
    readers = [line for line in entry.split("\n") if "fusion(" in line and "%layer__ssm_state__" in line]
    assert len(readers) == 2, readers
    writers = [line for line in readers if re.search(r"= f32\[%d,32,128,128\]" % B, line)]
    assert len(writers) == 1 and not re.search(r"= f32\[%d,32,128,128\]\S* copy\(" % B, entry)


def test_kda_chunk_admission_compiles_in_row_blocks_of_sixteen(one_chip):
    """The same layer on an admission chunk (8 rows x 128 columns: two
    chunks of 64 in a loop): the chip's compiler takes the chunked form,
    whose scores are formed a row block of 16 at a time (``bf16[8, 32, 4,
    16, 64]``), and the loop's temporaries stay far under one decode state."""
    compiled, text = _kda_mixer(one_chip, 8, 128)
    assert re.search(r"bf16\[8,32,4,16,64\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 10**6


def _latent_sublayer(one_chip, held, B, C, n_blocks, T, rows=None):
    """One ``DeepseekV3Attention`` sublayer at published widths over a pool
    of ``B`` slots x ``C`` positions, compiled with the layer donated:
    ``T`` columns a row, for all slots (a decode step) or for a group of
    ``rows`` slots at a traced first block (a chunk of an admission). With
    ``held`` the pool is as the engine holds it across programs, taken from
    the rule and not restated (``ops/kv_cache.py::hold_pool``: rows padded
    to whole lanes); without, as the model's ``init_cache`` makes it. Every
    layout is the runtime's own. Returns the entry computation's
    pool-shaped ``copy`` / ``transpose`` lines and the pool parameter's."""
    from trlx_tpu.models.deepseek_v3 import DeepseekV3Attention, DeepseekV3Config, init_deepseek_v3_cache
    from trlx_tpu.ops import kv_cache as kc

    cfg = DeepseekV3Config(num_hidden_layers=1, first_k_dense_replace=1, dtype="bfloat16", param_dtype="bfloat16")
    module = DeepseekV3Attention(cfg)
    A = rows or B

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    on_chip = lambda tree: jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)

    def make():
        layer = dict(init_deepseek_v3_cache(cfg, B, C)[0], block_tables=jnp.zeros((A, n_blocks), jnp.int32))
        if rows:
            layer["slot_ids"] = jnp.zeros((A,), jnp.int32)
        return kc.hold_pool(layer) if held else layer

    layer = jax.eval_shape(make)
    width = layer["k"].shape[-1]
    assert (cfg.latent_width, width) == (576, 640 if held else 576)
    if rows:
        assert kc.writes_whole_blocks(layer, jnp.zeros((A, T, 1, 1)), 0)
    view = C if not rows else 512
    args = (sds((A, T, cfg.hidden_size), jnp.bfloat16), sds((A, 1, 1, view), jnp.float32), sds((A, T), jnp.int32))
    index = sds((), jnp.int32) if rows else sds((B,), jnp.int32)
    params = jax.eval_shape(
        lambda *a: module.init(jax.random.PRNGKey(0), *a), *args, layer, jnp.zeros(index.shape, jnp.int32)
    )

    def step(params, x, bias, pos, layer, index):
        if rows:  # chunk ``index`` of an admission: whole blocks from a traced first block
            (layer,) = kc.starting_at_block((layer,), index * (T // (C // n_blocks)))
            index = index * T
        return module.apply(params, x, bias, pos, layer, index)

    compiled = jax.jit(step, donate_argnums=(4,)).lower(on_chip(params), *args, on_chip(layer), index).compile()
    entry = compiled.as_text().split("\nENTRY ", 1)[1].split("\n")
    pool = r"\[%d,%d(,1)?,%d\]" % (B, C, width)
    assert not [l for l in entry if re.search(r"= f32" + pool, l)]
    copies = [l for l in entry if re.search(r"= bf16" + pool + r"\S* (copy|transpose)\(", l)]
    (parameter,) = [l for l in entry if re.search(r"= bf16" + pool + r"\S* parameter\(", l)]
    return copies, parameter


HELD = pytest.mark.parametrize("held", [True, False], ids=["held_in_rows_of_whole_lanes", "as_the_model_allocates_it"])


@HELD
def test_latent_decode_layer_reads_its_pool_in_stored_order(one_chip, held):
    """One latent-attention sublayer of the ``serve-deepseekv3-reason1k``
    decode step at published widths (64 slots x 1536 positions of one
    576-value row, bf16; 128 heads): one row a slot written in place, the
    absorbed read of the pool as stored. The chip's compiler takes it, no
    float32 copy of the pool exists, and nothing re-lays the pool between
    the write and the two products that read it: with the ``H`` queries on
    the left of the scores' product (the grouped read at one KV head) it
    copied the written pool position-minor, 113 MB a layer and step
    (PERF.md section 6, PR 53). Held as the engine holds it, rows of whole
    lanes, the runtime lays the pool row-minor itself and **no** operation
    of the program copies or transposes a pool (PR 57). The second case
    documents why: a pool whose rows are no multiple of 128 lanes the
    runtime lays position-minor (``{1,3,2,0}``) and the program copies it at
    both edges; a libtpu that stops doing so fails that case by name, and
    the rule has lost its reason."""
    copies, parameter = _latent_sublayer(one_chip, held, B=64, C=1536, n_blocks=96, T=1)
    # none of them follows the write: the scatter's result feeds the reads as it is
    assert not [l for l in copies if "scatter" in l.split("metadata=")[-1]]
    assert len(copies) == (0 if held else 2), copies
    assert ("{3,1,2,0:" if held else "{1,3,2,0:") in parameter, parameter


@HELD
def test_a_latent_chunk_admission_holds_no_copy_of_its_pool(one_chip, held):
    """The same sublayer in a chunk of an admission (a group of 8 rows,
    ``T`` 128 columns at a traced first block, whole blocks of 16 scattered
    into the pool viewed by blocks, ``ops/kv_cache.py::_scatter_blocks``):
    held by the engine's rule no pool-shaped copy either, and the same two
    as the model's ``init_cache`` makes it, so the ten copies go from a
    chunk forward as from a decode step."""
    copies, _ = _latent_sublayer(one_chip, held, B=64, C=1536, n_blocks=96, T=128, rows=8)
    assert len(copies) == (0 if held else 2), copies


@pytest.mark.parametrize("T,first", [(128, "traced"), (512, 0)], ids=["chunk", "whole"])
@pytest.mark.parametrize(
    "C,H", [(640, 16), (1024, 2)], ids=["pythia_16_heads", "zaya_2_kv_heads"]
)
def test_an_admission_layer_writes_whole_blocks_in_place(one_chip, C, H, T, first):
    """One attention layer of an admission forward at the serving cells'
    pools (32 slots, bf16, blocks of 16; 640 x 16 x 128 and 1024 x 2 x 128):
    a group of 8 rows, one of them a dummy, writes ``T`` columns into the
    donated pool and attends over its gathered view. The chip's compiler
    makes the write one scatter a pool of ``8 x T // 16`` windows into the
    pool viewed by blocks (a bitcast), and no operation returns, copies or
    re-tiles anything pool-sized: the view ``[..., 16, H, 128]`` did that
    to the two-head pool (PERF.md §6, PR 49). Nothing runs: no time here."""
    from trlx_tpu.ops import kv_cache as kc
    from trlx_tpu.ops.attention import decode_attention

    B, A, Dh, bs, Q = 32, 8, 128, 16, 512

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((B, C, H, Dh), jnp.bfloat16)
    cache = {"k": pool, "v": pool, "block_tables": sds((A, C // bs), jnp.int32), "slot_ids": sds((A,), jnp.int32)}
    new = sds((A, T, H, Dh), jnp.bfloat16)
    view = C if first == 0 else Q
    bias = sds((A, 1, 1, view), jnp.float32)

    def layer(q, k, v, cache, c, bias):
        if first == 0:
            return decode_attention(q, k, v, cache, 0, bias, causal=True)
        (cache,) = kc.starting_at_block((cache,), c * (T // bs))
        return decode_attention(q, k, v, cache, c * T, bias, causal=True)

    assert kc.writes_whole_blocks(cache, new, 0)
    compiled = jax.jit(layer, donate_argnums=(3,)).lower(new, new, new, cache, sds((), jnp.int32), bias).compile()
    text = compiled.as_text()
    pool_elems = B * C * H * Dh
    moved = []
    for m in re.finditer(r"= \w+\[([\d,]+)\]\S* (copy|copy-start|transpose|convert)\(", text):
        dims = [int(d) for d in m.group(1).split(",")]
        # (the whole forward's scores [8, 16, 512, 640] count as many elements as pythia's pool)
        if np.prod(dims) >= pool_elems and dims[-1] == Dh:
            moved.append(m.group(0))
    assert not moved
    windows = re.findall(r"= bf16\[%d,%d,%d,%d\]\S* scatter\(" % (B, C // bs, bs * H, Dh), text)
    assert len(windows) == 2
    assert not re.search(r"= bf16\[%d,%d,%d\]\S* scatter\(" % (B * C, H, Dh), text)


@HELD
@pytest.mark.parametrize("program", ["chunk", "whole", "step"])
def test_a_pool_of_heads_of_256_is_held_in_lane_rows_and_no_program_relays_it(one_chip, held, program):
    """One full-attention sublayer's cache traffic at the
    ``serve-qwen3next-chat512`` cell's widths (bf16, 128 slots x 1024
    positions, 2 KV heads of 256 under 16 query heads, blocks of 16, the
    pool donated) in the engine's three programs: a chunk of an admission
    (a group of 8 rows x 128 columns from a traced block), a whole admission
    (8 x 512) and the decode step (one row a slot, the pool read as stored).
    Held by the rule (``ops/kv_cache.py::hold_pool``: ``[128, 1024, 4,
    128]``, a head as its two lane rows) the pool's view by blocks is a
    bitcast and no operation of ``ENTRY`` returns anything pool-sized but
    the two writes, in place; the chunk's and the step's temporaries stay
    under an eighth of a pool (the whole admission's are its float32 scores,
    ``[8, 16, 512, 1024]``, twice a pool, as they are without the rule). The
    other case documents why: as the model allocates it (``[128, 1024, 2,
    256]``, ``T(2,128)``) each admission re-tiles the whole pool into the
    block view (``[128, 64, 32, 256]``, ``T(8,128)``) and back, a ``reshape``
    each way for K and for V (0.59 ms each on the chip, PERF.md section 6,
    PR 63), and the step, which writes by position, does not; a libtpu that
    stops doing so fails that case by name, and the rule has lost its
    reason. Nothing runs: no time here."""
    from trlx_tpu.ops import kv_cache as kc
    from trlx_tpu.ops.attention import decode_attention

    S, C, H, Hq, Dh, A, bs, Q = 128, 1024, 2, 16, 256, 8, 16, 512

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layer = jax.eval_shape(lambda: kc.kv_buffers(1, S, C, H, Dh, jnp.bfloat16)[0])
    if held:
        layer = jax.eval_shape(kc.hold_pool, layer)
    shape = layer["k"].shape
    assert shape == ((S, C, 4, 128) if held else (S, C, H, Dh)) and kc.block_view_is_bitcast(layer) == held
    pool = sds(shape, jnp.bfloat16)
    if program == "step":
        cache = {"k": pool, "v": pool, "block_tables": sds((S, C // bs), jnp.int32)}
        q, new = sds((S, 1, Hq, Dh), jnp.bfloat16), sds((S, 1, H, Dh), jnp.bfloat16)
        assert kc.reads_as_stored(cache, new, sds((S,), jnp.int32))
        lowered = jax.jit(decode_attention, donate_argnums=(3,)).lower(
            q, new, new, cache, sds((S,), jnp.int32), sds((S, 1, 1, C), jnp.float32)
        )
    else:
        T = 128 if program == "chunk" else Q
        cache = {"k": pool, "v": pool, "block_tables": sds((A, C // bs), jnp.int32), "slot_ids": sds((A,), jnp.int32)}
        q, new = sds((A, T, Hq, Dh), jnp.bfloat16), sds((A, T, H, Dh), jnp.bfloat16)
        assert kc.writes_whole_blocks(cache, new, 0)

        def admit(q, k, v, cache, c, bias):
            if program == "whole":
                return decode_attention(q, k, v, cache, 0, bias, causal=True)
            (cache,) = kc.starting_at_block((cache,), c * (T // bs))
            return decode_attention(q, k, v, cache, c * T, bias, causal=True)

        bias = sds((A, 1, 1, Q if program == "chunk" else C), jnp.float32)
        lowered = jax.jit(admit, donate_argnums=(3,)).lower(q, new, new, cache, sds((), jnp.int32), bias)
    compiled = lowered.compile()
    entry = compiled.as_text().split("\nENTRY ", 1)[1]
    pool_elems = int(np.prod(shape))
    returned = {}
    for m in re.finditer(r"= bf16\[([\d,]+)\]\S* ([\w\-]+)\(", entry):
        dims = [int(d) for d in m.group(1).split(",")]
        if np.prod(dims) == pool_elems and dims[0] == S and m.group(2) not in ("parameter", "bitcast"):
            returned.setdefault(m.group(2), []).append(dims)
    relaid = 0 if held or program == "step" else 4
    assert len(returned.pop("reshape", [])) == relaid, entry
    # what is left are the two writes, in place: a fusion each, of the pool as it lies or as viewed by blocks
    assert list(returned) == ["fusion"] and len(returned["fusion"]) == 2, returned
    if not relaid and program != "whole":
        assert compiled.memory_analysis().temp_size_in_bytes < 2 * pool_elems // 8
    if program != "step":
        view = [S, C // bs, bs * shape[2], shape[3]]
        assert returned["fusion"] == [view, view]
        assert len(re.findall(r"= bf16\[%d,%d,%d,%d\]\S* bitcast\(" % tuple(view), entry)) == (2 if held else 0)


def _while_bodies(text, fused=False):
    """The text of every computation some ``while`` of the compiled module
    names as its body, and of the branches of the ``conditional``s in them;
    ``fused``: of the fusion bodies those call, and no other."""
    computations = dict(
        (m.group(1), m.group(2))
        for m in re.finditer(r"^(?:ENTRY )?%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text, re.S | re.M)
    )
    names = set(re.findall(r"body=%?([\w.\-]+)", text))
    assert names and names <= set(computations)
    for name in sorted(names):
        for branches in re.findall(r"branch_computations=\{([^}]*)\}", computations[name]):
            names |= set(re.findall(r"%?([\w.\-]+)", branches))
    if fused:
        todo, names = sorted(names), set()
        while todo:
            for callee in re.findall(r"calls=%?([\w.\-]+)", computations[todo.pop()]):
                if callee not in names:
                    names.add(callee)
                    todo.append(callee)
    return "\n".join(computations[name] for name in sorted(names))


def _two_slices(a, layer, width, name):
    """``ops/attention.py::_leading`` as it must not be written: the layer
    first, its leading positions after."""
    mine = a if layer is None else a[layer]
    return mine[..., :width] if name.endswith("_scale") else mine[:, :width]


@pytest.mark.parametrize(
    "B,Q,R,stored,carry,read",
    [(64, 64, 448, "s8", True, None), (64, 64, 448, "s8", True, _two_slices),
     (64, 512, 48, "bf16", False, None), (32, 512, 48, "bf16", True, None)],
    ids=["longgen-int8", "longgen-int8-sliced-twice", "tldr-bf16", "half-tldr-bf16"],
)
def test_the_samplers_loop_writes_its_cache_in_place_and_writes_none_of_it_back(
    one_chip, monkeypatch, B, Q, R, stored, carry, read
):
    """The fixed sampler of the PPO cells (gpt2-medium: 24 layers, 16 heads
    of 64, bf16 rollout parameters as ``compute_dtype_cast`` leaves them,
    sampling on with the end token held back, as ``benchmark/ppo_driver.py``
    sets it) at longgen's shapes (batch 64, 64 + 448, int8 by ``auto``:
    33.5 MB a layer), at tldr's (64, 512 + 48, bf16: 73.4 MB) and at half
    tldr's batch (36.7 MB). Carried a layer at a time, 32 of longgen's 48
    buffers and 12 of the half batch's were written in ``S(1)`` and copied
    back whole every step, 1.07 GB a step in longgen (PERF.md §6, PR 50).
    Where a layer is small enough for that
    (``kv_cache.py::staged_by_the_compiler``) the loop carries one array a
    kind for all layers: inside the ``while`` body each layer's write is one
    in-place ``dynamic-update-slice`` of that array in HBM and nothing
    cache-shaped is staged or moved. tldr's layers stay on their own, are
    written in HBM and prefetched for their read alone. In every case no
    write lands in ``S(1)``, nothing cache-shaped is copied out of it and
    nothing cache-shaped is copied.

    The read's width follows the written context (PR 56): longgen's steps
    read 128, 256, 384 and 512 positions, one branch of a ``conditional`` a
    layer and width, each taking its slice of the carry in ONE ``slice``
    that fuses into the two products (nested operands ``s8[64,w,1024]``):
    no branch stages a layer in ``S(1)`` or copies anything cache-shaped,
    and the 48 + 48 writes stay in place. Sliced twice (the layer, then its
    leading positions) every branch first stages the whole 33.5 MB layer
    into ``S(1)`` and narrows it after, so all the bytes are read again:
    the case kept to show it. tldr's one width lowers to the text the
    sampler had before. Nothing runs: no time here."""
    import functools
    import hashlib

    from trlx_tpu.ops import attention
    from trlx_tpu.ops.kv_cache import decode_read_widths

    from trlx_tpu.models.gpt2 import GPT2Config, init_cache
    from trlx_tpu.models.heads import CausalLMWithValueHead
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler
    from trlx_tpu.utils import compute_dtype_cast

    L, HD, C = 24, 1024, Q + R
    cfg = GPT2Config(
        vocab_size=50257, n_positions=1024, n_embd=HD, n_layer=L, n_head=16,
        dtype="bfloat16", param_dtype="float32", kv_cache_dtype="auto",
    )
    model = CausalLMWithValueHead(cfg)

    def apply_fn(params, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None, last_only=False):
        return model.apply(
            {"params": params}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache, cache_index=cache_index,
            last_only=last_only,
        )

    gen = GenerationConfig(
        max_new_tokens=R, min_new_tokens=R, top_k=0, do_sample=True,
        eos_token_id=50256, pad_token_id=50256,
    )
    sampler = make_sampler(apply_fn, functools.partial(init_cache, cfg), gen, Q)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(
        lambda: compute_dtype_cast(
            model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"],
            jnp.bfloat16,
        )
    )
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), params)
    prompts = sds((B, Q), jnp.int32)
    if read is not None:
        monkeypatch.setattr(attention, "_leading", read)
    lowered = jax.jit(sampler).lower(params, prompts, prompts, sds((2,), jnp.uint32))
    if (B, Q, R) == (64, 512, 48):
        # ppo-gpt2m-tldr: the program of the commit before the read's width
        assert hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16] == "6317887e45a55647"
    text = lowered.compile().as_text()
    body = _while_bodies(text)

    widths = decode_read_widths(C, Q)
    assert widths == ((128, 256, 384, 512) if Q == 64 else (C,))
    assert len(re.findall(r" conditional\(", body)) == (L if len(widths) > 1 else 0)
    # a layer's leading positions [B, w, HD], in fast memory: staged whole
    staged = re.findall(
        r"= %s\[%d,(?:%s),%d\]\{[^}]*S\(1\)[^}]*\} fusion\(" % (stored, B, "|".join(map(str, widths)), HD),
        body,
    )
    if read is not None:
        assert len(staged) >= 2 * L and all("[%d,%d,%d]" % (B, C, HD) in line for line in staged)
        return
    assert not staged
    products = _while_bodies(text, fused=True)
    for w in widths[:-1]:
        # the narrower widths exist only as operands fused into the products
        assert "%s[%d,%d,%d]" % (stored, B, w, HD) in products
        assert "%s[%d,%d,%d]" % (stored, B, w, HD) not in body
    if stored == "s8":
        scales = re.findall(r"= bf16\[%d,%d,16,%d\]\S* dynamic-update-slice\(" % (L, B, C), body)
        assert len(scales) == 2 * L

    # the carry [L, B, C, HD], a layer's buffer [B, C, HD] or a part of one
    cache_shaped = r"%s\[(?:\d+,){1,2}(?:%s),%d\]" % (stored, "|".join(map(str, widths)), HD)
    written = "%s[%s%d,%d,%d]" % (stored, "%d," % L if carry else "", B, C, HD)
    writes = re.findall(r"= (%s)(\{[^}]*\}) dynamic-update-slice\(" % re.escape(written), body)
    assert len(writes) == 2 * L
    assert not [layout for _, layout in writes if "S(1)" in layout]
    moves = [
        line for line in body.split("\n")
        if re.search(r" (?:copy-start|copy-done|slice-start|slice-done)\(", line)
        and re.search(cache_shaped, line)
    ]
    # a copy-start's result is (destination, source, context): out of S(1)
    # is a write-back
    assert not [
        line for line in moves
        if re.search(r"= \(%s\{(?![^}]*S\(1\))[^}]*\}, %s\{[^}]*S\(1\)" % (cache_shaped, cache_shaped), line)
    ]
    assert not (carry and moves)
    assert not re.findall(r"= %s\S* copy\(" % cache_shaped, body)
