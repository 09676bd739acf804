#!/usr/bin/env python3
"""Where do the flash kernels overtake the XLA attention path on this chip?

    python tools/attention_crossover.py [--out chiprun_out/attention_crossover.json]

Forward, and forward + ``jax.grad`` (dq, dk, dv), of uncached causal
self-attention with a left-padding ``[B, 1, 1, T]`` bias in bfloat16, at the
shapes the cells' updates and scoring forwards have and at head size 128:
the XLA path of ``dot_product_attention`` (pinned with ``learned_bias=True``,
which computes the same thing) against ``flash_attention`` at a list of
tilings. A tiling is ``(padded T, block_q, block_k)``: the inputs are padded
to ``padded T`` here, as the kernel's own prologue would, so a tile larger
than T can be timed too. q, k and v are held ``[B, T, H * D]``, as a model's
projections leave them, and each path splits the heads off by a reshape and
folds its output back, as a model's block does: what either path then pays
to reach its own layout is in its time.

Each variant runs ``--layers`` times inside one jitted ``lax.scan`` whose
carry depends on the result, and the window ends on ``block_until_ready``;
the figure is the median of ``--reps`` such calls over the layers, in
milliseconds a layer. Beside it the largest absolute difference from the
XLA path (output, and gradients where taken). The constants this sets are
``FLASH_MIN_SEQ_CAUSAL`` (``ops/attention.py``) and ``fitted_block``
(``ops/flash_attention.py``); the table it printed is in ``PERF.md`` §6.
``--row-caps 128,256,320,1024`` times every tiling once a cap on the query
rows a loop iteration of the one-tile kernels takes (``ROW_CHUNK``, set for
the sweep; a cap of the whole length is the tile as straight code): the
table that constant comes from, in one call with ``--fitted``.
Refuses to run without a TPU: a CPU time is no crossover.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.ops.attention import NEG_INF, dot_product_attention, padding_bias
import trlx_tpu.ops.flash_attention as kernels
from trlx_tpu.ops.flash_attention import fitted_block, flash_attention

# (B, T, H, D, with the backward?)
SHAPES = [
    (16, 560, 16, 64, True),   # ppo-gpt2m-tldr: the update's minibatch
    (64, 560, 16, 64, False),  # ... collection's scoring forward
    (16, 512, 16, 64, True),   # ppo-gpt2m-longgen: the update
    (64, 512, 16, 64, False),  # ... its scoring forward
    (16, 384, 16, 64, True),   # under every cell: where the crossover lies
    (16, 256, 16, 64, True),
    (16, 512, 16, 128, True),  # Dh 128 (pythia, olmoe, granite)
    (16, 640, 16, 128, True),
    (16, 1024, 16, 64, True),  # the tiling measured before this table
    (16, 592, 16, 64, True),   # 16 x a prime: no divisor for a row chunk but 16
]


def tilings(T):
    """Candidate ``(padded T, block_q, block_k)`` for a length. A key tile
    is a multiple of 128 or the whole padded length: the ``[B, 1, 1, T]``
    bias is cut along its lanes, and Mosaic refuses any other cut (320)."""
    up = -(-T // 128) * 128
    sizes = [s for s in (128, 256, 320, 512) if up % s == 0 and s < up] + [up]
    out = [
        (up, bq, bk)
        for bq in sizes
        for bk in sizes
        if bq >= bk and (bk % 128 == 0 or bk == up)
    ]
    if up % 256:
        out.append((up + 128, 256, 256))
    if T % 16 == 0 and T != up:
        out.append((T, T, T))  # one tile of T itself, nothing padded
    if T < 1024:
        out.append((1024, 512, 512))  # what min(512, ceil8(T)) chose
    return out


def as_the_model_holds_them(fn, H):
    """``fn`` over ``[B, T, H, D]`` as a function of ``[B, T, H * D]``."""
    def folded(q, k, v, bias):
        B, T, _ = q.shape
        out = fn(*(x.reshape(B, T, H, -1) for x in (q, k, v)), bias)
        return out.reshape(B, T, -1)

    return folded


def xla_path(q, k, v, bias):
    return dot_product_attention(q, k, v, bias, causal=True, learned_bias=True)


def flash_path(pad_T, block_q, block_k):
    def fn(q, k, v, bias):
        T = q.shape[1]
        extra = pad_T - T
        if extra:
            widths = [(0, 0), (0, extra), (0, 0), (0, 0)]
            q, k, v = (jnp.pad(x, widths) for x in (q, k, v))
            bias = jnp.pad(
                bias, [(0, 0), (0, 0), (0, 0), (0, extra)],
                constant_values=NEG_INF,
            )
        out = flash_attention(
            q, k, v, bias, causal=True, block_q=block_q, block_k=block_k
        )
        return out[:, :T]

    return fn


def looped(fn, layers, backward):
    """``layers`` applications of ``fn`` in one program, each reading what
    the one before wrote."""
    if backward:
        def loss(q, k, v, bias, w):
            return jnp.sum(fn(q, k, v, bias).astype(jnp.float32) * w)

        grad = jax.grad(loss, argnums=(0, 1, 2))

        def body(carry, _):
            q, k, v, bias, w = carry
            dq, dk, dv = grad(q, k, v, bias, w)
            eps = jnp.asarray(1e-3, q.dtype)
            return (q + eps * dq, k + eps * dk, v + eps * dv, bias, w), None
    else:
        def body(carry, _):
            q, k, v, bias, w = carry
            out = fn(q, k, v, bias)
            return (q + jnp.asarray(1e-3, q.dtype) * out, k, v, bias, w), None

    @jax.jit
    def run(q, k, v, bias, w):
        carry, _ = jax.lax.scan(body, (q, k, v, bias, w), None, length=layers)
        return carry[:3]

    return run


def inputs(B, T, H, D, seed):
    rng = np.random.default_rng(seed)
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, T, H * D)), jnp.bfloat16) for _ in range(3)
    )
    # left padding as the cells have it: a row's first columns are pad,
    # anywhere from none to three quarters of the prompt. A pad position's
    # output is uniform weights over whatever keys its path visits (the
    # kernels skip future tiles) and no real position reads it, so its
    # cotangent is zero, as the masked loss makes it, and ``w`` doubles as
    # the mask of the rows the paths are compared on
    pads = rng.integers(0, (3 * T) // 4, size=B)
    mask = (np.arange(T)[None, :] >= pads[:, None]).astype(np.int32)
    w = jnp.asarray(
        rng.normal(size=(B, T, H * D)) * mask[:, :, None], jnp.float32
    )
    return q, k, v, padding_bias(jnp.asarray(mask)), w


def once(fn, args, backward):
    """One application's results, for the comparison."""
    real = args[4] != 0

    def forward(q, k, v, bias):
        return jnp.where(real, fn(q, k, v, bias), 0)

    if not backward:
        return (jax.jit(forward)(*args[:4]),)

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v, args[3]).astype(jnp.float32) * args[4])

    out = jax.jit(forward)(*args[:4])
    return (out,) + jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*args[:3])


def timed(run, args, reps):
    jax.block_until_ready(run(*args))
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        walls.append(time.perf_counter() - t0)
    return statistics.median(walls)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/attention_crossover.json")
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--only", type=lambda s: [int(i) for i in s.split(",")], default=None,
        help="indices into SHAPES, comma-separated (default: all)",
    )
    ap.add_argument(
        "--fitted", action="store_true",
        help="time only the tiling fitted_block chooses at each length",
    )
    ap.add_argument(
        "--row-caps", type=lambda s: [int(i) for i in s.split(",")], default=[None],
        help="values of flash_attention.ROW_CHUNK to time each tiling under "
        "(default: the one the tree has)",
    )
    opts = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("attention_crossover: no TPU; a CPU time is no crossover", file=sys.stderr)
        return 3
    device = jax.devices()[0].device_kind
    rows = []
    for B, T, H, D, has_bwd in (
        SHAPES if opts.only is None else [SHAPES[i] for i in opts.only]
    ):
        args = inputs(B, T, H, D, opts.seed)
        for backward in ([False, True] if has_bwd else [False]):
            xla = as_the_model_holds_them(xla_path, H)
            ref = once(xla, args, backward)
            xla_ms = 1e3 * timed(looped(xla, opts.layers, backward), args, opts.reps) / opts.layers
            rows.append(dict(shape=[B, T, H, D], backward=backward, path="xla", ms=xla_ms))
            print(f"[{B},{T},{H},{D}] {'fwd+bwd' if backward else 'fwd    '} xla {xla_ms:8.3f} ms", flush=True)
            fit = fitted_block(T)
            for (pad_T, bq, bk), cap in itertools.product(tilings(T), opts.row_caps):
                if opts.fitted and (pad_T, bq, bk) != (-(-T // fit) * fit, fit, fit):
                    continue
                if cap is not None:
                    kernels.ROW_CHUNK = cap  # read where a call is traced
                fn = as_the_model_holds_them(flash_path(pad_T, bq, bk), H)
                row = dict(shape=[B, T, H, D], backward=backward, path="flash", tiling=[pad_T, bq, bk])
                label = f"   flash pad {pad_T:4d} tiles {bq:4d} x {bk:4d}"
                if (bq, bk) == (pad_T, pad_T):  # the one-tile kernels: their row loop
                    row["rows_a_chunk"] = kernels._row_chunk(pad_T)
                    label += f" rows {row['rows_a_chunk']:4d}"
                elif cap is not opts.row_caps[0]:
                    continue  # the tiled kernels have no row loop to sweep
                try:
                    got = once(fn, args, backward)
                    row["max_abs_diff"] = [
                        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                        for a, b in zip(got, ref)
                    ]
                    row["ms"] = 1e3 * timed(looped(fn, opts.layers, backward), args, opts.reps) / opts.layers
                    row["xla_over_flash"] = xla_ms / row["ms"]
                    print(
                        f"{label} {row['ms']:8.3f} ms"
                        f"  xla/flash {row['xla_over_flash']:5.2f}  diff "
                        + " ".join(f"{d:.3g}" for d in row["max_abs_diff"]),
                        flush=True,
                    )
                except Exception as e:  # a tiling Mosaic refuses is a row of the table
                    row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                    print(f"{label} refused: {row['error'][:120]}", flush=True)
                rows.append(row)
    os.makedirs(os.path.dirname(opts.out) or ".", exist_ok=True)
    with open(opts.out, "w") as f:
        json.dump(dict(device=device, layers=opts.layers, reps=opts.reps, rows=rows), f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
