"""Invariants of the benchmark's FLOP/byte accounting
(`benchmark/arithmetic.py`, through a family's ``shape(cfg)``).

`phase_mfu` and the decode rooflines are only as honest as these counts;
pin the properties that reading the code can't guarantee — a frozen
workload must cost strictly less, sentinel values of ``unfrozen`` must not
prune anything, and the cache's bytes must be exactly the term the cache
dtype moves.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402
from benchmark.arithmetic import (  # noqa: E402
    decode_step_bytes, model_shape, ppo_phase_flops,
)

# the longgen cell's phase: the one whose update is pruned (top 2 blocks)
PHASE = dict(Q=64, R=448, rollouts=64, ppo_epochs=4)


def _shape(config="gpt2-medium"):
    cfg = harness.load_json("configs", f"{config}.json")
    return model_shape(harness.load_family(cfg), cfg)


def test_frozen_workload_costs_strictly_less():
    s = _shape()
    c_full, t_full = ppo_phase_flops(s, **PHASE, unfrozen=0)
    c_frozen, t_frozen = ppo_phase_flops(s, **PHASE, unfrozen=2)
    # the reference pass is one full-depth pass under BOTH definitions
    # (hydra ref == full-copy ref in FLOPs; DCE pinned in test_freezing),
    # so collect FLOPs match...
    assert c_full == c_frozen
    # ...and the frozen train phase prunes the backward below the branch
    assert t_frozen < t_full
    # bwd = 2x fwd at full train: the pruned saving is bounded by that
    assert t_frozen > t_full / 3


def test_unfrozen_out_of_range_counts_as_full():
    # k <= 0 and k >= L both mean "no pruning" in the models (the mask
    # semantics live in the trainers; accounting must not halve anything
    # on sentinel values)
    s = _shape()
    base = ppo_phase_flops(s, **PHASE, unfrozen=0)
    for k in (-1, len(s["layers"]), len(s["layers"]) + 3):
        assert ppo_phase_flops(s, **PHASE, unfrozen=k) == base


def test_decode_bytes_scale_with_cache_dtype():
    for config in ("gpt2-medium", "pythia-1.4b", "olmoe-1b-7b"):
        s = _shape(config)
        batch, context = 64, 511
        bf16 = decode_step_bytes(s, batch, context, kv_bytes=2)
        int8 = decode_step_bytes(s, batch, context, kv_bytes=1)
        assert int8 < bf16
        # the weights a step reads are cache-dtype-invariant; the delta is
        # exactly the cache read + the one position written, at one byte
        # less per value
        cache_values = (
            sum(l["kv_values"] for l in s["layers"]) * batch * (context + 1)
        )
        assert cache_values > 0 and bf16 - int8 == cache_values
        # halving the weights' bytes leaves the cache term where it was
        assert bf16 - decode_step_bytes(
            s, batch, context, weight_bytes=1, kv_bytes=2
        ) == (bf16 - 2 * cache_values) / 2
