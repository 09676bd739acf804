"""Traffic from a seed: prompts, lengths and an open-loop arrival schedule.

One general generator; a traffic mix is the ``traffic`` group of a cell's
JSON file. The *set* of lengths and the arrival *schedule* are drawn once
from the mix's own ``traffic_seed`` and are the same in every run;
``--seed`` decides the order of the lengths and every token id (and the
weights). So every seed offers the same work at the same instants, as the
benchmark's contract asks where a seed would otherwise change the work: a
Poisson count over a 45 s window would alone move the completed tokens per
second by 5% from seed to seed, and the order of the gaps is the bursts,
which decide the queueing tail — started at another point of their cycle
they moved the 95th percentile of time to first token by 30% between
seeds while a seed repeated within 2-6% (PR 23, chip). The price, stated
in the cell's ``why``: a cell's tails are those of one burst pattern. A
seed repeats exactly, and no draw depends on how fast the system under
test ran.

Length distributions (``{"dist": ...}``):

- ``uniform``: whole numbers ``lo``..``hi`` inclusive;
- ``lognormal``: ``median`` and ``sigma`` of the underlying normal,
  clipped to ``lo``..``hi``;
- ``fixed``: ``value``.

Arrival processes (``{"process": ...}``). The offered rate is a share of
the knee the cell's sweep measured (``knee_per_s`` x ``load``: 0.8 below
it, above 1 for a saturated cell), ``round(rate * seconds)`` arrivals whose
gaps are scaled to fill the window exactly — one fixed schedule per mix,
not a fresh Poisson sample per seed:

- ``poisson``: exponential gaps;
- ``gamma``: gamma gaps with coefficient of variation ``cv`` (cv 1 is
  Poisson, larger is burstier).
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

STREAMS = {"lengths": 1, "tokens": 2, "arrivals": 3, "check": 4, "program": 5}


def rng_for(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), STREAMS[stream]])


def program_seed(seed: int) -> int:
    """A 31-bit seed for the program's own ``train.seed`` / server seed,
    derived from ``--seed`` (which may pass 2**31)."""
    return int(rng_for(seed, "program").integers(0, 2**31 - 1))


def draw_lengths(spec: Dict[str, Any], n: int, traffic_seed: int, seed: int) -> np.ndarray:
    """``n`` lengths: the set from ``traffic_seed``, the order from ``seed``."""
    base = rng_for(traffic_seed, "lengths")
    dist = spec["dist"]
    if dist == "fixed":
        out = np.full(n, int(spec["value"]), np.int64)
    elif dist == "uniform":
        out = base.integers(int(spec["lo"]), int(spec["hi"]) + 1, size=n)
    elif dist == "lognormal":
        raw = np.exp(base.normal(np.log(spec["median"]), spec["sigma"], size=n))
        out = np.clip(np.rint(raw), spec["lo"], spec["hi"]).astype(np.int64)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return rng_for(seed, "lengths").permutation(out)


def draw_prompts(lengths_spec, n: int, vocab_size: int, traffic_seed: int,
                 seed: int, reserved: int = 1) -> List[List[int]]:
    """``n`` prompts of token ids in ``[1, vocab_size - reserved)`` — the
    last ``reserved`` ids (EOS / pad) never appear in a prompt."""
    lengths = draw_lengths(lengths_spec, n, traffic_seed, seed)
    toks = rng_for(seed, "tokens")
    hi = vocab_size - reserved
    return [[int(t) for t in toks.integers(1, hi, size=int(k))] for k in lengths]


def offered_rate(spec: Dict[str, Any]) -> float:
    """Requests a second: the measured knee times the cell's load."""
    return float(spec["knee_per_s"]) * float(spec["load"])


def arrival_times(spec: Dict[str, Any], seconds: float, traffic_seed: int) -> np.ndarray:
    """Due times in seconds from the window's start: ascending, the first
    at 0, all ``< seconds``; the same for every ``--seed``."""
    n = int(round(offered_rate(spec) * seconds))
    base = rng_for(traffic_seed, "arrivals")
    if spec["process"] == "poisson":
        gaps = base.exponential(1.0, size=n)
    elif spec["process"] == "gamma":
        shape = 1.0 / float(spec["cv"]) ** 2
        gaps = base.gamma(shape, 1.0 / shape, size=n)
    else:
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    gaps = gaps * (seconds / gaps.sum())
    return np.cumsum(gaps) - gaps


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty sequence (q in 0..100)."""
    xs = sorted(values)
    rank = max(1, int(np.ceil(q / 100.0 * len(xs))))
    return float(xs[rank - 1])
