"""Traffic from a seed: prompts, lengths and an open-loop arrival schedule.

One general generator; a traffic mix is the ``traffic`` group of a cell's
JSON file. The *set* of lengths and the arrival *schedule* are drawn once
from the mix's own ``traffic_seed`` and are the same in every run;
``--seed`` decides every token id (and, where the mix names no
``order_seed`` or ``weights_seed``, the order of the lengths and the
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

What a seed may not change, and the key of a mix that keeps it from it:

1. the arrival schedule: always the mix's (``traffic_seed``);
2. the set of lengths: always the mix's (``traffic_seed``);
3. how many tokens a request asks for: a ``serve`` mix's
   ``min_new_tokens`` (optional; 1 where absent). EOS is a token id the
   seeded head draws like any other, so at a vocabulary of 16,160 and 1024
   tokens a request 6% of the requests stopped early, 2-4 of 61 a window
   by the seed (PR 53, chip). A mix that sets the key to its
   ``max_new_tokens`` gets requests that run to their budget, as a serving
   benchmark that sends ``ignore_eos`` does;
4. the bytes a step must read: a ``serve`` mix's ``weights_seed``
   (optional; ``--seed`` makes the weights where absent). A seeded router
   has favourites, so a model a seed touched 11.5-13.2 of 16 held experts
   a step and took 25.7-27.1 ms for it (PR 53, chip). A mix that gives the
   key serves that one model in every run, as a deployment serves one
   checkpoint;
5. which prompts are admitted together: a ``serve`` mix's ``order_seed``
   (optional; ``--seed`` orders the lengths where absent). The engine
   admits what has arrived in groups padded to the group's longest prompt,
   so the order of the lengths decides how many admission forwards a window
   holds and how wide they are, and every running request waits through
   each: with 1 to 4 fixed, 26 runs on 20 seeds of requests 27.5 s long
   read 872.8-892.9 tokens/s by the seed (sd 0.54%) where two runs of one
   seed lie 0.2% apart, and a step's time explained none of it (PR 55,
   chip). A mix that gives the key offers the same lengths at the same
   instants in every run.

A new serve cell in which a request draws EOS with a probability over 1%,
or whose step's reads depend on the seed's router, sets 3 and 4 from the
start; one whose requests outlast a good part of the window sets 5 too.
``--seed`` still decides every token id (which experts a step touches),
the program's own seed (:func:`program_seed`: the sampler's draws), the
reference check's fresh prompts and, where the mix names no
``order_seed``, the order of the lengths.

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
                 seed: int, reserved: int = 1, order_seed: int = None) -> List[List[int]]:
    """``n`` prompts of token ids in ``[1, vocab_size - reserved)`` — the
    last ``reserved`` ids (EOS / pad) never appear in a prompt. The token
    ids are ``seed``'s; so is the order of the lengths, but where a mix
    gives an ``order_seed``."""
    lengths = draw_lengths(lengths_spec, n, traffic_seed, seed if order_seed is None else order_seed)
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
