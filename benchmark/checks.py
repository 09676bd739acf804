"""The comparisons that decide ``correct``.

Reference agreement compares logits and log-probabilities, never sampled
tokens, against the plain float32 reference of the family on the run's own
weights. Errors are reported scale-free where the scale is known: a logit
error is divided by the standard deviation of the reference's logits (1.0
would be "as wrong as a shuffled answer"), a log-probability error is left
in nats.

The reference is computed a row at a time and only where it is compared:
the family's trunk runs over a row's [query; response], and its head over
the hidden states of the R response-predicting positions alone, so the
result is [n, R, V] and no array of T x V elements exists at any point (at
a vocabulary of 73,448 and 16k positions that array would be 19 GB).

The tolerances are measured, and kept with the distributions they were set
from: in ``tolerances.json`` for a configuration that names no table of
its own, else in the file the configuration's ``tolerances`` key names
(:func:`tolerances_of`), which :func:`check_tolerance_file` holds to the
rule ``tolerances.json`` states of itself.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Tuple

import numpy as np

from benchmark.harness import HERE, CheckLog, load_json, memory_peak_bytes

TOLERANCE_NAMES = ("logits_rms_rel", "logits_max_rel", "logprob_rms", "logprob_max")
MIN_RUNS, MIN_SEEDS, MIN_CHEAPER_RUNS, MAX_ROOM = 8, 4, 4, 3.0


def tolerance_for(compute_dtype: str, kv_cache_dtype: str) -> Dict[str, float]:
    """The shared table's entry: what a configuration that names no
    tolerance file of its own is held to."""
    return tolerances_of({"run": {"dtype": compute_dtype}}, kv_cache_dtype)


def check_tolerance_file(table: Dict[str, Any], where: str) -> None:
    """A configuration's own tolerance file against the rule
    ``tolerances.json`` states of itself; a breach raises ``ValueError``
    naming the file, the key, the cell and the tolerance. Under each key
    ``"<dtype>/kv-<cache dtype>"``:

    - ``tolerances`` gives ``logprob_rms`` and ``logprob_max`` (and the two
      ``logits_*`` where a cell compares the update's logits);
    - ``measured`` gives, for each cell, the readings of each tolerance's
      quantity as ``{"runs", "seeds", "min", "median", "max"}``: at least
      ``MIN_RUNS`` runs over at least ``MIN_SEEDS`` seeds, every reading
      under the tolerance, and the tolerance no more than ``MAX_ROOM``
      times the largest reading of any cell beside it;
    - ``cheaper`` gives, for each cell, ``logprob_rms`` read with the cache
      or the compute one precision below what the configuration states
      (``what`` says which), at least ``MIN_CHEAPER_RUNS`` runs: the
      tolerance lies under the smallest of them, so a program that
      computes below what its configuration states fails.
    """
    def refuse(what: str):
        raise ValueError(f"tolerance file {where}: {what}")

    for group in ("how", "tolerances", "measured", "cheaper"):
        if group not in table:
            refuse(f"no {group!r} group")
    for key, tol in table["tolerances"].items():
        for name in ("logprob_rms", "logprob_max"):
            if name not in tol:
                refuse(f"tolerances[{key!r}] lacks {name!r}")
        unknown = sorted(set(tol) - set(TOLERANCE_NAMES))
        if unknown:
            refuse(f"tolerances[{key!r}] has unknown names {unknown}")
        cells = table["measured"].get(key)
        if not cells:
            refuse(f"measured lacks {key!r}")
        for name, limit in tol.items():
            readings = {c: d[name] for c, d in cells.items() if name in d}
            if not readings:
                refuse(f"tolerances[{key!r}][{name!r}] has no reading beside it in measured")
            for cell, d in readings.items():
                at = f"measured[{key!r}][{cell!r}][{name!r}]"
                missing = [k for k in ("runs", "seeds", "min", "median", "max") if k not in d]
                if missing:
                    refuse(f"{at} lacks {missing}")
                if d["runs"] < MIN_RUNS or d["seeds"] < MIN_SEEDS:
                    refuse(f"{at} has {d['runs']} runs over {d['seeds']} seeds; "
                           f"at least {MIN_RUNS} over {MIN_SEEDS} are asked for")
                if not d["min"] <= d["median"] <= d["max"] < limit:
                    refuse(f"{at}: min <= median <= max < {limit} does not hold")
            largest = max(d["max"] for d in readings.values())
            if limit > MAX_ROOM * largest:
                refuse(f"tolerances[{key!r}][{name!r}] = {limit} is over {MAX_ROOM:g} times "
                       f"the largest reading beside it ({largest})")
        if not table["cheaper"].get(key):
            refuse(f"cheaper lacks {key!r}")
        for cell, d in table["cheaper"][key].items():
            at = f"cheaper[{key!r}][{cell!r}]"
            if "what" not in d or not all(k in d.get("logprob_rms", {}) for k in ("runs", "min")):
                refuse(f"{at} lacks 'what' or logprob_rms's 'runs' and 'min'")
            if d["logprob_rms"]["runs"] < MIN_CHEAPER_RUNS:
                refuse(f"{at} has {d['logprob_rms']['runs']} runs; at least {MIN_CHEAPER_RUNS} are asked for")
            if tol["logprob_rms"] >= d["logprob_rms"]["min"]:
                refuse(f"tolerances[{key!r}]['logprob_rms'] = {tol['logprob_rms']} is not under the "
                       f"smallest reading of {at} ({d['logprob_rms']['min']}: {d['what']})")


def tolerance_table(config_file: Dict[str, Any], root: str = HERE) -> Tuple[str, Dict[str, Any]]:
    """(where, ``tolerances`` by ``"<dtype>/kv-<cache dtype>"``) for a
    configuration: of the tolerance file it names (``"tolerances":
    "benchmark/tolerances/<name>.json"``, relative to the checkout, which
    is the parent of ``root``; checked as it is read), else of the shared
    table."""
    if "tolerances" not in config_file:
        return "benchmark/tolerances.json", load_json("tolerances.json")["tolerances"]
    where = config_file["tolerances"]
    with open(os.path.realpath(os.path.join(os.path.dirname(root), where))) as f:
        table = json.load(f)
    check_tolerance_file(table, where)
    return where, table["tolerances"]


def tolerances_of(config_file: Dict[str, Any], kv_cache_dtype: str, root: str = HERE) -> Dict[str, float]:
    """What this configuration's runs are held to under the cache dtype
    the program resolved."""
    where, table = tolerance_table(config_file, root)
    key = f"{config_file['run']['dtype']}/kv-{kv_cache_dtype}"
    if key not in table:
        raise KeyError(f"no measured tolerance for {key!r} in {where}")
    return table[key]


def error_stats(got, ref, scale: float = 1.0, where=None) -> Tuple[float, float]:
    """(rms, max) of ``got - ref`` divided by ``scale``, over the rows
    (leading axes) that ``where`` marks, or over every element."""
    d = np.asarray(got, np.float64) - np.asarray(ref, np.float64)
    if where is not None:
        d = d[np.asarray(where).astype(bool)]
    return float(np.sqrt((d**2).mean()) / scale), float(np.abs(d).max() / scale)


def reference_row(family, config_file: Dict[str, Any], query_length: int):
    """The program the reference runs for one row: ``(params, ids [1, T],
    mask [1, T]) -> [1, R, V]`` float32, the family's head on its trunk's
    hidden states at the response-predicting positions Q-1 .. T-2."""
    def row(params, ids, mask):
        hidden = family.trunk(params, config_file, ids, mask)
        return family.head(params, config_file, hidden[:, query_length - 1 : -1])

    return row


def reference_logits(family, config_file: Dict[str, Any], backbone_params, ids, mask,
                     query_length: int) -> np.ndarray:
    """The float32 reference of the configuration's ``family``
    (``harness.load_family``) on ``ids``/``mask`` ([n, T] = [query;
    response]) where it is compared: [n, R, V] on the host, a row a call of
    one jitted program (:func:`reference_row`) on the device that holds the
    (gathered) parameters."""
    import jax

    t0 = time.time()
    row = jax.jit(reference_row(family, config_file, query_length))
    out = np.concatenate([
        np.asarray(row(backbone_params, ids[r : r + 1], mask[r : r + 1]), np.float32)
        for r in range(ids.shape[0])
    ])
    n, R, V = out.shape
    print(f"note reference: rows={n} T={ids.shape[1]} R={R} V={V} seconds={time.time() - t0:.2f} "
          f"peak_bytes_in_use={memory_peak_bytes()}", flush=True)
    return out


def compare_with_reference(log: CheckLog, tag: str, ref_logits, response_tokens, response_mask,
                           recorded_logprobs, update_logits, tol: Dict[str, float]) -> bool:
    """``ref_logits``: [n, R, V], the reference at the response-predicting
    positions (:func:`reference_logits`); the program's ``update_logits``
    ([n, R, V], may be None) and the log-probabilities it ``recorded`` for
    the tokens it drew ([n, R]) are held to it where ``response_mask`` is 1
    (a response that stopped on EOS has nothing after it to compare)."""
    import jax

    ref = np.asarray(ref_logits, np.float32)
    ok = True
    scale = float(ref[np.asarray(response_mask).astype(bool)].std())
    if update_logits is not None:
        rms, mx = error_stats(update_logits, ref, scale, response_mask)
        ok &= log.line(f"{tag}.update_logits_rms_rel", rms, f"<= {tol['logits_rms_rel']}",
                       np.isfinite(rms) and rms <= tol["logits_rms_rel"])
        ok &= log.line(f"{tag}.update_logits_max_rel", mx, f"<= {tol['logits_max_rel']}",
                       np.isfinite(mx) and mx <= tol["logits_max_rel"])
    ref_lp = np.asarray(jax.nn.log_softmax(ref, axis=-1))
    toks = np.asarray(response_tokens)
    ref_at = np.take_along_axis(ref_lp, toks[..., None], axis=-1)[..., 0]
    rms, mx = error_stats(recorded_logprobs, ref_at, 1.0, response_mask)
    ok &= log.line(f"{tag}.sampled_logprob_rms", rms, f"<= {tol['logprob_rms']}",
                   np.isfinite(rms) and rms <= tol["logprob_rms"])
    ok &= log.line(f"{tag}.sampled_logprob_max", mx, f"<= {tol['logprob_max']}",
                   np.isfinite(mx) and mx <= tol["logprob_max"])
    return bool(ok)
