"""The comparisons that decide ``correct``.

Reference agreement compares logits and log-probabilities, never sampled
tokens, against the plain float32 reference of the family on the run's own
weights. Errors are reported scale-free where the scale is known: a logit
error is divided by the standard deviation of the reference's logits (1.0
would be "as wrong as a shuffled answer"), a log-probability error is left
in nats. The tolerances and the distributions they were set from are in
``tolerances.json``.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from benchmark.harness import check_line, load_json


def tolerance_for(compute_dtype: str, kv_cache_dtype: str) -> Dict[str, float]:
    table = load_json("tolerances.json")["tolerances"]
    key = f"{compute_dtype}/kv-{kv_cache_dtype}"
    if key not in table:
        raise KeyError(f"no measured tolerance for {key!r} in benchmark/tolerances.json")
    return table[key]


def error_stats(got, ref, scale: float = 1.0, where=None) -> Tuple[float, float]:
    """(rms, max) of ``got - ref`` divided by ``scale``, over the rows
    (leading axes) that ``where`` marks, or over every element."""
    d = np.asarray(got, np.float64) - np.asarray(ref, np.float64)
    if where is not None:
        d = d[np.asarray(where).astype(bool)]
    return float(np.sqrt((d**2).mean()) / scale), float(np.abs(d).max() / scale)


def reference_logits(family, config_file: Dict[str, Any], backbone_params, ids, mask):
    """The float32 reference of the configuration's ``family``
    (``harness.load_family``) on ``ids``/``mask`` ([n, T]); one jitted
    call on the first device holding the (gathered) parameters."""
    import jax

    return jax.jit(lambda p, i, m: family.forward(p, config_file, i, m))(backbone_params, ids, mask)


def compare_with_reference(tag: str, ref_logits, query_length: int,
                           response_tokens, response_mask, recorded_logprobs,
                           update_logits, tol: Dict[str, float]) -> bool:
    """``ref_logits``: [n, T, V] over [query; response]; the program's
    ``update_logits`` ([n, R, V], may be None) and the log-probabilities it
    ``recorded`` for the tokens it drew ([n, R]) are held to it at the
    response-predicting positions Q-1 .. T-2 where ``response_mask`` is 1
    (a response that stopped on EOS has nothing after it to compare)."""
    import jax

    Q = query_length
    ref = np.asarray(ref_logits, np.float32)[:, Q - 1 : -1]
    ok = True
    scale = float(ref[np.asarray(response_mask).astype(bool)].std())
    if update_logits is not None:
        rms, mx = error_stats(update_logits, ref, scale, response_mask)
        ok &= check_line(f"{tag}.update_logits_rms_rel", rms, f"<= {tol['logits_rms_rel']}",
                         np.isfinite(rms) and rms <= tol["logits_rms_rel"])
        ok &= check_line(f"{tag}.update_logits_max_rel", mx, f"<= {tol['logits_max_rel']}",
                         np.isfinite(mx) and mx <= tol["logits_max_rel"])
    ref_lp = np.asarray(jax.nn.log_softmax(ref, axis=-1))
    toks = np.asarray(response_tokens)
    ref_at = np.take_along_axis(ref_lp, toks[..., None], axis=-1)[..., 0]
    rms, mx = error_stats(recorded_logprobs, ref_at, 1.0, response_mask)
    ok &= check_line(f"{tag}.sampled_logprob_rms", rms, f"<= {tol['logprob_rms']}",
                     np.isfinite(rms) and rms <= tol["logprob_rms"])
    ok &= check_line(f"{tag}.sampled_logprob_max", mx, f"<= {tol['logprob_max']}",
                     np.isfinite(mx) and mx <= tol["logprob_max"])
    return bool(ok)
