"""What the algorithm requires, from shapes alone: parameters, FLOPs, bytes.

The yardstick's arithmetic. Nothing here reads the program: a later PR may
change how the program computes a phase, and is still divided into these
numbers. "Required" means the forward and backward passes of the model as
published; recomputation is not counted, and neither is work a clever
program may skip (the frozen trunk's backward IS skipped in the count,
because no algorithm needs it).

Shapes come from a configuration file (HF key names) through
:func:`model_shape`, so both families share every formula below:

- ``d`` hidden, ``L`` layers, ``V`` vocabulary, ``ff`` MLP width,
- ``tied``: the head reuses the token embedding (gpt2) or is a matrix of
  its own (neox ``embed_out``),
- ``learned_pos``: rows of a learned position table (gpt2) or 0 (rotary).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; an unlisted ``device_kind`` raises."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise RuntimeError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"benchmark/peaks.json (known: {sorted(table)})"
        )
    return table[device_kind]


def model_shape(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the formulas need, from a configuration file's HF keys."""
    if cfg["model_type"] == "gpt2":
        d = cfg["n_embd"]
        return {
            "d": d, "L": cfg["n_layer"], "V": cfg["vocab_size"],
            "H": cfg["n_head"], "ff": cfg.get("n_inner") or 4 * d,
            "tied": True, "learned_pos": cfg["n_positions"],
        }
    if cfg["model_type"] == "gpt_neox":
        d = cfg["hidden_size"]
        return {
            "d": d, "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"],
            "H": cfg["num_attention_heads"], "ff": cfg["intermediate_size"],
            "tied": False, "learned_pos": 0,
        }
    raise ValueError(f"no shape rule for model_type {cfg['model_type']!r}")


def block_params(s) -> int:
    """One transformer block: QKV + output projection, the MLP, two
    LayerNorms, all with biases."""
    d, ff = s["d"], s["ff"]
    attn = d * 3 * d + 3 * d + d * d + d
    mlp = d * ff + ff + ff * d + d
    return attn + mlp + 4 * d


def backbone_params(s) -> int:
    """Every parameter of the language model (no value head)."""
    head = 0 if s["tied"] else s["d"] * s["V"]
    return (
        s["V"] * s["d"] + s["learned_pos"] * s["d"]
        + s["L"] * block_params(s) + 2 * s["d"] + head
    )


def matmul_params_per_layer(s) -> int:
    return 4 * s["d"] * s["d"] + 2 * s["d"] * s["ff"]


def forward_flops(s, tokens: int, ctx_sum: int, head_tokens: int, layers=None) -> float:
    """Matmul FLOPs of a forward over ``tokens`` positions whose attention
    contexts sum to ``ctx_sum`` (a causal pass over T: T(T+1)/2), with the
    head applied at ``head_tokens`` positions. 2 FLOPs per multiply-add;
    QK^T and AV cost 4*d per (token, context position) and layer."""
    L = s["L"] if layers is None else layers
    trunk = 2 * matmul_params_per_layer(s) * L * tokens + 4 * L * s["d"] * ctx_sum
    return trunk + 2 * s["d"] * s["V"] * head_tokens


def ppo_phase_flops(s, Q: int, R: int, rollouts: int, ppo_epochs: int, unfrozen: int = 0):
    """(collect, train) required FLOPs of one PPO phase.

    Collect: a prefill over Q with the head at the last position, R decode
    steps at growing context, one frozen-reference pass over T = Q + R with
    the head at the R response positions. Train, per epoch and rollout: a
    forward over T with the head at R positions and its backward (twice the
    forward); with only the top ``unfrozen`` blocks trained the backward
    runs through those blocks and the head alone."""
    T = Q + R
    ctx_T = T * (T + 1) // 2
    prefill = forward_flops(s, Q, Q * (Q + 1) // 2, 1)
    decode = forward_flops(s, R, sum(Q + t + 1 for t in range(R)), R)
    ref = forward_flops(s, T, ctx_T, R)
    fwd = forward_flops(s, T, ctx_T, R)
    if 0 < unfrozen < s["L"]:
        bwd = 2 * forward_flops(s, T, ctx_T, R, layers=unfrozen)
    else:
        bwd = 2 * fwd
    return rollouts * (prefill + decode + ref), ppo_epochs * rollouts * (fwd + bwd)


def decode_step_bytes(s, batch: int, context: float, weight_bytes: int = 2,
                      kv_bytes: int = 2, shards: int = 1) -> float:
    """Bytes one decode step must move on one chip: every weight once at
    the compute dtype (divided over ``shards`` chips where the weights are
    sharded, as fsdp leaves them to be gathered), the keys and values of ``context`` cached positions read and
    one position written, at the cache dtype, for ``batch`` sequences."""
    # the blocks, the final LayerNorm and the head matrix (tied or not);
    # the embedding lookup touches ``batch`` rows, which is not counted
    weights = s["L"] * block_params(s) + 2 * s["d"] + s["d"] * s["V"]
    kv = 2 * s["L"] * batch * (context + 1) * s["d"] * kv_bytes
    return weights * weight_bytes / shards + kv
