"""What the algorithm requires, from shapes alone: parameters, FLOPs, bytes.

The yardstick's arithmetic. Nothing here reads the program: a later PR may
change how the program computes a phase, and is still divided into these
numbers. "Required" means the forward and backward passes of the model as
published; recomputation is not counted, and neither is work a clever
program may skip (the frozen trunk's backward IS skipped in the count,
because no algorithm needs it).

Nothing here knows a model family either. A family's file under
``reference/`` exports ``shape(cfg)``, its shape rule on a configuration
file's published keys, and every formula below is computed from what that
returns (:func:`model_shape` checks it):

- ``embed_params``: the token table and any learned position table: held,
  never multiplied with; a decode step touches ``batch`` rows of it, which
  is not counted.
- ``layers``: one entry per block, in order, so that leading dense layers,
  window and global layers or recurrent layers are entries of the list
  and not formulas of their own. An entry keeps three counts of
  parameters apart, which are one number only in a dense block:

  - ``params``: the parameters the block holds;
  - ``matmul_params``: the matrix parameters one token is multiplied with
    (2 FLOPs each) - in a routed block the router and
    ``experts_per_token`` experts, not all of them;
  - ``read_params``: the weights every decode step reads whatever it
    routes, and, in a routed block, ``routed``: ``{"expert_params",
    "per_token"}``, the size of one expert and how many a token
    chooses: a step reads an expert only where a token chose it
    (:func:`decode_read_params`);

  and ``attn_dim``, query heads x head size (QK^T and AV cost 4 FLOPs
  times this for each pair of token and context position), and
  ``kv_values``, the cache values written per position: KV heads x head
  size x 2, and 0 in a block that keeps none. Two more counts are given
  only by a block that has them, and an absent one means what it meant
  before they existed:

  - ``kv_read_cap``: the most cached positions a decode step must read in
    this block whatever the context - a window's width less the position
    being written; a selection's top-k x block with its initial and local
    blocks and, in ``kv_values``' units, its compressed keys. Absent: the
    step reads its whole context;
  - ``state_values``: the values of state a sequence reads and writes
    once a step (a linear-attention or state-space block: heads x key size
    x value size, plus any convolution tail), at the byte width the
    configuration's ``run`` group states (``state_dtype``). Absent: none.
- ``final``: the final norm and the head, as ``params`` (what they hold
  beyond the token table: a tied head holds nothing), ``matmul_params``
  and ``read_params``.
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


LAYER_KEYS = ("params", "matmul_params", "read_params", "attn_dim", "kv_values")
OPTIONAL_LAYER_KEYS = ("kv_read_cap", "state_values")
FINAL_KEYS = ("params", "matmul_params", "read_params")
ROUTED_KEYS = ("expert_params", "per_token")
DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def model_shape(family, cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The family's shape rule on a configuration, refused here, by name,
    where it lacks a count the formulas read or gives one that is not a
    whole number."""
    s = family.shape(cfg)
    groups = [("shape", s, ("embed_params",)), ("final", s["final"], FINAL_KEYS)]
    for i, layer in enumerate(s["layers"]):
        groups.append((f"layers[{i}]", layer, LAYER_KEYS + tuple(k for k in OPTIONAL_LAYER_KEYS if k in layer)))
        if "routed" in layer:
            groups.append((f"layers[{i}].routed", layer["routed"], ROUTED_KEYS))
    for where, group, keys in groups:
        for key in keys:
            if not isinstance(group.get(key), int) or group[key] < 0:
                raise ValueError(f"{family.__name__}.shape: {where}[{key!r}] is {group.get(key)!r}")
    return s


def backbone_params(s) -> int:
    """Every parameter of the language model (no value head)."""
    return s["embed_params"] + sum(l["params"] for l in s["layers"]) + s["final"]["params"]


def forward_flops(s, tokens: int, ctx_sum: int, head_tokens: int, layers=None) -> int:
    """Matmul FLOPs of a forward over ``tokens`` positions whose attention
    contexts sum to ``ctx_sum`` (a causal pass over T: T(T+1)/2), with the
    head applied at ``head_tokens`` positions; through the top ``layers``
    blocks only where that is given. 2 FLOPs per multiply-add."""
    blocks = s["layers"] if layers is None else s["layers"][len(s["layers"]) - layers:]
    trunk = sum(2 * l["matmul_params"] * tokens + 4 * l["attn_dim"] * ctx_sum for l in blocks)
    return trunk + 2 * s["final"]["matmul_params"] * head_tokens


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
    if 0 < unfrozen < len(s["layers"]):
        bwd = 2 * forward_flops(s, T, ctx_T, R, layers=unfrozen)
    else:
        bwd = 2 * fwd
    return rollouts * (prefill + decode + ref), ppo_epochs * rollouts * (fwd + bwd)


def decode_read_params(layer) -> int:
    """The weights of one block that a decode step must read, whatever
    its batch. A dense block: all of them. A routed block: what every
    token passes through, and ``per_token`` experts. Which experts a step
    reads is decided by the routing, not by shapes: the tokens of a step
    choose between ``per_token`` distinct experts (they all agree) and
    ``batch x per_token``, or every expert (no two agree). The least is
    taken, because it is the one count no correct program can move fewer
    bytes than: a single token is multiplied with ``per_token`` whole
    experts, and a step whose tokens agree reads no more. Any larger
    count, such as the expected number of distinct experts under even
    routing, is one that a skewed and correct step beats: its share of
    the roofline would read over 100%. So for a large batch this is a
    floor well under what the step reads; a family that wants the tight
    figure counts the experts the program did touch from a counter, in a
    count function of its own (``readers.op_roofline``)."""
    routed = layer.get("routed")
    if not routed:
        return layer["read_params"]
    return layer["read_params"] + routed["expert_params"] * routed["per_token"]


def decode_step_bytes(s, batch: float, context: float, weight_bytes: int = 2,
                      kv_bytes: int = 2, shards: int = 1, state_bytes: int = None) -> float:
    """Bytes one decode step must move on one chip: the weights it must
    read (:func:`decode_read_params` of every block, the final norm and
    the head matrix, tied or not) once at the compute dtype, divided over
    ``shards`` chips where the weights are sharded, as fsdp leaves them to
    be gathered; for ``batch`` sequences, at the cache dtype, the keys and
    values of ``context`` cached positions read - in a block that gives
    ``kv_read_cap``, of that many at most - and one position written; and
    a block's ``state_values`` read and written once a sequence at
    ``state_bytes`` a value. The count is the least a correct program can
    move, so a share of the roofline over 100% is a fault of the program's
    accounting or of a family's shape rule, never of this formula: a cap
    or a state must be what the published equations require a step to
    touch, not what one implementation happens to."""
    weights = sum(decode_read_params(l) for l in s["layers"]) + s["final"]["read_params"]
    whole = sum(l["kv_values"] for l in s["layers"] if "kv_read_cap" not in l)
    kv = whole * batch * (context + 1) * kv_bytes
    kv += sum(l["kv_values"] * batch * (min(context, l["kv_read_cap"]) + 1) * kv_bytes
              for l in s["layers"] if "kv_read_cap" in l)
    state = sum(l.get("state_values", 0) for l in s["layers"])
    if state and state_bytes is None:
        raise ValueError("the shape has blocks with `state_values` and no `state_bytes` was given: "
                         "the configuration's `run` group states no `state_dtype`")
    return weights * weight_bytes / shards + kv + 2 * state * batch * (state_bytes or 0)
