"""A family that is none of the benchmark's own, added by files alone
(``tests/benchmark/test_benchmark_manifest.py`` lays this directory over a
copy of ``benchmark/``). A pre-norm decoder in plain ``jax.numpy`` float32:
RMSNorm, no biases, full rotary, grouped-query attention
(``num_key_value_heads`` under ``num_attention_heads``), gated (SwiGLU)
MLPs; a block's attention is of the kind ``layer_types`` names for it:
``full`` (causal softmax over every position), ``window`` (over the last
``sliding_window`` positions, itself among them) or ``linear`` (no softmax
and no rotary: a query reads the state ``S_t = S_{t-1} + k_t^T v_t`` of its
KV head, ``o_t = q_t S_t / sqrt(Dh)``, so the block caches no keys or
values and carries ``KV heads x Dh x Dh`` values of state a sequence); the
first ``first_k_dense`` blocks have one MLP ``intermediate_size``
wide, every later block a router over ``num_experts`` gated experts
``moe_intermediate_size`` wide, of which each token takes the
``num_experts_per_tok`` with the largest softmax weight (not renormalised);
an untied head.

``params``: ``wte``, ``h_<i>/{ln_1, attn/{q,k,v,o}, ln_2, mlp/{gate,up,down}
| moe/{router, gate,up,down}}`` (the experts stacked on a leading axis),
``ln_f``, ``lm_head``; every matrix is ``[in, out]``.

The forward comes in the two halves the checks ask for, :func:`trunk` and
:func:`head`; the configuration names a tolerance file of its own
(``tolerances/toymoe-tiny.json``) and :func:`shape` gives the window block
the most positions a decode step reads there (``kv_read_cap``) and the
linear block its state (``state_values``).
"""

import jax
import jax.numpy as jnp

from benchmark.reference.gpt2 import positions_of
from benchmark.reference.neox import rotate


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def gated(x, p):
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def routed(x, p, k):
    """Every expert is computed here and the unchosen ones weighted 0: a
    reference may waste work. ``arithmetic`` counts the ``k`` chosen."""
    weights = jax.nn.softmax(x @ p["router"], axis=-1)  # [B, T, E]
    top, idx = jax.lax.top_k(weights, k)
    gate = jnp.zeros_like(weights).at[
        jnp.arange(x.shape[0])[:, None, None], jnp.arange(x.shape[1])[None, :, None], idx].set(top)
    each = jax.vmap(lambda g, u, d: gated(x, {"gate": g, "up": u, "down": d}))(
        p["gate"], p["up"], p["down"])  # [E, B, T, D]
    return jnp.einsum("bte,ebtd->btd", gate, each)


def mixed(q, k, v, mask, kind, window):
    """q, k, v: [B, T, H, Dh]; mask: [B, T] of 0/1; left-padded, so a
    distance between real tokens is a distance between columns."""
    T = q.shape[1]
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]  # query - key
    allowed = (ahead >= 0) & ((ahead < window) | (kind != "window"))
    allowed = allowed[None, None] & (mask[:, None, None, :] > 0)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
    if kind == "linear":  # q_t S_t with S_t the sum of k_s^T v_s over s <= t
        weights = jnp.where(allowed, scores, 0.0)
    else:
        weights = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def trunk(params, cfg, input_ids, mask):
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    eps, H, G = cfg["rms_norm_eps"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pos = positions_of(mask)
    with jax.default_matmul_precision("highest"):
        x = p["wte"][input_ids]
        B, T, D = x.shape
        Dh = D // H
        for i in range(cfg["num_hidden_layers"]):
            blk, kind = p[f"h_{i}"], cfg["layer_types"][i]
            h = rms_norm(x, blk["ln_1"], eps)
            q = (h @ blk["attn"]["q"]).reshape(B, T, H, Dh)
            k = (h @ blk["attn"]["k"]).reshape(B, T, G, Dh)
            v = (h @ blk["attn"]["v"]).reshape(B, T, G, Dh)
            if kind != "linear":
                q, k = rotate(q, pos, Dh, cfg["rope_theta"]), rotate(k, pos, Dh, cfg["rope_theta"])
            k, v = (jnp.repeat(a, H // G, axis=2) for a in (k, v))  # each group of queries shares a head
            a = mixed(q, k, v, mask, kind, cfg["sliding_window"])
            x = x + a.reshape(B, T, D) @ blk["attn"]["o"]
            h = rms_norm(x, blk["ln_2"], eps)
            if i < cfg["first_k_dense"]:
                x = x + gated(h, blk["mlp"])
            else:
                x = x + routed(h, blk["moe"], cfg["num_experts_per_tok"])
        return rms_norm(x, p["ln_f"], eps)


def head(params, cfg, hidden):
    with jax.default_matmul_precision("highest"):
        return hidden @ jnp.asarray(params["lm_head"], jnp.float32)


def forward(params, cfg, input_ids, mask):
    return head(params, cfg, trunk(params, cfg, input_ids, mask))


def shape(cfg):
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    Dh = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * Dh
    attn = d * d + 2 * d * kv + d * d
    norms = 2 * d
    dense_mlp = 3 * d * cfg["intermediate_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    router = d * E
    dense = {"params": attn + dense_mlp + norms, "matmul_params": attn + dense_mlp,
             "read_params": attn + dense_mlp + norms, "attn_dim": d, "kv_values": 2 * kv}
    sparse = {"params": attn + router + E * expert + norms,
              "matmul_params": attn + router + k * expert,
              "read_params": attn + router + norms,
              "routed": {"expert_params": expert, "per_token": k},
              "attn_dim": d, "kv_values": 2 * kv}
    n_dense = cfg["first_k_dense"]
    layers = [dict(dense if i < n_dense else sparse) for i in range(cfg["num_hidden_layers"])]
    for layer, kind in zip(layers, cfg["layer_types"]):
        if kind == "window":  # a step reads the window's other positions and writes its own
            layer["kv_read_cap"] = cfg["sliding_window"] - 1
        elif kind == "linear":  # no cache; a state a KV head, no pair of positions is scored
            layer.update(kv_values=0, attn_dim=0, state_values=cfg["num_key_value_heads"] * Dh * Dh)
    return {
        "embed_params": V * d,
        "layers": layers,
        "final": {"params": d + d * V, "matmul_params": d * V, "read_params": d + d * V},
    }
