"""OLMoE forward pass in plain ``jax.numpy`` float32.

Written from the published equations (Muennighoff et al. 2024, OLMoE,
section 2 and the released ``config.json``; checked against the
``transformers`` implementation by ``tests/test_olmoe.py``): token
embeddings only; each block computes ``x = x + attn(rms(x))`` then ``x = x
+ moe(rms(x))`` (sequential pre-norm, RMSNorm, no bias anywhere);
attention projects ``q``, ``k``, ``v`` separately, normalises ``q`` and
``k`` with an RMSNorm over the **whole projected width** before the split
into heads, turns every dimension of a head with half-rotation rotary
(base ``rope_theta``) and takes its softmax in float32; the expert layer
routes with ``p = softmax(h W_r)`` over ``num_experts`` experts, keeps the
``num_experts_per_tok`` largest ``p`` as they are (divided by their sum
only if ``norm_topk_prob``) and adds ``p_j * W_down,j(silu(W_gate,j h) *
W_up,j h)`` over the chosen experts; a final RMSNorm and an untied head.

**Every expert is computed on every token**, one expert at a time in a
loop, and a weight of 0 drops the unchosen ones: no sort, no grouped
call, no cache. A reference may waste work; it upcasts one expert at a
time, so beside 10.5 GB of served bf16 weights it never holds more than
one expert's matrices in float32.

Departure: left-padded prompts, as in ``gpt2.py`` (positions count real
tokens, padded keys are masked).

``params`` is the backbone's tree as the program names it (``wte``,
``h_<i>/{ln_1, attn/{q_proj,k_proj,v_proj,o_proj,q_norm,k_norm}, ln_2,
mlp/{router,w_gate,w_up,w_down}}`` with the experts stacked on a leading
axis, ``ln_f``, ``lm_head``); every matrix is ``[in, out]``.
"""

import re

import jax
import jax.numpy as jnp

from benchmark.reference.gpt2 import masked_attention, positions_of
from benchmark.reference.neox import rotate

f32 = lambda a: jnp.asarray(a, jnp.float32)


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * f32(p["scale"])


def router_weights(h, router, k, norm_topk):
    """([.., E] combine weights: ``p`` at the chosen experts, 0 elsewhere;
    [.., E] ``p``; [.., E] logits)."""
    logits = h @ f32(router)
    p = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(p, k)
    if norm_topk:
        top = top / top.sum(-1, keepdims=True)
    chosen = (jax.nn.one_hot(idx, p.shape[-1], dtype=p.dtype) * top[..., None]).sum(-2)
    return chosen, p, logits


def experts(h, mlp, weights):
    """Every expert on every token, one at a time."""
    def one(acc, xs):
        w_gate, w_up, w_down, w = xs
        y = (jax.nn.silu(h @ f32(w_gate)) * (h @ f32(w_up))) @ f32(w_down)
        return acc + y * w[..., None], None

    per_expert = jnp.moveaxis(weights, -1, 0)  # [E, B, T]
    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h), (mlp["w_gate"], mlp["w_up"], mlp["w_down"], per_expert)
    )
    return out


def trunk_with_aux(params, cfg, input_ids, mask):
    """(the hidden states after the final RMSNorm, [B, T, D] float32; the
    load-balancing loss as the program sows it: the mean over blocks of
    ``E * sum_e f_e P_e``, ``f_e`` the copies routed to expert ``e`` per
    token, ``P_e`` the mean ``p_e``, both over the real tokens). ``cfg``
    holds the HF keys ``hidden_size``, ``num_hidden_layers``,
    ``num_attention_heads``, ``num_experts``, ``num_experts_per_tok``,
    ``norm_topk_prob``, ``rms_norm_eps``, ``rope_theta``."""
    eps, H = cfg["rms_norm_eps"], cfg["num_attention_heads"]
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    pos = positions_of(mask)
    aux = []
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"]["embedding"])[input_ids]
        B, T, D = x.shape
        Dh = D // H
        for i in range(cfg["num_hidden_layers"]):
            blk = params[f"h_{i}"]
            a = blk["attn"]
            h = rms_norm(x, blk["ln_1"], eps)
            q = rms_norm(h @ f32(a["q_proj"]["kernel"]), a["q_norm"], eps).reshape(B, T, H, Dh)
            kk = rms_norm(h @ f32(a["k_proj"]["kernel"]), a["k_norm"], eps).reshape(B, T, H, Dh)
            v = (h @ f32(a["v_proj"]["kernel"])).reshape(B, T, H, Dh)
            q, kk = rotate(q, pos, Dh, cfg["rope_theta"]), rotate(kk, pos, Dh, cfg["rope_theta"])
            x = x + masked_attention(q, kk, v, mask).reshape(B, T, D) @ f32(a["o_proj"]["kernel"])
            h = rms_norm(x, blk["ln_2"], eps)
            w, p, _ = router_weights(h, blk["mlp"]["router"], k, cfg.get("norm_topk_prob", False))
            x = x + experts(h, blk["mlp"], w)
            real = mask.astype(jnp.float32)[..., None] / mask.sum()
            per_token = jax.lax.stop_gradient(((w > 0) * real).sum((0, 1)))
            aux.append(E * (per_token * (p * real).sum((0, 1))).sum())
        return rms_norm(x, params["ln_f"], eps), jnp.mean(jnp.stack(aux))


def trunk(params, cfg, input_ids, mask):
    return trunk_with_aux(params, cfg, input_ids, mask)[0]


def head(params, cfg, hidden):
    """Logits [..., V] of hidden states [..., D]: the untied ``lm_head``."""
    with jax.default_matmul_precision("highest"):
        return hidden @ f32(params["lm_head"]["kernel"])


def forward_with_aux(params, cfg, input_ids, mask):
    """(logits [B, T, V] float32, the load-balancing loss)."""
    hidden, aux = trunk_with_aux(params, cfg, input_ids, mask)
    return head(params, cfg, hidden), aux


def forward(params, cfg, input_ids, mask):
    """Logits [B, T, V] in float32: the head on every position of the trunk."""
    return forward_with_aux(params, cfg, input_ids, mask)[0]


def shape(cfg):
    """A block holds its attention (four d x d matrices), a router, E
    experts of three d x F matrices and four norm vectors (two of the
    block, two of QK-norm); a token is multiplied with the attention, the
    router and ``num_experts_per_tok`` experts; a decode step reads the
    attention, the router, the norms and the experts its tokens chose."""
    d, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    attn, router, norms, expert = 4 * d * d, d * E, 4 * d, 3 * d * F
    block = {
        "params": attn + router + E * expert + norms,
        "matmul_params": attn + router + k * expert,
        "read_params": attn + router + norms,
        "routed": {"expert_params": expert, "per_token": k},
        "attn_dim": d,
        "kv_values": 2 * d,
    }
    return {
        "embed_params": V * d,
        "layers": [block] * cfg["num_hidden_layers"],
        "final": {"params": d + d * V, "matmul_params": d * V, "read_params": d + d * V},
    }


def check_config(cfg):
    """What the program's ``OlmoeConfig`` refuses by name."""
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("the program's olmoe family builds no grouped KV heads")
    for key in ("clip_qkv", "rope_scaling"):
        if cfg.get(key) is not None:
            raise ValueError(f"the program's olmoe family builds no {key}")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("the program's olmoe family builds no tied head")


# -- the grouped multiplication's required work (``readers.op_roofline``) --- #


def _gmm_calls(ops):
    """Each matching operation with its result's rows and width, read from
    the name ``trace_reduce.op_kind`` gives it (``ragged-dot bf16[256,1024]``)."""
    out = []
    for name, op in ops.items():
        m = re.search(r"\[(\d+),(\d+)\]", name)
        if m:
            out.append((int(m.group(1)), int(m.group(2)), op["count"]))
    return out


def gmm_prefill_count(record, ops):
    """(FLOPs, bytes) the matching executions require at prefill shapes,
    where the multiplication is compute-bound: every routed row times one
    ``d x F`` matrix, 2 FLOPs a multiply-add - the result's rows x its
    width x the other width. Bytes: the rows read and written once (the
    weights, read at most once an expert, are left out: the least)."""
    cf = record["cell"]["config_file"]
    d, F = cf["hidden_size"], cf["intermediate_size"]
    flops = moved = 0
    for rows, width, count in _gmm_calls(ops):
        other = d if width == F else F
        flops += 2 * rows * width * other * count
        moved += 2 * rows * (width + other) * count
    return flops, moved


def gmm_decode_count(record, ops):
    """(FLOPs, bytes) at decode shapes, where it is bound by reading
    weights: every execution reads one ``d x F`` matrix of each expert the
    step *touched* - the program's own gauge ``moe/experts_touched``
    (mean over blocks and steps), never all of them - in bf16."""
    cf = record["cell"]["config_file"]
    d, F = cf["hidden_size"], cf["intermediate_size"]
    touched = record.get("gauges", {}).get("moe/experts_touched")
    if touched is None:
        return 0.0, 0.0
    flops = moved = 0.0
    for rows, width, count in _gmm_calls(ops):
        flops += 2 * rows * d * F * count
        moved += touched * d * F * 2 * count
    return flops, moved
