"""GPT-2 forward pass in plain ``jax.numpy`` float32.

Written from the published equations (Radford et al. 2019; the layout of
OpenAI's ``model.py``): learned token + position embeddings, pre-LN blocks
``x += attn(ln_1(x)); x += mlp(ln_2(x))``, one fused QKV projection split
in thirds, causal softmax attention scaled by ``1/sqrt(head_dim)``, a 4x
MLP with the tanh GELU (``gelu_new``), a final LayerNorm and a head tied
to the token embedding. No cache, no kernels, no batching tricks.

One departure from the paper, which has no padding: prompts here are
left-padded, so a token's position is the count of real tokens before it
and padded keys are masked out — the convention of every HF GPT-2 caller
that passes ``attention_mask`` and ``position_ids``.

``params`` is the backbone's parameter tree as the program names it
(``wte/embedding``, ``wpe/embedding``, ``h_<i>/{ln_1,attn/{c_attn,c_proj},
ln_2,mlp/{c_fc,c_proj}}``, ``ln_f``); it is read as float32 whatever it is
stored in.

The forward comes in two halves, :func:`trunk` and :func:`head`, so that a
check can ask for the logits of a few positions without forming those of
every one (``benchmark/checks.py``); :func:`forward` is their composition.

:func:`shape` is the family's shape rule: what ``benchmark/arithmetic.py``
reckons parameters, FLOPs and bytes from (the keys are explained there).
"""

import jax
import jax.numpy as jnp


def layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def dense(x, p):
    y = x @ p["kernel"]
    return y + p["bias"] if "bias" in p else y


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


QUERY_BLOCK = 1024


def attend(q, k, v, mask, first=0):
    """q: [B, Tq, H, Dh], the queries at positions ``first`` onwards; k, v:
    [B, T, H, Dh]; mask: [B, T] of 0/1. Causal, padded keys out."""
    T = k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
    at = first + jnp.arange(q.shape[1])
    allowed = (jnp.arange(T)[None, :] <= at[:, None])[None, None] & (
        mask[:, None, None, :] > 0
    )
    scores = jnp.where(allowed, scores, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def masked_attention(q, k, v, mask):
    """q, k, v: [B, T, H, Dh]; mask: [B, T] of 0/1. Causal, padded keys out.
    Past ``QUERY_BLOCK`` positions the queries go ``QUERY_BLOCK`` at a time,
    each block against every key: a query's row of the softmax is its own,
    so the arithmetic is the same and no [T, T] array is formed."""
    B, T, H, Dh = q.shape
    if T <= QUERY_BLOCK:
        return attend(q, k, v, mask)
    blocks = -(-T // QUERY_BLOCK)
    padded = jnp.pad(q, ((0, 0), (0, blocks * QUERY_BLOCK - T), (0, 0), (0, 0)))
    padded = jnp.moveaxis(padded.reshape(B, blocks, QUERY_BLOCK, H, Dh), 1, 0)
    out = jax.lax.map(
        lambda x: attend(x[0], k, v, mask, x[1]),
        (padded, jnp.arange(blocks) * QUERY_BLOCK),
    )
    return jnp.moveaxis(out, 0, 1).reshape(B, blocks * QUERY_BLOCK, H, Dh)[:, :T]


def positions_of(mask):
    return jnp.clip(jnp.cumsum(mask, axis=-1) - 1, 0, None)


def trunk(params, cfg, input_ids, mask):
    """The hidden states after the final LayerNorm, [B, T, D] in float32.
    ``cfg`` holds the HF keys ``n_embd``, ``n_layer``, ``n_head`` and
    optionally ``layer_norm_epsilon``."""
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    eps = cfg.get("layer_norm_epsilon", 1e-5)
    H = cfg["n_head"]
    with jax.default_matmul_precision("highest"):
        x = p["wte"]["embedding"][input_ids] + p["wpe"]["embedding"][positions_of(mask)]
        B, T, D = x.shape
        for i in range(cfg["n_layer"]):
            blk = p[f"h_{i}"]
            qkv = dense(layer_norm(x, blk["ln_1"], eps), blk["attn"]["c_attn"])
            q, k, v = (a.reshape(B, T, H, D // H) for a in jnp.split(qkv, 3, axis=-1))
            a = masked_attention(q, k, v, mask).reshape(B, T, D)
            x = x + dense(a, blk["attn"]["c_proj"])
            h = gelu_tanh(dense(layer_norm(x, blk["ln_2"], eps), blk["mlp"]["c_fc"]))
            x = x + dense(h, blk["mlp"]["c_proj"])
        return layer_norm(x, p["ln_f"], eps)


def head(params, cfg, hidden):
    """Logits [..., V] of hidden states [..., D]: the token table again."""
    with jax.default_matmul_precision("highest"):
        return hidden @ jnp.asarray(params["wte"]["embedding"], jnp.float32).T


def forward(params, cfg, input_ids, mask):
    """Logits [B, T, V] in float32: the head on every position of the trunk."""
    return head(params, cfg, trunk(params, cfg, input_ids, mask))


def block_shape(d, ff):
    """One block with a fused QKV and an output projection, a two-matrix
    MLP ``ff`` wide and two LayerNorms, all with biases. Nothing is
    routed: a token is multiplied with every matrix and a decode step
    reads every weight; every head keeps its own keys and values."""
    attn = d * 3 * d + 3 * d + d * d + d
    mlp = d * ff + ff + ff * d + d
    params = attn + mlp + 4 * d
    return {"params": params, "matmul_params": 4 * d * d + 2 * d * ff,
            "read_params": params, "attn_dim": d, "kv_values": 2 * d}


def shape(cfg):
    """Learned positions beside the token table; the head is the token
    table again, so it holds nothing of its own and is multiplied with
    (and read) all the same."""
    d, V = cfg["n_embd"], cfg["vocab_size"]
    return {
        "embed_params": V * d + cfg["n_positions"] * d,
        "layers": [block_shape(d, cfg.get("n_inner") or 4 * d)] * cfg["n_layer"],
        "final": {"params": 2 * d, "matmul_params": d * V, "read_params": 2 * d + d * V},
    }
