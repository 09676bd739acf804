"""GPT-NeoX (Pythia) forward pass in plain ``jax.numpy`` float32.

Written from the published equations (Black et al. 2022, GPT-NeoX-20B,
section 2; Biderman et al. 2023, Pythia): token embeddings only; each
block computes ``x + attn(ln_1(x)) + mlp(ln_2(x))`` (parallel residual,
two LayerNorms); the fused QKV projection is laid out head-major
(``[H, 3*Dh]``, HF's layout); rotary embeddings (Su et al. 2021) turn the
first ``rotary_pct`` of each head's dimensions, in the half-rotation
convention, with base ``rotary_emb_base``; the MLP is ``intermediate_size``
wide with GELU; a final LayerNorm and an untied ``embed_out`` head with no
bias. No cache, no kernels.

Departure: left-padded prompts, as in ``gpt2.py`` (positions count real
tokens, padded keys are masked).

The GELU is the one ``hidden_act`` names: Pythia publishes ``"gelu"``, the
exact erf form, and that is what a run compares against. The program under
test computes the tanh approximation for this family
(``models/neox.py::NeoXMLP``); the two differ by under 5e-4 per activation,
which the measured tolerance contains (PERF.md, Open questions). The tests
also pass ``"gelu_new"`` to pin the rest of the arithmetic to 1e-5.

``params`` is the backbone's tree as the program names it (``wte``,
``h_<i>/{ln_1,attn/{query_key_value,dense},ln_2,mlp/{dense_h_to_4h,
dense_4h_to_h}}``, ``ln_f``, ``lm_head``).
"""

import jax
import jax.numpy as jnp

from benchmark.reference.gpt2 import (
    block_shape,
    dense,
    gelu_tanh,
    layer_norm,
    masked_attention,
    positions_of,
)


def rotate(x, positions, rotary_dim, base):
    """x: [B, T, H, Dh]; turns the first ``rotary_dim`` dims of each head."""
    inv_freq = 1.0 / base ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # [B, T, rd/2]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], -1)
    return jnp.concatenate([rot * cos + turned * sin, rest], -1)


def gelu_erf(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0)))


ACTIVATIONS = {"gelu": gelu_erf, "gelu_new": gelu_tanh}


def trunk(params, cfg, input_ids, mask):
    """The hidden states after the final LayerNorm, [B, T, D] in float32.
    ``cfg`` holds the HF keys ``hidden_size``, ``num_hidden_layers``,
    ``num_attention_heads``, ``rotary_pct``, ``rotary_emb_base``,
    ``use_parallel_residual``, ``layer_norm_eps``, ``hidden_act``."""
    act = ACTIVATIONS[cfg.get("hidden_act", "gelu")]
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    eps = cfg.get("layer_norm_eps", 1e-5)
    H = cfg["num_attention_heads"]
    pos = positions_of(mask)
    with jax.default_matmul_precision("highest"):
        x = p["wte"]["embedding"][input_ids]
        B, T, D = x.shape
        Dh = D // H
        rd = int(Dh * cfg.get("rotary_pct", 0.25))
        base = cfg.get("rotary_emb_base", 10000.0)
        for i in range(cfg["num_hidden_layers"]):
            blk = p[f"h_{i}"]
            qkv = dense(layer_norm(x, blk["ln_1"], eps), blk["attn"]["query_key_value"])
            qkv = qkv.reshape(B, T, H, 3 * Dh)
            q, k, v = qkv[..., :Dh], qkv[..., Dh : 2 * Dh], qkv[..., 2 * Dh :]
            q, k = rotate(q, pos, rd, base), rotate(k, pos, rd, base)
            a = dense(masked_attention(q, k, v, mask).reshape(B, T, D), blk["attn"]["dense"])
            if cfg.get("use_parallel_residual", True):
                m_in = layer_norm(x, blk["ln_2"], eps)
            else:
                m_in = layer_norm(x + a, blk["ln_2"], eps)
            m = dense(act(dense(m_in, blk["mlp"]["dense_h_to_4h"])), blk["mlp"]["dense_4h_to_h"])
            x = x + a + m
        return layer_norm(x, p["ln_f"], eps)


def head(params, cfg, hidden):
    """Logits [..., V] of hidden states [..., D]: the untied ``lm_head``."""
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params["lm_head"])
    with jax.default_matmul_precision("highest"):
        return dense(hidden, p)


def forward(params, cfg, input_ids, mask):
    """Logits [B, T, V] in float32: the head on every position of the trunk."""
    return head(params, cfg, trunk(params, cfg, input_ids, mask))


def shape(cfg):
    """The block holds what a GPT-2 block holds (the parallel residual
    changes no size); no position table; the head is a matrix of its own."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "embed_params": V * d,
        "layers": [block_shape(d, cfg["intermediate_size"])] * cfg["num_hidden_layers"],
        "final": {"params": 2 * d + d * V, "matmul_params": d * V, "read_params": 2 * d + d * V},
    }


def check_config(cfg):
    """The program builds the MLP 4 x hidden wide and has no key for it."""
    if cfg["intermediate_size"] != 4 * cfg["hidden_size"]:
        raise ValueError(
            "the program's NeoXConfig assumes intermediate_size = 4 x "
            "hidden_size; this configuration publishes another"
        )
