"""Ling-3.0-flash (``model_type: bailing_hybrid``; the language model of
``inclusionAI/Ling-3.0-flash-VL``) forward pass in plain ``jax.numpy``
float32.

Written from the keys of the published ``config.json`` (``[c]``) and the
published rules they name: Kimi Linear (arXiv:2510.26692, the delta rule
whose decay is a vector over a head's key channels, "KDA"), DeepSeek-V2
(arXiv:2405.04434, section 2.1: latent attention) and DeepSeek-V3
(arXiv:2412.19437, section 2.1.2: the group-limited sigmoid router). What
is read into a key by this repository is marked ``[a]`` and listed under
``assumed`` in the configuration file. d 2560, H 32, head 128, c 512, rope
64, E 512 in 8 groups, k 8 as published:

    block i, input h [T, d]:  h += mix_i(rms(h));  h += ffn_i(rms(h))     eps 1e-6, no biases  [c]
    mix_i = latent attention where (i + 1) % layer_group_size == 0, else KDA                  [a]
    ffn_i = dense SwiGLU (d -> 6144 -> d) for i < first_k_dense_replace, else routed          [c]
    logits = rms(h_L) W_head                                               untied              [a]

    KDA(x), heads of 128 keys and 128 values, as many key heads as heads                      [c]
      [q | k | v] = silu(conv4([x W_q | x W_k | x W_v]))     depthwise, causal, no bias        [a]
      q <- q / sqrt(sum q^2 + 1e-6) * 128^-1/2,  k <- k / sqrt(sum k^2 + 1e-6)   a head       [a]
      g_t = kda_lower_bound * sigmoid(exp(A_log[h]) (x W_g + dt_bias))  in (-5, 0), a vector
            of 128 a head; W_g full rank (no_kda_lora)                                         [a]
      beta_t = sigmoid(x W_b)                                 one number a head
      S <- Diag(exp g_t) S;  u = S^T k_t;  S <- S + k_t (beta_t (v_t - u))^T;  o_t = S^T q_t
      o_t <- o_t / sqrt(mean o_t^2 + eps) * w_n * sigmoid(x W_z)[h]   a head's norm           [a]
      out = concat_h(o) W_o

    - **one state update a position**, a ``lax.scan`` over the positions
      with the state ``[128, 128]`` a head: never a chunked form.

    Latent(x), H heads:
      q = x W_q -> H heads of [q_nope (128) | q_rope (64)]    no low-rank pair (q_lora_rank null)  [c]
      [c_kv | k_r] = x W_kva  (d -> 512 + 64);  c_kv <- rms(c_kv)                              [a] use_qk_norm
      q_rope <- R_t q_rope;  k_r <- R_t k_r, one key part for all heads
      R_t: the pairs (2j, 2j+1), j < 32, turned by t theta^(-2j / 64), theta 6e6, no scaling   [a] pair layout
      [k_nope_h | v_h] = c_kv W_kvb  (512 -> H x (128 + 128))
      score_h(t, u) = 192^-1/2 (q_nope_h . k_nope_h(u) + q_rope_h . k_r(u)),  u <= t
      o_h = sum_u softmax_u(score_h) v_h(u);  out = concat_h(o_h) W_o

    routed ffn:
      sg = sigmoid(x W_r) over E, float32;  sg' = sg + b  (for the choice only; b zeros at first)
      8 groups of 64; a group's score is the sum of its two largest sg';                       [a]
      the 4 best groups stay, the others' sg' are masked with -inf; e_1..e_8 = the 8 largest
      w_j = 2.5 sg[e_j] / (sum_j sg[e_j] + 1e-20)
      y = sum_j w_j E_{e_j}(x) + S(x),  E and the one shared S SwiGLU d -> 768 -> d, no clamp  [a]

**The chip's share.** The parameter tree holds experts ``first_local_expert
.. + num_experts`` of the router's ``num_router_experts``. The router keeps
every output, its groups and its 8 choices; every held expert is computed on
every token, one at a time in a loop, weighted by the router (0 where it was
not chosen); the absent experts' terms are left out, exactly as the program
leaves them out, and that partial sum (with ``S(x)``, which every chip
computes) goes on to the next block.

Nothing here is shared with ``trlx_tpu``: no cache, no chunk, no solve, no
absorbed product, no sort, no grouped call, no ``top_k``. An expert is
upcast as it is used, the dense block's feed-forward runs over its width in
blocks, the latent layer's queries go ``QUERY_BLOCK`` at a time against
every key, the token table is read by rows and the head runs over the
vocabulary in blocks.

Departures from the published description, each also under ``assumed``:
left-padded prompts (padded keys are masked, rotary positions count a row's
real tokens, a padded position feeds zeros to the KDA mixer, so the state
and the convolution's window are zero when a row's first token arrives, as
they are for an unpadded sequence); no multi-token-prediction module and no
vision tower (the catalog's ``config`` has no count key for either); no
SwiGLU clamp (the limit lists are 0 for every block the cut keeps).

``params`` is the backbone's tree as the program names it (``wte``,
``h_<i>/{ln_1, kda/{in_proj_qkv, g_proj, in_proj_bz, conv_weight [K, C],
dt_bias, A_log, norm, out_proj} | attn/{q_proj, kv_a_proj, kv_a_norm,
kv_b_proj, o_proj}, ln_2, mlp/{gate_proj, up_proj, down_proj} | mlp/{router,
router_bias, w_gate, w_up, w_down} + shared/{gate_proj, up_proj,
down_proj}}``, ``ln_f``, ``lm_head``); every matrix ``[in, out]``,
``in_proj_bz``'s columns ``[b | z]``.
"""

import re

import jax
import jax.numpy as jnp

f32 = lambda a: jnp.asarray(a, jnp.float32)
HEAD_BLOCKS = 4
MLP_BLOCKS = 8
QUERY_BLOCK = 256
KDA, LATENT = "kda", "latent_attention"


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * f32(scale)


def layer_kinds(cfg):
    every = cfg["layer_group_size"]
    return [LATENT if (i + 1) % every == 0 else KDA for i in range(cfg["num_hidden_layers"])]


def l2(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def kda(u, p, cfg, mask):
    """The mixer of one KDA block on the normed input ``u`` [B, T, d]."""
    B, T, _ = u.shape
    H, D, K = cfg["num_attention_heads"], cfg["head_dim"], cfg["short_conv_kernel_size"]
    real = mask.astype(jnp.float32)[..., None]
    x = u * real
    qkv = x @ f32(p["in_proj_qkv"]["kernel"])
    w = f32(p["conv_weight"])  # [K, C]; w[K - 1] multiplies the position itself
    padded = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j : j + T] * w[j] for j in range(K))) * real
    heads = lambda a: a.reshape(B, T, H, D)
    q = l2(heads(qkv[..., : H * D])) * D**-0.5
    k = l2(heads(qkv[..., H * D : 2 * H * D]))
    v = heads(qkv[..., 2 * H * D :])
    rate = jnp.exp(f32(p["A_log"]))[:, None]  # [H, 1]
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(rate * heads(x @ f32(p["g_proj"]["kernel"]) + f32(p["dt_bias"])))
    bz = x @ f32(p["in_proj_bz"]["kernel"])
    beta, z = jax.nn.sigmoid(bz[..., :H]), bz[..., H:]
    # a padded position leaves the state as it is: no decay, no write
    g, beta = g * real[..., None], beta * real

    def position(S, xs):
        q_t, k_t, v_t, g_t, beta_t = xs  # [B, H, D], [B, H]
        S = S * jnp.exp(g_t)[..., None]  # Diag(exp g) S: a state row a key channel
        held = (S * k_t[..., None]).sum(-2)  # S^T k
        S = S + k_t[..., None] * (beta_t[..., None] * (v_t - held))[..., None, :]
        return S, (S * q_t[..., None]).sum(-2)

    by_position = lambda a: jnp.moveaxis(a, 1, 0)
    _, o = jax.lax.scan(
        position, jnp.zeros((B, H, D, D), jnp.float32),
        tuple(by_position(a) for a in (q, k, v, g, beta)),
    )
    o = jnp.moveaxis(o, 0, 1)  # [B, T, H, D]
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + cfg["rms_norm_eps"]) * f32(p["norm"])
    o = o * jax.nn.sigmoid(z)[..., None]
    return o.reshape(B, T, H * D) @ f32(p["out_proj"]["kernel"])


def rotate_pairs(x, positions, rope, theta):
    """``R_t`` on the last axis of ``x`` [B, T, ..., rope]: the pair
    ``(x_2j, x_2j+1)`` turned by ``t theta^(-2j / rope)``."""
    freq = float(theta) ** (-2 * jnp.arange(rope // 2, dtype=jnp.float32) / rope)
    angle = positions.astype(jnp.float32)[..., None] * freq  # [B, T, rope / 2]
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3) + angle.shape[-1:])
    a, b = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle), a * jnp.sin(angle) + b * jnp.cos(angle)], -1)
    return turned.reshape(x.shape)


def attend(q, k, v, mask, scale, first=0):
    """q [B, Tq, H, Dk] over k [B, T, H, Dk], v [B, T, H, Dv]; query ``i``
    sits at position ``first + i``; causal, padded keys out."""
    Tq, T = q.shape[1], k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    allowed = (jnp.arange(T)[None, :] <= first + jnp.arange(Tq)[:, None])[None, None] & (
        mask[:, None, None, :] > 0
    )
    weights = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def masked_attention(q, k, v, mask, scale):
    """Past ``QUERY_BLOCK`` positions the queries go ``QUERY_BLOCK`` at a
    time, each block against every key: a query's row of the softmax is its
    own, so the arithmetic is the same."""
    B, T, H, Dk = q.shape
    if T <= QUERY_BLOCK:
        return attend(q, k, v, mask, scale)
    blocks = -(-T // QUERY_BLOCK)
    padded = jnp.pad(q, ((0, 0), (0, blocks * QUERY_BLOCK - T), (0, 0), (0, 0)))
    padded = jnp.moveaxis(padded.reshape(B, blocks, QUERY_BLOCK, H, Dk), 1, 0)
    out = jax.lax.map(
        lambda x: attend(x[0], k, v, mask, scale, x[1]),
        (padded, jnp.arange(blocks) * QUERY_BLOCK),
    )
    return jnp.moveaxis(out, 0, 1).reshape(B, blocks * QUERY_BLOCK, H, v.shape[-1])[:, :T]


def latent_attention(x, a, cfg, mask, positions):
    """The mixer of one latent block on the normed input ``x`` [B, T, d],
    in the published (decompressed) form."""
    B, T, _ = x.shape
    H, C = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, Dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    theta = cfg["rope_theta"]
    q = (x @ f32(a["q_proj"]["kernel"])).reshape(B, T, H, nope + rope)
    q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], positions, rope, theta)], -1)
    down = x @ f32(a["kv_a_proj"]["kernel"])
    c_kv = rms_norm(down[..., :C], a["kv_a_norm"]["scale"], cfg["rms_norm_eps"])
    k_r = rotate_pairs(down[..., C:], positions, rope, theta)
    kv = (c_kv @ f32(a["kv_b_proj"]["kernel"])).reshape(B, T, H, nope + Dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r[:, :, None, :], (B, T, H, rope))], -1)
    out = masked_attention(q, k, kv[..., nope:], mask, (nope + rope) ** -0.5)
    return out.reshape(B, T, H * Dv) @ f32(a["o_proj"]["kernel"])


def swiglu(h, p, blocks=1):
    """``W_down(silu(W_gate h) * W_up h)``, over the width in ``blocks``
    (the sum over the width is a sum of the blocks' products)."""
    width = p["gate_proj"]["kernel"].shape[1]
    if blocks == 1 or width % blocks:
        gate, up = h @ f32(p["gate_proj"]["kernel"]), h @ f32(p["up_proj"]["kernel"])
        return (jax.nn.silu(gate) * up) @ f32(p["down_proj"]["kernel"])
    step = width // blocks

    def one(acc, at):
        cut = lambda w, axis: f32(jax.lax.dynamic_slice_in_dim(w, at, step, axis))
        gate, up = h @ cut(p["gate_proj"]["kernel"], 1), h @ cut(p["up_proj"]["kernel"], 1)
        return acc + (jax.nn.silu(gate) * up) @ cut(p["down_proj"]["kernel"], 0), None

    return jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(blocks) * step)[0]


def expert_counts(cfg):
    """(the router's width, experts held here, the first held)."""
    held = cfg["num_experts"]
    return cfg.get("num_router_experts") or held, held, cfg.get("first_local_expert", 0)


def router_weights(h, mlp, cfg):
    """[.., E] combine weights: ``routed_scaling_factor`` times the chosen
    experts' renormalised sigmoid scores, 0 elsewhere."""
    G, keep, k = cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(h @ f32(mlp["router"]))
    E = scores.shape[-1]
    biased = scores + f32(mlp["router_bias"])
    grouped = biased.reshape(biased.shape[:-1] + (G, E // G))
    group_score = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)  # [.., G]
    # a group stays where fewer than `keep` groups score higher
    rank = (group_score[..., None, :] > group_score[..., :, None]).sum(-1)
    limited = jnp.where((rank < keep)[..., None], grouped, -jnp.inf).reshape(biased.shape)
    kth = jnp.sort(limited, axis=-1)[..., E - k, None]
    chosen = (limited >= kth) & jnp.isfinite(limited)
    picked = jnp.where(chosen, scores, 0.0)
    return cfg["routed_scaling_factor"] * picked / (picked.sum(-1, keepdims=True) + 1e-20)


def held_experts(h, mlp, weights):
    """Every held expert on every token, one at a time; ``weights`` [.., held]."""
    def one(acc, xs):
        w_gate, w_up, w_down, w = xs
        y = (jax.nn.silu(h @ f32(w_gate)) * (h @ f32(w_up))) @ f32(w_down)
        return acc + y * w[..., None], None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (mlp["w_gate"], mlp["w_up"], mlp["w_down"], jnp.moveaxis(weights, -1, 0)),
    )
    return out


def trunk(params, cfg, input_ids, mask):
    """The hidden states after the final RMSNorm, [B, T, d] float32."""
    eps = cfg["rms_norm_eps"]
    _, held, first = expert_counts(cfg)
    positions = jnp.clip(jnp.cumsum(mask, axis=-1) - 1, 0, None)
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"]["embedding"][input_ids])
        for i, kind in enumerate(layer_kinds(cfg)):
            blk = params[f"h_{i}"]
            h = rms_norm(x, blk["ln_1"]["scale"], eps)
            if kind == LATENT:
                x = x + latent_attention(h, blk["attn"], cfg, mask, positions)
            else:
                x = x + kda(h, blk["kda"], cfg, mask)
            h = rms_norm(x, blk["ln_2"]["scale"], eps)
            if i < cfg["first_k_dense_replace"]:
                x = x + swiglu(h, blk["mlp"], MLP_BLOCKS)
                continue
            w = router_weights(h, blk["mlp"], cfg)
            x = x + held_experts(h, blk["mlp"], w[..., first : first + held]) + swiglu(h, blk["shared"])
        return rms_norm(x, params["ln_f"]["scale"], eps)


def head(params, cfg, hidden):
    """Logits [..., V] of hidden states [..., d]: the untied ``lm_head``,
    over the vocabulary in blocks."""
    w = params["lm_head"]["kernel"]
    V = w.shape[1]
    step = -(-V // HEAD_BLOCKS)
    with jax.default_matmul_precision("highest"):
        parts = [hidden @ f32(w[:, at : at + step]) for at in range(0, V, step)]
    return jnp.concatenate(parts, axis=-1)


def forward(params, cfg, input_ids, mask):
    """Logits [B, T, V] in float32: the head on every position of the trunk."""
    return head(params, cfg, trunk(params, cfg, input_ids, mask))


def sizes(cfg):
    """The counts the shape rule and the count functions share."""
    d, H, D = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    C, K = cfg["kv_lora_rank"], cfg["short_conv_kernel_size"]
    nope, rope, Dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    F = cfg["moe_intermediate_size"]
    return {
        # W_q, W_k, W_v, W_g (full rank), the two head-wise matrices, W_o
        "kda_matrices": 4 * d * H * D + 2 * d * H + H * D * d,
        # the taps, A_log, dt_bias, the head norm
        "kda_other": K * 3 * H * D + H + H * D + D,
        "state": H * D * D, "tail": (K - 1) * 3 * H * D,
        "latent_matrices": d * H * (nope + rope) + d * (C + rope) + C * H * (nope + Dv) + H * Dv * d,
        "latent_other": C,
        "dense": 3 * d * cfg["intermediate_size"],
        "expert": 3 * d * F,
        "shared": 3 * d * cfg["moe_shared_expert_intermediate_size"],
    }


def shape(cfg):
    """A block holds its mixer (a KDA block: the four full-rank projections,
    the two head-wise ones, the convolution's taps, ``A_log``, ``dt_bias``,
    the head norm and ``W_o``; a latent block: ``W_q``, the down-projection
    with its norm, the decompression ``W_kvb`` and ``W_o``), two norm
    vectors and its feed-forward: a dense block the SwiGLU, a routed block
    the router over the published expert count with its selection bias, the
    shared expert and the experts **held here**. A token is multiplied with
    the mixer's matrices and the dense SwiGLU, or the router, the shared
    expert and as much of an expert as it is expected to choose here, ``k x
    held / E`` of one (one expert at 64 of 512: even routing). A decode
    step reads everything but the routed experts whatever it routes, and of
    those ``per_token`` = ``k``: one token's choices where they all lie
    here, which a step of this cell (256 x 8 choices over 512) passes by far
    (``moe_ep8_gmm_decode_count`` counts the experts the program touched).
    A KDA block caches no keys and carries its state and its convolution
    tail, read and written once a step; a latent block costs ``2 (nope +
    rope) + 2 v`` FLOPs a head and pair of positions in the published form,
    so its ``attn_dim`` is ``H (nope + rope + v) / 2``, writes one latent
    row a position, ``c + rope`` values, and reads one a cached position."""
    d, V, H = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    nope, rope, Dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    E, held, _ = expert_counts(cfg)
    k = cfg["num_experts_per_tok"]
    n = sizes(cfg)
    if (H * (nope + rope + Dv)) % 2 or (k * held * n["expert"]) % E:
        raise ValueError("attn_dim or the expected share of an expert a token is no whole number")
    mixers = {
        KDA: {"matrices": n["kda_matrices"], "other": n["kda_other"], "attn_dim": 0,
              "kv_values": 0, "state_values": n["state"] + n["tail"]},
        LATENT: {"matrices": n["latent_matrices"], "other": n["latent_other"],
                 "attn_dim": H * (nope + rope + Dv) // 2, "kv_values": cfg["kv_lora_rank"] + rope},
    }
    layers = []
    for i, kind in enumerate(layer_kinds(cfg)):
        m = mixers[kind]
        mixer = m["matrices"] + m["other"] + 2 * d
        if i < cfg["first_k_dense_replace"]:
            layer = {
                "params": mixer + n["dense"],
                "matmul_params": m["matrices"] + n["dense"],
                "read_params": mixer + n["dense"],
            }
        else:
            fixed = mixer + n["shared"] + d * E + E
            layer = {
                "params": fixed + held * n["expert"],
                "matmul_params": m["matrices"] + n["shared"] + d * E + k * held * n["expert"] // E,
                "read_params": fixed,
                "routed": {"expert_params": n["expert"], "per_token": k},
            }
        layer.update(attn_dim=m["attn_dim"], kv_values=m["kv_values"])
        if "state_values" in m:
            layer["state_values"] = m["state_values"]
        layers.append(layer)
    return {
        "embed_params": V * d,
        "layers": layers,
        "final": {"params": d + d * V, "matmul_params": d * V, "read_params": d + d * V},
    }


def check_config(cfg):
    """What the program's ``LingConfig`` refuses by name, and what a file of
    the cut must keep consistent."""
    for key, want in (("rope_scaling", None), ("q_lora_rank", None), ("use_mla_nope", False),
                      ("num_kv_heads_for_linear_attn", 0), ("kda_safe_gate", True), ("no_kda_lora", True),
                      ("use_kda_lora", False), ("linear_silu", True), ("group_norm_size", 1),
                      ("gated_attention_proj_granularity_type", "head_wise"), ("use_qk_norm", True),
                      ("value_norm", False), ("up_proj_norm", False), ("use_nGPT", False),
                      ("scale_router_input", False), ("score_function", "sigmoid"), ("norm_topk_prob", True),
                      ("moe_router_enable_expert_bias", True), ("tie_word_embeddings", False)):
        if cfg.get(key, want) != want:
            raise ValueError(f"the program's ling family builds no {key}={cfg[key]!r}")
    L = cfg["num_hidden_layers"]
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(cfg.get(key, ())[:L]):
            raise ValueError(f"the program's ling family builds no SwiGLU clamp ({key}={cfg[key]!r})")
    if not cfg["kda_lower_bound"] < 0:
        raise ValueError("kda_lower_bound bounds nothing")
    if cfg["rotary_dim"] != cfg["qk_rope_head_dim"] or cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("rotary_dim is the latent layer's qk_rope_head_dim, and every head reads the one latent")
    if not 0 <= cfg["first_k_dense_replace"] <= L:
        raise ValueError("first_k_dense_replace is not among num_hidden_layers")
    E, held, first = expert_counts(cfg)
    if held > E or not 0 <= first <= E - held:
        raise ValueError(f"num_experts={held} from {first} on are not among the router's {E}")
    if E % cfg["n_group"] or not 0 < cfg["topk_group"] <= cfg["n_group"]:
        raise ValueError(f"n_group={cfg['n_group']} / topk_group={cfg['topk_group']} do not divide the router's {E}")
    run = cfg.get("run", {})
    if "state_dtype" in run and run["state_dtype"] != cfg.get("state_dtype", "float32"):
        raise ValueError(
            f"state_dtype is {cfg.get('state_dtype', 'float32')!r} for the program and "
            f"{run['state_dtype']!r} under run (what a step's bytes are counted at)"
        )
    if run.get("kv_cache_dtype", "bfloat16") != "bfloat16":
        raise ValueError("the program's ling family builds no int8 latent beside state layers")


# -- required work of the new kernels (``readers.op_roofline``) ------------- #


def _calls(ops):
    """Each matching operation's result shape (the numbers in the brackets
    of the name ``trace_reduce.op_kind`` gives it) and its count."""
    out = []
    for name, op in ops.items():
        m = re.search(r"\[([\d,]+)\]", name)
        if m:
            out.append((tuple(int(x) for x in m.group(1).split(",")), op["count"]))
    return out


def kda_step_count(record, ops):
    """(FLOPs, bytes) of the decode step's passes over the layers' states,
    counted once an execution of the operation that reads a layer's state
    out under ``k`` and ``q`` (``f32[slots, H, D]`` by its name: one a KDA
    layer a step; the pass that writes the new states, which the compiler
    may join over the layers of a step into one operation, adds its time
    only). The rule requires, a layer and step, one read and one write of
    the ``[slots, H, D, D]`` state at the configuration's ``state_dtype``
    and, a state value, a decay multiply and a multiply-add each for ``S^T
    k``, the rank-one write and ``S^T q``: 7 FLOPs, far under the bytes'
    time. A program that passes over the state three times reads a third."""
    from benchmark.arithmetic import DTYPE_BYTES

    cf = record["cell"]["config_file"]
    H, D = cf["num_attention_heads"], cf["head_dim"]
    slots = record["cell"]["traffic_file"]["slots"]
    width = DTYPE_BYTES[cf["run"]["state_dtype"]]
    n = sum(count for shape, count in _calls(ops) if shape == (slots, H, D))
    values = slots * H * D * D
    return 7.0 * values * n, 2.0 * values * width * n


CHUNK_STATE_UPDATE = "convolution_add_fusion"
# the columns a chunk of the program's chunked form takes (the default of
# ``ops/delta.py::kda_mix``; no published key gives it, and the operation
# the work is counted at does not carry it in its name)
CHUNK_COLUMNS = 64


def kda_chunk_prefill_count(record, ops):
    """(FLOPs, bytes) of the chunked rule in an admission's forwards. The
    pattern takes every operation of the rule's chunk loop (results ``[rows,
    H, ...]`` of sizes up to a head's 128: the products, the solve's levels
    and the passes between them; their time is the rule's); the work is
    counted once a chunk, at the operation that forms a chunk's outgoing
    state, ``CHUNK_STATE_UPDATE f32[rows, H, D, D]``. A chunk of ``L`` =
    ``CHUNK_COLUMNS`` columns requires, a row and head, in the chunked form
    as published (one reference a chunk: this program's row blocks about
    their own middles multiply the same ``L x L x D`` products): ``4 L^2 D``
    for the two score matrices (``k k^T`` and ``q k^T`` under the channels'
    decay), ``L^3`` for the unit-triangular solve, ``4 L^2 D`` for ``W`` and
    ``U``, ``3 x 2 L D^2`` for the three products with the state (``W S``,
    ``q S`` and the state's update) and ``2 L^2 D`` for the read-out inside
    the chunk. Bytes: ``q``, ``k``, ``v`` read and the outputs written in
    bf16, the gate's ``L x D`` read in float32, and the state read and
    written in float32."""
    cf = record["cell"]["config_file"]
    H, D, L = cf["num_attention_heads"], cf["head_dim"], CHUNK_COLUMNS
    a_head = 4 * L * L * D + L**3 + 4 * L * L * D + 6 * L * D * D + 2 * L * L * D
    flops = moved = 0.0
    for name, op in ops.items():
        m = re.match(CHUNK_STATE_UPDATE + r" f32\[(\d+),(\d+),(\d+),(\d+)\]$", name)
        if not m or tuple(int(x) for x in m.groups()[1:]) != (H, D, D):
            continue
        rows = int(m.group(1))
        flops += op["count"] * rows * H * a_head
        moved += op["count"] * rows * H * (2.0 * L * 4 * D + 4.0 * L * D + 2 * 4.0 * D * D)
    return flops, moved


def mla_hybrid_absorbed_read_count(record, ops):
    """(FLOPs, bytes) of the decode step's read of the latent layer's pool,
    counted once an execution of the operation that forms the scores
    (``f32[slots, capacity, H]`` by its name: one a latent layer a step; the
    values' product ``bf16[slots, H, c + rope]`` beside it in the pattern
    adds its time only). The published mathematics requires, a sequence and
    cached position, the scores and the values of ``H`` heads in whichever
    form costs less: absorbed, ``2 (c + rope) + 2 c`` FLOPs a head over one
    row of ``c + rope`` values read once in bf16. Positions: the traced
    slice's mean batch x its mean context (the driver's ``decode``), not the
    pool's capacity, which the program reads whole."""
    cf = record["cell"]["config_file"]
    H, C, rope = cf["num_attention_heads"], cf["kv_lora_rank"], cf["qk_rope_head_dim"]
    d = record["decode"]
    n = sum(count for shape, count in _calls(ops) if len(shape) == 3 and shape[2] == H and shape[1] > H)
    positions = d["batch"] * (d["mean_context"] + 1) * n
    return 2.0 * H * (2 * C + rope) * positions, 2.0 * (C + rope) * positions


def _gmm_calls(ops):
    return [(s[0], s[1], count) for s, count in _calls(ops) if len(s) == 2]


def moe_ep8_gmm_decode_count(record, ops):
    """(FLOPs, bytes) of the grouped multiplication at decode shapes, where
    it is bound by reading weights: every execution reads one ``d x F``
    matrix of each held expert the step *touched* (the program's gauge
    ``moe/experts_touched``, mean over blocks and steps) in bf16; FLOPs over
    the rows whose expert is held here (``moe/rows_here_share``)."""
    cf = record["cell"]["config_file"]
    d, F = cf["hidden_size"], cf["moe_intermediate_size"]
    gauges = record.get("gauges", {})
    touched = gauges.get("moe/experts_touched")
    if touched is None:
        return 0.0, 0.0
    E, held, _ = expert_counts(cf)
    share = gauges.get("moe/rows_here_share", held / E)
    flops = moved = 0.0
    for rows, _, count in _gmm_calls(ops):
        flops += 2.0 * share * rows * d * F * count
        moved += touched * d * F * 2.0 * count
    return flops, moved


def moe_ep8_gmm_prefill_count(record, ops):
    """(FLOPs, bytes) of the grouped multiplication in an admission's
    forwards: every row whose expert is held here times one ``d x F``
    matrix. The call is handed all ``tokens x k`` sorted copies and the held
    experts' group sizes; the rest are multiplied with nothing. Their share
    is the program's gauge ``moe/rows_here_share`` (the mean over the
    polled decode steps: the same router on the same kind of tokens), else
    the even-routing share ``held / E``. Bytes: those rows read and written
    once and every held expert's matrix read once, bf16."""
    cf = record["cell"]["config_file"]
    d, F = cf["hidden_size"], cf["moe_intermediate_size"]
    E, held, _ = expert_counts(cf)
    share = record.get("gauges", {}).get("moe/rows_here_share", held / E)
    flops = moved = 0.0
    for rows, _, count in _gmm_calls(ops):
        flops += 2.0 * share * rows * d * F * count
        moved += (2.0 * share * rows * (d + F) + 2.0 * held * d * F) * count
    return flops, moved
