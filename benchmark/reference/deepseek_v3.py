"""DeepSeek-V3 (``model_type: deepseek_v3``) forward pass in plain
``jax.numpy`` float32, in the published (decompressed) form only.

Written from the published ``config.json`` of ``deepseek-ai/DeepSeek-V3``
(``[c]``), the technical report (DeepSeek-AI 2024, arXiv:2412.19437,
sections 2.1.1 and 2.1.2; DeepSeek-V2, arXiv:2405.04434, section 2.1 for
the latent attention) and the family's own inference code (``[p]``), and
from what is assumed here (``[a]``, each listed under ``assumed`` in the
configuration file). d 7168, H 128, nope 128, rope 64, v 128, c_q 1536,
c_kv 512, E 256 in 8 groups, k 8 as published:

    block, input h [T, d]:  h += Attn(rms(h));  h += FFN(rms(h))     eps 1e-6, no biases  [c]
    logits = rms(h_L) W_head                                          untied               [c]

    Attn(x), per position t:
      c_q = rms(x W_dq)  (d -> c_q);  q = c_q W_uq -> H heads of [q_nope | q_rope]        [p]
      [c_kv | k_r] = x W_dkv  (d -> c_kv + rope);  c_kv <- rms(c_kv)                       [p]
      q_rope <- R_t q_rope;  k_r <- R_t k_r, one key part for all heads                    [p]
      [k_nope_h | v_h] = c_kv W_ukv  (c_kv -> H x (nope + v))                              [p]
      score_h(t, u) = s (q_nope_h . k_nope_h(u) + q_rope_h . k_r(u)),  u <= t
      o_h = sum_u softmax_u(score_h) v_h(u);  out = concat_h(o_h) W_o  (H v -> d)
      s = (nope + rope)^-1/2 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1                   [p]
      R_t: rotation of the pairs (2j, 2j+1), j < rope / 2, by t f'_j                        [a] pair layout
      f_j = theta^(-2j / rope); YaRN: c(b) = rope ln(L / (2 pi b)) / (2 ln theta),
      lo = max(floor(c(beta_fast)), 0), hi = min(ceil(c(beta_slow)), rope - 1),
      r_j = clip((j - lo) / (hi - lo), 0, 1),  f'_j = f_j (1 - r_j) + (f_j / factor) r_j;
      cos and sin carry mscale(factor, mscale) / mscale(factor, mscale_all_dim) = 1         [p]

    FFN of the first first_k_dense_replace blocks: W_down(silu(W_gate x) * W_up x), d -> 18432 -> d
    FFN of the others:
      sg = sigmoid(x W_r) over E, float32;  sg' = sg + b  (for the choice only)            [p]
      n_group groups of E / n_group; a group's score is the sum of its two largest sg';
      the topk_group best groups stay, the others' sg' are masked with -inf;               [a] mask value
      e_1..e_k = the k largest of what stays
      w_j = routed_scaling_factor sg[e_j] / (sum_j sg[e_j] + 1e-20)
      y = sum_j w_j E_{e_j}(x) + S(x),  E and the one shared S SwiGLU d -> 2048 -> d

**The chip's share.** The parameter tree holds experts
``first_local_expert .. + n_routed_experts`` of the router's
``num_router_experts``. The router keeps every output, its groups and its
``k`` choices; every held expert is computed on every token, one at a time
in a loop, weighted by the router (0 where it was not chosen); the absent
experts' terms are left out, exactly as the program leaves them out, and
that partial sum (with ``S(x)``, which every chip computes) goes on to the
next block.

Nothing here is shared with ``trlx_tpu/ops``: no cache, no absorbed
product, no sort, no grouped call, no ``top_k``; the rotation is written on
the pairs, the group limit with sorts. An expert is upcast as it is used,
the dense block's feed-forward runs over its width in blocks, the queries
go ``QUERY_BLOCK`` at a time against every key, the token table is read by
rows and the head runs over the vocabulary in blocks, so beside 9.1 GB of
served bf16 weights no float32 copy of more than one matrix exists and no
``[T, T]`` array a head is formed for more than a block of queries.

Departures: left-padded prompts (padded keys are masked; rotary positions
count a row's real tokens).

``params`` is the backbone's tree as the program names it (``wte``,
``h_<i>/{ln_1, attn/{q_a_proj, q_a_norm, q_b_proj, kv_a_proj, kv_a_norm,
kv_b_proj, o_proj}, ln_2, mlp/{gate_proj, up_proj, down_proj} |
mlp/{router, router_bias, w_gate, w_up, w_down} + shared/{gate_proj,
up_proj, down_proj}}``, ``ln_f``, ``lm_head``); every matrix ``[in, out]``.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np

f32 = lambda a: jnp.asarray(a, jnp.float32)
HEAD_BLOCKS = 4
MLP_BLOCKS = 8
QUERY_BLOCK = 256


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * f32(scale)


def yarn_frequencies(cfg):
    """``f'_j``, j < rope / 2, float32 (the module docstring's formulas)."""
    rope, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    j = np.arange(rope // 2, dtype=np.float32)
    freq = (theta ** (-2 * j / rope)).astype(np.float32)
    group = cfg.get("rope_scaling")
    if group is None:
        return freq
    turn = lambda b: rope * math.log(group["original_max_position_embeddings"] / (2 * math.pi * b)) / (
        2 * math.log(theta))
    lo = max(math.floor(turn(group["beta_fast"])), 0)
    hi = min(math.ceil(turn(group["beta_slow"])), rope - 1)
    r = np.clip((j - lo) / (hi - lo), 0, 1).astype(np.float32)
    return freq * (1 - r) + freq / np.float32(group["factor"]) * r


def score_scale(cfg):
    """``s``: the head's ``1 / sqrt`` and YaRN's ``m^2``."""
    group = cfg.get("rope_scaling")
    m = 1.0 if group is None else 0.1 * group["mscale_all_dim"] * math.log(group["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rotate_pairs(x, positions, freq):
    """``R_t`` on the last axis of ``x`` [B, T, ..., rope]: the pair
    ``(x_2j, x_2j+1)`` turned by ``t f'_j``."""
    angle = positions.astype(jnp.float32)[..., None] * f32(freq)  # [B, T, rope / 2]
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


def attention(x, a, cfg, mask, positions):
    """``Attn`` of one block on the normed input ``x`` [B, T, d]."""
    B, T, _ = x.shape
    H, C = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, Dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, freq = cfg["rms_norm_eps"], yarn_frequencies(cfg)
    c_q = rms_norm(x @ f32(a["q_a_proj"]["kernel"]), a["q_a_norm"]["scale"], eps)
    q = (c_q @ f32(a["q_b_proj"]["kernel"])).reshape(B, T, H, nope + rope)
    q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], positions, freq)], -1)
    down = x @ f32(a["kv_a_proj"]["kernel"])
    c_kv = rms_norm(down[..., :C], a["kv_a_norm"]["scale"], eps)
    k_r = rotate_pairs(down[..., C:], positions, freq)
    kv = (c_kv @ f32(a["kv_b_proj"]["kernel"])).reshape(B, T, H, nope + Dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r[:, :, None, :], (B, T, H, rope))], -1)
    out = masked_attention(q, k, kv[..., nope:], mask, score_scale(cfg))
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
    held = cfg["n_routed_experts"]
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


def positions_of(mask):
    return jnp.clip(jnp.cumsum(mask, axis=-1) - 1, 0, None)


def trunk(params, cfg, input_ids, mask):
    """The hidden states after the final RMSNorm, [B, T, d] float32."""
    eps = cfg["rms_norm_eps"]
    _, held, first = expert_counts(cfg)
    positions = positions_of(mask)
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"]["embedding"][input_ids])
        for i in range(cfg["num_hidden_layers"]):
            blk = params[f"h_{i}"]
            x = x + attention(rms_norm(x, blk["ln_1"]["scale"], eps), blk["attn"], cfg, mask, positions)
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
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    c_q, C = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, Dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    F = cfg["moe_intermediate_size"]
    return {
        "attn_matrices": d * c_q + c_q * H * (nope + rope) + d * (C + rope) + C * H * (nope + Dv) + H * Dv * d,
        "attn_norms": c_q + C,
        "dense": 3 * d * cfg["intermediate_size"],
        "expert": 3 * d * F,
        "shared": 3 * d * F * cfg["n_shared_experts"],
    }


def shape(cfg):
    """A block holds its attention (the two low-rank pairs, the
    decompression ``W_ukv``, ``W_o`` and the two inner norms), two norm
    vectors and its feed-forward: a dense block the SwiGLU, a routed block
    the router over the published expert count with its selection bias, the
    shared expert and the experts **held here**. A token is multiplied with
    the attention's matrices and the dense SwiGLU, or the router, the shared
    expert and as much of an expert as it is expected to choose here, ``k x
    held / E`` of one (half an expert at 16 of 256: even routing). A decode
    step reads everything but the routed experts whatever it routes, and of
    those ``per_token`` = ``k``: one token's choices where they all lie
    here, which a step of this cell (64 x 8 choices over 256) passes by far
    (``moe_ep16_gmm_decode_count`` counts the experts the program touched).
    Attention costs ``2 (nope + rope) + 2 v`` FLOPs a head and pair of
    positions in the published form, so ``attn_dim`` is ``H (nope + rope +
    v) / 2``; a position writes one latent row, ``c_kv + rope`` values, and
    a step reads one a cached position."""
    d, V, H = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    nope, rope, Dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    E, held, _ = expert_counts(cfg)
    k = cfg["num_experts_per_tok"]
    n = sizes(cfg)
    attn = n["attn_matrices"] + n["attn_norms"]
    if (H * (nope + rope + Dv)) % 2 or (k * held * n["expert"]) % E:
        raise ValueError("attn_dim or the expected share of an expert a token is no whole number")
    common = {"attn_dim": H * (nope + rope + Dv) // 2, "kv_values": cfg["kv_lora_rank"] + rope}
    dense = {
        "params": attn + n["dense"] + 2 * d,
        "matmul_params": n["attn_matrices"] + n["dense"],
        "read_params": attn + n["dense"] + 2 * d,
        **common,
    }
    fixed = attn + n["shared"] + d * E + E + 2 * d
    routed = {
        "params": fixed + held * n["expert"],
        "matmul_params": n["attn_matrices"] + n["shared"] + d * E + k * held * n["expert"] // E,
        "read_params": fixed,
        "routed": {"expert_params": n["expert"], "per_token": k},
        **common,
    }
    L, first = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return {
        "embed_params": V * d,
        "layers": [dense] * first + [routed] * (L - first),
        "final": {"params": d + d * V, "matmul_params": d * V, "read_params": d + d * V},
    }


def check_config(cfg):
    """What the program's ``DeepseekV3Config`` refuses by name, and what a
    file of the cut must keep consistent."""
    for key, want in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"), ("norm_topk_prob", True),
                      ("attention_bias", False), ("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("moe_layer_freq", 1), ("ep_size", 1), ("num_nextn_predict_layers", 0)):
        if cfg.get(key, want) != want:
            raise ValueError(f"the program's deepseek_v3 family builds no {key}={cfg[key]!r}")
    if cfg.get("rope_scaling") is not None and cfg["rope_scaling"].get("type") != "yarn":
        raise ValueError("the program's deepseek_v3 family builds no rope_scaling but yarn")
    group = cfg.get("rope_scaling") or {}
    if group.get("factor", 1) > 1 and group.get("mscale", 1) != group.get("mscale_all_dim", 0):
        raise ValueError("the program's deepseek_v3 family builds no rope_scaling whose mscale differs from its mscale_all_dim")
    if cfg.get("q_lora_rank") is None:
        raise ValueError("the program's deepseek_v3 family builds no q_lora_rank=None")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("num_key_value_heads differs from num_attention_heads: every head reads the one latent")
    if not 0 <= cfg["first_k_dense_replace"] <= cfg["num_hidden_layers"]:
        raise ValueError("first_k_dense_replace is not among num_hidden_layers")
    E, held, first = expert_counts(cfg)
    if held > E or not 0 <= first <= E - held:
        raise ValueError(f"n_routed_experts={held} from {first} on are not among the router's {E}")
    if E % cfg["n_group"] or not 0 < cfg["topk_group"] <= cfg["n_group"]:
        raise ValueError(f"n_group={cfg['n_group']} / topk_group={cfg['topk_group']} do not divide the router's {E}")
    if cfg.get("run", {}).get("kv_cache_dtype", "bfloat16") != "bfloat16":
        raise ValueError("the program's deepseek_v3 family builds no int8 latent")


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


def _attention_sizes(record):
    cf = record["cell"]["config_file"]
    return (cf["num_attention_heads"], cf["kv_lora_rank"], cf["qk_nope_head_dim"],
            cf["qk_rope_head_dim"], cf["v_head_dim"])


def mla_absorbed_read_count(record, ops):
    """(FLOPs, bytes) of the decode step's read of the latent pool, counted
    once an execution of the operation that forms the scores (``f32[slots,
    capacity, H]`` by its name: the rows on the left; the values' product
    beside it in the pattern adds its time only, and the softmax's
    statistics between the two, ``f32[slots, H]``, are left out: a name
    that any fusion of that result shares). The published mathematics requires, a sequence and cached
    position, the scores and the values of ``H`` heads in whichever form
    costs less: absorbed, ``2 (c + rope) + 2 c`` FLOPs a head over one row
    of ``c + rope`` values read once. Positions: the traced slice's mean
    batch x its mean context (the driver's ``decode``), not the pool's
    capacity, which the program reads whole."""
    H, C, nope, rope, Dv = _attention_sizes(record)
    d = record["decode"]
    n = sum(count for shape, count in _calls(ops) if len(shape) == 3 and shape[2] == H and shape[1] > H)
    positions = d["batch"] * (d["mean_context"] + 1) * n
    return 2.0 * H * (2 * C + rope) * positions, 2.0 * (C + rope) * positions


def mla_prefill_attn_count(record, ops):
    """(FLOPs, bytes) of an admission's decompress-and-attend, counted once
    an execution of the operation that forms a layer's scores (``f32[rows,
    H, columns]`` by its name; the decompression ``bf16[rows, view, H, nope
    + v]``, which gives ``view``, the slices of its result and the values'
    product beside it in the pattern add their time only). What the
    published mathematics requires of such a call: the positions it can see
    through ``W_ukv`` (``2 c H (nope + v)`` FLOPs each) and the causal
    attention of its columns over them (``2 (nope + rope) + 2 v`` a head
    and pair). A whole forward (``columns == view``) sees its own columns
    and needs half the pairs. A chunk sees ``(c + 1) x columns`` positions
    by its place ``c``, which a name does not show: counted at ``(view +
    columns) / 2`` positions and ``columns x view / 2`` pairs, the mean over
    a group that runs every chunk and under the mean of one that skips its
    first (the later chunks see more), so the share is never over what ran.
    Bytes: the latent rows and the queries read, the result written, bf16."""
    H, C, nope, rope, Dv = _attention_sizes(record)
    calls = _calls(ops)
    views = [s[1] for s, _ in calls if len(s) == 4 and s[2] == H and s[3] == nope + Dv]
    flops = moved = 0.0
    for s, count in calls:
        if len(s) != 3 or s[1] != H:
            continue
        rows, cols = s[0], s[2]
        view = max(views + [cols])
        seen = view if cols == view else (view + cols) / 2
        flops += count * rows * (2.0 * C * H * (nope + Dv) * seen + (2.0 * (nope + rope) + 2.0 * Dv) * H * cols * view / 2)
        moved += count * 2.0 * rows * (seen * (C + rope) + cols * H * (nope + rope + Dv))
    return flops, moved


def _gmm_calls(ops):
    return [(s[0], s[1], count) for s, count in _calls(ops) if len(s) == 2]


def moe_ep16_gmm_decode_count(record, ops):
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


def moe_ep16_gmm_prefill_count(record, ops):
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
    for rows, width, count in _gmm_calls(ops):
        flops += 2.0 * share * rows * d * F * count
        moved += (2.0 * share * rows * (d + F) + 2.0 * held * d * F) * count
    return flops, moved
