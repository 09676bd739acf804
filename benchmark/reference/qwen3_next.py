"""Qwen3-Next (``model_type: qwen3_next``) forward pass in plain
``jax.numpy`` float32.

Written from the published ``config.json`` of
``Qwen/Qwen3-Next-80B-A3B-Instruct`` and the family's ``transformers``
module; the rule is Yang, Kautz & Hatamizadeh, "Gated Delta Networks",
arXiv:2412.06464. ``h`` is ``[T, d]``, d 2048 as published:

    norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)        zero-centred, eps 1e-6
    h += mix_i(norm(h));  h += moe(norm(h))              no biases anywhere
    logits = norm(h_L) W_head                             untied

``mix_i`` is attention where ``(i + 1) % full_attention_interval == 0``, else
the gated delta rule.

Gated delta rule, ``linear_num_key_heads`` key heads and
``linear_num_value_heads`` value heads of 128:

    [q | k | v | z] = x W_qkvz;  [b | a] = x W_ba
    [q | k | v] <- silu(conv([q | k | v]))      depthwise, causal, width 4, no bias; z is not convolved
    q <- q / sqrt(sum q^2 + 1e-6) * Dk^-1/2,  k <- k / sqrt(sum k^2 + 1e-6)    a head;
    value head h reads key head h // (Hv / Hk)
    beta_t = sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t + dt_bias)          a value head
    S <- exp(g_t) S;  u = S^T k_t;  S <- S + k_t (beta_t (v_t - u))^T;  o_t = S^T q_t
    o_t <- o_t / sqrt(mean o_t^2 + eps) * w_n * silu(z_t)                      a head
    out = concat_h(o) W_o

- **one state update a position**, a ``lax.scan`` over the positions with
the state ``[Dk, Dv]`` a value head: never the chunked form.

Attention, ``num_attention_heads`` query over ``num_key_value_heads`` KV heads
of ``head_dim``:

    [q | gate] = x W_q  split a head;  k = x W_k;  v = x W_v
    q <- norm(q), k <- norm(k) a head; rotary on the first
    partial_rotary_factor x head_dim of a head, halves against each other,
    theta = rope_theta, no scaling
    o = softmax(q k^T head_dim^-1/2) v  (causal);  o <- o * sigmoid(gate);  out = o W_o

MoE, every block:

    p = softmax(x W_r) over num_router_experts;  e_1..e_k the k largest
    w_j = p[e_j] / sum_j p[e_j]                  (norm_topk_prob)
    y = sum_j w_j E_{e_j}(x) + sigmoid(x w_s) S(x),   E and the one shared S SwiGLU

**The chip's share.** The parameter tree holds experts ``first_local_expert
.. + num_experts`` of the router's ``num_router_experts``. The router keeps
every output and its ``k`` choices; every held expert is computed on every
token, one at a time in a loop, weighted by the router (0 where it was not
chosen); the absent experts' terms are left out, exactly as the program
leaves them out, and that partial sum (with the gated ``S(x)``, which every
chip computes) goes on to the next block.

Nothing here is shared with ``trlx_tpu``: no cache, no chunk, no solve, no
sort, no grouped call. An expert is upcast as it is used, the token table is
read by rows and the head runs over the vocabulary in blocks.

Departures from the published files, each also under ``assumed`` in the
configuration file: the fused projections' columns are ``[q | k | v | z]``
and ``[b | a]`` whole (the published module interleaves them a key head;
with seeded weights any fixed order is the same function); left-padded
prompts (padded keys are masked, rotary positions count a row's real
tokens, a padded position feeds zeros to the mixer, so the state and the
convolution's window are zero when a row's first token arrives, as they are
for an unpadded sequence); no multi-token-prediction module (``config.json``
has no key for it).

``params`` is the backbone's tree as the program names it (``wte``,
``h_<i>/{ln_1, linear_attn/{in_proj_qkvz, in_proj_ba, conv_weight [K, C],
dt_bias, A_log, norm, out_proj} | attn/{q_proj, k_proj, v_proj, q_norm,
k_norm, o_proj}, ln_2, mlp/{router, w_gate, w_up, w_down}, shared/{gate_proj,
up_proj, down_proj, gate}}``, ``ln_f``, ``lm_head``); every matrix ``[in,
out]``.
"""

import re

import jax
import jax.numpy as jnp

f32 = lambda a: jnp.asarray(a, jnp.float32)
HEAD_BLOCKS = 4
FULL, LINEAR = "full_attention", "linear_attention"


def rms_norm(x, offset, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + f32(offset))


def layer_kinds(cfg):
    if cfg.get("layer_types"):
        return list(cfg["layer_types"])
    every = cfg["full_attention_interval"]
    return [FULL if (i + 1) % every == 0 else LINEAR for i in range(cfg["num_hidden_layers"])]


def l2(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def gated_delta_net(u, p, cfg, mask):
    """The mixer of one ``linear_attention`` layer on the normed input ``u``
    [B, T, d]."""
    B, T, _ = u.shape
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    Dk, Dv, K = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]
    real = mask.astype(jnp.float32)[..., None]
    proj = (u * real) @ f32(p["in_proj_qkvz"]["kernel"])
    width = 2 * Hk * Dk + Hv * Dv
    qkv, z = proj[..., :width], proj[..., width:].reshape(B, T, Hv, Dv)
    ba = (u * real) @ f32(p["in_proj_ba"]["kernel"])
    b, a = ba[..., :Hv], ba[..., Hv:]
    w = f32(p["conv_weight"])  # [K, C]; w[K - 1] multiplies the position itself
    padded = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j : j + T] * w[j] for j in range(K))) * real
    q = l2(qkv[..., : Hk * Dk].reshape(B, T, Hk, Dk)) * Dk**-0.5
    k = l2(qkv[..., Hk * Dk : 2 * Hk * Dk].reshape(B, T, Hk, Dk))
    v = qkv[..., 2 * Hk * Dk :].reshape(B, T, Hv, Dv)
    q, k = jnp.repeat(q, Hv // Hk, axis=2), jnp.repeat(k, Hv // Hk, axis=2)
    beta = jax.nn.sigmoid(b)  # [B, T, Hv]
    g = -jnp.exp(f32(p["A_log"])) * jax.nn.softplus(a + f32(p["dt_bias"]))

    def position(S, xs):
        q_t, k_t, v_t, g_t, beta_t = xs  # [B, Hv, D*], [B, Hv]
        S = S * jnp.exp(g_t)[..., None, None]
        held = (S * k_t[..., None]).sum(-2)  # S^T k
        S = S + k_t[..., None] * (beta_t[..., None] * (v_t - held))[..., None, :]
        return S, (S * q_t[..., None]).sum(-2)

    by_position = lambda x: jnp.moveaxis(x, 1, 0)
    _, o = jax.lax.scan(
        position, jnp.zeros((B, Hv, Dk, Dv), jnp.float32),
        tuple(by_position(x) for x in (q, k, v, g, beta)),
    )
    o = jnp.moveaxis(o, 0, 1)  # [B, T, Hv, Dv]
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + cfg["rms_norm_eps"]) * f32(p["norm"])
    o = o * jax.nn.silu(z)
    return o.reshape(B, T, Hv * Dv) @ f32(p["out_proj"]["kernel"])


def rotate_halves(x, positions, rot, theta):
    """The first ``rot`` of a head's values, halves against each other."""
    inv_freq = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [B, T, rot / 2]
    sin, cos = jnp.sin(angles)[:, :, None, :], jnp.cos(angles)[:, :, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2 : rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def gated_attention(u, a, cfg, mask, positions):
    B, T, _ = u.shape
    H, H_kv, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, rot = cfg["rms_norm_eps"], int(Dh * cfg["partial_rotary_factor"])
    q_gate = (u @ f32(a["q_proj"]["kernel"])).reshape(B, T, H, 2 * Dh)
    q, gate = rms_norm(q_gate[..., :Dh], a["q_norm"]["scale"], eps), q_gate[..., Dh:]
    k = rms_norm((u @ f32(a["k_proj"]["kernel"])).reshape(B, T, H_kv, Dh), a["k_norm"]["scale"], eps)
    v = (u @ f32(a["v_proj"]["kernel"])).reshape(B, T, H_kv, Dh)
    q = rotate_halves(q, positions, rot, cfg["rope_theta"])
    k = rotate_halves(k, positions, rot, cfg["rope_theta"])
    G = H // H_kv
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * Dh**-0.5
    allowed = (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])[None, None] & (
        mask[:, None, None, :] > 0
    )
    weights = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", weights, v) * jax.nn.sigmoid(gate)
    return o.reshape(B, T, H * Dh) @ f32(a["o_proj"]["kernel"])


def expert_counts(cfg):
    """(the router's width, experts held here, the first held)."""
    held = cfg["num_experts"]
    return cfg.get("num_router_experts") or held, held, cfg.get("first_local_expert", 0)


def router_weights(h, router, k, norm_topk=True):
    """[.., E] combine weights: the softmax over all experts at the ``k``
    largest, divided by their sum, 0 elsewhere."""
    probs = jax.nn.softmax(h @ f32(router), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if norm_topk:
        top = top / top.sum(-1, keepdims=True)
    return (jax.nn.one_hot(idx, probs.shape[-1], dtype=top.dtype) * top[..., None]).sum(-2)


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


def shared_expert(h, p):
    gate, up = h @ f32(p["gate_proj"]["kernel"]), h @ f32(p["up_proj"]["kernel"])
    y = (jax.nn.silu(gate) * up) @ f32(p["down_proj"]["kernel"])
    return jax.nn.sigmoid((h * f32(p["gate"])).sum(-1, keepdims=True)) * y


def trunk(params, cfg, input_ids, mask):
    """The hidden states after the final norm, [B, T, d] float32."""
    eps = cfg["rms_norm_eps"]
    _, held, first = expert_counts(cfg)
    positions = jnp.clip(jnp.cumsum(mask, axis=-1) - 1, 0, None)
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"]["embedding"][input_ids])
        for i, kind in enumerate(layer_kinds(cfg)):
            blk = params[f"h_{i}"]
            h = rms_norm(x, blk["ln_1"]["scale"], eps)
            if kind == FULL:
                x = x + gated_attention(h, blk["attn"], cfg, mask, positions)
            else:
                x = x + gated_delta_net(h, blk["linear_attn"], cfg, mask)
            h = rms_norm(x, blk["ln_2"]["scale"], eps)
            w = router_weights(h, blk["mlp"]["router"], cfg["num_experts_per_tok"],
                               cfg.get("norm_topk_prob", True))
            x = x + held_experts(h, blk["mlp"], w[..., first : first + held]) + shared_expert(h, blk["shared"])
        return rms_norm(x, params["ln_f"]["scale"], eps)


def head(params, cfg, hidden):
    """Logits [..., V] of hidden states [..., d]: the untied head, over the
    vocabulary in blocks."""
    kernel = params["lm_head"]["kernel"]
    V = kernel.shape[1]
    step = -(-V // HEAD_BLOCKS)
    with jax.default_matmul_precision("highest"):
        parts = [hidden @ f32(kernel[:, at : at + step]) for at in range(0, V, step)]
    return jnp.concatenate(parts, axis=-1)


def forward(params, cfg, input_ids, mask):
    """Logits [B, T, V] in float32: the head on every position of the trunk."""
    return head(params, cfg, trunk(params, cfg, input_ids, mask))


def sizes(cfg):
    """The counts the shape rule and the count functions share."""
    d = cfg["hidden_size"]
    H, H_kv, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    Dk, Dv, K = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]
    channels = 2 * Hk * Dk + Hv * Dv
    return {
        "linear_matrices": d * (channels + Hv * Dv) + d * 2 * Hv + Hv * Dv * d,
        "linear_other": K * channels + 2 * Hv + Dv,
        "state": Hv * Dk * Dv, "tail": (K - 1) * channels,
        "attn_matrices": d * H * 2 * Dh + 2 * d * H_kv * Dh + H * Dh * d,
        "attn_other": 2 * Dh,
        "expert": 3 * d * cfg["moe_intermediate_size"],
        "shared": 3 * d * cfg["shared_expert_intermediate_size"] + d,
    }


def shape(cfg):
    """A block holds its mixer (a ``linear_attention`` layer: the two fused
    projections, the convolution's taps, ``dt_bias``, ``A_log``, the gated
    norm and ``W_o``; a ``full_attention`` layer: ``W_q`` with the gate's
    half, ``W_k``, ``W_v``, ``W_o`` and the two head norms), the gated shared
    expert, the router over the published expert count, two norm vectors and
    the experts **held here**. A token is multiplied with the mixer's
    matrices, the shared expert (its gate's vector too), the router and as
    much of an expert as it is expected to choose here, ``k x held / E`` of
    one (2.5 experts at 128 of 512: even routing). A decode step reads
    everything but the routed experts whatever it routes, and of those
    ``per_token`` = ``k``: one token's choices where they all lie here, which
    a step of this cell (128 x 10 choices over 512) passes by far
    (``moe_ep4_gmm_decode_count`` counts the experts the program touched). A
    ``linear_attention`` block caches no keys and carries its state and its
    convolution tail, read and written once a step; a ``full_attention``
    block writes ``2 H_kv head_dim`` values a position."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    E, held, _ = expert_counts(cfg)
    k = cfg["num_experts_per_tok"]
    n = sizes(cfg)
    if (k * held * n["expert"]) % E:
        raise ValueError("the expected share of an expert a token is no whole number")
    mixers = {
        LINEAR: {"matrices": n["linear_matrices"], "other": n["linear_other"], "attn_dim": 0,
                 "kv_values": 0, "state_values": n["state"] + n["tail"]},
        FULL: {"matrices": n["attn_matrices"], "other": n["attn_other"],
               "attn_dim": cfg["num_attention_heads"] * cfg["head_dim"],
               "kv_values": 2 * cfg["num_key_value_heads"] * cfg["head_dim"]},
    }
    layers = []
    for kind in layer_kinds(cfg):
        m = mixers[kind]
        fixed = m["matrices"] + m["other"] + n["shared"] + d * E + 2 * d
        layer = {
            "params": fixed + held * n["expert"],
            "matmul_params": m["matrices"] + n["shared"] + d * E + k * held * n["expert"] // E,
            "read_params": fixed,
            "routed": {"expert_params": n["expert"], "per_token": k},
            "attn_dim": m["attn_dim"],
            "kv_values": m["kv_values"],
        }
        if "state_values" in m:
            layer["state_values"] = m["state_values"]
        layers.append(layer)
    return {
        "embed_params": V * d,
        "layers": layers,
        "final": {"params": d + d * V, "matmul_params": d * V, "read_params": d + d * V},
    }


def check_config(cfg):
    """What the program's ``Qwen3NextConfig`` refuses by name, and what a
    file of the cut must keep consistent."""
    for key, want in (("rope_scaling", None), ("decoder_sparse_step", 1), ("attention_bias", False),
                      ("use_sliding_window", False), ("tie_word_embeddings", False),
                      ("hidden_act", "silu")):
        if cfg.get(key, want) != want:
            raise ValueError(f"the program's qwen3_next family builds no {key}={cfg[key]!r}")
    if cfg.get("mlp_only_layers"):
        raise ValueError(f"the program's qwen3_next family builds no mlp_only_layers={cfg['mlp_only_layers']!r}")
    if len(layer_kinds(cfg)) != cfg["num_hidden_layers"] or set(layer_kinds(cfg)) - {FULL, LINEAR}:
        raise ValueError("layer_types does not name num_hidden_layers layers of the two kinds")
    if cfg["num_attention_heads"] % cfg["num_key_value_heads"]:
        raise ValueError("num_attention_heads does not divide over num_key_value_heads")
    if cfg["linear_num_value_heads"] % cfg["linear_num_key_heads"]:
        raise ValueError("linear_num_value_heads does not divide over linear_num_key_heads")
    E, held, first = expert_counts(cfg)
    if held > E or not 0 <= first <= E - held:
        raise ValueError(f"num_experts={held} from {first} on are not among the router's {E}")
    run = cfg.get("run", {})
    if "state_dtype" in run and run["state_dtype"] != cfg.get("state_dtype", "float32"):
        raise ValueError(
            f"state_dtype is {cfg.get('state_dtype', 'float32')!r} for the program and "
            f"{run['state_dtype']!r} under run (what a step's bytes are counted at)"
        )
    if run.get("kv_cache_dtype", "bfloat16") != "bfloat16":
        raise ValueError("the program's qwen3_next family builds no int8 cache beside state layers")


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


def _state_sizes(record):
    cf = record["cell"]["config_file"]
    return (cf["linear_num_key_heads"], cf["linear_num_value_heads"],
            cf["linear_key_head_dim"], cf["linear_value_head_dim"])


def gdn_step_count(record, ops):
    """(FLOPs, bytes) of the decode step's passes over the layers' states,
    counted once an execution of the operation that reads a layer's state
    out under ``k`` and ``q`` (``f32[slots, Hv, Dv]`` by its name: one a
    linear layer a step; the pass that writes the new states, which the
    compiler may join over the layers of a step into one operation, adds
    its time only). The rule requires, a layer and step, one read and one
    write of the ``[slots, Hv, Dk, Dv]`` state at the configuration's
    ``state_dtype`` and, a state value, a decay multiply and a multiply-add
    each for ``S^T k``, the rank-one write and ``S^T q``: 7 FLOPs, far under
    the bytes' time. A program that passes over the state three times reads
    a third."""
    from benchmark.arithmetic import DTYPE_BYTES

    _, Hv, Dk, Dv = _state_sizes(record)
    slots = record["cell"]["traffic_file"]["slots"]
    width = DTYPE_BYTES[record["cell"]["config_file"]["run"]["state_dtype"]]
    n = sum(count for shape, count in _calls(ops) if shape == (slots, Hv, Dv))
    values = slots * Hv * Dk * Dv
    return 7.0 * values * n, 2.0 * values * width * n


CHUNK_OUTPUT = "convolution_multiply_fusion"


def gdn_chunk_prefill_count(record, ops):
    """(FLOPs, bytes) of the chunked rule in an admission's forwards. The
    pattern takes every operation of the rule's chunk loop (results ``[rows,
    Hv, ...]``: the products, the solve's levels and the passes between
    them; their time is the rule's); the work is counted once a chunk, at
    the operation that forms a chunk's outputs, ``CHUNK_OUTPUT f32[rows, Hv,
    L, Dv]`` (``L`` read off its name). A chunk of ``L`` columns requires, a
    row and value head: ``4 L^2 Dk`` for the two score matrices (``k k^T``
    and ``q k^T``), ``L^3`` for the unit-triangular solve, ``2 L^2 (Dk +
    Dv)`` for ``W`` and ``U``, ``3 x 2 L Dk Dv`` for the three products with
    the state (``W S``, ``q S`` and the state's update) and ``2 L^2 Dv`` for
    the read-out inside the chunk. Bytes: ``q``, ``k`` of the key heads and
    ``v`` read, the outputs written, bf16, and the state read and written
    in float32."""
    Hk, Hv, Dk, Dv = _state_sizes(record)
    flops = moved = 0.0
    for name, op in ops.items():
        m = re.match(CHUNK_OUTPUT + r" f32\[(\d+),(\d+),(\d+),(\d+)\]$", name)
        if not m or (int(m.group(2)), int(m.group(4))) != (Hv, Dv):
            continue
        rows, L = int(m.group(1)), int(m.group(3))
        a_head = 4 * L * L * Dk + L**3 + 2 * L * L * (Dk + Dv) + 6 * L * Dk * Dv + 2 * L * L * Dv
        flops += op["count"] * rows * Hv * a_head
        moved += op["count"] * rows * (2.0 * L * (2 * Hk * Dk + 2 * Hv * Dv) + 2 * 4.0 * Hv * Dk * Dv)
    return flops, moved


def _gmm_calls(ops):
    return [(s[0], s[1], count) for s, count in _calls(ops) if len(s) == 2]


def moe_ep4_gmm_decode_count(record, ops):
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


def moe_ep4_gmm_prefill_count(record, ops):
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
