"""Nemotron-H (``nemotron_h``: Nemotron 3 Super 120B-A12B) forward pass in
plain ``jax.numpy`` float32.

Written from the published ``config.json`` keys and the family's published
module (Mamba-2 is Dao & Gu 2024, section 6's recurrence; the router is
DeepSeek-V3's, section 2.1.2). A reading that is this repository's own is
marked ``[a]`` and listed under ``assumed`` in the configuration file.

**A layer is one sublayer**, named by ``hybrid_override_pattern``:

    h <- h + mix_i(rms_i(h))      ``M`` a Mamba-2 mixer, ``E`` the routed MLP, ``*`` attention
    logits = rms_f(h) W_head      untied; RMSNorm eps ``layer_norm_epsilon``, weight x normed, float32 [a]

``M`` on the normed input ``u``, zero at padded positions, ``H`` =
``mamba_num_heads`` heads of ``P`` = ``mamba_head_dim``, a state of ``N`` =
``ssm_state_size``, ``G`` = ``n_groups`` groups of ``B`` and ``C``:

    [z | xBC | dt] = u W_in                          H P | H P + 2 G N | H; no bias
    xBC = silu(causal_conv_K(xBC) + b_conv)          depthwise, K = conv_kernel; zero at padded positions again
    x [H, P],  B [G, N],  C [G, N]                   head h reads group g = h // (H / G)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)    time_step_min/max/floor are the initialiser's, no clamp here [a]
    S_h <- exp(dt_h A_h) S_h + dt_h x_h B_g^T;  y_h = S_h C_g + D_h x_h      S [P, N] a head
    out = ( rms_group(y * silu(z)) * w ) W_out       the norm over a group's H P / G channels at a time [a]

**one state update a position**, a ``lax.scan`` over the positions, no chunks
and no decay matrix. ``*``: ``num_attention_heads`` query heads over
``num_key_value_heads`` KV heads of ``head_dim`` (query head ``h`` reads KV
head ``h // (H / H_kv)``), no bias, **no positions** [a] (``rope_theta`` and
``partial_rotary_factor`` are published and read by no layer),
``softmax(q k^T head_dim^-1/2)`` in float32, causal. ``E`` on the normed
input ``u``:

    s = sigmoid(u W_r)  over num_router_experts;  choice: the k largest of s + bias     the router reads u [a];
                                                                                       one group (n_group 1): no limit
    w_j = routed_scaling_factor * s[e_j] / (sum_j s[e_j] + 1e-20)
    l = u W_dn                                       d -> moe_latent_size; no bias, norm or activation [a]
    r = sum_j w_j W2[e_j] act(W1[e_j] l)             act = relu(.)^2 (``mlp_hidden_act`` relu2); no gate
    y = r W_up + S2 act(S1 u)                        back to d; the shared expert reads u itself

**The chip's share.** The parameter tree holds experts ``first_local_expert
.. + n_routed_experts`` of the router's ``num_router_experts``. Every held
expert is computed on every token, one at a time in a loop, weighted by the
router (0 where it was not chosen); the absent experts' terms are left out,
exactly as the program leaves them out, and that partial sum is what goes up
through ``W_up`` and on to the next layer. No sort, no grouped call, no cache;
a matrix is upcast as it is used and the head runs over the vocabulary in
blocks, so beside 9.3 GB of served bf16 weights no float32 copy of more than
one matrix exists.

Departures: left-padded prompts (padded keys are masked; a padded position
feeds zeros to the mixer, so state and convolution window are zero when a
row's first token arrives, as they are for an unpadded sequence); the
multi-token-prediction module (``num_nextn_predict_layers``) is not part of
the forward pass and is left out.

``params`` is the backbone's tree as the program names it (``wte``,
``h_<i>/{ln_1, mamba/{in_proj, conv_weight [K, C], conv_bias, dt_bias, A_log,
D, norm, out_proj} | attn/{q_proj, k_proj, v_proj, o_proj} | mlp/{router,
router_bias, latent_down, latent_up, w_up, w_down} + shared/{up_proj,
down_proj}}``, ``ln_f``, ``lm_head``); every matrix is ``[in, out]``.
"""

import re

import jax
import jax.numpy as jnp

f32 = lambda a: jnp.asarray(a, jnp.float32)
HEAD_BLOCKS = 8
MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
relu2 = lambda a: jnp.square(jax.nn.relu(a))


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * f32(scale)


def grouped_attention(q, k, v, mask, scale):
    """q [B, T, H, Dh] over k, v [B, T, H_kv, Dh]; causal, padded keys out."""
    B, T, H, Dh = q.shape
    G = H // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    allowed = (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])[None, None] & (
        mask[:, None, None, :] > 0
    )
    weights = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def mamba(u, p, cfg, mask):
    """The mixer of one ``M`` layer on the normed input ``u`` [B, T, D]."""
    B, T, _ = u.shape
    H, P, N = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"]
    G, K = cfg["n_groups"], cfg["conv_kernel"]
    inner, width = H * P, H * P + 2 * G * N
    real = mask.astype(jnp.float32)[..., None]
    proj = (u * real) @ f32(p["in_proj"]["kernel"])
    z, xBC, dt = proj[..., :inner], proj[..., inner : inner + width], proj[..., inner + width :]
    w = f32(p["conv_weight"])  # [K, C]; w[K - 1] multiplies the position itself
    padded = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, k : k + T] * w[k] for k in range(K))
    if "conv_bias" in p:
        conv = conv + f32(p["conv_bias"])
    xBC = jax.nn.silu(conv) * real
    x = xBC[..., :inner].reshape(B, T, H, P)
    # a group's B and C, repeated for the group's H / G consecutive heads
    per_head = lambda a: jnp.repeat(a.reshape(B, T, G, N), H // G, axis=2)
    Bm, Cm = per_head(xBC[..., inner : inner + G * N]), per_head(xBC[..., inner + G * N :])
    dt = jax.nn.softplus(dt + f32(p["dt_bias"]))  # [B, T, H]
    A = -jnp.exp(f32(p["A_log"]))

    def position(S, xs):
        x_t, B_t, C_t, dt_t = xs  # [B, H, P], [B, H, N], [B, H, N], [B, H]
        S = S * jnp.exp(dt_t * A)[..., None, None] + (
            (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :]
        )
        return S, (S * C_t[:, :, None, :]).sum(-1)

    by_position = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(
        position, jnp.zeros((B, H, P, N), jnp.float32),
        (by_position(x), by_position(Bm), by_position(Cm), by_position(dt)),
    )
    y = jnp.moveaxis(y, 0, 1) + f32(p["D"])[None, None, :, None] * x
    y = y.reshape(B, T, inner) * jax.nn.silu(z)
    # the gated norm, a group's channels at a time [a]
    y = y.reshape(B, T, G, inner // G)
    y = (y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + cfg["layer_norm_epsilon"])).reshape(B, T, inner)
    return (y * f32(p["norm"])) @ f32(p["out_proj"]["kernel"])


def router_weights(u, mlp, cfg):
    """[.., E] combine weights: ``routed_scaling_factor`` times the chosen
    sigmoid scores over their sum at the ``num_experts_per_tok`` experts
    with the largest biased scores, 0 elsewhere. ``n_group`` = ``topk_group``
    = 1: every expert is in the one group that stays."""
    scores = jax.nn.sigmoid(u @ f32(mlp["router"]))
    _, idx = jax.lax.top_k(scores + f32(mlp["router_bias"]), cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    w = cfg["routed_scaling_factor"] * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    return (jax.nn.one_hot(idx, scores.shape[-1], dtype=w.dtype) * w[..., None]).sum(-2)


def held_experts(latent, mlp, weights):
    """Every held expert on every token's latent, one at a time;
    ``weights`` [.., held]."""
    def one(acc, xs):
        w_up, w_down, w = xs
        return acc + (relu2(latent @ f32(w_up)) @ f32(w_down)) * w[..., None], None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(latent), (mlp["w_up"], mlp["w_down"], jnp.moveaxis(weights, -1, 0))
    )
    return out


def routed_mlp(u, blk, cfg):
    """An ``E`` layer on the normed input: the held experts' part of the
    routed sum, taken back up, plus the shared expert."""
    _, held, first = expert_counts(cfg)
    mlp, shared = blk["mlp"], blk["shared"]
    w = router_weights(u, mlp, cfg)
    latent = u @ f32(mlp["latent_down"]["kernel"])
    routed = held_experts(latent, mlp, w[..., first : first + held])
    return routed @ f32(mlp["latent_up"]["kernel"]) + (
        relu2(u @ f32(shared["up_proj"]["kernel"])) @ f32(shared["down_proj"]["kernel"])
    )


def expert_counts(cfg):
    """(the router's width, experts held here, the first held)."""
    held = cfg["n_routed_experts"]
    return cfg.get("num_router_experts") or held, held, cfg.get("first_local_expert", 0)


def trunk(params, cfg, input_ids, mask):
    """The hidden states after the final RMSNorm, [B, T, D] float32."""
    eps = cfg["layer_norm_epsilon"]
    H, H_kv, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"]["embedding"][input_ids])
        B, T, D = x.shape
        for i, kind in enumerate(cfg["hybrid_override_pattern"]):
            blk = params[f"h_{i}"]
            u = rms_norm(x, blk["ln_1"]["scale"], eps)
            if kind == ATTENTION:
                a = blk["attn"]
                q = (u @ f32(a["q_proj"]["kernel"])).reshape(B, T, H, Dh)
                k = (u @ f32(a["k_proj"]["kernel"])).reshape(B, T, H_kv, Dh)
                v = (u @ f32(a["v_proj"]["kernel"])).reshape(B, T, H_kv, Dh)
                mixed = grouped_attention(q, k, v, mask, Dh ** -0.5)
                mixed = mixed.reshape(B, T, H * Dh) @ f32(a["o_proj"]["kernel"])
            elif kind == MAMBA:
                mixed = mamba(u, blk["mamba"], cfg, mask)
            else:
                mixed = routed_mlp(u, blk, cfg)
            x = x + mixed
        return rms_norm(x, params["ln_f"]["scale"], eps)


def head(params, cfg, hidden):
    """Logits [..., V] of hidden states [..., D]: the untied head, over the
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
    """The parameter counts the shape rule and the tests reckon with."""
    d = cfg["hidden_size"]
    H, H_kv, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    Hm, P, N = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"]
    G, K = cfg["n_groups"], cfg["conv_kernel"]
    Z, F, Fs = cfg["moe_latent_size"], cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"]
    E, _, _ = expert_counts(cfg)
    inner = Hm * P
    width = inner + 2 * G * N
    return {
        "mamba_matrices": d * (inner + width + Hm) + inner * d,
        # the taps and their bias, dt_bias, A_log, D, the gated norm
        "mamba_other": K * width + (width if cfg.get("use_conv_bias", True) else 0) + 3 * Hm + inner,
        "state": Hm * P * N, "tail": (K - 1) * width,
        "attention_matrices": 2 * d * H * Dh + 2 * d * H_kv * Dh,
        "expert": 2 * Z * F,
        # the router, the two latent projections, the shared expert
        "routed_matrices": d * E + 2 * d * Z + 2 * d * Fs,
        "routed_other": E,  # the selection bias
    }


def shape(cfg):
    """A layer is its one sublayer and its norm vector. An ``M`` layer holds
    the two projections, the convolution, ``dt_bias``, ``A_log``, ``D`` and
    the gated norm, caches no keys and carries its state and convolution
    tail, read and written once a step. A ``*`` layer holds ``q``/``o`` ``d
    x H Dh`` and ``k``/``v`` ``d x H_kv Dh`` and writes ``2 H_kv Dh`` values
    a position. An ``E`` layer holds the router over the published expert
    count with its bias, the two latent projections, the shared expert and
    the experts **held here**, two ``latent x F`` matrices each; a token is
    multiplied with everything but the experts and with as many held experts
    as it is expected to choose, ``k x held / E`` (5.5 at the cut: a whole
    number of parameters; even routing); a decode step reads everything but
    the experts whatever it routes, and of the held experts the least its
    tokens must include, ``max(0, k - (E - held))``: 0 where a token's
    choices can all lie on other chips (``arithmetic.decode_read_params``'
    own rule; the tight figure is :func:`moe_latent_gmm_decode_count`'s)."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    E, held, _ = expert_counts(cfg)
    k = cfg["num_experts_per_tok"]
    n = sizes(cfg)
    if (k * held * n["expert"]) % E:
        raise ValueError(f"{k} x {held} / {E} held experts a token is no whole number of parameters")
    layers = []
    for kind in cfg["hybrid_override_pattern"]:
        if kind == MAMBA:
            held_here = n["mamba_matrices"] + n["mamba_other"] + d
            layer = {"params": held_here, "matmul_params": n["mamba_matrices"], "read_params": held_here,
                     "attn_dim": 0, "kv_values": 0, "state_values": n["state"] + n["tail"]}
        elif kind == ATTENTION:
            held_here = n["attention_matrices"] + d
            layer = {"params": held_here, "matmul_params": n["attention_matrices"], "read_params": held_here,
                     "attn_dim": cfg["num_attention_heads"] * cfg["head_dim"],
                     "kv_values": 2 * cfg["num_key_value_heads"] * cfg["head_dim"]}
        else:
            fixed = n["routed_matrices"] + n["routed_other"] + d
            layer = {"params": fixed + held * n["expert"],
                     "matmul_params": n["routed_matrices"] + k * held * n["expert"] // E,
                     "read_params": fixed,
                     "routed": {"expert_params": n["expert"], "per_token": max(0, k - (E - held))},
                     "attn_dim": 0, "kv_values": 0}
        layers.append(layer)
    return {
        "embed_params": V * d,
        "layers": layers,
        "final": {"params": d + d * V, "matmul_params": d * V, "read_params": d + d * V},
    }


def check_config(cfg):
    """What the program's ``NemotronHConfig`` refuses by name, and what a
    file of the cut must keep consistent."""
    for key, want in (("attention_bias", False), ("use_bias", False), ("mamba_proj_bias", False),
                      ("mlp_bias", False), ("tie_word_embeddings", False), ("residual_in_fp32", False),
                      ("sliding_window", None), ("norm_topk_prob", True), ("n_shared_experts", 1),
                      ("mamba_hidden_act", "silu"), ("mlp_hidden_act", "relu2"),
                      ("num_nextn_predict_layers", 0), ("n_group", 1), ("topk_group", 1)):
        if cfg.get(key, want) != want:
            raise ValueError(f"the program's nemotron_h family (or this reference) builds no {key}={cfg[key]!r}")
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"] or set(pattern) - {MAMBA, ATTENTION, EXPERTS}:
        raise ValueError("hybrid_override_pattern does not name num_hidden_layers layers of M, E and *")
    if cfg["mamba_num_heads"] * cfg["mamba_head_dim"] != cfg["expand"] * cfg["hidden_size"]:
        raise ValueError("mamba_num_heads * mamba_head_dim != expand * hidden_size")
    if cfg["mamba_num_heads"] % cfg["n_groups"]:
        raise ValueError("mamba_num_heads does not divide into n_groups")
    E, held, first = expert_counts(cfg)
    if held > E or not 0 <= first <= E - held:
        raise ValueError(f"n_routed_experts={held} from {first} on are not among the router's {E}")
    run = cfg.get("run", {})
    if "state_dtype" in run and run["state_dtype"] != cfg.get("state_dtype", "float32"):
        raise ValueError(
            f"state_dtype is {cfg.get('state_dtype', 'float32')!r} for the program and "
            f"{run['state_dtype']!r} under run (what a step's bytes are counted at)"
        )
    if run.get("kv_cache_dtype", "bfloat16") != "bfloat16":
        raise ValueError("the program's nemotron_h family builds no int8 cache beside state layers")


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


def moe_latent_gmm_decode_count(record, ops):
    """(FLOPs, bytes) of the grouped multiplication at decode shapes, where
    it is bound by reading weights: every execution (two a layer: an expert
    is two matrices and no gate) reads one ``latent x F`` matrix of each held
    expert the step *touched* (the program's gauge ``moe/experts_touched``,
    mean over layers and steps) in bf16; FLOPs over the rows whose expert is
    held here (``moe/rows_here_share``)."""
    cf = record["cell"]["config_file"]
    Z, F = cf["moe_latent_size"], cf["moe_intermediate_size"]
    gauges = record.get("gauges", {})
    touched = gauges.get("moe/experts_touched")
    if touched is None:
        return 0.0, 0.0
    E, held, _ = expert_counts(cf)
    share = gauges.get("moe/rows_here_share", held / E)
    flops = moved = 0.0
    for (rows, _), count in _calls(ops):
        flops += 2.0 * share * rows * Z * F * count
        moved += touched * Z * F * 2.0 * count
    return flops, moved


def moe_latent_gmm_prefill_count(record, ops):
    """(FLOPs, bytes) of the grouped multiplication in an admission's
    forwards: every row whose expert is held here times one ``latent x F``
    matrix. The call is handed all ``tokens x k`` sorted copies and the held
    experts' group sizes; the rest are multiplied with nothing. Their share
    is the program's gauge ``moe/rows_here_share`` (the mean over the polled
    decode steps: the same router on the same kind of tokens), else the
    even-routing share ``held / E``. Bytes: those rows read and written once
    and every held expert's matrix read once, bf16."""
    cf = record["cell"]["config_file"]
    Z, F = cf["moe_latent_size"], cf["moe_intermediate_size"]
    E, held, _ = expert_counts(cf)
    share = record.get("gauges", {}).get("moe/rows_here_share", held / E)
    flops = moved = 0.0
    for (rows, _), count in _calls(ops):
        flops += 2.0 * share * rows * Z * F * count
        moved += (2.0 * share * rows * (Z + F) + 2.0 * held * Z * F) * count
    return flops, moved


def ssm_group_step_count(record, ops):
    """(FLOPs, bytes) of the decode step's pass over the layers' states,
    counted once an execution of the operation that reads a layer's state
    out under its group's ``C`` (``f32[slots, H, P]`` by its name: one an
    ``M`` layer a step; what else the pattern takes adds its time only). The
    rule requires, a layer and step, one read and one write of the ``[slots,
    H, P, N]`` state at the configuration's ``state_dtype`` and, a state
    value, a decay multiply, an outer-product multiply-add and a multiply-add
    of the read-out: 5 FLOPs, far under the bytes' time."""
    from benchmark.arithmetic import DTYPE_BYTES

    cf = record["cell"]["config_file"]
    H, P, N = cf["mamba_num_heads"], cf["mamba_head_dim"], cf["ssm_state_size"]
    slots = record["cell"]["traffic_file"]["slots"]
    n = sum(count for shape, count in _calls(ops) if shape == (slots, H, P))
    values = slots * H * P * N
    return 5.0 * values * n, 2.0 * values * DTYPE_BYTES[cf["run"]["state_dtype"]] * n


def ssm_group_scan_count(record, ops):
    """(FLOPs, bytes) of the chunked scan in an admission's forwards. The
    pattern takes the operations of the scan's chunk loop (the products, the
    passes between them and the stacking of the chunks; their time is the
    scan's); the work is counted once a chunk and layer, at the operation
    that spreads a chunk's decays over the groups, ``f32[rows, G, H / G, L,
    L]`` by its name (one an execution of the loop's body in the whole
    admission and in an admission chunk alike). A chunk of ``L`` =
    ``chunk_size`` columns requires, a row and head, ``2 L L P`` (inside the
    chunk) and ``2 x 2 L P N`` (the carried state read out and updated)
    FLOPs, and ``2 L L N`` a row and **group** for the scores a group's
    heads share. Bytes: the inputs read and the outputs written once in bf16
    and the state read and written in float32."""
    cf = record["cell"]["config_file"]
    H, P, N = cf["mamba_num_heads"], cf["mamba_head_dim"], cf["ssm_state_size"]
    G, L = cf["n_groups"], cf["chunk_size"]
    flops = moved = 0.0
    for shape_, count in _calls(ops):
        if shape_[1:] != (G, H // G, L, L):
            continue
        rows = shape_[0]
        flops += count * rows * (H * (2 * L * L * P + 4 * L * P * N) + G * 2 * L * L * N)
        moved += count * rows * (2 * 2 * L * (H * P + 2 * G * N) + 2 * 4 * H * P * N)
    return flops, moved
