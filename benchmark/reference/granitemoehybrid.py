"""Granite 4.0-H (``granitemoehybrid``) forward pass in plain ``jax.numpy``
float32.

Written from the published ``config.json`` and the family's
``transformers`` module (there is no paper of the architecture; Mamba-2 is
Dao & Gu 2024, section 6's recurrence):

    h0 = embedding_multiplier * E[ids]
    h += residual_multiplier * mix(rms(h))
    h += residual_multiplier * (moe(rms(h)) + shared(rms(h)))
    logits = rms(h) E^T / logits_scaling

``mix`` of a ``mamba`` layer: ``[z | xBC | dt] = u W_in`` with ``u`` the
normed input, zero at padded positions; ``xBC = silu(conv(xBC) + b)`` (a
depthwise causal convolution of ``mamba_d_conv``), zero at padded positions
again, split into ``x`` (``mamba_n_heads`` heads of ``mamba_d_head``), ``B``
and ``C`` (``mamba_d_state`` each, one group for all heads); ``dt =
softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head the state ``S`` of
``[head, state]``: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t =
S_t C_t + D x_t`` - **one state update a position**, a ``lax.scan`` over the
positions, no chunks and no decay matrix; ``out = (rms(y * silu(z)) * w)
W_out``. ``mix`` of an ``attention`` layer: ``num_attention_heads`` query
heads over ``num_key_value_heads`` KV heads (query head ``h`` reads KV head
``h // G``), no bias, no positions, ``softmax(q k^T * attention_multiplier)``
in float32. ``moe``: ``logits = h W_r`` over ``num_router_experts``, the
``num_experts_per_tok`` largest, their weights the softmax over the chosen
logits; ``W_down(silu(W_gate h) * W_up h)`` of each; ``shared`` is one more
such MLP, ``shared_intermediate_size`` wide, on every token.

**The chip's share.** The parameter tree holds experts
``first_local_expert .. + num_local_experts`` of the router's
``num_router_experts``. Every held expert is computed on every token, one
at a time in a loop, weighted by the router (0 where it was not chosen);
the absent experts' terms are left out, exactly as the program leaves them
out, and that partial sum goes on to the next layer. No sort, no grouped
call, no cache; an expert is upcast as it is used, the token table is read
by rows and the head runs over the vocabulary in blocks, so beside 9.9 GB
of served bf16 weights no float32 copy of more than one matrix exists.

Departures: left-padded prompts (padded keys are masked; a padded position
feeds zeros to the mixer, so state and convolution window are zero when a
row's first token arrives, as they are for an unpadded sequence); the
multipliers are applied in float32.

``params`` is the backbone's tree as the program names it (``wte``,
``h_<i>/{ln_1, mamba/{in_proj, conv_weight [K, C], conv_bias, dt_bias,
A_log, D, norm, out_proj} | attn/{q_proj, k_proj, v_proj, o_proj}, ln_2,
mlp/{router, w_gate, w_up, w_down}, shared/{gate_proj, up_proj,
down_proj}}``, ``ln_f``); every matrix is ``[in, out]``.
"""

import re

import jax
import jax.numpy as jnp

from benchmark.reference.olmoe import gmm_decode_count, gmm_prefill_count

f32 = lambda a: jnp.asarray(a, jnp.float32)
HEAD_BLOCKS = 8


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
    """The mixer of one ``mamba`` layer on the normed input ``u`` [B, T, D]."""
    B, T, _ = u.shape
    H, P, N, K = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    inner = H * P
    real = mask.astype(jnp.float32)[..., None]
    proj = (u * real) @ f32(p["in_proj"]["kernel"])
    z, xBC, dt = proj[..., :inner], proj[..., inner : inner + inner + 2 * N], proj[..., inner + inner + 2 * N :]
    w = f32(p["conv_weight"])  # [K, C]; w[K - 1] multiplies the position itself
    padded = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, k : k + T] * w[k] for k in range(K))
    if "conv_bias" in p:
        conv = conv + f32(p["conv_bias"])
    xBC = jax.nn.silu(conv) * real
    x = xBC[..., :inner].reshape(B, T, H, P)
    Bm, Cm = xBC[..., inner : inner + N], xBC[..., inner + N :]
    dt = jax.nn.softplus(dt + f32(p["dt_bias"]))  # [B, T, H]
    A = -jnp.exp(f32(p["A_log"]))

    def position(S, xs):
        x_t, B_t, C_t, dt_t = xs  # [B, H, P], [B, N], [B, N], [B, H]
        S = S * jnp.exp(dt_t * A)[..., None, None] + (
            (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :]
        )
        return S, (S * C_t[:, None, None, :]).sum(-1)

    by_position = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(
        position, jnp.zeros((B, H, P, N), jnp.float32),
        (by_position(x), by_position(Bm), by_position(Cm), by_position(dt)),
    )
    y = jnp.moveaxis(y, 0, 1) + f32(p["D"])[None, None, :, None] * x
    y = y.reshape(B, T, inner) * jax.nn.silu(z)
    return rms_norm(y, p["norm"], cfg["rms_norm_eps"]) @ f32(p["out_proj"]["kernel"])


def router_weights(h, router, k):
    """[.., E] combine weights: the softmax over the ``k`` largest logits at
    the chosen experts, 0 elsewhere."""
    logits = h @ f32(router)
    top, idx = jax.lax.top_k(logits, k)
    top = jax.nn.softmax(top, axis=-1)
    return (jax.nn.one_hot(idx, logits.shape[-1], dtype=top.dtype) * top[..., None]).sum(-2)


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


def swiglu(h, p):
    gate, up = h @ f32(p["gate_proj"]["kernel"]), h @ f32(p["up_proj"]["kernel"])
    return (jax.nn.silu(gate) * up) @ f32(p["down_proj"]["kernel"])


def expert_counts(cfg):
    """(the router's width, experts held here, the first held)."""
    held = cfg["num_local_experts"]
    return cfg.get("num_router_experts") or held, held, cfg.get("first_local_expert", 0)


def trunk(params, cfg, input_ids, mask):
    """The hidden states after the final RMSNorm, [B, T, D] float32."""
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    H, H_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    _, held, first = expert_counts(cfg)
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"]["embedding"][input_ids]) * cfg["embedding_multiplier"]
        B, T, D = x.shape
        Dh = D // H
        for i, kind in enumerate(cfg["layer_types"]):
            blk = params[f"h_{i}"]
            h = rms_norm(x, blk["ln_1"]["scale"], eps)
            if kind == "attention":
                a = blk["attn"]
                q = (h @ f32(a["q_proj"]["kernel"])).reshape(B, T, H, Dh)
                k = (h @ f32(a["k_proj"]["kernel"])).reshape(B, T, H_kv, Dh)
                v = (h @ f32(a["v_proj"]["kernel"])).reshape(B, T, H_kv, Dh)
                mixed = grouped_attention(q, k, v, mask, cfg["attention_multiplier"])
                mixed = mixed.reshape(B, T, D) @ f32(a["o_proj"]["kernel"])
            else:
                mixed = mamba(h, blk["mamba"], cfg, mask)
            x = x + res * mixed
            h = rms_norm(x, blk["ln_2"]["scale"], eps)
            w = router_weights(h, blk["mlp"]["router"], cfg["num_experts_per_tok"])
            routed = held_experts(h, blk["mlp"], w[..., first : first + held])
            x = x + res * (routed + swiglu(h, blk["shared"]))
        return rms_norm(x, params["ln_f"]["scale"], eps)


def head(params, cfg, hidden):
    """Logits [..., V] of hidden states [..., D]: the token table again,
    over the vocabulary in blocks, divided by ``logits_scaling``."""
    table = params["wte"]["embedding"]
    V = table.shape[0]
    step = -(-V // HEAD_BLOCKS)
    with jax.default_matmul_precision("highest"):
        parts = [hidden @ f32(table[at : at + step]).T for at in range(0, V, step)]
    return jnp.concatenate(parts, axis=-1) / cfg["logits_scaling"]


def forward(params, cfg, input_ids, mask):
    """Logits [B, T, V] in float32: the head on every position of the trunk."""
    return head(params, cfg, trunk(params, cfg, input_ids, mask))


def shape(cfg):
    """A layer holds its mixer (a ``mamba`` layer: the two projections, the
    convolution, ``dt_bias``, ``A_log``, ``D`` and the gated norm; an
    ``attention`` layer: ``q``/``o`` ``d x d`` and ``k``/``v`` ``d x H_kv
    Dh``), the shared MLP, the router over the published expert count, two
    norm vectors and the experts **held here**. A token is multiplied with
    the mixer's matrices, the shared MLP, the router and as many held
    experts as it is expected to choose, ``k x held / E`` (a whole number
    for the configurations there are; even routing). A decode step reads
    everything but the experts whatever it routes; of the held experts the
    least its tokens must include, ``max(0, k - (E - held))``: 0 where a
    token's choices can all lie on other chips
    (``arithmetic.decode_read_params``' own rule; the tight figure is
    :func:`moe_share_gmm_decode_count`'s). A ``mamba`` layer caches no
    keys and carries its state and convolution tail, read and written once
    a step; the ``attention`` layer writes ``2 H_kv Dh`` values a position."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    F, Fs = cfg["intermediate_size"], cfg["shared_intermediate_size"]
    H, H_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = d // H
    E, held, _ = expert_counts(cfg)
    k = cfg["num_experts_per_tok"]
    Hm, P, N, K = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    inner = Hm * P
    width = inner + 2 * N
    expert, shared, router, norms = 3 * d * F, 3 * d * Fs, d * E, 2 * d
    if (k * held) % E:
        raise ValueError(f"{k} x {held} / {E} held experts a token is no whole number")
    mixers = {
        "mamba": {
            "matrices": d * (inner + width + Hm) + inner * d,
            "other": K * width + (width if cfg.get("mamba_conv_bias", True) else 0) + 3 * Hm + inner,
            "attn_dim": 0, "kv_values": 0, "state_values": Hm * P * N + (K - 1) * width,
        },
        "attention": {
            "matrices": 2 * d * d + 2 * d * H_kv * Dh, "other": 0,
            "attn_dim": d, "kv_values": 2 * H_kv * Dh,
        },
    }
    layers = []
    for kind in cfg["layer_types"]:
        m = mixers[kind]
        fixed = m["matrices"] + m["other"] + shared + router + norms
        layer = {
            "params": fixed + held * expert,
            "matmul_params": m["matrices"] + shared + router + (k * held // E) * expert,
            "read_params": fixed,
            "routed": {"expert_params": expert, "per_token": max(0, k - (E - held))},
            "attn_dim": m["attn_dim"],
            "kv_values": m["kv_values"],
        }
        if "state_values" in m:
            layer["state_values"] = m["state_values"]
        layers.append(layer)
    return {
        "embed_params": V * d,
        "layers": layers,
        "final": {"params": d, "matmul_params": d * V, "read_params": d + d * V},
    }


def check_config(cfg):
    """What the program's ``GraniteMoeHybridConfig`` refuses by name, and
    what a file of the cut must keep consistent."""
    for key, want in (("rope_scaling", None), ("position_embedding_type", "nope"),
                      ("attention_bias", False), ("mamba_proj_bias", False),
                      ("mamba_n_groups", 1), ("tie_word_embeddings", True), ("hidden_act", "silu")):
        if cfg.get(key, want) != want:
            raise ValueError(f"the program's granitemoehybrid family builds no {key}={cfg[key]!r}")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    E, held, first = expert_counts(cfg)
    if held > E or not 0 <= first <= E - held:
        raise ValueError(f"num_local_experts={held} from {first} on are not among the router's {E}")
    run = cfg.get("run", {})
    if "state_dtype" in run and run["state_dtype"] != cfg.get("state_dtype", "float32"):
        raise ValueError(
            f"state_dtype is {cfg.get('state_dtype', 'float32')!r} for the program and "
            f"{run['state_dtype']!r} under run (what a step's bytes are counted at)"
        )
    if run.get("kv_cache_dtype", "bfloat16") != "bfloat16":
        raise ValueError("the program's granitemoehybrid family builds no int8 cache beside state layers")


# -- required work of the new kernels (``readers.op_roofline``) ------------- #

moe_share_gmm_decode_count = gmm_decode_count  # touched held experts x one d x F matrix, bf16


def moe_share_gmm_prefill_count(record, ops):
    """(FLOPs, bytes) of the grouped multiplication in an admission's
    forwards: ``olmoe.gmm_prefill_count`` (every row of the call times one
    ``d x F`` matrix) over the rows whose expert is held here alone - the
    call is handed all ``tokens x k`` sorted copies and the held experts'
    group sizes, and the rest are multiplied with nothing. Their share is
    the program's own gauge ``moe/rows_here_share`` (the mean over the
    polled decode steps: the same router on the same kind of tokens), else
    the even-routing share ``held / E``."""
    E, held, _ = expert_counts(record["cell"]["config_file"])
    share = record.get("gauges", {}).get("moe/rows_here_share", held / E)
    flops, moved = gmm_prefill_count(record, ops)
    return share * flops, share * moved


def ssm_step_count(record, ops):
    """(FLOPs, bytes) of the matching executions of the decode step's state
    update: each reads and writes the ``[slots, H, P, N]`` state of one
    layer once, at the configuration's ``state_dtype``; per state value one
    decay multiply, one outer-product multiply-add and one multiply-add of
    the read-out: 5 FLOPs, far under the bytes' time."""
    from benchmark.arithmetic import DTYPE_BYTES

    cf = record["cell"]["config_file"]
    values = cf["mamba_n_heads"] * cf["mamba_d_head"] * cf["mamba_d_state"]
    slots = record["cell"]["traffic_file"]["slots"]
    n = sum(op["count"] for op in ops.values())
    return 5.0 * values * slots * n, 2.0 * values * DTYPE_BYTES[cf["run"]["state_dtype"]] * slots * n


def ssm_scan_count(record, ops):
    """(FLOPs, bytes) of the matching executions of the chunked scan in an
    admission's forwards. The pattern takes the scan's products and the
    elementwise passes that feed them (their time is the scan's); the work
    is counted once a chunk, at the operation that reads the carried state
    out, ``convolution_multiply_fusion f32[rows, L, H, P]``: a chunk of
    ``L`` columns requires, a row and head, ``2 L L P`` (inside the chunk)
    and ``2 x 2 L P N`` (the carried state read out and updated) FLOPs, and
    ``2 L L N`` a row for the scores all heads share. Bytes: the inputs
    read and the outputs written once in bf16 and the state read and
    written in float32."""
    cf = record["cell"]["config_file"]
    H, P, N = cf["mamba_n_heads"], cf["mamba_d_head"], cf["mamba_d_state"]
    flops = moved = 0.0
    for name, op in ops.items():
        m = re.match(r"convolution_multiply_fusion f32\[(\d+),(\d+),(\d+),(\d+)\]", name)
        if not m:
            continue
        rows, L = int(m.group(1)), int(m.group(2))
        flops += op["count"] * rows * (H * (2 * L * L * P + 4 * L * P * N) + 2 * L * L * N)
        moved += op["count"] * rows * (2 * 2 * L * (H * P + 2 * N) + 2 * 4 * H * P * N)
    return flops, moved
