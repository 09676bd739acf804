"""ZAYA1 (``model_type: zaya``) forward pass in plain ``jax.numpy`` float32.

Written from the published ``config.json`` of ``Zyphra/ZAYA1-8B`` (``[c]``
below), from Zyphra's descriptions of compressed convolutional attention
and of the ZAYA1 models (``[p]``: arXiv:2510.04476 and arXiv:2511.17127 as
ISSUE 45 recalls the numbers; there is no network on the machine this was
written on, so the checkable part is the catalog row's ``described_as`` and
the sibling row's keys ``cca``, ``zaya_use_eda``, ``zaya_use_mod``,
``scale_residual_merge``, ``zaya_mlp_expansion`` 256, ``zaya_high_prec``),
and from what is assumed here (``[a]``, each listed under ``assumed`` in the
configuration file; seeded weights make none of them change a shape or a
cost). d 2048, H_q 8, H_kv 2, G = 4, Dh 128, R 256, E 16 as published:

    block l, input x [T, d], router carry r_prev [T, R] (zeros at l = 0)

    u  = rms(x; w1, eps)                                                [c]
    q~ = u Wq [H_q Dh]   k~ = u Wk [H_kv Dh]   no bias                   [c] sizes, [p] compression
    v  = [ u_t Wv1 | u_{t-1} Wv2 ]   KV head 0 from the token itself, KV head 1 from the
                                     token before (zeros before the first)   [p] shift, [a] which half
    z  = [q~ | k~]
    c0_t    = sum_{j<K0} w0[j] * z_{t-K0+1+j} + b0     depthwise, causal, K0 = cca_time0   [c], [p]
    c1_t[h] = sum_{j<K1} c0_{t-K1+1+j}[h] W1[h,j] + b1[h]   per head h of the H_q + H_kv,
                                     W1[h,j] Dh x Dh, causal, K1 = cca_time1  [c], [p], [a] biases
    q_t[h] = c1_t[h] + (q~_t[h] + k~_t[h // G]) / 2                      [p] q-k mean
    k_t[g] = c1_t[H_q+g] + (k~_t[g] + mean_{h in g} q~_t[h]) / 2         [p], [a] grouped form
    q <- sqrt(Dh) q / |q| ;  k <- tau[g] sqrt(Dh) k / |k|                [p]; [a] 1e-6 under the root
    rotary (half-split) on the first partial_rotary_factor Dh of a head, theta 5e6   [c]
    o = softmax(q k^T / sqrt(Dh) + causal) v, G query heads a KV head; a = o Wo   [c], [a] the score's scale
    x <- (al1 * x + be1) + (ga1 * a + de1)                               [p] residual scaling, [a] form

    u = rms(x; w2)
    r = u Wd + bd;  r <- r + eta * r_prev  (carried on)                  [c] R, [p] EDA, [a] form
    s = softmax(W3 gelu(W2 gelu(W1 rms(r; wr))))  over E + 1             [p] MLP router, MoD; [a] E + 1, exact gelu
    e = argmax(s + b),  p = s[e]             b moves the choice only     [p]
    y = p * (silu(u Wg[e]) * (u Wu[e])) Wdn[e]  if e < E, else 0         [c]
    x <- (al2 * x + be2) + (ga2 * y + de2)

    logits = rms(x_L; wf) Emb^T              tied                        [c]

Nothing here is shared with ``trlx_tpu/ops``: the convolutions are explicit
shifted sums over the whole sequence, every expert is computed on every
token (one at a time in a loop) and weighted by the router's one-hot
choice, the skip's column of that one-hot is never multiplied with
anything, there is no sort, no grouped call and no cache. An expert is
upcast as it is used, the token table is read by rows and the head runs
over the vocabulary in blocks, so beside the served bf16 weights no
float32 copy of more than one matrix exists.

Departures: left-padded prompts (padded keys are masked; a padded position
feeds zeros to the projections and to both convolutions, so the windows
are zero when a row's first token arrives, as they are for an unpadded
sequence; rotary positions count a row's real tokens); the residual
scaling is applied in float32.

``params`` is the backbone's tree as the program names it (``wte``,
``h_<i>/{ln_1, attn/{q_proj, k_proj, v_proj, o_proj, conv0_weight [K0, C],
conv0_bias, conv1_weight [H, K1, Dh, Dh], conv1_bias, k_temp}, merge_1,
ln_2, mlp/{router/{down, down_bias, carry_scale, norm, fc1, fc2, out,
balance_bias}, w_gate, w_up, w_down}, merge_2}``, ``ln_f``); every matrix is
``[in, out]``; ``v_proj`` is ``[Wv1 | Wv2]``.
"""

import re

import jax
import jax.numpy as jnp

f32 = lambda a: jnp.asarray(a, jnp.float32)
HEAD_BLOCKS = 16
NORM_EPS = 1e-6
LAYER_KIND = "hybrid"


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * f32(scale)


def shifted(x, by):
    """``x`` [B, T, ...] moved ``by`` positions later, zeros moved in."""
    if by == 0:
        return x
    return jnp.pad(x, ((0, 0), (by, 0)) + ((0, 0),) * (x.ndim - 2))[:, : x.shape[1]]


def rope_of(cfg):
    """(theta, rotated dimensions of a head) from the published nested group."""
    group = cfg["rope_parameters"][LAYER_KIND]
    return float(group["rope_theta"]), int(cfg["head_dim"] * group["partial_rotary_factor"])


def rotary(x, positions, theta, dims):
    """Half-split rotation of the first ``dims`` of each head; x [B, T, H, Dh]."""
    inv = 1.0 / theta ** (jnp.arange(0, dims, 2, dtype=jnp.float32) / dims)
    ang = positions.astype(jnp.float32)[..., None] * inv
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None]
    rot, rest = x[..., :dims], x[..., dims:]
    turned = jnp.concatenate([-rot[..., dims // 2 :], rot[..., : dims // 2]], -1)
    return jnp.concatenate([rot * cos + turned * sin, rest], -1)


def grouped_attention(q, k, v, mask):
    """q [B, T, H, Dh] over k, v [B, T, H_kv, Dh]; causal, padded keys out,
    scores over sqrt(Dh)."""
    B, T, H, Dh = q.shape
    G = H // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(Dh))
    allowed = (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])[None, None] & (
        mask[:, None, None, :] > 0
    )
    weights = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def l2(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + NORM_EPS) * jnp.sqrt(jnp.float32(x.shape[-1]))


def cca(u, a, cfg, mask, positions):
    """The attention of one layer on the normed input ``u`` [B, T, d]."""
    B, T, _ = u.shape
    Hq, Hkv, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    G, K0, K1 = Hq // Hkv, cfg["cca_time0"], cfg["cca_time1"]
    real = mask.astype(jnp.float32)[..., None]
    u = u * real
    q_lat = (u @ f32(a["q_proj"]["kernel"])).reshape(B, T, Hq, Dh)
    k_lat = (u @ f32(a["k_proj"]["kernel"])).reshape(B, T, Hkv, Dh)
    v_lat = (u @ f32(a["v_proj"]["kernel"])).reshape(B, T, Hkv, Dh)
    z = jnp.concatenate([q_lat, k_lat], axis=2)  # [B, T, H, Dh]
    w0 = f32(a["conv0_weight"]).reshape(K0, Hq + Hkv, Dh)
    c0 = sum(shifted(z, K0 - 1 - j) * w0[j] for j in range(K0))
    c0 = (c0 + f32(a["conv0_bias"]).reshape(Hq + Hkv, Dh)) * real[..., None]
    w1 = f32(a["conv1_weight"])  # [H, K1, Dh, Dh]
    c1 = sum(jnp.einsum("bthd,hde->bthe", shifted(c0, K1 - 1 - j), w1[:, j]) for j in range(K1))
    c1 = c1 + f32(a["conv1_bias"])
    q = c1[:, :, :Hq] + (q_lat + jnp.repeat(k_lat, G, axis=2)) / 2
    k = c1[:, :, Hq:] + (k_lat + q_lat.reshape(B, T, Hkv, G, Dh).mean(3)) / 2
    q = l2(q)
    k = l2(k) * f32(a["k_temp"])[:, None]
    half = Hkv // 2
    v = jnp.concatenate([v_lat[:, :, :half], shifted(v_lat[:, :, half:], 1)], axis=2)
    theta, dims = rope_of(cfg)
    q, k = rotary(q, positions, theta, dims), rotary(k, positions, theta, dims)
    out = grouped_attention(q, k, v, mask)
    return out.reshape(B, T, Hq * Dh) @ f32(a["o_proj"]["kernel"])


def merge(x, branch, m):
    return (x * f32(m["skip_scale"]) + f32(m["skip_bias"])) + (
        branch * f32(m["branch_scale"]) + f32(m["branch_bias"])
    )


def router(u, r_prev, p, cfg):
    """``(one-hot combine weights [.., E + 1], the choice [..], the carry)``."""
    r = u @ f32(p["down"]) + f32(p["down_bias"])
    if r_prev is not None:
        r = r + f32(p["carry_scale"]) * r_prev
    h = rms_norm(r, p["norm"], cfg["rms_norm_eps"])
    h = jax.nn.gelu(h @ f32(p["fc1"]), approximate=False)
    h = jax.nn.gelu(h @ f32(p["fc2"]), approximate=False)
    s = jax.nn.softmax(h @ f32(p["out"]), axis=-1)
    e = jnp.argmax(s + f32(p["balance_bias"]), axis=-1)
    return jax.nn.one_hot(e, s.shape[-1], dtype=s.dtype) * s, e, r


def experts(u, mlp, weights):
    """Every expert on every token, one at a time; ``weights`` [.., E] (the
    skip's column is left out by the caller: nothing computes it)."""
    def one(acc, xs):
        w_gate, w_up, w_down, w = xs
        y = (jax.nn.silu(u @ f32(w_gate)) * (u @ f32(w_up))) @ f32(w_down)
        return acc + y * w[..., None], None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (mlp["w_gate"], mlp["w_up"], mlp["w_down"], jnp.moveaxis(weights, -1, 0)),
    )
    return out


def trunk_with_choices(params, cfg, input_ids, mask):
    """(the hidden states after the final RMSNorm [B, T, d] float32, the
    router's choice in every layer [L, B, T])."""
    eps, E = cfg["rms_norm_eps"], cfg["num_experts"]
    positions = jnp.clip(jnp.cumsum(mask, axis=-1) - 1, 0, None)
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"]["embedding"][input_ids])
        carry, chosen = None, []
        for i in range(cfg["num_hidden_layers"]):
            blk = params[f"h_{i}"]
            a = cca(rms_norm(x, blk["ln_1"]["scale"], eps), blk["attn"], cfg, mask, positions)
            x = merge(x, a, blk["merge_1"])
            u = rms_norm(x, blk["ln_2"]["scale"], eps)
            w, e, carry = router(u, carry, blk["mlp"]["router"], cfg)
            x = merge(x, experts(u, blk["mlp"], w[..., :E]), blk["merge_2"])
            chosen.append(e)
        return rms_norm(x, params["ln_f"]["scale"], eps), jnp.stack(chosen)


def trunk(params, cfg, input_ids, mask):
    """The hidden states after the final RMSNorm, [B, T, d] float32."""
    return trunk_with_choices(params, cfg, input_ids, mask)[0]


def head(params, cfg, hidden):
    """Logits [..., V] of hidden states [..., d]: the token table again,
    over the vocabulary in blocks."""
    table = params["wte"]["embedding"]
    V = table.shape[0]
    step = -(-V // HEAD_BLOCKS)
    with jax.default_matmul_precision("highest"):
        parts = [hidden @ f32(table[at : at + step]).T for at in range(0, V, step)]
    return jnp.concatenate(parts, axis=-1)


def forward(params, cfg, input_ids, mask):
    """Logits [B, T, V] in float32: the head on every position of the trunk."""
    return head(params, cfg, trunk(params, cfg, input_ids, mask))


def sizes(cfg):
    """The counts of one layer by part, from the published keys."""
    d, Dh = cfg["hidden_size"], cfg["head_dim"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    H, K0, K1 = Hq + Hkv, cfg["cca_time0"], cfg["cca_time1"]
    R, E, F = cfg["router_hidden_size"], cfg["num_experts"], cfg["moe_intermediate_size"]
    return {
        "projections": d * Hq * Dh + 2 * d * Hkv * Dh + Hq * Dh * d,
        "mix_matrices": H * K1 * Dh * Dh,
        "mix_other": K0 * H * Dh + 2 * H * Dh + Hkv,  # depthwise taps, two biases, tau
        "router_matrices": d * R + 2 * R * R + R * (E + 1),
        "router_other": 3 * R + E + 1,  # bd, eta, the norm's scale, b
        "vectors": 2 * d + 8 * d,  # two norms, two merges of four vectors
        "expert": 3 * d * F,
        "tail": ((K0 - 1) + (K1 - 1)) * H * Dh + Hkv // 2 * Dh,
    }


def shape(cfg):
    """A layer holds its four projections, the mix (the per-head taps, the
    depthwise taps, two biases, the temperature), the router (its
    down-projection, two square matrices and the ``R x (E + 1)`` output;
    ``bd``, ``eta``, a norm scale, the balancing bias), ten vectors of ``d``
    (two norms, two residual merges) and ``E`` experts of three ``d x F``
    matrices. A token is multiplied with the projections, the per-head
    taps, the router's matrices and **one** expert (the skip: none, which
    the count leaves at one). A decode step reads everything but the
    experts whatever it routes, and at least one expert. Attention is
    ``H_q Dh`` wide; a position writes ``2 H_kv Dh`` cache values; a
    sequence carries the tail (``state_values``: the last ``K0 - 1`` rows of
    ``[q~ | k~]``, the last ``K1 - 1`` rows of the first convolution's
    output, the shifted value's source), read and written once a step at
    ``run.state_dtype``."""
    n = sizes(cfg)
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    fixed = (n["projections"] + n["mix_matrices"] + n["mix_other"] + n["router_matrices"]
             + n["router_other"] + n["vectors"])
    layer = {
        "params": fixed + cfg["num_experts"] * n["expert"],
        "matmul_params": n["projections"] + n["mix_matrices"] + n["router_matrices"] + n["expert"],
        "read_params": fixed,
        "routed": {"expert_params": n["expert"], "per_token": cfg["num_experts_per_tok"]},
        "attn_dim": cfg["num_attention_heads"] * cfg["head_dim"],
        "kv_values": 2 * cfg["num_key_value_heads"] * cfg["head_dim"],
        "state_values": n["tail"],
    }
    return {
        "embed_params": V * d,
        "layers": [dict(layer) for _ in range(cfg["num_hidden_layers"])],
        "final": {"params": d, "matmul_params": d * V, "read_params": d + d * V},
    }


def check_config(cfg):
    """What the program's ``ZayaConfig`` refuses by name, and what a file of
    the cut must keep consistent."""
    for key, want in (("attention_bias", False), ("lm_head_bias", False), ("sliding_window", None),
                      ("tie_word_embeddings", True), ("hidden_act", "silu"), ("num_experts_per_tok", 1)):
        if cfg.get(key, want) != want:
            raise ValueError(f"the program's zaya family builds no {key}={cfg[key]!r}")
    kinds = cfg["layer_types"]
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) != {LAYER_KIND}:
        raise ValueError(f"layer_types must name num_hidden_layers layers of kind {LAYER_KIND!r}")
    group = cfg["rope_parameters"].get(LAYER_KIND)
    if not group or group.get("partial_rotary_factor") != cfg["partial_rotary_factor"]:
        raise ValueError("rope_parameters lacks the layers' group or disagrees with partial_rotary_factor")
    if cfg["num_attention_heads"] % cfg["num_key_value_heads"] or cfg["num_key_value_heads"] % 2:
        raise ValueError("num_attention_heads must divide over an even num_key_value_heads")
    run = cfg.get("run", {})
    if "state_dtype" in run and run["state_dtype"] != cfg.get("state_dtype", "float32"):
        raise ValueError(
            f"state_dtype is {cfg.get('state_dtype', 'float32')!r} for the program and "
            f"{run['state_dtype']!r} under run (what a step's bytes are counted at)"
        )
    if run.get("kv_cache_dtype", "bfloat16") != "bfloat16":
        raise ValueError("the program's zaya family builds no int8 cache beside a tail")


# -- required work of the new kernels (``readers.op_roofline``) ------------- #


def _calls(ops):
    """Each matching operation with the sizes of its result, read from the
    name ``trace_reduce.op_kind`` gives it (``ragged-dot bf16[32,2048]``)."""
    out = []
    for name, op in ops.items():
        m = re.search(r"\[([\d,]+)\]", name)
        if m:
            out.append(([int(x) for x in m.group(1).split(",")], op["count"]))
    return out


def moe_top1_gmm_decode_count(record, ops):
    """(FLOPs, bytes) of the grouped multiplication in a decode step, where
    it is bound by reading weights: every execution reads one ``d x F``
    matrix of each expert the step *touched* (the program's own gauge
    ``moe/experts_touched``, mean over blocks and steps, never all of
    them) in bf16; a row that chose the skip touches none."""
    cf = record["cell"]["config_file"]
    d, F = cf["hidden_size"], cf["moe_intermediate_size"]
    touched = record.get("gauges", {}).get("moe/experts_touched")
    if touched is None:
        return 0.0, 0.0
    flops = moved = 0.0
    for (rows, _), count in _calls(ops):
        flops += 2.0 * rows * d * F * count
        moved += touched * d * F * 2 * count
    return flops, moved


def moe_top1_gmm_prefill_count(record, ops):
    """(FLOPs, bytes) of the grouped multiplication in an admission's
    forwards, where it is compute-bound: every row that chose an expert
    times one ``d x F`` matrix, 2 FLOPs a multiply-add; the rows that chose
    the skip are handed over with the rest and multiplied with nothing:
    their share is the program's own gauge ``moe/skip_share`` (the mean
    over the polled decode steps: the same router on the same kind of
    tokens; 0 where it is absent). Bytes: the kept rows read and written
    once in bf16."""
    cf = record["cell"]["config_file"]
    d, F = cf["hidden_size"], cf["moe_intermediate_size"]
    kept = 1.0 - record.get("gauges", {}).get("moe/skip_share", 0.0)
    flops = moved = 0.0
    for (rows, width), count in _calls(ops):
        other = d if width == F else F
        flops += 2.0 * rows * width * other * count * kept
        moved += 2.0 * rows * (width + other) * count * kept
    return flops, moved


def cca_mix_prefill_count(record, ops):
    """(FLOPs, bytes) of the per-head mix (the second convolution) in an
    admission's forwards. The compiler makes of it a batch of ``H`` plain
    products, ``fusion f32[H, rows, Dh]``, between two layout passes (the
    taps laid out heads first, the result back to ``[.., rows, H, Dh]``):
    the pattern takes all three (their time is the mix's) and the work is
    counted once, at the product: a row is ``K1`` taps of ``Dh x Dh`` a
    head, ``2 x rows x H x K1 x Dh x Dh`` FLOPs. Bytes: the taps' weights
    once an execution in bf16; the rows themselves are a chunk's
    activations, which the compiler keeps in the chip's fast memory
    between the passes (``S(1)`` in the compiled layouts), so no traffic
    with the device's memory is required of them and the FLOPs bound it."""
    cf = record["cell"]["config_file"]
    Dh, K1 = cf["head_dim"], cf["cca_time1"]
    H = cf["num_attention_heads"] + cf["num_key_value_heads"]
    flops = moved = 0.0
    for dims, count in _calls(ops):
        if len(dims) != 3 or dims[0] != H or dims[2] != Dh:
            continue
        flops += 2.0 * dims[1] * H * K1 * Dh * Dh * count
        moved += 2.0 * H * K1 * Dh * Dh * count
    return flops, moved
