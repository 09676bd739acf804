#!/usr/bin/env python3
"""Chip smoke: PPO -> checkpoint -> serve, once, on the TPU, in one process.

The quickest proof that the system still starts on the chip. It drives the
main path through the entry points a user calls — ``trlx_tpu.train`` on
both rollout paths, then ``InferenceServer`` on the checkpoint the training
run wrote — at the published width and depth of gpt2-small, with weights
made from a seed and
pre-tokenized integer prompts: no network, no tokenizer, no HF checkpoint.
It then proves the Pallas flash kernels were compiled by Mosaic inside the
real T = 1024 train step and agree with the XLA attention path.

Contract (the driver runs ``python3 chip_smoke.py`` from the repo root):

- leg 1 is a gate: no TPU, or a ``device_kind`` missing from the one peaks
  table (``trlx_tpu/telemetry/attribution.py``), raises before anything
  compiles — the exit code is nonzero and no result line is printed;
- every leg prints one line naming the platform, device kind and count and
  the jax / jaxlib / libtpu versions; the first failed check raises, so a
  failed leg is a nonzero exit;
- the last line of stdout is one JSON object,
  ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
- one process holds the chip(s): nothing here starts a child that needs it;
- it writes only under ``chip_smoke_out/`` (recreated each run); compiled
  programs go where ``trlx_tpu/utils/compile_cache.py`` places them;
- unchanged on one chip and on all chips of a host (``mesh: {dp: -1}``,
  batch sizes divisible by 4).

The leg functions take the architecture, mesh and sizes as arguments so the
CPU rehearsal (``tests/test_chip_smoke.py``, marked slow) can call them at
toy width with the kernels in interpret mode; ``main`` passes none.
"""

from __future__ import annotations

import gc
import json
import os
import re
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
OUT_DIR = os.path.join(REPO, "chip_smoke_out")

GPT2_SMALL = {
    "vocab_size": 50257,
    "n_positions": 1024,
    "n_embd": 768,
    "n_layer": 12,
    "n_head": 12,
    "kv_cache_dtype": "auto",
}
DP_MESH = {"dp": -1, "fsdp": 1, "tp": 1}


class SmokeFailure(Exception):
    """A leg's check did not hold."""


def check(ok, message):
    if not ok:
        raise SmokeFailure(message)


def versions():
    from importlib import metadata

    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "absent"
    return f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu}"


def describe_devices():
    import jax

    dev = jax.devices()[0]
    return (
        f"platform={dev.platform} device_kind={dev.device_kind!r} "
        f"devices={len(jax.devices())}"
    )


# seconds jax spent in backend compiles (a persistent-cache hit counts the
# retrieval): set-up time, reported per leg so a cold and a warm run compare
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_seconds = [0.0]


def _on_duration(event, duration, **_):
    if event == _COMPILE_EVENT:
        _compile_seconds[0] += duration


def run_leg(name, fn, *args, **kwargs):
    """Run one leg, print its line. A failed check propagates: the first
    failed leg ends the process with a nonzero code."""
    t0, c0 = time.time(), _compile_seconds[0]
    out = fn(*args, **kwargs)
    print(
        f"leg {name}: ok in {time.time() - t0:.1f}s "
        f"(compile {_compile_seconds[0] - c0:.1f}s) | "
        f"{describe_devices()} {versions()}",
        flush=True,
    )
    return out


# ------------------------------ leg 1: gate ------------------------------- #


def leg_device():
    from trlx_tpu.telemetry.attribution import require_tpu

    return require_tpu()


# --------------------------- legs 2-3: training --------------------------- #


def ppo_config(arch, mesh, out_dir, name, *, engine="fixed", phases=1,
               seq_length=64, new_tokens=48, batch_size=16, num_rollouts=128):
    """The bench workload (faithful definition: all layers train, 2-layer
    hydra KL-ref) cut to ``phases`` whole phases, with the end-of-run
    checkpoint and every side output under ``out_dir``."""
    from trlx_tpu.data.configs import TRLConfig

    ppo_epochs = 4
    eos = arch["vocab_size"] - 1
    return TRLConfig.from_dict({
        "model": {
            "model_type": "gpt2",
            "num_layers_unfrozen": 0,
            "ref_branch_layers": min(2, arch["n_layer"] - 1),
            "model_arch": dict(arch),
        },
        "train": {
            "seq_length": seq_length,
            "batch_size": batch_size,
            "epochs": phases,
            "total_steps": phases * ppo_epochs * (num_rollouts // batch_size),
            "eval_interval": 1000000,
            "checkpoint_interval": 1000000,
            "checkpoint_dir": os.path.join(out_dir, f"ckpt_{name}"),
            # a tenth of the bench's rate: from seeded (not pretrained)
            # weights the bench rate moves the policy far enough inside the
            # first phase to trip the ratio-explosion detector (max
            # log-ratio 4.2 at step 7 on the v5e, PR 21, whatever the
            # reward), and the smoke judges the program, not the schedule
            "lr_init": 1.0e-5,
            "lr_target": 1.0e-5,
            "mesh": dict(mesh),
            "dtype": "bfloat16",
            "health": {
                "enabled": True,
                "dump_dir": os.path.join(out_dir, "health_dumps"),
            },
            "rollout": {"engine": engine},
        },
        "method": {
            "name": "PPOConfig",
            "num_rollouts": num_rollouts,
            "chunk_size": num_rollouts,
            "ppo_epochs": ppo_epochs,
            "init_kl_coef": 0.2,
            "target": 6,
            "horizon": 10000,
            "cliprange_reward": 10,
            "scale_reward": "running",
            "gen_kwargs": {
                "max_new_tokens": new_tokens,
                "min_new_tokens": new_tokens,
                "top_k": 0,
                "do_sample": True,
                "eos_token_id": eos,
                "pad_token_id": eos,
            },
        },
    })


def make_prompts(vocab_size, n, max_len, seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)
    hi = max(vocab_size - 2, 3)
    return [
        [int(t) for t in rng.integers(1, hi, size=int(rng.integers(4, max_len + 1)))]
        for _ in range(n)
    ]


def check_placement(tree, what):
    """Every array lives on jax's (accelerator) devices and, with more than
    one, is laid out over all of them."""
    import jax

    devices = set(jax.devices())
    for leaf in jax.tree_util.tree_leaves(tree):
        placed = set(leaf.sharding.device_set)
        check(
            placed == devices,
            f"{what}: a {leaf.shape} array sits on {len(placed)} of "
            f"{len(devices)} devices ({sorted(d.id for d in placed)})",
        )


def check_memory_balance():
    """Per-device allocator bytes within 2x of each other: the max over
    devices (telemetry/device_metrics.py::snapshot) would not show
    'everything on device 0'."""
    import jax

    from trlx_tpu.telemetry.device_metrics import device_memory_stats

    stats = device_memory_stats()
    if not stats:
        # only the CPU rehearsal's backend has no allocator counters
        check(
            jax.devices()[0].platform != "tpu",
            "memory_stats() missing on a TPU device",
        )
        return
    in_use = [s["bytes_in_use"] for s in stats]
    check(
        min(in_use) > 0 and max(in_use) <= 2 * min(in_use),
        f"per-device bytes_in_use unbalanced: {in_use}",
    )


def all_finite(tree):
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(tree)
    ok = jax.jit(
        lambda xs: jnp.stack([jnp.isfinite(x).all() for x in xs]).all()
    )(leaves)
    return bool(ok)


def leg_train(arch, mesh, out_dir, name, *, engine="fixed", phases=1,
              **sizes):
    """``trlx_tpu.train`` for ``phases`` whole phases; returns the run's
    checkpoint directory."""
    import numpy as np

    import trlx_tpu

    config = ppo_config(
        arch, mesh, out_dir, name, engine=engine, phases=phases, **sizes
    )
    vocab = arch["vocab_size"]
    seen = {"samples": 0, "max_token": -1, "empty": 0}

    def reward_fn(samples, queries, response_gt=None):
        # without a tokenizer a sample is its token ids joined by spaces;
        # the reward (share of even ids: cheap, bounded, and with a spread
        # even on random tokens) parses them, and the ids are what leg 2
        # checks against the vocabulary
        scores = []
        for s in samples:
            ids = [int(t) for t in s.split()]
            seen["samples"] += 1
            seen["empty"] += not ids
            seen["max_token"] = max([seen["max_token"], *ids])
            scores.append(sum(t % 2 == 0 for t in ids) / max(len(ids), 1))
        return scores

    prompts = make_prompts(
        vocab, 2 * config.method.num_rollouts, min(32, config.train.seq_length)
    )
    trainer = trlx_tpu.train(reward_fn=reward_fn, prompts=prompts, config=config)

    target = config.train.total_steps
    step = int(trainer.state.step)
    check(step == target, f"state.step {step} != target {target}")
    check(all_finite(trainer.state.params), "non-finite parameter leaf")
    final = trainer._final_stats
    watched = {
        k: float(np.asarray(v)) for k, v in final.items()
        if k.startswith(("losses/", "policy/", "reward/"))
    }
    watched["mean_rollout_kl"] = float(np.asarray(trainer.mean_kl))
    check(
        any(k.startswith("losses/") for k in watched),
        f"no loss in the run's final stats: {sorted(final)}",
    )
    bad = {k: v for k, v in watched.items() if not np.isfinite(v)}
    check(not bad, f"non-finite training stats: {bad}")
    check(
        seen["samples"] >= phases * config.method.num_rollouts
        and not seen["empty"],
        f"reward_fn saw {seen['samples']} samples, {seen['empty']} empty",
    )
    check(
        0 <= seen["max_token"] < vocab,
        f"sampled token {seen['max_token']} outside vocab {vocab}",
    )
    rollouts = trainer.buffer.full
    tokens = np.asarray(rollouts.response_tokens)
    check(
        tokens.min() >= 0 and tokens.max() < vocab,
        f"buffer tokens outside [0, {vocab})",
    )
    events = trainer.health_monitor.events
    errors = [e.to_dict() for e in events if e.severity == "error"]
    check(not errors, f"health events of severity error: {errors}")
    check_placement(trainer.state.params, "params")
    check_placement(rollouts, "rollout batch")
    check_memory_balance()

    check(
        trainer.rollout_engine == engine,
        f"run asked for the {engine} sampler and finished on "
        f"{trainer.rollout_engine}",
    )
    check(
        not any(e.detector == "engine-fallback" for e in events),
        "engine-fallback health event",
    )
    return config.train.checkpoint_dir


# ----------------------- leg 2b: the decode loop's KV ----------------------- #

_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$")
_HLO_INSTRUCTION = re.compile(
    r"^\s*(ROOT )?%[\w.\-]+ = (.*?) ([\w\-]+)\((.*)$"
)
_HLO_LOOP_CALLEES = re.compile(
    r"(?:body|condition|to_apply|true_computation|false_computation)="
    r"%([\w.\-]+)|branch_computations=\{([^}]*)\}"
)
# what may yield a whole KV buffer inside the decode loop without moving
# one: plumbing, the in-place write, control flow that hands the carry on,
# and a kernel (which aliases its input)
_KV_PLUMBING = {
    "parameter", "get-tuple-element", "tuple", "bitcast", "opt-barrier",
    "dynamic-update-slice", "while", "conditional", "call", "custom-call",
}


def kv_ops_in_loops(hlo_text, kv_shapes):
    """``[(computation, instruction line)]``: every instruction inside a
    ``while`` loop of a compiled HLO module (its body and what that calls,
    fusion bodies aside — their values are never materialised) whose
    result holds one of ``kv_shapes`` (``"bf16[8,128,768]"``) and which is
    not plumbing, an in-place ``dynamic-update-slice`` (bare, or the root
    of its fusion) or a move between memory spaces (a ``copy``,
    ``copy-start`` or ``copy-done`` with a memory space, ``S(n)``, at
    either end: the step's one read issued early into fast memory, or a
    small buffer the compiler keeps there). A ``copy`` within HBM, a
    ``pad``, a ``convert`` or any other fusion of that shape is a whole
    buffer produced per step — the fault ISSUE 25 removed."""
    computations, name = {}, None
    for line in hlo_text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            name = m.group(1)
            computations[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            computations[name].append(line)

    def root_op(computation):
        for line in computations.get(computation, ()):
            m = _HLO_INSTRUCTION.match(line)
            if m and m.group(1):
                return m.group(3)
        return None

    todo = [
        m.group(1)
        for lines in computations.values()
        for line in lines
        for m in re.finditer(r"body=%([\w.\-]+)", line)
    ]
    inside, found = set(), []
    while todo:
        name = todo.pop()
        if name in inside or name not in computations:
            continue
        inside.add(name)
        results = {}  # instruction -> its result type, operands aside
        for line in computations[name]:
            for m in _HLO_LOOP_CALLEES.finditer(line):
                todo.extend(
                    [m.group(1)] if m.group(1)
                    else re.findall(r"%([\w.\-]+)", m.group(2))
                )
            m = _HLO_INSTRUCTION.match(line)
            if not m:
                continue
            result, op, rest = m.group(2), m.group(3), m.group(4)
            if op.endswith("-start") and result.startswith("(("):
                # an async op's result leads with its operands' own types
                depth = 0
                for end, ch in enumerate(result[1:], 1):
                    depth += (ch == "(") - (ch == ")")
                    if depth == 0:
                        break
                result = result[end:]
            results[line.split("=")[0].strip().removeprefix("ROOT ")] = result
            if not any(shape in result for shape in kv_shapes):
                continue
            if op in _KV_PLUMBING:
                continue
            if op in ("copy", "copy-start", "copy-done"):
                # a move between memory spaces (either end carries one)
                source = re.match(r"(%[\w.\-]+)", rest)
                ends = result + results.get(source.group(1), "") if source else result
                if "S(" in ends:
                    continue
            if op == "fusion":
                callee = re.search(r"calls=%([\w.\-]+)", rest)
                if callee and root_op(callee.group(1)) == "dynamic-update-slice":
                    continue
            found.append((name, line.strip()[:240]))
    return found


def decode_loop_kv_ops(arch, kv_cache_dtype, *, batch=8, seq_length=112,
                       new_tokens=48):
    """Compile the fixed sampler for the default device at a small shape
    and list what :func:`kv_ops_in_loops` finds in its optimised HLO: a
    whole layer's buffer, or its leading positions at one of the widths the
    decode read takes (112 + 48: 128 and 160; each branch's slice has to
    fuse into the products, never be staged or copied on its own)."""
    import functools

    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.gpt2 import GPT2Config, GPT2Model, init_cache
    from trlx_tpu.models.heads import CausalLMWithValueHead
    from trlx_tpu.ops.kv_cache import decode_read_widths
    from trlx_tpu.ops.sampling import GenerationConfig, make_sampler

    cfg = GPT2Config.from_dict(
        dict(arch, kv_cache_dtype=kv_cache_dtype, dtype="bfloat16")
    )
    model = CausalLMWithValueHead(cfg, backbone_cls=GPT2Model)
    gen = GenerationConfig(
        max_new_tokens=new_tokens, eos_token_id=arch["vocab_size"] - 1,
        pad_token_id=arch["vocab_size"] - 1,
    )

    def apply_fn(params, input_ids, attention_mask=None, position_ids=None,
                 cache=None, cache_index=None, last_only=False):
        return model.apply(
            {"params": params}, input_ids, attention_mask=attention_mask,
            position_ids=position_ids, cache=cache, cache_index=cache_index,
            last_only=last_only,
        )

    sampler = make_sampler(
        apply_fn, functools.partial(init_cache, cfg), gen, seq_length
    )
    params = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    ids = jax.ShapeDtypeStruct((batch, seq_length), jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    text = jax.jit(sampler).lower(params, ids, ids, key).compile().as_text()
    check("while(" in text, "no while loop in the compiled sampler")
    capacity = seq_length + new_tokens
    heads, width = cfg.n_head, cfg.n_embd
    element = "s8" if kv_cache_dtype == "int8" else "bf16"
    return kv_ops_in_loops(text, [
        f"{element}[{batch},{capacity},{heads},{width // heads}]",
        *(f"{element}[{batch},{w},{width}]" for w in decode_read_widths(capacity, seq_length)),
    ])


def leg_decode_loop(arch):
    """The regression guard a CPU test cannot give: in the program the
    chip's compiler makes of the fixed sampler, no operation of the decode
    loop produces a whole KV buffer — bf16 cache and int8 cache."""
    for kv_cache_dtype in ("bfloat16", "int8"):
        found = decode_loop_kv_ops(arch, kv_cache_dtype)
        check(
            not found,
            f"{kv_cache_dtype} cache: the decode loop produces whole KV "
            f"buffers: {found[:4]}",
        )


# ------------------------------ leg 4: serve ------------------------------ #


def leg_serve(arch, mesh, out_dir, checkpoint_dir, *, n_requests=48,
              seq_length=64, new_tokens=48, slots=16):
    """``InferenceServer`` on the training run's checkpoint answers
    ``n_requests`` prompts of mixed lengths through submit / wait."""
    from trlx_tpu.inference.server import SERVE_HISTOGRAMS, InferenceServer
    from trlx_tpu.telemetry.health import without_timing

    config = ppo_config(
        arch, mesh, out_dir, "serve", seq_length=seq_length,
        new_tokens=new_tokens,
    )
    config.train.rollout = {
        "slots": slots, "admit_width": slots // 2, "harvest_width": slots // 2,
    }
    # the first requests wait for the engine programs to compile (minutes,
    # cold): the queue-wait budget covers that, so slo-breach stays a
    # statement about serving, not about XLA
    config.train.serving = {
        "slo_classes": {"standard": {"queue_wait_budget_ms": 1800000}},
    }
    # a request may stop early here, unlike the fixed-length rollouts
    config.method.gen_kwargs["min_new_tokens"] = 1
    server = InferenceServer(config, checkpoint_dir=checkpoint_dir)
    check_placement(server.params, "served params")

    vocab = arch["vocab_size"]
    prompts = make_prompts(vocab, n_requests, seq_length, seed=1)
    half = n_requests // 2
    ids = server.submit(prompts[:half])
    results = server.wait(ids)
    more = server.submit(prompts[half:])
    results.update(server.wait(more))
    ids += more

    for rid in ids:
        out = results.get(rid)
        check(out is not None, f"request {rid} never completed")
        check(out["length"] >= 1, f"request {rid} completed with no token")
        toks = out["tokens"][: out["length"]]
        check(
            all(0 <= int(t) < vocab for t in toks),
            f"request {rid}: token outside vocab {vocab}",
        )
    stalls = [e.message for e in server.health_events if e.detector == "host-stall"]
    if stalls:
        print(f"note host-stall while serving (not part of the check): {stalls}", flush=True)
    events = [e.to_dict() for e in without_timing(server.health_events)]
    check(not events, f"health events while serving: {events}")
    metrics = server.metrics()
    empty = [k for k in SERVE_HISTOGRAMS if not metrics.get(k, {}).get("count")]
    check(not empty, f"serve histograms empty: {empty}")


# ----------------------------- leg 5: kernels ----------------------------- #

_CUSTOM_CALL_SHAPE = re.compile(r"=\s*\(?\s*\w+\[([\d,]+)\]")


def flash_call_shapes(hlo_text):
    """Result shapes of the Mosaic custom calls in a compiled
    (post-partitioning, so per-device) HLO module: a call's first result,
    q-shaped as the kernels hold it ([B, T, H * D], or heads-major
    [B * H, T, D]: ``ops/flash_attention.py::operand_layout``)."""
    shapes = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _CUSTOM_CALL_SHAPE.search(line)
        if m:
            shapes.append(tuple(int(d) for d in m.group(1).split(",")))
    return shapes


def leg_flash_in_train_step(arch, mesh, out_dir, *, seq_length=960,
                            new_tokens=64, batch_size=8, on_tpu=True):
    """One PPO update at total length ``seq_length + new_tokens`` through
    the trainer's own programs (sample -> ref score -> rewards -> train
    step). On the TPU, T = 1024 routes the step's attention to the flash
    kernels: the compiled step must hold Mosaic custom calls whose operands
    are per-device shards of the batch and the heads."""
    import jax
    import numpy as np

    from trlx_tpu.data.ppo_types import PPORolloutBatch
    from trlx_tpu.parallel.mesh import batch_sharding
    from trlx_tpu.utils.loading import get_trainer

    config = ppo_config(
        arch, mesh, out_dir, "flash", seq_length=seq_length,
        new_tokens=new_tokens, batch_size=batch_size,
        num_rollouts=batch_size,
    )
    trainer = get_trainer(config.train.trainer)(
        config, reward_fn=lambda **kw: [0.0]
    )
    rng = np.random.default_rng(2)
    ids = jax.numpy.asarray(
        rng.integers(1, arch["vocab_size"] - 2, size=(batch_size, seq_length)),
        jax.numpy.int32,
    )
    mask = jax.numpy.ones_like(ids)
    out = trainer.sample(ids, mask)
    ref = trainer.score_ref(ids, mask, out.tokens, out.response_mask)
    rewards = trainer.compute_rewards(
        out.logprobs, ref, out.response_mask,
        np.linspace(-1.0, 1.0, batch_size).astype(np.float32),
    )
    batch = jax.device_put(
        PPORolloutBatch(
            query_tokens=ids, query_mask=mask, response_tokens=out.tokens,
            response_mask=out.response_mask, logprobs=out.logprobs,
            values=out.values, rewards=rewards,
        ),
        batch_sharding(trainer.mesh),
    )
    compiled = trainer._train_step_jit.lower(trainer.state, batch).compile()
    if on_tpu:
        shapes = flash_call_shapes(compiled.as_text())
        check(
            shapes,
            f"no tpu_custom_call in the compiled T={seq_length + new_tokens} "
            "train step: its attention did not reach the flash kernels",
        )
        axes = dict(trainer.mesh.shape)
        rows = batch_size // (axes["dp"] * axes["fsdp"])
        heads = arch["n_head"] // axes["tp"]
        T, D = seq_length + new_tokens, arch["n_embd"] // arch["n_head"]
        local = {(rows, T, heads * D), (rows * heads, T, D)}
        gathered = sorted(set(shapes) - local)
        check(
            not gathered,
            f"flash custom calls on mesh {axes} see {gathered}, not the "
            f"per-device {rows} rows x {heads} heads ({sorted(local)}): "
            "operands were gathered",
        )
    trainer.state, stats = compiled(trainer.state, batch)
    loss = float(np.asarray(stats["losses/total_loss"]))
    check(np.isfinite(loss), f"T={seq_length + new_tokens} step loss {loss}")
    check(all_finite(trainer.state.params), "non-finite params after the step")


def _close(a, b, tol, what):
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    scale = max(float(np.abs(b).max()), 1.0)
    err = float(np.abs(a - b).max())
    check(
        np.isfinite(a).all() and err <= tol * scale,
        f"{what}: max |kernel - xla| = {err:.3g} over tolerance "
        f"{tol:g} x {scale:.3g}",
    )


def leg_flash_vs_xla(*, lengths=(1024, 2048), heads=12, depth=64, batch=2,
                     interpret=False):
    """Forward and ``jax.grad`` through ``flash_attention`` at gpt2-small's
    head shape (bf16, causal) against the XLA attention path."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.ops.attention import dot_product_attention
    from trlx_tpu.ops.flash_attention import flash_attention

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=interpret)

    def xla(q, k, v):
        # learned_bias pins dot_product_attention to its XLA path
        return dot_product_attention(
            q, k, v, None, causal=True, learned_bias=True
        )

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v).astype(jnp.float32) ** 2).sum()

    for T in lengths:
        keys = jax.random.split(jax.random.PRNGKey(T), 3)
        q, k, v = (
            jax.random.normal(key, (batch, T, heads, depth), jnp.bfloat16)
            for key in keys
        )
        _close(jax.jit(flash)(q, k, v), jax.jit(xla)(q, k, v), 2e-2,
               f"flash forward T={T}")
        got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
        want = jax.jit(jax.grad(loss(xla), argnums=(0, 1, 2)))(q, k, v)
        for name, a, b in zip("qkv", got, want):
            _close(a, b, 4e-2, f"flash d{name} T={T}")


def leg_flash_blocks(*, length=1024, heads=12, depth=64, batch=2,
                     interpret=False):
    """``flash_block_fwd`` / ``flash_block_bwd`` (the f32 path ring
    attention uses) once with a bias, against the same block math in XLA."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.ops.attention import NEG_INF
    from trlx_tpu.ops.flash_attention import flash_block_bwd, flash_block_fwd

    T = length
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v = (
        jax.random.normal(key, (batch, T, heads, depth), jnp.float32)
        for key in keys[:3]
    )
    do = jax.random.normal(keys[3], (batch, heads, T, depth), jnp.float32)
    # a ring block's bias: causal positions plus one row's padded keys
    pos = jnp.arange(T)
    valid = jnp.ones((batch, T), bool).at[0, T - T // 8:].set(False)
    bias = (
        jnp.where(pos[None, :] <= pos[:, None], 0.0, NEG_INF)[None, None]
        + jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]
    )
    scale = float(depth ** -0.5)

    def xla_fwd(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale + bias
        lse = jax.nn.logsumexp(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bhqd", jnp.exp(s - lse[..., None]), v)
        return o, lse

    def xla_bwd(q, k, v, o, lse, do):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale + bias
        p = jnp.exp(s - lse[..., None])
        delta = jnp.sum(do * o, axis=-1)
        dv = jnp.einsum("bhqk,bhqd->bkhd", p, do)
        ds = p * (jnp.einsum("bhqd,bkhd->bhqk", do, v) - delta[..., None])
        dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k) * scale
        dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q) * scale
        return dq, dk, dv

    o, lse = jax.jit(
        lambda q, k, v: flash_block_fwd(
            q, k, v, bias, scale=scale, interpret=interpret
        )
    )(q, k, v)
    o_ref, lse_ref = jax.jit(xla_fwd)(q, k, v)
    _close(o, o_ref, 2e-2, "flash_block_fwd o")
    _close(lse, lse_ref, 2e-2, "flash_block_fwd lse")
    grads = jax.jit(
        lambda q, k, v, o, lse, do: flash_block_bwd(
            q, k, v, bias, o, lse, do, scale=scale, interpret=interpret
        )
    )(q, k, v, o_ref, lse_ref, do)
    for name, a, b in zip(
        "qkv", grads, jax.jit(xla_bwd)(q, k, v, o_ref, lse_ref, do)
    ):
        _close(a, b, 2e-2, f"flash_block_bwd d{name}")


# --------------------------------- main ----------------------------------- #


def main():
    os.environ.setdefault("WANDB_DISABLED", "1")
    t0 = time.time()
    device = run_leg("1 device gate", leg_device)

    import jax

    from trlx_tpu.utils.compile_cache import enable_compile_cache

    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    cache_dir = enable_compile_cache()
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    print(f"compile cache: {cache_dir}", flush=True)

    checkpoint = run_leg(
        "2 train fixed sampler", leg_train, GPT2_SMALL, DP_MESH, OUT_DIR,
        "fixed", phases=2,
    )
    gc.collect()
    run_leg("2b decode loop copies no KV buffer", leg_decode_loop, GPT2_SMALL)
    run_leg(
        "3 train continuous engine", leg_train, GPT2_SMALL, DP_MESH, OUT_DIR,
        "continuous", engine="continuous",
    )
    gc.collect()
    run_leg("4 serve", leg_serve, GPT2_SMALL, DP_MESH, OUT_DIR, checkpoint)
    gc.collect()
    run_leg(
        "5a flash in train step", leg_flash_in_train_step, GPT2_SMALL,
        DP_MESH, OUT_DIR,
    )
    gc.collect()
    run_leg("5b flash vs xla", leg_flash_vs_xla)
    run_leg("5c flash blocks", leg_flash_blocks)

    print(
        f"all legs ok in {time.time() - t0:.1f}s, "
        f"{_compile_seconds[0]:.1f}s of it in backend compiles",
        flush=True,
    )
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
