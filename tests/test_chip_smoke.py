"""`chip_smoke.py` and the no-hidden-fallback contracts around it.

The tier-1 part builds no trainer: the device gate, the compile-cache
placement, the peaks lookup and the narrowed engine fallback are host-side
decisions, and the one compiled test (the flash kernels sharded over the
program's mesh, interpret mode, ~1.5 s) is what the four-chip legs rely on.
The toy-width CPU rehearsal of the smoke's legs (the same functions the chip
run calls) is marked slow.
"""

import os
from types import SimpleNamespace

import jax
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_device_gate_refuses_cpu(capsys):
    # main() raises at leg 1 — an uncaught exception is the nonzero exit —
    # before it prints a result or touches its output directory
    with pytest.raises(RuntimeError, match="no TPU"):
        chip_smoke.main()
    out = capsys.readouterr().out
    assert '"ok"' not in out and "leg" not in out


def test_unknown_device_kind_raises_in_peaks_lookup():
    from trlx_tpu.telemetry.attribution import (
        BF16_PEAK_TFLOPS,
        HBM_PEAK_GBPS,
        device_peaks,
    )

    assert set(BF16_PEAK_TFLOPS) == set(HBM_PEAK_GBPS)
    assert device_peaks("TPU v5 lite") == (197.0, 819.0)
    for unknown in ("cpu", "TPU v99"):
        with pytest.raises(ValueError, match="no published peaks"):
            device_peaks(unknown)


def test_compile_cache_helper_placement(monkeypatch):
    from trlx_tpu.utils import compile_cache

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updates.append((name, value))
    )
    # an exported directory is jax's business: the helper sets nothing
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, "/placed/from/outside")
    assert compile_cache.enable_compile_cache() == "/placed/from/outside"
    assert updates == []
    # unset: one fixed directory inside the checkout, ignored by git
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV)
    placed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == placed
    assert compile_cache.enable_compile_cache() == placed  # same again
    assert updates == [("jax_compilation_cache_dir", placed)] * 2
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def _fake_orchestrator(engine_error):
    from trlx_tpu.orchestrator.ppo_orchestrator import PPOOrchestrator

    cleared = []
    trainer = SimpleNamespace(
        rollout_engine="continuous",
        _rollout_engine_obj=object(),
        async_config=None,
        _stream=None,
        events=[],
        buffer=SimpleNamespace(clear_history=lambda: cleared.append(True)),
    )
    trainer.emit_health_event = lambda **kw: trainer.events.append(kw)
    orch = object.__new__(PPOOrchestrator)
    orch.trainer = trainer
    orch._engine_error = None

    def engine_phase(num_rollouts, iter_count):
        raise engine_error

    orch._make_experience_continuous = engine_phase
    orch._make_experience_fixed = lambda n, i: "collected on the fixed sampler"
    return orch, trainer, cleared


def test_engine_fallback_reraises_non_transient_error():
    # what a Mosaic/XLA compile error, an out-of-memory or a refused shape
    # looks like from the orchestrator: not an OSError
    error = RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")
    orch, trainer, cleared = _fake_orchestrator(error)
    with pytest.raises(RuntimeError) as raised:
        orch.make_experience(8, 0)
    assert raised.value is error
    assert trainer.rollout_engine == "continuous"
    assert trainer._rollout_engine_obj is not None
    assert not trainer.events and not cleared


def test_engine_fallback_still_degrades_on_transient_error():
    orch, trainer, cleared = _fake_orchestrator(TimeoutError("admission"))
    assert orch.make_experience(8, 0) == "collected on the fixed sampler"
    assert trainer.rollout_engine == "fixed"
    assert trainer._rollout_engine_obj is None
    assert [e["detector"] for e in trainer.events] == ["engine-fallback"]
    assert cleared


def test_traced_on_declares_the_mesh_for_the_trace_only():
    from trlx_tpu.parallel.mesh import program_mesh, traced_on

    outer, inner = object(), object()

    def train_step(x):
        return program_mesh(), x

    scoped = traced_on(outer, train_step)
    assert scoped.__name__ == "train_step"  # compile logs key on the name
    assert program_mesh() is None
    assert scoped(3) == (outer, 3)
    # nested programs (an actor subset inside a learner's trace) each see
    # their own mesh, and the declaration unwinds even through an error
    assert traced_on(outer, lambda: traced_on(inner, program_mesh)())() is inner
    with pytest.raises(ZeroDivisionError):
        traced_on(outer, lambda: 1 / 0)()
    assert program_mesh() is None


# what the chip's compiler made of the decode loop before and after ISSUE 25,
# cut to the lines the guard reads: the parent copied the carried buffer into
# and out of its update fusion at every step (and around each `cond` branch)
_LOOP_HLO = """\
HloModule jit_sampler

%fused_dus (p0: bf16[8,128,768], p1: bf16[8,1,768], p2: s32[]) -> bf16[8,128,768] {
  %p0 = bf16[8,128,768]{2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %dus = bf16[8,128,768]{2,1,0:T(8,128)(2,1)} dynamic-update-slice(%p0, %p1, %c, %p2, %c)
}

%fused_read (p0: bf16[8,128,768]) -> f32[8,12,128] {
  %p0 = bf16[8,128,768]{2,1,0:T(8,128)(2,1)} parameter(0)
  %inside = bf16[8,128,768]{2,1,0:T(8,128)(2,1)} bitcast(%p0)
  ROOT %dot = f32[8,12,128]{2,1,0:T(8,128)} convolution(%q, %inside)
}

%branch_run (arg: (bf16[8,128,768])) -> (bf16[8,128,768]) {
  %arg = (bf16[8,128,768]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %gte.9 = bf16[8,128,768]{2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=0
  BRANCH_LINE
  ROOT %t = (bf16[8,128,768]{2,1,0:T(8,128)(2,1)}) tuple(%gte.9)
}

%body (carry: (s32[], bf16[8,128,768])) -> (s32[], bf16[8,128,768]) {
  %carry = (s32[]{:T(128)}, bf16[8,128,768]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %gte.1 = bf16[8,128,768]{2,1,0:T(8,128)(2,1)} get-tuple-element(%carry), index=1
  %write = bf16[8,128,768]{2,1,0:T(8,128)(2,1)} fusion(%gte.1, %new, %t), kind=kLoop, calls=%fused_dus
  %copy-start.1 = (bf16[8,128,768]{2,1,0:T(8,128)(2,1)S(1)}, bf16[8,128,768]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%write)
  %copy-done.1 = bf16[8,128,768]{2,1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.1)
  %slice-start.1 = ((bf16[8,128,768]{2,1,0:T(8,128)(2,1)}), bf16[8,32,768]{2,1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) slice-start(%write), slice={[0:8], [0:32], [0:768]}
  %scores = f32[8,12,128]{2,1,0:T(8,128)} fusion(%copy-done.1), kind=kOutput, calls=%fused_read
  %cond.1 = (bf16[8,128,768]{2,1,0:T(8,128)(2,1)}) conditional(%p, %a, %a), branch_computations={%branch_run, %branch_run}
  BODY_LINE
  ROOT %out = (s32[]{:T(128)}, bf16[8,128,768]{2,1,0:T(8,128)(2,1)}) tuple(%t1, %write)
}

%cond (carry: (s32[], bf16[8,128,768])) -> pred[] {
  %carry = (s32[]{:T(128)}, bf16[8,128,768]{2,1,0:T(8,128)(2,1)}) parameter(0)
  ROOT %lt = pred[]{:T(512)} compare(%t, %r), direction=LT
}

ENTRY %main (p: bf16[8,112,768]) -> bf16[8,128,768] {
  %prefill = bf16[8,128,768]{2,1,0:T(8,128)(2,1)} copy(%padded)
  %loop = (s32[]{:T(128)}, bf16[8,128,768]{2,1,0:T(8,128)(2,1)}) while(%init), condition=%cond, body=%body
  ROOT %r = bf16[8,128,768]{2,1,0:T(8,128)(2,1)} get-tuple-element(%loop), index=1
}
"""
_KV_COPY = "%copy.7 = bf16[8,128,768]{2,1,0:T(8,128)(2,1)} copy(%gte.1)"
_KV_CONVERT = (
    "%f.3 = bf16[8,128,768]{2,1,0:T(8,128)(2,1)} fusion(%gte.9), kind=kLoop, "
    "calls=%fused_read"
)


@pytest.mark.parametrize(
    "body_line,branch_line,expected",
    [
        ("", "", []),  # in-place write, prefetch, plumbing: clean
        (_KV_COPY, "", [("body", _KV_COPY)]),  # the per-step copy
        ("", _KV_CONVERT, [("branch_run", _KV_CONVERT)]),  # inside a cond
    ],
    ids=["clean", "copy_in_body", "fusion_in_cond_branch"],
)
def test_kv_ops_in_loops_reads_compiled_hlo(body_line, branch_line, expected):
    text = _LOOP_HLO.replace("BODY_LINE", body_line).replace(
        "BRANCH_LINE", branch_line
    )
    found = chip_smoke.kv_ops_in_loops(
        text, ["bf16[8,128,768]", "bf16[8,128,12,64]"]
    )
    assert found == expected
    # the prefill's copy, outside any loop, is not the guard's business
    assert all("%prefill" not in line for _, line in found)


@pytest.mark.slow  # ~1.5 min: two toy PPO runs, a server and the kernels
def test_cpu_rehearsal_of_the_legs(tmp_path):
    toy = {
        "vocab_size": 128, "n_positions": 128, "n_embd": 64, "n_layer": 3,
        "n_head": 4, "kv_cache_dtype": "auto",
    }
    mesh = chip_smoke.DP_MESH
    out = str(tmp_path)
    sizes = dict(seq_length=16, new_tokens=8, batch_size=8, num_rollouts=32)
    checkpoint = chip_smoke.leg_train(
        toy, mesh, out, "fixed", phases=2, **sizes
    )
    # the chip's compiler decides what the guard finds; here only that
    # the sampler compiles and the reader runs over its HLO
    for kv in ("bfloat16", "int8"):
        found = chip_smoke.decode_loop_kv_ops(
            toy, kv, batch=4, seq_length=16, new_tokens=8
        )
        assert isinstance(found, list)
    chip_smoke.leg_train(
        toy, mesh, out, "continuous", engine="continuous", **sizes
    )
    chip_smoke.leg_serve(
        toy, mesh, out, checkpoint, n_requests=12, seq_length=16,
        new_tokens=8, slots=16,
    )
    chip_smoke.leg_flash_in_train_step(
        toy, mesh, out, seq_length=24, new_tokens=8, batch_size=8,
        on_tpu=False,
    )
    chip_smoke.leg_flash_vs_xla(
        lengths=(64,), heads=2, depth=16, batch=1, interpret=True
    )
    chip_smoke.leg_flash_blocks(
        length=64, heads=2, depth=16, batch=1, interpret=True
    )


def test_flash_route_shards_over_the_program_mesh():
    """What the chip's four-device legs rely on: inside a program whose
    mesh was declared with ``traced_on``, the flash kernels run under a
    shard_map — batch over dp x fsdp, heads over tp — and match the XLA
    path, forward and gradients (padded query rows masked out of the loss,
    as training does)."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from trlx_tpu.ops.attention import (
        dot_product_attention,
        flash_on_program_mesh,
        padding_bias,
    )
    from trlx_tpu.parallel.mesh import make_mesh, program_mesh, traced_on

    mesh = make_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    rng = np.random.default_rng(0)
    B, T, H, D = 8, 32, 4, 16
    spec = P(("dp", "fsdp"), None, "tp", None)
    q, k, v = (
        jax.device_put(
            jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32),
            NamedSharding(mesh, spec),
        )
        for _ in range(3)
    )
    mask = np.ones((B, T), np.int32)
    mask[1, :5] = 0
    mask = jax.device_put(
        jnp.asarray(mask), NamedSharding(mesh, P(("dp", "fsdp")))
    )

    def loss(attend):
        def f(q, k, v, mask):
            out = attend(q, k, v, padding_bias(mask))
            return ((out * mask[:, :, None, None]) ** 2).sum(), out

        return jax.grad(f, argnums=(0, 1, 2), has_aux=True)

    def flash(q, k, v, bias):
        assert program_mesh() is mesh
        return flash_on_program_mesh(
            q, k, v, bias, causal=True, interpret=True
        )

    def xla(q, k, v, bias):
        return dot_product_attention(q, k, v, bias, causal=True)

    grads, out = jax.jit(traced_on(mesh, loss(flash)))(q, k, v, mask)
    want_grads, want = jax.jit(loss(xla))(q, k, v, mask)
    assert program_mesh() is None  # the declaration ends with the trace
    assert out.sharding.spec == spec
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
