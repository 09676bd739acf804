"""Golden tests for the PR-2 analysis engines: NaN-source dataflow
(`nan_flow`), eqn-level sanitizer replay (`sanitizer`), and
collective-sequence divergence (`collective_trace`) + the host-branch AST
rule.

One seeded-violation + clean-pass pair per NaN-flow pattern; the
sanitizer on a toy jaxpr with a planted 0/0 (plus scan-iteration
attribution); collective divergence on two hand-built jaxprs with
mismatched psum sequences. Trainer-building end-to-end runs live under
the ``slow`` marker (the per-rule fixtures here stay compile-free)."""

import os
import textwrap

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _analyze(fn, *args, facts=None):
    import jax

    from trlx_tpu.analysis.nan_flow import analyze_program

    jaxpr = jax.make_jaxpr(fn)(*args)
    # repo_root=HERE: the fixture frames live in this test file, which
    # matches no NAN_ALLOWLIST entry
    return analyze_program(jaxpr, "fixture", repo_root=HERE, in_facts=facts)


# --------------------------- nan-flow patterns --------------------------- #

def test_nanflow_unguarded_div_fires_and_eps_guard_passes():
    import jax.numpy as jnp

    ones = jnp.ones((4,))
    bad = _analyze(lambda a, b: a / jnp.sum(b), ones, ones)
    assert [f.rule for f in bad] == ["nan-unguarded"]
    ok = _analyze(lambda a, b: a / (jnp.sum(b * b) + 1e-6), ones, ones)
    assert ok == []


def test_nanflow_unclipped_exp_fires_and_clip_guard_passes():
    import jax.numpy as jnp

    ones = jnp.ones((4,))
    bad = _analyze(lambda x: jnp.exp(x), ones)
    assert [f.rule for f in bad] == ["nan-unguarded"]
    assert "overflow" in bad[0].message
    ok = _analyze(lambda x: jnp.exp(jnp.clip(x, -30.0, 30.0)), ones)
    assert ok == []


def test_nanflow_eps_free_rsqrt_fires_and_eps_guard_passes():
    import jax
    import jax.numpy as jnp

    ones = jnp.ones((4,))
    bad = _analyze(lambda x: jax.lax.rsqrt(x), ones)
    assert [f.rule for f in bad] == ["nan-unguarded"]
    ok = _analyze(lambda x: jax.lax.rsqrt(jnp.mean(x * x) + 1e-8), ones)
    assert ok == []


def test_nanflow_unguarded_log_fires_and_softmax_shift_passes():
    import jax
    import jax.numpy as jnp

    ones = jnp.ones((4, 8))
    bad = _analyze(lambda x: jnp.log(x), ones)
    assert [f.rule for f in bad] == ["nan-unguarded"]

    def logsumexp_style(x):
        shifted = x - jax.lax.stop_gradient(
            jnp.max(x, axis=-1, keepdims=True)
        )
        return jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))

    assert _analyze(logsumexp_style, ones) == []


def test_nanflow_where_grad_trap_fires_with_dedicated_rule():
    import jax.numpy as jnp

    ones = jnp.ones((4,))
    bad = _analyze(
        lambda x, m: jnp.where(m > 0, jnp.log(x), 0.0), ones, ones
    )
    assert [f.rule for f in bad] == ["where-grad-trap"]
    ok = _analyze(
        lambda x, m: jnp.where(m > 0, jnp.log(jnp.maximum(x, 1e-8)), 0.0),
        ones, ones,
    )
    assert ok == []


def test_nanflow_inf_masked_softmax_fires_and_unmasked_passes():
    import jax
    import jax.numpy as jnp

    ones = jnp.ones((4, 8))

    def masked_softmax(x, m):
        x = jnp.where(m > 0, x, -jnp.inf)
        s = x - jax.lax.stop_gradient(jnp.max(x, axis=-1, keepdims=True))
        e = jnp.exp(s)
        return e / jnp.sum(e, axis=-1, keepdims=True)

    bad = _analyze(masked_softmax, ones, ones)
    assert [f.rule for f in bad] == ["inf-mask-softmax"]

    def plain_softmax(x):
        s = x - jax.lax.stop_gradient(jnp.max(x, axis=-1, keepdims=True))
        e = jnp.exp(s)
        return e / jnp.sum(e, axis=-1, keepdims=True)

    assert _analyze(plain_softmax, ones) == []


def test_nanflow_input_facts_guard_masked_whitening():
    """whiten(x, mask)-style math is provable only with the mask's 0/1
    data contract seeded at the program boundary."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.analysis.nan_flow import Fact, input_facts

    def whiten_like(x, mask):
        n = jnp.maximum(jnp.sum(mask), 1.0)
        mean = jnp.sum(x * mask) / n
        centered = x - mean
        var = jnp.sum(centered * centered * mask) / n
        return centered * jax.lax.rsqrt(var + 1e-8)

    ones = jnp.ones((4,))
    # without facts the mask product can be negative -> rsqrt unproven
    assert len(_analyze(whiten_like, ones, ones)) == 1
    facts = input_facts(["batch.x", "batch.response_mask"])
    assert facts[1] == Fact(lo=0.0, hi=1.0)
    assert _analyze(whiten_like, ones, ones, facts=facts) == []


def test_nanflow_repo_ppo_loss_is_guarded():
    """The shipped PPO loss (post log-ratio clamp) analyzes clean with
    batch-contract facts — the regression test for the fsdp/tp guard."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.analysis.nan_flow import Fact, analyze_program
    from trlx_tpu.ops.ppo_math import ppo_loss

    B, R = 4, 6
    f32 = lambda: jnp.ones((B, R), jnp.float32)

    def loss(logprobs, values, old_logprobs, old_values, adv, ret, mask):
        return ppo_loss(
            logprobs, values, old_logprobs, old_values, adv, ret, mask,
            cliprange=0.2, cliprange_value=0.2, vf_coef=1.0,
        )[0]

    jaxpr = jax.make_jaxpr(loss)(*([f32()] * 7))
    mask_fact = Fact(lo=0.0, hi=1.0)
    facts = [Fact(hi=0.0), Fact(), Fact(hi=0.0), Fact(), Fact(), Fact(),
             mask_fact]
    findings = analyze_program(
        jaxpr, "ppo_loss", repo_root=REPO, in_facts=facts
    )
    assert findings == [], [f.format_text() for f in findings]


# ------------------------------ sanitizer -------------------------------- #

def test_sanitizer_localizes_planted_zero_div():
    import jax
    import jax.numpy as jnp

    from trlx_tpu.analysis.sanitizer import sanitize_jaxpr

    def f(x, y):
        a = x + 1.0
        b = a / y  # 0/0 when x == -1, y == 0
        return jnp.sum(b * 2.0)

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4,)), jnp.ones((4,)))
    res = sanitize_jaxpr(
        jaxpr,
        [jnp.full((4,), -1.0), jnp.zeros((4,))],
        subject="toy",
        arg_names=["x", "y"],
    )
    assert not res.clean
    assert res.offence.primitive == "div"
    assert res.offence.kind == "nan"
    assert "y" in res.offence.input_paths


def test_sanitizer_clean_on_healthy_values():
    import jax
    import jax.numpy as jnp

    from trlx_tpu.analysis.sanitizer import sanitize_jaxpr

    def f(x, y):
        return jnp.sum((x + 1.0) / y)

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4,)), jnp.ones((4,)))
    res = sanitize_jaxpr(jaxpr, [jnp.ones((4,)), jnp.ones((4,))], "toy")
    assert res.clean
    assert "clean" in res.format_text()


def test_sanitizer_reports_scan_iteration():
    import jax
    import jax.numpy as jnp

    from trlx_tpu.analysis.sanitizer import sanitize_jaxpr

    def g(xs):
        def body(c, x):
            return c, jnp.log(x)

        return jax.lax.scan(body, 0.0, xs)

    xs = jnp.asarray([1.0, 2.0, -3.0, 4.0])
    jaxpr = jax.make_jaxpr(g)(xs)
    res = sanitize_jaxpr(jaxpr, [xs], "scan-toy")
    assert not res.clean
    assert res.offence.primitive == "log"
    assert res.offence.iteration == 2


def test_sanitizer_inf_mask_fill_is_not_an_offence():
    """-inf mask fills are intentional; only NaN (or inf minted from
    finite inputs) counts."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.analysis.sanitizer import sanitize_jaxpr

    def f(x, m):
        masked = jnp.where(m > 0, x, -jnp.inf)
        s = masked - jax.lax.stop_gradient(
            jnp.max(masked, axis=-1, keepdims=True)
        )
        e = jnp.exp(s)
        return e / jnp.sum(e, axis=-1, keepdims=True)

    x = jnp.ones((2, 4))
    m = jnp.asarray([[1, 1, 0, 0], [1, 0, 1, 0]], jnp.int32)
    jaxpr = jax.make_jaxpr(f)(x, m)
    res = sanitize_jaxpr(jaxpr, [x, m], "masked-softmax")
    assert res.clean, res.format_text()


@pytest.mark.slow
def test_sanitizer_trainer_planted_nan_names_param_path():
    from trlx_tpu.analysis.sanitizer import sanitize_trainer

    res = sanitize_trainer("ppo", plant=True)
    assert not res.clean
    assert any("state.params" in p for p in res.offence.input_paths)
    assert res.offence.file  # source provenance attached


# --------------------------- collective trace ---------------------------- #

def _psum_sequence_jaxpr(axis_ops):
    """Hand-build a jaxpr whose named-collective sequence is ``axis_ops``
    (list of psum axis names) over a 1-axis mesh per name."""
    import numpy as np

    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()), ("ax",))

    def f(x):
        for _ in axis_ops:
            x = jax.lax.psum(x, "ax")
        return x

    n = len(jax.devices())
    return jax.make_jaxpr(
        shard_map(f, mesh=mesh, in_specs=P("ax"), out_specs=P())
    )(jax.numpy.ones((n,), jax.numpy.float32))


def test_collective_divergence_fires_on_mismatched_psum_sequences():
    from trlx_tpu.analysis.collective_trace import (
        check_sequences,
        collective_sequence,
    )

    # (recent JAX lowers a replicated-operand psum as pbroadcast+psum2,
    # so the raw sequences are longer than the source-level psum count —
    # what matters is that the two schedules differ)
    two = collective_sequence(_psum_sequence_jaxpr(["ax", "ax"]))
    three = collective_sequence(_psum_sequence_jaxpr(["ax", "ax", "ax"]))
    assert len(two) < len(three)
    findings = check_sequences(
        {"mesh-a": two, "mesh-b": three}, "fixture"
    )
    assert [f.rule for f in findings] == ["collective-divergence"]
    assert "position" in findings[0].message


def test_collective_divergence_clean_up_to_axis_renaming():
    from trlx_tpu.analysis.collective_trace import canonicalize, check_sequences

    a = [("psum", ("dp",), ""), ("all_gather", ("dp", "tp"), "")]
    b = [("psum", ("x",), ""), ("all_gather", ("x", "y"), "")]
    assert canonicalize(a) == canonicalize(b)
    assert check_sequences({"m1": a, "m2": b}, "fixture") == []


def test_collective_divergence_detects_axis_structure_mismatch():
    from trlx_tpu.analysis.collective_trace import check_sequences

    a = [("psum", ("dp", "fsdp"), "")]
    b = [("psum", ("x",), "")]
    findings = check_sequences({"m1": a, "m2": b}, "fixture")
    assert [f.rule for f in findings] == ["collective-divergence"]


@pytest.mark.slow
def test_collective_schedule_identical_across_ppo_mesh_matrix():
    from trlx_tpu.analysis.collective_trace import check_trainer

    findings, covered = check_trainer("ppo")
    assert findings == [], [f.message for f in findings]
    assert len(covered) == 4


# ----------------------------- host-branch ------------------------------- #

def _lint(src, path="fixture.py"):
    from trlx_tpu.analysis.ast_lint import lint_source

    return lint_source(textwrap.dedent(src), path)


def test_host_branch_fires_on_stats_subscript_condition():
    findings, _ = _lint(
        """
        def learn(self):
            step_stats = self.fetch()
            if step_stats["losses/total_loss"] > 10:
                self.save()
        """
    )
    assert [f.rule for f in findings] == ["host-branch"]


def test_host_branch_fires_on_float_of_device_value():
    findings, _ = _lint(
        """
        def learn(loss):
            while float(loss) > 0.5:
                loss = train()
        """
    )
    assert [f.rule for f in findings] == ["host-branch"]


def test_host_branch_ignores_step_counters_and_traced_code():
    findings, _ = _lint(
        """
        import jax

        def learn(self, iv):
            if iv["do_eval"]:
                self.evaluate()
            if int(self.state.step) >= 10:
                return

        @jax.jit
        def step(x, stats):
            return x
        """
    )
    assert findings == []


def test_host_branch_assignment_is_not_a_branch():
    findings, _ = _lint(
        """
        def learn(self, scores):
            stats = {}
            stats["reward/mean"] = float(scores.mean())
            return stats
        """
    )
    assert findings == []


# ------------------------------- registry -------------------------------- #

def test_new_rules_are_registered_with_engines():
    from trlx_tpu.analysis.registry import all_rules, get_rule

    by_id = {r.id: r for r in all_rules()}
    assert by_id["nan-unguarded"].engine == "nanflow"
    assert by_id["where-grad-trap"].engine == "nanflow"
    assert by_id["inf-mask-softmax"].engine == "nanflow"
    assert by_id["collective-divergence"].engine == "collective"
    assert by_id["sanitizer-nonfinite"].engine == "sanitizer"
    assert by_id["host-branch"].engine == "ast"
    assert get_rule("nan-unguarded").severity == "error"


def test_nanflow_findings_honor_inline_suppression():
    """nanflow findings carry source locations, so the shared
    `# tpu-lint: disable=` machinery applies to them unchanged."""
    from trlx_tpu.analysis.findings import Finding, filter_suppressed

    finding = Finding(
        rule="nan-unguarded", message="x", file="f.py", line=2,
        engine="nanflow",
    )
    kept, suppressed = filter_suppressed(
        [finding],
        {"f.py": ["", "y = x / z  # tpu-lint: disable=nan-unguarded"]},
    )
    assert kept == [] and suppressed == 1
