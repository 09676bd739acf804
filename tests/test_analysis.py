"""Golden tests for the static-analysis pass (`trlx_tpu/analysis/`).

One seeded-violation fixture per rule asserting the rule fires, plus
clean-repo runs asserting zero findings. The jaxpr fixtures build small
standalone programs (no trainer construction) so each rule is tested in
isolation; one non-slow end-to-end audit covers the PPO trainer, and the
full four-trainer audit runs under the ``slow`` marker.
"""

import subprocess
import sys
import textwrap

import pytest

REPO = __file__.rsplit("/tests/", 1)[0]


# --------------------------- AST-lint fixtures --------------------------- #

def _lint(src, path="fixture.py"):
    from trlx_tpu.analysis.ast_lint import lint_source

    findings, suppressed = lint_source(textwrap.dedent(src), path)
    return findings, suppressed


def test_host_item_fires_in_jitted_fn():
    findings, _ = _lint(
        """
        import jax

        @jax.jit
        def step(x):
            return x.item()
        """
    )
    assert [f.rule for f in findings] == ["host-item"]


def test_host_item_ok_outside_trace():
    findings, _ = _lint(
        """
        def host_loop(x):
            return x.item()
        """
    )
    assert findings == []


def test_host_scalar_cast_fires_and_static_shapes_exempt():
    findings, _ = _lint(
        """
        import jax

        @jax.jit
        def step(x):
            B, T = x.shape
            scale = float(1.0 / (T ** 0.5))  # static: shape-derived
            return float(x.sum()) * scale    # traced value: violation
        """
    )
    assert [f.rule for f in findings] == ["host-scalar-cast"]


def test_host_transfer_fires_via_lax_scan_callee():
    # traced indirectly: the fn is passed to lax.scan, not decorated
    findings, _ = _lint(
        """
        import jax
        import numpy as np

        def body(carry, x):
            return carry, np.asarray(x)

        def outer(xs):
            return jax.lax.scan(body, 0, xs)
        """
    )
    assert [f.rule for f in findings] == ["host-transfer"]


def test_device_get_fires_transitively():
    # body -> helper call chain: helper is traced because body is
    findings, _ = _lint(
        """
        import jax

        def helper(x):
            return jax.device_get(x)

        @jax.jit
        def step(x):
            return helper(x)
        """
    )
    assert [f.rule for f in findings] == ["host-transfer"]


def test_py_random_fires():
    findings, _ = _lint(
        """
        import jax
        import random

        @jax.jit
        def step(x):
            return x * random.random()
        """
    )
    assert [f.rule for f in findings] == ["py-random"]


def test_jax_random_is_not_py_random():
    # `from jax import random` is device RNG — must not trip the rule
    findings, _ = _lint(
        """
        import jax
        from jax import random

        @jax.jit
        def step(key, x):
            return x * random.uniform(key, x.shape)
        """
    )
    assert findings == []


def test_np_in_ops_fires_only_for_ops_paths():
    src = """
    import numpy as np

    def kernel(x):
        return np.tanh(x)
    """
    in_ops, _ = _lint(src, path="trlx_tpu/ops/fixture.py")
    assert [f.rule for f in in_ops] == ["np-in-ops"]
    outside, _ = _lint(src, path="trlx_tpu/utils/fixture.py")
    assert outside == []


def test_inline_suppression_silences_and_counts():
    findings, suppressed = _lint(
        """
        import jax

        @jax.jit
        def step(x):
            return x.item()  # tpu-lint: disable=host-item
        """
    )
    assert findings == []
    assert suppressed == 1


def test_suppression_is_rule_specific():
    findings, suppressed = _lint(
        """
        import jax

        @jax.jit
        def step(x):
            return x.item()  # tpu-lint: disable=py-random
        """
    )
    assert [f.rule for f in findings] == ["host-item"]
    assert suppressed == 0


# -------------------------- jaxpr-audit fixtures ------------------------- #

def test_fp64_rule_fires_on_x64_program():
    import jax
    import jax.numpy as jnp

    from trlx_tpu.analysis.jaxpr_audit import check_no_fp64

    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(
            lambda x: jnp.sum(x * jnp.float64(2.0))
        )(jnp.ones((4,), jnp.float64))
    findings = check_no_fp64(jaxpr, "fixture")
    assert findings and all(f.rule == "fp64" for f in findings)


def test_fp64_rule_clean_on_f32_program():
    import jax
    import jax.numpy as jnp

    from trlx_tpu.analysis.jaxpr_audit import check_no_fp64

    jaxpr = jax.make_jaxpr(lambda x: jnp.sum(x * 2.0))(
        jnp.ones((4,), jnp.float32)
    )
    assert check_no_fp64(jaxpr, "fixture") == []


def _shard_map_psum_jaxpr():
    """A jaxpr whose psum names axis 'model' (valid on its own mesh)."""
    import numpy as np

    import jax
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("model",))
    f = shard_map(
        lambda x: jax.lax.psum(x, "model"),
        mesh=mesh,
        in_specs=P("model"),
        out_specs=P(),
    )
    n = len(jax.devices())
    return jax.make_jaxpr(f)(jax.numpy.ones((n,), jax.numpy.float32))


def test_collective_axis_rule_fires_on_unknown_axis():
    from trlx_tpu.analysis.jaxpr_audit import check_collective_axes

    jaxpr = _shard_map_psum_jaxpr()
    findings = check_collective_axes(
        jaxpr, {"dp", "fsdp", "tp", "sp", "pp", "ep"}, "fixture"
    )
    assert findings and all(f.rule == "collective-axis" for f in findings)
    assert "model" in findings[0].message


def test_collective_axis_rule_clean_on_known_axis():
    from trlx_tpu.analysis.jaxpr_audit import check_collective_axes

    jaxpr = _shard_map_psum_jaxpr()
    assert check_collective_axes(jaxpr, {"model"}, "fixture") == []


def test_donation_rule_fires_without_donate_argnums():
    import jax
    import jax.numpy as jnp

    from trlx_tpu.analysis.jaxpr_audit import check_donation

    def step(state, x):
        return state + x.sum(), x * 2

    x = jnp.ones((4,), jnp.float32)
    undonated = jax.make_jaxpr(jax.jit(step))(jnp.float32(0.0), x)
    findings = check_donation(undonated, 1, "fixture")
    assert [f.rule for f in findings] == ["donation"]

    donated = jax.make_jaxpr(jax.jit(step, donate_argnums=(0,)))(
        jnp.float32(0.0), x
    )
    assert check_donation(donated, 1, "fixture") == []


def test_precision_leak_rule_fires_on_forward_upcast():
    import jax
    import jax.numpy as jnp

    from trlx_tpu.analysis.jaxpr_audit import check_precision_leak

    def forward(x):  # rank-3 bf16 activation upcast mid-forward
        h = x.astype(jnp.float32)
        return (h @ h.transpose(0, 2, 1)).astype(jnp.bfloat16)

    jaxpr = jax.make_jaxpr(forward)(jnp.ones((2, 4, 8), jnp.bfloat16))
    findings = check_precision_leak(
        jaxpr, "fixture", repo_root=REPO.rsplit("/", 1)[0]
    )
    assert findings and all(f.rule == "precision-leak" for f in findings)


def test_precision_leak_ignores_scalar_and_rank2_casts():
    import jax
    import jax.numpy as jnp

    from trlx_tpu.analysis.jaxpr_audit import check_precision_leak

    def forward(x):  # values-style rank-2 cast: allowed
        return x.astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(forward)(jnp.ones((2, 4), jnp.bfloat16))
    assert check_precision_leak(
        jaxpr, "fixture", repo_root=REPO.rsplit("/", 1)[0]
    ) == []


def test_an_audit_that_cannot_read_an_equations_frames_raises(monkeypatch):
    """jax 0.9.0 changed what ``source_info_util.user_frames`` takes; the
    call sat under ``except Exception: return None`` and every located
    finding of four engines was dropped in silence. The seam catches
    nothing now: when jax's reader moves again, the audit raises."""
    import jax
    import jax.numpy as jnp
    from jax._src import source_info_util

    from trlx_tpu.analysis.jaxpr_audit import check_precision_leak
    from trlx_tpu.analysis.nan_flow import analyze_program

    upcast = jax.make_jaxpr(lambda x: x.astype(jnp.float32) * 2)(jnp.ones((2, 4, 8), jnp.bfloat16))
    log = jax.make_jaxpr(lambda x: jnp.log(x))(jnp.ones((4, 8)))
    root = REPO.rsplit("/", 1)[0]
    assert check_precision_leak(upcast, "fixture", repo_root=root)  # both see, unpatched
    assert analyze_program(log, "fixture", repo_root=root)

    def moved(*args, **kwargs):
        raise TypeError("user_frames() takes a Traceback")

    monkeypatch.setattr(source_info_util, "user_frames", moved)
    with pytest.raises(TypeError, match="user_frames"):
        check_precision_leak(upcast, "fixture", repo_root=root)
    with pytest.raises(TypeError, match="user_frames"):
        analyze_program(log, "fixture", repo_root=root)


# ------------------------ partition-rule validation ---------------------- #

def test_partition_rule_unknown_axis_raises_with_path():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from trlx_tpu.parallel import PartitionRuleError, make_mesh
    from trlx_tpu.parallel.partition import make_partition_specs

    mesh = make_mesh({"dp": -1})
    params = {"block": {"kernel": jnp.ones((8, 8))}}
    with pytest.raises(PartitionRuleError) as e:
        make_partition_specs(params, mesh, [(r"kernel", P(None, "model"))])
    assert "block/kernel" in str(e.value)
    assert "model" in str(e.value)


def test_partition_rule_non_divisible_dim_raises_with_path():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from trlx_tpu.parallel import PartitionRuleError, make_mesh
    from trlx_tpu.parallel.partition import make_partition_specs

    if len(jax.devices()) < 2:
        pytest.skip("needs a tp>1 mesh")
    mesh = make_mesh({"dp": -1, "tp": 2})
    params = {"odd": {"kernel": jnp.ones((8, 7))}}  # 7 % 2 != 0
    with pytest.raises(PartitionRuleError) as e:
        make_partition_specs(params, mesh, [(r"kernel", P(None, "tp"))])
    assert "odd/kernel" in str(e.value)


def test_partition_rule_size_one_axis_is_noop():
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from trlx_tpu.parallel import make_mesh
    from trlx_tpu.parallel.partition import make_partition_specs

    mesh = make_mesh({"dp": -1, "tp": 1})
    params = {"odd": {"kernel": jnp.ones((8, 7))}}
    specs = make_partition_specs(
        params, mesh, [(r"kernel", P(None, "tp"))], min_shard_size=1 << 30
    )
    assert specs["odd"]["kernel"] == P()


def test_registered_family_rules_are_mesh_valid():
    from trlx_tpu.analysis.harness import audit_mesh
    from trlx_tpu.analysis.jaxpr_audit import check_partition_specs

    findings, covered = check_partition_specs(audit_mesh())
    assert findings == []
    assert len(covered) == 6  # all registered families


# --------------------------- end-to-end audits --------------------------- #

def test_clean_repo_ast_run():
    from trlx_tpu.analysis import run

    report = run(engine="ast", paths=[f"{REPO}/trlx_tpu"])
    assert report.findings == [], report.format_text()


def test_ppo_trainer_audit_clean_and_covers_step():
    from trlx_tpu.analysis.jaxpr_audit import audit_trainers

    report = audit_trainers(["ppo"])
    assert "ppo.train_step" in report.covered
    assert "ppo.rollout" in report.covered
    assert report.findings == [], report.format_text()


@pytest.mark.slow
def test_full_audit_all_trainers_clean():
    from trlx_tpu.analysis.jaxpr_audit import audit_trainers

    report = audit_trainers()
    for kind in ("ppo", "ilql", "grpo", "seq2seq"):
        assert f"{kind}.train_step" in report.covered
    assert report.findings == [], report.format_text()


@pytest.mark.slow
def test_cli_strict_nonzero_on_seeded_fixture(tmp_path):
    fixture = tmp_path / "bad.py"
    fixture.write_text(
        "import jax\n\n@jax.jit\ndef step(x):\n    return x.item()\n"
    )
    proc = subprocess.run(
        [
            sys.executable, "-m", "trlx_tpu.analysis", "--engine", "ast",
            "--strict", "--paths", str(fixture),
        ],
        cwd=REPO,
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "host-item" in proc.stdout
