"""Golden tests for engines 6-7 (`resource_audit.py`, `donation.py`).

PR-1/PR-2 pattern: one seeded-violation fixture + a clean case per rule
(small standalone jitted programs, no trainer construction), suppression
coverage for every new rule id, one non-slow end-to-end check of the PPO
trainer against the committed budget lockfile, and the full-CLI strict
run under the ``slow`` marker.
"""

import json
import subprocess
import sys
import textwrap
from functools import partial

import pytest

REPO = __file__.rsplit("/tests/", 1)[0]


def _jxp(fn, *args, **jit_kwargs):
    import jax

    return jax.make_jaxpr(jax.jit(fn, **jit_kwargs))(*args)


# ----------------------- peak-HBM liveness fixtures ---------------------- #

def test_peak_hbm_donation_is_in_place_reuse():
    import jax.numpy as jnp

    from trlx_tpu.analysis import resource_audit as ra

    x = jnp.zeros((256, 256), jnp.float32)  # 256 KiB
    fn = lambda x: x * 2.0 + 1.0
    donating = ra.analyze_closed_jaxpr(_jxp(fn, x, donate_argnums=(0,)), "d")
    pinned = ra.analyze_closed_jaxpr(_jxp(fn, x), "p")
    # without donation the input is caller-owned for the whole program:
    # peak carries input + intermediate + output; donation lets the input
    # die at its last use (XLA's in-place reuse) — one buffer less
    assert donating.donated_bytes == x.nbytes
    assert pinned.donated_bytes == 0
    assert pinned.peak_hbm_bytes - donating.peak_hbm_bytes == x.nbytes


def test_peak_hbm_sharding_divisors_divide_input_bytes():
    import jax.numpy as jnp

    from trlx_tpu.analysis import resource_audit as ra

    x = jnp.zeros((8, 128), jnp.float32)
    closed = _jxp(lambda x: x.sum(), x)
    replicated = ra.analyze_closed_jaxpr(closed, "s")
    sharded = ra.analyze_closed_jaxpr(closed, "s", input_divisors=[4])
    assert replicated.input_bytes == x.nbytes
    assert sharded.input_bytes == x.nbytes // 4
    assert sharded.peak_hbm_bytes < replicated.peak_hbm_bytes


def test_peak_hbm_scales_with_buffer_size():
    # the monotonicity the budget gate relies on
    import jax.numpy as jnp

    from trlx_tpu.analysis import resource_audit as ra

    def peak(n):
        x = jnp.zeros((n, n), jnp.float32)
        return ra.analyze_closed_jaxpr(
            _jxp(lambda x: (x * 2.0).sum(), x), "fx.step"
        ).peak_hbm_bytes

    assert peak(128) > peak(64) > 0


# ------------------------------ FLOP fixtures ---------------------------- #

def test_flop_count_dot_general_exact():
    import jax.numpy as jnp

    from trlx_tpu.analysis import resource_audit as ra

    closed = _jxp(lambda a, b: a @ b, jnp.zeros((4, 8)), jnp.zeros((8, 16)))
    assert ra.analyze_closed_jaxpr(closed, "dot").flops == 2 * 4 * 8 * 16


def test_flop_count_scan_multiplies_by_length():
    import jax
    import jax.numpy as jnp

    from trlx_tpu.analysis import resource_audit as ra

    def body(c, _):
        return c @ jnp.zeros((8, 8)), None

    closed = _jxp(
        lambda c: jax.lax.scan(body, c, None, length=5), jnp.zeros((4, 8))
    )
    assert ra.analyze_closed_jaxpr(closed, "scan").flops == 5 * 2 * 4 * 8 * 8


# -------------------------- collective cost model ------------------------ #

def test_collective_cost_model_counts_and_ring_bytes():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from trlx_tpu.analysis import resource_audit as ra
    from jax import shard_map
    from trlx_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": -1, "fsdp": 1, "tp": 1})
    n = mesh.shape["dp"]

    @partial(shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P())
    def f(x):
        return jax.lax.psum(x, "dp")

    closed = jax.make_jaxpr(f)(jnp.zeros((n, 4), jnp.float32))
    res = ra.analyze_closed_jaxpr(closed, "psum", axis_sizes={"dp": n})
    (key,) = [k for k in res.collectives if k.startswith("psum")]
    assert res.collectives[key]["count"] == 1
    # per-device shard is (1, 4) f32 = 16 B; ring all-reduce moves
    # 2*(n-1)/n of the payload per device
    assert res.collectives[key]["bytes"] == int(2 * (n - 1) / n * 16)
    assert res.collective_bytes == res.collectives[key]["bytes"]


def test_collective_moved_bytes_factors():
    from trlx_tpu.analysis.resource_audit import _moved_bytes

    assert _moved_bytes("psum", 1000, 4) == 1500  # 2(n-1)/n of full input
    # all_gather's operand is the PRE-gather shard: (n-1) shards moved
    assert _moved_bytes("all_gather", 1000, 4) == 3000
    assert _moved_bytes("reduce_scatter", 1000, 4) == 750  # (n-1)/n
    assert _moved_bytes("ppermute", 1000, 4) == 1000  # one hop
    assert _moved_bytes("psum", 1000, 1) == 0  # size-1 axis moves nothing


# ------------------------------ budget gate ------------------------------ #

def _resources_pair():
    """(small, inflated) resources for the same subject — the inflated
    program carries a 4x bigger live buffer."""
    import jax.numpy as jnp

    from trlx_tpu.analysis import resource_audit as ra

    def prog(n):
        x = jnp.zeros((n, n), jnp.float32)
        return ra.analyze_closed_jaxpr(
            _jxp(lambda x: (x * 2.0).sum(), x), "fx.step"
        )

    return prog(64), prog(128)


def test_hbm_over_budget_fires_on_inflated_buffer():
    from trlx_tpu.analysis import resource_audit as ra

    small, big = _resources_pair()
    budgets = ra.make_budgets([small], {"dp": 8})
    assert ra.check_budgets([small], budgets, {"dp": 8}) == []
    findings = ra.check_budgets([big], budgets, {"dp": 8})
    assert [f.rule for f in findings] == ["hbm-over-budget"]
    assert findings[0].severity == "error"
    assert "fx.step" in findings[0].message


def test_hbm_budget_tolerance_absorbs_small_growth():
    from trlx_tpu.analysis import resource_audit as ra

    small, _ = _resources_pair()
    budgets = ra.make_budgets([small], {"dp": 8})
    # shrink the committed number by just under the 5% default tolerance
    entry = budgets["programs"]["fx.step"]
    entry["peak_hbm_bytes"] = int(entry["peak_hbm_bytes"] / 1.04)
    assert ra.check_budgets([small], budgets, {"dp": 8}) == []
    # a per-program tolerance override tightens the gate
    entry["tolerance_pct"] = 1.0
    findings = ra.check_budgets([small], budgets, {"dp": 8})
    assert [f.rule for f in findings] == ["hbm-over-budget"]


def test_collective_bytes_regression_fires_on_new_collective():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from trlx_tpu.analysis import resource_audit as ra
    from jax import shard_map
    from trlx_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": -1, "fsdp": 1, "tp": 1})
    n = mesh.shape["dp"]
    x = jnp.zeros((n, 4), jnp.float32)
    before = ra.analyze_closed_jaxpr(
        jax.make_jaxpr(lambda x: x * 2.0)(x), "fx.step",
        axis_sizes={"dp": n},
    )

    @partial(shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    def with_psum(x):
        return x * jax.lax.psum(x.sum(), "dp")

    after = ra.analyze_closed_jaxpr(
        jax.make_jaxpr(with_psum)(x), "fx.step", axis_sizes={"dp": n}
    )
    budgets = ra.make_budgets([before], {"dp": n})
    rules = [f.rule for f in ra.check_budgets([after], budgets, {"dp": n})]
    # a program whose budget says "no collectives" growing one is a
    # regression no tolerance absorbs
    assert "collective-bytes-regression" in rules


def test_budget_missing_program_mesh_mismatch_and_stale_entries():
    from trlx_tpu.analysis import resource_audit as ra

    small, _ = _resources_pair()
    budgets = ra.make_budgets([small], {"dp": 8})

    # traced program with no committed entry
    orphan = ra.ProgramResources(
        subject="fx.new_step", peak_hbm_bytes=1, input_bytes=1,
        donated_bytes=0, output_bytes=1, flops=0,
    )
    findings = ra.check_budgets([small, orphan], budgets, {"dp": 8})
    assert ["hbm-over-budget"] == [f.rule for f in findings]
    assert "--update-budgets" in findings[0].message

    # mesh mismatch short-circuits: per-device numbers are incomparable
    findings = ra.check_budgets([small], budgets, {"dp": 4})
    assert [f.rule for f in findings] == ["hbm-over-budget"]
    assert "mesh" in findings[0].message

    # stale entry for a kind that WAS traced -> prune warning
    budgets["programs"]["fx.removed"] = {
        "peak_hbm_bytes": 1, "collective_bytes": 0,
    }
    findings = ra.check_budgets([small], budgets, {"dp": 8})
    assert [(f.rule, f.severity) for f in findings] == [
        ("hbm-over-budget", "warning")
    ]


def test_budgets_file_roundtrip(tmp_path):
    from trlx_tpu.analysis import resource_audit as ra

    small, _ = _resources_pair()
    path = str(tmp_path / "budgets.json")
    ra.write_budgets(ra.make_budgets([small], {"dp": 8}), path)
    budgets = ra.load_budgets(path)
    assert budgets["schema_version"] == ra.BUDGETS_SCHEMA_VERSION
    assert ra.check_budgets([small], budgets, {"dp": 8}, path) == []


def test_update_budgets_partial_merge_and_mesh_refusal(tmp_path):
    # a --trainers subset relock must MERGE into the lockfile (keeping
    # the untraced kinds' entries and every reviewer tolerance override),
    # and must refuse outright when the subset traced on a different mesh
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    from trlx_tpu.analysis import resource_audit as ra

    path = str(tmp_path / "budgets.json")
    x = jnp.zeros((8, 8), jnp.float32)

    def traced_on(mesh_shape):
        return SimpleNamespace(
            closed_jaxpr=jax.make_jaxpr(lambda x: x + 1.0)(x),
            subject="fx.step", mesh_shape=mesh_shape,
            input_divisors=None, def_site=None,
        )

    ra.write_budgets({
        "schema_version": ra.BUDGETS_SCHEMA_VERSION,
        "mesh": {"dp": 8},
        "tolerance_pct": 7.5,
        "programs": {
            "fx.step": {"peak_hbm_bytes": 1, "collective_bytes": 0,
                        "collective_count": 0, "flops": 0,
                        "tolerance_pct": 2.0},
            "other.step": {"peak_hbm_bytes": 123, "collective_bytes": 0,
                           "collective_count": 0, "flops": 0},
        },
    }, path)

    report, _ = ra.audit_resources(
        kinds=["fx"], budgets_path=path, update=True,
        programs=[traced_on({"dp": 8})],
    )
    assert report.findings == []
    merged = ra.load_budgets(path)
    assert merged["programs"]["other.step"]["peak_hbm_bytes"] == 123
    fx = merged["programs"]["fx.step"]
    assert fx["peak_hbm_bytes"] > 1  # relocked from the trace
    assert fx["tolerance_pct"] == 2.0  # override survives regeneration

    # subset trace on another mesh: refuse, write nothing
    report, _ = ra.audit_resources(
        kinds=["fx"], budgets_path=path, update=True,
        programs=[traced_on({"dp": 4})],
    )
    assert [f.rule for f in report.findings] == ["hbm-over-budget"]
    assert "refusing" in report.findings[0].message
    assert ra.load_budgets(path) == merged

    # a FULL relock (no --trainers) intentionally prunes other kinds but
    # still carries the tolerance overrides forward
    report, _ = ra.audit_resources(
        kinds=None, budgets_path=path, update=True,
        programs=[traced_on({"dp": 8})],
    )
    assert report.findings == []
    full = ra.load_budgets(path)
    assert set(full["programs"]) == {"fx.step"}
    assert full["programs"]["fx.step"]["tolerance_pct"] == 2.0
    assert full["tolerance_pct"] == 7.5
    # the file-level tolerance override also survives the PARTIAL merge
    # (re-check on the merged file from the subset relock above)
    assert merged["tolerance_pct"] == 7.5


def test_update_budgets_preserves_foreign_sections(tmp_path):
    # a resource relock must pass OTHER engines' lockfile sections
    # (compile_budgets, engine 8; perf_budgets, engine 10) through
    # untouched — before this guard a `--resources --update-budgets`
    # silently wiped them out of the shared lockfile
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    from trlx_tpu.analysis import resource_audit as ra

    path = str(tmp_path / "budgets.json")
    x = jnp.zeros((4, 4), jnp.float32)
    program = SimpleNamespace(
        closed_jaxpr=jax.make_jaxpr(lambda x: x * 2.0)(x),
        subject="fx.step", mesh_shape={"dp": 8},
        input_divisors=None, def_site=None,
    )
    foreign_compile = {"mesh": {"dp": 8}, "programs": {"fx.step": {"compiles": 1}}}
    foreign_perf = {"platforms": {"cpu": {"spans": {}}}}
    ra.write_budgets({
        "schema_version": ra.BUDGETS_SCHEMA_VERSION,
        "mesh": {"dp": 8},
        "tolerance_pct": 7.5,
        "programs": {},
        "compile_budgets": foreign_compile,
        "perf_budgets": foreign_perf,
    }, path)

    report, _ = ra.audit_resources(
        kinds=["fx"], budgets_path=path, update=True, programs=[program],
    )
    assert report.findings == []
    merged = ra.load_budgets(path)
    assert merged["compile_budgets"] == foreign_compile
    assert merged["perf_budgets"] == foreign_perf
    assert "fx.step" in merged["programs"]
    assert merged["tolerance_pct"] == 7.5


# ---------------------------- donation fixtures -------------------------- #

def test_donation_ignored_fires_without_matching_output():
    import jax.numpy as jnp

    from trlx_tpu.analysis import donation

    x = jnp.zeros((32, 32), jnp.float32)
    closed = _jxp(lambda x: x.sum(), x, donate_argnums=(0,))
    findings = donation.check_donation_ignored(
        closed, "fx.step", ["state.w"], ("fx.py", 3)
    )
    assert [f.rule for f in findings] == ["donation-ignored"]
    assert findings[0].severity == "warning"
    assert "state.w" in findings[0].message
    assert (findings[0].file, findings[0].line) == ("fx.py", 3)


def test_donation_ignored_clean_when_output_reuses_buffer():
    import jax.numpy as jnp

    from trlx_tpu.analysis import donation

    x = jnp.zeros((32, 32), jnp.float32)
    closed = _jxp(lambda x: x + 1, x, donate_argnums=(0,))
    assert donation.check_donation_ignored(closed, "fx.step") == []


def test_alias_escape_fires_on_forwarded_input():
    import jax.numpy as jnp

    from trlx_tpu.analysis import donation

    x = jnp.zeros((4,), jnp.float32)
    closed = _jxp(lambda x, y: (x, y + 1), x, x)
    findings = donation.check_alias_escape(
        closed, "fx.snap", ["params.w", "other"], ("fx.py", 7)
    )
    assert [f.rule for f in findings] == ["alias-escape"]
    assert "params.w" in findings[0].message
    assert (findings[0].file, findings[0].line) == ("fx.py", 7)


def test_alias_escape_allows_copies_and_donated_forwarding():
    import jax.numpy as jnp

    from trlx_tpu.analysis import donation

    x = jnp.zeros((4,), jnp.float32)
    # a real copy materializes a fresh buffer
    copied = _jxp(lambda x, y: (x + 0, y + 1), x, x)
    assert donation.check_alias_escape(copied, "fx") == []
    # forwarding a DONATED input is intended aliasing
    donated = _jxp(lambda x, y: (x, y + 1), x, x, donate_argnums=(0,))
    assert donation.check_alias_escape(donated, "fx") == []


# --------------------------- use-after-donate ---------------------------- #

_UAD_BAD = """
import jax

class Trainer:
    def build(self):
        self._train_step_jit = jax.jit(self._step, donate_argnums=(0,))

    def learn(self, mb):
        stats = self._train_step_jit(self.state, mb)
        return self.state.params, stats
"""

_UAD_GOOD = """
import jax

class Trainer:
    def build(self):
        self._train_step_jit = jax.jit(self._step, donate_argnums=(0,))

    def learn(self, mb):
        self.state, stats = self._train_step_jit(self.state, mb)
        return self.state.params, stats
"""


def test_use_after_donate_fires_with_file_line():
    from trlx_tpu.analysis.donation import check_use_after_donate_source

    findings, _ = check_use_after_donate_source(
        textwrap.dedent(_UAD_BAD), "fixture.py"
    )
    assert [f.rule for f in findings] == ["use-after-donate"]
    assert findings[0].file == "fixture.py"
    assert findings[0].line == 10  # the read, not the donating call
    assert "self.state" in findings[0].message


def test_use_after_donate_rebind_is_clean():
    from trlx_tpu.analysis.donation import check_use_after_donate_source

    findings, _ = check_use_after_donate_source(
        textwrap.dedent(_UAD_GOOD), "fixture.py"
    )
    assert findings == []


def test_use_after_donate_discovers_local_jit_bindings():
    from trlx_tpu.analysis.donation import check_use_after_donate_source

    src = """
    import jax

    def run(state, mb):
        step = jax.jit(lambda s, b: (s, {}), donate_argnums=(0,))
        stats = step(state, mb)
        return state
    """
    findings, _ = check_use_after_donate_source(
        textwrap.dedent(src), "fixture.py"
    )
    assert [f.rule for f in findings] == ["use-after-donate"]


def test_use_after_donate_loop_rebinding_pattern_is_clean():
    # the stepwise trainer loop: donate + rebind every iteration
    from trlx_tpu.analysis.donation import check_use_after_donate_source

    src = """
    import jax

    class Trainer:
        def build(self):
            self._train_step_jit = jax.jit(self._step, donate_argnums=(0,))

        def learn(self, mbs):
            for mb in mbs:
                self.state, stats = self._train_step_jit(self.state, mb)
                self.log(self.state.step, stats)
            return self.state
    """
    findings, _ = check_use_after_donate_source(
        textwrap.dedent(src), "fixture.py"
    )
    assert findings == []


def test_use_after_donate_body_donation_does_not_poison_earlier_reads():
    # a donation INSIDE a compound statement's body applies at its own
    # statement — a read earlier in the same body (or the header) must
    # not be flagged; a read AFTER it without rebinding still is
    from trlx_tpu.analysis.donation import check_use_after_donate_source

    src = """
    import jax

    class Trainer:
        def build(self):
            self._train_step_jit = jax.jit(self._step, donate_argnums=(0,))

        def guarded(self, mb, cond):
            if cond:
                self.log(self.state.step)
                self.state, s = self._train_step_jit(self.state, mb)
            return self.state

        def bad_tail(self, mb, cond):
            if cond:
                s = self._train_step_jit(self.state, mb)
                self.log(self.state.step)
            return self.state
    """
    findings, _ = check_use_after_donate_source(
        textwrap.dedent(src), "fixture.py"
    )
    assert [(f.rule, f.subject) for f in findings] == [
        ("use-after-donate", "bad_tail()"),
        ("use-after-donate", "bad_tail()"),  # the post-if read of self.state
    ]


# --------------------------- suppression coverage ------------------------ #

def test_use_after_donate_inline_suppression():
    from trlx_tpu.analysis.donation import check_use_after_donate_source

    suppressed_src = _UAD_BAD.replace(
        "return self.state.params, stats",
        "return self.state.params, stats"
        "  # tpu-lint: disable=use-after-donate",
    )
    findings, n_suppressed = check_use_after_donate_source(
        textwrap.dedent(suppressed_src), "fixture.py"
    )
    assert findings == []
    assert n_suppressed == 1


def test_donation_jaxpr_rules_suppress_at_def_site(tmp_path):
    # donation-ignored / alias-escape anchor to the traced callable's def
    # line — a directive there silences them like any other finding
    import jax.numpy as jnp

    from trlx_tpu.analysis import donation
    from trlx_tpu.analysis.findings import filter_suppressed

    fixture = tmp_path / "step.py"
    fixture.write_text(
        "def step(x):"
        "  # tpu-lint: disable=donation-ignored,alias-escape\n"
        "    return x.sum()\n"
    )
    x = jnp.zeros((8, 8), jnp.float32)
    findings = donation.check_donation_ignored(
        _jxp(lambda x: x.sum(), x, donate_argnums=(0,)),
        "fx.step", None, (str(fixture), 1),
    ) + donation.check_alias_escape(
        _jxp(lambda x, y: (x, y + 1), x, x),
        "fx.step", None, (str(fixture), 1),
    )
    assert len(findings) == 2
    kept, n_suppressed = filter_suppressed(findings)
    assert kept == []
    assert n_suppressed == 2


def test_budget_rules_suppress_at_def_site(tmp_path):
    # budget findings anchor to the traced callable's def line
    # (ProgramResources.def_site) and run through filter_suppressed in
    # audit_resources — a directive there silences the gate for real
    from trlx_tpu.analysis import resource_audit as ra
    from trlx_tpu.analysis.findings import filter_suppressed

    fixture = tmp_path / "step.py"
    fixture.write_text(
        "def step(x):"
        "  # tpu-lint: disable=hbm-over-budget,collective-bytes-regression\n"
        "    return x\n"
    )
    small, big = _resources_pair()
    big.def_site = (str(fixture), 1)
    big.collectives = {"psum[dp]": {"count": 1, "bytes": 64}}
    budgets = ra.make_budgets([small], {"dp": 8})
    findings = ra.check_budgets([big], budgets, {"dp": 8})
    assert sorted(f.rule for f in findings) == [
        "collective-bytes-regression", "hbm-over-budget",
    ]
    assert all(f.file == str(fixture) and f.line == 1 for f in findings)
    kept, n_suppressed = filter_suppressed(findings)
    assert kept == []
    assert n_suppressed == 2


def test_new_rules_registered_with_engines():
    from trlx_tpu.analysis.registry import get_rule

    assert get_rule("hbm-over-budget").engine == "resource"
    assert get_rule("collective-bytes-regression").engine == "resource"
    assert get_rule("use-after-donate").engine == "donation"
    assert get_rule("donation-ignored").engine == "donation"
    assert get_rule("alias-escape").engine == "donation"


# ------------------------- JSON artifact stability ----------------------- #

def test_report_json_schema_version_and_stable_ordering():
    from trlx_tpu.analysis.findings import (
        Finding,
        JSON_SCHEMA_VERSION,
        Report,
    )

    r = Report()
    r.extend([
        Finding(rule="zz", message="late", file="b.py", line=2),
        Finding(rule="aa", message="early", file="a.py", line=9),
        Finding(rule="aa", message="early", file="a.py", line=1),
    ])
    r.covered += ["z-subject", "a-subject"]
    payload = json.loads(r.to_json())
    assert payload["schema_version"] == JSON_SCHEMA_VERSION
    assert [f["rule"] for f in payload["findings"]] == ["aa", "aa", "zz"]
    assert [f["line"] for f in payload["findings"]][:2] == [1, 9]
    assert payload["covered"] == ["a-subject", "z-subject"]
    # insertion order must not leak into the artifact
    r2 = Report()
    r2.extend(list(reversed(r.findings)))
    r2.covered += ["a-subject", "z-subject"]
    assert r2.to_json() == r.to_json()


# --------------------------- end-to-end audits --------------------------- #

def test_donation_host_pass_clean_on_repo():
    from trlx_tpu.analysis.donation import lint_paths

    report = lint_paths([f"{REPO}/trlx_tpu"])
    assert report.findings == [], "\n".join(
        f.format_text() for f in report.findings
    )


@pytest.mark.slow
def test_ppo_resources_clean_against_committed_budgets_and_seeded_trip():
    # one trainer build covers: (a) the committed lockfile accepts the
    # current trace, (b) shrinking a committed budget trips the gate,
    # (c) the donation jaxpr rules pass on the real programs.
    # `slow`: tier-1 already pays one ppo trace (test_analysis.py) and
    # sits near the 870 s budget — this second trace runs in the nightly
    # tier with the other trainer-tracing e2e tests (the CI
    # resource-budget job gates the lockfile on every push regardless)
    from trlx_tpu.analysis import donation, harness
    from trlx_tpu.analysis import resource_audit as ra

    programs = list(harness.trace_trainer("ppo"))
    resources, mesh_shape = ra.collect_resources(programs=programs)
    budgets = ra.load_budgets(ra.default_budgets_path())
    assert ra.check_budgets(resources, budgets, mesh_shape) == [], (
        "committed budgets rejected the current ppo trace — regenerate "
        "with --update-budgets if the growth is intended"
    )

    # seeded regression: pretend the committed peak was 40% smaller
    import copy

    shrunk = copy.deepcopy(budgets)
    shrunk["programs"]["ppo.train_step"]["peak_hbm_bytes"] = int(
        shrunk["programs"]["ppo.train_step"]["peak_hbm_bytes"] * 0.6
    )
    findings = ra.check_budgets(resources, shrunk, mesh_shape)
    assert [f.rule for f in findings] == ["hbm-over-budget"]
    assert findings[0].subject == "ppo.train_step"

    report = donation.audit_traced_programs(programs)
    assert report.findings == [], report.format_text()
    assert "donation:ppo.train_step" in report.covered


@pytest.mark.slow
def test_resources_cli_strict_clean_and_json_schema():
    proc = subprocess.run(
        [
            sys.executable, "-m", "trlx_tpu.analysis", "--resources",
            "--strict", "--json",
        ],
        cwd=REPO,
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["schema_version"] == 2
    subjects = [r["subject"] for r in payload["resources"]]
    assert subjects == sorted(subjects)
    for kind in ("ppo", "ilql", "grpo", "seq2seq"):
        assert f"{kind}.train_step" in subjects
    assert payload["findings"] == []


@pytest.mark.slow
def test_resources_cli_update_budgets_roundtrip(tmp_path):
    budgets_path = str(tmp_path / "budgets.json")
    write = subprocess.run(
        [
            sys.executable, "-m", "trlx_tpu.analysis", "--resources",
            "--trainers", "ppo", "--update-budgets",
            "--budgets", budgets_path,
        ],
        cwd=REPO,
        capture_output=True,
        text=True,
    )
    assert write.returncode == 0, write.stdout + write.stderr
    check = subprocess.run(
        [
            sys.executable, "-m", "trlx_tpu.analysis", "--resources",
            "--trainers", "ppo", "--strict", "--budgets", budgets_path,
        ],
        cwd=REPO,
        capture_output=True,
        text=True,
    )
    assert check.returncode == 0, check.stdout + check.stderr
