"""Engine 13 (compiled-HLO lowering audit): parser fixtures, seeded +
clean pairs per rule, suppression round-trips, the planted
eager-sharded-concat canary, known-miscompile registry stale/flip
cases, and the hlo_budgets lockfile hygiene (foreign sections preserved
byte-identical, cross-mesh partial relocks refused)."""

import json
import subprocess
import sys
from types import SimpleNamespace

import pytest

from trlx_tpu.analysis import hlo_audit as hlo
from trlx_tpu.analysis.findings import Finding, filter_suppressed

REPO = __file__.rsplit("/tests/", 1)[0]
MESH222 = {"dp": 2, "fsdp": 2, "tp": 2}

# Canned optimized-HLO lines in the exact shapes jaxlib 0.4.x prints —
# the parser must handle explicit groups, both iota forms, tuple-shaped
# all-reduces, and collective-permute's source_target_pairs.
_HLO_EXPLICIT = (
    '  %all-reduce.1 = s32[8,6]{1,0} all-reduce(s32[8,6]{1,0} %concatenate.1), '
    'channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, '
    'use_global_device_ids=true, to_apply=%add.clone, '
    'metadata={op_name="jit(fn)/jit(main)/concatenate" '
    'source_file="/repo/x.py" source_line=12}'
)
_HLO_IOTA = (
    '  %all-gather.3 = f32[64,32]{1,0} all-gather(f32[32,32]{1,0} %p), '
    'channel_id=2, replica_groups=[4,2]<=[8], dimensions={0}, '
    'use_global_device_ids=true, metadata={op_name="jit(step)/all_gather"}'
)
_HLO_IOTA_T = (
    '  %reduce-scatter.4 = f32[8,32]{1,0} reduce-scatter(f32[32,32]{1,0} %g), '
    'channel_id=5, replica_groups=[2,4]<=[4,2]T(1,0), dimensions={0}, '
    'to_apply=%add, metadata={op_name="jit(step)/psum_scatter"}'
)
_HLO_PAIRS = (
    '  %collective-permute.1 = f32[4,32]{1,0} collective-permute('
    'f32[4,32]{1,0} %x), channel_id=3, '
    'source_target_pairs={{0,1},{1,0},{2,3},{3,2}}, '
    'metadata={op_name="jit(step)/ppermute"}'
)
_HLO_TUPLE = (
    '  %all-reduce.9 = (f32[32,32]{1,0}, f32[32]{0}) all-reduce('
    'f32[32,32]{1,0} %a, f32[32]{0} %b), channel_id=4, '
    'replica_groups={{0,1},{2,3},{4,5},{6,7}}, to_apply=%add, '
    'metadata={op_name="jit(train)/add"}'
)
_HLO_DONE = (
    "  %all-gather-done.3 = f32[64,32]{1,0} all-gather-done("
    "f32[64,32]{1,0} %all-gather-start.3)"
)
_HLO_UPCAST_BAD = (
    '  %convert.5 = f32[8,16,32]{2,1,0} convert(bf16[8,16,32]{2,1,0} %act), '
    'metadata={op_name="jit(step)/transformer/mlp/convert" '
    'source_file="/repo/trlx_tpu/models/gpt2.py" source_line=100}'
)
_HLO_UPCAST_ALLOWED = (
    '  %convert.6 = f32[8,16,32]{2,1,0} convert(bf16[8,16,32]{2,1,0} %att), '
    'metadata={op_name="jit(step)/transformer/softmax/convert"}'
)
_HLO_UPCAST_SCALAR = "  %convert.7 = f32[] convert(bf16[] %s)"
_HLO_UPCAST_VECTOR = "  %convert.8 = f32[32]{0} convert(bf16[32]{0} %v)"


# ------------------------------ parsing ---------------------------------- #

def test_parse_explicit_groups_and_metadata():
    (c,) = hlo.parse_hlo_collectives(_HLO_EXPLICIT)
    assert c.kind == "all-reduce"
    assert c.dtype == "s32"
    assert c.elems == 48 and c.bytes == 192
    assert c.groups == [[0, 1, 2, 3, 4, 5, 6, 7]]
    assert c.to_apply == "add.clone"
    assert c.op_name.endswith("/concatenate")
    assert c.axes(MESH222) == ("dp", "fsdp", "tp")


def test_parse_iota_groups():
    (c,) = hlo.parse_hlo_collectives(_HLO_IOTA)
    assert c.kind == "all-gather"
    assert c.groups == [[0, 1], [2, 3], [4, 5], [6, 7]]
    # adjacent device ids differ only in the innermost (tp) coordinate
    assert c.axes(MESH222) == ("tp",)


def test_parse_iota_transposed_groups():
    (c,) = hlo.parse_hlo_collectives(_HLO_IOTA_T)
    assert c.kind == "reduce-scatter"
    # iota(8).reshape(4,2).T -> rows [[0,2,4,6],[1,3,5,7]]
    assert c.groups == [[0, 2, 4, 6], [1, 3, 5, 7]]
    assert c.axes(MESH222) == ("dp", "fsdp")


def test_parse_source_target_pairs():
    (c,) = hlo.parse_hlo_collectives(_HLO_PAIRS)
    assert c.kind == "collective-permute"
    assert c.pairs == [(0, 1), (1, 0), (2, 3), (3, 2)]
    assert c.axes(MESH222) == ("tp",)


def test_parse_tuple_shaped_all_reduce():
    (c,) = hlo.parse_hlo_collectives(_HLO_TUPLE)
    assert c.dtype == "f32"
    assert c.elems == 32 * 32 + 32
    assert c.bytes == 4 * (32 * 32 + 32)
    assert c.axes(MESH222) == ("tp",)


def test_parse_skips_done_ops_and_counts_profile():
    text = "\n".join([_HLO_EXPLICIT, _HLO_IOTA, _HLO_DONE, _HLO_IOTA])
    collectives = hlo.parse_hlo_collectives(text)
    assert [c.kind for c in collectives] == [
        "all-reduce", "all-gather", "all-gather",
    ]
    profile = hlo.collective_profile(collectives, MESH222)
    assert profile == {
        "all-reduce[dp,fsdp,tp]|s32": 1,
        "all-gather[tp]|f32": 2,
    }


# --------------------- lowering-collective-drift -------------------------- #

def _cp(text, subject="fx.step", explicit=()):
    cp = hlo.CompiledProgram(
        subject=subject, mesh_label="dp=2/fsdp=2/tp=2", mesh_shape=MESH222,
        def_site=("fx.py", 3),
    )
    cp.collectives = hlo.parse_hlo_collectives(text)
    cp.profile = hlo.collective_profile(cp.collectives, MESH222)
    cp.explicit_intent = list(explicit)
    return cp


def test_concat_minted_replica_sum_fires():
    findings = hlo.check_lowering_drift(_cp(_HLO_EXPLICIT), None)
    assert [f.rule for f in findings] == ["lowering-collective-drift"]
    assert "replica-axis all-reduce over [dp,fsdp,tp]" in findings[0].message
    assert "spmd_stack" in findings[0].message
    assert (findings[0].file, findings[0].line) == ("fx.py", 3)


def test_benign_all_reduce_is_clean():
    assert hlo.check_lowering_drift(_cp(_HLO_TUPLE), None) == []


def test_dropped_explicit_collective_fires_and_surviving_is_clean():
    intent = [("psum", ("tp",), "")]
    # no all-reduce in the module -> the author's psum was dropped
    dropped = hlo.check_lowering_drift(_cp(_HLO_IOTA, explicit=intent), None)
    assert [f.rule for f in dropped] == ["lowering-collective-drift"]
    assert "psum" in dropped[0].message
    # an all-reduce survives -> clean
    assert hlo.check_lowering_drift(_cp(_HLO_TUPLE, explicit=intent), None) == []


def test_profile_drift_against_locked_entry():
    cp = _cp(_HLO_IOTA)
    locked = {"collectives": {"all-gather[tp]|f32": 1}}
    assert hlo.check_lowering_drift(cp, locked) == []
    drifted = {"collectives": {"all-gather[tp]|f32": 2}}
    findings = hlo.check_lowering_drift(cp, drifted)
    assert [f.rule for f in findings] == ["lowering-collective-drift"]
    assert "all-gather[tp]|f32: 2 -> 1" in findings[0].message


def test_prng_bitgen_concat_allreduce_is_exempt():
    """jax.random's threefry bit generation concatenates the two u32
    output halves inside jit(_uniform)/jit(_gumbel); GSPMD recombines
    the shards with a correct zero-pad + all-reduce(add) — not the
    PR-2 signature. The repo-authored concat scope still fires."""
    prng = (
        '  %all-reduce.6 = u32[256]{0} all-reduce(u32[256]{0} %c), '
        'channel_id=2, replica_groups={{0,1,2,3,4,5,6,7}}, '
        'use_global_device_ids=true, to_apply=%add.6.clone, '
        'metadata={op_name="jit(sampler)/jit(main)/while/body/'
        'jit(_gumbel)/jit(_uniform)/concatenate"}'
    )
    assert hlo.concat_minted_collectives(
        hlo.parse_hlo_collectives(prng)
    ) == []
    assert len(hlo.concat_minted_collectives(
        hlo.parse_hlo_collectives(_HLO_EXPLICIT)
    )) == 1


# --------------------------- hlo-dtype-upcast ----------------------------- #

def test_dtype_upcast_seeded_and_clean():
    bad = hlo.extract_dtype_upcasts(_HLO_UPCAST_BAD)
    assert len(bad) == 1 and bad[0].shape == "f32[8,16,32]"
    assert bad[0].source_line == 100
    # allowlisted op_name, scalar, and vector converts are all clean
    clean = "\n".join(
        [_HLO_UPCAST_ALLOWED, _HLO_UPCAST_SCALAR, _HLO_UPCAST_VECTOR]
    )
    assert hlo.extract_dtype_upcasts(clean) == []

    cp = _cp("")
    cp.upcasts = bad
    findings = hlo.check_dtype_upcasts(cp)
    assert [f.rule for f in findings] == ["hlo-dtype-upcast"]
    assert findings[0].severity == "warning"
    assert "gpt2.py:100" in findings[0].message


def test_dtype_upcast_skips_unattributed_and_blessed_sources():
    # no op_name metadata -> compiler fusion/remat plumbing, skipped
    anonymous = (
        "  %convert.9 = f32[2,8,16]{2,1,0} convert(bf16[2,8,16]{2,1,0} %x)"
    )
    assert hlo.extract_dtype_upcasts(anonymous) == []
    # authored in a file whose f32 compute is contractual -> skipped
    blessed = (
        '  %convert.10 = f32[8,16,32]{2,1,0} convert(bf16[8,16,32]{2,1,0} %y), '
        'metadata={op_name="jit(step)/T5Stack/dec_0/mlp/convert" '
        'source_file="/repo/trlx_tpu/models/t5.py" source_line=91}'
    )
    assert hlo.extract_dtype_upcasts(blessed) == []
    # identical authored converts (per-layer AD transposes) dedupe to one
    assert len(hlo.extract_dtype_upcasts(
        "\n".join([_HLO_UPCAST_BAD, _HLO_UPCAST_BAD])
    )) == 1


# --------------------------- hlo-memory-drift ----------------------------- #

def test_memory_drift_seeded_and_clean():
    cp = _cp("")
    cp.temp_bytes, cp.argument_bytes = 900, 200
    cp.output_bytes, cp.alias_bytes = 100, 200
    assert cp.peak_bytes == 1000
    # within tolerance -> clean
    assert hlo.check_memory_drift(cp, {"peak_bytes": 990}, 5.0) == []
    # past tolerance -> error naming the growth
    findings = hlo.check_memory_drift(cp, {"peak_bytes": 900}, 5.0)
    assert [f.rule for f in findings] == ["hlo-memory-drift"]
    assert "900 -> 1000" in findings[0].message
    # per-entry tolerance override wins
    assert hlo.check_memory_drift(
        cp, {"peak_bytes": 900, "tolerance_pct": 20.0}, 5.0
    ) == []
    # missing entry -> error telling the builder to lock
    missing = hlo.check_memory_drift(cp, None, 5.0)
    assert [f.rule for f in missing] == ["hlo-memory-drift"]
    assert "--update-budgets" in missing[0].message


# --------------------------- spmd-concat-hazard --------------------------- #

def test_planted_concat_trips_hazard_walk():
    program = hlo.plant_hazard_program()
    findings = hlo.check_concat_hazard(program)
    assert [f.rule for f in findings] == ["spmd-concat-hazard"]
    assert findings[0].file and findings[0].file.endswith("hlo_audit.py")
    assert findings[0].line  # the planted concatenate's own line


def test_replicated_concat_is_clean():
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda a, b: jnp.concatenate([a, b], axis=0))
    sds = jax.ShapeDtypeStruct((8, 6), jnp.int32)
    program = SimpleNamespace(
        subject="fx.concat",
        closed_jaxpr=jax.make_jaxpr(fn)(sds, sds),
        mesh_shape=MESH222,
        input_divisors=[1, 1],  # replicated operands carry no hazard
        def_site=None,
    )
    assert hlo.check_concat_hazard(program) == []


def test_concat_along_replicated_dim_of_sharded_operands_is_clean():
    """The `[query; response]` shape: batch-sharded (dim 0) rollout
    tensors concatenated along the *sequence* axis (dim 1) lower to a
    local per-shard concat — not the PR-2 hazard, which needs the
    concat to run along a mesh-split dimension."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from trlx_tpu.analysis import harness

    mesh = harness.audit_mesh()
    batch = NamedSharding(mesh, P(("dp", "fsdp"), None))

    fn = jax.jit(
        lambda a, b: jnp.concatenate([a, b], axis=1),
        in_shardings=(batch, batch),
    )
    sds = jax.ShapeDtypeStruct((8, 6), jnp.int32)
    program = SimpleNamespace(
        subject="fx.seq_concat",
        closed_jaxpr=jax.make_jaxpr(fn)(sds, sds),
        mesh_shape={k: int(v) for k, v in mesh.shape.items()},
        input_divisors=harness.flat_sharding_divisors(
            ((sds, sds),), ((batch, batch),)
        ),
        input_sharded_dims=harness.flat_sharded_dims(
            ((sds, sds),), ((batch, batch),)
        ),
        def_site=None,
    )
    assert program.input_sharded_dims == [(0,), (0,)]
    assert hlo.check_concat_hazard(program) == []


def test_blessed_helper_names_are_exempt():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from trlx_tpu.analysis import harness

    mesh = harness.audit_mesh()
    row = NamedSharding(mesh, P(("dp", "fsdp"), None))

    def spmd_stack(a, b):  # same name as the blessed helper
        return jnp.concatenate([a, b], axis=0)

    fn = jax.jit(spmd_stack, in_shardings=(row, row))
    sds = jax.ShapeDtypeStruct((8, 6), jnp.int32)
    program = SimpleNamespace(
        subject="fx.blessed",
        closed_jaxpr=jax.make_jaxpr(fn)(sds, sds),
        mesh_shape={k: int(v) for k, v in mesh.shape.items()},
        input_divisors=[4, 4],
        def_site=None,
    )
    import os

    assert hlo.check_concat_hazard(
        program, repo_root=os.path.dirname(__file__)
    ) == []


# ----------------------- the tier-1 planted canary ------------------------ #

def test_planted_concat_canary_compiles_and_trips_both_rules():
    """The PR-2 shape, end to end on one tiny program: the jaxpr-side
    hazard rule names the seeded eager concat whatever the compiler does
    with it, and the compiled side says what this jaxlib (0.9.0) does: two
    all-gathers and a slice, no replica-axis sum, so the drift rule is
    quiet. A jaxlib that mints the sum again turns this red, and then
    ``spmd_stack`` / ``concat_cols`` / ``stack_cols`` are live again
    (tools/pp_miscompile_repro.py; ROADMAP.md Queue 3 item 8)."""
    program = hlo.plant_hazard_program()
    cp = hlo.compile_program(program)
    assert [f.rule for f in hlo.check_concat_hazard(program)] == ["spmd-concat-hazard"]
    assert {c.kind for c in cp.collectives} == {"all-gather"}
    assert hlo.concat_minted_collectives(cp.collectives) == []
    assert hlo.check_lowering_drift(cp, None) == []


# -------------------------- suppression round-trip ------------------------ #

@pytest.mark.parametrize(
    "rule_id",
    [
        "lowering-collective-drift",
        "hlo-dtype-upcast",
        "hlo-memory-drift",
        "spmd-concat-hazard",
    ],
)
def test_suppression_round_trip(tmp_path, rule_id):
    src = tmp_path / "prog.py"
    src.write_text(f"x = 1  # tpu-lint: disable={rule_id}\ny = 2\n")
    sev = "warning" if rule_id == "hlo-dtype-upcast" else "error"
    on_directive = Finding(
        rule=rule_id, message="m", severity=sev, file=str(src), line=1,
        subject="fx", engine="hlo",
    )
    elsewhere = Finding(
        rule=rule_id, message="m", severity=sev, file=str(src), line=2,
        subject="fx", engine="hlo",
    )
    kept, n = filter_suppressed([on_directive, elsewhere])
    assert n == 1
    assert kept == [elsewhere]


def test_new_rules_registered():
    from trlx_tpu.analysis.registry import all_rules

    ids = {r.id for r in all_rules("hlo")}
    assert ids == {
        "lowering-collective-drift", "hlo-dtype-upcast",
        "hlo-memory-drift", "spmd-concat-hazard",
    }


# ----------------------- known-miscompile registry ------------------------ #

def test_registry_quiet_on_verified_jaxlib():
    findings, covered = hlo.check_known_miscompiles(
        jaxlib_version="0.4.36", probe=False
    )
    assert findings == []
    assert sorted(covered) == [
        "known-miscompile:multihost-sync-barrier-abort",
        "known-miscompile:pp-cached-decode-stack",
        "known-miscompile:sharded-concat-replica-sum",
    ]


def test_registry_stale_on_jaxlib_bump():
    findings, _ = hlo.check_known_miscompiles(
        jaxlib_version="9.9.9", probe=False
    )
    assert len(findings) == len(hlo.KNOWN_MISCOMPILES)
    for f in findings:
        assert f.severity == "warning"
        assert "FIXED" in f.message and "retire" in f.message
    repros = "\n".join(f.message for f in findings)
    assert "tools/pp_miscompile_repro.py" in repros
    assert "tools/multiprocess_probe.py" in repros


def test_registry_flip_when_probe_stops_reproducing(monkeypatch):
    # the live probe detects an upstream fix even with no version bump
    monkeypatch.setattr(hlo, "_probe_concat_miscompile", lambda: False)
    findings, _ = hlo.check_known_miscompiles(
        jaxlib_version="0.4.36", probe=True
    )
    assert [f.subject for f in findings] == [
        "known-miscompile:sharded-concat-replica-sum"
    ]
    assert "no longer reproduces" in findings[0].message


# -------------------------- lockfile hygiene ------------------------------ #

def _tiny_program(subject="fx.step", mesh_shape=None):
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: x * 2.0)
    x = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    return SimpleNamespace(
        subject=subject,
        closed_jaxpr=jax.make_jaxpr(fn)(x),
        mesh_shape=mesh_shape or {"dp": 8},
        mesh_axes={"dp"},
        input_divisors=None,
        def_site=None,
        jit_fn=fn,
        example_args=(x,),
    )


def test_update_budgets_preserves_foreign_sections(tmp_path):
    # an hlo relock must pass every other engine's lockfile section
    # through BYTE-identical — the PR-8 section-wipe class of bug
    from trlx_tpu.analysis import resource_audit as ra

    path = str(tmp_path / "budgets.json")
    foreign = {
        "schema_version": 1,
        "mesh": {"dp": 8},
        "tolerance_pct": 7.5,
        "programs": {"fx.step": {"peak_hbm_bytes": 11}},
        "compile_budgets": {"mesh": {"dp": 8},
                            "programs": {"fx.step": {"compiles": 1}}},
        "perf_budgets": {"platforms": {"cpu": {"spans": {}}}},
        "lockstep_budgets": {"hosts": 2, "programs": {}},
    }
    ra.write_budgets(foreign, path)
    before = {
        k: json.dumps(v, sort_keys=True)
        for k, v in foreign.items()
        if k != "hlo_budgets"
    }

    report, _ = hlo.audit_hlo(
        kinds=["fx"], budgets_path=path, update=True,
        programs=[_tiny_program()], registry_probe=False,
    )
    assert report.findings == []
    merged = ra.load_budgets(path)
    for key, frozen in before.items():
        assert json.dumps(merged[key], sort_keys=True) == frozen, key
    assert "fx.step" in merged["hlo_budgets"]["programs"]
    entry = merged["hlo_budgets"]["programs"]["fx.step"]
    assert entry["collectives"] == {}
    assert entry["peak_bytes"] >= 0


def test_update_budgets_refuses_cross_mesh_partial_relock(tmp_path):
    from trlx_tpu.analysis import resource_audit as ra

    path = str(tmp_path / "budgets.json")
    ra.write_budgets({
        "hlo_budgets": {
            "mesh": {"dp": 4},
            "tolerance_pct": 5.0,
            "programs": {"other.step": {"collectives": {},
                                        "peak_bytes": 7}},
        },
    }, path)
    frozen = json.dumps(ra.load_budgets(path), sort_keys=True)

    report, _ = hlo.audit_hlo(
        kinds=["fx"], budgets_path=path, update=True,
        programs=[_tiny_program(mesh_shape={"dp": 8})],
        registry_probe=False,
    )
    assert [f.rule for f in report.findings] == ["lowering-collective-drift"]
    assert "refusing" in report.findings[0].message
    # nothing was written
    assert json.dumps(ra.load_budgets(path), sort_keys=True) == frozen


def test_partial_relock_merges_and_full_relock_prunes(tmp_path):
    from trlx_tpu.analysis import resource_audit as ra

    path = str(tmp_path / "budgets.json")
    ra.write_budgets({
        "hlo_budgets": {
            "mesh": {"dp": 8},
            "tolerance_pct": 5.0,
            "programs": {
                "fx.step": {"collectives": {}, "peak_bytes": 1},
                "other.step": {"collectives": {}, "peak_bytes": 123},
            },
        },
    }, path)

    report, _ = hlo.audit_hlo(
        kinds=["fx"], budgets_path=path, update=True,
        programs=[_tiny_program(mesh_shape={"dp": 8})],
        registry_probe=False,
    )
    assert report.findings == []
    merged = ra.load_budgets(path)["hlo_budgets"]["programs"]
    assert merged["other.step"]["peak_bytes"] == 123  # foreign kind kept
    assert merged["fx.step"]["peak_bytes"] >= 0  # relocked

    report, _ = hlo.audit_hlo(
        kinds=None, budgets_path=path, update=True,
        programs=[_tiny_program(mesh_shape={"dp": 8})],
        registry_probe=False,
    )
    assert report.findings == []
    full = ra.load_budgets(path)["hlo_budgets"]["programs"]
    assert set(full) == {"fx.step"}  # a full relock intentionally prunes


def test_update_refused_while_rule_findings_exist(tmp_path):
    # a tree that trips the hazard rule cannot relock its way past it
    path = str(tmp_path / "budgets.json")
    report, _ = hlo.audit_hlo(
        budgets_path=path, update=True,
        programs=[hlo.plant_hazard_program()], registry_probe=False,
    )
    assert any(
        f.rule == "spmd-concat-hazard" for f in report.findings
    )
    import os

    assert not os.path.exists(path)


# ------------------------------ CLI (nightly) ----------------------------- #

@pytest.mark.slow
def test_cli_hlo_audit_strict_json_clean():
    proc = subprocess.run(
        [sys.executable, "-m", "trlx_tpu.analysis", "--hlo-audit",
         "--strict", "--json"],
        capture_output=True, text=True, timeout=1500, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []
    assert len(payload["covered"]) >= 236
    assert any(
        c.startswith("known-miscompile:") for c in payload["covered"]
    )


@pytest.mark.slow
def test_cli_plant_hazard_exits_one_naming_both_rules():
    proc = subprocess.run(
        [sys.executable, "-m", "trlx_tpu.analysis", "--plant-hazard"],
        capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "spmd-concat-hazard" in proc.stdout
    assert "hlo_audit.py" in proc.stdout  # planted concat localized
    # jaxlib 0.9.0 lowers the plant to all-gathers: no replica-axis sum to
    # drift (test_planted_concat_canary_compiles_and_trips_both_rules)
    assert "lowering-collective-drift" not in proc.stdout
