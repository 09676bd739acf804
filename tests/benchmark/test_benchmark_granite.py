"""The granite-4.0-h-small configuration and its cell: the shape rule's
counts and a decode step's bytes pinned by hand (ISSUE 35's arithmetic),
the published keys against the catalog row, the reference's two halves, the
count functions of the new readers on made-up trace operations, the
configuration's own tolerance file against the rule, and a CPU rehearsal of
``serve-granite4hs-chat`` at a toy size through the code the chip runs
(form only: CPU numbers)."""

import json

import numpy as np
import pytest

from benchmark import arithmetic, checks, harness
from benchmark.run import run_cell

import manifest_cells

CELL = "serve-granite4hs-chat"
CONFIG = "granite-4.0-h-small"
TRAFFIC = "chat-granite4hs"
# by hand, d 4096: a mamba mixer = 4096 x 16768 (in: 8192 z + 8448 xBC + 128 dt)
# + 4 x 8448 + 8448 (conv, bias) + 3 x 128 (dt_bias, A_log, D) + 8192 (norm) +
# 8192 x 4096 (out) = 102,286,976; attention = 2 x 4096^2 + 2 x 4096 x 1024 =
# 41,943,040; shared MLP 3 x 4096 x 1536; router 4096 x 72; two norms; an expert
# 3 x 4096 x 768
MIXER, ATTN, SHARED, ROUTER, NORMS, EXPERT = 102_286_976, 41_943_040, 18_874_368, 294_912, 8_192, 9_437_184
TABLE = 100_352 * 4096
STATE = 128 * 64 * 128 + 3 * 8448


@pytest.fixture(scope="module")
def config_file():
    return harness.load_json("configs", "granite-4.0-h-small.json")


def shape_of(cf):
    return arithmetic.model_shape(harness.load_family(cf), cf)


def test_parameters_of_the_cut_and_of_a_whole_period(config_file):
    mamba, attn = MIXER + SHARED + ROUTER + NORMS, ATTN + SHARED + ROUTER + NORMS
    assert (mamba, attn) == (121_464_448, 61_120_512)
    held = 9 * (mamba + 36 * EXPERT) + (attn + 36 * EXPERT) + TABLE + 4096
    assert held == 4_962_732_672 == config_file["parameters"]
    assert arithmetic.backbone_params(shape_of(config_file)) == held
    whole = dict(config_file, num_local_experts=72)
    assert arithmetic.backbone_params(shape_of(whole)) == held + 10 * 36 * EXPERT == 8_360_118_912


def test_shape_entries_by_kind(config_file):
    s = shape_of(config_file)
    assert len(s["layers"]) == 10
    kinds = config_file["layer_types"]
    assert kinds == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    for kind, layer in zip(kinds, s["layers"]):
        # 10 x 36 / 72 = 5 held experts a token expected; at least max(0, 10 - 36) = 0 must be read
        assert layer["routed"] == {"expert_params": EXPERT, "per_token": 0}
        if kind == "mamba":
            assert layer["params"] == 461_203_072
            assert layer["matmul_params"] == 4096 * 16768 + 8192 * 4096 + SHARED + ROUTER + 5 * EXPERT
            assert layer["read_params"] == arithmetic.decode_read_params(layer) == 121_464_448
            assert (layer["attn_dim"], layer["kv_values"], layer["state_values"]) == (0, 0, STATE)
        else:
            assert layer["params"] == 400_859_136
            assert layer["matmul_params"] == ATTN + SHARED + ROUTER + 5 * EXPERT
            assert layer["read_params"] == 61_120_512
            assert (layer["attn_dim"], layer["kv_values"]) == (4096, 2048) and "state_values" not in layer
    assert s["embed_params"] == TABLE
    assert s["final"] == {"params": 4096, "matmul_params": TABLE, "read_params": 4096 + TABLE}
    all_held = shape_of(dict(config_file, num_local_experts=72))["layers"][0]
    assert all_held["routed"]["per_token"] == 10


def test_a_decode_steps_bytes_by_hand(config_file):
    s = shape_of(config_file)
    # weights and head once in bf16; 14 sequences at 192 cached positions: the one
    # attention layer's keys and values, nine layers' float32 state both ways
    weights = 2 * (9 * 121_464_448 + 61_120_512 + 4096 + TABLE)
    kv = 2048 * 14 * 193 * 2
    state = 2 * 9 * STATE * 14 * 4
    assert (weights, kv, state) == (3_130_692_864, 11_067_392, 1_082_511_360)
    got = arithmetic.decode_step_bytes(s, 14, 192, weight_bytes=2, kv_bytes=2, state_bytes=4)
    assert got == weights + kv + state
    with pytest.raises(ValueError, match="state_bytes"):
        arithmetic.decode_step_bytes(s, 14, 192)


def test_published_keys_are_the_catalog_rows(config_file):
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
        "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 768, "logits_scaling": 16,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
        "mamba_proj_bias": False, "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32, "num_experts_per_tok": 10,
        "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 72,
        "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 1536,
        "tie_word_embeddings": True, "vocab_size": 100352,
        "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    }
    differs = sorted(k for k, v in published.items() if config_file.get(k, "absent") != v)
    assert differs == sorted(config_file["reduced"]) == ["layer_types", "num_hidden_layers", "num_local_experts"]
    assert config_file["layer_types"] == published["layer_types"][:10]  # the first period, as it is
    assert config_file["num_router_experts"] == 72 and config_file["first_local_expert"] == 0
    assert config_file["published"]["num_local_experts"] == 72
    assert set(config_file["assumed"]) >= {"weights", "intermediate_size", "head_dim", "initialisers",
                                            "eos_token_id", "state_dtype"}
    assert "2 chips" in config_file["deployment"]
    assert config_file["run"]["state_dtype"] == config_file["state_dtype"] == "float32"


def test_check_config_refuses_an_inconsistent_file(config_file):
    family = harness.load_family(config_file)
    family.check_config(config_file)
    for over, said in [
        ({"num_local_experts": 80}, "router"),
        ({"state_dtype": "bfloat16"}, "state_dtype"),
        ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
        ({"mamba_n_groups": 8}, "mamba_n_groups"),
        ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ]:
        with pytest.raises(ValueError, match=said):
            family.check_config(dict(config_file, **over))


def test_the_program_builds_the_configuration(config_file):
    from trlx_tpu.models.registry import get_model_family

    family = get_model_family(config_file["model_type"])
    cfg = family.config_cls.from_dict(harness.arch_of(config_file))
    assert (cfg.num_router_experts, cfg.num_local_experts, cfg.num_experts_per_tok) == (72, 36, 10)
    assert cfg.layer_types.count("attention") == 1 and cfg.num_hidden_layers == 10
    assert (cfg.head_dim, cfg.conv_channels, cfg.state_dtype) == (128, 8448, "float32")


TINY = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=4, layer_types=["mamba", "attention", "mamba", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=32, shared_intermediate_size=48,
    num_local_experts=4, num_router_experts=8, num_experts_per_tok=2, mamba_n_heads=16, mamba_d_head=8,
    mamba_d_state=16, mamba_chunk_size=8,
)


def test_the_halves_compose_and_the_head_runs_in_blocks(config_file):
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.registry import get_model_family

    cf = dict(config_file, **TINY)
    family = harness.load_family(cf)
    fam = get_model_family(cf["model_type"])
    arch = dict(harness.arch_of(cf), dtype="float32", param_dtype="float32")
    model = fam.backbone_cls(fam.config_cls.from_dict(arch))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 95, (2, 12)), jnp.int32)
    mask = jnp.asarray([[1] * 12, [0] * 5 + [1] * 7], jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    hidden = family.trunk(params, cf, ids, mask)
    np.testing.assert_array_equal(np.asarray(family.head(params, cf, hidden)),
                                  np.asarray(family.forward(params, cf, ids, mask)))
    # the rows the checks ask for are the same rows of the whole
    part = family.head(params, cf, hidden[:, 7:-1])
    np.testing.assert_allclose(np.asarray(part), np.asarray(family.forward(params, cf, ids, mask))[:, 7:-1],
                               rtol=1e-6, atol=1e-6)
    got = model.apply({"params": params}, ids, attention_mask=mask)["logits"]
    sd = float(np.asarray(family.forward(params, cf, ids, mask))[np.asarray(mask) > 0].std())
    err = np.abs(np.asarray(got) - np.asarray(family.forward(params, cf, ids, mask)))[np.asarray(mask) > 0]
    assert err.max() / sd < 1e-5


def record_of(config_file, gauges=None):
    return {"cell": {"config_file": config_file, "traffic_file": {"slots": 32}}, "gauges": gauges or {}}


def test_count_functions_of_the_new_readers(config_file):
    family = harness.load_family(config_file)
    # the step's state update: 32 slots x 128 x 64 x 128 float32 read and written an execution
    ops = {"fusion f32[32,128,64,128]": {"s": 1.0, "count": 18}}
    flops, moved = family.ssm_step_count(record_of(config_file), ops)
    assert moved == 18 * 2 * 32 * 1_048_576 * 4 and flops == 18 * 5 * 32 * 1_048_576
    assert moved / 819e9 > flops / 197e12  # bound by the bytes
    # a chunk of the scan: 8 rows x 128 columns, counted at the read-out of the carried
    # state, named by its result [rows, L, H, P]; the other matches add their time only
    ops = {"convolution_multiply_fusion f32[8,128,128,64]": {"s": 1.0, "count": 9},
           "fusion f32[8,128,128,64]": {"s": 1.0, "count": 9}}
    flops, moved = family.ssm_scan_count(record_of(config_file), ops)
    per = 8 * (128 * (2 * 128 * 128 * 64 + 4 * 128 * 64 * 128) + 2 * 128 * 128 * 128)
    assert flops == 9 * per
    assert moved == 9 * 8 * (4 * 128 * (8192 + 256) + 8 * 1_048_576)
    # the held experts a step touched x one d x F matrix in bf16
    ops = {"ragged-dot-none bf16[320,768]": {"s": 1.0, "count": 20}}
    assert family.moe_share_gmm_decode_count(record_of(config_file), ops) == (0.0, 0.0)
    flops, moved = family.moe_share_gmm_decode_count(
        record_of(config_file, {"moe/experts_touched": 30.0}), ops)
    assert moved == 20 * 30.0 * 4096 * 768 * 2
    # the grouped multiplication at an admission's rows: the held share of the copies it is handed
    ops = {"ragged-dot-none bf16[10240,768]": {"s": 1.0, "count": 20},
           "ragged-dot-none bf16[10240,4096]": {"s": 1.0, "count": 10}}
    every = 2 * 10240 * 768 * 4096 * 30
    assert family.moe_share_gmm_prefill_count(record_of(config_file), ops)[0] == 0.5 * every  # 36 of 72
    flops, moved = family.moe_share_gmm_prefill_count(
        record_of(config_file, {"moe/rows_here_share": 0.45}), ops)
    assert flops == pytest.approx(0.45 * every) and moved == pytest.approx(0.45 * 2 * 10240 * (768 + 4096) * 30)


OWN = {"ssm_step_roofline": manifest_cells.roofline("state-space layer"),
       "ssm_scan_prefill_roofline": manifest_cells.roofline("state-space layer"),
       "moe_share_gmm_decode_roofline": manifest_cells.roofline("expert layer"),
       "moe_share_gmm_prefill_roofline": manifest_cells.roofline("expert layer")}


def test_manifest_lists_the_cell_and_its_readers(either_tree):
    manifest, root = either_tree
    manifest_cells.cell_is_listed(manifest, root, CELL, CONFIG, TRAFFIC, chips=1)
    manifest_cells.own_metrics_list_the_cell(manifest, CELL, OWN)
    names = manifest_cells.metric_names(CELL, root)
    assert set(OWN) | {"ssm_state_gb", "moe_rows_here_share", "decode_serve_roofline", "moe_experts_touched",
                       "hbm_peak_gb.serve", "serve_pool_block_bitcast_share"} <= names
    # every serve metric the other serve cells all report is read here too
    manifest_cells.lists_what_every_other_serve_cell_lists(manifest, root, CELL)
    # olmoe's patterns (256; 8192 | 32768 rows) read nothing here
    assert not {"moe_gmm_decode_roofline", "moe_gmm_prefill_roofline"} & names
    traffic = manifest_cells.load(root, "traffic", TRAFFIC)
    chat = manifest_cells.load(root, "traffic", "chat")
    # chat.json key for key but for the knee, the sweep's
    assert set(chat) == set(traffic) and {k for k in chat if chat[k] != traffic[k]} <= {"name", "arrivals"}
    assert traffic["arrivals"]["load"] == chat["arrivals"]["load"] == 0.8


def test_the_configurations_own_tolerances_keep_the_rule(config_file):
    where = config_file["tolerances"]
    with open(harness.REPO + "/" + where) as f:
        table = json.load(f)
    checks.check_tolerance_file(table, where)
    tol = checks.tolerances_of(config_file, "bfloat16")
    shared = checks.tolerance_for("bfloat16", "bfloat16")
    # logits near 0.08 in size: the shared table would pass a program wrong by a factor of ten
    assert tol["logprob_rms"] < shared["logprob_rms"] / 3
    cheaper = table["cheaper"]["bfloat16/kv-bfloat16"][CELL]
    assert cheaper["logprob_rms"]["min"] > tol["logprob_rms"]


@pytest.fixture
def quiet_program(monkeypatch):
    monkeypatch.setenv("WANDB_DISABLED", "1")
    monkeypatch.setattr(harness, "place_compile_cache", lambda: "off")


def shrunk():
    cell = harness.load_cell(CELL)
    cell["config_file"].update(TINY)
    cell["config_file"].pop("tolerances", None)  # measured at the published sizes: the shared table at a toy size
    cell["mesh"] = {"dp": -1, "fsdp": 1, "tp": 1}
    cell["traffic_file"].update(
        seq_length=16, max_new_tokens=8, slots=16, admit_width=8, harvest_width=8,
        prompt_lengths={"dist": "lognormal", "median": 8, "sigma": 0.5, "lo": 2, "hi": 16},
        arrivals={"process": "poisson", "knee_per_s": 25.0, "load": 0.8}, warmup_requests=12,
        drain_limit_s=30, trace_seconds=1)
    return cell


@pytest.mark.parametrize("trace", [False, True], ids=["end-to-end", "traced"])
def test_cpu_rehearsal_of_the_cell(trace, capsys, quiet_program):
    line = run_cell(CELL, 2**31 + 35, 2.0, trace, allow_cpu=True, cell=shrunk())
    out = json.loads(line)
    said = capsys.readouterr().out
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] == 40
    assert out["device"]["platform"] == "cpu"
    assert all(c["ok"] for c in out["checks"].values())
    assert out["checks"]["reference.sampled_logprob_rms"]["value"] < 1e-3
    assert "check accounting.compiles_in_window" in said
    if not trace:
        assert set(out["metrics"]) == {"serve_itl_p95_ms", "serve_tokens_per_s", "setup_s"}
        return
    # program counters read on any platform; the device trace has no TPU plane here
    assert {"ssm_state_gb", "moe_rows_here_share", "moe_experts_touched", "moe_max_load",
            "engine_slot_util", "serve_itl_p99_ms"} <= set(out["metrics"])
    assert out["metrics"]["ssm_state_gb"]["value"] == pytest.approx(3 * 16 * (16 * 8 * 16 + 3 * 160) * 4 / 1e9)
    assert 0 < out["metrics"]["moe_rows_here_share"]["value"] < 1
    assert out["metrics"]["moe_experts_touched"]["value"] <= 4
    assert "ssm_step_roofline" not in out["metrics"] and "busy_s" not in out["device"]
