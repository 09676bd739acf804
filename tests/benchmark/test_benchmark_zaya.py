"""The zaya1-8b configuration and its cell: the shape rule's counts and a
decode step's bytes pinned by hand (ISSUE 45's arithmetic), the published
keys against the catalog row, the reference's two halves, the count
functions of the new readers on made-up trace operations, and a CPU
rehearsal of ``serve-zaya1-8b-reason`` at a toy size through the code the
chip runs (form only: CPU numbers)."""

import json

import numpy as np
import pytest

from benchmark import arithmetic, checks, harness
from benchmark.run import run_cell

import manifest_cells

CELL = "serve-zaya1-8b-reason"
CONFIG = "zaya1-8b"
TRAFFIC = "reason-zaya1-8b"
# by hand, d 2048, 8 query and 2 KV heads of 128, R 256, E 16, F 2048:
# projections 2048 x 1024 + 2 x 2048 x 256 + 1024 x 2048 = 5,242,880; the mix 10 x 2 x 128 x 128 =
# 327,680 taps + 2 x 1280 depthwise + 2 x 1280 biases + 2 temperatures = 5,122; the router 2048 x 256 +
# 2 x 256^2 + 256 x 17 = 659,712 matrices + 3 x 256 + 17 = 785; ten vectors of 2048; an expert 3 x 2048^2
PROJ, MIX, MIX_OTHER, ROUTER, ROUTER_OTHER, VECTORS, EXPERT = 5_242_880, 327_680, 5_122, 659_712, 785, 20_480, 12_582_912
FIXED = PROJ + MIX + MIX_OTHER + ROUTER + ROUTER_OTHER + VECTORS
TABLE = 262_272 * 2048
TAIL = 2 * 1280 + 128


@pytest.fixture(scope="module")
def config_file():
    return harness.load_json("configs", "zaya1-8b.json")


def shape_of(cf):
    return arithmetic.model_shape(harness.load_family(cf), cf)


def test_parameters_of_the_cut_and_of_the_whole_model(config_file):
    assert FIXED == 6_256_659 and FIXED + 16 * EXPERT == 207_583_251
    held = 20 * (FIXED + 16 * EXPERT) + TABLE + 2048
    assert held == 4_688_800_124 == config_file["parameters"]
    assert arithmetic.backbone_params(shape_of(config_file)) == held
    whole = dict(config_file, num_hidden_layers=40, layer_types=["hybrid"] * 40)
    assert arithmetic.backbone_params(shape_of(whole)) == held + 20 * 207_583_251 == 8_840_465_144


def test_shape_entries(config_file):
    s = shape_of(config_file)
    assert len(s["layers"]) == 20 and config_file["layer_types"] == ["hybrid"] * 20
    for layer in s["layers"]:
        assert layer["params"] == 207_583_251
        # a token is multiplied with the projections, the per-head taps, the router's matrices and one expert
        assert layer["matmul_params"] == PROJ + MIX + ROUTER + EXPERT == 18_813_184
        assert layer["read_params"] == FIXED
        assert layer["routed"] == {"expert_params": EXPERT, "per_token": 1}
        assert arithmetic.decode_read_params(layer) == FIXED + EXPERT
        assert (layer["attn_dim"], layer["kv_values"], layer["state_values"]) == (1024, 512, TAIL)
    assert s["embed_params"] == TABLE == 537_133_056
    assert s["final"] == {"params": 2048, "matmul_params": TABLE, "read_params": 2048 + TABLE}


def test_a_decode_steps_bytes_by_hand(config_file):
    s = shape_of(config_file)
    # weights and the tied head once in bf16 (one expert a block: the least); 25 sequences at 384
    # cached positions of 512 values a layer; the float32 tail both ways
    weights = 2 * (20 * (FIXED + EXPERT) + 2048 + TABLE)
    kv = 20 * 512 * 25 * 385 * 2
    tail = 2 * 20 * TAIL * 25 * 4
    assert (weights, kv, tail) == (1_827_853_048, 197_120_000, 10_752_000)
    got = arithmetic.decode_step_bytes(s, 25, 384, weight_bytes=2, kv_bytes=2, state_bytes=4)
    assert got == weights + kv + tail
    with pytest.raises(ValueError, match="state_bytes"):
        arithmetic.decode_step_bytes(s, 25, 384)


def test_published_keys_are_the_catalog_rows(config_file):
    published = {
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
        "max_position_embeddings": 131072, "model_type": "zaya", "moe_intermediate_size": 2048,
        "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
        "rope_parameters": {
            "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000, "rope_type": "default"},
            "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000, "rope_type": "default"},
            "rope_type": "default"},
        "router_hidden_size": 256, "sliding_window": None, "tie_word_embeddings": True, "vocab_size": 262272,
    }
    differs = sorted(k for k, v in published.items() if config_file.get(k, "absent") != v)
    assert differs == sorted(config_file["reduced"]) == ["layer_types", "num_hidden_layers"]
    assert config_file["layer_types"] == published["layer_types"][:20]
    assert config_file["source"] == "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
    assert config_file["published"]["num_hidden_layers"] == 40
    # every assumption ISSUE 45 marks [a], the initialisers and the rest
    assert set(config_file["assumed"]) >= {
        "weights", "value_shift", "conv_biases", "grouped_qk_mean", "score_scale", "residual_merge",
        "router_carry", "router_outputs", "initialisers", "eos_token_id", "state_dtype",
        "rope_parameters", "norm_eps"}
    assert "two chips" in config_file["deployment"] and "20 layers" in config_file["deployment"]
    assert config_file["run"]["state_dtype"] == config_file["state_dtype"] == "float32"
    assert set(config_file["run"]["arch_keys"]) >= set(published) - {"model_type"}


def test_check_config_refuses_an_inconsistent_file(config_file):
    family = harness.load_family(config_file)
    family.check_config(config_file)
    sliding = ["hybrid"] * 19 + ["hybrid_sliding"]
    for over, said in [
        ({"layer_types": sliding}, "layer_types"),
        ({"num_hidden_layers": 21}, "layer_types"),
        ({"sliding_window": 4096}, "sliding_window"),
        ({"num_experts_per_tok": 2}, "num_experts_per_tok"),
        ({"state_dtype": "bfloat16"}, "state_dtype"),
        ({"partial_rotary_factor": 1.0}, "rope_parameters"),
        ({"tie_word_embeddings": False}, "tie_word_embeddings"),
        ({"run": dict(config_file["run"], kv_cache_dtype="int8")}, "int8"),
    ]:
        with pytest.raises(ValueError, match=said):
            family.check_config(dict(config_file, **over))


def test_the_program_builds_the_configuration(config_file):
    from trlx_tpu.models.registry import get_model_family

    family = get_model_family(config_file["model_type"])
    cfg = family.config_cls.from_dict(harness.arch_of(config_file))
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.router_hidden_size) == (16, 1, 256)
    assert (cfg.num_hidden_layers, cfg.mix_heads, cfg.mix_channels, cfg.rotary_dim) == (20, 10, 1280, 64)
    assert (cfg.rope_theta, cfg.state_dtype) == (5e6, "float32")
    cache = jax_eval_shape(lambda: family.init_cache(cfg, 32, 1024))
    assert cache[0]["k"].shape == (32, 1024, 2, 128) and cache[0]["tail_z"].shape == (32, 1, 1280)
    # 1 KB a position a layer; the tail's values a sequence are the shape rule's
    assert 2 * 2 * 128 * 2 == 1024
    per_slot = sum(int(np.prod(cache[0][k].shape[1:])) for k in ("tail_z", "tail_c0", "tail_v"))
    assert per_slot == TAIL == shape_of(config_file)["layers"][0]["state_values"]


def jax_eval_shape(fn):
    import jax

    return jax.eval_shape(fn)


TINY = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=3, layer_types=["hybrid"] * 3, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_intermediate_size=32, num_experts=4, router_hidden_size=16,
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
    hidden, chosen = family.trunk_with_choices(params, cf, ids, mask)
    assert chosen.shape == (3, 2, 12) and int(chosen.max()) <= 4  # index 4 is the skip
    whole = np.asarray(family.forward(params, cf, ids, mask))
    np.testing.assert_array_equal(np.asarray(family.head(params, cf, hidden)), whole)
    assert family.HEAD_BLOCKS > 1 and whole.shape == (2, 12, 96)
    # the rows the checks ask for are the same rows of the whole
    part = family.head(params, cf, hidden[:, 7:-1])
    np.testing.assert_allclose(np.asarray(part), whole[:, 7:-1], rtol=1e-6, atol=1e-6)
    got = model.apply({"params": params}, ids, attention_mask=mask)["logits"]
    real = np.asarray(mask) > 0
    assert np.abs(np.asarray(got) - whole)[real].max() / whole[real].std() < 1e-5


def record_of(config_file, gauges=None):
    return {"cell": {"config_file": config_file, "traffic_file": {"slots": 32}}, "gauges": gauges or {}}


def test_count_functions_of_the_new_readers(config_file):
    family = harness.load_family(config_file)
    # a decode step's grouped multiplication: the touched experts x one d x F matrix in bf16
    ops = {"ragged-dot-none bf16[32,2048]": {"s": 1.0, "count": 60}}
    assert family.moe_top1_gmm_decode_count(record_of(config_file), ops) == (0.0, 0.0)
    flops, moved = family.moe_top1_gmm_decode_count(record_of(config_file, {"moe/experts_touched": 11.5}), ops)
    assert moved == 60 * 11.5 * 2048 * 2048 * 2 and flops == 60 * 2 * 32 * 2048 * 2048
    assert moved / 819e9 > flops / 197e12  # bound by the bytes
    # at an admission's rows: every row that chose an expert, the skipped left out
    ops = {"ragged-dot-none bf16[1024,2048]": {"s": 1.0, "count": 60}, "ragged-dot-none bf16[4096,2048]": {"s": 1.0, "count": 3}}
    every = 2 * 2048 * 2048 * (1024 * 60 + 4096 * 3)
    assert family.moe_top1_gmm_prefill_count(record_of(config_file), ops)[0] == every
    flops, moved = family.moe_top1_gmm_prefill_count(record_of(config_file, {"moe/skip_share": 0.25}), ops)
    assert flops == pytest.approx(0.75 * every) and moved == pytest.approx(0.75 * 2 * 4096 * (1024 * 60 + 4096 * 3))
    assert flops / 197e12 > moved / 819e9  # bound by the FLOPs
    # the per-head mix of a chunk forward: counted once, at the product [H, rows, Dh]; the layout
    # passes around it add their time only
    ops = {"fusion f32[10,1024,128]": {"s": 1.0, "count": 20}, "copy bf16[10,8,128,256]": {"s": 1.0, "count": 20},
           "fusion f32[8,128,10,128]": {"s": 1.0, "count": 20}, "fusion f32[10,4096,128]": {"s": 1.0, "count": 2}}
    flops, moved = family.cca_mix_prefill_count(record_of(config_file), ops)
    assert flops == 2 * 10 * 2 * 128 * 128 * (1024 * 20 + 4096 * 2)
    assert moved == 22 * 2 * 327_680 and flops / 197e12 > moved / 819e9  # bound by the FLOPs
    import re

    reader = harness.load_json("layer_metrics", "cca_mix_prefill_roofline.json")["reader"]
    assert all(re.search(reader["op"], name) for name in ops)
    assert not re.search(reader["op"], "fusion f32[10,1024,1280]")


OWN = {"moe_top1_gmm_decode_roofline": manifest_cells.roofline("expert layer"),
       "moe_top1_gmm_prefill_roofline": manifest_cells.roofline("expert layer"),
       "cca_mix_prefill_roofline": manifest_cells.roofline("CCA layer"),
       "moe_skip_share": manifest_cells.gauge("expert layer", "serve_itl_p95_ms", "share", "lower"),
       "cca_tail_gb": manifest_cells.gauge("device", "serve_tokens_per_s", "GB", "lower")}


def test_manifest_lists_the_cell_and_its_readers(either_tree):
    manifest, root = either_tree
    _, config = manifest_cells.cell_is_listed(manifest, root, CELL, CONFIG, TRAFFIC, chips=1)
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    manifest_cells.own_metrics_list_the_cell(manifest, CELL, OWN)
    names = manifest_cells.metric_names(CELL, root)
    assert set(OWN) | {"decode_serve_roofline", "moe_experts_touched", "moe_max_load", "hbm_peak_gb.serve",
                       "serve_step_ahead_share", "serve_long_gap_share", "serve_pool_block_bitcast_share"} <= names
    # every serve metric the other serve cells all report is read here too
    manifest_cells.lists_what_every_other_serve_cell_lists(manifest, root, CELL)
    # the other routed cells' patterns (256 | 320 rows) and the state's readers read nothing here
    assert not {"moe_gmm_decode_roofline", "moe_share_gmm_decode_roofline", "ssm_state_gb", "moe_rows_here_share"} & names
    traffic = manifest_cells.load(root, "traffic", TRAFFIC)
    chat = manifest_cells.load(root, "traffic", "chat")
    # chat.json key for key but for the answers' length and the knee, the sweep's (4.0 here as there)
    assert set(chat) == set(traffic)
    assert {"name", "max_new_tokens"} <= {k for k in chat if chat[k] != traffic[k]} <= {"name", "arrivals", "max_new_tokens"}
    assert traffic["max_new_tokens"] == 512 and traffic["arrivals"]["load"] == chat["arrivals"]["load"] == 0.8
    assert {k for k in chat["arrivals"] if chat["arrivals"][k] != traffic["arrivals"][k]} <= {"knee_per_s"}


def test_the_tolerances_the_cell_is_held_to(config_file):
    tol = checks.tolerances_of(config_file, "bfloat16")
    assert set(tol) >= {"logprob_rms", "logprob_max"}
    if "tolerances" in config_file:  # its own, measured on itself, and kept to the rule
        with open(harness.REPO + "/" + config_file["tolerances"]) as f:
            table = json.load(f)
        checks.check_tolerance_file(table, config_file["tolerances"])
        cheaper = table["cheaper"]["bfloat16/kv-bfloat16"][CELL]
        assert cheaper["logprob_rms"]["min"] > tol["logprob_rms"]
    else:
        assert tol == checks.tolerance_for("bfloat16", "bfloat16")


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
    line = run_cell(CELL, 2**31 + 45, 2.0, trace, allow_cpu=True, cell=shrunk())
    out = json.loads(line)
    said = capsys.readouterr().out
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] == 40
    assert out["device"]["platform"] == "cpu"
    assert all(c["ok"] for c in out["checks"].values())
    assert out["checks"]["reference.sampled_logprob_rms"]["value"] < 5e-3
    assert "check accounting.compiles_in_window" in said
    if not trace:
        assert set(out["metrics"]) == {"serve_itl_p95_ms", "serve_tokens_per_s", "setup_s"}
        return
    # program counters read on any platform; the device trace has no TPU plane here
    assert {"cca_tail_gb", "moe_skip_share", "moe_experts_touched", "moe_max_load",
            "engine_slot_util", "serve_itl_p99_ms", "serve_step_ahead_share"} <= set(out["metrics"])
    assert out["metrics"]["cca_tail_gb"]["value"] == pytest.approx(3 * 16 * (96 + 96 + 16) * 4 / 1e9)
    assert 0 <= out["metrics"]["moe_skip_share"]["value"] < 1
    assert out["metrics"]["moe_experts_touched"]["value"] <= 4
    assert "moe_top1_gmm_decode_roofline" not in out["metrics"] and "busy_s" not in out["device"]
