"""The qwen3-next-80b-a3b configuration and its cell: the shape rule's counts
and a decode step's bytes pinned by hand (ISSUE 58's arithmetic), the
published keys against the catalog row, the reference's two halves, the count
functions of the new readers on made-up trace operations, the tolerance file
under its rule, the manifest's entries, and a CPU rehearsal of
``serve-qwen3next-chat512`` at a toy size through the code the chip runs
(form only: CPU numbers)."""

import json
import re

import numpy as np
import pytest

from benchmark import arithmetic, checks, harness
from benchmark.run import run_cell

import manifest_cells

CELL = "serve-qwen3next-chat512"
CONFIG = "qwen3-next-80b-a3b"
TRAFFIC = "chat512-qwen3next"
# by hand, d 2048. A linear mixer: W_qkvz 2048 x (2048 + 2048 + 4096 + 4096), W_ba 2048 x 64, W_o 4096 x 2048;
# the taps 4 x 8192, dt_bias and A_log 32 each, the gated norm 128
LINEAR_MATRICES = 25_165_824 + 131_072 + 8_388_608
LINEAR = LINEAR_MATRICES + 32_768 + 64 + 128
# an attention mixer: W_q 2048 x 16 x 512 (the gate's half with it), W_k and W_v 2048 x 512, W_o 4096 x 2048; two head norms
ATTN_MATRICES = 16_777_216 + 2 * 1_048_576 + 8_388_608
ATTN = ATTN_MATRICES + 512
EXPERT = 3 * 2048 * 512
SHARED = EXPERT + 2048  # the gate's vector
ROUTER = 2048 * 512
TABLE = 37984 * 2048
FIXED_LINEAR = LINEAR + SHARED + ROUTER + 2 * 2048  # a block without its routed experts
FIXED_FULL = ATTN + SHARED + ROUTER + 2 * 2048
STATE_VALUES = 32 * 128 * 128 + 3 * 8192


@pytest.fixture(scope="module")
def config_file():
    return harness.load_json("configs", f"{CONFIG}.json")


def shape_of(cf):
    return arithmetic.model_shape(harness.load_family(cf), cf)


def test_parameters_of_the_cut_and_of_the_whole_model(config_file):
    assert (LINEAR, ATTN, EXPERT, ROUTER, SHARED) == (33_718_464, 27_263_488, 3_145_728, 1_048_576, 3_147_776)
    assert (FIXED_LINEAR + 128 * EXPERT, FIXED_FULL + 128 * EXPERT) == (440_572_096, 434_117_120)
    held = 6 * (FIXED_LINEAR + 128 * EXPERT) + 2 * (FIXED_FULL + 128 * EXPERT) + 2 * TABLE + 2048
    assert held == 3_667_251_328 == config_file["parameters"]
    assert arithmetic.backbone_params(shape_of(config_file)) == held
    assert 2 * held / 1e9 == pytest.approx(7.33, abs=0.005)  # bf16
    whole = dict(config_file, num_hidden_layers=48, num_experts=512, vocab_size=151936)
    want = 36 * (FIXED_LINEAR + 512 * EXPERT) + 12 * (FIXED_FULL + 512 * EXPERT) + 2 * 151936 * 2048 + 2048
    assert arithmetic.backbone_params(shape_of(whole)) == want == 79_674_391_296 == config_file["parameters_whole_model"]
    assert "79,674,391,296" in config_file["published"]["parameters"]


def test_shape_entries(config_file):
    s = shape_of(config_file)
    kinds = harness.load_family(config_file).layer_kinds(config_file)
    assert kinds == ["linear_attention"] * 3 + ["full_attention"] + ["linear_attention"] * 3 + ["full_attention"]
    assert len(s["layers"]) == 8
    for kind, layer in zip(kinds, s["layers"]):
        # 10 choices x 128 of 512 held: 2.5 experts a token, counted as 10 x 128 x 3145728 // 512
        routed_share = 10 * 128 * EXPERT // 512
        assert routed_share == 7_864_320
        assert layer["routed"] == {"expert_params": 3_145_728, "per_token": 10}
        if kind == "linear_attention":
            assert layer["params"] == 440_572_096 and layer["read_params"] == FIXED_LINEAR
            assert layer["matmul_params"] == LINEAR_MATRICES + SHARED + ROUTER + routed_share
            assert (layer["attn_dim"], layer["kv_values"], layer["state_values"]) == (0, 0, STATE_VALUES)
            assert STATE_VALUES == 548_864
        else:
            assert layer["params"] == 434_117_120 and layer["read_params"] == FIXED_FULL
            assert layer["matmul_params"] == ATTN_MATRICES + SHARED + ROUTER + routed_share
            assert (layer["attn_dim"], layer["kv_values"]) == (16 * 256, 2 * 2 * 256) and "state_values" not in layer
        assert "kv_read_cap" not in layer
        assert arithmetic.decode_read_params(layer) == layer["read_params"] + 10 * EXPERT
    assert s["embed_params"] == TABLE == 77_791_232
    assert s["final"] == {"params": 2048 + TABLE, "matmul_params": TABLE, "read_params": 2048 + TABLE}


def test_a_decode_steps_bytes_by_hand(config_file):
    s = shape_of(config_file)
    # the weights a step must read once in bf16 (10 experts a block: one token's choices), the head; 100
    # sequences: a state and a tail a linear layer read and written at float32, 640 cached positions of 1024
    # values (2 KB) in the two full layers
    weights = 2 * (6 * (FIXED_LINEAR + 10 * EXPERT) + 2 * (FIXED_FULL + 10 * EXPERT) + 2048 + TABLE)
    kv = 2 * 1024 * 100 * 641 * 2
    state = 2 * 6 * STATE_VALUES * 100 * 4
    assert (weights, kv, state) == (1_239_785_728, 262_553_600, 2_634_547_200)
    assert arithmetic.decode_step_bytes(s, 100, 640, weight_bytes=2, kv_bytes=2, state_bytes=4) == weights + kv + state
    with pytest.raises(ValueError, match="state_dtype"):
        arithmetic.decode_step_bytes(s, 100, 640)
    # the issue's footprint at 128 slots: the states, the tails, the two pools of capacity 1024
    assert 128 * 6 * 32 * 128 * 128 * 4 / 1e9 == pytest.approx(1.61, abs=0.005)
    assert 128 * 6 * 3 * 8192 * 4 / 1e9 == pytest.approx(0.075, abs=0.001)
    assert 128 * 1024 * 2 * 1024 * 2 / 1e9 == pytest.approx(0.54, abs=0.005)


def test_published_keys_are_the_catalog_rows(config_file):
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
        "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
        "num_experts_per_tok": 10, "num_hidden_layers": 48, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
    }
    differs = sorted(k for k, v in published.items() if config_file.get(k, "absent") != v)
    assert differs == sorted(config_file["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert sorted(config_file["reduced_how"]) == differs
    assert {k: config_file["published"][k] for k in differs} == {k: published[k] for k in differs}
    assert (config_file["num_hidden_layers"], config_file["num_experts"], config_file["vocab_size"]) == (8, 128, 37984)
    assert config_file["vocab_size"] * 4 == published["vocab_size"]  # a quarter
    assert config_file["num_hidden_layers"] % published["full_attention_interval"] == 0  # whole periods
    assert (config_file["num_router_experts"], config_file["first_local_expert"]) == (512, 0)
    # the cut brings keys of its own and no width: every other top-level number is a published key's
    own = {k for k, v in config_file.items() if isinstance(v, (int, float)) and not isinstance(v, bool)} - set(published)
    assert own == {"num_router_experts", "first_local_expert", "router_aux_loss_coef", "parameters",
                   "parameters_whole_model"}
    assert config_file["source"] == "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
    assert set(config_file["assumed"]) >= {
        "weights", "initialisers", "column_order", "eos_token_id", "state_dtype", "router_aux_loss_coef",
        "multi_token_prediction"}
    assert "4 chips" in config_file["deployment"] and "128 of 512" in config_file["deployment"]
    assert set(config_file["run"]["arch_keys"]) >= (set(published) - {"model_type"}) | {
        "num_router_experts", "first_local_expert", "state_dtype"}
    run = config_file["run"]
    assert run["dtype"] == run["param_dtype"] == run["kv_cache_dtype"] == "bfloat16" and run["state_dtype"] == "float32"
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["source_url"] == config_file["source"]]
    assert len(row) == 1 and row[0]["config"] == published


def test_check_config_refuses_an_inconsistent_file(config_file):
    family = harness.load_family(config_file)
    family.check_config(config_file)
    for over, said in [
        ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
        ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
        ({"mlp_only_layers": [0]}, "mlp_only_layers"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"use_sliding_window": True}, "use_sliding_window"),
        ({"hidden_act": "gelu"}, "hidden_act"),
        ({"layer_types": ["full_attention"] * 7}, "layer_types"),
        ({"num_key_value_heads": 3}, "num_key_value_heads"),
        ({"linear_num_value_heads": 24}, "linear_num_value_heads"),
        ({"num_experts": 513}, "not among the router's 512"),
        ({"first_local_expert": 385}, "not among the router's 512"),
        ({"state_dtype": "bfloat16"}, "state_dtype"),
        ({"run": dict(config_file["run"], kv_cache_dtype="int8")}, "int8"),
    ]:
        with pytest.raises(ValueError, match=said):
            family.check_config(dict(config_file, **over))


def test_the_program_builds_the_configuration(config_file):
    import jax

    from trlx_tpu.models.registry import get_model_family

    family = get_model_family(config_file["model_type"])
    cfg = family.config_cls.from_dict(harness.arch_of(config_file))
    assert (cfg.num_experts, cfg.num_router_experts, cfg.first_local_expert) == (128, 512, 0)
    assert cfg.layer_types == tuple(harness.load_family(config_file).layer_kinds(config_file))
    assert (cfg.rotary_dim, cfg.conv_channels, cfg.linear_chunk_size) == (64, 8192, 64)
    cache = jax.eval_shape(lambda: family.init_cache(cfg, 128, 1024))
    for kind, layer in zip(cfg.layer_types, cache):
        if kind == "full_attention":
            assert layer["k"].shape == layer["v"].shape == (128, 1024, 2, 256)
        else:
            assert layer["ssm_state"].shape == (128, 32, 128, 128) and layer["conv_tail"].shape == (128, 3, 8192)
            assert layer["ssm_state"].dtype == layer["conv_tail"].dtype == np.float32
    # 2 KB a position a full layer: the row the shape rule counts
    assert shape_of(config_file)["layers"][3]["kv_values"] * 2 == 2048


TINY = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=4, full_attention_interval=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, moe_intermediate_size=32, shared_expert_intermediate_size=48, num_experts=4,
    num_router_experts=16, first_local_expert=4, num_experts_per_tok=4,
)


def test_the_halves_compose_and_the_program_reads_the_same_logits(config_file):
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
    whole = np.asarray(family.forward(params, cf, ids, mask))
    np.testing.assert_array_equal(np.asarray(family.head(params, cf, hidden)), whole)
    assert family.HEAD_BLOCKS > 1 and whole.shape == (2, 12, 96)
    # the rows the checks ask for are the same rows of the whole
    part = family.head(params, cf, hidden[:, 7:-1])
    np.testing.assert_allclose(np.asarray(part), whole[:, 7:-1], rtol=1e-6, atol=1e-6)
    # and the program at float32, holding experts 4..7 of 16, reads the same logits
    got = model.apply({"params": params}, ids, attention_mask=mask)["logits"]
    real = np.asarray(mask) > 0
    assert np.abs(np.asarray(got) - whole)[real].max() / whole[real].std() < 2e-5


def record_of(config_file, gauges=None):
    return {"cell": {"config_file": config_file, "traffic_file": {"slots": 128}}, "gauges": gauges or {}}


def pattern(name):
    return harness.load_json("layer_metrics", f"{name}.json")["reader"]["op"]


# as a trace of the finished program names them (my chip run, PR 58): six reads a step, one a linear layer, and the
# six writes of the new states joined by the compiler into one operation a step
STEP_OPS = {"multiply_reduce_fusion f32[128,32,128]": {"s": 1.0, "count": 60},
            "multiply_add_fusion f32[128,32,128,128]": {"s": 1.0, "count": 10}}
CHUNK_OPS = {"convolution_multiply_fusion f32[8,32,64,128]": {"s": 1.0, "count": 24},
             "convolution_add_fusion f32[8,32,128,128]": {"s": 1.0, "count": 24},
             "fusion f32[8,32,64,128]": {"s": 1.0, "count": 24},
             "fusion bf16[8,32,64,64]": {"s": 1.0, "count": 48},
             "convolution_negate_fusion f32[8,32,16,2,2]": {"s": 1.0, "count": 24},
             "bitcast_dynamic-update-slice_fusion f32[2,8,32,64,128]": {"s": 1.0, "count": 24}}


def test_count_functions_of_the_new_readers(config_file):
    family = harness.load_family(config_file)
    # the step's passes over one layer's state: 128 slots x 32 x 128 x 128 float32 read and written, counted
    # at the operation that reads a layer's state out; the pass that writes the new states adds its time
    flops, moved = family.gdn_step_count(record_of(config_file), STEP_OPS)
    assert moved == 60 * 2 * 268_435_456 and flops == 60 * 7 * 67_108_864
    assert moved / 819e9 > flops / 197e12  # bound by the bytes
    assert all(re.search(pattern("gdn_step_roofline"), name) for name in STEP_OPS)
    for other in ("broadcast f32[128,32,128]", "multiply_reduce_fusion f32[128,32]", "fusion f32[128,3,8192]"):
        assert not re.search(pattern("gdn_step_roofline"), other)
    # a program that passed over the state three times would read a third: one execution at three times the time
    assert 100 * (2 * 268_435_456 / 819e9) / (3 * 2 * 268_435_456 / 819e9) == pytest.approx(33.3, abs=0.1)
    # a chunk of the rule: 8 rows, 32 value heads, L = 64, counted at the chunk's outputs [rows, Hv, L, Dv]
    flops, moved = family.gdn_chunk_prefill_count(record_of(config_file), CHUNK_OPS)
    L = 64
    a_head = 4 * L * L * 128 + L**3 + 4 * L * L * 128 + 3 * 2 * L * 128 * 128 + 2 * L * L * 128
    assert flops == 24 * 8 * 32 * a_head
    assert moved == 24 * 8 * (2 * L * (2 * 2048 + 2 * 4096) + 8 * 32 * 128 * 128)
    assert all(re.search(pattern("gdn_chunk_prefill_roofline"), name) for name in CHUNK_OPS)
    # the attention's, the experts' and the decode step's operations stay out
    for other in ("fusion f32[8,2,8,512]", "fusion bf16[8,128,12288]", "fusion bf16[8,512,32,128]",
                  "ragged-dot-none bf16[10240,512]", "multiply_add_fusion f32[128,32,128,128]", "fusion f32[8,32]"):
        assert not re.search(pattern("gdn_chunk_prefill_roofline"), other)
    # a decode step's grouped multiplication: the touched held experts x one d x F matrix in bf16
    ops = {"ragged-dot-none bf16[1280,512]": {"s": 1.0, "count": 160}, "ragged-dot-none bf16[1280,2048]": {"s": 1.0, "count": 80}}
    assert family.moe_ep4_gmm_decode_count(record_of(config_file), ops) == (0.0, 0.0)
    gauges = {"moe/experts_touched": 117.0, "moe/rows_here_share": 0.25}
    flops, moved = family.moe_ep4_gmm_decode_count(record_of(config_file, gauges), ops)
    assert moved == 240 * 117.0 * 2048 * 512 * 2 and flops == pytest.approx(240 * 2 * 0.25 * 1280 * 2048 * 512)
    assert moved / 819e9 > flops / 197e12  # bound by the bytes
    assert all(re.search(pattern("moe_ep4_gmm_decode_roofline"), name) for name in ops)
    # at an admission's rows: the rows whose expert is held here, every held expert's matrix read once
    ops = {"ragged-dot-none bf16[10240,512]": {"s": 1.0, "count": 16}, "ragged-dot-none bf16[40960,2048]": {"s": 1.0, "count": 8}}
    rows = 10240 * 16 + 40960 * 8
    flops, moved = family.moe_ep4_gmm_prefill_count(record_of(config_file), ops)
    assert flops == 2 * rows / 4 * 2048 * 512  # the even share, 128 of 512
    flops, moved = family.moe_ep4_gmm_prefill_count(record_of(config_file, gauges), ops)
    assert flops == pytest.approx(2 * 0.25 * rows * 2048 * 512)
    assert moved == pytest.approx(2 * 0.25 * rows * (2048 + 512) + 24 * 2 * 128 * 2048 * 512)
    assert all(re.search(pattern("moe_ep4_gmm_prefill_roofline"), name) for name in ops)
    # the other routed cells' row counts read nothing here, and this cell's nothing there
    for other in ("moe_gmm_decode_roofline", "moe_share_gmm_decode_roofline", "moe_top1_gmm_decode_roofline",
                  "moe_ep16_gmm_decode_roofline"):
        assert not re.search(pattern(other), "ragged-dot-none bf16[1280,512]")
    assert not re.search(pattern("moe_ep4_gmm_decode_roofline"), "ragged-dot-none bf16[512,2048]")
    assert not re.search(pattern("ssm_step_roofline"), "multiply_add_fusion f32[128,32,128,128]")


OWN = {"gdn_step_roofline": "linear attention", "gdn_chunk_prefill_roofline": "linear attention",
       "moe_ep4_gmm_decode_roofline": "expert layer", "moe_ep4_gmm_prefill_roofline": "expert layer"}


def test_manifest_lists_the_cell_and_its_readers(either_tree):
    manifest, root = either_tree
    # one chip: nothing it measures exists only across chips
    listed, _ = manifest_cells.cell_is_listed(manifest, root, CELL, CONFIG, TRAFFIC, chips=1)
    manifest_cells.own_metrics_list_the_cell(manifest, CELL, {n: manifest_cells.roofline(l) for n, l in OWN.items()})
    names = manifest_cells.metric_names(CELL, root)
    assert set(OWN) | {"decode_serve_roofline", "moe_experts_touched", "moe_max_load", "moe_rows_here_share",
                       "ssm_state_gb", "hbm_peak_gb.serve", "serve_step_ahead_share", "serve_long_gap_share",
                       "serve_pool_block_bitcast_share"} <= names
    # every serve metric the other serve cells all report is read here too
    manifest_cells.lists_what_every_other_serve_cell_lists(manifest, root, CELL)
    # the other cells' patterns and the tail's and the latent pool's readers read nothing here
    assert not {"moe_gmm_decode_roofline", "moe_share_gmm_decode_roofline", "moe_top1_gmm_decode_roofline",
                "moe_ep16_gmm_decode_roofline", "ssm_step_roofline", "ssm_scan_prefill_roofline", "cca_tail_gb",
                "mla_latent_gb", "moe_skip_share"} & names
    readers = {s["name"]: s["reader"] for s in harness.load_layer_metrics(CELL, root=root)}
    assert readers["ssm_state_gb"] == {"kind": "counter", "name": "cache/state_gb"}
    assert all(readers[n]["kind"] == "op_roofline" for n in OWN)
    assert {n: readers[n]["count"] for n in OWN} == {
        "gdn_step_roofline": "gdn_step_count", "gdn_chunk_prefill_roofline": "gdn_chunk_prefill_count",
        "moe_ep4_gmm_decode_roofline": "moe_ep4_gmm_decode_count",
        "moe_ep4_gmm_prefill_roofline": "moe_ep4_gmm_prefill_count"}
    family = harness.load_family(manifest_cells.load(root, "configs", CONFIG), root)
    assert all(callable(getattr(family, readers[n]["count"])) for n in OWN)
    traffic = manifest_cells.load(root, "traffic", TRAFFIC)
    assert (traffic["driver"], traffic["seq_length"], traffic["max_new_tokens"], traffic["min_new_tokens"],
            traffic["slots"], traffic["admit_width"], traffic["harvest_width"], traffic["drain_limit_s"],
            traffic["warmup_requests"], traffic["trace_seconds"]) == ("serve", 512, 512, 512, 128, 8, 8, 30, 12, 8)
    assert traffic["prompt_lengths"] == manifest_cells.load(root, "traffic", "reason-zaya1-8b")["prompt_lengths"]
    assert traffic["prompt_lengths"] == {"dist": "lognormal", "median": 128, "sigma": 0.8, "lo": 16, "hi": 512}
    assert traffic["weights_seed"] == traffic["order_seed"] == traffic["traffic_seed"] == 20261003
    assert traffic["arrivals"]["process"] == "poisson" and traffic["arrivals"]["load"] in (0.8, 0.7)
    assert traffic["arrivals"]["load"] == 0.8 or "0.7" in listed["why"]  # 0.7 only with its reason in `why`
    # over the quarter vocabulary a request of 512 tokens would draw EOS with 1.3%: past the README's 1%
    assert 1 - (1 - 1 / 37984) ** 512 == pytest.approx(0.0134, abs=0.0005)


def test_the_tolerances_the_cell_is_held_to(config_file):
    tol = checks.tolerances_of(config_file, "bfloat16")
    assert set(tol) >= {"logprob_rms", "logprob_max"}
    assert config_file["tolerances"] == f"benchmark/tolerances/{CONFIG}.json"
    with open(harness.REPO + "/" + config_file["tolerances"]) as f:
        table = json.load(f)
    checks.check_tolerance_file(table, config_file["tolerances"])  # measured on itself, and kept to the rule
    measured = table["measured"]["bfloat16/kv-bfloat16"][CELL]
    assert measured["logprob_rms"]["runs"] >= 8 and measured["logprob_rms"]["seeds"] >= 4
    assert measured["logprob_rms"]["max"] < tol["logprob_rms"] <= 3 * measured["logprob_rms"]["max"]
    cheaper = table["cheaper"]["bfloat16/kv-bfloat16"][CELL]
    assert cheaper["logprob_rms"]["runs"] >= 4 and cheaper["logprob_rms"]["min"] > tol["logprob_rms"]
    # what the tolerance does not hold is said, with its readings beside
    assert "DOES NOT HOLD" in table["how"] and table["beside"]


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
        seq_length=16, max_new_tokens=8, min_new_tokens=8, slots=16, admit_width=8, harvest_width=8,
        prompt_lengths={"dist": "lognormal", "median": 8, "sigma": 0.5, "lo": 2, "hi": 16},
        arrivals={"process": "poisson", "knee_per_s": 25.0, "load": 0.8}, warmup_requests=12,
        drain_limit_s=30, trace_seconds=1)
    return cell


@pytest.mark.parametrize("trace", [False, True], ids=["end-to-end", "traced"])
def test_cpu_rehearsal_of_the_cell(trace, capsys, quiet_program):
    line = run_cell(CELL, 2**31 + 58, 2.0, trace, allow_cpu=True, cell=shrunk())
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
    assert {"ssm_state_gb", "moe_rows_here_share", "moe_experts_touched", "moe_max_load",
            "engine_slot_util", "serve_itl_p99_ms", "serve_step_ahead_share"} <= set(out["metrics"])
    # three linear layers x 16 slots x (4 x 8 x 16 state + 3 x 96 tail) float32
    assert out["metrics"]["ssm_state_gb"]["value"] == pytest.approx(3 * 16 * (512 + 288) * 4 / 1e9)
    assert 0 < out["metrics"]["moe_rows_here_share"]["value"] < 1
    assert out["metrics"]["moe_experts_touched"]["value"] <= 4
    assert not set(OWN) & set(out["metrics"]) and "busy_s" not in out["device"]
