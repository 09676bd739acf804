"""The deepseek-v3 configuration and its cell: the shape rule's counts and a
decode step's bytes pinned by hand (ISSUE 53's arithmetic), the published
keys against the catalog row, the reference's two halves and its blocks,
the count functions of the new readers on made-up trace operations, the
tolerance file under its rule, the manifest's entries, and a CPU rehearsal
of ``serve-deepseekv3-reason1k`` at a toy size through the code the chip runs
(form only: CPU numbers)."""

import contextlib
import json
import re

import numpy as np
import pytest

from benchmark import arithmetic, checks, harness
from benchmark.run import run_cell

import manifest_cells

CELL = "serve-deepseekv3-reason1k"
CONFIG = "deepseek-v3"
TRAFFIC = "reason1k-deepseekv3"
OLD_CELL = "serve-deepseekv3-reason"  # replaced by PR 55; its readings stay in the tolerance file as evidence
# by hand, d 7168, 128 heads of nope 128 + rope 64 (scores) and 128 (values), c_q 1536, c_kv 512:
# W_dq 7168 x 1536 + W_uq 1536 x 24576 + W_dkv 7168 x 576 + W_ukv 512 x 32768 + W_o 16384 x 7168; the two inner norms
ATTN = 11_010_048 + 37_748_736 + 4_128_768 + 16_777_216 + 117_440_512
ATTN_NORMS = 1536 + 512
DENSE = 3 * 7168 * 18432
EXPERT = 3 * 7168 * 2048
ROUTER = 7168 * 256
TABLE = 16160 * 7168
DENSE_BLOCK = ATTN + ATTN_NORMS + DENSE + 2 * 7168
FIXED = ATTN + ATTN_NORMS + EXPERT + ROUTER + 256 + 2 * 7168  # a routed block without its routed experts
ROUTED_BLOCK = FIXED + 16 * EXPERT
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn"}


@pytest.fixture(scope="module")
def config_file():
    return harness.load_json("configs", "deepseek-v3.json")


def shape_of(cf):
    return arithmetic.model_shape(harness.load_family(cf), cf)


def test_parameters_of_the_cut_and_of_the_whole_model(config_file):
    assert (ATTN, DENSE, EXPERT, ROUTER) == (187_105_280, 396_361_728, 44_040_192, 1_835_008)
    assert (DENSE_BLOCK, ROUTED_BLOCK) == (583_483_392, 937_640_192)
    held = DENSE_BLOCK + 4 * ROUTED_BLOCK + 2 * TABLE + 7168
    assert held == 4_565_721_088 == config_file["parameters"]
    assert arithmetic.backbone_params(shape_of(config_file)) == held
    assert config_file["bytes"]["weights_gb"] == pytest.approx(2 * held / 1e9)
    assert config_file["bytes"]["latent_pool_gb"] == pytest.approx(64 * 1536 * 5 * 576 * 2 / 1e9)
    whole = dict(config_file, num_hidden_layers=61, first_k_dense_replace=3, n_routed_experts=256, vocab_size=129280)
    want = 3 * DENSE_BLOCK + 58 * (FIXED + 256 * EXPERT) + 2 * 129280 * 7168 + 7168
    assert arithmetic.backbone_params(shape_of(whole)) == want == 671_026_419_200
    assert "671,026,419,200" in config_file["published"]["parameters"]


def test_shape_entries(config_file):
    s = shape_of(config_file)
    assert len(s["layers"]) == 5
    dense, routed = s["layers"][0], s["layers"][1:]
    assert dense["params"] == dense["read_params"] == DENSE_BLOCK and "routed" not in dense
    assert dense["matmul_params"] == ATTN + DENSE
    for layer in routed:
        assert layer["params"] == ROUTED_BLOCK and layer["read_params"] == FIXED
        # the attention, the shared expert, the router and half an expert: 8 choices x 16 of 256 held
        assert layer["matmul_params"] == ATTN + EXPERT + ROUTER + EXPERT // 2
        assert layer["routed"] == {"expert_params": 44_040_192, "per_token": 8}
        assert arithmetic.decode_read_params(layer) == FIXED + 8 * EXPERT
    for layer in s["layers"]:
        # 4 x attn_dim is 2 x 192 + 2 x 128 a head and pair; a position keeps one latent row
        assert layer["attn_dim"] == 128 * 160 == 20480 and 4 * layer["attn_dim"] == 128 * (2 * 192 + 2 * 128)
        assert layer["kv_values"] == 576 and "state_values" not in layer and "kv_read_cap" not in layer
    assert s["embed_params"] == TABLE == 115_834_880
    assert s["final"] == {"params": 7168 + TABLE, "matmul_params": TABLE, "read_params": 7168 + TABLE}


def test_a_decode_steps_bytes_by_hand(config_file):
    s = shape_of(config_file)
    # the weights a step must read once in bf16 (8 experts a routed block: one token's choices), the head;
    # 50 sequences at 640 cached positions of one 576-value row a layer
    weights = 2 * (DENSE_BLOCK + 4 * (FIXED + 8 * EXPERT) + 7168 + TABLE)
    kv = 5 * 576 * 50 * 641 * 2
    assert (weights, kv) == (6_081_200_128, 184_608_000)
    assert arithmetic.decode_step_bytes(s, 50, 640, weight_bytes=2, kv_bytes=2) == weights + kv
    # keys and values of 128 heads would be 71 times the row
    assert 128 * (192 + 128) / 576 == pytest.approx(71.1, abs=0.05)


def test_published_keys_are_the_catalog_rows(config_file):
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3, "hidden_act": "silu", "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512, "max_position_embeddings": 163840, "model_type": "deepseek_v3",
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
        "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 128, "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_scaling": YARN,
        "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280,
    }
    differs = sorted(k for k, v in published.items() if config_file.get(k, "absent") != v)
    assert differs == sorted(config_file["reduced"]) == [
        "first_k_dense_replace", "n_routed_experts", "num_hidden_layers", "num_nextn_predict_layers", "vocab_size"]
    assert sorted(config_file["reduced_how"]) == differs
    assert {k: config_file["published"][k] for k in differs} == {k: published[k] for k in differs}
    assert (config_file["num_hidden_layers"], config_file["first_k_dense_replace"], config_file["n_routed_experts"],
            config_file["vocab_size"], config_file["num_nextn_predict_layers"]) == (5, 1, 16, 16160, 0)
    assert config_file["vocab_size"] * 8 == published["vocab_size"]  # an eighth, the floor
    assert (config_file["num_router_experts"], config_file["first_local_expert"]) == (256, 0)
    # the cut brings those two keys of its own and no other: every other top-level number is a published key's
    own = {k for k, v in config_file.items() if isinstance(v, (int, float)) and not isinstance(v, bool)} - set(published)
    assert own == {"num_router_experts", "first_local_expert", "parameters"}
    assert config_file["source"] == "https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json"
    assert set(config_file["assumed"]) >= {
        "weights", "rotary_pair_layout", "dropped_group_mask", "initialisers", "e_score_correction_bias",
        "eos_token_id", "shared_expert_sum"}
    assert "16 chips" in config_file["deployment"] and "16 of 256" in config_file["deployment"]
    assert set(config_file["run"]["arch_keys"]) >= (set(published) - {"model_type"}) | {
        "num_router_experts", "first_local_expert"}
    assert config_file["run"]["dtype"] == config_file["run"]["param_dtype"] == config_file["run"]["kv_cache_dtype"] == "bfloat16"


def test_check_config_refuses_an_inconsistent_file(config_file):
    family = harness.load_family(config_file)
    family.check_config(config_file)
    for over, said in [
        ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
        ({"scoring_func": "softmax"}, "scoring_func"),
        ({"topk_method": "greedy"}, "topk_method"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"q_lora_rank": None}, "q_lora_rank"),
        ({"num_key_value_heads": 8}, "num_key_value_heads"),
        ({"rope_scaling": {"type": "linear", "factor": 4}}, "rope_scaling"),
        ({"first_k_dense_replace": 6}, "first_k_dense_replace"),
        ({"n_routed_experts": 257}, "not among the router's 256"),
        ({"first_local_expert": 241}, "not among the router's 256"),
        ({"n_group": 7}, "n_group"),
        ({"rope_scaling": dict(config_file["rope_scaling"], mscale_all_dim=0.707)}, "mscale"),
        ({"run": dict(config_file["run"], kv_cache_dtype="int8")}, "int8"),
    ]:
        with pytest.raises(ValueError, match=said):
            family.check_config(dict(config_file, **over))


def jax_eval_shape(fn):
    import jax

    return jax.eval_shape(fn)


def test_the_program_builds_the_configuration(config_file):
    from trlx_tpu.models.registry import get_model_family

    family = get_model_family(config_file["model_type"])
    cfg = family.config_cls.from_dict(harness.arch_of(config_file))
    assert (cfg.n_routed_experts, cfg.num_router_experts, cfg.first_local_expert) == (16, 256, 0)
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace, cfg.latent_width, cfg.qk_head_dim) == (5, 1, 576, 192)
    assert cfg.score_scale == pytest.approx(192 ** -0.5 * 1.3688879 ** 2, rel=1e-6)
    cache = jax_eval_shape(lambda: family.init_cache(cfg, 64, 1536))
    assert len(cache) == 5 and all(set(layer) == {"k"} and layer["k"].shape == (64, 1536, 1, 576) for layer in cache)
    # 1.15 KB a position a layer: the row the shape rule counts
    assert 576 * 2 == 1152 and shape_of(config_file)["layers"][0]["kv_values"] == cfg.latent_width


TINY = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=12,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=4, num_router_experts=16, first_local_expert=4,
    num_experts_per_tok=4, n_group=4, topk_group=2,
)


def test_the_halves_compose_and_the_blocks_change_nothing(config_file):
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
    assert family.HEAD_BLOCKS > 1 and family.MLP_BLOCKS > 1 and whole.shape == (2, 12, 96)
    # the rows the checks ask for are the same rows of the whole
    part = family.head(params, cf, hidden[:, 7:-1])
    np.testing.assert_allclose(np.asarray(part), whole[:, 7:-1], rtol=1e-6, atol=1e-6)
    # queries in blocks and the dense feed-forward in blocks are the same arithmetic
    old = family.QUERY_BLOCK, family.MLP_BLOCKS
    try:
        family.QUERY_BLOCK, family.MLP_BLOCKS = 5, 1
        np.testing.assert_allclose(np.asarray(family.forward(params, cf, ids, mask)), whole, rtol=1e-5, atol=1e-6)
    finally:
        family.QUERY_BLOCK, family.MLP_BLOCKS = old
    # and the program at float32, holding experts 4..7 of 16, reads the same logits
    got = model.apply({"params": params}, ids, attention_mask=mask)["logits"]
    real = np.asarray(mask) > 0
    assert np.abs(np.asarray(got) - whole)[real].max() / whole[real].std() < 1e-5


def record_of(config_file, gauges=None, decode=None):
    return {"cell": {"config_file": config_file, "traffic_file": {"slots": 64}}, "gauges": gauges or {},
            "decode": decode or {"batch": 48.0, "mean_context": 640.0}}


def pattern(name):
    return harness.load_json("layer_metrics", f"{name}.json")["reader"]["op"]


def test_count_functions_of_the_new_readers(config_file):
    family = harness.load_family(config_file)
    # the decode step's absorbed read: counted once a layer's scores [slots, capacity, H]; 2 (c + rope) + 2 c
    # FLOPs a head and position over one 576-value row read once, at the slice's mean batch and context
    ops = {"fusion f32[64,1536,128]": {"s": 1.0, "count": 10}, "fusion bf16[64,128,576]": {"s": 1.0, "count": 10}}
    flops, moved = family.mla_absorbed_read_count(record_of(config_file), ops)
    assert flops == 10 * 48 * 641 * 128 * 2 * (2 * 512 + 64) and moved == 10 * 48 * 641 * 576 * 2
    assert flops / 197e12 == pytest.approx(moved / 819e9, rel=0.12)  # at the chip's ridge: 256 FLOPs a byte
    assert all(re.search(pattern("mla_absorbed_read_roofline"), name) for name in ops)
    # the softmax's statistics [slots, H] stay out: any fusion of that result would add its time to the share
    for other in ("fusion f32[64,1536,1280]", "fusion f32[64,128]", "fusion bf16[64,128]"):
        assert not re.search(pattern("mla_absorbed_read_roofline"), other)
    # an admission's decompress-and-attend, counted once a layer's scores [rows, H, columns]
    ops = {"convolution_convert_fusion bf16[8,512,128,256]": {"s": 1.0, "count": 7}, "slice bf16[8,512,128,128]": {"s": 1.0, "count": 14},
           "fusion f32[8,128,512]": {"s": 1.0, "count": 3}, "fusion bf16[8,128,128,512]": {"s": 1.0, "count": 3},
           "fusion f32[8,128,128]": {"s": 1.0, "count": 4}, "fusion bf16[8,128,128,128]": {"s": 1.0, "count": 4}}
    flops, moved = family.mla_prefill_attn_count(record_of(config_file), ops)
    decompress, pair = 2 * 512 * 128 * 256, 128 * (2 * 192 + 2 * 128)
    whole = 8 * (decompress * 512 + pair * 512 * 512 / 2)  # its own columns, half the pairs
    chunk = 8 * (decompress * (512 + 128) / 2 + pair * 128 * 512 / 2)  # the mean over a group's four chunks
    assert flops == 3 * whole + 4 * chunk and flops / 197e12 > moved / 819e9  # bound by the FLOPs
    assert all(re.search(pattern("mla_prefill_attn_roofline"), name) for name in ops)
    assert not re.search(pattern("mla_prefill_attn_roofline"), "fusion f32[8,128]")
    # a decode step's grouped multiplication: the touched held experts x one d x F matrix in bf16
    ops = {"ragged-dot-none bf16[512,2048]": {"s": 1.0, "count": 60}, "ragged-dot-none bf16[512,7168]": {"s": 1.0, "count": 30}}
    assert family.moe_ep16_gmm_decode_count(record_of(config_file), ops) == (0.0, 0.0)
    gauges = {"moe/experts_touched": 13.5, "moe/rows_here_share": 0.06}
    flops, moved = family.moe_ep16_gmm_decode_count(record_of(config_file, gauges), ops)
    assert moved == 90 * 13.5 * 7168 * 2048 * 2 and flops == pytest.approx(90 * 2 * 0.06 * 512 * 7168 * 2048)
    assert moved / 819e9 > flops / 197e12  # bound by the bytes
    assert all(re.search(pattern("moe_ep16_gmm_decode_roofline"), name) for name in ops)
    # at an admission's rows: the rows whose expert is held here, every held expert's matrix read once
    ops = {"ragged-dot-none bf16[8192,2048]": {"s": 1.0, "count": 8}, "ragged-dot-none bf16[32768,7168]": {"s": 1.0, "count": 4}}
    rows = 8192 * 8 + 32768 * 4
    flops, moved = family.moe_ep16_gmm_prefill_count(record_of(config_file), ops)
    assert flops == 2 * rows / 16 * 7168 * 2048  # the even share, 16 of 256
    flops, moved = family.moe_ep16_gmm_prefill_count(record_of(config_file, gauges), ops)
    assert flops == pytest.approx(2 * 0.06 * rows * 7168 * 2048)
    assert moved == pytest.approx(2 * 0.06 * rows * (7168 + 2048) + 12 * 2 * 16 * 7168 * 2048)
    assert all(re.search(pattern("moe_ep16_gmm_prefill_roofline"), name) for name in ops)
    # the other routed cells' row counts read nothing here, and this cell's nothing there
    for other in ("moe_gmm_decode_roofline", "moe_share_gmm_decode_roofline", "moe_top1_gmm_decode_roofline"):
        assert not re.search(pattern(other), "ragged-dot-none bf16[512,2048]")
    assert not re.search(pattern("moe_ep16_gmm_decode_roofline"), "ragged-dot-none bf16[256,1024]")


OWN = {"mla_latent_gb": ("device", "serve_tokens_per_s"), "mla_absorbed_read_roofline": ("latent attention", "serve_itl_p95_ms"),
       "mla_prefill_attn_roofline": ("latent attention", "serve_itl_p95_ms"),
       "moe_ep16_gmm_decode_roofline": ("expert layer", "serve_itl_p95_ms"),
       "moe_ep16_gmm_prefill_roofline": ("expert layer", "serve_itl_p95_ms")}


def test_manifest_lists_the_cell_and_its_readers(either_tree):
    manifest, root = either_tree
    manifest_cells.cell_is_listed(manifest, root, CELL, CONFIG, TRAFFIC, chips=1)
    manifest_cells.own_metrics_list_the_cell(manifest, CELL, {
        n: manifest_cells.gauge(layer, moves, "GB", "lower") if n == "mla_latent_gb" else manifest_cells.roofline(layer, moves)
        for n, (layer, moves) in OWN.items()})
    names = manifest_cells.metric_names(CELL, root)
    assert set(OWN) | {"decode_serve_roofline", "moe_experts_touched", "moe_max_load", "moe_rows_here_share",
                       "hbm_peak_gb.serve", "serve_step_ahead_share", "serve_long_gap_share"} <= names
    # every serve metric the other serve cells all report is read here too
    manifest_cells.lists_what_every_other_serve_cell_lists(manifest, root, CELL)
    # a latent pool has no block view: the share of block writes that are bitcasts is the cells' that keep keys and values
    assert "serve_pool_block_bitcast_share" not in names
    # the other routed cells' patterns and the state's and the tail's readers read nothing here
    assert not {"moe_gmm_decode_roofline", "moe_share_gmm_decode_roofline", "moe_top1_gmm_decode_roofline",
                "ssm_state_gb", "cca_tail_gb", "moe_skip_share"} & names
    readers = {s["name"]: s["reader"] for s in harness.load_layer_metrics(CELL, root=root)}
    assert readers["mla_latent_gb"] == {"kind": "counter", "name": "cache/latent_gb"}
    assert all(readers[n]["kind"] == "op_roofline" for n in OWN if n != "mla_latent_gb")
    traffic = manifest_cells.load(root, "traffic", TRAFFIC)
    zaya = manifest_cells.load(root, "traffic", "reason-zaya1-8b")
    # zaya's mix key for key but for the answers' length, the slots, the drain, the seed and the knee, the sweep's,
    # and the three keys that keep a seed from changing the work (PR 55): every answer runs to its budget, one model,
    # one order of the prompt lengths
    assert set(traffic) - set(zaya) == {"min_new_tokens", "weights_seed", "order_seed"} and set(zaya) <= set(traffic)
    assert traffic["min_new_tokens"] == traffic["max_new_tokens"]
    assert traffic["weights_seed"] == traffic["order_seed"] == traffic["traffic_seed"]
    assert {k for k in zaya if zaya[k] != traffic[k]} == {
        "name", "traffic_seed", "max_new_tokens", "slots", "arrivals", "drain_limit_s"}
    assert (traffic["seq_length"], traffic["max_new_tokens"], traffic["slots"], traffic["admit_width"],
            traffic["harvest_width"], traffic["drain_limit_s"], traffic["warmup_requests"], traffic["trace_seconds"]) == (
        512, 1024, 64, 8, 8, 40, 12, 8)
    assert traffic["prompt_lengths"] == {"dist": "lognormal", "median": 128, "sigma": 0.8, "lo": 16, "hi": 512}
    assert traffic["arrivals"]["process"] == "poisson" and traffic["arrivals"]["load"] == 0.8
    assert {k for k in zaya["arrivals"] if zaya["arrivals"][k] != traffic["arrivals"][k]} <= {"knee_per_s"}


def test_the_tolerances_the_cell_is_held_to(config_file):
    tol = checks.tolerances_of(config_file, "bfloat16")
    assert set(tol) >= {"logprob_rms", "logprob_max"}
    assert config_file["tolerances"] == "benchmark/tolerances/deepseek-v3.json"
    with open(harness.REPO + "/" + config_file["tolerances"]) as f:
        table = json.load(f)
    checks.check_tolerance_file(table, config_file["tolerances"])  # measured on itself, and kept to the rule
    measured = table["measured"]["bfloat16/kv-bfloat16"][CELL]
    assert measured["logprob_rms"]["runs"] >= 8 and measured["logprob_rms"]["seeds"] >= 4
    assert measured["logprob_rms"]["max"] < tol["logprob_rms"] <= 3 * measured["logprob_rms"]["max"]
    # the control, read on the cell as it was and again on this one; the replaced cell's readings stay as evidence
    for cell in (CELL, OLD_CELL):
        cheaper = table["cheaper"]["bfloat16/kv-bfloat16"][cell]
        assert cheaper["logprob_rms"]["runs"] >= 4 and cheaper["logprob_rms"]["min"] > tol["logprob_rms"]
    assert table["measured"]["bfloat16/kv-bfloat16"][OLD_CELL]["logprob_rms"]["runs"] == 36


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
    line = run_cell(CELL, 2**31 + 53, 2.0, trace, allow_cpu=True, cell=shrunk())
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
    assert {"mla_latent_gb", "moe_rows_here_share", "moe_experts_touched", "moe_max_load",
            "engine_slot_util", "serve_itl_p99_ms", "serve_step_ahead_share"} <= set(out["metrics"])
    assert out["metrics"]["mla_latent_gb"]["value"] == pytest.approx(3 * 16 * 24 * 24 * 2 / 1e9)
    assert 0 < out["metrics"]["moe_rows_here_share"]["value"] < 1
    assert out["metrics"]["moe_experts_touched"]["value"] <= 4
    assert "mla_absorbed_read_roofline" not in out["metrics"] and "busy_s" not in out["device"]


@pytest.mark.parametrize("seed", [2**31 + 57, 2**31 + 58])
def test_the_float8_latent_control_comes_out_not_correct(seed, monkeypatch, quiet_program):
    """The cell's control as the tree keeps it (``benchmark/tools/control_run.py --control
    float8_latent``; the chip's readings at the cell's own size are the tolerance file's ``cheaper``
    group) planted under a rehearsal, beside a sound run of the same seed. Two things are the toy's
    own, for both runs alike: every expert is chosen (4 of 4, so no choice can fall the other way
    between bfloat16 and the float32 reference, which at this size swamps any rounding), and the
    seeded matrices are four times louder (at width 64, normal(0.02) gives logits too flat for a
    rounded cache to move). The reference lifts the server's own tree, so it follows both."""
    import importlib.util
    import os

    import jax

    from benchmark import serve_driver

    spec = importlib.util.spec_from_file_location(
        "control_run", os.path.join(harness.REPO, "benchmark", "tools", "control_run.py"))
    control_run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control_run)
    seeded = serve_driver.seeded_params
    monkeypatch.setattr(serve_driver, "seeded_params", lambda config, s: jax.tree_util.tree_map(
        lambda x: 4.0 * x if x.ndim >= 2 else x, seeded(config, s)))

    def rms(control):
        cell = shrunk()
        cell["config_file"].update(num_router_experts=4, n_routed_experts=4, first_local_expert=0,
                                   num_experts_per_tok=4, n_group=1, topk_group=1)
        with control_run.CONTROLS["float8_latent"]() if control else contextlib.nullcontext():
            out = json.loads(run_cell(CELL, seed, 1.0, False, allow_cpu=True, cell=cell))
        check = out["checks"]["reference.sampled_logprob_rms"]
        assert out["correct"] is check["ok"] and out["failed"] == 0
        return check["value"], check["ok"]

    import trlx_tpu.models.deepseek_v3 as family

    program = family.decode_attention
    sound, ok = rms(False)
    assert ok
    cheaper, ok = rms(True)
    assert not ok and cheaper > 2 * sound
    assert family.decode_attention is program  # the control takes itself out again
