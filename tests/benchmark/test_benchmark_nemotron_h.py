"""The nemotron-3-super-120b-a12b configuration and its cell: the shape
rule's counts and a decode step's bytes pinned by hand (ISSUE 66's
arithmetic), the published keys against the catalog row, the reference's two
halves, the count functions of the new readers on trace operations as the
finished program names them, the tolerance file under its rule, the
manifest's entries, a CPU rehearsal of ``serve-nemotron3super-reason512`` at
a toy size through the code the chip runs (form only: CPU numbers), and the
cell's control (the compute one precision below) coming out not correct
there."""

import contextlib
import json
import re

import numpy as np
import pytest

from benchmark import arithmetic, checks, harness
from benchmark.run import run_cell

import manifest_cells

CELL = "serve-nemotron3super-reason512"
CONFIG = "nemotron-3-super-120b-a12b"
TRAFFIC = "reason512-nemotron3super"
# by hand, d 4096. An M layer: W_in 4096 x (8192 + 10240 + 128), W_out 8192 x 4096; the taps 4 x 10240 and their
# bias, dt_bias, A_log and D 128 each, the gated norm 8192; the layer's norm 4096
M_MATRICES = 4096 * 18560 + 8192 * 4096
M_LAYER = M_MATRICES + 40960 + 10240 + 3 * 128 + 8192 + 4096
# a * layer: q and o 4096 x 4096, k and v 4096 x 256
ATT_MATRICES = 2 * 4096 * 4096 + 2 * 4096 * 256
ATT_LAYER = ATT_MATRICES + 4096
# an E layer without its routed experts: the router 4096 x 512 and its bias, the latent's way down and up
# (4096 x 1024 each), the shared expert 2 x 4096 x 5376, the norm
EXPERT = 2 * 1024 * 2688
E_MATRICES = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
E_FIXED = E_MATRICES + 512 + 4096
TABLE = 32768 * 4096
STATE_VALUES = 128 * 64 * 128 + 3 * 10240


@pytest.fixture(scope="module")
def config_file():
    return harness.load_json("configs", f"{CONFIG}.json")


def shape_of(cf):
    return arithmetic.model_shape(harness.load_family(cf), cf)


def test_parameters_of_the_cut_and_of_the_whole_model(config_file):
    assert (M_LAYER, ATT_LAYER, EXPERT, E_FIXED) == (109_640_064, 35_655_680, 5_505_024, 54_530_560)
    assert E_FIXED + 512 * EXPERT == 2_873_102_848
    held = 5 * M_LAYER + ATT_LAYER + 5 * (E_FIXED + 128 * EXPERT) + 2 * TABLE + 4096
    assert held == 4_648_163_712 == config_file["parameters"]  # the issue's count
    assert arithmetic.backbone_params(shape_of(config_file)) == held
    assert 2 * held / 1e9 == pytest.approx(9.30, abs=0.005)  # bf16
    # the two counts the catalog gives and the reading was not fitted to: 120B held, 12B a token
    whole = dict(config_file, **config_file["published"])
    whole.update(n_routed_experts=512, num_router_experts=512)
    s = shape_of(whole)
    want = 40 * M_LAYER + 8 * ATT_LAYER + 40 * (E_FIXED + 512 * EXPERT) + 2 * 131072 * 4096 + 4096
    assert arithmetic.backbone_params(s) == want == 120_668_707_840
    a_token = sum(l["matmul_params"] for l in s["layers"]) + s["final"]["matmul_params"]
    assert a_token == 40 * M_MATRICES + 8 * ATT_MATRICES + 40 * (E_MATRICES + 22 * EXPERT) + 131072 * 4096
    assert a_token / 1e9 == pytest.approx(12.2, abs=0.05)
    assert "120.67 B" in config_file["published"]["parameters"]


def test_shape_entries(config_file):
    s = shape_of(config_file)
    pattern = config_file["hybrid_override_pattern"]
    assert pattern == "MEMEMEMEM*E" and len(s["layers"]) == 11
    for kind, layer in zip(pattern, s["layers"]):
        assert "kv_read_cap" not in layer
        if kind == "M":
            assert layer["params"] == layer["read_params"] == M_LAYER and layer["matmul_params"] == M_MATRICES
            assert (layer["attn_dim"], layer["kv_values"], layer["state_values"]) == (0, 0, STATE_VALUES)
            assert "routed" not in layer
        elif kind == "*":
            assert layer["params"] == layer["read_params"] == ATT_LAYER and layer["matmul_params"] == ATT_MATRICES
            # 32 heads of 128; 2 KV heads of 128, keys and values: 1 KB a position in bf16
            assert (layer["attn_dim"], layer["kv_values"]) == (4096, 512) and "state_values" not in layer
        else:
            assert layer["params"] == E_FIXED + 128 * EXPERT and layer["read_params"] == E_FIXED
            # 22 choices x 128 of 512 held: 5.5 experts a token, a whole number of parameters
            assert layer["matmul_params"] == E_MATRICES + 11 * EXPERT // 2
            # a token's 22 choices can all lie on the other three chips: the least a step must read is none
            assert layer["routed"] == {"expert_params": EXPERT, "per_token": 0}
            assert (layer["attn_dim"], layer["kv_values"]) == (0, 0) and "state_values" not in layer
            assert arithmetic.decode_read_params(layer) == E_FIXED
    assert STATE_VALUES == 1_079_296
    assert s["embed_params"] == TABLE == 134_217_728
    assert s["final"] == {"params": 4096 + TABLE, "matmul_params": TABLE, "read_params": 4096 + TABLE}


def test_a_decode_steps_bytes_by_hand(config_file):
    s = shape_of(config_file)
    # the weights a step must read once in bf16 whatever it routes, and the head; 64 sequences: a state and a tail
    # an M layer read and written at float32, 640 cached positions of 512 values in the one attention layer
    weights = 2 * (5 * M_LAYER + ATT_LAYER + 5 * E_FIXED + 4096 + TABLE)
    kv = 512 * 64 * 641 * 2
    state = 2 * 5 * STATE_VALUES * 64 * 4
    assert (weights, kv, state) == (1_981_461_248, 42_008_576, 2_762_997_760)
    assert arithmetic.decode_step_bytes(s, 64, 640, weight_bytes=2, kv_bytes=2, state_bytes=4) == weights + kv + state
    with pytest.raises(ValueError, match="state_dtype"):
        arithmetic.decode_step_bytes(s, 64, 640)
    # the issue's arithmetic at 64 slots: 21.6 MB of state a sequence, 1.38 GB in all, 0.07 GB of pool
    assert 5 * STATE_VALUES * 4 / 1e6 == pytest.approx(21.6, abs=0.05)
    assert 64 * 5 * STATE_VALUES * 4 / 1e9 == pytest.approx(1.38, abs=0.005)
    assert 64 * 1024 * 1024 / 1e9 == pytest.approx(0.07, abs=0.005)
    # what the floor leaves out: the experts a step does touch, about 120 of 128 a layer at 64 full slots
    assert 5 * 120 * 2 * EXPERT / 1e9 == pytest.approx(6.6, abs=0.05)


def test_published_keys_are_the_catalog_rows(config_file):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["source_url"] == config_file["source"]]
    assert len(row) == 1 and row[0]["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
    published = row[0]["config"]
    differs = sorted(k for k, v in published.items() if config_file.get(k, "absent") != v)
    assert differs == sorted(config_file["reduced"]) == [
        "hybrid_override_pattern", "mtp_hybrid_override_pattern", "n_routed_experts", "num_hidden_layers",
        "num_nextn_predict_layers", "vocab_size"]
    assert sorted(config_file["reduced_how"]) == differs
    assert {k: config_file["published"][k] for k in differs} == {k: published[k] for k in differs}
    assert (config_file["num_hidden_layers"], config_file["n_routed_experts"], config_file["vocab_size"],
            config_file["num_nextn_predict_layers"]) == (11, 128, 32768, 0)
    assert config_file["vocab_size"] * 4 == published["vocab_size"] and config_file["n_routed_experts"] * 4 == 512
    # one whole period of the published string, as it is: 5 : 5 : 1 is the published 40 : 40 : 8
    assert published["hybrid_override_pattern"][27:38] == config_file["hybrid_override_pattern"] == "MEMEMEMEM*E"
    assert [published["hybrid_override_pattern"].count(c) for c in "ME*"] == [40, 40, 8]
    assert (config_file["num_router_experts"], config_file["first_local_expert"]) == (512, 0)
    # every published width unchanged
    assert (config_file["hidden_size"], config_file["mamba_num_heads"], config_file["mamba_head_dim"],
            config_file["ssm_state_size"], config_file["n_groups"], config_file["conv_kernel"],
            config_file["num_attention_heads"], config_file["num_key_value_heads"], config_file["head_dim"],
            config_file["moe_latent_size"], config_file["moe_intermediate_size"],
            config_file["moe_shared_expert_intermediate_size"], config_file["num_experts_per_tok"],
            config_file["routed_scaling_factor"], config_file["chunk_size"], config_file["expand"]) == (
        4096, 128, 64, 128, 8, 4, 32, 2, 128, 1024, 2688, 5376, 22, 5, 128, 2)
    # the cut brings keys of its own and no width: every other top-level number is a published key's
    own = {k for k, v in config_file.items() if isinstance(v, (int, float)) and not isinstance(v, bool)} - set(published)
    assert own == {"num_router_experts", "first_local_expert", "parameters"}
    assert set(config_file["assumed"]) >= {
        "weights", "rms_norm", "positions", "dt", "gated_norm", "router_input", "latent", "intermediate_size",
        "initialisers", "eos_token_id", "state_dtype", "unread"}
    assert "4 chips" in config_file["deployment"] and "128 of 512" in config_file["deployment"]
    assert "32,768" in config_file["deployment"]
    unread = {"intermediate_size", "max_position_embeddings", "model_type", "moe_shared_expert_overlap", "norm_eps",
              "mtp_hybrid_override_pattern", "num_logits_to_keep", "partial_rotary_factor",
              "rescale_prenorm_residual", "use_mamba_kernels"}
    assert set(config_file["run"]["arch_keys"]) >= (set(published) - unread) | {
        "num_router_experts", "first_local_expert", "state_dtype"}
    run = config_file["run"]
    assert run["dtype"] == run["param_dtype"] == run["kv_cache_dtype"] == "bfloat16" and run["state_dtype"] == "float32"


def test_check_config_refuses_an_inconsistent_file(config_file):
    family = harness.load_family(config_file)
    family.check_config(config_file)
    for over, said in [
        ({"hybrid_override_pattern": "MEMEMEMEM-E"}, "hybrid_override_pattern"),
        ({"num_hidden_layers": 12}, "hybrid_override_pattern"),
        ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
        ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
        ({"mamba_hidden_act": "gelu"}, "mamba_hidden_act"),
        ({"n_shared_experts": 2}, "n_shared_experts"),
        ({"n_group": 8}, "n_group"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"residual_in_fp32": True}, "residual_in_fp32"),
        ({"n_groups": 3}, "n_groups"),
        ({"mamba_num_heads": 64}, "expand"),
        ({"n_routed_experts": 513}, "not among the router's 512"),
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
    assert family.name == "nemotron_h"
    cfg = family.config_cls.from_dict(harness.arch_of(config_file))
    assert (cfg.n_routed_experts, cfg.num_router_experts, cfg.first_local_expert) == (128, 512, 0)
    assert "".join(cfg.layer_types) == config_file["hybrid_override_pattern"]
    assert cfg.cache_layer_types == ("M",) * 5 + ("*",) and (cfg.conv_channels, cfg.mamba_inner) == (10240, 8192)
    cache = jax.eval_shape(lambda: family.init_cache(cfg, 64, 1024))
    assert len(cache) == 6  # the five expert layers keep nothing
    for kind, layer in zip(cfg.cache_layer_types, cache):
        if kind == "*":
            assert set(layer) == {"k", "v"} and layer["k"].shape == (64, 1024, 2, 128)
        else:
            assert layer["ssm_state"].shape == (64, 128, 64, 128) and layer["conv_tail"].shape == (64, 3, 10240)
            assert layer["ssm_state"].dtype == layer["conv_tail"].dtype == np.float32
    assert shape_of(config_file)["layers"][9]["kv_values"] * 2 == 1024  # 1 KB a position


TINY = dict(
    vocab_size=96, hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
    mamba_head_dim=16, ssm_state_size=16, n_groups=2, chunk_size=8, n_routed_experts=4, num_router_experts=16,
    first_local_expert=4, num_experts_per_tok=6, moe_latent_size=32, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=80,
)
SHALLOW = dict(TINY, num_hidden_layers=5, hybrid_override_pattern="MEM*E")  # every kind of layer, fewer to compile


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
    params = jax.jit(lambda k: model.init(k, ids)["params"])(jax.random.PRNGKey(0))
    hidden = jax.jit(lambda p: family.trunk(p, cf, ids, mask))(params)
    whole = np.asarray(jax.jit(lambda p: family.forward(p, cf, ids, mask))(params))
    np.testing.assert_allclose(np.asarray(family.head(params, cf, hidden)), whole, rtol=1e-6, atol=1e-6)
    assert family.HEAD_BLOCKS > 1 and whole.shape == (2, 12, 96)
    # the rows the checks ask for are the same rows of the whole
    part = family.head(params, cf, hidden[:, 7:-1])
    np.testing.assert_allclose(np.asarray(part), whole[:, 7:-1], rtol=1e-6, atol=1e-6)
    # and the program at float32, holding experts 4..7 of 16, reads the same logits
    got = jax.jit(lambda p: model.apply({"params": p}, ids, attention_mask=mask)["logits"])(params)
    real = np.asarray(mask) > 0
    assert np.abs(np.asarray(got) - whole)[real].max() / whole[real].std() < 1e-5


def record_of(config_file, gauges=None):
    return {"cell": {"config_file": config_file, "traffic_file": {"slots": 64}}, "gauges": gauges or {}}


def pattern(name):
    return harness.load_json("layer_metrics", f"{name}.json")["reader"]["op"]


# as a trace of the finished program names them (my chip run, PR 66): five reads of a state a step, one an M layer;
# an admission's chunk loop, one execution of its body a chunk and layer
STEP_OPS = {"multiply_reduce_fusion f32[64,128,64]": {"s": 1.0, "count": 50}}
SCAN_OPS = {"broadcast f32[8,8,16,128,128]": {"s": 1.0, "count": 25},
            "fusion f32[8,8,128,16,64]": {"s": 1.0, "count": 24},
            "fusion f32[8,8,16,64,128]": {"s": 1.0, "count": 23},
            "multiply_add_fusion f32[8,128,64,128]": {"s": 1.0, "count": 21},
            "fusion f32[4,8,128,128,64]": {"s": 1.0, "count": 20},
            "fusion f32[8,128,128,64]": {"s": 1.0, "count": 5},
            "dynamic-slice_convert_fusion f32[1,8,128,128,64]": {"s": 1.0, "count": 20},
            "copy f32[8,4,128,128,64]": {"s": 1.0, "count": 5},
            "reshape bf16[8,4,128,128,64]": {"s": 1.0, "count": 5}}


def test_count_functions_of_the_new_readers(config_file):
    family = harness.load_family(config_file)
    # the step's pass over one layer's state: 64 slots x 128 x 64 x 128 float32 read and written (2 x 268 MB)
    flops, moved = family.ssm_group_step_count(record_of(config_file), STEP_OPS)
    assert moved == 50 * 2 * 268_435_456 and flops == 50 * 5 * 67_108_864
    assert moved / 819e9 > flops / 197e12  # bound by the bytes
    assert all(re.search(pattern("ssm_group_step_roofline"), name) for name in STEP_OPS)
    for other in ("multiply_reduce_fusion f32[32,128,64]", "broadcast_multiply_fusion f32[64,128,64]",
                  "multiply_reduce_fusion f32[64]", "fusion f32[64,128,64,128]", "multiply_reduce_fusion f32[128,32,128]"):
        assert not re.search(pattern("ssm_group_step_roofline"), other)
    # a chunk of the scan: 8 rows, L = 128, counted once a chunk and layer at the decays spread over the groups
    flops, moved = family.ssm_group_scan_count(record_of(config_file), SCAN_OPS)
    L, H, P, N, G = 128, 128, 64, 128, 8
    assert flops == 25 * 8 * (H * (2 * L * L * P + 4 * L * P * N) + G * 2 * L * L * N)
    assert moved == 25 * 8 * (2 * 2 * L * (H * P + 2 * G * N) + 2 * 4 * H * P * N)
    assert moved / 819e9 > flops / 197e12  # bound by the bytes at one chunk's columns a state
    assert all(re.search(pattern("ssm_group_scan_prefill_roofline"), name) for name in SCAN_OPS)
    # the decode step's operations, the experts', the projections' and the norms' stay out
    for other in ("multiply_reduce_fusion f32[64,128,64]", "fusion f32[64,128,64,128]", "fusion bf16[8,128,18560]",
                  "fusion f32[8,128]", "ragged-dot-none bf16[22528,2688]", "fusion f32[8,128,8192]",
                  "dynamic-slice_dynamic-update-slice_fusion f32[8,128,64,128]", "fusion bf16[8,512,10240]"):
        assert not re.search(pattern("ssm_group_scan_prefill_roofline"), other)
    # a decode step's grouped multiplication, two an expert layer: the touched held experts x one 1024 x 2688
    # matrix in bf16
    ops = {"ragged-dot-none bf16[1408,2688]": {"s": 1.0, "count": 50}, "ragged-dot-none bf16[1408,1024]": {"s": 1.0, "count": 50}}
    assert family.moe_latent_gmm_decode_count(record_of(config_file), ops) == (0.0, 0.0)
    gauges = {"moe/experts_touched": 64.0, "moe/rows_here_share": 0.25}
    flops, moved = family.moe_latent_gmm_decode_count(record_of(config_file, gauges), ops)
    assert moved == 100 * 64.0 * 1024 * 2688 * 2 and flops == pytest.approx(100 * 2 * 0.25 * 1408 * 1024 * 2688)
    assert moved / 819e9 > flops / 197e12  # bound by the bytes: a held expert sees 2.75 rows
    assert all(re.search(pattern("moe_latent_gmm_decode_roofline"), name) for name in ops)
    # at an admission's rows (8 x 128 x 22 a chunk, 8 x 512 x 22 whole): the rows whose expert is held here, every
    # held expert's matrix read once
    ops = {"ragged-dot-none bf16[22528,2688]": {"s": 1.0, "count": 10}, "ragged-dot-none bf16[90112,1024]": {"s": 1.0, "count": 5}}
    rows = 22528 * 10 + 90112 * 5
    flops, moved = family.moe_latent_gmm_prefill_count(record_of(config_file), ops)
    assert flops == 2 * rows / 4 * 1024 * 2688  # the even share, 128 of 512
    flops, moved = family.moe_latent_gmm_prefill_count(record_of(config_file, gauges), ops)
    assert flops == pytest.approx(2 * 0.25 * rows * 1024 * 2688)
    assert moved == pytest.approx(2 * 0.25 * rows * (1024 + 2688) + 15 * 2 * 128 * 1024 * 2688)
    assert all(re.search(pattern("moe_latent_gmm_prefill_roofline"), name) for name in ops)
    assert not re.search(pattern("moe_latent_gmm_decode_roofline"), "ragged-dot-none bf16[22528,2688]")
    assert not re.search(pattern("moe_latent_gmm_prefill_roofline"), "ragged-dot-none bf16[1408,2688]")


OWN = {"moe_latent_gmm_decode_roofline": "expert layer", "moe_latent_gmm_prefill_roofline": "expert layer",
       "ssm_group_step_roofline": "state-space layer", "ssm_group_scan_prefill_roofline": "state-space layer"}


def test_manifest_lists_the_cell_and_its_readers(either_tree):
    manifest, root = either_tree
    listed, _ = manifest_cells.cell_is_listed(manifest, root, CELL, CONFIG, TRAFFIC, chips=1)
    manifest_cells.own_metrics_list_the_cell(manifest, CELL, {n: manifest_cells.roofline(l) for n, l in OWN.items()})
    names = manifest_cells.metric_names(CELL, root)
    assert set(OWN) | {"decode_serve_roofline", "moe_experts_touched", "moe_max_load", "moe_rows_here_share",
                       "ssm_state_gb", "hbm_peak_gb.serve",
                       "serve_step_ahead_share", "serve_long_gap_share"} <= names
    # every serve metric the other serve cells all report is read here too
    manifest_cells.lists_what_every_other_serve_cell_lists(manifest, root, CELL)
    # `serve_pool_block_bitcast_share` stays with the five cells it names: `test_benchmark_block_write_metric.py`
    # pins that list, and a `model_config` PR edits no file the benchmark has (CHANGES.md, PR 66); this cell's pool of
    # 2 KV heads of 128 is a bitcast at the block write like zaya's (the gauge reads 1.0 in its runs)
    assert "serve_pool_block_bitcast_share" not in names
    readers = {s["name"]: s["reader"] for s in harness.load_layer_metrics(CELL, root=root)}
    assert readers["ssm_state_gb"] == {"kind": "counter", "name": "cache/state_gb"}
    assert all(readers[n]["kind"] == "op_roofline" for n in OWN)
    assert {n: readers[n]["count"] for n in OWN} == {
        "moe_latent_gmm_decode_roofline": "moe_latent_gmm_decode_count",
        "moe_latent_gmm_prefill_roofline": "moe_latent_gmm_prefill_count",
        "ssm_group_step_roofline": "ssm_group_step_count", "ssm_group_scan_prefill_roofline": "ssm_group_scan_count"}
    family = harness.load_family(manifest_cells.load(root, "configs", CONFIG), root)
    assert all(callable(getattr(family, readers[n]["count"])) for n in OWN)
    # the mix: open loop on one fixed schedule, every request to its budget, one model, 64 slots (ISSUE 66)
    traffic = manifest_cells.load(root, "traffic", TRAFFIC)
    assert traffic["driver"] == "serve" and traffic["slots"] == 64
    assert traffic["weights_seed"] == traffic["order_seed"] == traffic["traffic_seed"] == 20261005
    assert (traffic["seq_length"], traffic["max_new_tokens"], traffic["min_new_tokens"], traffic["admit_width"],
            traffic["harvest_width"], traffic["drain_limit_s"], traffic["warmup_requests"],
            traffic["trace_seconds"]) == (512, 512, 512, 8, 8, 30, 12, 8)
    assert traffic["prompt_lengths"] == {"dist": "lognormal", "median": 128, "sigma": 0.8, "lo": 16, "hi": 512}
    assert traffic["arrivals"]["process"] == "poisson" and traffic["arrivals"]["load"] == 0.8
    assert set(traffic["arrivals"]) == {"process", "knee_per_s", "load"}
    # the rate the cell's `why` states is the mix's
    assert "%g/s" % round(traffic["arrivals"]["knee_per_s"] * 0.8, 2) in listed["why"]
    assert "64 slots" in listed["why"] and "2.75 rows" in listed["why"]
    # over a quarter of the vocabulary a request of 512 tokens would draw EOS with 1.6%: past the README's 1%
    assert 1 - (1 - 1 / 32768) ** 512 == pytest.approx(0.0155, abs=0.0005)


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
    cell["config_file"].update(SHALLOW)
    cell["config_file"].pop("tolerances", None)  # measured at the published sizes: the shared table at a toy size
    cell["mesh"] = {"dp": -1, "fsdp": 1, "tp": 1}
    cell["traffic_file"].update(
        seq_length=16, max_new_tokens=8, min_new_tokens=8, slots=16, admit_width=8, harvest_width=8,
        prompt_lengths={"dist": "lognormal", "median": 8, "sigma": 0.5, "lo": 2, "hi": 16},
        arrivals={"process": "poisson", "knee_per_s": 25.0, "load": 0.8}, warmup_requests=12,
        drain_limit_s=30, trace_seconds=1)
    return cell


def test_cpu_rehearsal_of_the_cell(capsys, quiet_program):
    line = run_cell(CELL, 2**31 + 66, 2.0, True, allow_cpu=True, cell=shrunk())
    out = json.loads(line)
    said = capsys.readouterr().out
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] == 40
    assert out["device"]["platform"] == "cpu"
    assert all(c["ok"] for c in out["checks"].values())
    assert out["checks"]["reference.sampled_logprob_rms"]["value"] < 5e-3
    assert "check accounting.compiles_in_window" in said
    # program counters read on any platform; the device trace has no TPU plane here
    assert {"ssm_state_gb", "moe_rows_here_share", "moe_experts_touched", "moe_max_load", "engine_slot_util",
            "serve_itl_p99_ms", "serve_step_ahead_share"} <= set(out["metrics"])
    # two M layers x 16 slots x (8 x 16 x 16 state + 3 x 192 tail) float32; nothing for an expert layer
    assert out["metrics"]["ssm_state_gb"]["value"] == pytest.approx(2 * 16 * (2048 + 576) * 4 / 1e9)
    assert 0 < out["metrics"]["moe_rows_here_share"]["value"] < 1
    assert out["metrics"]["moe_experts_touched"]["value"] <= 4
    assert not set(OWN) & set(out["metrics"]) and "busy_s" not in out["device"]


@contextlib.contextmanager
def float8_compute():
    """The cell's control (the tolerance file's ``cheaper`` group; the chip's
    readings at the cell's own size are there): the compute one precision
    below the bfloat16 the configuration states. The input of every Dense
    projection (the mixer's two, the attention's four, the latent's way
    down, the shared expert's way in, the head) and of the expert layer
    rounded to float8_e4m3fn; weights as served, accumulation float32, the
    state float32."""
    import flax.linen as nn
    import jax.numpy as jnp

    from trlx_tpu.ops import moe

    f8 = lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
    dense_call, layer = nn.Dense.__call__, moe.expert_layer
    nn.Dense.__call__ = lambda self, x: dense_call(self, f8(x))
    moe.expert_layer = lambda h, *a, **kw: layer(f8(h), *a, **kw)
    try:
        yield
    finally:
        nn.Dense.__call__, moe.expert_layer = dense_call, layer


def test_the_float8_compute_control_comes_out_not_correct(monkeypatch, quiet_program):
    """The control planted under a rehearsal, beside a sound run of the same
    seed. Two things are the toy's own, for both runs alike: every expert is
    chosen (4 of 4, so no choice can fall the other way between bfloat16 and
    the float32 reference, which at this size swamps any rounding), and the
    seeded matrices are twice as loud (at width 64, normal(0.02) gives logits
    so flat that rounded inputs hardly move them). The reference lifts the
    server's own tree, so it follows both."""
    import flax.linen as nn
    import jax

    from benchmark import serve_driver
    from trlx_tpu.ops import moe

    seeded = serve_driver.seeded_params
    monkeypatch.setattr(serve_driver, "seeded_params", lambda config, s: jax.tree_util.tree_map(
        lambda x: 2.0 * x if x.ndim >= 2 else x, seeded(config, s)))

    def rms(control):
        cell = shrunk()
        cell["config_file"].update(num_router_experts=4, n_routed_experts=4, first_local_expert=0,
                                   num_experts_per_tok=4)
        with float8_compute() if control else contextlib.nullcontext():
            out = json.loads(run_cell(CELL, 2**31 + 66, 1.0, False, allow_cpu=True, cell=cell))
        check = out["checks"]["reference.sampled_logprob_rms"]
        assert out["correct"] is check["ok"] and out["failed"] == 0
        return check["value"], check["ok"]

    dense_call, layer = nn.Dense.__call__, moe.expert_layer
    sound, ok = rms(False)
    assert ok
    cheaper, ok = rms(True)
    assert not ok and cheaper > 2 * sound
    assert nn.Dense.__call__ is dense_call and moe.expert_layer is layer  # the control takes itself out again
