"""The OLMoE configuration and its cell: parameters and shape sums pinned
by hand, the count functions of the grouped multiplication on made-up
trace operations, the manifest's entries, and a CPU rehearsal of
``serve-olmoe1b7b-chat`` at a toy size through the code the chip runs
(form only: CPU numbers)."""

import json

import pytest

from benchmark import arithmetic, harness
from benchmark.run import run_cell

import manifest_cells

CELL = "serve-olmoe1b7b-chat"
CONFIG = "olmoe-1b-7b"
TRAFFIC = "chat-olmoe"
# by hand: a block = 4 x 2048^2 (attention) + 2048 x 64 (router) + 64 x 3 x
# 2048 x 1024 (experts) + 4 x 2048 (norms) = 419,569,664; the embedding and
# the head 50304 x 2048 each, the final norm 2048
BLOCK = 16_777_216 + 131_072 + 402_653_184 + 8_192
ENDS = 2 * 103_022_592 + 2_048


@pytest.fixture(scope="module")
def config_file():
    return harness.load_json("configs", f"{CONFIG}.json")


def shape_of(cf):
    return arithmetic.model_shape(harness.load_family(cf), cf)


@pytest.mark.parametrize("layers,parameters", [(12, 5_240_883_200), (16, 6_919_161_856)])
def test_parameters_at_the_shipped_and_the_published_depth(config_file, layers, parameters):
    cf = dict(config_file, num_hidden_layers=layers)
    assert BLOCK == 419_569_664 and layers * BLOCK + ENDS == parameters
    assert arithmetic.backbone_params(shape_of(cf)) == parameters
    if layers == config_file["num_hidden_layers"]:
        assert config_file["parameters"] == parameters


def test_shape_sums(config_file):
    s = shape_of(config_file)
    block = s["layers"][0]
    assert all(l == block for l in s["layers"]) and len(s["layers"]) == 12
    expert = 3 * 2048 * 1024
    assert block["routed"] == {"expert_params": expert, "per_token": 8}
    # a token is multiplied with attention, the router and 8 experts: 67.2 M
    assert block["matmul_params"] == 16_777_216 + 131_072 + 8 * expert == 67_239_936
    assert block["read_params"] == 16_777_216 + 131_072 + 8_192
    assert arithmetic.decode_read_params(block) == block["read_params"] + 8 * expert
    assert (block["attn_dim"], block["kv_values"]) == (2048, 4096)
    assert s["final"] == {"params": 2048 + 103_022_592, "matmul_params": 103_022_592,
                          "read_params": 2048 + 103_022_592}


def test_published_keys_are_the_catalog_rows(config_file):
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304,
    }
    differs = sorted(k for k, v in published.items() if config_file.get(k, "absent") != v)
    assert differs == config_file["reduced"] == ["num_hidden_layers"]
    assert set(config_file["assumed"]) >= {"weights", "intermediate_size", "router_aux_loss_coef"}
    assert config_file["deployment"] and config_file["run"]["param_dtype"] == "bfloat16"


def test_the_program_builds_the_configuration(config_file):
    from trlx_tpu.models.registry import get_model_family

    family = get_model_family(config_file["model_type"])
    cfg = family.config_cls.from_dict(harness.arch_of(config_file))
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.intermediate_size) == (64, 8, 1024)
    assert cfg.num_hidden_layers == 12 and cfg.kv_cache_dtype == "bfloat16"


def record_of(config_file, touched=None):
    return {"cell": {"config_file": config_file},
            "gauges": {} if touched is None else {"moe/experts_touched": touched}}


def test_prefill_count_is_the_routed_rows_work(config_file):
    family = harness.load_family(config_file)
    ops = {"ragged-dot bf16[32768,1024]": {"s": 1.0, "count": 2},
           "ragged-dot bf16[32768,2048]": {"s": 1.0, "count": 1}}
    flops, moved = family.gmm_prefill_count(record_of(config_file), ops)
    assert flops == 3 * 2 * 32768 * 2048 * 1024  # rows x 3 d F, 2 a multiply-add
    assert moved == 3 * 2 * 32768 * (2048 + 1024)


def test_the_two_gmm_readers_split_the_forwards_from_the_decode_step():
    """A server's admission runs the grouped multiplication at a chunk
    forward's 8192 rows (8 x 128 columns x 8 experts a token) and at the
    whole group's 32768; a decode step runs it at 256 (32 slots x 8)."""
    import re

    prefill, decode = (re.compile(harness.load_json("layer_metrics", f"moe_gmm_{k}_roofline.json")["reader"]["op"])
                       for k in ("prefill", "decode"))
    names = {rows: [f"ragged-dot-none bf16[{rows},{w}]" for w in (1024, 2048)] for rows in (256, 8192, 32768)}
    for rows in (8192, 32768):
        assert all(prefill.search(n) and not decode.search(n) for n in names[rows])
    assert all(decode.search(n) and not prefill.search(n) for n in names[256])
    assert not prefill.search("fusion bf16[8192,2048]") and not prefill.search("ragged-dot-none f32[8192,1024]")


def test_prefill_count_adds_the_chunk_forwards_to_the_whole_ones(config_file):
    family = harness.load_family(config_file)
    ops = {"ragged-dot-none bf16[8192,1024]": {"s": 1.0, "count": 50},
           "ragged-dot-none bf16[8192,2048]": {"s": 1.0, "count": 25},
           "ragged-dot-none bf16[32768,1024]": {"s": 1.0, "count": 14},
           "ragged-dot-none bf16[32768,2048]": {"s": 1.0, "count": 7}}
    flops, _ = family.gmm_prefill_count(record_of(config_file), ops)
    assert flops == 3 * 2 * 2048 * 1024 * (25 * 8192 + 7 * 32768)


def test_decode_count_reads_the_touched_experts_only(config_file):
    family = harness.load_family(config_file)
    ops = {"ragged-dot bf16[256,1024]": {"s": 1.0, "count": 2},
           "ragged-dot bf16[256,2048]": {"s": 1.0, "count": 1}}
    flops, moved = family.gmm_decode_count(record_of(config_file, 60.5), ops)
    assert moved == 3 * 60.5 * 2048 * 1024 * 2  # 12.58 MB an expert over the three calls
    assert moved < 3 * 64 * 2048 * 1024 * 2 and flops == 3 * 2 * 256 * 2048 * 1024
    # the parent's program has no such gauge: nothing to count, nothing raised
    assert family.gmm_decode_count(record_of(config_file), ops) == (0.0, 0.0)


OWN = {"moe_gmm_decode_roofline": manifest_cells.roofline("expert layer"),
       "moe_gmm_prefill_roofline": manifest_cells.roofline("expert layer")}


def test_manifest_lists_the_cell_and_its_readers(either_tree):
    manifest, root = either_tree
    _, config = manifest_cells.cell_is_listed(manifest, root, CELL, CONFIG, TRAFFIC, chips=1)
    assert config["reduced"] == ["num_hidden_layers"]  # 12 of the published 16 blocks, every width as published
    manifest_cells.own_metrics_list_the_cell(manifest, CELL, OWN)
    names = manifest_cells.metric_names(CELL, root)
    assert set(OWN) | {"decode_serve_roofline", "moe_experts_touched", "moe_max_load", "hbm_peak_gb.serve",
                       "serve_pool_block_bitcast_share"} <= names
    # every serve metric the other serve cells all report is read here too
    manifest_cells.lists_what_every_other_serve_cell_lists(manifest, root, CELL)
    # every expert is held here, and the block keeps neither a state, a tail nor a latent row: those read nothing
    assert not {"moe_rows_here_share", "moe_skip_share", "ssm_state_gb", "cca_tail_gb", "mla_pool_pinned_share"} & names
    readers = {s["name"]: s["reader"] for s in harness.load_layer_metrics(CELL, root=root)}
    assert all(readers[n]["kind"] == "op_roofline" for n in OWN)
    assert {n: readers[n]["count"] for n in OWN} == {
        "moe_gmm_decode_roofline": "gmm_decode_count", "moe_gmm_prefill_roofline": "gmm_prefill_count"}
    family = harness.load_family(manifest_cells.load(root, "configs", CONFIG), root)
    assert all(callable(getattr(family, readers[n]["count"])) for n in OWN)
    # chat.json key for key but for the knee, the sweep's
    traffic, chat = manifest_cells.load(root, "traffic", TRAFFIC), manifest_cells.load(root, "traffic", "chat")
    assert set(chat) == set(traffic) and {k for k in chat if chat[k] != traffic[k]} <= {"name", "arrivals"}
    assert traffic["arrivals"]["load"] == chat["arrivals"]["load"] == 0.8


TINY = {"vocab_size": 96, "max_position_embeddings": 64, "hidden_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4, "intermediate_size": 16,
        "num_experts": 8, "num_experts_per_tok": 2}


def test_cpu_rehearsal_of_the_cell(capsys, monkeypatch):
    monkeypatch.setenv("WANDB_DISABLED", "1")
    monkeypatch.setattr(harness, "place_compile_cache", lambda: "off")
    cell = harness.load_cell(CELL)
    cell["config_file"].update(TINY)
    cell["mesh"] = {"dp": -1, "fsdp": 1, "tp": 1}
    cell["traffic_file"].update(
        seq_length=16, max_new_tokens=8, slots=16, admit_width=8, harvest_width=8,
        prompt_lengths={"dist": "lognormal", "median": 8, "sigma": 0.5, "lo": 2, "hi": 16},
        arrivals={"process": "poisson", "knee_per_s": 25.0, "load": 0.8}, warmup_requests=12,
        drain_limit_s=30, trace_seconds=1)
    out = json.loads(run_cell(cell["name"], 2**31 + 77, 2.0, True, allow_cpu=True, cell=cell))
    checks = [l for l in capsys.readouterr().out.splitlines() if l.startswith("check ")]
    assert out["correct"] is True and out["attempted"] == 40 and out["failed"] == 0
    assert any(c.startswith("check reference.sampled_logprob_rms") for c in checks)
    assert out["device"]["platform"] == "cpu"
    listed = {s["name"] for s in harness.load_layer_metrics(cell["name"])}
    assert set(out["metrics"]) <= listed
    # the program's gauges read on any platform; the trace has no TPU plane here
    assert 2 <= out["metrics"]["moe_experts_touched"]["value"] <= 8
    assert 1 / 8 <= out["metrics"]["moe_max_load"]["value"] <= 1
    assert {"serve_pump_p50_ms", "engine_slot_util"} <= set(out["metrics"])
    assert "moe_gmm_decode_roofline" not in out["metrics"]
