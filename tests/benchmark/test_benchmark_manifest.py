"""``BENCHMARK.json`` against the contract's format rules, and against the
data files the harness finds by name; and that a model family, a
configuration, a cell and a per-layer metric can each be added as new
files and manifest entries only (``files_only/`` holds the files: the family with its two halves, a window
block and a state block in its shape rule, and a tolerance file of the
configuration's own; ``conftest.py`` lays them over a copy, and every family's
manifest test runs on that tree too: the last test here holds each to it)."""

import ast
import json
import os
import re
import shutil

import numpy as np
import pytest

from benchmark import arithmetic, checks, harness, readers

import manifest_cells
from manifest_cells import one_line

REPO = harness.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# a key of `reduced` may never name a width; a count of layers (the depth cut) is none,
# though `num_hidden_layers` holds the word
WIDTHS = re.compile(r"(hidden(?!_layers$)|intermediate|latent|state|proj|window|_dim$|_rank$|head_dim|n_embd|n_inner|expand)")


@pytest.fixture(scope="module")
def manifest():
    path = os.path.join(REPO, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def test_top_level_keys_and_command(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51


def test_names_units_and_lines(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in manifest[group]]
        assert len(seen) == len(set(seen)), group
        names += seen
    metric_names = [e["name"] for e in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert one_line(m["layer"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and one_line(w["why"])
        assert w["chips"] in (1, 4)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"]) and len(c["reduced"]) <= 16


def test_cells_configs_and_traffic_resolve_to_files(manifest):
    config_names = {c["name"] for c in manifest["configs"]}
    pairs = set()
    for w in manifest["workloads"]:
        assert w["config"] in config_names
        cell = harness.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) == (
            w["config"], w["traffic"], w["chips"], w["why"])
        assert cell["traffic_file"]["driver"] in ("ppo", "serve")
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert {w["config"] for w in manifest["workloads"]} == config_names  # each used by a cell
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        with open(os.path.join(REPO, c["file"])) as f:
            cf = json.load(f)
        assert (cf["name"], cf["source"], cf["reduced"]) == (c["name"], c["source"], c["reduced"])
        assert "assumed" in cf and not any(WIDTHS.search(k) for k in cf["reduced"])
        assert os.path.exists(os.path.join(REPO, cf["reference"]))


@pytest.mark.parametrize("key,is_width", [
    ("num_hidden_layers", False), ("num_layers", False), ("n_layer", False),
    ("hidden_size", True), ("intermediate_size", True), ("moe_intermediate_size", True),
    ("head_dim", True), ("mamba_d_state", True), ("lightning_head_dim", True),
    ("kv_lora_rank", True), ("sliding_window", True),
])
def test_a_depth_cut_is_no_width_and_a_width_is(key, is_width):
    assert bool(WIDTHS.search(key)) == is_width


def test_at_most_one_cell_asks_for_four_chips(manifest):
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def cells_of(metric, manifest):
    return metric.get("workloads") or [w["name"] for w in manifest["workloads"]]


def test_every_metric_resolves_and_every_cell_reports_enough(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"] and e2e["setup_s"]["bound"] <= 0.1
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(cells_of(m, manifest)) <= cells, m["name"]
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        # the metric it moves is reported in every cell this one is read in
        assert set(cells_of(m, manifest)) <= set(cells_of(e2e[m["moves"]], manifest)), m["name"]
    for cell in cells:
        others = [n for n, m in e2e.items() if n != "setup_s" and cell in cells_of(m, manifest)]
        layers = [m for m in manifest["per_layer"] if cell in cells_of(m, manifest)]
        assert others and layers, cell


def test_every_listed_metric_has_a_reader_file_and_no_file_is_an_orphan(manifest):
    """Which cell reads which metric, its unit, layer and what it moves
    stand in the manifest alone; a file under ``layer_metrics/`` holds the
    reader and nothing the manifest says."""
    listed = {m["name"]: m for m in manifest["per_layer"]}
    on_disk = {f[:-len(".json")] for f in os.listdir(os.path.join(harness.HERE, "layer_metrics"))}
    assert on_disk == set(listed)
    for name in listed:
        assert set(harness.load_json("layer_metrics", f"{name}.json")) == {"reader"}, name
    found = {}
    for w in manifest["workloads"]:
        for spec in harness.load_layer_metrics(w["name"]):
            assert w["name"] in listed[spec["name"]]["workloads"], (spec["name"], w["name"])
            assert spec["reader"]["kind"] in readers.READERS
            assert {k: v for k, v in spec.items() if k != "reader"} == listed[spec["name"]]
            found.setdefault(spec["name"], []).append(w["name"])
    assert {n: sorted(c) for n, c in found.items()} == {
        n: sorted(m["workloads"]) for n, m in listed.items()}
    layers = {}
    for m in manifest["per_layer"]:  # one spelling per layer
        layers.setdefault(m["layer"].lower().strip(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_a_metric_that_lists_no_cells_is_refused_by_name(tmp_path, manifest):
    root = tmp_path / "benchmark"
    shutil.copytree(os.path.join(harness.HERE, "layer_metrics"), root / "layer_metrics")
    entries = [dict(m) for m in manifest["per_layer"]]
    bare = next(m for m in entries if m["name"] == "hbm_peak_gb.serve")  # any one: none of the asked cell's
    del bare["workloads"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(dict(manifest, per_layer=entries)))
    with pytest.raises(KeyError, match=r"hbm_peak_gb\.serve"):
        harness.load_layer_metrics("ppo-gpt2m-tldr", root=str(root))


def test_a_family_a_config_a_cell_and_a_metric_are_added_as_files_only(a_later_prs_tree):
    root, before = a_later_prs_tree
    assert not any("toymoe" in p.name for p in before)
    cell = harness.load_cell("ppo-toymoe-tldr", root=str(root))
    cf = cell["config_file"]
    assert cell["family"].__file__ == str(root / "reference" / "toymoe.py")
    assert cell["traffic_file"]["new_tokens"] == 48  # a traffic mix that was there
    assert harness.arch_of(cf)["num_experts_per_tok"] == 2

    # the sizes by hand: d 32, 4 heads of 8, 2 KV heads, vocab 96, no biases.
    # attention q 32x32 + k 32x16 + v 32x16 + o 32x32 = 3072; two RMSNorms 64
    # dense block: gated MLP 3 x 32 x 64 = 6144            -> holds 9280
    # routed block: router 32 x 8 = 256, 8 experts of 3 x 32 x 16 = 1536
    #               -> holds 3072 + 256 + 12288 + 64 = 15680
    # token table 3072, final norm 32, untied head 3072
    s = arithmetic.model_shape(cell["family"], cf)
    assert [l["params"] for l in s["layers"]] == [9280, 15680, 15680]
    assert arithmetic.backbone_params(s) == 3072 + 9280 + 2 * 15680 + 32 + 3072 == cf["parameters"]
    # a token is multiplied with 2 experts, not 8: 3072 + 256 + 2 x 1536 = 6400 a routed block
    per_token = arithmetic.forward_flops(s, 1, 0, 1)
    assert per_token == 2 * ((3072 + 6144) + 2 * 6400 + 3072) == 50176
    assert per_token < 2 * ((3072 + 6144) + 2 * (3072 + 256 + 8 * 1536) + 3072) == 87040
    # the cache is 2 KV heads x 8 x 2 = 32 values a position and block (2d would be 64);
    # the linear block keeps none, and a state of 2 KV heads x 8 x 8 = 128 values a sequence;
    # the window block (4 wide) reads 3 cached positions at most beside the one it writes
    assert [l["kv_values"] for l in s["layers"]] == [32, 32, 0]
    assert [(l.get("kv_read_cap"), l.get("state_values")) for l in s["layers"]] == [
        (None, None), (3, None), (None, 128)]
    # a decode step of 4 sequences at 9 cached positions, bf16 weights and cache, float32
    # state: it reads the dense block whole, of a routed block all but the experts (3392)
    # and 2 of them, the final norm and the head; the full block's 32 values x 10
    # positions a row and the window block's 32 x (3 + 1); and reads and writes 128
    # values of state a row
    weights = 9280 + 2 * (3392 + 2 * 1536) + (32 + 3072)
    cache = 2 * 4 * (32 * 10 + 32 * 4)
    state = 2 * 4 * 128 * 4
    assert (2 * weights, cache, state) == (50624, 3584, 4096)
    assert arithmetic.decode_step_bytes(s, 4, 9, state_bytes=4) == 50624 + 3584 + 4096 == 58304
    assert arithmetic.decode_step_bytes(s, 4, 9, shards=4, state_bytes=4) == 50624 / 4 + 7680
    # below, at and above the window's cap of 3 cached positions: the window block follows
    # the context up to it and no further, the full block all the way
    assert [arithmetic.decode_step_bytes(s, 1, c, state_bytes=0) - 2 * weights
            for c in (2, 3, 4, 100)] == [2 * 32 * (3 + 3), 2 * 32 * (4 + 4), 2 * 32 * (5 + 4),
                                         2 * 32 * (101 + 4)]
    # the weights read do not grow with the batch: 2 experts a routed block is the floor
    assert arithmetic.decode_step_bytes(s, 64, 0, kv_bytes=0, state_bytes=0) == 2 * weights
    # a state needs its byte width: the configuration's run group states it
    assert cf["run"]["state_dtype"] == "float32" and arithmetic.DTYPE_BYTES["float32"] == 4
    with pytest.raises(ValueError, match="state_dtype"):
        arithmetic.decode_step_bytes(s, 4, 9)
    # and the reader passes it on as it passes the cache's
    record = {"xplane": "recorded", "trace": {"devices": [0], "modules": {"jit_sampler": {"s": 1.0, "count": 1}}},
              "shape": s, "chips": 1, "kv_cache_dtype": "bfloat16", "state_dtype": cf["run"]["state_dtype"],
              "decode": {"batch": 4, "mean_context": 9}, "device": {"peaks": {"hbm_bytes_per_s": 58304.0}}}
    assert readers.decode_hbm_share(record, {"module": "jit_sampler"}) == 100.0

    # the configuration names a tolerance file of its own, and is held to that
    assert checks.tolerances_of(cf, "bfloat16", root=str(root)) == {
        "logits_rms_rel": 0.02, "logits_max_rel": 0.12, "logprob_rms": 0.012, "logprob_max": 0.09}
    assert checks.tolerances_of(cf, "bfloat16", root=str(root)) != checks.tolerance_for("bfloat16", "bfloat16")

    # the cell reads a metric that was there and a new one of the new kind
    specs = harness.load_layer_metrics("ppo-toymoe-tldr", root=str(root))
    assert [m["name"] for m in specs] == ["collect_ms", "moe_tokens_routed"]
    spans = harness.Spans()
    spans.records += [("collect", 0.0, 0.25), ("collect", 1.0, 1.35), ("collect", 2.0, 2.25)]
    record = {"spans": spans, "phases": 3, "counters": {"moe/tokens_routed": 1200.0}}
    assert readers.read_all(record, specs) == {
        "collect_ms": {"value": 250.0, "unit": "ms"},
        "moe_tokens_routed": {"value": 400.0, "unit": "tokens"}}
    # a reader that finds nothing to read returns nothing
    assert readers.read_all({"spans": harness.Spans(), "phases": 3, "counters": {}}, specs) == {}
    # the cells that were there read what they read
    assert [m["name"] for m in harness.load_layer_metrics("ppo-gpt2m-tldr", root=str(root))] == [
        m["name"] for m in harness.load_layer_metrics("ppo-gpt2m-tldr")]
    assert all(p.read_bytes() == b for p, b in before.items())  # nothing edited


def test_a_later_prs_serve_cell_comes_after_every_cell_and_a_metric_lists_some_serve_cells_only(a_later_prs_tree):
    """What every family's manifest test meets on its second tree."""
    root = str(a_later_prs_tree[0])
    later, stands = manifest_cells.read_manifest(root), manifest_cells.read_manifest()
    for group in ("configs", "workloads", "per_layer"):  # appended: everything that was there stands first, as it was
        assert [e["name"] for e in later[group]][:len(stands[group])] == [e["name"] for e in stands[group]]
    added = [w for w in later["workloads"] if w not in stands["workloads"]]
    assert [(w["name"], w["chips"]) for w in added] == [("ppo-toymoe-tldr", 1), ("serve-toymoe-chat", 4)]
    serve = manifest_cells.cells_by_driver(later, root)["serve"]
    assert serve == manifest_cells.SERVE_CELLS + ["serve-toymoe-chat"]
    assert harness.load_cell("serve-toymoe-chat", root=root)["traffic_file"]["driver"] == "serve"
    # it joins whatever every serve cell listed, the end-to-end metrics with them, and nothing of the PPO cells'
    everywhere = {m["name"] for m in stands["end_to_end"] + stands["per_layer"]
                  if manifest_cells.every_serve_cell_and_no_ppo_cell(m.get("workloads", ()))}
    assert {"serve_itl_p95_ms", "serve_tokens_per_s", "decode_serve_roofline"} <= everywhere
    reads = {m["name"] for m in later["end_to_end"] + later["per_layer"]
             if "serve-toymoe-chat" in m.get("workloads", ())}
    assert reads == everywhere | {"moe_rows_served"}
    manifest_cells.lists_what_every_other_serve_cell_lists(later, root, "serve-toymoe-chat")
    # the metric over a strict subset of the serve cells: two of them, one that was there
    some = next(m for m in later["per_layer"] if m["name"] == "moe_rows_served")["workloads"]
    assert len(some) == 2 and set(some) < set(serve) and set(some) & set(manifest_cells.SERVE_CELLS)
    for cell in some:
        assert "moe_rows_served" in manifest_cells.metric_names(cell, root)
    # and the repo's own tree has one such since PR 64: no rule asks it of the cells it leaves out
    witness = next(m for m in stands["per_layer"] if m["name"] == "serve_pool_block_bitcast_share")["workloads"]
    left_out = set(manifest_cells.SERVE_CELLS) - set(witness)
    assert len(left_out) >= 2  # each has another that lacks it among its others (one alone would be asked for it)
    for cell in left_out:
        manifest_cells.lists_what_every_other_serve_cell_lists(stands, harness.HERE, cell)


def test_the_added_familys_reference_runs_through_the_checks(a_later_prs_tree):
    import jax

    root, _ = a_later_prs_tree
    cell = harness.load_cell("ppo-toymoe-tldr", root=str(root))
    cf = cell["config_file"]
    rng = np.random.default_rng(26)
    d, V, kv, E = 32, 96, 16, 8
    mat = lambda *shape: rng.normal(0, shape[-2] ** -0.5, shape).astype(np.float32)
    attn = lambda: {"q": mat(d, d), "k": mat(d, kv), "v": mat(d, kv), "o": mat(d, d)}
    ones = np.ones(d, np.float32)
    params = {"wte": mat(V, d), "ln_f": ones, "lm_head": mat(d, V),
              "h_0": {"ln_1": ones, "ln_2": ones, "attn": attn(),
                      "mlp": {"gate": mat(d, 64), "up": mat(d, 64), "down": mat(64, d)}}}
    for i in (1, 2):
        params[f"h_{i}"] = {"ln_1": ones, "ln_2": ones, "attn": attn(), "moe": {
            "router": mat(d, E), "gate": mat(E, d, 16), "up": mat(E, d, 16), "down": mat(E, 16, d)}}
    held = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    assert held == cf["parameters"]  # the shape rule counts the tree the reference reads
    ids = rng.integers(0, V, (2, 12))
    mask = np.ones((2, 12), np.int32)
    mask[1, :5] = 0  # left-padded
    # the checks take the two halves a row at a time, at the response-predicting
    # positions Q-1 .. T-2 alone; every logit is the composition's, for the tests
    Q = 8
    cut = checks.reference_logits(cell["family"], cf, params, ids, mask, Q)
    assert cut.shape == (2, 12 - Q, V) and np.isfinite(cut).all()
    logits = np.asarray(cell["family"].forward(params, cf, ids, mask))
    assert logits.shape == (2, 12, V) and np.abs(cut - logits[:, Q - 1 : -1]).max() <= 1e-5
    # causal: a later token changes no earlier real position's logits
    ids2 = ids.copy()
    ids2[:, -1] = (ids2[:, -1] + 1) % V
    again = np.asarray(cell["family"].forward(params, cf, ids2, mask))
    real = mask[:, :-1].astype(bool)
    assert np.allclose(logits[:, :-1][real], again[:, :-1][real], atol=1e-5)
    assert np.abs(logits[:, -1] - again[:, -1]).max() > 0.1
    # the window block forgets: with every block a window of 4, three of them see 9 places
    # back and the first token, 11 back, moves nothing; the full and the linear block remember it
    ids3 = ids.copy()
    ids3[:, 0] = (ids3[:, 0] + 1) % V
    far = lambda c: np.abs(np.asarray(cell["family"].forward(params, c, ids3, mask))
                           - np.asarray(cell["family"].forward(params, c, ids, mask)))[:, -1].max()
    assert far(dict(cf, layer_types=["window"] * 3)) < 1e-5 < far(cf)


def test_the_ppo_driver_takes_the_rollout_engine_from_the_traffic_file():
    from benchmark import ppo_driver

    cell = harness.load_cell("ppo-gpt2m-longgen")
    assert "engine" not in cell["traffic_file"]
    assert ppo_driver.build_config(cell, 1).train.rollout["engine"] == "fixed"
    cell["traffic_file"]["engine"] = "continuous"
    assert ppo_driver.build_config(cell, 1).train.rollout["engine"] == "continuous"


def test_every_familys_manifest_test_takes_both_trees():
    """A test file of a family (it names its ``CELL``) has a manifest test,
    and that test takes ``conftest.py``'s ``either_tree``: so it runs on a
    tree with a later PR's cell, configuration and metrics after its own,
    and one that pins a position, counts the cells or lists the other cells
    by hand fails in the PR that writes it. Read off the source: no family
    file is imported."""
    here = os.path.dirname(os.path.abspath(__file__))
    families = {}
    for name in sorted(os.listdir(here)):
        if not re.fullmatch(r"test_benchmark_\w+\.py", name):
            continue
        with open(os.path.join(here, name)) as f:
            body = ast.parse(f.read()).body
        assigned = {t.id for node in body if isinstance(node, ast.Assign)
                    for t in node.targets if isinstance(t, ast.Name)}
        if "CELL" in assigned:
            families[name] = [[a.arg for a in node.args.args] for node in body
                              if isinstance(node, ast.FunctionDef) and node.name.startswith("test_manifest_")]
    assert "test_benchmark_olmoe.py" in families  # the walk finds them
    for name, signatures in families.items():
        assert any("either_tree" in args for args in signatures), f"{name}: no manifest test takes `either_tree`"
