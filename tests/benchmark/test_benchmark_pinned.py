"""The yardstick's numbers for the two published configurations and the
three cells, pinned to the last digit. They were read from the arithmetic
as PR 23 wrote it (closed formulas in ``d`` and ``ff``) and held while
ISSUE 26 moved the shape rules into the families' files and the formulas
onto per-layer quantities: a change to ``benchmark/arithmetic.py`` or to a
family's ``shape`` that moves one of them moves every roofline share and
``phase_mfu`` of the ledger with it, and says so here first.

Decode bytes are taken at the cell's chunk (or slot count) and at
``seq_length`` plus half the new tokens, as the drivers reckon them; a
sharded reading divides the batch and the weights over the chips."""

import pytest

from benchmark import arithmetic, harness

PARAMETERS = {"gpt2-medium": 354823168, "pythia-1.4b": 1414647808}
PHASE_FLOPS = {  # (collect, train) of one PPO phase
    "ppo-gpt2m-tldr": (45908760854528, 275413041414144),
    "ppo-gpt2m-longgen": (47143702364160, 131628030492672),
}
DECODE_BYTES = {  # (cache bytes a value, shards): bytes of one decode step on one chip
    "ppo-gpt2m-tldr": {(2, 1): 4086061056.0, (2, 4): 1021515264.0,
                       (1, 1): 2396805120.0, (1, 4): 599201280.0},
    "ppo-gpt2m-longgen": {(2, 1): 2525779968.0, (2, 4): 631444992.0,
                          (1, 1): 1616664576.0, (1, 4): 404166144.0},
    "serve-pythia1b4-chat": {(2, 1): 6253420544.0, (2, 4): 1563355136.0,
                             (1, 1): 4438335488.0, (1, 4): 1109583872.0},
}
# a batch and a context that no power of two divides (a serving slice's means)
FRACTIONAL = {"gpt2-medium": 1214137221.1200001, "pythia-1.4b": 3636426506.2400002}


def shape_of(config_file):
    return arithmetic.model_shape(harness.load_family(config_file), config_file)


@pytest.mark.parametrize("name", sorted(PARAMETERS))
def test_published_parameter_counts(name):
    cf = harness.load_json("configs", f"{name}.json")
    assert arithmetic.backbone_params(shape_of(cf)) == PARAMETERS[name] == cf["parameters"]
    assert arithmetic.decode_step_bytes(shape_of(cf), 25.6, 200.3) == FRACTIONAL[name]


@pytest.mark.parametrize("name", sorted(PHASE_FLOPS))
def test_ppo_phase_flops(name):
    cell = harness.load_cell(name)
    t = cell["traffic_file"]
    got = arithmetic.ppo_phase_flops(
        shape_of(cell["config_file"]), t["seq_length"], t["new_tokens"], t["num_rollouts"],
        t["ppo_epochs"], t["num_layers_unfrozen"] or 0)
    assert got == PHASE_FLOPS[name] and all(isinstance(x, int) for x in got)


@pytest.mark.parametrize("name,kv_bytes,shards", [
    (name, kv, shards) for name in sorted(DECODE_BYTES) for kv, shards in sorted(DECODE_BYTES[name])
])
def test_decode_step_bytes(name, kv_bytes, shards):
    cell = harness.load_cell(name)
    t = cell["traffic_file"]
    batch = t["chunk_size"] if t["driver"] == "ppo" else t["slots"]
    context = t["seq_length"] + (t.get("new_tokens") or t["max_new_tokens"]) / 2.0
    got = arithmetic.decode_step_bytes(
        shape_of(cell["config_file"]), batch / shards, context,
        weight_bytes=2, kv_bytes=kv_bytes, shards=shards)
    assert got == DECODE_BYTES[name][(kv_bytes, shards)]
