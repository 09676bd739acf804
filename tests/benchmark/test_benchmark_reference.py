"""The plain float32 references against the program's own gpt2 and neox
forward at a tiny size on the CPU, and the FLOP / byte / parameter
arithmetic against the models' own parameter counts (the published sizes'
numbers are pinned in ``test_benchmark_pinned.py``).

Measured at this size over the five seeds below (PR 23, CPU; error of the
program's logits against the reference, as a share of the reference
logits' standard deviation, max over seeds):

    family    program dtype   rms      max
    gpt2      float32         4e-7     2.3e-6
    neox      float32         3e-7     1.5e-6   (reference told "gelu_new")
    neox      float32         2e-4     9e-4     (published erf GELU: the
                                                program's tanh form differs)
    gpt2      bfloat16        0.0091   0.056
    neox      bfloat16        0.0079   0.038

So float32 is held to 1e-5 (4x the largest max), which a bfloat16 or any
lower-precision forward misses by three orders of magnitude; bfloat16 is
held to rms 0.02 / max 0.15 (2x and 2.7x the largest), which float32 beats
by four orders and an 8-bit forward (rms > 0.1 at this depth) would miss.
The published-width tolerances that decide a run's ``correct`` are measured
on the chip and kept, with their distributions, in benchmark/tolerances.json.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import arithmetic, checks, harness

GPT2 = {"model_type": "gpt2", "vocab_size": 96, "n_positions": 64, "n_embd": 32,
        "n_layer": 2, "n_head": 4, "reference": "benchmark/reference/gpt2.py"}
NEOX = {"model_type": "gpt_neox", "vocab_size": 96, "max_position_embeddings": 64,
        "hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 4,
        "intermediate_size": 128, "rotary_pct": 0.25, "rotary_emb_base": 10000.0,
        "use_parallel_residual": True, "reference": "benchmark/reference/neox.py"}
SEEDS = (0, 1, 2, 3, 2**31 + 4)
TOL = {"float32": (1e-5, 1e-5), "bfloat16": (0.02, 0.15)}


def program_and_reference(cfg, dtype, seed, hidden_act=None):
    from trlx_tpu.models.registry import get_model_family

    family = get_model_family(cfg["model_type"])
    arch = family.config_cls.from_dict({**cfg, "dtype": dtype, "param_dtype": "float32"})
    model = family.backbone_cls(arch)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(seed)
    B, T = 4, 24
    ids = rng.integers(0, cfg["vocab_size"], (B, T))
    lens = rng.integers(4, T + 1, B)
    mask = (np.arange(T)[None, :] >= (T - lens)[:, None]).astype(np.int32)  # left-padded
    got = model.apply({"params": params}, jnp.asarray(ids), attention_mask=jnp.asarray(mask))
    ref_cfg = dict(cfg, **({"hidden_act": hidden_act} if hidden_act else {}))
    ref = harness.load_family(cfg).forward(params, ref_cfg, jnp.asarray(ids), jnp.asarray(mask))
    m = mask.astype(bool)
    return np.asarray(got["logits"])[m], np.asarray(ref)[m], params


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("cfg,act", [(GPT2, None), (NEOX, "gelu_new")], ids=["gpt2", "neox"])
def test_program_agrees_with_the_plain_reference_over_five_seeds(cfg, act, dtype):
    rms_tol, max_tol = TOL[dtype]
    for seed in SEEDS:
        got, ref, _ = program_and_reference(cfg, dtype, seed, act)
        rms, mx = checks.error_stats(got, ref, scale=float(ref.std()))
        assert rms <= rms_tol and mx <= max_tol, (seed, rms, mx)


def test_lower_precision_than_stated_fails_the_float32_tolerance():
    got, ref, _ = program_and_reference(GPT2, "bfloat16", 0)
    rms, mx = checks.error_stats(got, ref, scale=float(ref.std()))
    assert rms > 100 * TOL["float32"][0]


def test_published_erf_gelu_differs_from_the_programs_tanh_by_under_1e3():
    got, ref, _ = program_and_reference(NEOX, "float32", 0, "gelu")
    rms, mx = checks.error_stats(got, ref, scale=float(ref.std()))
    assert 1e-5 < mx < 2e-3


OLMOE = {"model_type": "olmoe", "vocab_size": 96, "max_position_embeddings": 64, "hidden_size": 32,
         "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 4,
         "intermediate_size": 16, "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": False,
         "rms_norm_eps": 1e-5, "rope_theta": 10000, "reference": "benchmark/reference/olmoe.py"}


@pytest.mark.parametrize("cfg", [GPT2, NEOX, OLMOE], ids=["gpt2", "neox", "olmoe"])
def test_the_reference_in_blocks_is_the_whole_forward_cut_to_the_response(cfg, capsys):
    """What the checks compute (a row a call, the head on the hidden states
    of positions Q-1 .. T-2 alone) against every logit of ``forward``, on
    seeded weights with left-padded rows."""
    from trlx_tpu.models.registry import get_model_family

    program = get_model_family(cfg["model_type"])
    arch = program.config_cls.from_dict({k: v for k, v in cfg.items() if k != "reference"})
    params = program.backbone_cls(arch).init(jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32))["params"]
    family = harness.load_family(cfg)
    rng = np.random.default_rng(33)
    n, T, Q = 3, 24, 17
    ids = rng.integers(0, cfg["vocab_size"], (n, T))
    mask = (np.arange(T)[None, :] >= np.array([0, 5, 11])[:, None]).astype(np.int32)  # left-padded
    whole = np.asarray(family.forward(params, cfg, jnp.asarray(ids), jnp.asarray(mask)))
    cut = checks.reference_logits(family, cfg, params, jnp.asarray(ids), jnp.asarray(mask), Q)
    assert cut.shape == (n, T - Q, cfg["vocab_size"]) and cut.dtype == np.float32
    assert np.abs(cut - whole[:, Q - 1 : -1]).max() <= 1e-5
    note = [l for l in capsys.readouterr().out.splitlines() if l.startswith("note reference:")]
    assert len(note) == 1 and f"rows={n} T={T} R={T - Q} V={cfg['vocab_size']} seconds=" in note[0]
    assert "peak_bytes_in_use=" in note[0]


def _sizes(jaxpr):
    """The element count of every array any equation of a jaxpr makes,
    through every nested program (jit, scan, map, cond, while)."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield int(np.prod(v.aval.shape)) if hasattr(v.aval, "shape") else 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _sizes(inner)


def test_the_checks_program_forms_no_array_of_every_positions_logits():
    """At the drawn long-context cell's sizes (Q 16384 + 128 new, vocabulary
    73,448; a two-layer trunk of d 64 stands for the model) the program the
    checks run for a row makes its [1, R, V] result and nothing with T x V
    elements: abstract evaluation, nothing that large is run. The whole
    forward does make one, which is what the same walk has to see."""
    Q, R, V, d = 16384, 128, 73448, 64
    T = Q + R
    cfg = {"n_embd": d, "n_layer": 2, "n_head": 2, "vocab_size": V, "n_positions": T}
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    lin = lambda i, o: {"kernel": f32(i, o), "bias": f32(o)}
    norm = {"scale": f32(d), "bias": f32(d)}
    block = {"ln_1": norm, "ln_2": norm, "attn": {"c_attn": lin(d, 3 * d), "c_proj": lin(d, d)},
             "mlp": {"c_fc": lin(d, 4 * d), "c_proj": lin(4 * d, d)}}
    params = {"wte": {"embedding": f32(V, d)}, "wpe": {"embedding": f32(T, d)},
              "h_0": block, "h_1": block, "ln_f": norm}
    row = jax.ShapeDtypeStruct((1, T), jnp.int32)
    family = harness.load_family(GPT2)
    made = jax.make_jaxpr(checks.reference_row(family, cfg, Q))(params, row, row)
    assert [v.aval.shape for v in made.jaxpr.outvars] == [(1, R, V)]
    largest = max(_sizes(made.jaxpr))
    assert R * V <= largest < T * V // 8  # its own result, the token table, a block of scores
    whole = jax.make_jaxpr(lambda p, i, m: family.forward(p, cfg, i, m))(params, row, row)
    assert max(_sizes(whole.jaxpr)) >= T * V


def test_queries_in_blocks_are_the_same_attention():
    """Past ``QUERY_BLOCK`` positions the reference's attention takes its
    queries a block at a time; at a block of 16 under 50 positions (a last
    block of 2) it is the one-piece attention row for row."""
    from benchmark.reference import gpt2

    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 50, 3, 8)), jnp.float32) for _ in range(3))
    mask = jnp.asarray((np.arange(50)[None, :] >= np.array([0, 13])[:, None]).astype(np.int32))
    whole = gpt2.attend(q, k, v, mask)
    assert gpt2.QUERY_BLOCK == 1024 and np.array_equal(np.asarray(gpt2.masked_attention(q, k, v, mask)), np.asarray(whole))
    gpt2.QUERY_BLOCK = 16
    try:
        blocked = gpt2.masked_attention(q, k, v, mask)
    finally:
        gpt2.QUERY_BLOCK = 1024
    assert blocked.shape == whole.shape and np.abs(np.asarray(blocked) - np.asarray(whole)).max() <= 1e-6


def shape_of(cfg):
    return arithmetic.model_shape(harness.load_family(cfg), cfg)


@pytest.mark.parametrize("cfg", [GPT2, NEOX], ids=["gpt2", "neox"])
def test_parameter_count_matches_the_programs_own_tree(cfg):
    _, _, params = program_and_reference(cfg, "float32", 0)
    own = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    assert arithmetic.backbone_params(shape_of(cfg)) == own


@pytest.mark.parametrize("cfg", [GPT2, NEOX], ids=["gpt2", "neox"])
def test_flops_follow_the_matmul_parameters(cfg):
    s = shape_of(cfg)
    # a forward of n tokens with no attention context and the head on each
    # costs 2 FLOPs per matmul parameter and token: per block the four
    # d x d of attention and the two d x 4d of the MLP, and the d x V head
    n, d, V = 10, 32, 96
    assert [l["matmul_params"] for l in s["layers"]] == [4 * d * d + 2 * d * 4 * d] * 2
    matmul = 2 * (4 * d * d + 2 * d * 4 * d) + d * V
    assert arithmetic.forward_flops(s, n, 0, n) == 2 * matmul * n
    # QK^T and AV: 4 x d for each pair of token and context position, a block
    assert arithmetic.forward_flops(s, n, 7, n) - arithmetic.forward_flops(s, n, 0, n) == 2 * 4 * d * 7
    collect, train = arithmetic.ppo_phase_flops(s, Q=8, R=4, rollouts=2, ppo_epochs=3)
    fwd = arithmetic.forward_flops(s, 12, 12 * 13 // 2, 4)
    assert train == 3 * 2 * 3 * fwd  # epochs x rollouts x (forward + 2x backward)
    _, pruned = arithmetic.ppo_phase_flops(s, 8, 4, 2, 3, unfrozen=1)
    assert fwd * 6 < pruned < train  # the frozen trunk's backward is not required
    top = arithmetic.forward_flops(s, 12, 12 * 13 // 2, 4, layers=1)
    assert pruned == 3 * 2 * (fwd + 2 * top) and top < fwd
    assert collect > 2 * arithmetic.forward_flops(s, 12, 0, 0)


@pytest.mark.parametrize("cfg", [GPT2, NEOX], ids=["gpt2", "neox"])
def test_decode_step_bytes_count_weights_once_and_the_cache_by_dtype(cfg):
    s = shape_of(cfg)
    d, V = 32, 96
    # the blocks (what the program's own tree holds in them), the final
    # LayerNorm and the head matrix, tied or not; no embedding table
    weights = sum(l["params"] for l in s["layers"]) + 2 * d + d * V
    assert arithmetic.decode_step_bytes(s, 0, 0) == 2 * weights
    one = arithmetic.decode_step_bytes(s, 1, 9, kv_bytes=2) - 2 * weights
    assert one == 2 * len(s["layers"]) * 10 * d * 2  # keys and values d wide each
    assert arithmetic.decode_step_bytes(s, 1, 9, kv_bytes=1) - 2 * weights == one / 2
    assert arithmetic.decode_step_bytes(s, 0, 0, shards=4) == 2 * weights / 4


def test_a_shape_rule_that_lacks_a_count_is_refused_by_name():
    import types

    broken = types.SimpleNamespace(__name__="broken", shape=lambda cfg: {
        "embed_params": 1, "final": {"params": 1, "matmul_params": 1, "read_params": 1},
        "layers": [{"params": 1, "matmul_params": 1, "read_params": 1, "attn_dim": 1}]})
    with pytest.raises(ValueError, match=r"layers\[0\]\['kv_values'\]"):
        arithmetic.model_shape(broken, {})


def capped_shape(**over):
    """Two blocks with nothing but a cache: 8 values a position each, one
    reading at most 5 cached positions; and one block with a state."""
    layer = {"params": 0, "matmul_params": 0, "read_params": 0, "attn_dim": 4}
    layers = [dict(layer, kv_values=8), dict(layer, kv_values=8, kv_read_cap=5),
              dict(layer, kv_values=0, state_values=100)]
    layers[1].update({k: v for k, v in over.items() if k == "kv_read_cap"})
    layers[2].update({k: v for k, v in over.items() if k == "state_values"})
    return {"embed_params": 0, "layers": layers,
            "final": {"params": 0, "matmul_params": 0, "read_params": 0}}


@pytest.mark.parametrize("context,positions", [(3, 4 + 4), (5, 6 + 6), (6, 7 + 6), (1000, 1001 + 6)],
                         ids=["below", "at", "above", "far-above"])
def test_a_capped_block_reads_its_cap_and_a_state_block_its_state(context, positions):
    import types

    s = arithmetic.model_shape(types.SimpleNamespace(__name__="capped", shape=lambda cfg: capped_shape()), {})
    # 3 sequences, a bf16 cache, a float32 state: the free block reads its context and
    # writes one position, the capped one min(context, 5) and one; the state is read
    # and written once a sequence
    got = arithmetic.decode_step_bytes(s, 3, context, kv_bytes=2, state_bytes=4)
    assert got == 8 * 3 * positions * 2 + 2 * 100 * 3 * 4
    assert arithmetic.decode_step_bytes(s, 3, context, kv_bytes=2, state_bytes=2) == got - 2 * 100 * 3 * 2
    with pytest.raises(ValueError, match="state_dtype"):
        arithmetic.decode_step_bytes(s, 3, context)


@pytest.mark.parametrize("key,value", [("kv_read_cap", -1), ("kv_read_cap", 4.5), ("state_values", -8),
                                       ("state_values", 0.5), ("kv_read_cap", None)])
def test_a_negative_or_fractional_optional_count_is_refused_by_name(key, value):
    import types

    broken = types.SimpleNamespace(__name__="broken", shape=lambda cfg: capped_shape(**{key: value}))
    index = {"kv_read_cap": 1, "state_values": 2}[key]
    with pytest.raises(ValueError, match=rf"layers\[{index}\]\['{key}'\]"):
        arithmetic.model_shape(broken, {})


def test_peaks_table_refuses_an_unknown_device():
    assert arithmetic.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(RuntimeError):
        arithmetic.load_peaks("cpu")


def test_tolerances_carry_their_measured_distribution():
    table = harness.load_json("tolerances.json")
    for key, tol in table["tolerances"].items():
        assert set(tol) >= {"logits_rms_rel", "logits_max_rel", "logprob_rms", "logprob_max"}
        assert key in table["measured"], key
        for cell, dist in table["measured"][key].items():
            for name, d in dist.items():  # every reading passed, with room
                assert d["min"] <= d["median"] <= d["max"] < tol[name], (key, cell, name)


TODAY = {  # the shared table's numbers, which no configuration of the benchmark leaves
    "bfloat16/kv-bfloat16": {"logits_rms_rel": 0.025, "logits_max_rel": 0.16, "logprob_rms": 0.011, "logprob_max": 0.1},
    "bfloat16/kv-int8": {"logits_rms_rel": 0.03, "logits_max_rel": 0.2, "logprob_rms": 0.035, "logprob_max": 0.16},
}


@pytest.mark.parametrize("name", ["gpt2-medium", "pythia-1.4b", "olmoe-1b-7b"])
def test_a_configuration_that_names_no_table_resolves_to_todays_numbers(name):
    cf = harness.load_json("configs", f"{name}.json")
    assert "tolerances" not in cf and checks.tolerance_table(cf)[0] == "benchmark/tolerances.json"
    for kv in ("bfloat16", "int8"):
        assert checks.tolerances_of(cf, kv) == TODAY[f"bfloat16/kv-{kv}"] == checks.tolerance_for("bfloat16", kv)
    with pytest.raises(KeyError, match="kv-fp8"):
        checks.tolerances_of(cf, "fp8")


def own_table():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "files_only", "benchmark",
                        "tolerances", "toymoe-tiny.json")
    with open(path) as f:
        return json.load(f)


KEY, CELL = "bfloat16/kv-bfloat16", "ppo-toymoe-tldr"


def _set(path, value):
    def change(table):
        at = table
        for step in path[:-1]:
            at = at[step]
        if value is None:
            del at[path[-1]]
        else:
            at[path[-1]] = value
    return change


BREACHES = {
    "too-few-runs": (_set(("measured", KEY, CELL, "logprob_rms", "runs"), 7), "7 runs over 4 seeds"),
    "too-few-seeds": (_set(("measured", KEY, CELL, "logprob_max", "seeds"), 3), "8 runs over 3 seeds"),
    "a-reading-over-its-tolerance": (_set(("measured", KEY, CELL, "logits_rms_rel", "max"), 0.021),
                                     r"\['logits_rms_rel'\]: min <= median <= max < 0.02"),
    "over-3-times-the-largest-reading": (_set(("tolerances", KEY, "logprob_max"), 0.13),
                                         r"\['logprob_max'\] = 0.13 is over 3 times the largest reading"),
    "not-under-a-cheaper-reading": (_set(("tolerances", KEY, "logprob_rms"), 0.014),
                                    r"\['logprob_rms'\] = 0.014 is not under the smallest reading of cheaper"),
    "too-few-cheaper-runs": (_set(("cheaper", KEY, CELL, "logprob_rms", "runs"), 3), "has 3 runs; at least 4"),
    "a-missing-tolerance": (_set(("tolerances", KEY, "logprob_max"), None), "lacks 'logprob_max'"),
    "a-missing-reading": (_set(("measured", KEY, CELL, "logits_max_rel"), None),
                          r"\['logits_max_rel'\] has no reading beside it"),
    "a-missing-statistic": (_set(("measured", KEY, CELL, "logprob_rms", "median"), None), r"lacks \['median'\]"),
    "no-cheaper-group": (_set(("cheaper",), None), "no 'cheaper' group"),
    "no-cheaper-readings-under-the-key": (_set(("cheaper", KEY), None), "cheaper lacks"),
}


def test_a_configurations_own_tolerance_file_keeps_the_rule():
    checks.check_tolerance_file(own_table(), "the fixture")  # raises nothing


@pytest.mark.parametrize("breach", sorted(BREACHES))
def test_a_tolerance_file_that_breaks_a_rule_is_refused_by_name(breach):
    change, message = BREACHES[breach]
    table = own_table()
    change(table)
    with pytest.raises(ValueError, match="tolerance file configs/x.json: .*" + message):
        checks.check_tolerance_file(table, "configs/x.json")


def test_a_lower_precision_cache_than_stated_fails_the_bf16_tolerance():
    """The int8 cache's measured log-probability error, every reading of
    it, is over the tolerance a configuration stating a bf16 cache is held
    to: the program cannot serve from a cheaper cache than it states and
    still report ``correct``."""
    table = harness.load_json("tolerances.json")
    stated = table["tolerances"]["bfloat16/kv-bfloat16"]["logprob_rms"]
    cheaper = [d["logprob_rms"]["min"] for d in table["measured"]["bfloat16/kv-int8"].values()]
    assert cheaper and min(cheaper) > stated
