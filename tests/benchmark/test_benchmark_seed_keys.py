"""The optional keys of a ``serve`` traffic file that keep a seed from
changing the work (ISSUE 55; ``benchmark/loadgen.py``'s docstring):
``min_new_tokens`` (a mix that sets it to its ``max_new_tokens`` gets
requests that run to their budget whatever the seeded head draws, and the
accounting then refuses any shorter result), ``weights_seed`` (one model
for every ``--seed``) and ``order_seed`` (one order of the prompt lengths,
so the same prompts are admitted together in every run). ``--seed`` keeps
deciding every token id and the server's own seed, and whatever a mix
names no key for. A traffic file without them builds the configuration it
built before, serves a model a seed and orders the lengths by the seed.
CPU rehearsals at a toy size: asserted for their form, never for a time."""

import time

import numpy as np
import pytest

from benchmark import harness, loadgen, serve_driver
from test_benchmark_deepseek_v3 import CELL, shrunk as shrunk_reason1k
from test_benchmark_rehearsal import quiet_program, shrunk  # noqa: F401  (autouse fixture)

PYTHIA = "serve-pythia1b4-chat"
# what `build_config` hands the program for chat.json at pythia-1.4b's vocabulary, as it did before the keys
PINNED_GEN_KWARGS = {"max_new_tokens": 128, "min_new_tokens": 1, "top_k": 0, "do_sample": True,
                     "eos_token_id": 50303, "pad_token_id": 50303}


def run(cell, seed, seconds=2.0):
    device = harness.require_chips(int(cell["chips"]), allow_cpu=True)
    return serve_driver.run(cell, seed, seconds, False, time.time(), device)


def test_a_mix_without_the_keys_builds_the_configuration_it_built_before():
    cell = harness.load_cell(PYTHIA)
    assert not {"min_new_tokens", "weights_seed", "order_seed"} & set(cell["traffic_file"])
    built = serve_driver.build_config(cell).to_dict()
    assert built["method"]["gen_kwargs"] == PINNED_GEN_KWARGS
    assert built["train"]["rollout"]["slots"] == 32 and built["train"]["seq_length"] == 512
    # the default said aloud is the same configuration, key for key; the key set is the program's own
    cell["traffic_file"]["min_new_tokens"] = 1
    assert serve_driver.build_config(cell).to_dict() == built
    cell["traffic_file"]["min_new_tokens"] = 128
    kwargs = serve_driver.build_config(cell).to_dict()["method"]["gen_kwargs"]
    assert kwargs == dict(PINNED_GEN_KWARGS, min_new_tokens=128)
    # and no accepted mix but the replaced cell's sets either key: their programs are the parent's
    for name in ("chat", "chat-olmoe", "chat-granite4hs", "reason-zaya1-8b"):
        assert not {"min_new_tokens", "weights_seed", "order_seed"} & set(harness.load_json("traffic", f"{name}.json"))
    reason1k = harness.load_json("traffic", "reason1k-deepseekv3.json")
    assert (reason1k["min_new_tokens"], reason1k["weights_seed"], reason1k["order_seed"]) == (
        reason1k["max_new_tokens"], reason1k["traffic_seed"], reason1k["traffic_seed"])


def test_cpu_rehearsal_of_reason1k_every_request_runs_to_its_budget(monkeypatch):
    """At a toy vocabulary of 96 and 8 tokens a request, one request in
    twelve would draw EOS (id 95) and stop early; under the key none does."""
    cell = shrunk_reason1k()
    assert cell["name"] == CELL and cell["traffic_file"]["min_new_tokens"] == cell["traffic_file"]["max_new_tokens"] == 8
    seen = {}
    drive = serve_driver.drive

    def watched(server, prompts, due, seconds, *rest, **kw):
        out = drive(server, prompts, due, seconds, *rest, **kw)
        if seconds:  # the window, not the warm-up
            seen.update(out)
        return out

    monkeypatch.setattr(serve_driver, "drive", watched)
    out = run(cell, 2**31 + 551)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] == 40
    assert len(seen["done"]) == 40
    assert all(seen["results"][c.rid]["length"] == 8 == len(c.token_times) for c in seen["done"])
    assert all(95 not in seen["results"][c.rid]["tokens"] for c in seen["done"])
    line = out["checks"].entries["accounting.requests_off_budget"]
    assert line["value"] == 0 and line["ok"] and "after 8 or more" in line["limit"]


@pytest.mark.parametrize("least,refused", [(None, 0), (8, 1)], ids=["a-stop-on-eos-passes", "under-the-key-it-is-refused"])
def test_the_accounting_refuses_a_planted_short_answer_where_the_mix_sets_the_budget_as_the_least(least, refused, monkeypatch):
    """One finished request's answer cut to three tokens that end in EOS,
    streamed as many as returned: a mix with no ``min_new_tokens`` lets a
    stop on EOS pass, one whose least is the budget counts it off budget
    and the run is not correct."""
    cell = shrunk(PYTHIA)
    eos = cell["config_file"]["vocab_size"] - 1
    if least is not None:
        cell["traffic_file"]["min_new_tokens"] = least
    drive = serve_driver.drive

    def planted(server, prompts, due, seconds, *rest, **kw):
        out = drive(server, prompts, due, seconds, *rest, **kw)
        if seconds:
            # (a request that stopped early of itself would hide the planted one's count)
            c = next(c for c in out["done"] if out["results"][c.rid]["length"] == 8)
            res = out["results"][c.rid]
            res["tokens"] = [int(x) for x in res["tokens"][:2]] + [eos]
            res["length"] = 3
            del c.token_times[3:]
        return out

    monkeypatch.setattr(serve_driver, "drive", planted)
    out = run(cell, 2**31 + 552)
    line = out["checks"].entries["accounting.requests_off_budget"]
    assert line["value"] == refused and line["ok"] is (refused == 0)
    assert out["correct"] is (refused == 0)


class Reached(Exception):
    """The window's first call: everything a seed decides has been made."""


def made_from(cell, seed, monkeypatch):
    """(served parameters, the server's own seed, the window's prompts) of
    ``serve_driver.run`` for ``--seed``, stopped where the warm-up would
    begin."""
    from trlx_tpu.inference.server import InferenceServer

    got = {}

    def recording(self, config, params=None, seed=0, **kw):  # (no server is built: nothing of it is read before the warm-up)
        got["params"], got["server_seed"] = params, seed

    def stop(*args, **kw):
        raise Reached

    def drawn(*args, **kw):
        got["prompts"] = draw(*args, **kw)
        return got["prompts"]

    draw = loadgen.draw_prompts
    with monkeypatch.context() as m:
        m.setattr(InferenceServer, "__init__", recording)
        m.setattr(serve_driver, "drive", stop)
        m.setattr(loadgen, "draw_prompts", drawn)
        with pytest.raises(Reached):
            run(cell, seed)
    import jax

    return ([np.asarray(x) for x in jax.tree_util.tree_leaves(got["params"])], got["server_seed"], got["prompts"])


def test_one_model_under_a_weights_seed_and_a_model_a_seed_without(monkeypatch):
    a, b = 2**31 + 553, 2**31 + 554
    fixed = shrunk(PYTHIA)
    fixed["traffic_file"]["weights_seed"] = 20261002
    pa, sa, qa = made_from(fixed, a, monkeypatch)
    pb, sb, qb = made_from(fixed, b, monkeypatch)
    assert len(pa) == len(pb) > 4 and all(np.array_equal(x, y) for x, y in zip(pa, pb))  # bit-equal
    # `--seed` keeps what it decided before: the server's seed, the order of the lengths, every token id
    assert sa == loadgen.program_seed(a) != sb == loadgen.program_seed(b)
    assert qa != qb and sorted(map(len, qa)) == sorted(map(len, qb)) and list(map(len, qa)) != list(map(len, qb))
    # the model is the weights' seed's, not either run's
    free = shrunk(PYTHIA)
    fa, sfa, qfa = made_from(free, a, monkeypatch)
    fb, _, _ = made_from(free, b, monkeypatch)
    drawn = [(x, y) for x, y in zip(fa, fb) if x.ndim >= 2]  # (the vectors start as zeros and ones on any seed)
    assert len(drawn) >= 8 and all(not np.array_equal(x, y) for x, y in drawn)  # a model a seed
    assert any(not np.array_equal(x, y) for x, y in zip(fa, pa))
    assert (sfa, qfa) == (sa, qa)  # the key moves nothing else
    again, _, _ = made_from(dict(free, traffic_file=dict(free["traffic_file"], weights_seed=a)), b, monkeypatch)
    assert all(np.array_equal(x, y) for x, y in zip(again, fa))  # seeded_params(config, weights_seed), as `--seed` a made it


def test_one_order_of_the_lengths_under_an_order_seed_and_the_seeds_own_without(monkeypatch):
    a, b = 2**31 + 555, 2**31 + 556
    fixed = shrunk(PYTHIA)
    fixed["traffic_file"]["order_seed"] = 20261002
    pa, sa, qa = made_from(fixed, a, monkeypatch)
    pb, sb, qb = made_from(fixed, b, monkeypatch)
    # the same lengths in the same places, warm-up and window alike; every token id is still the seed's
    assert list(map(len, qa)) == list(map(len, qb)) and len(set(map(len, qa))) > 4
    assert all(x != y for x, y in zip(qa, qb) if len(x) >= 4)
    # and the weights and the server's seed are still the seed's
    assert sa != sb and any(not np.array_equal(x, y) for x, y in zip(pa, pb) if x.ndim >= 2)
    free = shrunk(PYTHIA)
    _, _, fa = made_from(free, a, monkeypatch)
    _, _, fb = made_from(free, b, monkeypatch)
    assert sorted(map(len, fa)) == sorted(map(len, qa)) and list(map(len, fa)) != list(map(len, fb)) != list(map(len, qa))
    # the key moves nothing else: the order is `order_seed`'s as `--seed` of that value would have made it
    lengths = loadgen.draw_lengths(free["traffic_file"]["prompt_lengths"], len(qa), free["traffic_file"]["traffic_seed"], 20261002)
    assert list(map(len, qa)) == [int(k) for k in lengths]
    # the reference check's rows stay the seed's own: fresh prompts in another order every run
    assert loadgen.draw_prompts(free["traffic_file"]["prompt_lengths"], 8, 96, 7, a + 1) != loadgen.draw_prompts(
        free["traffic_file"]["prompt_lengths"], 8, 96, 7, b + 1)


def test_the_documents_say_what_a_seed_may_change():
    doc = loadgen.__doc__
    for word in ("min_new_tokens", "weights_seed", "order_seed", "arrival", "set"):
        assert word in doc
    with open(harness.HERE + "/README.md") as f:
        readme = f.read()
    assert "min_new_tokens" in readme and "weights_seed" in readme and "order_seed" in readme
