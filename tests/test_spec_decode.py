"""Speculative decoding (``rollout.spec_decode``): drafter units, the
``accept_drafts`` kernel, and the bitwise spec-on ↔ spec-off parity pin.

The correctness story is PR-8's per-row RNG contract: token t of a row
depends only on (prompt, draw index, params) via ``fold_in(row_key, t)``
— so the verify step's exact-match acceptance provably commits the SAME
tokens the one-token loop would have sampled, and the whole feature
lands under the repo's standard parity pin (tokens/masks bitwise,
logprobs/values exact on the f32 CPU tier). A wrong draft costs padded
verify FLOPs, never correctness.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.analysis import harness
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.inference import RolloutEngineConfig, SpecDecodeConfig
from trlx_tpu.telemetry.health import without_timing
from trlx_tpu.ops.sampling import (
    GenerationConfig,
    accept_drafts,
    make_row_keys,
)
from trlx_tpu.serving.prefix_cache import PrefixBlockPool
from trlx_tpu.serving.spec_drafter import (
    DEGRADE_PROBE_EVERY,
    NGramDrafter,
    TrieDrafter,
)

DP_MESH = {"dp": -1, "fsdp": 1, "tp": 1}
BASE_ROLLOUT = {
    "engine": "continuous", "slots": 16, "admit_width": 8,
    "harvest_width": 8, "block_size": 4, "per_row_rng": True,
}
SPEC = {"enabled": True, "max_draft": 3, "drafter": "ngram"}


# ------------------------------ config --------------------------------- #


def test_spec_config_validation():
    cfg = RolloutEngineConfig.from_dict(
        {"engine": "continuous", "spec_decode": dict(SPEC)}
    )
    assert cfg.spec_decode.enabled and cfg.spec_decode.max_draft == 3
    with pytest.raises(ValueError, match="Unknown train.rollout spec"):
        SpecDecodeConfig.from_dict({"enabeld": True})
    with pytest.raises(ValueError, match="drafter"):
        SpecDecodeConfig.from_dict({"drafter": "medusa"})
    with pytest.raises(ValueError, match="max_draft"):
        SpecDecodeConfig.from_dict({"max_draft": 0})
    with pytest.raises(ValueError, match="min_accept_ewma"):
        SpecDecodeConfig.from_dict({"min_accept_ewma": 1.5})
    with pytest.raises(ValueError, match="continuous"):
        RolloutEngineConfig.from_dict(
            {"engine": "fixed", "spec_decode": dict(SPEC)}
        )
    # disabled spec rides along under any engine
    RolloutEngineConfig.from_dict(
        {"engine": "fixed", "spec_decode": {"enabled": False}}
    )


# --------------------------- drafter units ------------------------------ #


def test_ngram_drafter_hit_and_miss():
    d = NGramDrafter(max_draft=4)
    d.observe_context(0, [5, 6, 7, 8, 5, 6, 7])
    # suffix [5,6,7] recurred at position 0 -> continuation [8,5,6,7]
    assert d.draft(0) == [8, 5, 6, 7]
    d.observe_tokens(0, [9])  # suffix now [6,7,9]: unseen -> miss
    assert d.draft(0) == []
    assert d.draft(1) == []  # unknown row
    d.forget(0)
    assert d.draft(0) == []  # history gone with the slot


def test_ngram_drafter_caps_at_max_draft():
    d = NGramDrafter(max_draft=2)
    d.observe_context(0, [1, 2, 3, 4, 5, 1, 2])
    assert d.draft(0) == [3, 4]  # continuation truncated to max_draft


def test_trie_drafter_global_corpus_hit():
    """A row whose OWN history never repeated still drafts from a
    published trie chain containing its suffix (the system-integrated
    drafter: other requests' prefixes predict this one)."""
    pool = PrefixBlockPool(pool_blocks=8, block_size=4, n_blocks=2)
    ids = np.asarray([3, 4, 5, 6, 7, 8, 9, 10])
    mask = np.ones((8,), np.int32)
    plan = pool.plan_admission(ids, mask)
    pool.mark_ready(plan.published)
    d = TrieDrafter(pool=pool, max_draft=3)
    d.observe_context(0, [1, 2, 3, 4, 5])  # suffix [3,4,5] in the chain
    assert d.draft(0) == [6, 7, 8]
    assert d.trie_hits == 1


def test_trie_drafter_partial_and_self_preference():
    """Own-history lookup wins over the trie corpus when both match."""
    pool = PrefixBlockPool(pool_blocks=8, block_size=4, n_blocks=2)
    ids = np.asarray([3, 4, 5, 20, 21, 22, 23, 24])
    mask = np.ones((8,), np.int32)
    plan = pool.plan_admission(ids, mask)
    pool.mark_ready(plan.published)
    d = TrieDrafter(pool=pool, max_draft=2)
    d.observe_context(0, [3, 4, 5, 9, 3, 4, 5])
    assert d.draft(0) == [9, 3]  # self-lookup, not the chain's [20, 21]
    assert d.trie_hits == 0


def test_trie_drafter_empty_trie_falls_back():
    """Empty / not-ready trie: the drafter degrades to pure n-gram
    self-lookup, and to no draft when that misses too."""
    pool = PrefixBlockPool(pool_blocks=8, block_size=4, n_blocks=2)
    d = TrieDrafter(pool=pool, max_draft=3)
    d.observe_context(0, [1, 2, 7, 1, 2])
    assert d.draft(0) == [7, 1, 2]  # self-lookup fallback
    d.observe_context(1, [1, 2, 3, 4, 5])
    assert d.draft(1) == []  # nothing anywhere
    # an in-flight (never marked ready) publish chain is not a corpus
    pool.plan_admission(
        np.asarray([1, 2, 3, 4, 5, 6, 7, 8]), np.ones((8,), np.int32)
    )
    assert pool.ready_chains() == []
    assert d.draft(1) == []


def test_accept_ewma_degrade_and_probe():
    """Below min_accept_ewma a tenant's rows stop drafting (graceful
    degrade, never an abort) — but a probe draft escapes every
    DEGRADE_PROBE_EVERY draws so the EWMA can recover."""
    d = NGramDrafter(max_draft=2, min_accept_ewma=0.4, ewma_alpha=0.5)
    d.observe_context(0, [1, 2, 3, 1, 2])
    d.set_tenant(0, "acme")
    assert d.draft(0) == [3, 1]
    for _ in range(8):  # hammer the EWMA with total rejection
        d.observe_accept(0, 2, 0)
    assert d.accept_ewma("acme") < 0.4
    draws = [d.draft(0) for _ in range(DEGRADE_PROBE_EVERY)]
    assert draws[:-1] == [[]] * (DEGRADE_PROBE_EVERY - 1)
    assert draws[-1] == [3, 1]  # the probe
    # acceptance recovers the tenant above the bar -> drafting resumes
    for _ in range(8):
        d.observe_accept(0, 2, 2)
    assert d.accept_ewma("acme") > 0.4
    assert d.draft(0) == [3, 1]


# --------------------------- accept kernel ------------------------------ #


def _peaked_logits(B, T, V, targets):
    """[B, T, V] logits so sharply peaked that sampling at any
    temperature picks ``targets[b][t]`` deterministically."""
    out = np.full((B, T, V), -1e9, np.float32)
    for b in range(B):
        for t in range(T):
            out[b, t, targets[b][t]] = 1e9
    return jnp.asarray(out)


def test_accept_drafts_prefix_semantics():
    """Sequential exact-match acceptance: full accept, partial accept
    (stop at first mismatch — later matches do NOT resurrect), all
    reject, and the finished-row / beyond-draft-len guards."""
    cfg = GenerationConfig(
        max_new_tokens=8, eos_token_id=30, pad_token_id=31,
        per_row_rng=True,
    )
    B, D, V = 4, 3, 32
    targets = [[4, 5, 6], [4, 9, 6], [9, 9, 9], [4, 5, 6]]
    logits = _peaked_logits(B, D, V, targets)
    values = jnp.zeros((B, D), jnp.float32)
    keys = make_row_keys(jax.random.PRNGKey(0), np.arange(B))
    draft = jnp.asarray(
        [[4, 5, 6], [4, 5, 6], [4, 5, 6], [4, 5, 6]], jnp.int32
    )
    # row 3: draft_len 1 caps acceptance even though all 3 would match
    draft_len = jnp.asarray([3, 3, 3, 1], jnp.int32)
    toks, acc, lps, vals, n_acc, fin = accept_drafts(
        cfg, logits, values,
        t0=jnp.zeros((B,), jnp.int32),
        finished=jnp.zeros((B,), bool),
        accepted0=jnp.ones((B,), bool),
        n_real=jnp.full((B,), 4, jnp.int32),
        draft=draft, draft_len=draft_len, row_keys=keys,
        budget=8,
    )
    np.testing.assert_array_equal(np.asarray(n_acc), [3, 1, 0, 1])
    np.testing.assert_array_equal(
        np.asarray(acc), [[1, 1, 1], [1, 0, 0], [0, 0, 0], [1, 0, 0]]
    )
    # accepted columns carry the TARGET tokens (== draft where accepted)
    np.testing.assert_array_equal(np.asarray(toks)[0], [4, 5, 6])
    # a finished row accepts nothing (its sampler emits pad, live=0)
    _, _, _, _, n_acc2, _ = accept_drafts(
        cfg, logits, values,
        t0=jnp.zeros((B,), jnp.int32),
        finished=jnp.ones((B,), bool),
        accepted0=jnp.ones((B,), bool),
        n_real=jnp.full((B,), 4, jnp.int32),
        draft=draft, draft_len=draft_len, row_keys=keys,
        budget=8,
    )
    np.testing.assert_array_equal(np.asarray(n_acc2), [0, 0, 0, 0])


# ------------------------- engine integration --------------------------- #


_CACHE = {}


def _spec_trainer(name, mesh, spec=None, min_accept_ewma=None):
    if name not in _CACHE:
        from trlx_tpu.trainer.ppo_trainer import PPOTrainer

        cfg = harness.tiny_config_dict("ppo", mesh=dict(mesh))
        cfg["method"]["num_rollouts"] = 16
        cfg["method"]["chunk_size"] = 8
        cfg["train"]["batch_size"] = 8
        rollout = dict(BASE_ROLLOUT)
        if spec:
            rollout["spec_decode"] = dict(spec)
            if min_accept_ewma is not None:
                rollout["spec_decode"]["min_accept_ewma"] = min_accept_ewma
        cfg["train"]["rollout"] = rollout
        cfg["method"]["gen_kwargs"]["min_new_tokens"] = 1
        _CACHE[name] = PPOTrainer(TRLConfig.from_dict(cfg))
    return _CACHE[name]


def _draftable_prompts(n, q):
    """Cyclic 2-token prompts: every suffix recurs, so the n-gram
    drafter proposes on the very first decode step of every row."""
    ids = np.zeros((n, q), np.int32)
    for i in range(n):
        ids[i] = ([1 + (i % 4), 2 + (i % 4)] * q)[:q]
    return ids, np.ones((n, q), np.int32)


def _drive_phase(trainer, ids, mask, n):
    trainer.rng = jax.random.PRNGKey(42)
    trainer.reset_rollout_phase()
    engine = trainer.rollout_engine_obj
    engine.start_phase(
        trainer.rollout_params(), trainer.rollout_phase_key()
    )
    engine.submit(ids, mask)
    got = {}
    for group in engine.drive(n):
        arrs = {
            k: np.asarray(group[k])
            for k in ("tokens", "response_mask", "logprobs", "values")
        }
        for j, r in enumerate(group["rows"]):
            assert r not in got, "row harvested twice"
            got[r] = {k: v[j] for k, v in arrs.items()}
    assert set(got) == set(range(n))
    return got


PARITY_MESHES = [
    pytest.param(DP_MESH, id="dp"),
    pytest.param(
        {"dp": 2, "fsdp": 2, "tp": 2}, id="fsdp_tp",
        marks=pytest.mark.slow,
    ),
    pytest.param(
        {"dp": -1, "fsdp": 1, "tp": 1, "sp": 2}, id="sp",
        marks=pytest.mark.slow,
    ),
]


@pytest.mark.parametrize("mesh", PARITY_MESHES)
def test_spec_bitwise_parity_full_phase(mesh):
    """THE acceptance pin: spec-on and spec-off decode the same prompt
    set to bitwise-identical per-row tokens and response masks —
    accepted draft tokens are provably the tokens the one-token loop
    would have sampled (per-row ``fold_in(row_key, t)`` keys), and
    rejected drafts leave no trace (OOB KV drops + causally-masked
    garbage). Logprobs/values exact on the f32 CPU dp tier, at the
    engine's established bf16 resolution on tp-sharded meshes."""
    mesh_id = "dp" if mesh == DP_MESH else ("sp" if "sp" in mesh else "mix")
    off = _spec_trainer(f"off_{mesh_id}", mesh)
    on = _spec_trainer(f"on_{mesh_id}", mesh, spec=SPEC)
    for a, b in zip(jax.tree_util.tree_leaves(off.state.params),
                    jax.tree_util.tree_leaves(on.state.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    N, Q = 16, off.query_length
    ids, mask = _draftable_prompts(N, Q)
    want = _drive_phase(off, ids, mask, N)
    got = _drive_phase(on, ids, mask, N)
    st = on.rollout_engine_obj.stats
    assert st.spec_steps >= 1 and st.spec_drafted > 0
    exact = mesh == DP_MESH  # f32 CPU tier: logprobs/values exact
    for r in range(N):
        np.testing.assert_array_equal(got[r]["tokens"],
                                      want[r]["tokens"])
        np.testing.assert_array_equal(got[r]["response_mask"],
                                      want[r]["response_mask"])
        if exact:
            np.testing.assert_array_equal(got[r]["logprobs"],
                                          want[r]["logprobs"])
            np.testing.assert_array_equal(got[r]["values"],
                                          want[r]["values"])
        else:
            np.testing.assert_allclose(got[r]["logprobs"],
                                       want[r]["logprobs"],
                                       rtol=0, atol=1e-2)
            np.testing.assert_allclose(got[r]["values"],
                                       want[r]["values"],
                                       rtol=0, atol=2e-2)
    # telemetry satellite: the gauges exist in the stats dict
    d = st.to_dict()
    for key in ("engine/spec_draft_len_p50", "engine/spec_accept_rate",
                "engine/spec_tokens_per_step"):
        assert key in d
    assert d["engine/spec_tokens_per_step"] >= 1.0


class _JunkDrafter:
    """Adversarial drafter: always proposes pad tokens — near-certain
    rejection at every position."""

    def __init__(self, token=31, n=3):
        self.token, self.n = token, n

    def draft(self, row):
        return [self.token] * self.n

    def observe_context(self, row, tokens):
        pass

    def observe_tokens(self, row, tokens):
        pass

    def observe_accept(self, row, n_proposed, n_accepted):
        pass

    def forget(self, row):
        pass

    def reset(self):
        pass


def test_all_rejected_still_progresses_bitwise():
    """The all-rejected edge: every verify step still commits >= 1
    token per live row (the anchor is sampled from the carried logits,
    not drafted — it is always the correct next token), so a
    pathologically wrong drafter can slow decode to one-token cadence
    but never stall or corrupt it."""
    off = _spec_trainer("off_dp", DP_MESH)
    on = _spec_trainer("junk_dp", DP_MESH, spec=SPEC)
    engine = on.rollout_engine_obj
    engine.spec_drafter = _JunkDrafter()
    N, Q = 16, off.query_length
    rng = np.random.default_rng(5)
    ids = rng.integers(1, 30, (N, Q)).astype(np.int32)
    mask = np.ones((N, Q), np.int32)
    want = _drive_phase(off, ids, mask, N)
    got = _drive_phase(on, ids, mask, N)
    st = engine.stats
    assert st.spec_steps >= 1 and st.spec_drafted > 0
    for r in range(N):
        np.testing.assert_array_equal(got[r]["tokens"],
                                      want[r]["tokens"])
        np.testing.assert_array_equal(got[r]["response_mask"],
                                      want[r]["response_mask"])
        np.testing.assert_array_equal(got[r]["logprobs"],
                                      want[r]["logprobs"])


def test_weight_push_invalidates_staged_drafts():
    """Regression: a weight push applied at the drive loop's safe point
    drops prefetched draft proposals — the next verify step re-drafts
    against histories observed under the NEW params version, keeping the
    draft-overlap window inside one version."""
    trainer = _spec_trainer("on_dp", DP_MESH, spec=SPEC)
    engine = trainer.rollout_engine_obj
    trainer.rng = jax.random.PRNGKey(9)
    trainer.reset_rollout_phase()
    engine.start_phase(
        trainer.rollout_params(), trainer.rollout_phase_key()
    )
    N, Q = 8, trainer.query_length
    ids, mask = _draftable_prompts(N, Q)
    engine.submit(ids, mask)
    # stage a prefetched draft matrix the way _verify_once would
    engine._staged_drafts = engine._draft_now()
    assert engine._staged_drafts is not None
    version = engine.param_version
    engine.push_weights(trainer.rollout_params(), version=version + 1)
    assert engine._staged_drafts is not None  # staged, not yet applied
    engine._apply_pending_push()
    assert engine._staged_drafts is None  # the invalidation under test
    assert engine.param_version == version + 1
    for _ in engine.drive(N):
        pass
    assert engine.pending == 0


def test_spec_serving_parity_with_sharing():
    """Serving-tier pin, sharing ON: the trie-drafted spec server and a
    spec-off server return bitwise-identical tokens for the same
    submission order, with the shared-prefix pool active in both (the
    trie drafter reads the pool it shares blocks from)."""
    from trlx_tpu.inference.server import InferenceServer

    def build(spec_on):
        # default audit mesh: its 4 data shards fit the 4-slot pool
        # (dp-only on 8 host devices would round admit_width past it)
        cfg = harness.tiny_config_dict("ppo")
        rollout = {
            "engine": "continuous",
            "slots": 4, "admit_width": 2, "harvest_width": 2,
            "block_size": 4,
        }
        if spec_on:
            rollout["spec_decode"] = {
                "enabled": True, "max_draft": 3, "drafter": "trie",
            }
        cfg["train"]["rollout"] = rollout
        cfg["train"]["serving"] = {
            "prefix_cache_blocks": 16,
            "slo_classes": {
                "interactive": {"queue_wait_budget_ms": 120000},
                "standard": {"queue_wait_budget_ms": 120000},
            },
        }
        return InferenceServer(TRLConfig.from_dict(cfg))

    base = build(False)
    spec = build(True)
    assert isinstance(spec.engine.spec_drafter, TrieDrafter)
    assert spec.engine.spec_drafter.pool is spec.prefix_pool
    Q = base.query_length
    prompts = [([3, 4] * Q)[:Q] for _ in range(4)]
    want = base.generate(prompts)
    got = spec.generate(prompts)
    for w, g in zip(want, got):
        assert w["tokens"] == g["tokens"]
    st = spec.engine.stats
    assert st.spec_steps >= 1 and st.spec_drafted > 0
    assert without_timing(spec.health_events) == []
    assert "engine/spec_accept_rate" in spec.stats()
