"""``ops/moe.py``'s plain expert (``W_down act(W_up h)``: two matrices, no
gate) through ``expert_layer``'s dispatch, grouped multiplication and
combine, on rows whose width is not the router's input width (a latent of
the stream), against a loop over experts. Toy sizes, float32."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu import telemetry
from trlx_tpu.ops import moe

D, Z, F, E = 24, 8, 12, 16  # the router's input, the experts' rows, an expert's width, the router's outputs


def weights(held, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        u=jax.random.normal(ks[0], (2, 7, D)), down=jax.random.normal(ks[1], (D, Z)) * 0.3,
        router=jax.random.normal(ks[2], (D, E)), bias=jax.random.normal(ks[3], (E,)) * 0.1,
        w_up=jax.random.normal(ks[4], (held, Z, F)) * 0.3,
        w_down=jax.random.normal(jax.random.fold_in(ks[4], 1), (held, F, Z)) * 0.3,
    )


def by_loop(latent, routing, w_up, w_down, first, act):
    """Every held expert on every row, weighted by the routing (0 where it
    was not chosen)."""
    flat = np.asarray(latent, np.float64).reshape(-1, latent.shape[-1])
    out = np.zeros((flat.shape[0], w_down.shape[-1]))
    experts, weights_ = np.asarray(routing.experts), np.asarray(routing.weights, np.float64)
    for e in range(w_up.shape[0]):
        w = (weights_ * (experts == first + e)).sum(-1)
        out += np.asarray(act(flat @ np.asarray(w_up[e], np.float64))) @ np.asarray(w_down[e], np.float64) * w[:, None]
    return out.reshape(latent.shape[:-1] + (-1,))


@pytest.mark.parametrize("k", [1, 6], ids=["one-choice", "six-choices"])
@pytest.mark.parametrize("held,first", [(E, 0), (4, 8)], ids=["all-held", "a-share"])
def test_the_plain_expert_in_a_latent_is_the_loop_over_experts(held, first, k):
    w = weights(held)
    routing = moe.route_group_limited(w["u"].reshape(-1, D), w["router"], w["bias"], k, n_group=1, topk_group=1, scale=5.0)
    latent = w["u"] @ w["down"]  # rows 8 wide; the router read 24
    with telemetry.scoped_metrics() as reg:
        y, back = moe.expert_layer(latent, None, None, w["w_up"], w["w_down"], dtype=jnp.float32,
                                   routing=routing, first_expert=first, activation="relu2")
        counters = reg.snapshot()["counters"]
    assert counters["moe/expert_form{form=plain}"] == 1 and "moe/expert_form{form=gated}" not in counters
    assert back is routing and y.shape == latent.shape
    want = by_loop(latent, routing, w["w_up"], w["w_down"], first, lambda a: np.square(np.maximum(a, 0)))
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    if held < E:  # routed over all E: some copies chose an expert that is not here and add nothing
        assert float(moe.routing_stats(routing, E, first, held)["rows_here_share"]) < 1


def test_the_result_has_w_downs_width():
    w = weights(E)
    routing = moe.route_group_limited(w["u"].reshape(-1, D), w["router"], w["bias"], 3, n_group=1, topk_group=1)
    latent = w["u"] @ w["down"]
    wide = jnp.concatenate([w["w_down"], w["w_down"]], axis=-1)  # [E, F, 2 Z]
    y, _ = moe.expert_layer(latent, None, None, w["w_up"], wide, dtype=jnp.float32, routing=routing, activation="relu2")
    assert y.shape == latent.shape[:-1] + (2 * Z,)
    want = by_loop(latent, routing, w["w_up"], w["w_down"], 0, lambda a: np.square(np.maximum(a, 0)))
    np.testing.assert_allclose(y[..., :Z], want, rtol=2e-5, atol=2e-5)


def test_the_gated_form_counts_itself_and_is_untouched_by_the_plain_one():
    w = weights(E)
    gate = jax.random.normal(jax.random.PRNGKey(9), (E, Z, F)) * 0.3
    latent = w["u"] @ w["down"]
    router = jax.random.normal(jax.random.PRNGKey(3), (Z, E))
    with telemetry.scoped_metrics() as reg:
        y, routing = moe.expert_layer(latent, router, gate, w["w_up"], w["w_down"], k=2, dtype=jnp.float32)
        counters = reg.snapshot()["counters"]
    assert counters["moe/expert_form{form=gated}"] == 1 and "moe/expert_form{form=plain}" not in counters
    flat = np.asarray(latent, np.float64).reshape(-1, Z)
    want = np.zeros_like(flat)
    for e in range(E):
        wt = (np.asarray(routing.weights) * (np.asarray(routing.experts) == e)).sum(-1)
        g = flat @ np.asarray(gate[e], np.float64)
        want += (g / (1 + np.exp(-g)) * (flat @ np.asarray(w["w_up"][e], np.float64))) @ np.asarray(w["w_down"][e], np.float64) * wt[:, None]
    np.testing.assert_allclose(y.reshape(-1, Z), want, rtol=2e-5, atol=2e-5)


def test_what_the_plain_form_does_not_take_is_refused_by_name():
    from trlx_tpu.parallel.mesh import make_mesh

    w = weights(E)
    latent = w["u"] @ w["down"]
    routing = moe.route_group_limited(w["u"].reshape(-1, D), w["router"], w["bias"], 2, n_group=1, topk_group=1)
    args = (latent, None, None, w["w_up"], w["w_down"])
    with pytest.raises(ValueError, match="gated experts .* or plain ones"):
        moe.expert_layer(*args, routing=routing)  # no gate and no activation
    with pytest.raises(ValueError, match="gated experts .* or plain ones"):
        moe.expert_layer(latent, None, w["w_up"], w["w_up"], w["w_down"], routing=routing, activation="relu2")
    with pytest.raises(ValueError, match="activation='tanh'"):
        moe.expert_layer(*args, routing=routing, activation="tanh")
    with pytest.raises(ValueError, match="are not among the router's 16"):
        moe.expert_layer(latent, None, None, w["w_up"][:4], w["w_down"][:4], routing=routing,
                         first_expert=13, activation="relu2")
    mesh = make_mesh({"dp": 1, "fsdp": 1, "tp": 1, "ep": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="a plain expert .* is not built on an ep mesh"):
        moe.expert_layer(*args, routing=routing, activation="relu2", mesh=mesh)
