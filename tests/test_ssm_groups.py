"""``ops/ssm.py`` with ``B`` and ``C`` in groups: a head reads the pair of
its group (``h // (H / G)``) in the chunked scan, in the step and in the
mixer between its projections, and the gated norm is a group's. One group
keeps the form it had (``[.., N]`` operands) and gives the same bits through
the grouped form. All at toy sizes in float32."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.ops import ssm


def inputs(G, B=2, T=21, H=8, P=4, N=6, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, G, N))
    Cm = jax.random.normal(ks[4], (B, T, G, N))
    D = jax.random.normal(ks[5], (H,))
    state = jax.random.normal(ks[6], (B, H, P, N))
    return x, dt, A, Bm, Cm, D, state


@jax.jit
def recurrence(x, dt, A, Bm, Cm, D, mask, state):
    """The rule as the module's docstring writes it, a column at a time,
    every head with its group's pair spelled out."""
    H, G = x.shape[2], Bm.shape[2]
    ys = []
    for t in range(x.shape[1]):
        d = dt[:, t] * mask[:, t, None]
        B_h, C_h = (jnp.repeat(a[:, t], H // G, axis=1) for a in (Bm, Cm))  # [B, H, N]
        state = state * jnp.exp(d * A)[..., None, None] + (d[..., None] * x[:, t])[..., None] * B_h[:, :, None, :]
        ys.append(jnp.einsum("bhpn,bhn->bhp", state, C_h) + D[None, :, None] * x[:, t])
    return jnp.stack(ys, axis=1), state


@pytest.mark.parametrize("chunk", [4, 32])
@pytest.mark.parametrize("G", [2, 4])
def test_the_chunked_scan_with_groups_is_the_plain_recurrence(G, chunk):
    x, dt, A, Bm, Cm, D, state = inputs(G)
    mask = jnp.ones(x.shape[:2])
    y, S = ssm.ssd_scan(x, dt, A, Bm, Cm, D, mask, state, chunk)
    want_y, want_S = recurrence(x, dt, A, Bm, Cm, D, mask, state)
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(S, want_S, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("G", [2, 4])
def test_the_step_with_groups_is_the_plain_recurrence_and_goes_on_from_a_scan(G):
    """A prefill of 13 columns through the scan, then eight steps, against
    the recurrence over all 21."""
    x, dt, A, Bm, Cm, D, state = inputs(G)
    mask = jnp.ones(x.shape[:2])
    want_y, want_S = recurrence(x, dt, A, Bm, Cm, D, mask, state)
    y, S = ssm.ssd_scan(x[:, :13], dt[:, :13], A, Bm[:, :13], Cm[:, :13], D, mask[:, :13], state, 4)
    np.testing.assert_allclose(y, want_y[:, :13], rtol=2e-5, atol=2e-5)
    for t in range(13, x.shape[1]):
        y_t, S = ssm.ssd_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D, mask[:, t], S)
        np.testing.assert_allclose(y_t, want_y[:, t], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(S, want_S, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("G", [1, 2, 4])
def test_masked_columns_leave_a_grouped_state_bit_for_bit(G):
    x, dt, A, Bm, Cm, D, state = inputs(G)
    if G == 1:
        Bm, Cm = Bm[:, :, 0], Cm[:, :, 0]
    nothing = jnp.zeros(x.shape[:2])
    _, S = ssm.ssd_scan(x, dt, A, Bm, Cm, D, nothing, state, 8)
    np.testing.assert_array_equal(S, state)
    _, S = ssm.ssd_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D, nothing[:, 0], state)
    np.testing.assert_array_equal(S, state)
    # a row masked, a row live: the masked row alone keeps its state
    first = jnp.asarray([0.0, 1.0])
    _, S = ssm.ssd_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D, first, state)
    np.testing.assert_array_equal(S[0], state[0])
    assert float(jnp.abs(S[1] - state[1]).max()) > 0


def test_one_group_through_the_grouped_form_gives_todays_bits():
    """``[.., N]`` operands take the form the module had before groups (the
    text of that branch is unchanged); the grouped form at ``G = 1`` is the
    same sums in the same order and returns the same bits, scan and step."""
    x, dt, A, Bm, Cm, D, state = inputs(1)
    mask = jnp.ones(x.shape[:2]).at[0, :3].set(0.0)
    before = ssm.ssd_scan(x, dt, A, Bm[:, :, 0], Cm[:, :, 0], D, mask, state, 8)
    grouped = ssm.ssd_scan(x, dt, A, Bm, Cm, D, mask, state, 8)
    for a, b in zip(before, grouped):
        np.testing.assert_array_equal(a, b)
    before = ssm.ssd_step(x[:, 5], dt[:, 5], A, Bm[:, 5, 0], Cm[:, 5, 0], D, mask[:, 5], state)
    grouped = ssm.ssd_step(x[:, 5], dt[:, 5], A, Bm[:, 5], Cm[:, 5], D, mask[:, 5], state)
    for a, b in zip(before, grouped):
        np.testing.assert_array_equal(a, b)


def test_a_head_reads_its_own_groups_pair_and_no_other():
    """Moving group 1's ``B`` and ``C`` moves the heads of group 1 alone."""
    x, dt, A, Bm, Cm, D, state = inputs(2)
    mask = jnp.ones(x.shape[:2])
    y, S = ssm.ssd_scan(x, dt, A, Bm, Cm, D, mask, state, 8)
    y2, S2 = ssm.ssd_scan(x, dt, A, Bm.at[:, :, 1].add(1.0), Cm.at[:, :, 1].add(1.0), D, mask, state, 8)
    np.testing.assert_array_equal(y[:, :, :4], y2[:, :, :4])
    np.testing.assert_array_equal(S[:, :4], S2[:, :4])
    assert float(jnp.abs(y[:, :, 4:] - y2[:, :, 4:]).min(axis=(0, 1, 3)).max()) > 0


@pytest.mark.parametrize("G", [1, 2, 8])
def test_the_gated_norm_is_a_groups(G):
    y = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 32))
    z = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 32))
    w = jax.random.normal(jax.random.PRNGKey(2), (32,))
    g = np.asarray(y * jax.nn.silu(z), np.float64).reshape(2, 5, G, 32 // G)
    want = (g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)).reshape(2, 5, 32) * np.asarray(w)
    np.testing.assert_allclose(ssm.gated_rms_norm(y, z, w, 1e-5, G), want, rtol=1e-5, atol=1e-6)
    if G == 1:  # the default is the whole width, as it was
        np.testing.assert_array_equal(ssm.gated_rms_norm(y, z, w, 1e-5), ssm.gated_rms_norm(y, z, w, 1e-5, 1))


def mixer_args(G, H=8, P=4, N=6, K=4, T=12, B=2, seed=0):
    width = H * P + 2 * G * N
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        xBC=jax.random.normal(ks[0], (B, T, width)), dt_raw=jax.random.normal(ks[1], (B, T, H)),
        conv_weight=jax.random.normal(ks[2], (K, width)) * 0.3, conv_bias=jax.random.normal(ks[3], (width,)) * 0.1,
        dt_bias=jax.random.normal(ks[4], (H,)) - 2.0, A_log=jnp.log(jnp.arange(1.0, H + 1)),
        D=jnp.ones((H,)), n_heads=H, head_dim=P, d_state=N, chunk=4,
    )


def test_the_mixer_splits_x_B_C_by_groups_and_a_prefill_then_steps_is_the_whole_sequence():
    """``xBC`` is ``[H P | G N | G N]``; the whole sequence uncached equals
    seven columns through the cache then five steps, state and tail carried."""
    from trlx_tpu import telemetry

    G = 2
    a = mixer_args(G)
    xBC, dt_raw = a.pop("xBC"), a.pop("dt_raw")
    with telemetry.scoped_metrics() as reg:
        whole, none = ssm.mamba2_mix(xBC, dt_raw, **a, n_groups=G)
        assert none is None and reg.snapshot()["gauges"]["ssm/groups"] == G
    layer = {"ssm_state": jnp.zeros((2, 8, 4, 6)), "conv_tail": jnp.zeros((2, 3, xBC.shape[-1]))}
    y, layer = ssm.mamba2_mix(xBC[:, :7], dt_raw[:, :7], **a, n_groups=G, cache_layer=layer)
    np.testing.assert_allclose(y, whole[:, :7], rtol=2e-5, atol=2e-5)
    for t in range(7, 12):
        y, layer = ssm.mamba2_mix(xBC[:, t : t + 1], dt_raw[:, t : t + 1], **a, n_groups=G, cache_layer=layer)
        np.testing.assert_allclose(y[:, 0], whole[:, t], rtol=2e-5, atol=2e-5)
    # the B and C columns of group 1 reach the heads of group 1 alone
    H, P, N = 8, 4, 6
    moved = xBC.at[..., H * P + N : H * P + 2 * N].add(0.5)  # B of group 1
    other, _ = ssm.mamba2_mix(moved, dt_raw, **a, n_groups=G)
    np.testing.assert_array_equal(whole[..., : 4 * P], other[..., : 4 * P])
    assert float(jnp.abs(whole[..., 4 * P :] - other[..., 4 * P :]).max()) > 0


def test_a_width_that_is_not_the_groups_is_refused():
    a = mixer_args(2)
    xBC, dt_raw = a.pop("xBC"), a.pop("dt_raw")
    with pytest.raises(ValueError, match="2 groups of B and C"):
        ssm.mamba2_mix(xBC[..., :-1], dt_raw, **a, n_groups=2)
    with pytest.raises(ValueError, match="3 groups"):
        ssm.mamba2_mix(xBC, dt_raw, **a, n_groups=3)
