"""Which path ``dot_product_attention`` takes, as a function of what a call
shows: ``ops/attention.py::attention_path``.

The rule runs at trace time and reads the backend, so the CPU sees it only
here: the backend is patched to ``"tpu"`` for the cases that need it and the
kernels themselves are never run (``tests/test_flash_attention.py`` holds
them to the XLA path in interpret mode).
"""

import jax
import jax.numpy as jnp
import pytest

from trlx_tpu.ops import attention
from trlx_tpu.ops.attention import (
    FLASH_MIN_SEQ,
    FLASH_MIN_SEQ_CAUSAL,
    attention_path,
    dot_product_attention,
    padding_bias,
)
from trlx_tpu.telemetry import get_metrics


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def shapes(q_len, k_len=None, heads=16, kv_heads=None, depth=64, batch=2):
    k_len = q_len if k_len is None else k_len
    kv_heads = heads if kv_heads is None else kv_heads
    return (batch, q_len, heads, depth), (batch, k_len, kv_heads, depth)


def path(q_len, k_len=None, *, causal=False, learned_bias=False, scale=None, **kw):
    return attention_path(
        *shapes(q_len, k_len, **kw),
        causal=causal, learned_bias=learned_bias, scale=scale,
    )


def test_the_crossovers_are_ordered():
    # the uncached causal call's crossover lies under the general one, and
    # at or under the cells' updates (T 512 and 560)
    assert 128 <= FLASH_MIN_SEQ_CAUSAL <= 512 < FLASH_MIN_SEQ == 1024


@pytest.mark.parametrize(
    "q_len", [FLASH_MIN_SEQ_CAUSAL, 512, 560, 640, 1000, 1024, 2048]
)
def test_uncached_causal_self_attention_takes_the_kernels(on_tpu, q_len):
    assert path(q_len, causal=True) == "flash"


@pytest.mark.parametrize("q_len", [8, 64, 256, 384, FLASH_MIN_SEQ_CAUSAL - 1])
def test_short_uncached_causal_calls_stay_on_xla(on_tpu, q_len):
    assert path(q_len, causal=True) == "xla"


@pytest.mark.parametrize(
    "q_len,k_len",
    [(512, 512), (640, 640), (128, 640), (1, 640), (512, 1023), (8, 2048)],
)
def test_a_cached_style_call_keeps_the_old_crossover(on_tpu, q_len, k_len):
    # what prefill, a prefill chunk, verify and a decode step send:
    # causal=False and an explicit bias, whatever the lengths
    assert path(q_len, k_len, causal=False) == "xla"


def test_a_cached_style_call_takes_the_kernels_from_1024(on_tpu):
    assert path(1024, 1024, causal=False) == "flash"
    assert path(1024, 2048, causal=False) == "flash"


def test_causal_with_unequal_lengths_keeps_the_old_crossover(on_tpu):
    assert path(512, 640, causal=True) == "xla"
    assert path(1024, 2048, causal=True) == "flash"


@pytest.mark.parametrize("q_len", [512, 560, 640, 1023])
@pytest.mark.parametrize(
    "kw", [dict(kv_heads=4), dict(scale=1 / 128), dict(kv_heads=4, scale=1 / 128)],
    ids=["grouped", "scale", "grouped+scale"],
)
def test_grouped_heads_or_a_scale_stay_on_xla_under_1024(on_tpu, q_len, kw):
    # granite's attention layer: 32 query over 8 KV heads, scores x 1/128
    assert path(q_len, causal=True, **kw) == "xla"


@pytest.mark.parametrize(
    "kw", [dict(kv_heads=4), dict(scale=1 / 128)], ids=["grouped", "scale"]
)
@pytest.mark.parametrize("causal", [True, False])
def test_grouped_heads_or_a_scale_are_refused_by_name_from_1024(on_tpu, causal, kw):
    with pytest.raises(ValueError, match="equal heads and the 1/sqrt"):
        path(1024, causal=causal, **kw)


@pytest.mark.parametrize("q_len", [560, 1024, 4096])
def test_a_learned_bias_pins_xla(on_tpu, q_len):
    assert path(q_len, causal=True, learned_bias=True) == "xla"
    # ... before the refusal: T5's bias with a scale would not raise either
    assert path(q_len, learned_bias=True, scale=1.0) == "xla"


@pytest.mark.parametrize("backend", ["cpu", "gpu"])
@pytest.mark.parametrize("q_len", [560, 1024, 4096])
def test_off_the_tpu_everything_is_xla(monkeypatch, backend, q_len):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert path(q_len, causal=True) == "xla"
    assert path(q_len, causal=False, kv_heads=4) == "xla"


def counts():
    reg = get_metrics()
    return {p: reg.counter("attention/path{path=%s}" % p).value for p in ("flash", "xla")}


def test_the_counter_counts_each_traced_site_once(monkeypatch):
    """``attention/path`` moves where the path is chosen, at trace time: once
    a call site of a traced program, not once an execution."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sent = []

    def kernel_stub(q, k, v, bias=None, *, causal=False, interpret=False):
        sent.append((q.shape, causal))
        return q

    monkeypatch.setattr(attention, "flash_on_program_mesh", kernel_stub)
    T = FLASH_MIN_SEQ_CAUSAL
    q = jnp.ones((1, T, 2, 8), jnp.float32)
    bias = padding_bias(jnp.ones((1, T), jnp.int32))

    @jax.jit
    def two_layers_and_a_cached_call(q):
        x = dot_product_attention(q, q, q, bias, causal=True)
        x = dot_product_attention(x, x, x, bias, causal=True)
        # a cached-style call of the same lengths: causal=False + a bias
        return dot_product_attention(x, x, x, bias)

    before = counts()
    for _ in range(3):  # traced once, run three times
        jax.block_until_ready(two_layers_and_a_cached_call(q))
    after = counts()
    assert after["flash"] - before["flash"] == 2
    assert after["xla"] - before["xla"] == 1
    assert sent == [((1, T, 2, 8), True)] * 2
