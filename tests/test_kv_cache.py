"""The KV cache's one owner (``trlx_tpu/ops/kv_cache.py``): the import
arrows point one way, and the one classification of a cache dict decides
which read ``decode_attention`` takes for every cache kind the engine and
both samplers build."""

import ast
import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "trlx_tpu")
ABOVE_OPS = ("models", "inference", "serving", "trainer")
# files that take cache names from ops/kv_cache.py, at top level only
CACHE_USERS = [
    "ops/attention.py", "ops/kv_cache.py", "ops/sampling.py",
    "models/gpt2.py", "models/gptj.py", "models/neox.py",
    "models/gpt_neo.py", "models/olmoe.py", "models/pp_runner.py",
    "inference/engine.py",
]


def _imports(source):
    """(module, names, nested) of every import statement, at any depth."""
    tree = ast.parse(source)
    top = set(map(id, tree.body))
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            yield node.module or "", [a.name for a in node.names], id(node) not in top
        elif isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, [], id(node) not in top


def _cache_names():
    with open(os.path.join(PKG, "ops", "kv_cache.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                names.update(
                    e.id for e in ast.walk(t) if isinstance(e, ast.Name)
                )
    return names


def _upward_imports(source):
    """Imports of a layer above ``ops`` in one ``ops`` file's source."""
    return [
        mod for mod, _, _ in _imports(source)
        if any(
            mod == f"trlx_tpu.{up}" or mod.startswith(f"trlx_tpu.{up}.")
            for up in ABOVE_OPS
        )
    ]


def _lazy_cache_imports(source, cache_names):
    """Function-level imports of the cache module or of a name it owns."""
    return [
        (mod, names) for mod, names, nested in _imports(source)
        if nested and (
            mod.endswith(".kv_cache") or cache_names.intersection(names)
        )
    ]


def test_ops_import_nothing_from_the_layers_above():
    ops = os.path.join(PKG, "ops")
    bad = {}
    for name in sorted(os.listdir(ops)):
        if name.endswith(".py"):
            with open(os.path.join(ops, name)) as f:
                found = _upward_imports(f.read())
            if found:
                bad[name] = found
    assert not bad
    assert not os.path.exists(os.path.join(PKG, "inference", "kv_cache.py"))


@pytest.mark.parametrize("rel", CACHE_USERS)
def test_cache_names_are_imported_at_top_level(rel):
    with open(os.path.join(PKG, rel)) as f:
        assert not _lazy_cache_imports(f.read(), _cache_names())


def test_the_import_checks_catch_a_lazy_import_put_back():
    names = _cache_names()
    assert {"quantize_kv", "kv_buffers", "paged_write_read", "cache_kind",
            "SHARED_POOL_KEYS", "INT8_KV_MAX_CAPACITY"} <= names
    lazy_up = "def f():\n    from trlx_tpu.models.gpt2 import quantize_kv\n"
    assert _upward_imports(lazy_up) == ["trlx_tpu.models.gpt2"]
    assert _lazy_cache_imports(lazy_up, names)
    assert _lazy_cache_imports(
        "class A:\n    def f(self):\n"
        "        from trlx_tpu.ops.kv_cache import SHARED_POOL_KEYS\n", names
    )
    assert _lazy_cache_imports(
        "def f():\n    from trlx_tpu.inference.kv_cache import x\n", names
    )
    top = "from trlx_tpu.ops.kv_cache import kv_buffers\n"
    assert not _lazy_cache_imports(top, names) and not _upward_imports(top)


# ------------------------------ classification ------------------------- #

B, H, C, DH = 2, 2, 16, 8


def _cache(layout, kv, shared):
    import jax.numpy as jnp

    from trlx_tpu.ops import kv_cache as kc

    if layout == kc.PAGED:
        cache = kc.init_paged_cache(
            1, B, C, H, DH, jnp.float32, kv, block_size=4
        )[0]
        if shared:
            cache = dict(
                cache,
                **kc.init_shared_pool(2, 4, H, DH, jnp.float32, kv),
                shared_tables=kc.empty_share_tables(B, C // 4),
                publish_tables=kc.empty_share_tables(B, C // 4),
            )
        return cache
    if layout == "folded-own":  # one layer's own buffers: the pp stage scan's call
        return kc.decode_kv_layout(kc.kv_buffers(1, B, C, H, DH, jnp.float32, kv)[0])
    if layout == kc.FOLDED:  # the fixed sampler's carry, as handed to layer 1 of 2
        carry = kc.decode_kv_layout(kc.kv_buffers(2, B, C, H, DH, jnp.float32, kv))
        return kc.layer_cache(carry, 1)
    return kc.kv_buffers(1, B, C, H, DH, jnp.float32, kv)[0]


# (layout, storage dtype, shared overlay) -> the attention/decode_path a
# one-token call and a prefill call take ("refused": a folded cache takes
# one position a call and nothing else)
KINDS = [
    ("dense", "bfloat16", False, "generic", "generic"),
    ("dense", "int8", False, "generic", "generic"),
    ("folded", "bfloat16", False, "fused", "refused"),
    ("folded", "int8", False, "fused", "refused"),
    ("folded-own", "bfloat16", False, "fused", "refused"),
    ("folded-own", "int8", False, "fused", "refused"),
    ("paged", "bfloat16", False, "paged", "generic"),
    ("paged", "int8", False, "generic", "generic"),
    ("paged", "bfloat16", True, "generic", "generic"),
    ("paged", "int8", True, "generic", "generic"),
]


def _path_taken(cache, q_len, index):
    import jax.numpy as jnp

    from trlx_tpu.ops.attention import causal_bias, decode_attention
    from trlx_tpu.telemetry import get_metrics

    def counts():
        return {
            p: get_metrics().counter("attention/decode_path{path=%s}" % p).value
            for p in ("fused", "paged", "generic")
        }

    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, q_len, H, DH)), jnp.float32)
        for _ in range(3)
    )
    before = counts()
    try:
        out, new_kv = decode_attention(
            q, k, v, cache, index, causal_bias(q_len, C, offset=index)
        )
    except ValueError:
        return "refused", None
    after = counts()
    took = [p for p in after if after[p] == before[p] + 1]
    assert len(took) == 1 and sum(after.values()) == sum(before.values()) + 1
    assert out.shape == q.shape and np.isfinite(np.asarray(out)).all()
    return took[0], new_kv


@pytest.mark.parametrize("layout,kv,shared,one_token,prefill", KINDS)
def test_cache_kind_decides_the_read(layout, kv, shared, one_token, prefill):
    import jax
    import jax.numpy as jnp

    from trlx_tpu.ops.kv_cache import LAYER, CacheKind, cache_kind, layer_cache

    cache = _cache(layout, kv, shared)
    # the carry says which layer a call is for under a key, and that key
    # (no rank: a dense buffer has four axes too) is what makes it folded
    layer = cache.get(LAYER)
    want = CacheKind(layout.split("-")[0], kv == "int8", shared, layer=layer)
    assert cache_kind(cache) == want and (layer is None) == (layout != "folded")
    paged = layout == "paged"
    at = jnp.full((B,), 5, jnp.int32) if paged else 5
    path, new_kv = _path_taken(cache, 1, at)
    assert path == one_token
    # the written cache is the same kind, key for key and shape for shape
    # (the carry comes back whole, to be handed to the next layer)
    if layer is not None:
        assert LAYER not in new_kv
        new_kv = layer_cache(new_kv, layer)
    assert cache_kind(new_kv) == want
    assert jax.tree_util.tree_map(jnp.shape, new_kv) == jax.tree_util.tree_map(
        jnp.shape, cache
    )
    path, _ = _path_taken(cache, 4, jnp.zeros((B,), jnp.int32) if paged else 0)
    assert path == prefill
