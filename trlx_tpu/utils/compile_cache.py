"""Where JAX's persistent compilation cache lives.

gpt2-small's sampler, train step, fused phase, engine and server programs
take minutes to compile from nothing, and every process that builds them
pays again unless the compiled executables are kept on disk. The cache
directory is part of nothing the program decides: an operator (or the
machine image) places it with ``JAX_COMPILATION_CACHE_DIR``, which jax
reads itself. Only when that is unset does the program pick a directory —
a fixed one inside the checkout, because the path must be the same in
every process for an entry written by one to be found by the next.

Called from the entry points that build jitted programs (``api.train``,
``InferenceServer``, ``chip_smoke.py``; ``benchmark/harness.py`` places its
own the same way); this is the only
place ``jax_compilation_cache_dir`` is set.
"""

from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Make sure a persistent compile cache directory is configured and
    return it. An exported ``JAX_COMPILATION_CACHE_DIR`` is left alone."""
    placed = os.environ.get(CACHE_DIR_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
