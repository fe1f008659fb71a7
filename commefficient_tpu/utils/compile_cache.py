"""Where the persistent XLA compile cache lives.

One rule for every entry point (cv_train.py, gpt2_train.py, bench.py,
chip_smoke.py): where JAX_COMPILATION_CACHE_DIR is set, JAX reads it and
nothing is set in code; where it is not, the cache goes to
`<checkout>/.jax_cache` — a fixed path computed from this file's location,
because the path is part of what a later process has to find again. The
tests keep their cache opt-in (tests/conftest.py) and never call this.
"""

from __future__ import annotations

import os

_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def ensure_compile_cache() -> str:
    """Place the compile cache (see module doc) and return its directory.
    Call before the first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", _DEFAULT)
    return _DEFAULT
