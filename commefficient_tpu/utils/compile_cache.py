"""Where the persistent XLA compile cache lives.

One rule for every entry point (cv_train.py, gpt2_train.py, chip_smoke.py,
benchmark/harness.py): where JAX_COMPILATION_CACHE_DIR is set, JAX reads it and
nothing is set in code; where it is not, the cache goes to
`<checkout>/.jax_cache` — a fixed path computed from this file's location,
because the path is part of what a later process has to find again. The
tests keep their cache opt-in (tests/conftest.py) and never call this.

The cache's key covers the program's metadata too. JAX leaves it out by
default, and the metadata is where `jax.named_scope` lives: an executable
compiled before a scope was added or renamed would then be read back for the
program that has it, and a profiler capture (obs/profiler.py's summary by
phase) would name the old scopes, or none. The price is a compile wherever a
line of the traced path moved.
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
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _DEFAULT)
    return _DEFAULT
