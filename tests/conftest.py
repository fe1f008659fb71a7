"""Test config: force an 8-device CPU mesh so multi-device sharding paths run
without TPU hardware (SURVEY.md §4 "Distributed without a cluster"). The
platform pin lives in commefficient_tpu.utils.hermetic, shared with
__graft_entry__."""

import os

from commefficient_tpu.utils.hermetic import force_hermetic_cpu

force_hermetic_cpu(8)


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# Persistent XLA compile cache: OPT-IN only. A repo-local default cache
# sounded right for this compile-bound suite, but on this box executables
# RELOADED from the disk cache are broken — the same jitted step that
# passes cold returns all-NaN params or segfaults the interpreter when a
# second process deserializes the cached executable (reproduced on
# tests/test_checkpoint.py: cold run passes, warm-cache rerun dies). That
# single poisoned default took the whole tier-1 suite from 184 passing to
# 0 (the segfault kills pytest mid-run). Export JAX_COMPILATION_CACHE_DIR
# explicitly if your jaxlib's cache round-trips correctly.
import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    # the env var alone is latched by jax._src.config at ITS import time,
    # which may be before conftest runs — in-process tests need the explicit
    # update; subprocess CLI tests inherit the env var
    jax.config.update(
        "jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"]
    )


def hermetic_subprocess_env() -> dict:
    """Env for SUBPROCESS tests: pin the 8-device CPU mesh (also used by
    test_distributed / test_determinism; in-process tests are already pinned
    via force_hermetic_cpu above)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        # the CLIs default their compile cache to <checkout>/.jax_cache
        # (utils/compile_cache.py); the tests' stays opt-in, as above
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return env
