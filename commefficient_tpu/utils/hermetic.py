"""CPU-backend pinning, shared by the tests and the multichip dry run.

Anything that must run on the host CPU whatever the ambient environment says
(the forced-multi-device test mesh, dryrun_multichip) pins the platform — and
optionally a virtual device count — before any backend initialises.
"""

from __future__ import annotations

import os


def backends_initialized() -> bool:
    """Whether any JAX backend has initialized (too late to join a
    cluster). jax has no public spelling of this; the private touchpoint
    lives here only (checked against jax 0.9.0)."""
    from jax._src import xla_bridge

    return bool(xla_bridge.backends_are_initialized())


def force_hermetic_cpu(n_devices: int | None = None) -> None:
    """Pin this process's JAX to the CPU backend; optionally force an
    n_devices virtual-device mesh (xla_force_host_platform_device_count).

    Must run before the first JAX computation. Safe to call after `import
    jax` as long as no backend has initialised yet (it sets the config
    explicitly, not just the env, because jax may have latched JAX_PLATFORMS
    from the ambient env at import time).
    """
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        # append, don't setdefault: a pre-existing XLA_FLAGS must not
        # silently drop the forced device count
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
