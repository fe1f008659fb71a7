"""Multi-host bootstrap — the rebuild's answer to "an NCCL/MPI backend that
scales to multi-host" (build brief; the reference itself is single-host
torch.multiprocessing — SURVEY.md §2 "Distributed comm backend", so this is
rebuild-side scale headroom, not a parity item).

On JAX the entire "backend" is: every host process calls
`jax.distributed.initialize` (on TPU pods the coordinator/process count/
process id all auto-detect from the TPU metadata environment), after which
`jax.devices()` spans the whole pod and the SAME single-process program —
`parallel.mesh.make_mesh` shardings, XLA collectives over ICI/DCN — runs
SPMD across hosts. No queues, no sends: the engine code is untouched.

    from commefficient_tpu.parallel import distributed, mesh
    distributed.initialize()          # no-op off-pod / single process
    m = mesh.make_mesh(num_slices=jax.device_count() // 8 // ...)

Both CLIs call `initialize()` up front (--multihost forces it; the default
auto mode only initializes when a multi-host environment is detected, so
laptops/CI never touch the distributed runtime)."""

from __future__ import annotations

import os

_INITIALIZED = False

# explicit-coordinator markers: any of these means a launcher configured a
# cluster and jax.distributed.initialize() can auto-configure from them
_COORDINATOR_ENV_VARS = (
    "JAX_COORDINATOR_ADDRESS",
    "COORDINATOR_ADDRESS",
    "MEGASCALE_COORDINATOR_ADDRESS",
)


def detected() -> bool:
    """Whether the process environment looks like one host of a MULTI-host
    launch. An explicit coordinator address counts; TPU_WORKER_HOSTNAMES
    counts only when it lists 2+ hosts — single-host TPU VMs set it with one
    entry, and initializing the distributed service there is pointless
    env-marker noise."""
    if any(os.environ.get(v) for v in _COORDINATOR_ENV_VARS):
        return True
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hosts.split(",") if h.strip()]) >= 2


def initialize(force: bool = False, fault_plan=None, retry_policy=None,
               **kwargs) -> bool:
    """Join the multi-host cluster (idempotent). Returns True if the
    distributed runtime is (now) initialized.

    - auto mode (force=False): initialize only when `detected()`, and any
      failure (backend already up, incomplete metadata) degrades to a
      warned single-host run — auto mode must never kill a job that would
      have run fine on one host.
    - force=True: initialize unconditionally and propagate failures
      (kwargs pass through to `jax.distributed.initialize`, e.g.
      coordinator_address/num_processes/process_id for non-TPU clusters
      where auto-detection has nothing to read).

    The join itself runs under bounded retries (resilience/retry, site
    "dist_init"): the common real-world failure is the coordinator not
    listening YET — pod hosts come up in arbitrary order — which a short
    backoff rides out where the old single attempt killed the job.
    `fault_plan` injects scheduled transient failures at the same site so
    tests exercise exactly this path.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return True
    if not (force or detected()):
        return False
    import sys

    import jax

    from ..resilience import retry as rtry
    from ..utils.hermetic import backends_initialized

    if backends_initialized():
        # too late to join a cluster; a forced request is a caller bug
        msg = ("distributed.initialize called after the JAX backend "
               "initialized; running single-host")
        if force:
            raise RuntimeError(msg)
        print(f"warning: {msg}", file=sys.stderr, flush=True)
        return False

    def join():
        if fault_plan is not None:
            fault_plan.fire_transient("dist_init")
        try:
            jax.distributed.initialize(**kwargs)
        except Exception:
            # a failed connect leaves jax's global client assigned, and every
            # later initialize() then raises "should only be called once" —
            # the retry would mask the real connectivity error and could
            # never succeed. Tear the half-initialized state down so the
            # next attempt is a genuine one.
            try:
                jax.distributed.shutdown()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
            raise

    try:
        rtry.with_retries(join, site="dist_init", policy=retry_policy)
    except Exception as e:  # noqa: BLE001 — auto mode degrades, forced raises
        if force:
            raise
        print(f"warning: multi-host auto-init failed ({type(e).__name__}: {e}); "
              "running single-host", file=sys.stderr, flush=True)
        return False
    _INITIALIZED = True
    return True


def initialize_from_args(args, fault_plan=None, retry_policy=None) -> bool:
    """CLI adapter: explicit cluster flags imply force (a user who typed a
    coordinator address wants a cluster — silently training single-host on
    each node would be the worst failure mode)."""
    cluster_kw = {
        k: v for k, v in (("coordinator_address", args.coordinator_address),
                          ("num_processes", args.num_processes),
                          ("process_id", args.process_id)) if v is not None
    }
    return initialize(force=args.multihost or bool(cluster_kw),
                      fault_plan=fault_plan, retry_policy=retry_policy,
                      **cluster_kw)


def all_hosts_max(value: int) -> int:
    """Max-reduce a small host-local integer over every process in the job —
    the agreement primitive behind multi-host coordinated preemption (the
    SIGTERM flag must become "any host was signalled" before anyone acts on
    it). Implemented as a process_allgather over the host axis (the
    `slices`/process dimension of the job): one int32 per host per call,
    negligible next to a round. Single-process returns the value unchanged
    without touching any collective, so laptops/CI never pay for it."""
    import jax

    if jax.process_count() == 1:
        return int(value)
    import numpy as np
    from jax.experimental import multihost_utils

    flags = multihost_utils.process_allgather(np.int32(value))
    return int(np.max(np.asarray(flags)))


def mesh_info(mesh) -> dict:
    """Mesh-level topology summary for startup logs: which axes the round
    shards over, how many ways the client cohort splits (= the devices the
    federated round scales across), and whether the once-per-round partial-
    wire merge crosses DCN (multi-slice) or stays on ICI. The CLIs print
    this next to the model line so a pod job that silently fell back to one
    device is visible in the first screen of output."""
    from . import mesh as meshlib

    return {
        "axes": dict(mesh.shape),
        "client_shards": meshlib.client_shards(mesh),
        "merge_crosses_dcn": meshlib.DCN_AXIS in mesh.axis_names,
    }


def process_info() -> dict:
    """Host-level topology summary for logs: which process this is, how many
    there are, and the local/global device split."""
    import jax

    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": jax.device_count(),
    }
