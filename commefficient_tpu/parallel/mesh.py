"""Device-mesh helpers — the TPU-native "communication backend".

The reference's transport is torch.multiprocessing queues + shared-memory
tensors on a single host (SURVEY.md §1, §2 "Distributed comm backend").  Here
there is no transport layer at all: sampled clients are a sharded batch axis
on a `jax.sharding.Mesh`, cross-client reductions are XLA collectives over
ICI (DCN at multi-slice scale), and weight "broadcast" is replicated-array
residency.  These helpers name the axes and build the shardings the round
engine uses.

Multi-slice (pod-scale) topology — BASELINE config #5 / SURVEY.md §7.7: a
`num_slices > 1` mesh adds a leading DCN axis.  Devices are grouped by their
`slice_index` (falling back to contiguous chunks on hosts that don't expose
one, e.g. the forced-CPU test mesh), so the model axis and the intra-slice
client axis always ride ICI while only the once-per-round client reduction
crosses DCN: sharding the sampled-client batch axis over
(DCN_AXIS, CLIENT_AXIS) makes XLA lower the client mean to an in-slice
reduce (ICI) followed by a cross-slice all-reduce of one [r, c] table or [d]
vector per round — exactly the traffic a parameter server would ship, with
no code beyond the sharding annotation.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DCN_AXIS = "slices"  # data-parallel axis across pod slices (DCN traffic)
CLIENT_AXIS = "clients"  # data-parallel axis over sampled virtual clients
SEQ_AXIS = "seq"  # sequence-parallel axis (ring attention, optional)
MODEL_AXIS = "model"  # tensor-parallel axis (GPT-2 path, optional)


def _group_by_slice(devs: np.ndarray, num_slices: int) -> np.ndarray:
    """[num_slices, per_slice] device grid, honoring hardware slice_index
    when the platform exposes it (TPU multi-slice), contiguous otherwise."""
    n = len(devs)
    if n % num_slices:
        raise ValueError(f"{n} devices not divisible by num_slices={num_slices}")
    per_slice = n // num_slices
    slice_ids = {getattr(d, "slice_index", None) for d in devs.flat}
    if None not in slice_ids and len(slice_ids) != num_slices:
        # real multi-slice hardware disagreeing with the requested layout:
        # a contiguous reshape would route "ICI" axes over DCN — say so
        print(
            f"warning: hardware reports {len(slice_ids)} slices but "
            f"num_slices={num_slices}; contiguous device grouping may place "
            "intra-slice mesh axes across DCN",
            flush=True,
        )
    if None not in slice_ids and len(slice_ids) == num_slices:
        rows = []
        for s in sorted(slice_ids):
            row = [d for d in devs.flat if d.slice_index == s]
            if len(row) != per_slice:
                raise ValueError(
                    f"slice {s} has {len(row)} devices, expected {per_slice}"
                )
            rows.append(row)
        return np.asarray(rows)
    return devs.reshape(num_slices, per_slice)


def make_mesh(
    num_devices: int | None = None,
    model_parallel: int = 1,
    num_slices: int = 1,
    seq_parallel: int = 1,
) -> Mesh:
    """Client mesh, axes outermost-to-innermost (slices, clients, seq, model)
    — axes of size 1 are omitted.  The innermost axes carry the
    latency-sensitive collectives (TP all-reduces, ring-attention ppermute)
    over ICI; only the once-per-round client reduction ever crosses DCN."""
    devs = jax.devices()
    n = len(devs) if num_devices is None else num_devices
    devs = np.asarray(devs[:n])
    inner = model_parallel * seq_parallel
    if n % (num_slices * inner):
        raise ValueError(
            f"{n} devices not divisible by num_slices={num_slices} x "
            f"seq_parallel={seq_parallel} x model_parallel={model_parallel}"
        )
    dims = []
    if num_slices > 1:
        devs = _group_by_slice(devs, num_slices)
        dims.append((DCN_AXIS, num_slices))
    dims.append((CLIENT_AXIS, n // (num_slices * inner)))
    if seq_parallel > 1:
        dims.append((SEQ_AXIS, seq_parallel))
    if model_parallel > 1:
        dims.append((MODEL_AXIS, model_parallel))
    return Mesh(
        devs.reshape([s for _, s in dims]), tuple(a for a, _ in dims)
    )


def client_axes(mesh: Mesh) -> tuple[str, ...] | str:
    """Mesh axes the sampled-client batch dimension shards over: the client
    axis, plus the DCN slice axis on hybrid meshes."""
    if DCN_AXIS in mesh.axis_names:
        return (DCN_AXIS, CLIENT_AXIS)
    return CLIENT_AXIS


def client_axis_names(mesh: Mesh) -> tuple[str, ...]:
    """`client_axes` normalized to a tuple — the form collectives
    (all_gather / axis_index) take inside the engine's sharded round."""
    axes = client_axes(mesh)
    return (axes,) if isinstance(axes, str) else tuple(axes)


def parse_mesh_spec(spec: str) -> dict:
    """Parse the CLI `--mesh clients=N[,slices=M]` spec into make_mesh-style
    sizes. Returns {"clients": N, "slices": M} (slices defaults to 1).
    Validation is loud: a typo'd axis silently training single-device is the
    failure mode the flag exists to prevent."""
    out = {"clients": 0, "slices": 1}
    seen: set[str] = set()
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"bad --mesh entry {part!r}: expected axis=size "
                "(e.g. clients=8 or clients=4,slices=2)"
            )
        axis, _, size = part.partition("=")
        axis = axis.strip()
        if axis not in ("clients", "slices"):
            raise ValueError(
                f"unknown --mesh axis {axis!r}: the round shards over "
                "'clients' (ICI) and 'slices' (DCN); model/seq parallelism "
                "keep their dedicated flags"
            )
        if axis in seen:
            # a duplicate is almost always a typo for the OTHER axis;
            # last-one-wins would train a silently different topology
            raise ValueError(f"--mesh sets axis {axis!r} twice: {spec!r}")
        seen.add(axis)
        try:
            out[axis] = int(size)
        except ValueError:
            raise ValueError(f"bad --mesh size {size!r} for axis {axis!r}")
        if out[axis] <= 0:
            raise ValueError(f"--mesh {axis} must be positive, got {out[axis]}")
    if out["clients"] <= 0:
        raise ValueError("--mesh must set clients=N (e.g. clients=8)")
    return out


def make_mesh_from_spec(
    spec: str, model_parallel: int = 1, seq_parallel: int = 1
) -> Mesh:
    """Build the mesh a `--mesh clients=N[,slices=M]` spec asks for, erroring
    (not degrading) when the host doesn't expose enough devices — an operator
    who typed a topology wants that topology or a loud failure."""
    import jax

    sizes = parse_mesh_spec(spec)
    need = sizes["clients"] * sizes["slices"] * model_parallel * seq_parallel
    have = len(jax.devices())
    if need > have:
        raise ValueError(
            f"--mesh {spec!r} (x model_parallel={model_parallel} x "
            f"seq_parallel={seq_parallel}) needs {need} devices; only {have} "
            "visible"
        )
    return make_mesh(
        need, model_parallel=model_parallel, num_slices=sizes["slices"],
        seq_parallel=seq_parallel,
    )


def merge_comm_bytes(n_shards: int, r: int, c: int, d: int) -> dict:
    """Analytic per-round cross-device traffic of the sharded round's merge,
    per device: the sketch-table merge (what the engine ships) vs the dense
    [d] all-reduce a gradient-synchronous data-parallel round would ship —
    the comm-efficiency headline of the README's multi-chip section.

    allgather = (S-1) tables received per device (the deterministic ordered
    merge the engine uses); psum = 2(S-1)/S tables (the classic ring
    all-reduce lower bound, for comparison); dense_allreduce = the same ring
    bound on [d] floats."""
    table = r * c * 4
    dense = d * 4
    s = max(n_shards, 1)
    ring = 2 * (s - 1) / s
    return {
        "sketch_table_mb": table / 1e6,
        "sketch_allgather_mb_per_device": (s - 1) * table / 1e6,
        "sketch_psum_mb_per_device": ring * table / 1e6,
        "dense_allreduce_mb_per_device": ring * dense / 1e6,
        "dense_over_sketch_ratio": d / (r * c),
    }


def client_shards(mesh: Mesh) -> int:
    """Total ways the client batch axis splits (must divide num_workers)."""
    n = mesh.shape[CLIENT_AXIS]
    if DCN_AXIS in mesh.axis_names:
        n *= mesh.shape[DCN_AXIS]
    return n


def client_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (sampled-client) axis over the client mesh axes."""
    return NamedSharding(mesh, P(client_axes(mesh)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_client_batch(mesh: Mesh, tree):
    """Place every array in `tree` with its leading [W] axis sharded over the
    client mesh axes (weights/params stay replicated — see `replicated`)."""
    return jax.device_put(tree, client_sharding(mesh))


def shard_stacked_client_batch(mesh: Mesh, tree):
    """Multi-round variant: leaves are [K, W, ...] (K stacked rounds); the
    round axis stays replicated and the client axis (axis 1) shards."""
    return jax.device_put(tree, NamedSharding(mesh, P(None, client_axes(mesh))))
