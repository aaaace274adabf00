"""Device mesh management.

Reference parity: this replaces the whole comm-bootstrap layer —
``NCCLCommContext`` ring registry (platform/collective_helper.h:65),
``gen_comm_id_helper.cc`` TCP bootstrap, and ``c_comm_init_op`` — with named
mesh axes over ICI/DCN.  A reference ``ring_id`` maps to a mesh axis name
('dp', 'sharding', 'mp', 'pp', 'sp', 'ep'); XLA inserts the collectives.
"""
from __future__ import annotations

import contextlib
import math
import os
import threading

import numpy as np
import jax
from jax.sharding import Mesh, PartitionSpec, NamedSharding

# canonical hybrid-parallel axis order (outer → inner = DCN → ICI)
AXES = ("dp", "sharding", "pp", "mp", "sp", "ep")

# the axes a data batch's leading dim shards over (dp + ZeRO sharding)
DATA_AXES = ("dp", "sharding")

_global_mesh: Mesh | None = None


def build_mesh(dp=1, sharding=1, pp=1, mp=1, sp=1, ep=1,
               devices=None) -> Mesh:
    """Create a hybrid-parallel mesh.  Any axis left at 1 still exists (size
    1) so sharding specs are uniform across strategies."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    sizes = {"dp": dp, "sharding": sharding, "pp": pp, "mp": mp, "sp": sp,
             "ep": ep}
    used = int(np.prod(list(sizes.values())))
    if used == 1:
        sizes["dp"] = n
        used = n
    elif sizes["dp"] == -1:
        rest = int(np.prod([v for k, v in sizes.items() if k != "dp"]))
        if rest == 0 or n % rest != 0:
            raise ValueError(
                f"cannot fill dp: {n} devices not divisible by {rest}")
        sizes["dp"] = n // rest  # fill remainder into dp
        used = int(np.prod(list(sizes.values())))
    if used != n:
        raise ValueError(
            f"mesh axes {sizes} require {used} devices, have {n}")
    arr = np.asarray(devices).reshape([sizes[a] for a in AXES])
    return Mesh(arr, AXES)


def serving_mesh(mp=1, dp=1, devices=None) -> Mesh:
    """2-D ``(mp, dp)`` mesh for the SERVING engine: the first
    ``mp * dp`` devices on the canonical hybrid axes with only 'mp'
    and 'dp' > 1 — the TP layers' ``PartitionSpec(..., "mp", ...)``
    weights shard over 'mp' (and replicate over 'dp'), while the
    engine shards its batch slots — KV block pools, block tables,
    device cursors — over 'dp'.  Unlike ``build_mesh`` this never
    swallows the whole device pool: a serving replica shards over
    exactly the chips it was given and leaves the rest to sibling
    replicas (the launcher spawns one process per replica, each with
    its own mesh)."""
    mp, dp = int(mp), int(dp)
    if mp < 1 or dp < 1:
        raise ValueError(f"mp and dp must be >= 1, got mp={mp} dp={dp}")
    need = mp * dp
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < need:
        shape = (f"mp={mp}, dp={dp}" if dp > 1 else f"mp={mp}")
        raise ValueError(
            f"serving_mesh({shape}) needs {need} devices, have "
            f"{len(devices)} — on CPU force a virtual pool with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    return build_mesh(mp=mp, dp=dp, devices=devices[:need])


def set_mesh(mesh: Mesh | None):
    global _global_mesh
    _global_mesh = mesh
    return mesh


def get_mesh() -> Mesh | None:
    return _global_mesh


def ensure_mesh() -> Mesh:
    global _global_mesh
    if _global_mesh is None:
        _global_mesh = build_mesh()
    return _global_mesh


_compiling = threading.local()


@contextlib.contextmanager
def compiling_for(mesh: Mesh):
    """Publish ``mesh`` as the one the program traced inside this block
    is compiled for.  A builder that jits over a mesh of its own
    (``TrainStep(mesh=...)``, the per-rank steps) wraps the call that
    traces in it, so that code which must know how many devices the
    program spans while it is traced (``program_devices``) reads the
    builder's mesh and not the process's."""
    prev = getattr(_compiling, "mesh", None)
    _compiling.mesh = mesh
    try:
        yield mesh
    finally:
        _compiling.mesh = prev


def program_devices() -> int:
    """How many devices the program now being traced spans: the mesh
    its builder published (``compiling_for``); where nobody published
    one, the process's mesh, if it has one (a program jitted over
    arrays that ``fleet.init`` or ``init_parallel_env`` placed); else
    one."""
    mesh = getattr(_compiling, "mesh", None) or _global_mesh
    return 1 if mesh is None else int(mesh.size)


def axis_size(name: str) -> int:
    mesh = get_mesh()
    if mesh is None:
        return 1
    return mesh.shape.get(name, 1)


def data_parallel_size() -> int:
    """Combined data-sharding degree (dp × sharding axes)."""
    return axis_size("dp") * axis_size("sharding")


def named_sharding(*spec) -> NamedSharding:
    return NamedSharding(ensure_mesh(), PartitionSpec(*spec))


def replicated() -> NamedSharding:
    return NamedSharding(ensure_mesh(), PartitionSpec())


def data_axes_size(mesh=None) -> int:
    mesh = mesh or ensure_mesh()
    n = 1
    for ax in DATA_AXES:
        n *= mesh.shape.get(ax, 1)
    return n


def batch_partition_spec(shape, mesh=None) -> PartitionSpec:
    """Leading-dim data sharding when divisible, replicated otherwise
    (the single source of the batch-spec policy; TrainStep reuses it)."""
    if shape and shape[0] % data_axes_size(mesh) == 0:
        return PartitionSpec(DATA_AXES, *([None] * (len(shape) - 1)))
    return PartitionSpec()


def host_local_to_global(array, mesh=None, *spec):
    """Assemble per-host local batches into one global array (multi-host:
    each process feeds its shard; reference equivalent is each trainer
    reading its own data partition).  No-op in single-process jobs.

    0-d arrays are replicated (they must be identical on every host).
    A local batch that does not divide evenly across the data axes is an
    error here — unlike single-host, a multi-host partial batch cannot
    silently fall back to replication (each host holds different rows);
    pad or drop_last upstream.
    """
    from ..core.tensor import Tensor
    arr = array._data if isinstance(array, Tensor) else array
    if jax.process_count() == 1:
        return arr
    mesh = mesh or ensure_mesh()
    from jax.experimental import multihost_utils
    arr = np.asarray(arr)
    if not spec:
        if arr.ndim == 0:
            pspec = PartitionSpec()
        else:
            local_per_host = data_axes_size(mesh) // jax.process_count()
            if local_per_host and arr.shape[0] % local_per_host != 0:
                raise ValueError(
                    f"multi-host batch: local leading dim {arr.shape[0]} "
                    f"does not divide across the per-host data-parallel "
                    f"degree {local_per_host}; pad the batch or use "
                    "drop_last=True")
            pspec = PartitionSpec(DATA_AXES,
                                  *([None] * (arr.ndim - 1)))
    else:
        pspec = PartitionSpec(*spec)
    return multihost_utils.host_local_array_to_global_array(
        arr, mesh, pspec)


def global_from_replicated(array, mesh=None, *spec):
    """Build a mesh-sharded global array from a batch every process holds
    IN FULL.  This is the multi-host feeding contract when the data axes
    do not split process-contiguously — e.g. pipeline parallelism whose
    'pp' ring spans hosts, where a single dp row-block lives on several
    processes (Megatron semantics: ranks in one dp group read identical
    data).  Works for any device permutation because each process cuts
    its addressable shards out of the full copy."""
    from ..core.tensor import Tensor
    arr = array._data if isinstance(array, Tensor) else array
    arr = np.asarray(arr)
    mesh = mesh or ensure_mesh()
    if spec:
        pspec = PartitionSpec(*spec)
    else:
        pspec = batch_partition_spec(arr.shape, mesh)
    sharding = jax.sharding.NamedSharding(mesh, pspec)
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])
