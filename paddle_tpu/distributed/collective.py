"""Collective communication API.

Reference parity: ``python/paddle/distributed/collective.py`` (all_reduce /
all_gather / broadcast / reduce / scatter / alltoall / send / recv over NCCL
rings via ``operators/collective/c_*``).

TPU-native design: collectives are **XLA ops on named mesh axes**, not
runtime calls on comm objects.  Inside a parallel region (shard_map over the
mesh — see ``parallel_region``), these functions lower to
psum/all_gather/ppermute/all_to_all on ICI.  Outside any region (plain
eager, world of 1 per process) they are identities — matching the
reference's behavior when world_size == 1.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec

from ..core.tensor import Tensor
from ..core.dispatch import ensure_tensor
from . import mesh as mesh_mod

# ReduceOp parity
class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


_axis_stack: list[str] = []


@contextlib.contextmanager
def axis_context(axis_name: str):
    """Entered by parallel regions so collectives know their axis."""
    _axis_stack.append(axis_name)
    try:
        yield
    finally:
        _axis_stack.pop()


def current_axis() -> str | None:
    return _axis_stack[-1] if _axis_stack else None


def _in_traced_region(x) -> bool:
    return bool(_axis_stack) and isinstance(x, jax.core.Tracer)


def _reduce_fn(op):
    return {"sum": lax.psum, "max": lax.pmax, "min": lax.pmin,
            "avg": lambda v, a: lax.pmean(v, a),
            "prod": lambda v, a: jnp.exp(lax.psum(jnp.log(v), a))}[op]


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """In-place allreduce (reference: c_allreduce_op.h:109)."""
    t = ensure_tensor(tensor)
    if _in_traced_region(t._data):
        axis = group or current_axis()
        t._data = _reduce_fn(op)(t._data, axis)
    # world of 1: identity
    return t


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    t = ensure_tensor(tensor)
    if _in_traced_region(t._data):
        axis = group or current_axis()
        gathered = lax.all_gather(t._data, axis)  # [world, ...]
        n = gathered.shape[0]
        if isinstance(tensor_list, list):
            tensor_list.extend(Tensor(gathered[i]) for i in range(n))
        return tensor_list
    if isinstance(tensor_list, list):
        tensor_list.append(Tensor(t._data))
    return tensor_list


def all_gather_object(obj_list, obj, group=None):
    obj_list.append(obj)
    return obj_list


def broadcast(tensor, src=0, group=None, sync_op=True):
    t = ensure_tensor(tensor)
    if _in_traced_region(t._data):
        axis = current_axis()
        # select src's value on every member of the axis
        gathered = lax.all_gather(t._data, axis)
        t._data = gathered[src]
    return t


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    t = ensure_tensor(tensor)
    if _in_traced_region(t._data):
        axis = current_axis()
        reduced = _reduce_fn(op)(t._data, axis)
        idx = lax.axis_index(axis)
        t._data = jnp.where(idx == dst, reduced, t._data)
    return t


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    t = ensure_tensor(tensor)
    if _in_traced_region(t._data):
        axis = current_axis()
        stacked = jnp.stack([ensure_tensor(x)._data for x in tensor_list])
        src_all = lax.all_gather(stacked, axis)[src]
        idx = lax.axis_index(axis)
        t._data = src_all[idx]
        return t
    if tensor_list:
        t._data = ensure_tensor(tensor_list[src])._data
    return t


def reduce_scatter(output, input_list, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    out = ensure_tensor(output)
    if _in_traced_region(out._data):
        axis = current_axis()
        stacked = jnp.stack([ensure_tensor(x)._data for x in input_list])
        out._data = lax.psum_scatter(stacked, axis, scatter_dimension=0,
                                     tiled=False)
        return out
    out._data = ensure_tensor(input_list[0])._data
    return out


def alltoall(in_tensor_list, out_tensor_list, group=None, sync_op=True):
    arrays = [ensure_tensor(t)._data for t in in_tensor_list]
    if _in_traced_region(arrays[0]):
        axis = current_axis()
        stacked = jnp.stack(arrays)  # [world, ...] per-destination
        exchanged = lax.all_to_all(stacked, axis, split_axis=0,
                                   concat_axis=0, tiled=False)
        for i in range(exchanged.shape[0]):
            out_tensor_list.append(Tensor(exchanged[i]))
        return out_tensor_list
    out_tensor_list.extend(Tensor(a) for a in arrays)
    return out_tensor_list


def send(tensor, dst=0, group=None, sync_op=True):
    raise NotImplementedError(
        "point-to-point send/recv maps to lax.ppermute inside a pipeline "
        "region on TPU; use paddle_tpu.distributed.p2p_shift or the "
        "pipeline engine (reference send_v2/recv_v2 have no eager analogue "
        "over ICI)")


recv = send
isend = send
irecv = send


def p2p_shift(x, axis=None, shift=1):
    """ppermute ring shift — the TPU-native send/recv used by pipeline
    schedules (reference: send_v2/recv_v2 P2P ops)."""
    t = ensure_tensor(x)
    axis = axis or current_axis()
    if not _in_traced_region(t._data):
        return t
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return Tensor(lax.ppermute(t._data, axis, perm))


def barrier(group=None):
    return None  # SPMD programs are globally synchronized by construction


def get_group(ring_id=0):
    return None


# -- convenience: run an SPMD region over the mesh ------------------------
def parallel_region(fn, axis="dp", mesh=None, in_specs=None, out_specs=None):
    """shard_map wrapper that also sets the collective axis context, so the
    paddle-style collective API above works inside `fn`."""
    mesh = mesh or mesh_mod.ensure_mesh()
    in_specs = in_specs if in_specs is not None else PartitionSpec(axis)
    out_specs = out_specs if out_specs is not None else PartitionSpec(axis)

    def wrapped(*arrays):
        with axis_context(axis):
            return fn(*arrays)

    return shard_map(wrapped, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs)
