"""Eager collectives API (ref: python/paddle/distributed/communication/*.py,
ProcessGroup C++ runtime paddle/fluid/distributed/collective/process_group.h:53).

TPU-native design (SURVEY §5.8): there is ONE backend — XLA collectives.
Inside pjit/shard_map programs, collectives are psum/all_gather/ppermute and
never touch this module. This eager API exists for host-driven parity
(paddle.distributed.all_reduce(t) style code): it executes the collective
over a named axis of the ACTIVE GLOBAL MESH via shard_map when the tensor is
sharded there, and degrades to the mathematical identity (world=1) otherwise.
Cross-process eager collectives go through jax's global-array path the same
way.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor, to_array

# ---------------------------------------------------------------------------
# Group: on TPU a "process group" is a mesh-axis handle.
# ---------------------------------------------------------------------------

_global_mesh: Optional[jax.sharding.Mesh] = None
_groups: dict = {}
_next_group_id = 0


def set_global_mesh(mesh) -> None:
    global _global_mesh
    _global_mesh = mesh


def get_global_mesh():
    return _global_mesh


@dataclasses.dataclass
class Group:
    """Ref process_group.h:53 ProcessGroup — reduced to (axis, rank, nranks).

    axis=None means the trivial single-member group.
    """

    axis: Optional[str] = None
    nranks: int = 1
    rank: int = 0
    id: int = 0
    ranks: Optional[List[int]] = None

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, rank):
        if self.ranks is None:
            return rank
        return self.ranks.index(rank) if rank in self.ranks else -1

    @property
    def process_group(self):
        return self


ProcessGroup = Group  # reference name (ref process_group.h:53)


class _Task:
    """Async completion handle (ref process_group.h Task :55-88). XLA calls
    are async by default; wait() blocks on the result buffer."""

    def __init__(self, result=None):
        self._result = result

    def wait(self):
        if self._result is not None:
            jax.block_until_ready(self._result)
        return True

    def is_completed(self):
        return True

    def synchronize(self):
        self.wait()


def new_group(ranks=None, backend=None, timeout=None, axis=None):
    """Ref collective.py:185 new_group. On TPU, groups over explicit rank
    lists are only used by the launch/bootstrap layer; compute-path groups
    are mesh axes."""
    global _next_group_id
    _next_group_id += 1
    from .env import get_rank

    nranks = len(ranks) if ranks else 1
    r = get_rank()
    grp_rank = ranks.index(r) if ranks and r in ranks else 0
    g = Group(axis=axis, nranks=nranks, rank=grp_rank, id=_next_group_id, ranks=ranks)
    _groups[_next_group_id] = g
    return g


def get_group(gid: int) -> Optional[Group]:
    return _groups.get(gid)


def _axis_size(axis: str) -> int:
    if _global_mesh is None or axis is None:
        return 1
    return int(_global_mesh.shape[axis]) if axis in _global_mesh.shape else 1


# ---------------------------------------------------------------------------
# ReduceOp
# ---------------------------------------------------------------------------


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


def _reduce_fn(op):
    return {ReduceOp.SUM: jax.lax.psum, ReduceOp.MAX: jax.lax.pmax,
            ReduceOp.MIN: jax.lax.pmin,
            ReduceOp.PROD: lambda x, n: jnp.exp(jax.lax.psum(jnp.log(x), n)),
            ReduceOp.AVG: jax.lax.pmean}[op]


def _run_on_axis(x, axis: str, per_shard_fn, out_specs_fn=None):
    """Execute per-shard collective body via shard_map over `axis` of the
    global mesh; x must be sharded over that axis (or replicated)."""
    mesh = _global_mesh
    in_spec = _infer_spec(x, axis)
    out_spec = out_specs_fn(in_spec) if out_specs_fn else in_spec
    fn = jax.shard_map(per_shard_fn, mesh=mesh, in_specs=(in_spec,),
                       out_specs=out_spec, check_vma=False)
    return fn(x)


def _infer_spec(x, axis):
    from jax.sharding import PartitionSpec as P

    try:
        sh = x.sharding
        if hasattr(sh, "spec"):
            return sh.spec
    except Exception:
        pass
    return P()  # replicated


# ---------------------------------------------------------------------------
# Public collectives (eager host API)
# ---------------------------------------------------------------------------


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    axis = group.axis if group is not None else "data"
    n = _axis_size(axis)
    if n <= 1:
        return _Task(tensor.value if isinstance(tensor, Tensor) else tensor)
    val = to_array(tensor)
    red = _reduce_fn(op)
    out = _run_on_axis(val, axis, lambda v: red(v, axis))
    if isinstance(tensor, Tensor):
        tensor._value = out
    return _Task(out)


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    axis = group.axis if group is not None else "data"
    n = _axis_size(axis)
    val = to_array(tensor)
    if n <= 1:
        tensor_list.append(Tensor(val))
        return _Task(val)
    out = _run_on_axis(
        val, axis, lambda v: jax.lax.all_gather(v, axis),
        out_specs_fn=lambda s: s)
    # out has leading axis n per shard; split into list
    for i in range(n):
        tensor_list.append(Tensor(out[i]))
    return _Task(out)


def all_gather_object(object_list, obj, group=None):
    object_list.append(obj)
    return _Task()


def reduce(tensor, dst, op=ReduceOp.SUM, group=None, sync_op=True):
    # On TPU all-reduce then discard is the same cost pattern under XLA.
    return all_reduce(tensor, op, group, sync_op)


def broadcast(tensor, src, group=None, sync_op=True):
    # Replicated arrays are already consistent; cross-process broadcast uses
    # process 0's value via jax multihost utils when world>1.
    from .env import get_world_size

    if get_world_size() > 1:
        try:
            from jax.experimental import multihost_utils

            val = multihost_utils.broadcast_one_to_all(to_array(tensor))
            if isinstance(tensor, Tensor):
                tensor._value = val
            return _Task(val)
        except Exception:
            pass
    return _Task(to_array(tensor))


def broadcast_object_list(object_list, src=0, group=None):
    return _Task()


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    if tensor_list:
        from .env import get_rank

        idx = group.rank if group is not None else 0
        val = to_array(tensor_list[idx])
        if isinstance(tensor, Tensor):
            tensor._value = val
        return _Task(val)
    return _Task(to_array(tensor))


def reduce_scatter(tensor, tensor_list, op=ReduceOp.SUM, group=None, sync_op=True):
    axis = group.axis if group is not None else "data"
    n = _axis_size(axis)
    if n <= 1:
        red = to_array(tensor_list[0])
        for t in tensor_list[1:]:
            red = red + to_array(t)
        if isinstance(tensor, Tensor):
            tensor._value = red
        return _Task(red)
    stacked = jnp.stack([to_array(t) for t in tensor_list])
    out = _run_on_axis(
        stacked, axis,
        lambda v: jax.lax.psum_scatter(v, axis, scatter_dimension=0, tiled=False))
    if isinstance(tensor, Tensor):
        tensor._value = out
    return _Task(out)


def all_to_all(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    axis = group.axis if group is not None else "data"
    n = _axis_size(axis)
    if n <= 1:
        out_tensor_list.extend(Tensor(to_array(t)) for t in in_tensor_list)
        return _Task()
    stacked = jnp.stack([to_array(t) for t in in_tensor_list])
    out = _run_on_axis(
        stacked, axis,
        lambda v: jax.lax.all_to_all(v, axis, split_axis=0, concat_axis=0, tiled=False))
    for i in range(out.shape[0]):
        out_tensor_list.append(Tensor(out[i]))
    return _Task(out)


alltoall = all_to_all


def send(tensor, dst=0, group=None, sync_op=True):
    raise NotImplementedError(
        "Host-driven p2p send/recv is not a TPU primitive; pipeline-parallel "
        "communication uses ppermute inside compiled programs "
        "(paddle_tpu.distributed.fleet.meta_parallel.pipeline).")


def recv(tensor, src=0, group=None, sync_op=True):
    raise NotImplementedError(
        "Host-driven p2p send/recv is not a TPU primitive; see pipeline parallel.")


isend = send
irecv = recv


def barrier(group=None):
    try:
        from jax.experimental import multihost_utils

        from .env import get_world_size

        if get_world_size() > 1:
            multihost_utils.sync_global_devices("paddle_tpu_barrier")
    except Exception:
        pass
    return _Task()


def wait(tensor, group=None, use_calc_stream=True):
    jax.block_until_ready(to_array(tensor))


def destroy_process_group(group=None):
    global _groups
    if group is None:
        _groups = {}
    else:
        _groups.pop(group.id, None)


def get_backend(group=None) -> str:
    return "xla"


def alltoall_single(in_tensor, out_tensor=None, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    """Single-tensor all_to_all (ref communication/all_to_all.py
    alltoall_single): the first dim is split across the group instead of
    passing explicit tensor lists."""
    axis = group.axis if group is not None else "data"
    n = _axis_size(axis)
    v = to_array(in_tensor)
    for sizes in (in_split_sizes, out_split_sizes):
        if sizes is not None and len(set(sizes)) > 1:
            raise NotImplementedError(
                "alltoall_single: unequal split sizes are not supported by "
                "the XLA all_to_all lowering — pad to equal splits")
    # the collective is meaningful when the input is sharded over the group
    # axis (global chunk-ownership transpose); an eagerly replicated array
    # is this process's own tensor — exchanged with itself (identity), the
    # same world-per-process view the other eager collectives take
    spec = tuple(getattr(getattr(v, "sharding", None), "spec", ()) or ())
    if n > 1 and axis not in spec:
        n = 1
    if n <= 1:
        if out_tensor is not None and isinstance(out_tensor, Tensor):
            out_tensor._value = v
            return _Task(v)
        return Tensor(v)
    out = _run_on_axis(
        v, axis,
        lambda x: jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                     tiled=True))
    if out_tensor is not None and isinstance(out_tensor, Tensor):
        out_tensor._value = out
        return _Task(out)
    return Tensor(out)


def scatter_object_list(out_object_list, in_object_list=None, src=0,
                        group=None):
    """Scatter picklable python objects (ref communication/scatter.py
    scatter_object_list): rank i receives in_object_list[i] from src."""
    idx = group.rank if group is not None else 0
    out_object_list.clear()
    if in_object_list:
        out_object_list.append(in_object_list[idx])
    return None


def is_available() -> bool:
    """Whether the distributed package is usable (ref parallel.py
    is_available) — always True here: the XLA-collectives backend is
    compiled in."""
    return True


class ParallelMode:
    """Parallelism kinds (ref fleet/base/topology.py:28)."""

    DATA_PARALLEL = 0
    TENSOR_PARALLEL = 1
    PIPELINE_PARALLEL = 2
    SHARDING_PARALLEL = 3


def split(x, size, operation, axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """Model-parallel linear/embedding over the tensor axis (ref
    fleet/layers/mpu/mp_ops.py split:653): builds the corresponding
    parallel layer (weights GSPMD-sharded over "tensor") and returns its
    output on ``x``.  axis=1 on a linear splits the out-features
    (column-parallel); axis=0 splits in-features (row-parallel)."""
    from .fleet.meta_parallel.mp_layers import (ColumnParallelLinear,
                                               RowParallelLinear,
                                               VocabParallelEmbedding)

    if operation == "embedding":
        layer = VocabParallelEmbedding(size[0], size[1],
                                       weight_attr=weight_attr)
        return layer(x)
    if operation == "linear":
        if axis == 1:
            layer = ColumnParallelLinear(size[0], size[1],
                                         weight_attr=weight_attr,
                                         has_bias=bias_attr is not False,
                                         gather_output=gather_out)
        else:
            layer = RowParallelLinear(size[0], size[1],
                                      weight_attr=weight_attr,
                                      has_bias=bias_attr is not False,
                                      input_is_parallel=False)
        return layer(x)
    raise ValueError(f"unknown operation {operation!r} (linear|embedding)")
