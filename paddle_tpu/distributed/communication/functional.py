"""Functional collectives (reference: python/paddle/distributed/communication/*).

Two execution regimes, one API:

1. **Traced under shard_map/pjit** (how fleet engines run): ops lower to XLA
   collective HLOs over ICI — ``lax.psum`` / ``all_gather`` / ``psum_scatter`` /
   ``ppermute`` / ``all_to_all`` with the group's mesh-axis name. This replaces the
   reference's NCCLCommContext (phi/core/distributed/nccl_comm_context.h:40).

2. **Eager, single-controller SPMD**: a jax.Array is already the *global* logical
   tensor, so rank-local collective semantics degenerate: tensors are replicated
   across the group and the ops compute the equivalent replicated result
   (e.g. all_reduce(SUM) == x * nranks). This mirrors how the reference's tests use
   collectives on identical inputs, and keeps user code portable.

3. **Eager, multi-process** (after a multi-host ``init_parallel_env``): each
   controller holds genuinely different data, so ``all_reduce`` builds a global
   array with one shard per process and runs a jitted cross-process psum over
   the coordination-service-backed mesh — true per-rank semantics, matching the
   reference's per-rank collective tests (test_collective_api_base.py).
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ...core.tensor import Tensor, unwrap, wrap
from .group import Group, ReduceOp, get_default_group


def _axis_bound(axis_name) -> bool:
    """True iff axis_name is bound in the current trace (inside shard_map/pmap)."""
    try:
        lax.axis_index(axis_name)
        return True
    except NameError:
        return False
    except Exception:
        return False


def _group(group) -> Group:
    return group if group is not None else get_default_group()


def _task():
    class _Done:
        def wait(self):
            return None

        def is_completed(self):
            return True

    return _Done()


_mp_reduce_cache: dict = {}


def _mp_all_reduce(x, op, ranks):
    """True cross-process eager all-reduce over the processes in ``ranks``
    (rank == process_index, the init_parallel_env contract): one shard per
    member process on a mesh of exactly the group's devices; the reduce is a
    jitted psum. Only member processes execute the computation — jax
    multi-controller permits submesh computations as long as every process
    owning a shard calls in (same contract as a NCCL subgroup). Compiled fns
    are cached per (op, ranks, shape, dtype) — re-jitting each call would
    recompile every time."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    key = (str(op), tuple(ranks), tuple(x.shape), str(x.dtype))
    entry = _mp_reduce_cache.get(key)
    if entry is None:
        by_proc = {}
        for d in jax.devices():
            by_proc.setdefault(d.process_index, d)
        devs = np.array([by_proc[p] for p in ranks])
        mesh = Mesh(devs, ("r",))

        def body(a):
            v = a[0]
            if op == ReduceOp.SUM:
                r = lax.psum(v, "r")
            elif op == ReduceOp.MAX:
                r = lax.pmax(v, "r")
            elif op == ReduceOp.MIN:
                r = lax.pmin(v, "r")
            elif op == ReduceOp.AVG:
                r = lax.pmean(v, "r")
            else:
                r = jnp.exp(lax.psum(jnp.log(v), "r"))
            return r[None]

        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("r"),
                                   out_specs=P("r")))
        entry = (fn, mesh, by_proc[jax.process_index()], len(devs))
        _mp_reduce_cache[key] = entry
    fn, mesh, mine, n = entry
    shard = jax.device_put(x[None], mine)
    arr = jax.make_array_from_single_device_arrays(
        (n,) + x.shape, NamedSharding(mesh, P("r")), [shard])
    return fn(arr).addressable_shards[0].data[0]


def all_reduce(tensor: Tensor, op=ReduceOp.SUM, group=None, sync_op=True, use_calc_stream=False):
    g = _group(group)
    x = unwrap(tensor)
    if _axis_bound(g.axis_name):
        if op == ReduceOp.SUM:
            out = lax.psum(x, g.axis_name)
        elif op == ReduceOp.MAX:
            out = lax.pmax(x, g.axis_name)
        elif op == ReduceOp.MIN:
            out = lax.pmin(x, g.axis_name)
        elif op == ReduceOp.AVG:
            out = lax.pmean(x, g.axis_name)
        else:
            out = jnp.exp(lax.psum(jnp.log(x), g.axis_name))
    elif (jax.process_count() > 1
          and not isinstance(x, jax.core.Tracer)):
        # true cross-process semantics: the group's rank list (rank ==
        # process_index) becomes a submesh of one device per member process.
        # EVERY member (and only members) must call in — the same collective
        # contract as a NCCL subgroup (reference
        # test_collective_api_base.py); a non-member calling is a clear
        # error rather than a silent over-reduce or a hang.
        ranks = sorted(g.ranks)
        # device-granular classification FIRST: a group over device ids (not
        # process ranks) must get the shard_map guidance, not a misleading
        # membership error no process could ever satisfy
        if ranks and ranks[-1] >= jax.process_count():
            if ranks == list(range(jax.device_count())):
                ranks = sorted(range(jax.process_count()))  # device-world grp
            else:
                raise NotImplementedError(
                    f"eager multi-process all_reduce: group ranks {ranks} "
                    "exceed the process count — device-granular subgroups "
                    "run inside shard_map over the group's mesh axis")
        if jax.process_index() not in ranks:
            raise RuntimeError(
                f"process {jax.process_index()} is not a member of {g} — "
                "only (and all of) the group's member processes may call "
                "all_reduce(group=g)")
        out = _mp_all_reduce(x, op, ranks)
    else:
        n = g.nranks
        if op == ReduceOp.SUM:
            out = x * n
        elif op == ReduceOp.AVG or op in (ReduceOp.MAX, ReduceOp.MIN):
            out = x
        else:
            out = x**n
    if isinstance(tensor, Tensor):
        tensor._replace_(out, None, 0)
        return _task()
    return out


def all_gather(tensor_list: Optional[List], tensor: Tensor, group=None, sync_op=True, axis=0):
    g = _group(group)
    x = unwrap(tensor)
    if _axis_bound(g.axis_name):
        out = lax.all_gather(x, g.axis_name, axis=axis, tiled=False)
        parts = [out[i] for i in range(g.nranks)] if axis == 0 else list(jnp.moveaxis(out, axis, 0))
    else:
        parts = [x for _ in range(g.nranks)]
    if tensor_list is not None:
        tensor_list.clear()
        tensor_list.extend(wrap(p) for p in parts)
        return _task()
    return [wrap(p) for p in parts]


def all_gather_into_tensor(out_tensor, tensor, group=None, sync_op=True):
    g = _group(group)
    x = unwrap(tensor)
    if _axis_bound(g.axis_name):
        out = lax.all_gather(x, g.axis_name, axis=0, tiled=True)
    else:
        out = jnp.concatenate([x] * g.nranks, axis=0)
    if out_tensor is not None:
        out_tensor._replace_(out, None, 0)
        return _task()
    return wrap(out)


def all_gather_object(object_list, obj, group=None):
    g = _group(group)
    object_list.clear()
    object_list.extend(obj for _ in range(g.nranks))


def reduce_scatter(tensor: Tensor, tensor_or_tensor_list, op=ReduceOp.SUM, group=None, sync_op=True):
    g = _group(group)
    if isinstance(tensor_or_tensor_list, (list, tuple)):
        x = jnp.concatenate([unwrap(t) for t in tensor_or_tensor_list], axis=0)
    else:
        x = unwrap(tensor_or_tensor_list)
    if _axis_bound(g.axis_name):
        out = lax.psum_scatter(x, g.axis_name, scatter_dimension=0, tiled=True)
    else:
        n = g.nranks
        shard = x.shape[0] // n
        out = x[:shard] * (n if op == ReduceOp.SUM else 1)
    tensor._replace_(out, None, 0)
    return _task()


def broadcast(tensor: Tensor, src=0, group=None, sync_op=True):
    g = _group(group)
    x = unwrap(tensor)
    if _axis_bound(g.axis_name):
        # select src's value on every member: gather then index (XLA folds this)
        gathered = lax.all_gather(x, g.axis_name, axis=0, tiled=False)
        out = gathered[g.get_group_rank(src) if src in g.ranks else src]
    else:
        out = x
    tensor._replace_(out, None, 0)
    return _task()


def broadcast_object_list(object_list, src=0, group=None):
    return object_list


def reduce(tensor: Tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    # on TPU a reduce is an all_reduce whose non-dst results are unused (XLA DCEs them)
    return all_reduce(tensor, op, group, sync_op)


def scatter(tensor: Tensor, tensor_list=None, src=0, group=None, sync_op=True):
    g = _group(group)
    if _axis_bound(g.axis_name):
        stacked = jnp.stack([unwrap(t) for t in tensor_list], axis=0) if tensor_list else unwrap(tensor)
        idx = lax.axis_index(g.axis_name)
        out = lax.dynamic_index_in_dim(stacked, idx, axis=0, keepdims=False)
    else:
        out = unwrap(tensor_list[0]) if tensor_list else unwrap(tensor)
    tensor._replace_(out, None, 0)
    return _task()


def scatter_object_list(out_object_list, in_object_list=None, src=0, group=None):
    out_object_list.clear()
    out_object_list.append(in_object_list[0] if in_object_list else None)


def alltoall(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    g = _group(group)
    if isinstance(in_tensor_list, (list, tuple)):
        x = jnp.stack([unwrap(t) for t in in_tensor_list], axis=0)
    else:
        x = unwrap(in_tensor_list)
    if _axis_bound(g.axis_name):
        out = lax.all_to_all(x, g.axis_name, split_axis=0, concat_axis=0, tiled=False)
    else:
        out = x
    parts = [out[i] for i in range(out.shape[0])]
    if out_tensor_list is not None:
        out_tensor_list.clear()
        out_tensor_list.extend(wrap(p) for p in parts)
        return _task()
    return [wrap(p) for p in parts]


all_to_all = alltoall


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None, out_split_sizes=None, group=None, sync_op=True):
    g = _group(group)
    x = unwrap(in_tensor)
    if _axis_bound(g.axis_name):
        out = lax.all_to_all(x, g.axis_name, split_axis=0, concat_axis=0, tiled=True)
    else:
        out = x
    if out_tensor is not None:
        out_tensor._replace_(out, None, 0)
        return _task()
    return wrap(out)


all_to_all_single = alltoall_single


def send(tensor, dst=0, group=None, sync_op=True):
    raise RuntimeError(
        "point-to-point send/recv only exist inside a pipeline shard_map on TPU "
        "(lax.ppermute edges) — use distributed.fleet PipelineParallel or p2p helpers"
    )


def recv(tensor, src=0, group=None, sync_op=True):
    raise RuntimeError(
        "point-to-point send/recv only exist inside a pipeline shard_map on TPU "
        "(lax.ppermute edges) — use distributed.fleet PipelineParallel or p2p helpers"
    )


def isend(tensor, dst=0, group=None):
    return send(tensor, dst, group)


def irecv(tensor, src=0, group=None):
    return recv(tensor, src, group)


class P2POp:
    def __init__(self, op, tensor, peer, group=None):
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    raise RuntimeError("use pipeline ppermute edges (fleet.meta_parallel.p2p) on TPU")


def barrier(group=None):
    from ..resilience import faults as _faults

    # fault site: a delayed collective (docs/RESILIENCE.md) — the watchdog
    # and retry drills inject here to model a straggling/partitioned rank
    _faults.maybe_inject("collective", "barrier")
    jax.effects_barrier()
    return _task()


# in-shard_map helpers used by the manual fleet engines
def ppermute(x, axis_name, perm):
    return lax.ppermute(x, axis_name, perm)


def axis_index(axis_name):
    return lax.axis_index(axis_name)
