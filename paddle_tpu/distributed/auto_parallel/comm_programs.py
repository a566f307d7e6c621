"""Collective-contract programs for the PT-COMM auditor (ROADMAP item 1).

One compact Megatron/FSDP-style train step whose EXPLICIT collectives
spell out the placement contract each recorded MULTICHIP mesh shape
implies — the artifact tools/audit_collectives.py traces under a
symbolic ``AbstractMesh`` (no devices, no XLA compile) and baselines in
tools/collective_baseline.json. The real sharded serving/training work
(item 1) inherits these as ratchets: the per-axis collective kinds,
counts and ring wire bytes recorded here are the contract its programs
must meet.

The step adapts to whichever axes the mesh declares (size-1 axes are
dropped):

- ``dp``            data parallel: gradient ``psum``
- ``fsdp``          ZeRO-3: params ``all_gather`` before use, gradients
                    ``psum_scatter`` back to shards (+ batch sharding)
- ``tp``            Megatron tensor parallel: column-parallel w1, row-
                    parallel w2, forward/backward partial-sum ``psum``
- ``sep``           Ulysses sequence parallel: ``all_to_all`` seq<->
                    feature around the sequence mixer (+ grad ``psum``)
- ``ep``            MoE expert parallel: ``global_scatter``/
                    ``global_gather`` token ``all_to_all`` dispatch
                    (+ batch sharding, grad ``psum``)
- ``pp``            pipeline: one boundary ``ppermute`` each direction

The backward pass is written out by hand (transposed matmuls) rather
than via ``jax.grad`` so the collective plan is explicit and readable —
this is a CONTRACT program: the auditor censuses what it dispatches, it
never executes.
"""

from __future__ import annotations

from typing import Dict, Tuple

__all__ = ["train_step_comm", "moe_combine_comm"]


def _prod(xs):
    n = 1
    for x in xs:
        n *= x
    return n


def train_step_comm(mesh_axes: Dict[str, int], *, batch_per_shard: int = 2,
                    seq_per_shard: int = 8, d_model: int = 32,
                    d_hidden: int = 64, dtype="bfloat16"):
    """Build the contract step for one mesh shape. Returns
    ``(fn, input_structs, input_names, axes)`` ready for
    ``trace_to_program`` — ``fn`` is the shard_map'd step over GLOBAL
    shapes, ``axes`` the normalized (size>1) mesh dict."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from ...static.comm.mesh import abstract_mesh, mesh_spec
    from ..utils.moe_utils import global_gather, global_scatter

    axes = {k: int(v) for k, v in mesh_axes.items() if int(v) > 1}
    if not axes:
        raise ValueError("train_step_comm needs at least one >1 mesh axis")
    dp, fsdp, tp = axes.get("dp", 1), axes.get("fsdp", 1), axes.get("tp", 1)
    sep, pp, ep = axes.get("sep", 1), axes.get("pp", 1), axes.get("ep", 1)
    # batch shards over every data-like axis present (fsdp = ZeRO data
    # parallelism; ep ranks own disjoint token sets pre-dispatch)
    data_axes = tuple(a for a in ("dp", "fsdp", "ep") if a in axes)
    B = batch_per_shard * _prod(axes[a] for a in data_axes)
    S = seq_per_shard * sep
    D, H = d_model, d_hidden
    assert D % max(sep, 1) == 0 and D % max(fsdp, 1) == 0
    assert H % max(tp, 1) == 0
    grad_sum_axes = tuple(a for a in ("dp", "ep", "sep") if a in axes)
    np_dtype = np.dtype(dtype)

    def step(w1, w2, x, y):
        # local shapes: w1 [D/fsdp, H/tp], w2 [H/tp, D/fsdp],
        # x/y [batch_per_shard, seq_per_shard, D]
        w1f, w2f = w1, w2
        if fsdp > 1:      # ZeRO-3: unshard params for the step's compute
            w1f = lax.all_gather(w1, "fsdp", axis=0, tiled=True)
            w2f = lax.all_gather(w2, "fsdp", axis=1, tiled=True)
        xs = x
        if sep > 1:       # Ulysses: seq<->feature exchange, mix, invert
            xs = lax.all_to_all(xs, "sep", split_axis=2, concat_axis=1,
                                tiled=True)               # [b, S, D/sep]
            xs = jax.nn.softmax(xs, axis=1) * xs          # global-seq mixer
            xs = lax.all_to_all(xs, "sep", split_axis=1, concat_axis=2,
                                tiled=True)               # [b, s, D]
        b, s = xs.shape[0], xs.shape[1]
        t = xs.reshape(b * s, D)
        if ep > 1:        # MoE: token dispatch to expert ranks
            t = global_scatter(t, axis_name="ep")
        h = jax.nn.relu(t @ w1f)                          # [T, H/tp] col-par
        o = h @ w2f                                       # [T, D] partial
        if tp > 1:
            o = lax.psum(o, "tp")                         # row-parallel fwd
        if ep > 1:
            o = global_gather(o, axis_name="ep")
            td = t                                        # dispatched tokens
        o = o.reshape(b, s, D)
        if pp > 1:        # stage boundary: activations forward
            o = lax.ppermute(o, "pp", [(i, (i + 1) % pp) for i in range(pp)])
        e = (o - y.astype(o.dtype)) * np_dtype.type(1.0 / (B * S * D))
        if pp > 1:        # stage boundary: error backward
            e = lax.ppermute(e, "pp", [(i, (i - 1) % pp) for i in range(pp)])
        et = e.reshape(b * s, D)
        if ep > 1:        # backward of global_gather = dispatch the error
            et = global_scatter(et, axis_name="ep")
            t = td
        gw2 = h.T @ et                                    # [H/tp, D]
        gh = (et @ w2f.T) * (h > 0).astype(h.dtype)       # [T, H/tp]
        gw1 = t.T @ gh                                    # [D, H/tp]
        gt = gh @ w1f.T                                   # [T, D] partial
        if tp > 1:
            gt = lax.psum(gt, "tp")                       # col-parallel bwd
        for a in grad_sum_axes:                           # data-axis sync
            gw1 = lax.psum(gw1, a)
            gw2 = lax.psum(gw2, a)
        if fsdp > 1:      # ZeRO-3: reduce gradients back to param shards
            gw1 = lax.psum_scatter(gw1, "fsdp", scatter_dimension=0,
                                   tiled=True)
            gw2 = lax.psum_scatter(gw2, "fsdp", scatter_dimension=1,
                                   tiled=True)
        loss = et.sum() + gt.sum() * np_dtype.type(0)
        for a in grad_sum_axes:
            loss = lax.psum(loss, a)
        lr = np_dtype.type(1e-3)
        return w1 - lr * gw1, w2 - lr * gw2, loss

    mesh = abstract_mesh(axes)
    w1_spec = mesh_spec(axes, "fsdp", "tp")
    w2_spec = mesh_spec(axes, "tp", "fsdp")
    act_spec = mesh_spec(axes, data_axes or None, "sep", None)
    fn = jax.shard_map(step, mesh=mesh,
                       in_specs=(w1_spec, w2_spec, act_spec, act_spec),
                       out_specs=(w1_spec, w2_spec, mesh_spec(axes)),
                       check_vma=False)
    sd = jax.ShapeDtypeStruct
    structs = (sd((D, H), np_dtype), sd((H, D), np_dtype),
               sd((B, S, D), np_dtype), sd((B, S, D), np_dtype))
    return fn, structs, ["w1", "w2", "x", "y"], axes


def moe_combine_comm(ep: int, *, tokens_per_rank: int = 16,
                     d_model: int = 16, dtype="bfloat16"
                     ) -> Tuple[object, tuple, list, Dict[str, int]]:
    """The MoE dispatch/combine spmd-rule program (SURVEY catalogue
    ``moe_combine``): ``global_scatter`` -> per-rank expert FFN ->
    ``global_gather``, the two token ``all_to_all``s every expert-
    parallel step pays. Same return contract as
    :func:`train_step_comm`."""
    import jax
    import numpy as np
    from jax import nn as jnn
    from jax.sharding import PartitionSpec as P

    from ...static.comm.mesh import abstract_mesh
    from ..utils.moe_utils import global_gather, global_scatter

    ep = int(ep)
    if tokens_per_rank % ep:
        raise ValueError("tokens_per_rank must divide the ep width")
    np_dtype = np.dtype(dtype)
    D = d_model

    def combine(x, we):
        xd = global_scatter(x, axis_name="ep")   # tokens -> expert ranks
        h = jnn.relu(xd @ we)                    # this rank's expert(s)
        return global_gather(h, axis_name="ep")  # tokens -> home ranks

    mesh = abstract_mesh({"ep": ep})
    fn = jax.shard_map(combine, mesh=mesh,
                       in_specs=(P("ep", None), P(None, None)),
                       out_specs=P("ep", None), check_vma=False)
    sd = jax.ShapeDtypeStruct
    structs = (sd((ep * tokens_per_rank, D), np_dtype),
               sd((D, D), np_dtype))
    return fn, structs, ["tokens", "w_expert"], {"ep": ep}
