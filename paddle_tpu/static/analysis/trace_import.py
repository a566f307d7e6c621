"""Import a traced callable into the Program IR (``trace_to_program``).

The model families execute eager jax on raw arrays for speed, so their
forwards never pass through ``record_op`` — but they ARE pure under tracing
(that's what jit.to_static exploits). This bridge runs ``jax.make_jaxpr``
over a functionalized forward and rebuilds the jaxpr as a ``Program``: one
``Operation`` per equation (the "kernel" is ``primitive.bind`` with the
equation's params, so the imported program replays under the Executor too),
parameters as named parameter Variables, trace-time constants as captured
Tensors, and per-equation source provenance from jaxpr source_info.

This is how tools/lint_graph.py records every in-repo model family for the
analyzer suite without requiring models to adopt the recording op path.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.extend.core import Literal

from ...core.static_graph import Operation, Program, Variable
from ...core.tensor import Tensor

__all__ = ["trace_to_program", "layer_to_program"]


def _summarize_src(eqn) -> Optional[str]:
    try:
        from jax._src import source_info_util

        s = source_info_util.summarize(eqn.source_info)
        return s or None
    except Exception:  # pragma: no cover - jax internals drift
        return None


def trace_to_program(fn, *input_structs, input_names: Optional[Sequence[str]] = None,
                     param_structs: Sequence = (), param_names: Sequence[str] = (),
                     param_tensors: Sequence = ()) -> Program:
    """Trace ``fn(params..., inputs...)`` (flat positional arrays) and rebuild
    the jaxpr as a Program. ``param_*`` describe the leading arguments that
    are model parameters (named Variables with ``is_parameter=True``)."""
    closed = jax.make_jaxpr(lambda *a: fn(*a))(*param_structs, *input_structs)
    jaxpr = closed.jaxpr
    prog = Program()
    blk = prog.global_block()

    env = {}
    n_params = len(list(param_structs))
    names = list(param_names) + [
        (input_names[i] if input_names and i < len(input_names)
         else f"feed_{i}")
        for i in range(len(jaxpr.invars) - n_params)]
    param_tensors = list(param_tensors)
    for i, var in enumerate(jaxpr.invars):
        name = names[i] if i < len(names) else f"arg_{i}"
        v = blk.create_var(var.aval.shape, var.aval.dtype, name=name,
                           is_feed=(i >= n_params))
        if i < n_params:
            v.is_parameter = True
            if i < len(param_tensors):
                v._param = param_tensors[i]  # back-link for analyzers
        env[var] = v

    for const_var, const_val in zip(jaxpr.constvars, closed.consts):
        t = Tensor(const_val) if not isinstance(const_val, Tensor) else const_val
        t.name = getattr(t, "name", None) or f"const_{len(env)}"
        env[const_var] = t

    for eqn in jaxpr.eqns:
        args = []
        for iv in eqn.invars:
            if isinstance(iv, Literal):
                args.append(np.asarray(iv.val) if hasattr(iv.val, "shape")
                            else iv.val)
            else:
                args.append(env[iv])
        prim, params = eqn.primitive, dict(eqn.params)

        # params live in the CLOSURE, not default args: closure cells holding
        # a dict are unfingerprintable, so CSE can never merge two same-
        # primitive eqns that differ only in params (e.g. two reshapes)
        def make_kernel(prim, params):
            def kernel(*xs):
                # the rebind eval_jaxpr does: call-like primitives
                # (custom_vjp_call, jit) take their inner jaxprs as
                # leading callables, not as keyword params
                subfuns, bind_params = prim.get_bind_params(params)
                out = prim.bind(*subfuns, *xs, **bind_params)
                return tuple(out) if prim.multiple_results else out
            # random_* eqns replay a PRNG key BAKED into the jaxpr — they are
            # deterministic, so the trace linter must not flag them unseeded
            kernel._jaxpr_import = True
            # back-links for the cost auditor's op-level fallback walk
            # (static/cost — Operation has __slots__, so they ride the fn)
            kernel._primitive = prim
            kernel._prim_params = params
            return kernel

        op = Operation(len(blk.ops), prim.name, make_kernel(prim, params),
                       args, {}, src=_summarize_src(eqn))
        blk.ops.append(op)
        prog._version += 1
        for ov in eqn.outvars:
            v = blk.create_var(ov.aval.shape, ov.aval.dtype,
                               name=prog._next_name(prim.name), op=op)
            op.outputs.append(v)
            env[ov] = v

    outs = []
    for ov in jaxpr.outvars:
        if isinstance(ov, Literal):
            continue
        o = env.get(ov)
        if isinstance(o, Variable):
            outs.append(o)
    prog._outputs = outs  # liveness roots for Program.diagnose()
    # the full ClosedJaxpr rides along for analyzers that must recurse into
    # container primitives (scan bodies, pjit calls) and read dataflow the
    # flattened op list cannot express — the PT-COST walker (static/cost)
    prog._closed_jaxpr = closed
    return prog


def layer_to_program(layer, *input_structs, input_names=None,
                     extra_kwargs=None) -> Program:
    """Functionalize a Layer (params+buffers become named inputs — the same
    split jit.to_static uses) and import its traced forward as a Program."""
    from ...jit.api import _collect_state, _Swap, _tree_unwrap

    names, tensors = _collect_state(layer)
    state_structs = [jax.ShapeDtypeStruct(tuple(t._data.shape), t._data.dtype)
                     for t in tensors]
    n_state = len(state_structs)
    kwargs = dict(extra_kwargs or {})

    def flat(*arrays):
        state, ins = arrays[:n_state], arrays[n_state:]
        with _Swap(tensors, list(state)):
            out = layer(*[Tensor(a) for a in ins], **kwargs)
        return _tree_unwrap(out)

    return trace_to_program(
        flat, *input_structs, input_names=input_names,
        param_structs=state_structs, param_names=names,
        param_tensors=tensors)
