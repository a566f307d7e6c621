"""The framework Tensor: a thin, mutable handle over an immutable ``jax.Array``.

Design (vs reference): the reference's ``paddle::Tensor`` (phi/api/include/tensor.h:82)
owns a DenseTensor + AutogradMeta. Here the payload is a ``jax.Array`` (XLA owns
memory/placement); autograd metadata is a pointer into the eager tape
(`paddle_tpu.core.autograd_engine`). Tensor is registered as a JAX pytree so it can
flow through ``jax.jit`` / ``jax.grad`` / shardings transparently.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtype_mod

_tensor_counter = [0]


class Tensor:
    __slots__ = (
        "_data",
        "stop_gradient",
        "_grad",
        "_node",
        "_out_idx",
        "name",
        "persistable",
        "_hooks",
        "is_parameter",
        "__weakref__",
        "__dict__",  # escape hatch: dist attrs (process_mesh/placements), pending buffer updates
    )

    def __init__(self, data, dtype=None, stop_gradient: bool = True, name: Optional[str] = None):
        if isinstance(data, Tensor):
            data = data._data
        if dtype is not None:
            dtype = dtype_mod.convert_dtype(dtype)
        if isinstance(data, jax.ShapeDtypeStruct):
            # a parameter built under LazyGuard: shape and type, no array
            self._data = data
        elif isinstance(data, (jax.Array, jax.core.Tracer)):
            self._data = data.astype(dtype) if (dtype is not None and data.dtype != dtype) else data
        else:
            if dtype is None and isinstance(data, (float,)):
                dtype = dtype_mod.get_default_dtype()
            if dtype is None and isinstance(data, np.ndarray) and data.dtype == np.float64:
                dtype = dtype_mod.get_default_dtype()
            self._data = jnp.asarray(data, dtype=dtype)
        self.stop_gradient = stop_gradient
        self._grad: Optional[Tensor] = None
        self._node = None
        self._out_idx = 0
        if name is None:
            _tensor_counter[0] += 1
            name = f"generated_tensor_{_tensor_counter[0]}"
        self.name = name
        self.persistable = False
        self._hooks = None
        self.is_parameter = False

    # ---- basic properties ----
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def dim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def place(self):
        try:
            devs = self._data.devices()
            return next(iter(devs))
        except Exception:
            return None

    @property
    def grad(self) -> Optional["Tensor"]:
        return self._grad

    @grad.setter
    def grad(self, value):
        if value is not None and not isinstance(value, Tensor):
            value = Tensor(value)
        self._grad = value

    @property
    def is_leaf(self) -> bool:
        return self._node is None

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    # ---- conversion ----
    def numpy(self) -> np.ndarray:
        return np.asarray(self._data)

    def item(self):
        return self._data.item()

    def tolist(self):
        return np.asarray(self._data).tolist()

    def __array__(self, dtype=None):
        a = np.asarray(self._data)
        return a.astype(dtype) if dtype is not None else a

    def __jax_array__(self):
        return self._data

    def __float__(self):
        return float(self._data)

    def __int__(self):
        return int(self._data)

    def __bool__(self):
        return bool(self._data)

    def __index__(self):
        return int(self._data)

    # ---- autograd ----
    def backward(self, grad_tensor: Optional["Tensor"] = None, retain_graph: bool = False):
        from . import autograd_engine

        autograd_engine.run_backward(self, grad_tensor, retain_graph)

    def clear_grad(self):
        self._grad = None

    clear_gradient = clear_grad

    def detach(self) -> "Tensor":
        t = Tensor(self._data, stop_gradient=True, name=self.name + ".detach")
        return t

    def detach_(self) -> "Tensor":
        self._node = None
        self.stop_gradient = True
        return self

    def clone(self) -> "Tensor":
        from . import op_registry

        return op_registry.apply_fn("clone", lambda x: x + 0, self)

    def register_hook(self, hook):
        if self._node is not None:
            # non-leaf: hook fires on this tensor's cotangent during backward
            if self._node.hooks is None:
                self._node.hooks = {}
            self._node.hooks.setdefault(self._out_idx, []).append(hook)
            hooks_ref = self._node.hooks[self._out_idx]

            class _NodeHandle:
                def remove(h):
                    if hook in hooks_ref:
                        hooks_ref.remove(hook)

            return _NodeHandle()
        if self._hooks is None:
            self._hooks = []
        self._hooks.append(hook)

        class _Handle:
            def __init__(h, hooks, fn):
                h._hooks, h._fn = hooks, fn

            def remove(h):
                if h._fn in h._hooks:
                    h._hooks.remove(h._fn)

        return _Handle(self._hooks, hook)

    # ---- mutation (eager only) ----
    def set_value(self, value):
        if isinstance(value, Tensor):
            value = value._data
        arr = jnp.asarray(value, dtype=self._data.dtype)
        if tuple(arr.shape) != tuple(self._data.shape):
            raise ValueError(f"set_value shape mismatch: {arr.shape} vs {self._data.shape}")
        self._data = arr
        return self

    def copy_(self, other, *a, **k):
        return self.set_value(other)

    def _replace_(self, new_data, node=None, idx=0):
        """Internal: rebind payload (used by in-place ops and functional swap)."""
        self._data = new_data
        self._node = node
        self._out_idx = idx
        return self

    def __repr__(self):
        grad_info = "" if self.stop_gradient else ", stop_gradient=False"
        return (
            f"Tensor(shape={self.shape}, dtype={dtype_mod.dtype_name(self.dtype)}{grad_info},\n"
            f"       {np.asarray(self._data)!r})"
        )

    __str__ = __repr__

    def __hash__(self):
        return id(self)

    # value/pin/cuda parity helpers
    def pin_memory(self):
        return self

    def cuda(self, *a, **k):
        return self

    def cpu(self):
        return self

    def to(self, *args, **kwargs):
        dtype = kwargs.get("dtype")
        for a in args:
            if isinstance(a, str) and a in ("cpu", "gpu", "tpu") or hasattr(a, "platform"):
                continue
            dtype = a
        if dtype is not None:
            return self.astype(dtype)
        return self

    def astype(self, dtype):  # overridden by tensor method installation (graph-aware)
        from . import op_registry

        dtype = dtype_mod.convert_dtype(dtype)
        return op_registry.apply_fn("cast", lambda x: x.astype(dtype), self)

    def value(self):
        return self

    def get_tensor(self):
        return self


def _tensor_flatten(t: Tensor):
    # NOTE: aux must NOT contain per-instance strings (e.g. .name) — jit caches on
    # pytree aux equality and unique names would force a retrace per call.
    return (t._data,), (t.stop_gradient,)


def _tensor_unflatten(aux, children):
    (data,) = children
    t = Tensor.__new__(Tensor)
    t._data = data
    t.stop_gradient = aux[0]
    t._grad = None
    t._node = None
    t._out_idx = 0
    t.name = "unflattened_tensor"
    t.persistable = False
    t._hooks = None
    t.is_parameter = False
    return t


jax.tree_util.register_pytree_node(Tensor, _tensor_flatten, _tensor_unflatten)


class Parameter(Tensor):
    """Trainable tensor (reference: python/paddle/base/framework.py EagerParamBase)."""

    __slots__ = ("trainable", "optimize_attr", "regularizer", "need_clip")

    def __init__(self, data, dtype=None, name=None, trainable=True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.is_parameter = True
        self.persistable = True
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.need_clip = True

    @property
    def initialized(self) -> bool:
        """False while the parameter, built under ``LazyGuard``, holds a
        shape and a type and no array."""
        return not isinstance(self._data, jax.ShapeDtypeStruct)


jax.tree_util.register_pytree_node(
    Parameter,
    _tensor_flatten,
    lambda aux, ch: _tensor_unflatten(aux, ch),
)


def unwrap(x):
    """Tensor | array | scalar -> jax-compatible value."""
    return x._data if isinstance(x, Tensor) else x


def wrap(x, stop_gradient=True) -> Tensor:
    return x if isinstance(x, Tensor) else Tensor(x, stop_gradient=stop_gradient)
