"""nn.Layer — module tree with parameters/buffers/hooks/state_dict.

Reference: python/paddle/nn/layer/layers.py:354 (class Layer). Same user contract
(named_parameters, sublayers, register_buffer, forward hooks, train/eval,
state_dict/set_state_dict), re-based on the framework Tensor/Parameter over jax.Array.
"""

from __future__ import annotations

import collections
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from ...core import dtype as dtype_mod
from ...core.tensor import Parameter, Tensor

_layer_counter = collections.defaultdict(int)


class HookRemoveHelper:
    def __init__(self, hooks, hook_id):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        object.__setattr__(self, "_parameters", collections.OrderedDict())
        object.__setattr__(self, "_sub_layers", collections.OrderedDict())
        object.__setattr__(self, "_buffers", collections.OrderedDict())
        object.__setattr__(self, "_non_persistable_buffer_names", set())
        self.training = True
        self._dtype = dtype_mod.convert_dtype(dtype)
        cls = self.__class__.__name__.lower()
        _layer_counter[cls] += 1
        self._full_name = (name_scope or cls) + f"_{_layer_counter[cls] - 1}"
        self._forward_pre_hooks: Dict[int, Callable] = collections.OrderedDict()
        self._forward_post_hooks: Dict[int, Callable] = collections.OrderedDict()
        self._hook_id = 0
        self._casted_by_pure_fp16 = False

    # ---- naming ----
    def full_name(self):
        return self._full_name

    # ---- attribute magic ----
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            for d in (layers, buffers):
                d.pop(name, None) if d else None
            params[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            for d in (params, buffers):
                d.pop(name, None) if d else None
            layers[name] = value
            self.__dict__.pop(name, None)
        elif params is not None and name in params:
            if value is None:
                params.pop(name)
                object.__setattr__(self, name, None)
            elif isinstance(value, Tensor):
                params[name].set_value(value)
            else:
                raise TypeError(f"cannot assign {type(value)} to parameter {name}")
        elif buffers is not None and name in buffers:
            if value is None or isinstance(value, Tensor):
                buffers[name] = value
            else:
                raise TypeError(f"cannot assign {type(value)} to buffer {name}")
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + list(self._sub_layers) + list(self._buffers)

    # ---- registration API ----
    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("add_parameter expects a Parameter")
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[name] = sublayer
        return sublayer

    def register_buffer(self, name: str, tensor: Optional[Tensor], persistable: bool = True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        elif tensor is not None:
            tensor.persistable = True
        return tensor

    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias: bool = False,
        default_initializer=None,
    ) -> Parameter:
        from .. import initializer as I

        dtype = dtype_mod.convert_dtype(dtype) or self._dtype
        init = default_initializer
        name = None
        learning_rate = 1.0
        trainable = True
        if attr is not None and attr is not False:
            init = getattr(attr, "initializer", None) or init
            name = getattr(attr, "name", None)
            learning_rate = getattr(attr, "learning_rate", 1.0)
            trainable = getattr(attr, "trainable", True)
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierNormal()
        from ...tensor.toplevel_extras import LazyGuard

        if LazyGuard.active():      # shape and type now, the array later
            import jax

            data = jax.ShapeDtypeStruct(tuple(int(d) for d in shape), dtype)
        else:
            data = init(shape, dtype)
        p = Parameter(data, dtype=dtype, name=name, trainable=trainable)
        p.optimize_attr["learning_rate"] = learning_rate
        if attr is not None and attr is not False:
            # per-param regularizer (overrides the optimizer-level
            # weight_decay — see Optimizer._decay_term)
            p.regularizer = getattr(attr, "regularizer", None)
        return p

    def create_tensor(self, name=None, dtype=None, persistable=False):
        import jax.numpy as jnp

        t = Tensor(jnp.zeros([], dtype_mod.convert_dtype(dtype) or self._dtype), name=name)
        t.persistable = persistable
        return t

    # ---- traversal ----
    def parameters(self, include_sublayers: bool = True) -> List[Parameter]:
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True, include_self=True) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            if not include_sublayers and layer is not self:
                continue
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (name + "." + pname if name else pname), p

    def buffers(self, include_sublayers: bool = True) -> List[Tensor]:
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True) -> Iterator[Tuple[str, Tensor]]:
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            if not include_sublayers and layer is not self:
                continue
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (name + "." + bname if name else bname), b

    def children(self) -> Iterator["Layer"]:
        for _, layer in self.named_children():
            yield layer

    def named_children(self) -> Iterator[Tuple[str, "Layer"]]:
        seen = set()
        for name, layer in self._sub_layers.items():
            if layer is not None and id(layer) not in seen:
                seen.add(id(layer))
                yield name, layer

    def sublayers(self, include_self: bool = False) -> List["Layer"]:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix="", include_self=False, layers_set=None) -> Iterator[Tuple[str, "Layer"]]:
        if layers_set is None:
            layers_set = set()
        if id(self) in layers_set:
            return
        layers_set.add(id(self))
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = prefix + ("." if prefix else "") + name
            yield from layer.named_sublayers(prefix=sub_prefix, include_self=True, layers_set=layers_set)

    def apply(self, fn):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    # ---- mode ----
    def train(self):
        self.training = True
        for layer in self.sublayers():
            layer.training = True
        return self

    def eval(self):
        self.training = False
        for layer in self.sublayers():
            layer.training = False
        return self

    # ---- hooks ----
    def register_forward_pre_hook(self, hook) -> HookRemoveHelper:
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook) -> HookRemoveHelper:
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # ---- forward ----
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            res = hook(self, inputs, outputs)
            if res is not None:
                outputs = res
        return outputs

    # ---- state dict ----
    def state_dict(self, destination=None, include_sublayers=True, structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(include_sublayers=include_sublayers):
            dest[structured_name_prefix + name] = p
        for name, layer in self.named_sublayers(include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or bname in layer._non_persistable_buffer_names:
                    continue
                key = (name + "." + bname) if name else bname
                dest[structured_name_prefix + key] = b
        return dest

    to_static_state_dict = state_dict

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k not in own:
                unexpected.append(k)
                continue
            tgt = own[k]
            arr = v._data if isinstance(v, Tensor) else v
            arr = np.asarray(arr)
            if tuple(arr.shape) != tuple(tgt.shape):
                raise ValueError(f"shape mismatch for {k}: {arr.shape} vs {tgt.shape}")
            tgt.set_value(arr.astype(np.dtype(tgt.dtype)) if arr.dtype != tgt.dtype else arr)
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # ---- dtype/device movement ----
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            self._cast_all(dtype_mod.convert_dtype(dtype))
        return self

    def astype(self, dtype):
        self._cast_all(dtype_mod.convert_dtype(dtype))
        return self

    def _cast_all(self, dtype, floating_only=True):
        import jax.numpy as jnp

        for p in self.parameters():
            if not floating_only or jnp.issubdtype(p.dtype, jnp.floating):
                p._data = p._data.astype(dtype)
        for _, b in self.named_buffers():
            if not floating_only or jnp.issubdtype(b.dtype, jnp.floating):
                b._data = b._data.astype(dtype)
        for layer in self.sublayers(include_self=True):
            layer._dtype = dtype

    def float(self):
        self._cast_all(dtype_mod.float32)
        return self

    def bfloat16(self):
        self._cast_all(dtype_mod.bfloat16)
        return self

    def half(self):
        self._cast_all(dtype_mod.float16)
        return self

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self._sub_layers.items():
            mod_str = repr(layer)
            mod_str = "\n  ".join(mod_str.split("\n"))
            lines.append(f"({name}): {mod_str}")
        main = self.__class__.__name__ + "("
        if extra:
            main += extra
        if lines:
            main += "\n  " + "\n  ".join(lines) + "\n"
        return main + ")"


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, l in enumerate(sublayers):
                self.add_sublayer(str(i), l)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LayerList(list(self._sub_layers.values())[idx])
        n = len(self._sub_layers)
        return self._sub_layers[str(idx % n if idx < 0 else idx)]

    def __setitem__(self, idx, layer):
        self._sub_layers[str(idx)] = layer

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def append(self, layer):
        self.add_sublayer(str(len(self._sub_layers)), layer)
        return self

    def insert(self, index, layer):
        layers = list(self._sub_layers.values())
        layers.insert(index, layer)
        self._sub_layers.clear()
        for i, l in enumerate(layers):
            self._sub_layers[str(i)] = l

    def extend(self, layers):
        for l in layers:
            self.append(l)
        return self


class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], collections.OrderedDict):
            for name, l in layers[0].items():
                self.add_sublayer(name, l)
        else:
            for i, l in enumerate(layers):
                if isinstance(l, tuple):
                    self.add_sublayer(l[0], l[1])
                else:
                    self.add_sublayer(str(i), l)

    def __getitem__(self, idx):
        return list(self._sub_layers.values())[idx]

    def __len__(self):
        return len(self._sub_layers)

    def forward(self, x):
        for layer in self._sub_layers.values():
            x = layer(x)
        return x


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def __getitem__(self, idx):
        return self._parameters[str(idx)]

    def __len__(self):
        return len(self._parameters)

    def __iter__(self):
        return iter(self._parameters.values())

    def append(self, parameter):
        self.add_parameter(str(len(self._parameters)), parameter)
        return self
