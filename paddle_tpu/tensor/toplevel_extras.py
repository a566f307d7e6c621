"""Top-level namespace completion (reference: python/paddle/__init__.py
__all__): the in-place op family (``x.add_(y)`` semantics via payload
rebinding) plus the remaining standalone functions."""

from __future__ import annotations

import itertools as _it

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dtype as dtype_mod
from ..core.op_registry import apply_fn
from ..core.tensor import Tensor, unwrap
from ..framework.random import next_key

__all__ = [
    "iinfo", "finfo", "dtype", "float8_e4m3fn", "float8_e5m2",
    "mm", "pdist", "hstack", "vstack", "dstack", "column_stack", "row_stack",
    "cartesian_prod", "combinations", "log_normal", "standard_gamma",
    "shape", "tolist", "is_grad_enabled", "rank", "LazyGuard", "check_shape",
    "disable_signal_handler", "get_cuda_rng_state", "set_cuda_rng_state",
    "CUDAPinnedPlace", "batch",
]

# dtype objects (reference: paddle.dtype + float8 members)
dtype = jnp.dtype
float8_e4m3fn = jnp.float8_e4m3fn
float8_e5m2 = jnp.float8_e5m2


def iinfo(dt):
    return jnp.iinfo(dtype_mod.convert_dtype(dt))


def finfo(dt):
    return jnp.finfo(dtype_mod.convert_dtype(dt))


def mm(input, mat2, name=None):
    from .math import matmul

    return matmul(input, mat2)


def pdist(x, p=2.0, name=None):
    """Pairwise distances of rows, condensed upper-triangle (reference: pdist)."""

    def fn(a):
        n = a.shape[0]
        d = a[:, None] - a[None]
        if p == 2.0:
            full = jnp.sqrt(jnp.maximum(jnp.sum(d * d, -1), 0.0))
        elif p == float("inf"):
            full = jnp.max(jnp.abs(d), -1)
        else:
            full = jnp.sum(jnp.abs(d) ** p, -1) ** (1.0 / p)
        iu = jnp.triu_indices(n, k=1)
        return full[iu]

    return apply_fn("pdist", fn, x)


def _stack_family(op_name, fn):
    def f(x, name=None):
        args = [t if isinstance(t, Tensor) else Tensor(np.asarray(t))
                for t in x]
        return apply_fn(op_name, lambda *a: fn(a), *args)

    f.__name__ = op_name
    return f


hstack = _stack_family("hstack", jnp.hstack)
vstack = _stack_family("vstack", jnp.vstack)
dstack = _stack_family("dstack", jnp.dstack)
column_stack = _stack_family("column_stack", jnp.column_stack)
row_stack = vstack  # reference alias


def cartesian_prod(x, name=None):
    """Cartesian product of 1-D tensors (reference: cartesian_prod)."""
    args = [t if isinstance(t, Tensor) else Tensor(np.asarray(t)) for t in x]

    def fn(*arrs):
        grids = jnp.meshgrid(*arrs, indexing="ij")
        return jnp.stack([g.ravel() for g in grids], axis=-1)

    return apply_fn("cartesian_prod", fn, *args)


def combinations(x, r=2, with_replacement=False, name=None):
    """r-combinations of a 1-D tensor's elements (reference: combinations)."""

    def fn(a):
        n = a.shape[0]
        idx_iter = (_it.combinations_with_replacement(range(n), r)
                    if with_replacement else _it.combinations(range(n), r))
        idx = np.array(list(idx_iter), np.int32).reshape(-1, r)
        return a[jnp.asarray(idx)]

    return apply_fn("combinations", fn, x)


def log_normal(mean=1.0, std=2.0, shape=None, dtype=None, name=None):
    """Log-normal samples: exp(N(mean, std)) (reference: log_normal)."""
    dt = dtype_mod.convert_dtype(dtype) or dtype_mod.get_default_dtype()
    shp = tuple(int(unwrap(s)) for s in (shape or [1]))
    return Tensor(jnp.exp(jax.random.normal(next_key(), shp) * std + mean).astype(dt))


def standard_gamma(alpha, name=None):
    def fn(a):
        return jax.random.gamma(next_key(), a)

    return apply_fn("standard_gamma", fn,
                    alpha if isinstance(alpha, Tensor) else Tensor(np.asarray(alpha, np.float32)))


def shape(input):
    """Runtime shape as an int tensor (reference: paddle.shape)."""
    return Tensor(np.asarray(unwrap(input).shape, np.int32))


def tolist(x):
    return x.tolist() if isinstance(x, Tensor) else np.asarray(x).tolist()


def is_grad_enabled():
    from ..core import autograd_engine

    return autograd_engine.grad_enabled()


def rank(input):
    """Tensor rank (ndim) as a 0-D tensor (reference: paddle.rank)."""
    return Tensor(np.asarray(unwrap(input).ndim, np.int32))


class LazyGuard:
    """Deferred-init guard (reference: LazyGuard). A parameter created
    inside the guard (``Layer.create_parameter``) runs no initializer and
    owns no device array: its ``_data`` is a ``jax.ShapeDtypeStruct`` that
    holds shape and type, annotations stay, ``initialized`` reads False until
    ``_data`` is assigned an array. So a model larger than half the device can be
    built, then given its weights, with one copy on the device. Outside the
    guard initialisation is eager as before. Guards nest."""

    _depth = 0

    def __enter__(self):
        LazyGuard._depth += 1
        return self

    def __exit__(self, *exc):
        LazyGuard._depth -= 1
        return False

    @staticmethod
    def active() -> bool:
        return LazyGuard._depth > 0


def check_shape(x, expected):
    got = list(unwrap(x).shape)
    exp = [int(s) if s is not None else None for s in expected]
    if len(got) != len(exp):
        raise ValueError(f"rank mismatch: got {got}, expected {exp}")
    for g, e in zip(got, exp):
        if e is not None and e != -1 and g != e:
            raise ValueError(f"shape mismatch: got {got}, expected {exp}")
    return True


def disable_signal_handler():
    pass  # no native signal handlers installed


def get_cuda_rng_state():
    from ..framework.random import get_rng_state

    return [get_rng_state()]


def set_cuda_rng_state(state):
    from ..framework.random import set_rng_state

    if isinstance(state, (list, tuple)) and state:
        set_rng_state(state[0])


class CUDAPinnedPlace:
    """Place stub (host staging is XLA's concern on TPU)."""


def batch(reader, batch_size, drop_last=False):
    """Deprecated reader decorator (reference: paddle.batch)."""

    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batched


# ---------------------------------------------------------------------------
# in-place variants: x.op_(...) rebinds the payload (the tape keeps the
# functional result, matching the reference's view-free inplace semantics)
# ---------------------------------------------------------------------------

# base names whose name_ form the reference exports at top level
# (where_ is special-cased below: its in-place target is x, not the condition)
INPLACE_BASES = [
    "addmm", "t", "cumsum", "cumprod", "logit", "equal", "cos",
    "tan", "unsqueeze", "logical_and", "less_than", "squeeze", "floor_divide",
    "remainder", "logical_or", "bitwise_and", "bitwise_or", "bitwise_xor",
    "bitwise_not", "less_equal", "triu", "sin", "mod", "abs", "tril", "pow",
    "acos", "expm1", "sinh", "sinc", "neg", "lgamma", "gammaincc", "gammainc",
    "square", "divide", "gammaln", "atan", "gcd", "lcm", "cast",
    "greater_equal", "erf", "greater_than", "tanh", "transpose", "flatten",
    "multiply", "logical_not", "log", "log2", "log10", "trunc", "frac",
    "digamma", "renorm", "multigammaln", "nan_to_num", "ldexp", "i0",
    "polygamma", "copysign", "bitwise_left_shift", "bitwise_right_shift",
    "masked_fill", "masked_scatter", "hypot", "floor_mod",
]


def _make_inplace(base_fn, name):
    def f(x, *args, **kwargs):
        out = base_fn(x, *args, **kwargs)
        return x._replace_(out._data, out._node, out._out_idx)

    f.__name__ = name
    return f


def _random_fill(name, sampler):
    """In-place random fill: x is overwritten with samples of its shape.
    Goes through _replace_ so the stale autograd node is dropped — the new
    value no longer depends on x's producers."""

    def f(x, *args, **kwargs):
        kwargs.pop("name", None)
        new = sampler(tuple(x.shape), *args, **kwargs).astype(x.dtype)
        return x._replace_(new)

    f.__name__ = name
    return f


log_normal_ = _random_fill(
    "log_normal_",
    lambda shp, mean=1.0, std=2.0: jnp.exp(
        jax.random.normal(next_key(), shp) * std + mean))

cauchy_ = _random_fill(
    "cauchy_",
    lambda shp, loc=0.0, scale=1.0: loc + scale * jax.random.cauchy(
        next_key(), shp))


def _geometric_sample(shp, probs):
    # continuous log(u)/log1p(-p), matching the reference's
    # x.uniform_().log_().divide_(log1p(-p)) (creation.py geometric_ — no floor)
    p = unwrap(probs) if isinstance(probs, Tensor) else jnp.asarray(float(probs))
    u = jax.random.uniform(next_key(), shp, minval=1e-7)
    return jnp.log(u) / jnp.log1p(-p)


geometric_ = _random_fill("geometric_", _geometric_sample)


def where_(condition, x, y, name=None):
    """In-place where: x receives where(condition, x, y) (reference: the
    in-place target is x, NOT the first positional arg — excluded from the
    generic _make_inplace family for exactly that reason)."""
    from .manipulation import where as _where

    out = _where(condition, x, y)
    return x._replace_(out._data, out._node, out._out_idx)


def install_inplace_variants(namespace):
    """Create the ``<op>_`` family from existing ops in ``namespace`` and
    install them both as module attributes and Tensor methods."""
    created = {}
    for base in INPLACE_BASES:
        fn = namespace.get(base)
        if fn is None:
            continue
        name = base + "_"
        wrapper = _make_inplace(fn, name)
        created[name] = wrapper
        if not hasattr(Tensor, name):
            setattr(Tensor, name, wrapper)
    for name, fn in (("log_normal_", log_normal_), ("cauchy_", cauchy_),
                     ("geometric_", geometric_), ("where_", where_)):
        created[name] = fn
        if name != "where_" and not hasattr(Tensor, name):
            setattr(Tensor, name, fn)
    return created
