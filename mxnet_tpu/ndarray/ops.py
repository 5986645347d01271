"""Operator library: elementwise / broadcast / reduce / shape / linalg / init.

Reference parity (leezu/mxnet): ``src/operator/tensor/*`` (~150 unary/binary
ops, broadcast/reduce machinery, matrix ops, indexing, ordering) and the
``src/operator/numpy/*`` numpy-semantics ops — SURVEY.md section 2.2.

Design (tpu-first): each op is a pure function over jax arrays composed from
``jax.numpy``/``jax.lax``; XLA fuses elementwise chains automatically (the
reference needed NVRTC pointwise-fusion codegen for this —
``src/operator/fusion/``). Autograd is provided uniformly by the vjp hook in
``register.invoke``, replacing per-op ``FGradient`` registrations.

These functions accept NDArrays (plus python scalars) and return NDArrays.
They are also valid under jax tracing, which is how hybridize builds one XLA
program from the same implementations.
"""
from __future__ import annotations

import builtins
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as _np

from ..context import Context, current_context
from .ndarray import NDArray, from_jax
from .register import invoke, register_op
from builtins import slice as builtins_slice

__all__: list = []  # populated by _public


def _public(fn, name=None):
    name = name or fn.__name__
    __all__.append(name)
    register_op(name, fn)
    return fn


def _as_nd(x: Any, ref: Optional[NDArray] = None) -> NDArray:
    if isinstance(x, NDArray):
        return x
    dtype = None
    if isinstance(x, (bool, int, float)) and ref is not None:
        dtype = ref.dtype
    return NDArray(jnp.asarray(x, dtype=dtype), _wrap=True)


# ---------------------------------------------------------------------------
# Creation ops (reference: src/operator/tensor/init_op.cc)
# ---------------------------------------------------------------------------

def _create(data, ctx, dtype):
    return NDArray(data, ctx=ctx, dtype=dtype)


@_public
def array(source_array, ctx: Optional[Context] = None, dtype=None) -> NDArray:
    """Create an NDArray from any array-like (``mx.nd.array``)."""
    if isinstance(source_array, NDArray):
        source_array = source_array._data
    return _create(source_array, ctx, dtype)


asarray = _public(array, "asarray")


@_public
def zeros(shape, ctx=None, dtype="float32") -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return _create(jnp.zeros(shape, dtype=dtype), ctx, None)


@_public
def ones(shape, ctx=None, dtype="float32") -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return _create(jnp.ones(shape, dtype=dtype), ctx, None)


@_public
def full(shape, val, ctx=None, dtype="float32") -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return _create(jnp.full(shape, val, dtype=dtype), ctx, None)


@_public
def empty(shape, ctx=None, dtype="float32") -> NDArray:
    return zeros(shape, ctx, dtype)


@_public
def arange(start, stop=None, step=1.0, ctx=None, dtype="float32") -> NDArray:
    return _create(jnp.arange(start, stop, step, dtype=dtype), ctx, None)


@_public
def linspace(start, stop, num=50, endpoint=True, ctx=None, dtype="float32"):
    return _create(jnp.linspace(start, stop, num, endpoint=endpoint,
                                dtype=dtype), ctx, None)


@_public
def eye(N, M=None, k=0, ctx=None, dtype="float32") -> NDArray:
    return _create(jnp.eye(N, M, k=k, dtype=dtype), ctx, None)


@_public
def zeros_like(a: NDArray, dtype=None) -> NDArray:
    dt = dtype
    return invoke("zeros_like", lambda x: jnp.zeros_like(x, dtype=dt), (_as_nd(a),))


@_public
def ones_like(a: NDArray, dtype=None) -> NDArray:
    dt = dtype
    return invoke("ones_like", lambda x: jnp.ones_like(x, dtype=dt), (_as_nd(a),))


@_public
def full_like(a: NDArray, fill_value, dtype=None) -> NDArray:
    dt, v = dtype, fill_value
    return invoke("full_like", lambda x: jnp.full_like(x, v, dtype=dt), (_as_nd(a),))


# ---------------------------------------------------------------------------
# Generic unary ops
# ---------------------------------------------------------------------------

_UNARY_TABLE = {
    "negative": jnp.negative, "abs": jnp.abs, "absolute": jnp.abs,
    "sign": jnp.sign, "rint": jnp.rint, "floor": jnp.floor,
    "ceil": jnp.ceil, "trunc": jnp.trunc, "fix": jnp.trunc,
    "square": jnp.square, "sqrt": jnp.sqrt, "cbrt": jnp.cbrt,
    "exp": jnp.exp, "expm1": jnp.expm1, "log": jnp.log, "log2": jnp.log2,
    "log10": jnp.log10, "log1p": jnp.log1p,
    "sin": jnp.sin, "cos": jnp.cos, "tan": jnp.tan,
    "arcsin": jnp.arcsin, "arccos": jnp.arccos, "arctan": jnp.arctan,
    "sinh": jnp.sinh, "cosh": jnp.cosh, "tanh": jnp.tanh,
    "arcsinh": jnp.arcsinh, "arccosh": jnp.arccosh, "arctanh": jnp.arctanh,
    "degrees": jnp.degrees, "radians": jnp.radians,
    "reciprocal": jnp.reciprocal,
    "logical_not": jnp.logical_not,
    "isnan": jnp.isnan, "isinf": jnp.isinf, "isfinite": jnp.isfinite,
    "erf": jax.scipy.special.erf, "erfinv": jax.scipy.special.erfinv,
    "gamma": lambda x: jnp.exp(jax.scipy.special.gammaln(x)),
    "gammaln": jax.scipy.special.gammaln,
    "sigmoid": jax.nn.sigmoid, "relu": jax.nn.relu,
    "softsign": jax.nn.soft_sign,
}


def _make_unary(name, impl):
    def op(a, **kw):
        return invoke(name, impl, (_as_nd(a),))
    op.__name__ = name
    op.__doc__ = f"Elementwise ``{name}`` (src/operator/tensor/elemwise_unary_op)."
    return _public(op, name)


for _n, _f in _UNARY_TABLE.items():
    globals()[_n] = _make_unary(_n, _f)

rsqrt = _public(lambda a: invoke("rsqrt", jax.lax.rsqrt, (_as_nd(a),)), "rsqrt")
rcbrt = _public(lambda a: invoke("rcbrt", lambda x: 1.0 / jnp.cbrt(x), (_as_nd(a),)), "rcbrt")


@_public
def round(a, decimals=0):  # noqa: A001
    d = decimals
    return invoke("round", lambda x: jnp.round(x, d), (_as_nd(a),))


# ---------------------------------------------------------------------------
# Generic binary broadcast ops (scalar operands bound statically)
# ---------------------------------------------------------------------------

_BINARY_TABLE = {
    "add": jnp.add, "subtract": jnp.subtract, "multiply": jnp.multiply,
    "divide": jnp.divide, "true_divide": jnp.true_divide,
    "floor_divide": jnp.floor_divide, "mod": jnp.mod, "fmod": jnp.fmod,
    "remainder": jnp.remainder,
    "power": jnp.power, "maximum": jnp.maximum, "minimum": jnp.minimum,
    "fmax": jnp.fmax, "fmin": jnp.fmin,
    "hypot": jnp.hypot, "arctan2": jnp.arctan2, "logaddexp": jnp.logaddexp,
    "copysign": jnp.copysign,
    "equal": jnp.equal, "not_equal": jnp.not_equal,
    "less": jnp.less, "less_equal": jnp.less_equal,
    "greater": jnp.greater, "greater_equal": jnp.greater_equal,
    "logical_and": jnp.logical_and, "logical_or": jnp.logical_or,
    "logical_xor": jnp.logical_xor,
}


def _make_binary(name, impl):
    def op(lhs, rhs, **kw):
        l_nd, r_nd = isinstance(lhs, NDArray), isinstance(rhs, NDArray)
        if l_nd and r_nd:
            return invoke(name, impl, (lhs, rhs))
        if l_nd:
            s = rhs
            return invoke(name, lambda a: impl(a, s), (lhs,))
        if r_nd:
            s = lhs
            return invoke(name, lambda b: impl(s, b), (rhs,))
        return NDArray(impl(jnp.asarray(lhs), jnp.asarray(rhs)), _wrap=True)
    op.__name__ = name
    op.__doc__ = (f"Broadcasting ``{name}`` "
                  f"(src/operator/tensor/elemwise_binary_broadcast_op).")
    return _public(op, name)


for _n, _f in _BINARY_TABLE.items():
    globals()[_n] = _make_binary(_n, _f)


@_public
def clip(a, a_min=None, a_max=None):
    lo, hi = a_min, a_max
    return invoke("clip", lambda x: jnp.clip(x, lo, hi), (_as_nd(a),))


@_public
def where(condition, x=None, y=None):
    if x is None and y is None:
        return invoke("where_idx", lambda c: jnp.where(c), (_as_nd(condition),))
    return invoke("where", lambda c, a, b: jnp.where(c, a, b),
                  (_as_nd(condition), _as_nd(x), _as_nd(y)))


# ---------------------------------------------------------------------------
# Reductions (reference: broadcast_reduce-inl, np reduce ops)
# ---------------------------------------------------------------------------

def _norm_axis(axis):
    if isinstance(axis, list):
        return tuple(axis)
    return axis


def _make_reduce(name, impl, has_dtype=True):
    def op(a, axis=None, keepdims=False, dtype=None, **kw):
        ax, kd, dt = _norm_axis(axis), keepdims, dtype
        if has_dtype:
            fn = lambda x: impl(x, axis=ax, keepdims=kd, dtype=dt)  # noqa: E731
        else:
            fn = lambda x: impl(x, axis=ax, keepdims=kd)  # noqa: E731
        return invoke(name, fn, (_as_nd(a),))
    op.__name__ = name
    op.__doc__ = f"Reduction ``{name}`` over axes (broadcast_reduce-inl)."
    return _public(op, name)


sum = _make_reduce("sum", jnp.sum)  # noqa: A001
mean = _make_reduce("mean", jnp.mean)
prod = _make_reduce("prod", jnp.prod)
max = _make_reduce("max", jnp.max, has_dtype=False)  # noqa: A001
min = _make_reduce("min", jnp.min, has_dtype=False)  # noqa: A001
amax, amin = max, min
_public(max, "amax"); _public(min, "amin")
all = _make_reduce("all", jnp.all, has_dtype=False)  # noqa: A001
any = _make_reduce("any", jnp.any, has_dtype=False)  # noqa: A001


@_public
def var(a, axis=None, ddof=0, keepdims=False, dtype=None):
    ax, kd, dd = _norm_axis(axis), keepdims, ddof
    return invoke("var", lambda x: jnp.var(x, axis=ax, ddof=dd, keepdims=kd),
                  (_as_nd(a),))


@_public
def std(a, axis=None, ddof=0, keepdims=False, dtype=None):
    ax, kd, dd = _norm_axis(axis), keepdims, ddof
    return invoke("std", lambda x: jnp.std(x, axis=ax, ddof=dd, keepdims=kd),
                  (_as_nd(a),))


@_public
def argmax(a, axis=None, keepdims=False):
    ax, kd = axis, keepdims
    return invoke("argmax", lambda x: jnp.argmax(x, axis=ax, keepdims=kd),
                  (_as_nd(a),))


@_public
def argmin(a, axis=None, keepdims=False):
    ax, kd = axis, keepdims
    return invoke("argmin", lambda x: jnp.argmin(x, axis=ax, keepdims=kd),
                  (_as_nd(a),))


@_public
def norm(a, ord=None, axis=None, keepdims=False):  # noqa: A002
    o, ax, kd = ord, _norm_axis(axis), keepdims
    def impl(x):
        if ax is None and x.ndim > 2:
            # flattened vector norm of the whole tensor (numpy semantics)
            flat = jnp.linalg.norm(x.reshape(-1), ord=o)
            return flat.reshape((1,) * x.ndim) if kd else flat
        return jnp.linalg.norm(x, ord=o, axis=ax, keepdims=kd)
    return invoke("norm", impl, (_as_nd(a),))


@_public
def cumsum(a, axis=None, dtype=None):
    ax, dt = axis, dtype
    return invoke("cumsum", lambda x: jnp.cumsum(x, axis=ax, dtype=dt),
                  (_as_nd(a),))


@_public
def cumprod(a, axis=None):
    ax = axis
    return invoke("cumprod", lambda x: jnp.cumprod(x, axis=ax), (_as_nd(a),))


@_public
def logsumexp(a, axis=None, keepdims=False):
    ax, kd = _norm_axis(axis), keepdims
    return invoke("logsumexp",
                  lambda x: jax.scipy.special.logsumexp(x, axis=ax, keepdims=kd),
                  (_as_nd(a),))


# ---------------------------------------------------------------------------
# Shape / layout ops (reference: matrix_op, np shape ops)
# ---------------------------------------------------------------------------

@_public
def reshape(a, newshape, order="C"):
    shp = tuple(newshape) if not isinstance(newshape, int) else (newshape,)
    return invoke("reshape", lambda x: jnp.reshape(x, shp), (_as_nd(a),))


@_public
def transpose(a, axes=None):
    ax = tuple(axes) if axes else None
    return invoke("transpose", lambda x: jnp.transpose(x, ax), (_as_nd(a),))


@_public
def swapaxes(a, axis1, axis2):
    a1, a2 = axis1, axis2
    return invoke("swapaxes", lambda x: jnp.swapaxes(x, a1, a2), (_as_nd(a),))


@_public
def moveaxis(a, source, destination):
    s, d = source, destination
    return invoke("moveaxis", lambda x: jnp.moveaxis(x, s, d), (_as_nd(a),))


@_public
def expand_dims(a, axis):
    ax = axis
    return invoke("expand_dims", lambda x: jnp.expand_dims(x, ax), (_as_nd(a),))


@_public
def squeeze(a, axis=None):
    ax = axis
    return invoke("squeeze", lambda x: jnp.squeeze(x, ax), (_as_nd(a),))


@_public
def broadcast_to(a, shape):
    shp = tuple(shape)
    return invoke("broadcast_to", lambda x: jnp.broadcast_to(x, shp), (_as_nd(a),))


@_public
def ravel(a):
    return reshape(a, (-1,))


@_public
def flatten(a):
    """Collapse all but the first axis (legacy ``Flatten`` semantics)."""
    nd = _as_nd(a)
    return reshape(nd, (nd.shape[0], -1))


@_public
def concatenate(seq, axis=0):
    ax = axis
    arrs = [_as_nd(s) for s in seq]
    return invoke("concatenate", lambda *xs: jnp.concatenate(xs, axis=ax), arrs)


@_public
def concat(*data, dim=0, axis=None):
    """Legacy ``concat`` (dim kwarg); also accepts a single list."""
    if len(data) == 1 and isinstance(data[0], (list, tuple)):
        data = tuple(data[0])
    return concatenate(data, axis=dim if axis is None else axis)


@_public
def stack(seq, axis=0):
    ax = axis
    arrs = [_as_nd(s) for s in seq]
    return invoke("stack", lambda *xs: jnp.stack(xs, axis=ax), arrs)


@_public
def split(a, indices_or_sections, axis=0):
    i, ax = indices_or_sections, axis
    if isinstance(i, (list, tuple)):
        i = tuple(i)
    return invoke("split", lambda x: tuple(jnp.split(x, i, axis=ax)),
                  (_as_nd(a),))


@_public
def slice_channel(data, num_outputs, axis=1, squeeze_axis=False):
    """Split along ``axis`` into ``num_outputs`` equal parts (reference:
    ``SliceChannel`` in src/operator/slice_channel.cc; default axis=1)."""
    n, ax, sq = num_outputs, axis, squeeze_axis

    def impl(x):
        parts = jnp.split(x, n, axis=ax)
        if sq:
            parts = [jnp.squeeze(p, axis=ax) for p in parts]
        return tuple(parts)

    return invoke("slice_channel", impl, (_as_nd(data),))


@_public
def array_split(a, indices_or_sections, axis=0):
    i, ax = indices_or_sections, axis
    return invoke("array_split",
                  lambda x: tuple(jnp.array_split(x, i, axis=ax)),
                  (_as_nd(a),))


@_public
def tile(a, reps):
    r = reps
    return invoke("tile", lambda x: jnp.tile(x, r), (_as_nd(a),))


@_public
def repeat(a, repeats, axis=None):
    r, ax = repeats, axis
    return invoke("repeat", lambda x: jnp.repeat(x, r, axis=ax), (_as_nd(a),))


@_public
def flip(a, axis=None):
    ax = axis
    return invoke("flip", lambda x: jnp.flip(x, axis=ax), (_as_nd(a),))


@_public
def roll(a, shift, axis=None):
    s, ax = shift, axis
    return invoke("roll", lambda x: jnp.roll(x, s, axis=ax), (_as_nd(a),))


@_public
def pad(a, pad_width, mode="constant", constant_values=0):
    pw, m, cv = pad_width, mode, constant_values
    def impl(x):
        if m == "constant":
            return jnp.pad(x, pw, mode=m, constant_values=cv)
        return jnp.pad(x, pw, mode=m)
    return invoke("pad", impl, (_as_nd(a),))


@_public
def slice_axis(a, axis, begin, end):
    ax, b, e = axis, begin, end
    def impl(x):
        idx = [builtins.slice(None)] * x.ndim
        idx[ax] = builtins.slice(b, e)
        return x[tuple(idx)]
    return invoke("slice_axis", impl, (_as_nd(a),))


@_public
def slice_like(a, b, axes=None):
    axs = axes
    bshape = _as_nd(b).shape
    def impl(x):
        idx = [builtins.slice(None)] * x.ndim
        rng = axs if axs is not None else range(x.ndim)
        for ax in rng:
            idx[ax] = builtins.slice(0, bshape[ax])
        return x[tuple(idx)]
    return invoke("slice_like", impl, (_as_nd(a),))


@_public
def atleast_1d(a):
    return invoke("atleast_1d", jnp.atleast_1d, (_as_nd(a),))


@_public
def atleast_2d(a):
    return invoke("atleast_2d", jnp.atleast_2d, (_as_nd(a),))


@_public
def tril(a, k=0):
    kk = k
    return invoke("tril", lambda x: jnp.tril(x, kk), (_as_nd(a),))


@_public
def triu(a, k=0):
    kk = k
    return invoke("triu", lambda x: jnp.triu(x, kk), (_as_nd(a),))


@_public
def diag(a, k=0):
    kk = k
    return invoke("diag", lambda x: jnp.diag(x, kk), (_as_nd(a),))


# ---------------------------------------------------------------------------
# Indexing / gather-scatter (reference: indexing_op.cc)
# ---------------------------------------------------------------------------

@_public
def take(a, indices, axis=None, mode="clip"):
    ax, md = axis, mode
    idx = _as_nd(indices)
    return invoke("take",
                  lambda x, i: jnp.take(x, i.astype(jnp.int32), axis=ax,
                                        mode=md if md != "raise" else "clip"),
                  (_as_nd(a), idx))


@_public
def take_along_axis(a, indices, axis):
    ax = axis
    return invoke("take_along_axis",
                  lambda x, i: jnp.take_along_axis(x, i.astype(jnp.int32), axis=ax),
                  (_as_nd(a), _as_nd(indices)))


@_public
def gather_nd(data, indices):
    """Gather with leading index tensor (src/operator/tensor/indexing_op.cc)."""
    def impl(x, i):
        i = i.astype(jnp.int32)
        idx = tuple(i[k] for k in range(i.shape[0]))
        return x[idx]
    return invoke("gather_nd", impl, (_as_nd(data), _as_nd(indices)))


@_public
def scatter_nd(data, indices, shape):
    shp = tuple(shape)
    def impl(d, i):
        i = i.astype(jnp.int32)
        idx = tuple(i[k] for k in range(i.shape[0]))
        return jnp.zeros(shp, d.dtype).at[idx].add(d)
    return invoke("scatter_nd", impl, (_as_nd(data), _as_nd(indices)))


@_public
def one_hot(indices, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    d, on, off, dt = depth, on_value, off_value, dtype
    return invoke("one_hot",
                  lambda i: jax.nn.one_hot(i.astype(jnp.int32), d, dtype=dt) *
                  (on - off) + off,
                  (_as_nd(indices),))


@_public
def unique(a, return_index=False, return_inverse=False, return_counts=False):
    nd = _as_nd(a)
    res = _np.unique(nd.asnumpy(), return_index=return_index,
                     return_inverse=return_inverse, return_counts=return_counts)
    if isinstance(res, tuple):
        return tuple(NDArray(r) for r in res)
    return NDArray(res)


@_public
def nonzero(a):
    nd = _as_nd(a)
    res = _np.nonzero(nd.asnumpy())
    return tuple(NDArray(r) for r in res)


@_public
def boolean_mask(data, mask):
    nd, m = _as_nd(data), _as_nd(mask)
    return NDArray(nd.asnumpy()[m.asnumpy().astype(bool)])


# ---------------------------------------------------------------------------
# Ordering (reference: ordering_op.cc — topk/sort/argsort via cub)
# ---------------------------------------------------------------------------

@_public
def sort(a, axis=-1, is_ascend=True):
    ax, asc = axis, is_ascend
    def impl(x):
        s = jnp.sort(x, axis=ax)
        return s if asc else jnp.flip(s, axis=ax)
    return invoke("sort", impl, (_as_nd(a),))


@_public
def argsort(a, axis=-1, is_ascend=True, dtype="float32"):
    ax, asc, dt = axis, is_ascend, dtype
    def impl(x):
        s = jnp.argsort(x, axis=ax)
        if not asc:
            s = jnp.flip(s, axis=ax)
        return s.astype(dt)
    return invoke("argsort", impl, (_as_nd(a),))


@_public
def topk(a, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    ax, kk, rt, asc, dt = axis, k, ret_typ, is_ascend, dtype
    def impl(x):
        xm = jnp.moveaxis(x, ax, -1)
        vals, idx = jax.lax.top_k(-xm if asc else xm, kk)
        if asc:
            vals = -vals
        vals = jnp.moveaxis(vals, -1, ax)
        idx = jnp.moveaxis(idx, -1, ax)
        if rt == "value":
            return vals
        if rt == "indices":
            return idx.astype(dt)
        return (vals, idx.astype(dt))
    return invoke("topk", impl, (_as_nd(a),))


@_public
def searchsorted(a, v, side="left"):
    s = side
    return invoke("searchsorted",
                  lambda x, q: jnp.searchsorted(x, q, side=s),
                  (_as_nd(a), _as_nd(v)))


# ---------------------------------------------------------------------------
# Linear algebra (reference: dot.cc, la_op.cc, np_matmul)
# ---------------------------------------------------------------------------

@_public
def dot(a, b):
    """MXNet ``dot``: inner product over last axis of a / first axis of b."""
    def impl(x, y):
        if x.ndim == 1 and y.ndim == 1:
            return jnp.dot(x, y)
        return jnp.tensordot(x, y, axes=([-1], [0]))
    return invoke("dot", impl, (_as_nd(a), _as_nd(b)))


@_public
def matmul(a, b):
    return invoke("matmul", jnp.matmul, (_as_nd(a), _as_nd(b)))


@_public
def batch_dot(a, b, transpose_a=False, transpose_b=False):
    ta, tb = transpose_a, transpose_b
    def impl(x, y):
        if ta:
            x = jnp.swapaxes(x, -1, -2)
        if tb:
            y = jnp.swapaxes(y, -1, -2)
        return jnp.matmul(x, y)
    return invoke("batch_dot", impl, (_as_nd(a), _as_nd(b)))


@_public
def tensordot(a, b, axes=2):
    ax = axes
    return invoke("tensordot", lambda x, y: jnp.tensordot(x, y, axes=ax),
                  (_as_nd(a), _as_nd(b)))


@_public
def einsum(subscripts, *operands, optimize=True):
    sub = subscripts
    arrs = [_as_nd(o) for o in operands]
    return invoke("einsum",
                  lambda *xs: jnp.einsum(sub, *xs,
                                         optimize="optimal" if optimize else False),
                  arrs)


@_public
def inner(a, b):
    return invoke("inner", jnp.inner, (_as_nd(a), _as_nd(b)))


@_public
def outer(a, b):
    return invoke("outer", jnp.outer, (_as_nd(a), _as_nd(b)))


@_public
def kron(a, b):
    return invoke("kron", jnp.kron, (_as_nd(a), _as_nd(b)))


@_public
def vdot(a, b):
    return invoke("vdot", jnp.vdot, (_as_nd(a), _as_nd(b)))


@_public
def trace(a, offset=0, axis1=0, axis2=1):
    o, a1, a2 = offset, axis1, axis2
    return invoke("trace", lambda x: jnp.trace(x, o, a1, a2), (_as_nd(a),))


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------

@_public
def cast(a, dtype):
    dt = dtype
    return invoke("cast", lambda x: x.astype(dt), (_as_nd(a),))


astype = _public(cast, "astype")


@_public
def identity(a):
    return invoke("identity", lambda x: x + 0, (_as_nd(a),))


@_public
def stop_gradient(a):
    return invoke("stop_gradient", jax.lax.stop_gradient, (_as_nd(a),))


BlockGrad = _public(stop_gradient, "BlockGrad")


@_public
def add_n(*args):
    """Sum of a list of arrays (reference: ElementwiseSum)."""
    if len(args) == 1 and isinstance(args[0], (list, tuple)):
        args = tuple(args[0])
    arrs = [_as_nd(a) for a in args]
    return invoke("add_n", lambda *xs: jax.tree_util.tree_reduce(jnp.add, list(xs)),
                  arrs)


ElementWiseSum = _public(add_n, "ElementWiseSum")


@_public
def maximum_n(*args):
    arrs = [_as_nd(a) for a in args]
    return invoke("maximum_n",
                  lambda *xs: jax.tree_util.tree_reduce(jnp.maximum, list(xs)), arrs)


@_public
def isclose(a, b, rtol=1e-5, atol=1e-8):
    rt, at = rtol, atol
    return invoke("isclose", lambda x, y: jnp.isclose(x, y, rt, at),
                  (_as_nd(a), _as_nd(b)))


@_public
def nan_to_num(a, nan=0.0, posinf=None, neginf=None):
    n, p, ng = nan, posinf, neginf
    return invoke("nan_to_num",
                  lambda x: jnp.nan_to_num(x, nan=n, posinf=p, neginf=ng),
                  (_as_nd(a),))


@_public
def diff(a, n=1, axis=-1):
    nn, ax = n, axis
    return invoke("diff", lambda x: jnp.diff(x, n=nn, axis=ax), (_as_nd(a),))


@_public
def meshgrid(*xs, indexing="xy"):
    ind = indexing
    arrs = [_as_nd(x) for x in xs]
    outs = jnp.meshgrid(*[a._data for a in arrs], indexing=ind)
    return tuple(from_jax(o) for o in outs)


@_public
def histogram(a, bins=10, range=None):  # noqa: A002
    nd = _as_nd(a)
    h, e = jnp.histogram(nd._data, bins=bins, range=range)
    return from_jax(h), from_jax(e)


@_public
def interp(x, xp, fp):
    return invoke("interp", jnp.interp, (_as_nd(x), _as_nd(xp), _as_nd(fp)))


@_public
def waitall():
    from .. import engine as _e
    _e.waitall()


# ---------------------------------------------------------------------------
# Legacy 1.x op-name aliases + remaining tensor ops (reference:
# src/operator/tensor/elemwise_binary_broadcast_op*, matrix_op*,
# src/operator/bilinear_sampler.cc, grid_generator.cc). The broadcast_*/
# elemwise_* spellings share one implementation — XLA broadcasts either
# way; keeping both names preserves the reference's public surface.
# ---------------------------------------------------------------------------

broadcast_add = _public(globals()["add"], "broadcast_add")
broadcast_plus = _public(globals()["add"], "broadcast_plus")
broadcast_sub = _public(globals()["subtract"], "broadcast_sub")
broadcast_minus = _public(globals()["subtract"], "broadcast_minus")
broadcast_mul = _public(globals()["multiply"], "broadcast_mul")
broadcast_div = _public(globals()["divide"], "broadcast_div")
broadcast_mod = _public(globals()["mod"], "broadcast_mod")
broadcast_power = _public(globals()["power"], "broadcast_power")
broadcast_maximum = _public(globals()["maximum"], "broadcast_maximum")
broadcast_minimum = _public(globals()["minimum"], "broadcast_minimum")
broadcast_equal = _public(globals()["equal"], "broadcast_equal")
broadcast_not_equal = _public(globals()["not_equal"], "broadcast_not_equal")
broadcast_greater = _public(globals()["greater"], "broadcast_greater")
broadcast_greater_equal = _public(globals()["greater_equal"],
                                  "broadcast_greater_equal")
broadcast_lesser = _public(globals()["less"], "broadcast_lesser")
broadcast_lesser_equal = _public(globals()["less_equal"],
                                 "broadcast_lesser_equal")
broadcast_logical_and = _public(globals()["logical_and"],
                                "broadcast_logical_and")
broadcast_logical_or = _public(globals()["logical_or"],
                               "broadcast_logical_or")
broadcast_logical_xor = _public(globals()["logical_xor"],
                                "broadcast_logical_xor")
elemwise_add = _public(globals()["add"], "elemwise_add")
elemwise_sub = _public(globals()["subtract"], "elemwise_sub")
elemwise_mul = _public(globals()["multiply"], "elemwise_mul")
elemwise_div = _public(globals()["divide"], "elemwise_div")


@_public
def broadcast_axis(data, axis=(), size=()):
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    sizes = (size,) if isinstance(size, int) else tuple(size)
    if len(axes) != len(sizes):
        raise ValueError(f"broadcast_axis: axis {axes} and size {sizes} "
                         "must have the same length")

    def impl(x):
        shape = list(x.shape)
        for ax, s in zip(axes, sizes):
            shape[ax] = s
        return jnp.broadcast_to(x, shape)

    return invoke("broadcast_axis", impl, (_as_nd(data),))


broadcast_axes = _public(globals()["broadcast_axis"], "broadcast_axes")


@_public
def broadcast_like(lhs, rhs, lhs_axes=None, rhs_axes=None):
    l, r = _as_nd(lhs), _as_nd(rhs)
    if lhs_axes is None:
        return invoke("broadcast_like",
                      lambda a, b: jnp.broadcast_to(a, b.shape), (l, r))
    if rhs_axes is None or len(tuple(lhs_axes)) != len(tuple(rhs_axes)):
        raise ValueError("broadcast_like: lhs_axes and rhs_axes must be "
                         "given together with equal length")
    l_axes, r_axes = tuple(lhs_axes), tuple(rhs_axes)

    def impl(a, b):
        shape = list(a.shape)
        for la, ra in zip(l_axes, r_axes):
            shape[la] = b.shape[ra]
        return jnp.broadcast_to(a, shape)

    return invoke("broadcast_like", impl, (l, r))


@_public
def reshape_like(lhs, rhs):
    return invoke("reshape_like",
                  lambda a, b: jnp.reshape(a, b.shape),
                  (_as_nd(lhs), _as_nd(rhs)))


@_public
def reverse(data, axis):
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    return invoke("reverse", lambda x: jnp.flip(x, axis=axes),
                  (_as_nd(data),))


@_public
def slice(data, begin, end, step=None):  # noqa: A001
    b, e = tuple(begin), tuple(end)
    st = tuple(step) if step is not None else (1,) * len(b)
    if len(b) != len(e) or len(st) != len(b):
        raise ValueError(f"slice: begin {b}, end {e}, step {st} must have "
                         "equal lengths")
    if 0 in st:  # NB: module-level `any` is the reduction op, not builtin
        raise ValueError("slice: step cannot be 0")
    sl = tuple(builtins_slice(bb, ee, ss)
               for bb, ee, ss in zip(b, e, st))
    return invoke("slice", lambda x: x[sl], (_as_nd(data),))


@_public
def softmin(data, axis=-1):
    return invoke("softmin",
                  lambda x: jax.nn.softmax(-x.astype(jnp.float32), axis=axis)
                  .astype(x.dtype), (_as_nd(data),))


@_public
def moments(data, axes=None, keepdims=False):
    ax = tuple(axes) if axes is not None else None

    def impl(x):
        m = jnp.mean(x, axis=ax, keepdims=keepdims)
        v = jnp.var(x, axis=ax, keepdims=keepdims)
        return m, v

    out = invoke("moments", impl, (_as_nd(data),))
    return out


@_public
def shape_array(data):
    nd = _as_nd(data)
    return from_jax(jnp.asarray(nd.shape, dtype=jnp.int32))


@_public
def size_array(data):
    nd = _as_nd(data)
    return from_jax(jnp.asarray([nd.size], dtype=jnp.int32))


@_public
def batch_take(a, indices):
    return invoke("batch_take",
                  lambda x, idx: jnp.take_along_axis(
                      x, idx[:, None].astype(jnp.int32), axis=1)[:, 0],
                  (_as_nd(a), _as_nd(indices)))


@_public
def grid_generator(data, transform_type="affine", target_shape=None):
    """Sampling-grid generation for spatial transformers (reference:
    src/operator/grid_generator.cc). 'affine': data is (N, 6) affine
    params; 'warp': data is (N, 2, H, W) flow offsets. Output grid is
    (N, 2, H, W) with x/y in [-1, 1]."""
    th, tw = (target_shape if transform_type == "affine"
              else _as_nd(data).shape[2:])

    def impl(d):
        if transform_type == "affine":
            n = d.shape[0]
            ys = jnp.linspace(-1.0, 1.0, th)
            xs = jnp.linspace(-1.0, 1.0, tw)
            gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
            ones = jnp.ones_like(gx)
            base = jnp.stack([gx.ravel(), gy.ravel(), ones.ravel()])  # 3,HW
            theta = d.reshape(n, 2, 3).astype(jnp.float32)
            out = jnp.einsum("nij,jk->nik", theta, base)  # n,2,HW
            return out.reshape(n, 2, th, tw)
        # warp: offsets are in pixels; normalize to [-1, 1]
        n, _, h, w = d.shape
        ys = jnp.arange(h, dtype=jnp.float32)
        xs = jnp.arange(w, dtype=jnp.float32)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        fx = (gx + d[:, 0].astype(jnp.float32)) * 2.0 / \
            jnp.maximum(w - 1, 1) - 1.0
        fy = (gy + d[:, 1].astype(jnp.float32)) * 2.0 / \
            jnp.maximum(h - 1, 1) - 1.0
        return jnp.stack([fx, fy], axis=1)

    return invoke("grid_generator", impl, (_as_nd(data),))


@_public
def bilinear_sampler(data, grid, cudnn_off=None):
    """Bilinear sampling of (N, C, H, W) data at grid locations
    (reference: src/operator/bilinear_sampler.cc; the spatial-transformer
    sampler). ``grid`` is (N, 2, Ho, Wo) with x/y in [-1, 1]; out-of-
    range samples read zero (border handled by clamping the gather and
    masking the weight)."""

    def impl(x, g):
        n, c, h, w = x.shape
        gx = (g[:, 0].astype(jnp.float32) + 1.0) * (w - 1) / 2.0
        gy = (g[:, 1].astype(jnp.float32) + 1.0) * (h - 1) / 2.0
        x0 = jnp.floor(gx)
        y0 = jnp.floor(gy)
        wx = gx - x0
        wy = gy - y0

        def gather(yy, xx):
            yi = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
            xi = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
            valid = ((yy >= 0) & (yy <= h - 1) &
                     (xx >= 0) & (xx <= w - 1)).astype(jnp.float32)
            vals = jax.vmap(
                lambda img, yj, xj: img[:, yj, xj])(x, yi, xi)  # n,c,Ho,Wo?
            return vals, valid

        v00, m00 = gather(y0, x0)
        v01, m01 = gather(y0, x0 + 1)
        v10, m10 = gather(y0 + 1, x0)
        v11, m11 = gather(y0 + 1, x0 + 1)
        w00 = ((1 - wy) * (1 - wx) * m00)[:, None]
        w01 = ((1 - wy) * wx * m01)[:, None]
        w10 = (wy * (1 - wx) * m10)[:, None]
        w11 = (wy * wx * m11)[:, None]
        out = (v00.astype(jnp.float32) * w00 +
               v01.astype(jnp.float32) * w01 +
               v10.astype(jnp.float32) * w10 +
               v11.astype(jnp.float32) * w11)
        return out.astype(x.dtype)

    return invoke("bilinear_sampler", impl, (_as_nd(data), _as_nd(grid)))


@_public
def depth_to_space(data, block_size: int):
    """Rearrange depth blocks into spatial blocks, NCHW (reference:
    src/operator/tensor/matrix_op DepthToSpace — the DCR layout the
    reference documents: reshape (N, b, b, C/b², H, W) → transpose →
    (N, C/b², H·b, W·b))."""
    b = int(block_size)

    def impl(x):
        n, c, h, w = x.shape
        t = x.reshape(n, b, b, c // (b * b), h, w)
        t = jnp.transpose(t, (0, 3, 4, 1, 5, 2))
        return t.reshape(n, c // (b * b), h * b, w * b)

    nd = _as_nd(data)
    if b <= 0 or nd.ndim != 4 or nd.shape[1] % (b * b):
        raise ValueError(
            f"depth_to_space: need NCHW with C divisible by block² and "
            f"a positive block (got shape {nd.shape}, block {b})")
    return invoke("depth_to_space", impl, (nd,))


@_public
def space_to_depth(data, block_size: int):
    """Inverse of :func:`depth_to_space` (reference SpaceToDepth)."""
    b = int(block_size)

    def impl(x):
        n, c, h, w = x.shape
        t = x.reshape(n, c, h // b, b, w // b, b)
        t = jnp.transpose(t, (0, 3, 5, 1, 2, 4))
        return t.reshape(n, c * b * b, h // b, w // b)

    nd = _as_nd(data)
    if b <= 0 or nd.ndim != 4 or nd.shape[2] % b or nd.shape[3] % b:
        raise ValueError(
            f"space_to_depth: need NCHW with H, W divisible by block "
            f"and a positive block (got shape {nd.shape}, block {b})")
    return invoke("space_to_depth", impl, (nd,))


@_public
def shuffle(data):
    """Random permutation along the first axis (reference:
    mx.nd.random.shuffle / src/operator/random/shuffle_op.cc). Draws
    from the framework RNG stream; rides as an op input so compiled
    programs reshuffle every call."""
    from . import random as _random
    seed = _random.split_seed()

    def impl(x, s):
        k = jax.random.wrap_key_data(s, impl="threefry2x32")
        return jax.random.permutation(k, x, axis=0)

    return invoke("shuffle", impl,
                  (_as_nd(data), _as_nd(seed)))


@_public
def spatial_transformer(data, loc, target_shape=None,
                        transform_type: str = "affine",
                        sampler_type: str = "bilinear"):
    """Spatial transformer network op (reference:
    src/operator/spatial_transformer.cc): affine grid from ``loc``
    (N, 6) + bilinear sampling of ``data`` — the composition of
    :func:`grid_generator` and :func:`bilinear_sampler`."""
    if transform_type != "affine" or sampler_type != "bilinear":
        raise ValueError("spatial_transformer supports transform_type="
                         "'affine' with sampler_type='bilinear'")
    if target_shape is None:
        target_shape = _as_nd(data).shape[2:]
    grid = grid_generator(loc, "affine", tuple(target_shape))
    return bilinear_sampler(data, grid)


@_public
def khatri_rao(*matrices):
    """Column-wise Kronecker product (reference:
    src/operator/contrib/krprod.cc — mx.nd.khatri_rao). All inputs are
    (r_i, k); output ((Πr_i), k)."""
    if not matrices:
        raise ValueError("khatri_rao needs at least one matrix")
    nds = tuple(_as_nd(m) for m in matrices)
    bad = False
    for m in nds:                      # ndim first: 0-d has no shape[-1]
        bad = bad or m.ndim != 2
    if not bad:
        bad = len({m.shape[-1] for m in nds}) != 1
    if bad:
        raise ValueError(
            "khatri_rao needs 2-D matrices with a COMMON column count; "
            f"got shapes {[m.shape for m in nds]}")

    def impl(*ms):
        out = ms[0]
        for m in ms[1:]:
            k = out.shape[1]
            out = (out[:, None, :] * m[None, :, :]).reshape(-1, k)
        return out

    return invoke("khatri_rao", impl, nds)
