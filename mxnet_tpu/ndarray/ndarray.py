"""NDArray — the imperative tensor.

Reference parity (leezu/mxnet): ``include/mxnet/ndarray.h`` /
``src/ndarray/ndarray.cc`` (NDArray + Chunk) and
``python/mxnet/ndarray/ndarray.py`` (operator sugar, indexing, asnumpy).

Design (tpu-first): an NDArray wraps a ``jax.Array`` (device buffer with
async semantics) — the Chunk/engine-var machinery of the reference collapses
into PJRT buffer futures. ``wait_to_read`` == ``block_until_ready``;
``asnumpy`` is the sync point. Under ``hybridize`` tracing the same class
wraps jax tracers, so one op implementation serves both execution modes
(the reference's "one op set, two runtimes" shape, SURVEY.md section 0).

numpy semantics are adopted from day one (``mx.np``-style: zero-dim arrays,
elementwise ``__eq__``) per SURVEY.md section 7 step 2.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as _np

from .. import engine
from ..base import MXNetError
from ..bulk import PendingBuffer
from ..context import Context, cpu, current_context

__all__ = ["NDArray", "from_jax", "waitall"]


def _jax_device_of(data: Any):
    try:
        devs = data.devices()
        if len(devs) == 1:
            return next(iter(devs))
    except Exception:
        pass
    return None


def _ctx_from_data(data: Any) -> Context:
    dev = _jax_device_of(data)
    if dev is None:
        return current_context()
    if dev.platform == "cpu":
        return Context("cpu", dev.id)
    return Context("tpu", dev.id)


def _raw(x: Any) -> Any:
    return x._data if isinstance(x, NDArray) else x


def _raw_key(key: Any) -> Any:
    if isinstance(key, tuple):
        return tuple(_raw(k) for k in key)
    return _raw(key)


class NDArray:
    """A multi-dimensional array on a device context.

    Create with ``mx.np.array`` / ``mx.np.zeros`` / etc.; direct construction
    from any array-like is also supported: ``NDArray([[1, 2], [3, 4]])``.
    """

    # _concrete_shadow: the concrete buffer while _data is temporarily a
    # tracer under gluon._bind_params (host-side layer logic — BatchNorm
    # virgin-stats resolution — inspects values mid-trace through it)
    # _grad_ready_cb: per-leaf grad-ready hook — backward_arrays calls
    # it (with this array) the moment this leaf's gradient finalizes
    # mid-backward; installed by gluon.Parameter.set_grad_ready_cb so
    # the overlapped kvstore scheduler can stream reduction buckets
    # while backward is still running
    __slots__ = ("_buf", "_ctx", "_ag_node", "_ag_out_idx", "_grad",
                 "_grad_req", "_fresh_grad", "_grad_ready_cb",
                 "_concrete_shadow", "__weakref__")

    # numpy interop priority (beats np.ndarray in mixed expressions)
    __array_priority__ = 1000.0

    # ------------------------------------------------------------------
    # The buffer slot. Under eager-op bulking (mxnet_tpu/bulk.py) _buf
    # may hold a PendingBuffer promise instead of a concrete jax array;
    # reading ._data is a materialization point (flushes the owning
    # segment), which is what makes bulking transparent to every
    # consumer in the codebase. Shape/dtype queries peek at _buf and
    # never force.
    # ------------------------------------------------------------------
    @property
    def _data(self) -> Any:
        d = self._buf
        if type(d) is PendingBuffer:
            d = d.force("host_read")
            self._buf = d
        return d

    @_data.setter
    def _data(self, value: Any) -> None:
        self._buf = value

    def _materialize(self, reason: str) -> Any:
        """Like reading ``._data`` but attributing the flush to
        ``reason`` (e.g. 'mutation' for in-place writes)."""
        d = self._buf
        if type(d) is PendingBuffer:
            d = d.force(reason)
            self._buf = d
        return d

    def _adopt(self, other: "NDArray") -> "NDArray":
        """In-place rebind to ``other``'s buffer WITHOUT forcing a
        pending promise (the in-place operator sugar: ``x += y`` stays
        bulked). Matches the historical ``self._data = other._data``
        contract exactly: only the buffer moves — autograd attachments
        of ``self`` are untouched.  A RECORDED pending value must
        materialize here: leaving it promised would let a later bulked
        consumer differentiate through the in-place op via the segment
        node ref, where per-op dispatch kept that node unreachable."""
        buf = other._buf
        if type(buf) is PendingBuffer and buf.value is None \
                and other._on_tape:
            buf.force("autograd")
        self._buf = other._buf
        return self

    def __init__(self, data: Any, ctx: Optional[Context] = None,
                 dtype: Any = None, _wrap: bool = False) -> None:
        if _wrap:
            self._data = data
            self._ctx = ctx
        else:
            if isinstance(data, NDArray):
                data = data._data
            ctx = ctx or current_context()
            if isinstance(data, _np.ndarray) and not isinstance(
                    data, jax.Array) and ctx.jax_device.platform != "cpu":
                # the TPU runtime reads the host buffer AFTER device_put
                # returns: on the v5e host, writing to the numpy array
                # right after construction changed the device value in
                # 40 of 40 trials (1-256 MB, PR 21 chip probe).  Snapshot
                # it so caller-side mutation cannot change the array's
                # value (immutability contract)
                data = _np.array(data, dtype=dtype, copy=True)
            arr = jnp.asarray(data, dtype=dtype)
            if not _is_tracer(arr):
                arr = jax.device_put(arr, ctx.jax_device)
            self._data = arr
            self._ctx = ctx
        self._ag_node = None
        self._ag_out_idx = 0
        self._grad = None
        self._grad_req = "null"
        self._fresh_grad = False
        self._grad_ready_cb = None

    # ------------------------------------------------------------------
    # Basic properties
    # ------------------------------------------------------------------
    @property
    def shape(self) -> tuple:
        return tuple(self._buf.shape)   # peek: never forces a pending buf

    @property
    def dtype(self):
        return _np.dtype(self._buf.dtype)

    @property
    def size(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def ndim(self) -> int:
        return self._buf.ndim

    @property
    def context(self) -> Context:
        if self._ctx is not None:
            return self._ctx
        self._ctx = _ctx_from_data(self._data)
        return self._ctx

    ctx = context
    device = context

    @property
    def stype(self) -> str:
        return "default"

    @property
    def T(self) -> "NDArray":
        return self.transpose()

    @property
    def grad(self) -> Optional["NDArray"]:
        """Gradient buffer attached via :meth:`attach_grad`."""
        return self._grad

    @property
    def _on_tape(self) -> bool:
        if self._ag_node is not None or self._grad_req != "null":
            return True
        # a promised buffer from a recorded bulked op joins the tape at
        # flush time — report it as recorded already
        buf = getattr(self, "_buf", None)   # sparse wrappers: no slot
        if type(buf) is PendingBuffer and buf.value is None:
            seg = buf.segment
            if not seg.flushed and buf.ni < len(seg.nodes):
                return seg.nodes[buf.ni].tainted
        return False

    # ------------------------------------------------------------------
    # Sync / transfer (reference: WaitToRead / asnumpy / CopyFromTo)
    # ------------------------------------------------------------------
    def wait_to_read(self) -> None:
        """Block until this array's value is computed (WaitForVar)."""
        engine._sync_and_translate(self._data)

    def asnumpy(self) -> _np.ndarray:
        """Copy to a numpy array — a synchronization point.

        Returns a WRITABLE, C-contiguous array (the reference's asnumpy
        copied into a fresh buffer): ``numpy.asarray`` of a jax array is
        a read-only view, whose `.reshape()` silently COPIES and breaks
        the mutate-a-view pattern (e.g. finite-difference
        perturbation)."""
        out = _np.asarray(engine._sync_and_translate(self._data))
        if not (out.flags.writeable and out.flags.c_contiguous):
            out = _np.array(out, order="C")
        return out

    def item(self) -> Any:
        return self.asnumpy().item()

    def tolist(self):
        return self.asnumpy().tolist()

    def asscalar(self) -> Any:
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.item()

    def astype(self, dtype, copy: bool = True) -> "NDArray":
        if not copy and _np.dtype(self._data.dtype) == _np.dtype(dtype):
            return self
        from .register import invoke
        dt = dtype
        return invoke("astype", lambda a: a.astype(dt), (self,))

    def copy(self) -> "NDArray":
        from .register import invoke
        return invoke("copy", lambda a: a + 0, (self,))

    def copyto(self, other) -> "NDArray":
        """Copy into another NDArray (in place) or onto a Context."""
        if isinstance(other, Context):
            return self.as_in_context(other)
        if isinstance(other, NDArray):
            other._data = jax.device_put(self._data, other.context.jax_device)
            return other
        raise TypeError(f"copyto does not support type {type(other)}")

    def as_in_context(self, ctx: Context) -> "NDArray":
        """Return a copy on ``ctx`` (same array if already there)."""
        if self.context == ctx and not _is_tracer(self._buf):
            return self
        from .._tape import is_recording
        from .register import invoke
        if is_recording() and self._on_tape:
            # Route through the op layer so the transfer is a proper tape
            # node (device_put is differentiable under jax).
            dev = ctx.jax_device
            return invoke("as_in_context",
                          lambda a: jax.device_put(a, dev), (self,), ctx=ctx)
        data = self._data
        if not _is_tracer(data):
            data = jax.device_put(data, ctx.jax_device)
        return NDArray(data, ctx=ctx, _wrap=True)

    as_in_ctx = as_in_context
    to_device = as_in_context

    def as_nd_ndarray(self) -> "NDArray":
        return self

    def as_np_ndarray(self) -> "NDArray":
        return self

    def detach(self) -> "NDArray":
        """Return a view detached from the autograd graph."""
        return NDArray(self._data, ctx=self._ctx, _wrap=True)

    # ------------------------------------------------------------------
    # Autograd (reference: MXAutogradMarkVariables / NDArray::Backward)
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req: str = "write", stype: str = None) -> None:
        """Allocate a gradient buffer updated by ``backward()``."""
        if grad_req not in ("write", "add", "null"):
            raise ValueError(f"invalid grad_req {grad_req!r}")
        self._grad_req = grad_req
        if grad_req != "null":
            z = jnp.zeros(self.shape, dtype=self._data.dtype)
            if not _is_tracer(self._data):
                z = jax.device_put(z, self.context.jax_device)
            self._grad = NDArray(z, ctx=self._ctx, _wrap=True)
        else:
            self._grad = None

    def _write_grad(self, cot: Any) -> None:
        if self._grad_req == "null":
            return
        from .._tape import RowSparseCot
        if isinstance(cot, RowSparseCot):
            # sparse-grad leaf (Embedding sparse_grad): the grad buffer
            # becomes a fresh RowSparseNDArray each backward, as in the
            # reference's kRowSparseStorage gradient contract
            from .sparse import RowSparseNDArray
            rsp = RowSparseNDArray(cot.values, cot.indices, cot.shape,
                                   ctx=self._ctx)
            if self._grad_req == "add" and self._grad is not None and \
                    getattr(self._grad, "stype", "default") == "row_sparse":
                merged = RowSparseCot(
                    jnp.concatenate([self._grad._sp_indices, cot.indices]),
                    jnp.concatenate([self._grad._sp_values, cot.values]),
                    cot.shape)
                rsp = RowSparseNDArray(merged.values, merged.indices,
                                       cot.shape, ctx=self._ctx)
            self._grad = rsp._canonical()
            self._fresh_grad = True
            return
        if cot is None:
            cot = jnp.zeros(self.shape, dtype=self._data.dtype)
        if cot.dtype != self._data.dtype:
            cot = cot.astype(self._data.dtype)
        # Write INTO the buffer allocated by attach_grad (rebinding its
        # _data) so references held to ``x.grad`` stay live — the
        # reference's in-place grad contract that optimizers rely on.
        if self._grad is None:
            self._grad = NDArray(cot, ctx=self._ctx, _wrap=True)
        elif self._grad_req == "add":
            self._grad._data = self._grad._data + cot
        else:
            self._grad._data = cot
        self._fresh_grad = True  # staleness marker read by Trainer
        engine.track(self._grad._data)

    def backward(self, out_grad: Optional["NDArray"] = None,
                 retain_graph: bool = False, train_mode: bool = True) -> None:
        """Compute gradients of this array w.r.t. attached variables."""
        from .._tape import backward_arrays
        backward_arrays([self], [out_grad], retain_graph=retain_graph)

    # ------------------------------------------------------------------
    # Indexing
    # ------------------------------------------------------------------
    def __getitem__(self, key) -> "NDArray":
        from .register import invoke
        k = _raw_key(key)
        nd_keys = [x for x in (key if isinstance(key, tuple) else (key,))
                   if isinstance(x, NDArray)]
        if nd_keys:
            # advanced indexing with NDArray indices: pass them as real
            # inputs so gather is differentiable w.r.t. self only
            def impl(a, *idx):
                it = iter(idx)
                kk = tuple(next(it) if isinstance(x, NDArray) else _raw(x)
                           for x in (key if isinstance(key, tuple) else (key,)))
                return a[kk if isinstance(key, tuple) else kk[0]]
            return invoke("getitem", impl, (self, *nd_keys))
        return invoke("getitem", lambda a: a[k], (self,))

    def __setitem__(self, key, value) -> None:
        v = _raw(value)
        k = _raw_key(key)
        # in-place write to a promised buffer: a mutation hazard — the
        # pending segment flushes before the write lands
        d = self._materialize("mutation")
        if isinstance(k, slice) and k == slice(None) and not isinstance(v, (int, float, complex)):
            # x[:] = v  — full overwrite, keep dtype
            self._data = jnp.broadcast_to(jnp.asarray(v, dtype=d.dtype),
                                          self.shape)
        else:
            self._data = d.at[k].set(v)
        engine.track(self._data)

    def __len__(self) -> int:
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self) -> bool:
        if self.size != 1:
            raise ValueError("The truth value of an array with more than one "
                             "element is ambiguous.")
        return bool(self.item())

    def __float__(self) -> float:
        return float(self.item())

    def __int__(self) -> int:
        return int(self.item())

    def __index__(self) -> int:
        return int(self.item())

    def __repr__(self) -> str:
        if _is_tracer(self._data):
            return f"NDArray(<traced {self.shape} {self._data.dtype}>)"
        return (f"{_np.array2string(self.asnumpy())}\n"
                f"<NDArray {self.shape} @{self.context}>")

    __hash__ = None  # elementwise __eq__ => unhashable, like numpy

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def __dlpack__(self, **kw):
        return self._data.__dlpack__(**kw)

    # ------------------------------------------------------------------
    # Arithmetic sugar (delegates to the op layer for autograd support)
    # ------------------------------------------------------------------
    def _binop(self, name, other, swap=False):
        from . import ops
        fn = getattr(ops, name)
        return fn(other, self) if swap else fn(self, other)

    def __add__(self, o): return self._binop("add", o)
    def __radd__(self, o): return self._binop("add", o, True)
    def __sub__(self, o): return self._binop("subtract", o)
    def __rsub__(self, o): return self._binop("subtract", o, True)
    def __mul__(self, o): return self._binop("multiply", o)
    def __rmul__(self, o): return self._binop("multiply", o, True)
    def __truediv__(self, o): return self._binop("divide", o)
    def __rtruediv__(self, o): return self._binop("divide", o, True)
    def __floordiv__(self, o): return self._binop("floor_divide", o)
    def __rfloordiv__(self, o): return self._binop("floor_divide", o, True)
    def __mod__(self, o): return self._binop("mod", o)
    def __rmod__(self, o): return self._binop("mod", o, True)
    def __pow__(self, o): return self._binop("power", o)
    def __rpow__(self, o): return self._binop("power", o, True)
    def __matmul__(self, o): return self._binop("matmul", o)
    def __rmatmul__(self, o): return self._binop("matmul", o, True)
    def __neg__(self): return self._binop("multiply", -1)
    def __pos__(self): return self
    def __abs__(self):
        from . import ops
        return ops.abs(self)

    def __eq__(self, o): return self._binop("equal", o)
    def __ne__(self, o): return self._binop("not_equal", o)
    def __lt__(self, o): return self._binop("less", o)
    def __le__(self, o): return self._binop("less_equal", o)
    def __gt__(self, o): return self._binop("greater", o)
    def __ge__(self, o): return self._binop("greater_equal", o)

    def __iadd__(self, o):
        return self._adopt(self._binop("add", o))

    def __isub__(self, o):
        return self._adopt(self._binop("subtract", o))

    def __imul__(self, o):
        return self._adopt(self._binop("multiply", o))

    def __itruediv__(self, o):
        return self._adopt(self._binop("divide", o))

    # ------------------------------------------------------------------
    # Method forms of common ops
    # ------------------------------------------------------------------
    def _op(self, name, *args, **kw):
        from . import ops
        return getattr(ops, name)(self, *args, **kw)

    def reshape(self, *shape, **kw):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return self._op("reshape", shape)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return self._op("transpose", axes if axes else None)

    def swapaxes(self, a1, a2): return self._op("swapaxes", a1, a2)
    def flatten(self): return self.reshape(self.shape[0] if self.ndim else 1, -1) \
        if self.ndim > 1 else self.reshape(-1)
    def ravel(self): return self.reshape(-1)
    def expand_dims(self, axis): return self._op("expand_dims", axis)
    def squeeze(self, axis=None): return self._op("squeeze", axis)
    def broadcast_to(self, shape): return self._op("broadcast_to", shape)
    def broadcast_like(self, other): return self._op("broadcast_to", other.shape)
    def repeat(self, repeats, axis=None): return self._op("repeat", repeats, axis)
    def tile(self, reps): return self._op("tile", reps)
    def split(self, *a, **kw): return self._op("split", *a, **kw)
    def flip(self, axis=None): return self._op("flip", axis)
    def take(self, indices, axis=None, mode="clip"):
        return self._op("take", indices, axis, mode)
    def slice_axis(self, axis, begin, end):
        return self._op("slice_axis", axis=axis, begin=begin, end=end)

    def sum(self, axis=None, keepdims=False, dtype=None):
        return self._op("sum", axis=axis, keepdims=keepdims, dtype=dtype)
    def mean(self, axis=None, keepdims=False, dtype=None):
        return self._op("mean", axis=axis, keepdims=keepdims, dtype=dtype)
    def max(self, axis=None, keepdims=False):
        return self._op("max", axis=axis, keepdims=keepdims)
    def min(self, axis=None, keepdims=False):
        return self._op("min", axis=axis, keepdims=keepdims)
    def prod(self, axis=None, keepdims=False):
        return self._op("prod", axis=axis, keepdims=keepdims)
    def argmax(self, axis=None): return self._op("argmax", axis=axis)
    def argmin(self, axis=None): return self._op("argmin", axis=axis)
    def norm(self, ord=None, axis=None, keepdims=False):
        return self._op("norm", ord=ord, axis=axis, keepdims=keepdims)
    def cumsum(self, axis=None): return self._op("cumsum", axis=axis)
    def var(self, axis=None, keepdims=False):
        return self._op("var", axis=axis, keepdims=keepdims)
    def std(self, axis=None, keepdims=False):
        return self._op("std", axis=axis, keepdims=keepdims)

    def dot(self, other): return self._op("dot", other)
    def abs(self): return self._op("abs")
    def exp(self): return self._op("exp")
    def log(self): return self._op("log")
    def sqrt(self): return self._op("sqrt")
    def square(self): return self._op("square")
    def sign(self): return self._op("sign")
    def round(self, decimals=0): return self._op("round", decimals)
    def floor(self): return self._op("floor")
    def ceil(self): return self._op("ceil")
    def clip(self, a_min=None, a_max=None): return self._op("clip", a_min, a_max)
    def maximum(self, other): return self._op("maximum", other)
    def minimum(self, other): return self._op("minimum", other)
    def sigmoid(self): return self._op("sigmoid")
    def tanh(self): return self._op("tanh")
    def relu(self): return self._op("relu")
    def softmax(self, axis=-1): return self._op("softmax", axis=axis)
    def log_softmax(self, axis=-1): return self._op("log_softmax", axis=axis)
    def one_hot(self, depth, **kw): return self._op("one_hot", depth, **kw)
    def astype_like(self, other): return self.astype(other.dtype)
    def zeros_like(self): return self._op("zeros_like")
    def ones_like(self): return self._op("ones_like")

    def tostype(self, stype: str) -> "NDArray":
        if stype == "default":
            return self
        from . import sparse as _sparse
        if stype == "row_sparse":
            return _sparse._dense_to_rsp(self)
        if stype == "csr":
            return _sparse._dense_to_csr(self)
        raise MXNetError(f"unknown storage type {stype!r}")


def _is_tracer(x: Any) -> bool:
    return isinstance(x, jax.core.Tracer)


def from_jax(data: Any, ctx: Optional[Context] = None) -> NDArray:
    """Zero-copy wrap of an existing jax array / tracer."""
    return NDArray(data, ctx=ctx, _wrap=True)


def waitall() -> None:
    engine.waitall()
