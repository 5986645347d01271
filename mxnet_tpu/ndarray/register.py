"""Op invocation core and registry.

Reference parity (leezu/mxnet): the NNVM registry + imperative dispatch —
``NNVM_REGISTER_OP`` / ``Imperative::Invoke`` / ``PushFCompute``
(``src/imperative/imperative_utils.h``) and the Python generated-op layer
(``python/mxnet/ndarray/register.py``).

Design (tpu-first): every op is a pure function over jax arrays. Imperative
execution dispatches it directly (jax's C++ eager path + async device
streams stand in for the ThreadedEngine). When autograd is recording and an
input is on the tape, the op executes under ``jax.vjp`` and a TapeNode holds
the pullback. Under hybridize, the same Python op functions run with tracers
inside one ``jax.jit`` — the analog of CachedOp bulking, with XLA doing the
fusion the reference got from pointwise-fusion RTC codegen.
"""
from __future__ import annotations

import functools
import threading
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from .. import bulk as _bulk
from .. import engine
from .. import faults as _faults
from .. import metrics as _metrics
from .._tape import TapeNode, is_recording
from ..base import register_env

__all__ = ["invoke", "register_op", "get_op", "list_ops", "wrap_out",
           "exec_cache_stats"]

register_env("MXNET_IMPERATIVE_EXEC_CACHE", "auto",
             "Per-op executable cache for imperative dispatch: 1 "
             "forces it on (the exec-cache CI sanitizer), 0 forces it "
             "off, 'auto' (default) lets the runtime decide per op. "
             "Read once per process; the CI 'exec-cache' variant runs "
             "the core suite with it forced on.")

# name -> {"fn": public python fn, "doc": ...}
_OP_REGISTRY: Dict[str, Dict[str, Any]] = {}

# flipped by mxnet_tpu.amp.init()/disable(); checked on the hot dispatch
# path before importing the amp module at all
_amp_state = {"active": False}

# flipped by mxnet_tpu.profiler.set_state(); same hot-path pattern
_profiler_state = {"on": False}


# id -> hook fn; multiple Monitors may collect concurrently
_monitor_state = {"hooks": {}}

# flipped on while any multi-device-sharded array is alive (see
# mark_mesh_resident); single-device programs never pay the per-op
# sharding scan, and the flag drops back off once the last mesh-resident
# buffer is garbage-collected (a discarded GPTPipe doesn't tax every
# later eager op)
_mesh_state = {"active": False, "live": 0, "pinned": False}


def mark_mesh_resident(holder) -> None:
    """Track ``holder`` — an object whose lifetime upper-bounds some
    multi-device-sharded buffer (the NDArray wrapper of a mesh-placed
    parameter, a mesh-sharded op output, a raw mesh array): the per-op
    harmonization scan stays enabled only while at least one such holder
    is alive. Register wrappers rather than raw buffers when the buffer
    is swapped in place every step (SPMDTrainer parameters)."""
    _mesh_state["active"] = True
    try:
        weakref.finalize(holder, _mesh_release)
        _mesh_state["live"] += 1
    except TypeError:
        # not weakref-able: latch conservatively (previous behavior)
        _mesh_state["pinned"] = True


def _mesh_release() -> None:
    _mesh_state["live"] -= 1
    if _mesh_state["live"] <= 0 and not _mesh_state["pinned"]:
        _mesh_state["active"] = False

# ---------------------------------------------------------------------------
# TPU-resident imperative mode: per-op executable cache
# (reference: src/imperative/imperative.cc Imperative::Invoke → PushFCompute —
# the per-op kernel dispatch; here each op becomes ONE cached XLA executable
# instead of a chain of per-primitive eager dispatches, and its outputs are
# real device buffers, so eager ops run on the accelerator and hybridize/jit
# consumers need no host->device re-transfer)
# ---------------------------------------------------------------------------

# (op name, closure token, recording) -> jitted callable. jax.jit handles
# the per-shape/dtype executable keying internally; the closure token keys
# the op's attributes (closure cell values), so behaviorally-equal closures
# share one traced wrapper. LRU-bounded: evicting a wrapper releases its
# compiled executables.
from collections import OrderedDict  # noqa: E402

_EXEC_CACHE: "OrderedDict[Any, Callable]" = OrderedDict()
_EXEC_CACHE_CAP = 1024

# Ops whose attrs churn (e.g. an annealed python scalar bound into the
# closure every step) would otherwise pay a fresh trace+compile per call;
# after _CHURN_LIMIT distinct attr tokens for one (op, code) we stop
# caching that op and dispatch it eagerly.
_CHURN_COUNT: Dict[Any, int] = {}
_CHURN_EAGER: set = set()
_CHURN_LIMIT = 16

# MXNET_IMPERATIVE_EXEC_CACHE: "auto" (cache when an input lives on an
# accelerator device), "1" (always — also on CPU; used by tests), "0" (off)
_exec_mode = {"value": None}


class _UnhashableAttr(Exception):
    pass


def _attr_token(v: Any, depth: int = 0) -> Any:
    """A hashable token for a closure cell value, or raise."""
    if depth > 4:
        raise _UnhashableAttr
    if v is None or isinstance(v, (str, bytes)):
        return v
    if isinstance(v, slice):
        return ("slice", _attr_token(v.start, depth + 1),
                _attr_token(v.stop, depth + 1),
                _attr_token(v.step, depth + 1))
    if isinstance(v, (bool, int, float)):
        # dict-key equality conflates 0 == 0.0 == False; the numeric TYPE
        # is part of the op's behavior (output dtype), so key it too
        return (type(v).__name__, v)
    if isinstance(v, (tuple, list)):
        return (type(v).__name__,) + tuple(
            _attr_token(x, depth + 1) for x in v)
    if isinstance(v, dict):
        try:
            return tuple(sorted(
                (k, _attr_token(x, depth + 1)) for k, x in v.items()))
        except TypeError:  # mixed-type keys don't sort
            raise _UnhashableAttr from None
    if isinstance(v, type) or hasattr(v, "dtype") and not hasattr(v, "shape"):
        return str(v)
    import numpy as _onp
    if isinstance(v, _onp.dtype):
        return str(v)
    if callable(v) and hasattr(v, "__code__"):
        return _closure_token(v, depth + 1)
    if callable(v):
        # code-less callable (jnp ufunc, builtin): stable object identity
        # is the token — the common case for scalar-operand binary ops
        try:
            hash(v)
            return v
        except TypeError:
            raise _UnhashableAttr from None
    raise _UnhashableAttr


def _closure_token(fn: Callable, depth: int = 0) -> Any:
    """Key an op impl closure by code object + attribute cell values.
    Cells holding arrays/objects (e.g. PRNG keys) are unhashable — such
    ops fall back to plain eager dispatch."""
    code = getattr(fn, "__code__", None)
    if code is None:
        # not a Python function (jnp ufunc, builtin): the stable callable
        # object itself is the token
        try:
            hash(fn)
        except TypeError:
            raise _UnhashableAttr from None
        return fn
    cells = fn.__closure__ or ()
    try:
        return (code,) + tuple(
            _attr_token(c.cell_contents, depth) for c in cells)
    except ValueError:  # empty (not-yet-bound) cell
        raise _UnhashableAttr from None


def _exec_cache_mode() -> str:
    mode = _exec_mode["value"]
    if mode is None:
        import os
        mode = os.environ.get("MXNET_IMPERATIVE_EXEC_CACHE", "auto")
        _exec_mode["value"] = mode
    return mode


def _should_use_exec_cache(arrays) -> bool:
    mode = _exec_cache_mode()
    if mode == "0":
        return False
    for a in arrays:
        if isinstance(a, jax.core.Tracer):
            return False  # inside a hybridize/jit trace: run inline
    if mode == "1":
        return True
    for a in arrays:
        if isinstance(a, jax.Array):
            try:
                devs = a.devices()
            except Exception:
                continue
            if any(d.platform != "cpu" for d in devs):
                return True
    return False


# Trace-failure poison, keyed by the FULL signature including input
# avals: a failure is often input-dependent (a weak-typed scalar, a
# shape-special-cased host check), so poisoning the (op, attrs) key
# alone would force ops eager forever even for inputs that trace fine.
# _EAGER_OPS is the cheap first-level guard so the hot path only builds
# an aval key for ops that have EVER failed.  Both are LRU-bounded
# (incremental eviction — a wholesale clear would make every known-bad
# signature re-pay a doomed trace at once); a stale _EAGER_OPS entry
# after its signatures evicted only costs an extra aval-key probe.
_EAGER_OPS: "OrderedDict[Any, None]" = OrderedDict()   # (name,tok,rec)
_EAGER_SIGS: "OrderedDict[Any, None]" = OrderedDict()  # (..., avalkey)
_EAGER_OPS_CAP = 1024
_EAGER_SIGS_CAP = 4096


def _aval_key(arrays) -> tuple:
    return tuple((tuple(getattr(a, "shape", ())),
                  str(getattr(a, "dtype", type(a).__name__)),
                  bool(getattr(a, "weak_type", False))) for a in arrays)


def _cached_exec(name: str, impl: Callable, arrays, record: bool):
    """Try the per-op executable cache; returns the raw result or None
    when the op must take the eager path."""
    try:
        token = _closure_token(impl)
    except _UnhashableAttr:
        return None  # attrs hold arrays/objects (e.g. PRNG keys)
    churn_key = (name, token[0] if isinstance(token, tuple) else token)
    if churn_key in _CHURN_EAGER:
        return None
    key = (name, token, record)
    if key in _EAGER_OPS and \
            (name, token, record, _aval_key(arrays)) in _EAGER_SIGS:
        return None     # this exact signature failed to trace before
    fn = _EXEC_CACHE.get(key)
    if fn is not None:
        _EXEC_CACHE.move_to_end(key)
        # a hit means attrs repeat — not the per-call-varying pattern the
        # churn guard targets
        _CHURN_COUNT.pop(churn_key, None)
        _metrics.COMPILE_HITS.inc()
    if fn is None:
        n = _CHURN_COUNT[churn_key] = _CHURN_COUNT.get(churn_key, 0) + 1
        if n > _CHURN_LIMIT:
            # attrs vary call-to-call (e.g. annealed scalars): caching
            # would trace+compile every step — stay eager from now on
            _CHURN_EAGER.add(churn_key)
            return None
        if record:
            # jax.vjp's pullback is a tree_util.Partial: its residuals
            # come back as device buffers and the pullback itself stays
            # jit-able for backward
            fn = jax.jit(lambda *xs: jax.vjp(impl, *xs))
        else:
            fn = jax.jit(impl)
        _EXEC_CACHE[key] = fn
        if len(_EXEC_CACHE) > _EXEC_CACHE_CAP:
            _EXEC_CACHE.popitem(last=False)
        _metrics.EXEC_CACHE_SIZE.set(len(_EXEC_CACHE))
    try:
        return fn(*arrays)
    except jax.errors.JAXTypeError:
        # op needs concrete values for THESE inputs (data-dependent host
        # checks, e.g. mode='raise' bounds validation on a weak-typed
        # scalar) — poison only this (op, attrs, avals) signature; other
        # input signatures keep using the cached wrapper
        _EAGER_OPS[key] = None
        if len(_EAGER_OPS) > _EAGER_OPS_CAP:
            _EAGER_OPS.popitem(last=False)
        _EAGER_SIGS[(name, token, record, _aval_key(arrays))] = None
        if len(_EAGER_SIGS) > _EAGER_SIGS_CAP:
            _EAGER_SIGS.popitem(last=False)
        return None


def _dispatch(name: str, impl: Callable, arrays, record: bool,
              eager_only: bool = False):
    """Run ``impl`` over raw arrays, through the per-op executable cache
    when eligible. Returns ``(outs, vjp_fn_or_None, cached)``."""
    if not eager_only and _should_use_exec_cache(arrays):
        result = _cached_exec(name, impl, arrays, record)
        if result is not None:
            if record:
                return result[0], result[1], True
            return result, None, True
    if record:
        outs, vjp_fn = jax.vjp(impl, *arrays)
        return outs, vjp_fn, False
    return impl(*arrays), None, False


def _harmonize_mesh_placement(arrays):
    """Eager ops mixing mesh-sharded operands (e.g. parameters placed by
    SPMDTrainer) with fresh single-device arrays: replicate the latter
    onto the same mesh so XLA can dispatch one program.  The mesh is one
    logical device in this framework's model (the reference instead
    *errors* on cross-context ops; here the mesh placement is an
    implementation detail the user never chose)."""
    mesh = None
    for a in arrays:
        if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer):
            sh = a.sharding
            if getattr(sh, "mesh", None) is not None \
                    and sh.num_devices > 1:
                mesh = sh.mesh
                break
    if mesh is None:
        return arrays
    out = []
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    for a in arrays:
        if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer) \
                and a.sharding.num_devices == 1:
            a = jax.device_put(a, rep)
        out.append(a)
    return out


# Re-entrancy guard for monitor hooks (per thread): a hook's own
# stat_func dispatches ops (abs/mean) through invoke(), and without the
# guard those instrumentation-internal dispatches re-fire every OTHER
# registered hook (Monitor._in_hook only protects the monitor against
# itself) — their stats then publish into mxnet_monitor_stat as if they
# were model ops.  Same rule the tracing layer follows by mirroring
# spans into the profiler via a direct event append instead of dispatch.
_monitor_tls = threading.local()


def _fire_monitor_hooks(name, outputs) -> None:
    if getattr(_monitor_tls, "active", False):
        return
    _monitor_tls.active = True
    try:
        for hook in list(_monitor_state["hooks"].values()):
            hook(name, outputs)
    finally:
        _monitor_tls.active = False


def exec_cache_stats() -> Dict[str, float]:
    """Snapshot of the compile-cache surface for tools and the serving
    health endpoint: per-op executable-cache size, eager-path hits, and
    process-wide XLA backend compiles (the jax.monitoring miss counter —
    covers hybridize/jit programs too, which is what serving warmup
    bounds)."""
    stats = {"size": len(_EXEC_CACHE),
             "hits": _metrics.COMPILE_HITS.value,
             "misses": _metrics.COMPILE_MISSES.value}
    stats.update(_bulk.bulk_stats())
    return stats


def register_op(name: str, fn: Callable, doc: str = "") -> Callable:
    """Register a public op under ``name`` (NNVM_REGISTER_OP analog)."""
    _OP_REGISTRY[name] = {"fn": fn, "doc": doc or (fn.__doc__ or "")}
    return fn


def get_op(name: str) -> Callable:
    """Look up a registered op by name (``mx.nd.op``-style access)."""
    return _OP_REGISTRY[name]["fn"]


def list_ops() -> List[str]:
    """All registered op names (``MXListAllOpNames`` analog)."""
    return sorted(_OP_REGISTRY)


def _ndarray_cls():
    from .ndarray import NDArray
    return NDArray


def wrap_out(data: Any, ctx=None) -> Any:
    """Wrap a raw jax array (or tracer) into an NDArray and track it."""
    NDArray = _ndarray_cls()
    out = NDArray(data, ctx=ctx, _wrap=True)
    engine.track(data)
    return out


def invoke_with_custom_vjp(name: str, impl: Callable,
                           inputs: Sequence[Any], vjp_fn: Callable,
                           ctx=None) -> Any:
    """Like :func:`invoke` but with a hand-written pullback instead of
    ``jax.vjp`` — for ops whose gradient is not a jax type (e.g. the
    row-sparse embedding grad). ``vjp_fn(out_cot) -> per-input cotangents``
    (None entries are skipped). Single-output ops only."""
    arrays = [x._data for x in inputs]
    _metrics.inc_op(name)
    if _faults._ARMED:
        _faults.maybe_fault("dispatch.op", op=name)
    if _mesh_state["active"]:
        arrays = _harmonize_mesh_placement(arrays)

    timer = None
    if _profiler_state["on"]:
        from ..profiler import op_timer
        timer = op_timer(name)
        if timer is not None:
            timer.__enter__()
    try:
        out = impl(*arrays)
    finally:
        if timer is not None:
            timer.__exit__()

    wrapped = wrap_out(out, ctx=ctx)
    if is_recording() and any(x._on_tape for x in inputs):
        node = TapeNode(name, vjp_fn, inputs,
                        [(tuple(out.shape), out.dtype)])
        node.out_arrays = [weakref.ref(wrapped)]
        wrapped._ag_node = node
        wrapped._ag_out_idx = 0

    if _monitor_state["hooks"]:
        _fire_monitor_hooks(name, (wrapped,))

    return wrapped


def invoke(name: str, impl: Callable, inputs: Sequence[Any],
           ctx=None, eager_only: bool = False) -> Any:
    """Execute op ``impl`` over NDArray ``inputs``; handle autograd.

    ``impl`` takes the raw arrays positionally (attrs must already be bound
    into the closure) and returns one array or a tuple of arrays.
    ``eager_only`` ops (data-dependent host-side behavior, e.g. bounds
    validation with mode='raise') bypass the per-op executable cache.
    """
    _metrics.inc_op(name)
    if _faults._ARMED:
        _faults.maybe_fault("dispatch.op", op=name)

    # Lazy bulking (mxnet_tpu/bulk.py): on the plain eager fast path the
    # op joins the pending segment and returns promised NDArrays without
    # dispatching anything. Paths that need per-op visibility or concrete
    # per-op arrays (amp casts, profiler timers, monitor hooks, mesh
    # harmonization, naive engine) keep per-op dispatch.
    # MXNET_IMPERATIVE_EXEC_CACHE=1 (the forced per-op-cache sanitizer
    # mode, ci/run.sh exec-cache) keeps per-op dispatch observable.
    if (not eager_only and not _amp_state["active"]
            and not _profiler_state["on"] and not _monitor_state["hooks"]
            and not _mesh_state["active"] and _exec_cache_mode() != "1"
            and _bulk.active()):
        try:
            token = _closure_token(impl)
        except _UnhashableAttr:
            token = None
        out = _bulk.try_append(name, impl, token, inputs, ctx)
        if out is not _bulk.NOT_BULKED:
            return out

    arrays = [x._data for x in inputs]
    if _mesh_state["active"]:
        arrays = _harmonize_mesh_placement(arrays)

    if _amp_state["active"]:
        from ..amp import apply_cast_policy
        arrays = apply_cast_policy(name, arrays)

    timer = None
    if _profiler_state["on"]:
        from ..profiler import op_timer
        timer = op_timer(name)
        if timer is not None:
            timer.__enter__()

    record = is_recording() and any(x._on_tape for x in inputs)
    try:
        outs, vjp_fn, cached = _dispatch(name, impl, arrays, record,
                                         eager_only)
    finally:
        if timer is not None:
            timer.__exit__()

    single = not isinstance(outs, (tuple, list))
    outs_t = (outs,) if single else tuple(outs)

    wrapped = [wrap_out(o, ctx=ctx) for o in outs_t]

    if _mesh_state["active"]:
        # mesh-sharded outputs keep the harmonization scan alive for as
        # long as THEY live (downstream eager ops still mix them with
        # fresh single-device arrays after the producing trainer/pipeline
        # is discarded)
        for w in wrapped:
            o = w._data
            if isinstance(o, jax.Array) and not isinstance(
                    o, jax.core.Tracer) \
                    and getattr(o.sharding, "num_devices", 1) > 1:
                mark_mesh_resident(w)

    if record:
        avals = [(tuple(o.shape), o.dtype) for o in outs_t]
        node = TapeNode(name, vjp_fn, inputs, avals, out_is_tuple=not single)
        node.jit_pull = cached
        node.out_arrays = [weakref.ref(w) for w in wrapped]
        for i, w in enumerate(wrapped):
            w._ag_node = node
            w._ag_out_idx = i

    if _monitor_state["hooks"]:
        _fire_monitor_hooks(name, tuple(wrapped))

    return wrapped[0] if single else tuple(wrapped)
