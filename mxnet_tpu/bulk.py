"""Lazy eager-op bulking: fuse imperative dispatch into segment-compiled
XLA executables.

Reference parity (leezu/mxnet): the dependency engine's op bulking
(``Imperative`` bulk scope + ``CachedOp``, ``MXNET_EXEC_BULK_EXEC_*``) —
the reference batches runs of imperative engine pushes into one engine op
so Python returns immediately and the engine dispatches once per segment.

Design (tpu-first): eager dispatch no longer executes each op as its own
XLA program.  ``register.invoke`` appends a node (op name, impl, input
bindings, attr token) to a per-thread *pending segment* and returns
NDArrays backed by :class:`PendingBuffer` promises (shape/dtype known via
``jax.eval_shape``; no device work dispatched yet).  A segment flushes
when

* a host read forces materialization (``asnumpy``/``item``/any direct
  ``._data`` access — shape/dtype peeks do NOT force),
* it reaches ``MXNET_BULK_MAX_OPS`` ops (1 = bulking off, the previous
  per-op dispatch),
* an un-jittable op or an in-place write to a pending buffer arrives,
* ``engine.waitall()`` or an autograd ``backward()`` boundary requires
  it.

On flush the segment's nodes (appended in program order, which IS a
topological order of the segment DAG) are traced once as a single
function, jitted, and the compiled callable is cached by *segment
signature* (op sequence + attr tokens + input binding structure + output
liveness; ``jax.jit`` keys input avals internally).  Steady-state
training replays one fused executable per segment instead of N per-op
dispatches, and XLA fuses elementwise chains (optimizer updates, loss
arithmetic, LSTM cell math) that previously crossed executable
boundaries.

Autograd: with ``MXNET_BULK_AUTOGRAD=fused`` (default) recorded ops stay
bulked — the flush runs ``jax.vjp`` over the whole segment function and
installs ONE TapeNode whose pullback maps segment-output cotangents to
segment-input cotangents (the fused analog of per-op TapeNodes; backward
dispatches it as one compiled program).  A recorded op consuming a
*pending un-recorded* value flushes first, so gradients never flow
through ops the per-op tape would not have recorded.  ``off`` forces
per-op dispatch inside ``record()`` scopes.

Mutation hazards: external inputs are captured *by value* at append time
(the raw buffer object), so a later in-place rebind of an input wrapper
cannot corrupt a pending node — eager call-time semantics are preserved
without ordering constraints.  Writing INTO a wrapper whose own buffer
is still pending (``x[k] = v``) flushes first (reason ``mutation``).

Numerics: a fused segment lets XLA contract patterns like ``a*b + c``
into a single FMA, so results can differ from per-op dispatch in the
last ulp — the same property hybridize has today.  Replays of the same
segment signature are bit-identical; see docs/performance.md.
"""
from __future__ import annotations

import threading
import weakref
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as _onp

from . import engine
from . import metrics as _metrics
from . import tracing as _tracing
from ._tape import TapeNode, is_recording
from .base import MXNetError, getenv, register_env

__all__ = ["PendingBuffer", "NOT_BULKED", "active", "max_ops",
           "set_max_ops", "flush_all", "flush_current", "flush_holding",
           "flush_recorded", "backward_segments_mode", "bulk_stats",
           "reset_caches"]

register_env("MXNET_BULK_MAX_OPS", 16,
             "Eager-op bulking segment size: imperative dispatch defers "
             "up to this many ops into one pending segment, then compiles "
             "and dispatches them as a single fused XLA executable. 1 "
             "disables bulking (per-op dispatch, the pre-bulking "
             "behavior). engine.set_bulk_size()/engine.bulk scope the "
             "same knob at runtime.")
register_env("MXNET_BULK_AUTOGRAD", "fused",
             "Bulking behavior inside autograd.record() scopes: 'fused' "
             "(default) keeps recorded ops bulked and differentiates the "
             "whole segment with one jax.vjp (one fused TapeNode); 'off' "
             "forces per-op dispatch while recording.")
register_env("MXNET_BULK_BACKWARD_SEGMENTS", "param",
             "Backward granularity of fused-autograd bulking: 'param' "
             "(default) closes the recorded segment whenever the op "
             "stream crosses a parameter boundary (a recorded op "
             "consuming a fresh attach_grad leaf) once the segment has "
             "captured at least MXNET_KV_BUCKET_BYTES of parameter "
             "bytes (the coalescing floor: layers smaller than one "
             "reduction bucket share a segment, so tiny models keep one "
             "fused backward and deep models cannot blow the segment "
             "cache).  The resulting chain of per-layer TapeNodes "
             "replays backward layer-by-layer in reverse, so parameter "
             "gradients materialize incrementally and the overlapped "
             "kvstore scheduler can stream reduction buckets DURING "
             "backward instead of only under optimizer compute.  'off' "
             "keeps the whole recorded run as one fused segment "
             "(pre-segmentation behavior).  Re-cut segments move XLA "
             "fusion (FMA) boundaries: losses match the monolithic "
             "backward to float ulp, replays of the same mode are "
             "bit-identical (see docs/performance.md).")

# runtime-settable copies of the env knobs (env read once, lazily)
_state: Dict[str, Any] = {"max_ops": None, "autograd": None}

# distinct-signature churn guard: an op whose attr token varies call to
# call would force a fresh segment compile per flush — after this many
# cache-missing flushes containing the same (op, code) the op is
# dispatched per-op instead (a cache hit clears its count).
_CHURN_LIMIT = 16

_SEG_CACHE_CAP = 256        # compiled segment executables (LRU)
_AVAL_CACHE_CAP = 4096      # eval_shape results
_POISON_CAP = 1024          # trace-failed signatures

NOT_BULKED = object()       # try_append result: caller takes per-op path


def max_ops() -> int:
    n = _state["max_ops"]
    if n is None:
        n = _state["max_ops"] = int(getenv("MXNET_BULK_MAX_OPS", 16))
    return n


def set_max_ops(n: int) -> int:
    """Set the bulking segment cap; returns the previous value.
    ``n <= 1`` disables bulking for subsequent ops (it does not flush
    an already-pending segment by itself)."""
    prev = max_ops()
    _state["max_ops"] = int(n)
    return prev


def _autograd_mode() -> str:
    m = _state["autograd"]
    if m is None:
        m = _state["autograd"] = getenv("MXNET_BULK_AUTOGRAD", "fused")
    return m


def backward_segments_mode() -> str:
    """'param' cuts recorded segments at parameter boundaries (subject
    to the coalescing floor), 'off' keeps one fused backward segment.
    Read live (not cached like max_ops): the dist-comm smoke and tests
    A/B the modes within one process."""
    m = getenv("MXNET_BULK_BACKWARD_SEGMENTS", "param")
    return m if m in ("param", "off") else "param"


def _segment_floor_bytes() -> int:
    """The coalescing floor for param-boundary cuts: segments keep
    absorbing layers until they hold one reduction bucket's worth of
    parameter bytes (MXNET_KV_BUCKET_BYTES), so per-layer cutting on a
    deep model of small layers neither blows the segment LRU nor
    recompiles per step — the segment grid stays O(model_bytes /
    bucket_bytes)."""
    try:
        return max(1, int(getenv("MXNET_KV_BUCKET_BYTES", 4 << 20)))
    except (TypeError, ValueError):
        return 4 << 20


def active() -> bool:
    """Bulking engages only when the segment cap exceeds one op and the
    engine is not in fully-synchronous NaiveEngine mode."""
    return max_ops() > 1 and not engine.is_naive()


# ---------------------------------------------------------------------------
# Pending buffers and segment nodes
# ---------------------------------------------------------------------------

_FAILED = object()   # PendingBuffer.value after a failed flush


class PendingBuffer:
    """A promised device buffer: the not-yet-materialized output of a
    pending segment node.  Carries the abstract value (shape/dtype/
    weak_type from ``jax.eval_shape``) so shape queries and dispatch
    never force materialization; any concrete read flushes the owning
    segment, after which :attr:`value` holds the real array."""

    __slots__ = ("shape", "dtype", "weak_type", "segment", "ni", "oi",
                 "value", "wref", "__weakref__")

    def __init__(self, sds: Any, segment: "Segment", ni: int,
                 oi: int) -> None:
        self.shape = tuple(sds.shape)
        self.dtype = sds.dtype
        self.weak_type = bool(getattr(sds, "weak_type", False))
        self.segment = segment
        self.ni = ni            # producing node index within the segment
        self.oi = oi            # output index within that node
        self.value = None       # concrete array once flushed
        self.wref = None        # weakref to the owning NDArray wrapper

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def force(self, reason: str = "host_read") -> Any:
        """Materialize: flush the owning segment (idempotent) and return
        the concrete array."""
        v = self.value
        if v is None:
            self.segment.flush(reason)
            v = self.value
        if v is _FAILED or v is None:
            raise MXNetError(
                "pending bulked segment failed to execute; the promised "
                f"buffer (shape {self.shape}, {self.dtype}) is lost: "
                f"{self.segment.error or 'unknown error'}")
        return v


class _Node:
    __slots__ = ("name", "impl", "token", "ins", "single", "out_sds",
                 "out_phs", "tainted", "ctx")

    def __init__(self, name, impl, token, ins, single, out_sds, tainted,
                 ctx=None):
        self.name = name
        self.impl = impl
        self.token = token
        self.ins = ins            # tuple of ('e', ext_idx) | ('n', ni, oi)
        self.single = single      # impl returned one array, not a tuple
        self.out_sds = out_sds    # tuple of ShapeDtypeStruct
        self.out_phs: List[Any] = []   # weakrefs to PendingBuffers
        self.tainted = tainted    # recorded: on the autograd tape
        self.ctx = ctx            # Context the outputs report


# live (unflushed) segments, all threads — waitall/backward flush them
_REG_LOCK = threading.Lock()
_LIVE_SEGMENTS: Dict[int, "Segment"] = {}

_TLS = threading.local()


class Segment:
    """One pending run of bulked ops owned by a single dispatching
    thread.  Appends happen only on the owner thread; a flush may come
    from any thread (cross-thread read, waitall) — both serialize on
    ``lock``."""

    __slots__ = ("nodes", "ext", "ext_wrappers", "ext_ids", "flushed",
                 "lock", "error", "leaf_ids", "param_bytes", "n_tainted",
                 "bwd_mode", "bwd_floor", "__weakref__")

    def __init__(self) -> None:
        self.nodes: List[_Node] = []
        self.ext: List[Any] = []            # captured raw input arrays
        self.ext_wrappers: List[Any] = []   # NDArray wrappers (tape ids)
        self.ext_ids: Dict[Tuple[int, int], int] = {}  # (wrapper,raw) ids
        self.flushed = False
        self.lock = threading.RLock()
        self.error: Optional[str] = None
        # backward segmentation bookkeeping: which attach_grad leaves
        # (parameters) this segment captured, and their raw byte total —
        # the param-boundary cut in try_append fires only once
        # param_bytes clears the coalescing floor
        self.leaf_ids: set = set()
        self.param_bytes = 0
        self.n_tainted = 0                  # recorded nodes appended
        # segmentation knobs resolved lazily, ONCE per segment (a
        # per-op env read would tax the whole dispatch hot path; a
        # segment's mode must not flip mid-build anyway, and tests
        # that monkeypatch the env get fresh segments constantly)
        self.bwd_mode: Optional[str] = None
        self.bwd_floor = 0
        with _REG_LOCK:
            _LIVE_SEGMENTS[id(self)] = self

    def ext_index(self, wrapper: Any, raw: Any) -> int:
        # key on BOTH identities: the same wrapper can be re-captured
        # with a different buffer if it was rebound between appends
        # (e.g. checkpoint restore set_data while a segment from the
        # settle forward was still pending) — each (wrapper, value)
        # pair is its own external input, value captured at append time
        key = (id(wrapper), id(raw))
        idx = self.ext_ids.get(key)
        if idx is None:
            idx = len(self.ext)
            self.ext_ids[key] = idx
            self.ext.append(raw)
            self.ext_wrappers.append(wrapper)
            if getattr(wrapper, "_grad_req", "null") != "null" and \
                    id(wrapper) not in self.leaf_ids:
                self.leaf_ids.add(id(wrapper))
                try:
                    self.param_bytes += int(raw.size) * int(
                        getattr(raw.dtype, "itemsize", 4))
                except Exception:   # noqa: BLE001 - sizeless capture
                    pass
        return idx

    # -- flush ---------------------------------------------------------
    def flush(self, reason: str) -> None:
        with self.lock:
            if self.flushed:
                return
            self.flushed = True
            nodes = self.nodes
            if not nodes:
                self._release()
                return
            _metrics.inc_bulk_segment(reason)
            _metrics.BULK_OPS_PER_SEGMENT.observe(len(nodes))
            # liveness: a node output is returned only while its promise
            # is still reachable (someone can still read it); dead
            # promises become XLA dead code inside the fused program
            returns: List[Tuple[int, int]] = []
            phs: List[PendingBuffer] = []
            for ni, node in enumerate(nodes):
                for oi, ref in enumerate(node.out_phs):
                    ph = ref()
                    if ph is not None and ph.value is None:
                        returns.append((ni, oi))
                        phs.append(ph)
            try:
                if returns:
                    # child of whatever step/backward span is active;
                    # reason="param_boundary" marks the per-layer
                    # backward segments
                    with _tracing.child_span("bulk.segment",
                                             reason=reason,
                                             ops=len(nodes)):
                        self._execute(nodes, returns, phs)
            except BaseException as exc:
                self.error = f"{type(exc).__name__}: {exc}"
                for ph in phs:
                    if ph.value is None:
                        ph.value = _FAILED
                raise
            finally:
                self._release()

    def _release(self) -> None:
        self.nodes = []
        self.ext = []
        self.ext_wrappers = []
        self.ext_ids = {}
        with _REG_LOCK:
            _LIVE_SEGMENTS.pop(id(self), None)

    def _execute(self, nodes, returns, phs) -> None:
        any_tainted = any(n.tainted for n in nodes)
        sig = (tuple((n.name, n.token, n.ins) for n in nodes),
               tuple(returns), any_tainted)
        if sig in _SEG_POISON:
            self._run_sequential(nodes, returns, phs)
            return
        fn = _SEG_CACHE.get(sig)
        if fn is not None:
            _SEG_CACHE.move_to_end(sig)
            _metrics.BULK_CACHE_HITS.inc()
            # attrs repeat: these ops are not the per-call-varying
            # pattern the churn guard targets
            for n in nodes:
                _CHURN_COUNT.pop((n.name, _token_head(n.token)), None)
        else:
            _metrics.BULK_CACHE_MISSES.inc()
            seg_fn = _make_seg_fn(
                [(n.impl, n.ins, n.single) for n in nodes], returns)
            if any_tainted:
                fn = jax.jit(lambda *xs: jax.vjp(seg_fn, *xs))
            else:
                fn = jax.jit(seg_fn)
            _SEG_CACHE[sig] = fn
            if len(_SEG_CACHE) > _SEG_CACHE_CAP:
                _SEG_CACHE.popitem(last=False)
            _metrics.BULK_CACHE_SIZE.set(len(_SEG_CACHE))
            # churn guard: count only NOVEL attr tokens per (op, code)
            # with no intervening cache hit — that is the signature of a
            # per-call-varying attr (annealed scalar) compiling a fresh
            # segment every flush.  Segment-shape diversity with
            # repeated tokens does not count.
            for n in nodes:
                key = (n.name, _token_head(n.token))
                seen = _CHURN_SEEN.get(key)
                if seen is None:
                    seen = _CHURN_SEEN[key] = set()
                if n.token not in seen:
                    if len(seen) > 4 * _CHURN_LIMIT:
                        seen.clear()
                    seen.add(n.token)
                    c = _CHURN_COUNT[key] = _CHURN_COUNT.get(key, 0) + 1
                    if c > _CHURN_LIMIT:
                        _BULK_EAGER.add(key)
        try:
            if any_tainted:
                outs, vjp_fn = fn(*self.ext)
            else:
                outs, vjp_fn = fn(*self.ext), None
        except jax.errors.JAXTypeError:
            # the segment needs concrete values somewhere eval_shape did
            # not catch — poison this signature and run per-op eagerly
            _SEG_POISON.add(sig)
            _SEG_CACHE.pop(sig, None)
            self._run_sequential(nodes, returns, phs)
            return
        for ph, arr in zip(phs, outs):
            ph.value = arr
            engine.track(arr)
        if any_tainted:
            self._install_tape(nodes, phs, vjp_fn)

    def _install_tape(self, nodes, phs, vjp_fn) -> None:
        """One fused TapeNode for the whole segment: cotangents of the
        live outputs map to cotangents of the external inputs.  Only
        recorded (tainted) outputs join the tape; un-recorded slots keep
        a None out_arrays entry so a cotangent later accumulated on such
        a wrapper (it has no _ag_node) can never leak into this node's
        pullback — matching per-op semantics where un-recorded ops have
        no TapeNode at all."""
        avals = [(ph.shape, ph.dtype) for ph in phs]
        node = TapeNode("_bulk_segment", vjp_fn, list(self.ext_wrappers),
                        avals, out_is_tuple=True)
        node.jit_pull = True
        outs: List[Any] = []
        for idx, ph in enumerate(phs):
            w = ph.wref() if ph.wref is not None else None
            if nodes[ph.ni].tainted and w is not None and w._buf is ph:
                outs.append(weakref.ref(w))
                w._ag_node = node
                w._ag_out_idx = idx
            else:
                outs.append(None)
        node.out_arrays = outs

    def _run_sequential(self, nodes, returns, phs) -> None:
        """Per-op fallback for trace-poisoned segments: execute node by
        node (per-op TapeNodes for recorded ops), preserving exact
        pre-bulking semantics."""
        vals: List[Tuple[Any, ...]] = []
        tape_nodes: Dict[int, TapeNode] = {}
        # (ni, oi) -> stub wrapper standing in for an intermediate whose
        # NDArray died (or was rebound) before the flush.  Stubs are
        # SHARED across consumers and linked to their producer's
        # TapeNode, so the backward chain through a dead temporary stays
        # connected exactly as per-op dispatch kept it (the consumer's
        # TapeNode.inputs strong ref keeps the stub alive).
        stubs: Dict[Tuple[int, int], Any] = {}

        def _node_wrapper(ni, oi):
            ref = nodes[ni].out_phs[oi]()
            w = ref.wref() if ref is not None and ref.wref is not None \
                else None
            if w is not None and w._buf is ref:
                return w
            stub = stubs.get((ni, oi))
            if stub is None:
                stub = _ndarray_cls()(vals[ni][oi], _wrap=True)
                ptn = tape_nodes.get(ni)
                if ptn is not None:
                    stub._ag_node = ptn
                    stub._ag_out_idx = oi
                    ptn.out_arrays[oi] = weakref.ref(stub)
                stubs[(ni, oi)] = stub
            return stub

        for ni, node in enumerate(nodes):
            ins = [self.ext[d[1]] if d[0] == "e" else vals[d[1]][d[2]]
                   for d in node.ins]
            if node.tainted:
                outs, vjp_fn = jax.vjp(node.impl, *ins)
            else:
                outs, vjp_fn = node.impl(*ins), None
            outs_t = (outs,) if node.single else tuple(outs)
            vals.append(outs_t)
            if node.tainted:
                in_wrappers = [
                    self.ext_wrappers[d[1]] if d[0] == "e"
                    else _node_wrapper(d[1], d[2]) for d in node.ins]
                tn = TapeNode(node.name, vjp_fn, in_wrappers,
                              [(tuple(o.shape), o.dtype) for o in outs_t],
                              out_is_tuple=not node.single)
                tn.out_arrays = [None] * len(outs_t)
                tape_nodes[ni] = tn
        for (ni, oi), ph in zip(returns, phs):
            ph.value = vals[ni][oi]
            engine.track(ph.value)
            tn = tape_nodes.get(ni)
            if tn is not None:
                w = ph.wref() if ph.wref is not None else None
                if w is not None and w._buf is ph:
                    tn.out_arrays[oi] = weakref.ref(w)
                    w._ag_node = tn
                    w._ag_out_idx = oi


def _make_seg_fn(plan, returns):
    """Build the single traced function for a segment.  ``plan`` holds
    (impl, input bindings, single-output flag) per node in program
    (= topological) order; the function is pure over the external
    arrays, so one jax.jit covers the whole run of ops."""
    def seg_fn(*ext):
        vals = []
        for impl, ins, single in plan:
            args = [ext[d[1]] if d[0] == "e" else vals[d[1]][d[2]]
                    for d in ins]
            out = impl(*args)
            vals.append((out,) if single else tuple(out))
        return tuple(vals[ni][oi] for ni, oi in returns)
    return seg_fn


def _token_head(token):
    return token[0] if isinstance(token, tuple) and token else token


class _LruSet:
    """Bounded membership set with incremental (oldest-first) eviction
    — a wholesale clear at cap would make every known entry re-pay its
    discovery cost at once (the clear-at-cap cliff this PR removes from
    the SPMD scalar cache)."""

    __slots__ = ("_cap", "_d")

    def __init__(self, cap: int) -> None:
        self._cap = cap
        self._d: "OrderedDict[Any, None]" = OrderedDict()

    def __contains__(self, key: Any) -> bool:
        if key in self._d:
            self._d.move_to_end(key)
            return True
        return False

    def __len__(self) -> int:
        return len(self._d)

    def add(self, key: Any) -> None:
        self._d[key] = None
        if len(self._d) > self._cap:
            self._d.popitem(last=False)

    def clear(self) -> None:
        self._d.clear()


# segment signature -> compiled callable (LRU)
_SEG_CACHE: "OrderedDict[Any, Callable]" = OrderedDict()
_SEG_POISON = _LruSet(_POISON_CAP)
_CHURN_COUNT: Dict[Any, int] = {}
_CHURN_SEEN: Dict[Any, set] = {}
_BULK_EAGER: set = set()

# (name, token, in-aval key) -> (tuple_of_sds, single) | _AVAL_BAD (LRU)
_AVAL_CACHE: "OrderedDict[Any, Any]" = OrderedDict()
_AVAL_BAD = object()

_ND_CLS = [None]


def _ndarray_cls():
    cls = _ND_CLS[0]
    if cls is None:
        from .ndarray.ndarray import NDArray
        cls = _ND_CLS[0] = NDArray
    return cls


def _sds_of(raw: Any) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(
        _onp.shape(raw), getattr(raw, "dtype", None),
        weak_type=bool(getattr(raw, "weak_type", False)))


def _out_avals(name, impl, token, in_sds):
    """eval_shape with memoization — the per-append cost collapses to a
    dict lookup in steady state."""
    key = (name, token, tuple((s.shape, str(s.dtype), s.weak_type)
                              for s in in_sds))
    got = _AVAL_CACHE.get(key)
    if got is not None:
        _AVAL_CACHE.move_to_end(key)
    else:
        if len(_AVAL_CACHE) > _AVAL_CACHE_CAP:
            _AVAL_CACHE.popitem(last=False)
        try:
            out = jax.eval_shape(impl, *in_sds)
        except Exception:   # noqa: BLE001 - any trace failure => eager op
            _AVAL_CACHE[key] = got = _AVAL_BAD
        else:
            single = not isinstance(out, (tuple, list))
            outs = (out,) if single else tuple(out)
            if any(not isinstance(o, jax.ShapeDtypeStruct) for o in outs):
                _AVAL_CACHE[key] = got = _AVAL_BAD
            else:
                _AVAL_CACHE[key] = got = (outs, single)
    return got


# ---------------------------------------------------------------------------
# The dispatch hook
# ---------------------------------------------------------------------------

def _current_segment() -> Segment:
    seg = getattr(_TLS, "segment", None)
    if seg is None or seg.flushed:
        seg = _TLS.segment = Segment()
    return seg


def _flush_pending_inputs(inputs, reason: str) -> None:
    for x in inputs:
        buf = getattr(x, "_buf", None)   # sparse wrappers have no slot
        if type(buf) is PendingBuffer and buf.value is None:
            buf.segment.flush(reason)


def try_append(name: str, impl: Callable, token: Any,
               inputs: Sequence[Any], ctx: Any) -> Any:
    """Append one op to the calling thread's pending segment; returns
    the promised NDArray output(s), or NOT_BULKED when the op must take
    the per-op path (the caller then reads ``._data``, which flushes any
    pending inputs)."""
    if token is None:   # attrs hold arrays/objects: unjittable closure
        _flush_pending_inputs(inputs, "unjittable")
        return NOT_BULKED
    if (name, _token_head(token)) in _BULK_EAGER:
        _flush_pending_inputs(inputs, "unjittable")
        return NOT_BULKED

    recording = is_recording()
    if recording and _autograd_mode() != "fused":
        _flush_pending_inputs(inputs, "autograd")
        return NOT_BULKED

    seg = _current_segment()
    # resolve inputs: concrete ext captures vs in-segment node refs
    resolved: List[Tuple] = []   # ('e', wrapper, raw) | ('n', ni, oi)
    in_sds: List[Any] = []
    tainted = False
    for x in inputs:
        buf = getattr(x, "_buf", None)
        if buf is None:
            # sparse wrappers (no raw buffer slot): per-op path — their
            # dense fallback warning and storage handling stay intact
            _flush_pending_inputs(inputs, "unjittable")
            return NOT_BULKED
        if type(buf) is PendingBuffer:
            if buf.value is None and buf.segment is seg \
                    and not seg.flushed \
                    and not (recording and (x._ag_node is not None
                                            or x._grad_req != "null")):
                try:
                    node = seg.nodes[buf.ni]
                except IndexError:
                    # raced a cross-thread flush that cleared the node
                    # list — the promise now has (or will have) a value
                    node = None
                if node is not None:
                    if recording and node.tainted:
                        tainted = True
                    resolved.append(("n", buf.ni, buf.oi))
                    in_sds.append(node.out_sds[buf.oi])
                    continue
            # Materialize (raises if that segment failed): the value was
            # flushed earlier, is pending on another thread's segment, or
            # carries an out-of-band tape attachment (autograd.Function
            # output, attach_grad mid-chain) whose node/leaf status is
            # invisible to the segment — it must participate as a real
            # external tape input.  Any stale node-ref entries this
            # leaves in `resolved` are discarded by the flushed-segment
            # retry below.
            buf = buf.force("autograd" if recording else "cross_thread")
        if isinstance(buf, jax.core.Tracer):
            return NOT_BULKED   # inside a hybridize/jit trace: run inline
        if recording and x._on_tape:
            tainted = True
        resolved.append(("e", x, buf))
        in_sds.append(_sds_of(buf))

    if tainted:
        # a recorded op must not consume a pending un-recorded value:
        # the fused vjp would differentiate through ops the per-op tape
        # never recorded — flush those first (they become concrete
        # external inputs, where the gradient correctly stops)
        try:
            mixed = any(d[0] == "n" and not seg.nodes[d[1]].tainted
                        for d in resolved)
        except IndexError:      # raced a cross-thread flush
            mixed = True
        if mixed:
            seg.flush("autograd")
            return try_append(name, impl, token, inputs, ctx)

        # per-layer backward segmentation (MXNET_BULK_BACKWARD_SEGMENTS
        # =param): a recorded op consuming a FRESH attach_grad leaf (a
        # parameter this segment has not captured) marks a layer
        # boundary.  Once the segment holds a reduction bucket's worth
        # of parameter bytes (the coalescing floor), close it — the
        # fused vjp chain then replays backward layer-by-layer in
        # reverse, each sub-segment's parameter gradients materialize
        # individually, and the overlapped kvstore scheduler streams
        # their buckets while the rest of backward still runs.
        if seg.n_tainted:
            mode = seg.bwd_mode
            if mode is None:
                mode = seg.bwd_mode = backward_segments_mode()
                seg.bwd_floor = _segment_floor_bytes()
            if mode == "param":
                fresh = any(
                    d[0] == "e"
                    and getattr(d[1], "_grad_req", "null") != "null"
                    and id(d[1]) not in seg.leaf_ids
                    for d in resolved)
                if fresh:
                    if seg.param_bytes >= seg.bwd_floor:
                        _metrics.inc_backward_segment("param_boundary")
                        seg.flush("param_boundary")
                        return try_append(name, impl, token, inputs,
                                          ctx)
                    _metrics.inc_backward_segment("coalesced")

    got = _out_avals(name, impl, token, in_sds)
    if got is _AVAL_BAD:
        _flush_pending_inputs(inputs, "unjittable")
        return NOT_BULKED
    out_sds, single = got

    if ctx is None:
        # promised wrappers need a Context that does not require reading
        # the (not yet existing) buffer: derive it per NODE — from the
        # op's own first concrete input, else inherited from the
        # producing node of its first in-segment input (a per-segment
        # ctx would mislabel outputs of later ops whose inputs live on
        # a different device)
        for d in resolved:
            if d[0] == "e":
                if d[1]._ctx is not None:
                    ctx = d[1]._ctx
                else:
                    from .ndarray.ndarray import _ctx_from_data
                    ctx = _ctx_from_data(d[2])
                break
        else:
            for d in resolved:
                if d[0] == "n":
                    try:
                        ctx = seg.nodes[d[1]].ctx
                    except IndexError:   # raced a cross-thread flush
                        ctx = None
                    break

    NDArray = _ndarray_cls()
    with seg.lock:
        if seg.flushed:     # raced with a cross-thread flush: retry
            return try_append(name, impl, token, inputs, ctx)
        ins = tuple(("e", seg.ext_index(d[1], d[2])) if d[0] == "e"
                    else d for d in resolved)
        node = _Node(name, impl, token, ins, single, out_sds, tainted,
                     ctx=ctx)
        seg.nodes.append(node)
        if tainted:
            seg.n_tainted += 1
        ni = len(seg.nodes) - 1
        wrapped = []
        for oi, sds in enumerate(out_sds):
            ph = PendingBuffer(sds, seg, ni, oi)
            node.out_phs.append(weakref.ref(ph))
            w = NDArray(ph, ctx=ctx, _wrap=True)
            ph.wref = weakref.ref(w)
            wrapped.append(w)
        if ni + 1 >= max_ops():
            seg.flush("max_ops")
    return wrapped[0] if single else tuple(wrapped)


# ---------------------------------------------------------------------------
# Flush entry points / stats
# ---------------------------------------------------------------------------

def flush_current(reason: str = "host_read") -> None:
    """Flush the calling thread's pending segment, if any."""
    seg = getattr(_TLS, "segment", None)
    if seg is not None and not seg.flushed:
        seg.flush(reason)


def flush_all(reason: str = "waitall") -> None:
    """Flush every live segment across all threads (waitall, backward,
    and buffer-donation barriers)."""
    with _REG_LOCK:
        segs = list(_LIVE_SEGMENTS.values())
    for seg in segs:
        seg.flush(reason)


def flush_holding(arrays: Any, reason: str = "mutation") -> None:
    """Targeted donation barrier: flush only the live segments that
    captured any of ``arrays`` (raw device buffers, matched by identity)
    as an external input, plus the calling thread's own segment.

    The per-step donation barriers (``SPMDTrainer.step``/``run_steps``,
    the gluon trainer's fused update) used to ``flush_all``: sound, but
    it force-segmented EVERY thread's pending work once per step —
    with the async input pipeline that meant the prefetch thread's
    in-build preprocessing segment was cut mid-batch at step cadence
    (serializing exactly the work the pipeline exists to overlap, and
    churning the segment cache with truncated signatures).  A segment
    that never captured a donated buffer cannot read deleted memory, so
    it may keep building; the caller's own segment is always flushed —
    it is the one that traced through the params being donated, and the
    id-scan would miss a buffer captured between scan and donation on
    this same thread."""
    ids = {id(a) for a in arrays if a is not None}
    flush_current(reason)
    with _REG_LOCK:
        segs = list(_LIVE_SEGMENTS.values())
    own = getattr(_TLS, "segment", None)
    for seg in segs:
        if seg is own or seg.flushed:
            continue
        with seg.lock:
            if any(id(raw) in ids for raw in seg.ext):
                seg.flush(reason)


def flush_recorded(reason: str = "autograd") -> None:
    """Autograd barrier: flush the calling thread's segment plus every
    live segment holding a RECORDED (tainted) node — those must install
    their fused TapeNodes before the tape is walked.  An unrecorded
    segment on another thread (the prefetch thread's in-build
    preprocessing, a serving worker between requests) has nothing on
    the tape and may keep building; any value of theirs this thread's
    graph consumed was already forced at the cross-thread read."""
    flush_current(reason)
    with _REG_LOCK:
        segs = list(_LIVE_SEGMENTS.values())
    own = getattr(_TLS, "segment", None)
    for seg in segs:
        if seg is own or seg.flushed:
            continue
        with seg.lock:
            if any(n.tainted for n in seg.nodes):
                seg.flush(reason)


def bulk_stats() -> Dict[str, float]:
    """Snapshot of the bulking surface (exec_cache_stats feeds this into
    tools and the serving health endpoint)."""
    return {
        "bulk_cache_size": len(_SEG_CACHE),
        "bulk_cache_hits": _metrics.BULK_CACHE_HITS.value,
        "bulk_cache_misses": _metrics.BULK_CACHE_MISSES.value,
    }


def reset_caches() -> None:
    """Flush pending work and drop every compiled-segment / aval / churn
    cache (test isolation)."""
    flush_all("waitall")
    _SEG_CACHE.clear()
    _SEG_POISON.clear()
    _CHURN_COUNT.clear()
    _CHURN_SEEN.clear()
    _BULK_EAGER.clear()
    _AVAL_CACHE.clear()
    _metrics.BULK_CACHE_SIZE.set(0)
