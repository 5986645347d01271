"""Block / HybridBlock — the neural-network module system.

Reference parity (leezu/mxnet): ``python/mxnet/gluon/block.py`` — ``Block``
(child/param registry via ``__setattr__``), ``HybridBlock`` (hybridize →
CachedOp; export), ``SymbolBlock`` analog via :func:`load_export`.

Design (tpu-first): ``hybridize()`` replaces the reference's
NNVM-trace-to-CachedOp (``src/imperative/cached_op.cc``) with a
``jax.jit``-compiled executable cached per input signature
(shapes/dtypes/train-flag). One trace captures forward; backward comes for
free through ``jax.vjp`` of the compiled callable, so a hybridized training
step runs as ONE fused XLA program each for fwd and bwd — the analog of
CachedOp's full fwd+bwd graph with op bulking, with XLA doing the fusion.
PRNG: the trace threads a threefry key argument so dropout stays pure
(``ndarray/random.py trace_key_scope``). ``static_alloc`` maps to buffer
donation, which XLA applies automatically where legal.
"""
from __future__ import annotations

import contextlib
import re
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as _np

from .. import base, engine
from .._tape import is_recording, is_training, set_training
from ..base import MXNetError, getenv, register_env
from ..context import Context, cpu, current_context
from ..ndarray.ndarray import NDArray, from_jax
from ..ndarray.register import invoke
from ..ndarray import random as _random
from .parameter import Constant, DeferredInitializationError, Parameter

__all__ = ["Block", "HybridBlock", "SymbolBlock", "nn_block_summary",
           "remat_call", "remat_stack"]

register_env(
    "MXNET_REMAT", 0,
    "Rematerialize (activation-checkpoint) transformer layers: forward "
    "saves only each layer's INPUT and backward recomputes its "
    "interior, cutting live-activation memory ~L-fold for ~1 extra "
    "forward of compute (jax.checkpoint per layer — the TPU-native "
    "memory/FLOPs trade). Engaged by the model-zoo encoder stacks "
    "(BERT, GPT) when set.")

_REMAT_LAST: List[Optional[bool]] = [None]

def _remat_enabled() -> bool:
    cur = bool(getenv("MXNET_REMAT", 0))
    if _REMAT_LAST[0] is None:
        _REMAT_LAST[0] = cur
    elif _REMAT_LAST[0] != cur:
        # toggling after a program compiled must re-trace, not replay
        # the stale executable (the same invariant the flash knobs keep
        # by resolving env outside the cached closure)
        _REMAT_LAST[0] = cur
        invalidate_cached_graphs()
    return cur


def remat_call(block, *args, key=None):
    """Run ``block(*args)`` under ``jax.checkpoint``: backward recomputes
    the block's interior from its inputs instead of saving every
    intermediate. ``args`` are NDArrays (or None placeholders, which
    pass through). ``key``: an explicit PRNG key scoped around the call
    so in-block dropout draws IDENTICAL randomness in the recompute —
    ambient stateful key draws would advance again and silently corrupt
    gradients, so callers with dropout must pass one."""
    present = [a is not None for a in args]
    arrays = [a._data for a in args if a is not None]

    def body(*arrs):
        it = iter(arrs)
        nd_args = [from_jax(next(it)) if p else None for p in present]
        if key is not None:
            with _random.trace_key_scope(key):
                out = block(*nd_args)
        else:
            out = block(*nd_args)
        return out._data

    return from_jax(jax.checkpoint(body)(*arrays))


def remat_stack(layers, x, *extra, dropout: float = 0.0):
    """Apply ``layers`` sequentially, each under :func:`remat_call` when
    ``MXNET_REMAT`` is set (plain loop otherwise). ``extra`` args (an
    attention mask, say) pass to every layer. ``dropout``: the layers'
    dropout rate — when active in training, each layer gets a
    deterministic folded key so the backward recompute draws identical
    masks. The single shared implementation behind the model-zoo
    encoder stacks."""
    if not _remat_enabled():
        for layer in layers:
            x = layer(x, *extra)
        return x
    base = (_random.split_key()
            if dropout and is_training() else None)
    for i, layer in enumerate(layers):
        key = jax.random.fold_in(base, i) if base is not None else None
        x = remat_call(layer, x, *extra, key=key)
    return x


class _ParamDict(OrderedDict):
    """Dict of name->Parameter with batch operations (reference:
    ``ParameterDict`` semantics on ``collect_params()`` result)."""

    def initialize(self, init: Any = None, ctx: Any = None,
                   force_reinit: bool = False, verbose: bool = False) -> None:
        for p in self.values():
            p.initialize(init=init, ctx=ctx, force_reinit=force_reinit)

    def zero_grad(self) -> None:
        for p in self.values():
            p.zero_grad()

    def setattr(self, name: str, value: Any) -> None:
        for p in self.values():
            setattr(p, name, value)

    def reset_ctx(self, ctx: Context) -> None:
        for p in self.values():
            p.reset_ctx(ctx)

    def save(self, filename: str) -> None:
        from ..ndarray_io import save_params
        save_params(filename, {k: v.data() for k, v in self.items()
                               if v.is_initialized
                               and getattr(v, "persistent", True)})

    def load(self, filename: str, ctx: Any = None,
             allow_missing: bool = False,
             ignore_extra: bool = False) -> None:
        from ..ndarray_io import load_params
        loaded = load_params(filename, ctx=ctx)
        for k, p in self.items():
            if k in loaded:
                p.set_data(loaded[k])
            elif not allow_missing and getattr(p, "persistent", True):
                raise MXNetError(f"Parameter {k} missing in file {filename}")
        if not ignore_extra:
            extra = set(loaded) - set(self)
            if extra:
                raise MXNetError(
                    f"File {filename} contains extra parameters: {sorted(extra)}")


class Block:
    """Base class for all neural network layers and models.

    Children and parameters register automatically on attribute assignment,
    mirroring the reference's ``Block.__setattr__`` registry.
    """

    def __init__(self, prefix: Optional[str] = None, params: Any = None) -> None:
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._reg_params: "OrderedDict[str, Parameter]" = OrderedDict()
        self._forward_hooks: List[Callable] = []
        self._forward_pre_hooks: List[Callable] = []
        self._prefix = prefix or ""

    # -- registry ----------------------------------------------------------
    def __setattr__(self, name: str, value: Any) -> None:
        if isinstance(value, Block):
            self.__dict__.setdefault("_children", OrderedDict())[name] = value
        elif isinstance(value, Parameter):
            self.__dict__.setdefault("_reg_params", OrderedDict())[name] = value
        super().__setattr__(name, value)

    def register_child(self, block: "Block", name: Optional[str] = None) -> None:
        self._children[name or str(len(self._children))] = block

    def register_parameter(self, name: str, param: Parameter) -> Parameter:
        self._reg_params[name] = param
        super().__setattr__(name, param)
        return param

    @property
    def params(self) -> _ParamDict:
        """This block's direct parameters (no children)."""
        return _ParamDict((n, p) for n, p in self._reg_params.items())

    def collect_params(self, select: Optional[str] = None) -> _ParamDict:
        """All parameters of self and descendants, keyed by attribute path
        (reference: ``Block.collect_params`` with regex select)."""
        out = _ParamDict()
        self._collect_params(out, prefix="")
        if select is not None:
            pat = re.compile(select)
            out = _ParamDict((k, v) for k, v in out.items() if pat.match(k))
        return out

    def _collect_params(self, out: _ParamDict, prefix: str) -> None:
        for name, p in self._reg_params.items():
            out[prefix + name] = p
        for cname, child in self._children.items():
            child._collect_params(out, prefix=f"{prefix}{cname}.")

    # -- lifecycle ---------------------------------------------------------
    def initialize(self, init: Any = None, ctx: Any = None,
                   verbose: bool = False, force_reinit: bool = False) -> None:
        self.collect_params().initialize(init=init, ctx=ctx,
                                         force_reinit=force_reinit)

    def cast(self, dtype: Any) -> None:
        for p in self.collect_params().values():
            p.cast(dtype)
        for child in self._children.values():
            pass  # params already covered by collect_params
        self._on_cast(dtype)

    def _on_cast(self, dtype: Any) -> None:
        for child in self._children.values():
            child._on_cast(dtype)

    def reset_ctx(self, ctx: Context) -> None:
        self.collect_params().reset_ctx(ctx)

    # -- persistence (format details in ndarray_io.py) ---------------------
    def save_parameters(self, filename: str, deduplicate: bool = False) -> None:
        """Save parameters by attribute path (reference:
        ``Block.save_parameters`` → .params file)."""
        self.collect_params().save(filename)

    def load_parameters(self, filename: str, ctx: Any = None,
                        allow_missing: bool = False,
                        ignore_extra: bool = False,
                        cast_dtype: bool = False) -> None:
        self.collect_params().load(filename, ctx=ctx,
                                   allow_missing=allow_missing,
                                   ignore_extra=ignore_extra)

    # -- hooks -------------------------------------------------------------
    def register_forward_hook(self, hook: Callable) -> None:
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook: Callable) -> None:
        self._forward_pre_hooks.append(hook)

    def apply(self, fn: Callable[["Block"], None]) -> "Block":
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def _epoch_sensitive(self) -> bool:
        """Does this block tree contain a layer whose host-side state can
        change the traced program (BatchNorm's virgin-stats flag)? Used
        to scope graph-epoch invalidation: blocks without such layers
        keep their compiled executables. Cached after the first walk."""
        cached = getattr(self, "_epoch_sensitive_cache", None)
        if cached is None:
            def walk(b) -> bool:
                if hasattr(b, "_stats_virgin"):
                    return True
                return any(walk(c) for c in b._children.values())
            cached = walk(self)
            self._epoch_sensitive_cache = cached
        return cached

    # -- execution ---------------------------------------------------------
    def __call__(self, *args: Any) -> Any:
        if args and isinstance(args[0], PreActivation) \
                and not getattr(type(self), "_consumes_preactivation", False):
            args = (args[0].materialize(),) + args[1:]
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args: Any) -> Any:
        raise NotImplementedError

    def summary(self, *inputs: Any) -> str:
        return nn_block_summary(self, *inputs)

    def __repr__(self) -> str:
        s = f"{type(self).__name__}("
        for name, child in self._children.items():
            child_repr = repr(child).replace("\n", "\n  ")
            s += f"\n  ({name}): {child_repr}"
        return s + ("\n)" if self._children else ")")


# bumped by layers whose HOST-side state changes the traced program
# (BatchNorm cold-start bootstrap): cached executables fold the epoch
# into their key, so the next call re-traces instead of replaying a
# stale graph
_GRAPH_EPOCH = [0]


def graph_epoch() -> int:
    # poll env-dependent trace knobs: a toggle between calls must bump
    # the epoch even though no trace (where the knob is read) has run
    _remat_enabled()
    base.poll_graph_knobs()
    return _GRAPH_EPOCH[0]


def invalidate_cached_graphs() -> None:
    _GRAPH_EPOCH[0] += 1


@contextlib.contextmanager
def _bind_params(params: Sequence[Parameter], arrays: Sequence[Any]):
    """Temporarily swap parameter buffers for traced arrays during jit
    tracing (how one forward implementation serves both runtimes).

    The concrete buffer is kept reachable as ``_concrete_shadow`` so
    host-side layer logic that must inspect actual VALUES mid-trace
    (BatchNorm virgin-stats resolution) can still see them."""
    saved = []
    for p, a in zip(params, arrays):
        saved.append(p._data._data)
        p._data._concrete_shadow = p._data._data
        p._data._data = a
    try:
        yield
    finally:
        for p, s in zip(params, saved):
            p._data._data = s
            try:
                del p._data._concrete_shadow
            except AttributeError:
                pass


def _collect_mutated(params: Sequence[Parameter],
                     bound_arrays: Sequence[Any]) -> List[Tuple[int, Any]]:
    """In-trace writes to parameter state (BatchNorm running stats) as
    ``(index, new_array)`` pairs — identity-compared against the arrays
    `_bind_params` bound, so it MUST run inside the ``_bind_params``
    scope, before the saved buffers are restored."""
    return [(i, p._data._data) for i, p in enumerate(params)
            if p._data._data is not bound_arrays[i]]


class HybridBlock(Block):
    """A Block that can be compiled to a single XLA executable.

    ``hybridize()`` turns subsequent calls into cached compiled programs
    keyed by input signature — the CachedOp analog. ``export()`` saves
    architecture + params for deployment.
    """

    def __init__(self, prefix: Optional[str] = None, params: Any = None) -> None:
        super().__init__(prefix, params)
        self._active = False
        self._cached_graph: Dict[tuple, Any] = {}
        self._flags: Dict[str, Any] = {}

    def hybridize(self, active: bool = True, static_alloc: bool = False,
                  static_shape: bool = False, **kwargs: Any) -> None:
        """Enable compiled execution (reference: ``HybridBlock.hybridize``;
        static_alloc ≙ XLA buffer donation, applied automatically).

        Note: hybridized calls rebind the buffers of input NDArrays (and
        parameters) in place to accelerator-resident copies the first time
        each is seen, so later consuming jit calls skip the host->device
        transfer; values are unchanged and later eager use stays valid.
        """
        self._active = active
        self._flags = dict(static_alloc=static_alloc,
                           static_shape=static_shape, **kwargs)
        self._cached_graph.clear()
        for child in self._children.values():
            if isinstance(child, HybridBlock):
                # children run inside the parent's trace; they stay eager
                # when called directly
                child._cached_graph.clear()

    def _ensure_shapes(self, *args: Any) -> None:
        """Run deferred shape inference by executing forward eagerly once
        if any parameter is still deferred."""
        deferred = [p for p in self.collect_params().values()
                    if not p.is_initialized and p._deferred_init is not None]
        if not deferred:
            return
        # A single eager forward resolves all deferred shapes via each
        # layer's infer-shape hooks.
        was = self._active
        self._active = False
        try:
            self.forward(*args)
        finally:
            self._active = was

    def optimize_for(self, x: Any, *args: Any,
                     backend: Optional[str] = None,
                     **kwargs: Any) -> "HybridBlock":
        """Apply a subgraph accelerator backend and warm-compile
        (reference ``optimize_for(backend)`` / ``MXNET_SUBGRAPH_BACKEND``).

        Built-in backends: 'xla' (default — hybridize + jit warm),
        'int8' (post-training quantization calibrated on ``x``), 'bf16'
        (AMP cast policy); more via ``mxnet_tpu.subgraph.register_backend``.
        Returns the optimized block (usually ``self``, mutated in place).
        """
        from ..subgraph import get_backend
        return get_backend(backend)(self, (x,) + args, **kwargs)

    def _make_traced(self, params: List[Parameter], train: bool,
                     cell: Dict[str, Any]) -> Callable:
        """Build the jittable closure shared by _call_cached and export:
        (rng_key, param_arrays, *inputs) -> flat output leaves, recording
        the output treedef into ``cell``."""
        block = self

        def traced(rng_key, param_arrays, *input_arrays):
            prev = set_training(train)
            try:
                with _bind_params(params, param_arrays), \
                        _random.trace_key_scope(rng_key):
                    inputs = [from_jax(a) for a in input_arrays]
                    out = block.forward(*inputs)
                    # BatchNorm running stats etc.: the reference updates
                    # them as a side effect of the cached graph
                    # (src/operator/nn/batch_norm); here they ride out as
                    # extra outputs and are written back by the caller
                    mutated = _collect_mutated(params, param_arrays)
            finally:
                set_training(prev)
            raw = jax.tree_util.tree_map(
                lambda o: o._data if isinstance(o, NDArray) else o, out,
                is_leaf=lambda o: isinstance(o, NDArray))
            leaves, treedef = jax.tree_util.tree_flatten(raw)
            cell["treedef"] = treedef
            cell["mutated_idx"] = [i for i, _ in mutated]
            return tuple(leaves) + tuple(a for _, a in mutated)

        return traced

    def _call_cached(self, *args: Any) -> Any:
        nd_args = [a if isinstance(a, NDArray) else NDArray(a) for a in args]
        self._ensure_shapes(*nd_args)
        from .parameter import dedupe_shared
        _, params = dedupe_shared(
            (k, p) for k, p in self.collect_params().items()
            if p.is_initialized)

        train = is_training()
        self._last_sig = [(tuple(a.shape), a.dtype) for a in nd_args]
        from ..ndarray.register import _amp_state
        amp_key = None
        if _amp_state["active"]:
            from ..amp import _STATE as _amp
            amp_key = str(_amp["target_dtype"])
        # a bumped epoch invalidates by CLEARING this block's cache (not
        # by keying on the epoch, which would strand the old compiled
        # executables in the dict for the block's lifetime) — and only
        # for blocks that CONTAIN an epoch-sensitive layer (BatchNorm):
        # other models' traced programs cannot have changed, so they
        # keep their executables
        if getattr(self, "_cache_epoch", None) != _GRAPH_EPOCH[0]:
            if self._epoch_sensitive():
                self._cached_graph.clear()
            self._cache_epoch = _GRAPH_EPOCH[0]
        # the remat flag joins the key: its value changes the traced
        # program for every remat-capable model, independent of the
        # BatchNorm-only epoch filter above
        key_sig = (tuple((tuple(a.shape), str(a.dtype)) for a in nd_args),
                   train, amp_key, _remat_enabled())
        entry = self._cached_graph.get(key_sig)
        if entry is None:
            cell: Dict[str, Any] = {}  # filled with treedef at trace time
            entry = (jax.jit(self._make_traced(params, train, cell)), cell)
            self._cached_graph[key_sig] = entry

        cached, cell = entry
        rng = _random.split_key()
        n_params = len(params)

        def impl(*arrays):
            return cached(rng, list(arrays[:n_params]), *arrays[n_params:])

        inputs = [p.data() for p in params] + nd_args
        flat_out = invoke(f"cached_{type(self).__name__}", impl, inputs)
        leaves = list(flat_out) if isinstance(flat_out, tuple) else [flat_out]
        m_idx = cell.get("mutated_idx") or []
        if m_idx:
            n_out = cell["treedef"].num_leaves
            for i, a in zip(m_idx, leaves[n_out:]):
                raw = a._data if isinstance(a, NDArray) else a
                params[i]._data._data = raw
            leaves = leaves[:n_out]
        return jax.tree_util.tree_unflatten(cell["treedef"], leaves)

    def __call__(self, *args: Any) -> Any:
        if self._active and not _tracing_now(args):
            if args and isinstance(args[0], PreActivation):
                # the hybrid cache boundary speaks NDArray: a deferred
                # epilogue materializes rather than crossing the jit
                args = (args[0].materialize(),) + args[1:]
            for hook in self._forward_pre_hooks:
                hook(self, args)
            out = self._call_cached(*args)
            for hook in self._forward_hooks:
                hook(self, args, out)
            return out
        return super().__call__(*args)

    # -- export/deploy -----------------------------------------------------
    def export(self, path: str, epoch: int = 0,
               input_signature: Optional[Sequence[tuple]] = None,
               dynamic_batch: bool = False) -> Tuple[str, str]:
        """Serialize a runnable program + params for deployment (reference:
        ``HybridBlock.export`` → ``prefix-symbol.json`` + ``.params``).

        The "symbol" payload is a jax.export StableHLO artifact traced in
        inference mode (the TPU-era graph format; the reference stored an
        NNVM json graph). ``input_signature`` is a list of (shape, dtype)
        per input; if omitted, the signature of the last hybridized call
        is used (so call the block once before exporting, as in the
        reference).

        ``dynamic_batch=True`` traces the leading dim of every input as a
        shape-polymorphic symbol: ONE serialized program answers every
        batch size — what the serving layer's batch buckets run against
        (a static artifact serves exactly its traced batch).  The batch
        entry of ``input_signature`` is then only a placeholder.
        """
        import base64
        import json

        if input_signature is None:
            input_signature = getattr(self, "_last_sig", None)
        if input_signature is None:
            raise MXNetError(
                "export() needs the input signature: run the block once "
                "(after hybridize()) or pass input_signature=[(shape, "
                "dtype), ...]")

        # tied/shared parameters (same object under several names) save
        # and trace ONCE, under their first name — a duplicate would
        # double-bind the buffer in the trace and read as a phantom
        # in-trace mutation
        from .parameter import dedupe_shared
        _pnames, _plist = dedupe_shared(
            (k, p) for k, p in self.collect_params().items()
            if p.is_initialized)
        params = dict(zip(_pnames, _plist))

        from jax import export as jax_export
        param_list = list(params.values())
        cell: Dict[str, Any] = {}
        key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
        param_specs = [jax.ShapeDtypeStruct(p.shape, p.dtype)
                       for p in param_list]
        if dynamic_batch:
            # one polymorphic symbol shared by every input's leading dim
            # (inputs batch together); inner dims stay concrete
            (bdim,) = jax_export.symbolic_shape("_b")
            in_specs = [jax.ShapeDtypeStruct((bdim,) + tuple(s)[1:], d)
                        for s, d in input_signature]
        else:
            in_specs = [jax.ShapeDtypeStruct(tuple(s), d)
                        for s, d in input_signature]
        jitted = jax.jit(self._make_traced(param_list, False, cell))
        exp = jax_export.export(jitted, platforms=("cpu", "tpu"))(
            key_spec, param_specs, *in_specs)
        if cell.get("mutated_idx"):
            raise MXNetError(
                "export traced a forward that mutates parameter state "
                "(training-mode BatchNorm?); export runs in inference "
                "mode — check autograd/use_global_stats configuration")
        program = bytes(exp.serialize())
        from .._durable import sha256_bytes, sha256_file
        meta = {
            "framework": "mxnet_tpu",
            "format_version": 1,
            "block": type(self).__name__,
            "dynamic_batch": bool(dynamic_batch),
            "inputs": [{"shape": list(s), "dtype": str(_np.dtype(d))}
                       for s, d in input_signature],
            "params": {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                       for k, v in params.items()},
            "param_order": list(params.keys()),
            "out_treedef": _treedef_to_obj(cell["treedef"]),
            "stablehlo": base64.b64encode(program).decode("ascii"),
            # the serving load path verifies these BEFORE deserializing:
            # a truncated/garbled artifact is named in a structured
            # error instead of an opaque deserializer crash
            "stablehlo_sha256": sha256_bytes(program),
        }
        # native-runtime deploy graph (c_predict_api analog): a layer-op
        # list MXPredCreate can execute with no Python, emitted whenever
        # the block maps onto the native op set
        from .deploy import deploy_graph
        meta["deploy_graph"] = deploy_graph(self)
        # write artifacts only after trace + serialization succeeded — a
        # failed export must not leave a stale .params behind. The FILE
        # carries EVERY name, aliases included (same array under each):
        # load_parameters and the native deploy_graph resolve parameters
        # by name and must find all of them; only the trace deduped.
        param_file = f"{path}-{epoch:04d}.params"
        from ..ndarray_io import save_params
        save_params(param_file,
                    {k: p.data() for k, p in self.collect_params().items()
                     if p.is_initialized})
        meta["params_sha256"] = sha256_file(param_file)
        sym_file = f"{path}-symbol.json"
        with open(sym_file, "w") as f:
            json.dump(meta, f, indent=2)
        return sym_file, param_file


def _treedef_to_obj(treedef: Any) -> Any:
    """Declarative (JSON-able) encoding of an output pytree structure.

    Supports the standard containers a forward may return (leaf, tuple,
    list, dict) — no pickle, so model files stay safe to load from
    untrusted sources.
    """
    n = treedef.num_leaves
    skeleton = jax.tree_util.tree_unflatten(treedef, list(range(n)))

    def enc(node: Any) -> Any:
        if isinstance(node, int):
            return {"t": "leaf"}
        if isinstance(node, tuple):
            return {"t": "tuple", "c": [enc(x) for x in node]}
        if isinstance(node, list):
            return {"t": "list", "c": [enc(x) for x in node]}
        if isinstance(node, dict):
            return {"t": "dict", "k": list(node.keys()),
                    "c": [enc(node[k]) for k in node.keys()]}
        if node is None:
            return {"t": "none"}
        raise MXNetError(
            f"export: forward returned a {type(node).__name__}; only "
            f"tuples/lists/dicts/arrays are exportable")

    return enc(skeleton)


def _obj_to_treedef(obj: Any) -> Any:
    def dec(node: Any) -> Any:
        t = node["t"]
        if t == "leaf":
            return 0  # placeholder leaf
        if t == "tuple":
            return tuple(dec(x) for x in node["c"])
        if t == "list":
            return [dec(x) for x in node["c"]]
        if t == "dict":
            return {k: dec(x) for k, x in zip(node["k"], node["c"])}
        if t == "none":
            return None
        raise MXNetError(f"bad treedef node type {t!r} in model file")

    return jax.tree_util.tree_structure(dec(obj))


class PreActivation:
    """A residual-block output BEFORE its epilogue ReLU, deferred so a
    consuming 1x1 conv can take the ReLU as a Pallas kernel prologue
    (ops/pallas/conv_fused.py) — the activated tensor then never
    round-trips HBM.  Blocks that understand the deferral set
    ``_consumes_preactivation = True``; every other ``Block.__call__``
    (and the hybrid cache boundary) materializes transparently, so the
    box can never leak into user code or a jit signature."""

    __slots__ = ("z",)

    def __init__(self, z) -> None:
        self.z = z

    def materialize(self):
        from .. import npx
        return npx.relu(self.z)


def _tracing_now(args) -> bool:
    for a in args:
        if isinstance(a, PreActivation):
            a = a.z
        data = a._data if isinstance(a, NDArray) else a
        if isinstance(data, jax.core.Tracer):
            return True
    return False


def _default_init_for(name: str):
    """Name-dispatched default initializer for symbol-created parameters
    (reference: the variable-name heuristics in ``initializer.py`` —
    gamma/moving_var -> ones, beta/bias/moving_mean -> zeros)."""
    from .. import initializer as _init_mod
    if name.endswith(("_gamma", "_moving_var", "_running_var")):
        return _init_mod.One()
    if name.endswith(("_beta", "_bias", "_moving_mean", "_running_mean")):
        return _init_mod.Zero()
    return None


class SymbolBlock(HybridBlock):
    """Run a symbolic graph as a gluon block (reference:
    ``gluon.SymbolBlock(outputs, inputs)`` and ``SymbolBlock.imports``
    over ``-symbol.json`` + ``.params``).

    Accepts either a ``mx.sym.Symbol`` with its input symbols (classic
    constructor), or a callable + params dict (used internally by
    ``imports`` for jax.export artifacts)."""

    def __init__(self, outputs: Any, inputs: Any = None,
                 params: Optional[Dict[str, Parameter]] = None) -> None:
        super().__init__()
        if hasattr(outputs, "_heads"):          # mx.sym.Symbol
            self._init_from_symbol(outputs, inputs, params)
            return
        self._fn = outputs
        self._symbol = None
        for k, v in (inputs if isinstance(inputs, dict)
                     else (params or {})).items():
            self._reg_params[k] = v

    def _init_from_symbol(self, outputs: Any, inputs: Any,
                          params: Optional[Dict[str, Parameter]]) -> None:
        from ..symbol.symbol import _eval_graph
        if inputs is None:
            raise MXNetError("SymbolBlock(symbol) requires the input "
                             "symbols, e.g. inputs=[mx.sym.var('data')]")
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        in_names = [i if isinstance(i, str) else i.name for i in inputs]
        self._symbol = outputs
        self._sym_input_names = in_names
        arg_names = [n for n in outputs.list_arguments()
                     if n not in in_names]
        aux_names = outputs.list_auxiliary_states()
        for n in arg_names:
            p = (params or {}).get(n) or Parameter(
                n, shape=None, allow_deferred_init=True,
                init=_default_init_for(n))
            self._reg_params[n] = p
        for n in aux_names:
            p = (params or {}).get(n) or Parameter(
                n, grad_req="null", shape=None, allow_deferred_init=True,
                init=_default_init_for(n))
            self._reg_params[n] = p

        def fn(*args: Any) -> Any:
            self._sym_finish_deferred(args)
            feed = {}
            for name, a in zip(in_names, args):
                feed[name] = a if isinstance(a, NDArray) else NDArray(a)
            for name, p in self._reg_params.items():
                feed[name] = p.data()

            def aux_hook(name: str, value: NDArray) -> None:
                self._reg_params[name].set_data(value.detach())

            from .._tape import is_training
            outs = _eval_graph(self._symbol, feed,
                               training=is_training(), aux_hook=aux_hook)
            return outs[0] if len(outs) == 1 else tuple(outs)

        self._fn = fn

    def _sym_finish_deferred(self, args: Any) -> None:
        pending = {n: p for n, p in self._reg_params.items()
                   if p._data is None and p._deferred_init is not None}
        if not pending:
            return
        from ..symbol.symbol import _infer_structs
        known = {n: tuple(a.shape)
                 for n, a in zip(self._sym_input_names, args)}
        var_structs, _ = _infer_structs(self._symbol, known, partial=True)
        for n, p in pending.items():
            st = var_structs.get(n)
            if st is None:
                raise MXNetError(
                    f"SymbolBlock: could not infer shape of parameter "
                    f"{n!r} from input shapes {known}")
            if p.dtype is None or _np.dtype(p.dtype) != _np.dtype(st.dtype):
                p.dtype = _np.dtype(st.dtype)
            p._finish_deferred_init(tuple(st.shape))

    @staticmethod
    def imports(symbol_file: str, input_names: Any = None,
                param_file: Optional[str] = None,
                ctx: Any = None) -> "SymbolBlock":
        """Load an exported model: deserializes the StableHLO artifact and
        rebinds the saved parameters (reference: ``SymbolBlock.imports``)."""
        import base64
        import json

        from jax import export as jax_export

        with open(symbol_file) as f:
            meta = json.load(f)
        if meta.get("framework") != "mxnet_tpu" or "stablehlo" not in meta:
            raise MXNetError(
                f"{symbol_file} is not an mxnet_tpu export (re-export with "
                "HybridBlock.export)")

        exp = jax_export.deserialize(
            bytearray(base64.b64decode(meta["stablehlo"])))
        treedef = _obj_to_treedef(meta["out_treedef"])
        order = meta["param_order"]

        params: Dict[str, Parameter] = {}
        if param_file is not None:
            from ..ndarray_io import load_params
            loaded = load_params(param_file, ctx=ctx)
            missing = [k for k in order if k not in loaded]
            if missing:
                raise MXNetError(
                    f"{param_file} is missing exported params: {missing}")
            for k in order:
                p = Parameter(k, shape=loaded[k].shape,
                              dtype=loaded[k].dtype, grad_req="null")
                p.set_data(loaded[k])
                params[k] = p
        elif order:
            # no params file: leave parameters uninitialized so first use
            # raises instead of silently running random weights
            raise MXNetError(
                "SymbolBlock.imports: this export has parameters — pass "
                "param_file=<prefix-NNNN.params> (loading without weights "
                "would silently return garbage)")

        def fn(*args: Any) -> Any:
            arrays = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
                      for a in args]
            rng = _random.split_key()
            if rng.shape != (2,):  # typed key -> raw uint32 pair
                rng = jax.random.key_data(rng)
            pa = [params[k].data()._data for k in order]
            leaves = exp.call(rng.astype(jnp.uint32), pa, *arrays)
            out = jax.tree_util.tree_unflatten(treedef, list(leaves))
            return jax.tree_util.tree_map(from_jax, out)

        return SymbolBlock(fn, params)

    def forward(self, *args: Any) -> Any:
        return self._fn(*args)


def nn_block_summary(block: Block, *inputs: Any) -> str:
    """Print a per-layer summary table (reference: ``Block.summary``)."""
    lines = [f"{'Layer':<40}{'Output Shape':<24}{'Param #':<12}"]
    total = 0
    for name, p in block.collect_params().items():
        n = 1
        for s in (p.shape or ()):
            n *= s
        total += n
        lines.append(f"{name:<40}{str(p.shape):<24}{n:<12}")
    lines.append(f"Total params: {total}")
    out = "\n".join(lines)
    print(out)
    return out
