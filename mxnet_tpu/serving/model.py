"""ServedModel — one ``predict(arrays) -> arrays`` surface over every
way a model reaches the server.

Backends:

* **export artifact** (``HybridBlock.export`` / ``Module.export`` output:
  ``prefix-symbol.json`` + ``prefix-NNNN.params``): the StableHLO
  program is deserialized once and called directly on raw arrays — the
  ``c_predict_api`` analog, no gluon graph in the hot path.  An artifact
  exported with ``dynamic_batch=True`` serves every batch bucket from
  ONE serialized program (shape-polymorphic leading dim); a static
  artifact pins the policy to its exported batch size.
* **live block** (a (Hybrid)Block or Module): hybridized and driven in
  predict mode — per-bucket executables appear through the normal jit
  cache.  The path for models that never went through export (tests,
  notebooks, zoo models).

Both backends share per-bucket compile accounting: the first execution
of each padded batch signature increments
``mxnet_serving_bucket_compiles_total{bucket=...}`` — with a
:class:`~mxnet_tpu.serving.batching.BucketPolicy` in front, that counter
is bounded by the bucket grid, and :meth:`ServedModel.warmup` moves all
of it to startup.
"""
from __future__ import annotations

import inspect
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as _np

from ..base import MXNetError
from .. import metrics as _metrics
from .. import tracing as _tracing
from .batching import BUCKET_COMPILES, BucketPolicy, INFER_SECONDS

__all__ = ["ServedModel", "DecodeModel", "Prefill", "load_served"]


def _sig_str(shapes: Sequence[Tuple[int, ...]]) -> str:
    return ";".join("x".join(map(str, s)) for s in shapes)


class ServedModel:
    """A loaded inference model: ``predict`` over numpy batch arrays.

    Build with :meth:`from_export`, :meth:`from_block`,
    :meth:`from_module`, or the path-sniffing :func:`load_served`.
    """

    def __init__(self, fn: Any, input_signature: List[Tuple[Tuple[int, ...],
                                                            Any]],
                 fixed_batch: Optional[int], name: str) -> None:
        self._fn = fn
        # per-input (shape_without_batch, dtype) — what a single request
        # sample must look like
        self.input_signature = input_signature
        # static exports serve exactly their traced batch size
        self.fixed_batch = fixed_batch
        self.name = name
        # guarded: the worker thread adds while /healthz threads read
        self._seen_lock = threading.Lock()
        self._seen_buckets: set = set()

    # -- constructors -------------------------------------------------------
    @staticmethod
    def from_export(symbol_file: str,
                    param_file: Optional[str] = None) -> "ServedModel":
        """Load an ``export()`` artifact for serving (the predict-API
        path: StableHLO called directly, no gluon objects per request).

        Artifacts carrying digests (``export()`` emits them) are
        **checksum-verified before deserialization**: a truncated or
        bit-flipped program/params file raises a structured error
        naming the artifact and the expected/actual digests, instead
        of an opaque deserializer crash (or, worse, a model that loads
        and serves garbage)."""
        import base64

        import jax
        import jax.numpy as jnp
        from jax import export as jax_export
        from .._durable import sha256_bytes, sha256_file

        with open(symbol_file) as f:
            meta = json.load(f)
        if meta.get("framework") != "mxnet_tpu" or "stablehlo" not in meta:
            raise MXNetError(
                f"{symbol_file} is not an mxnet_tpu export (re-export "
                "with HybridBlock.export)")
        if param_file is None:
            param_file = _guess_param_file(symbol_file)
        program = base64.b64decode(meta["stablehlo"])
        want = meta.get("stablehlo_sha256")
        if want is not None:
            got = sha256_bytes(program)
            if got != want:
                raise MXNetError(
                    f"export artifact {symbol_file} failed its program "
                    f"checksum (stablehlo_sha256 {want[:12]}…, file "
                    f"digests to {got[:12]}…) — the artifact is "
                    "truncated or garbled; re-export or restore it "
                    "before serving")
        order = meta["param_order"]
        params: List[Any] = []
        if order:
            if param_file is None:
                raise MXNetError(
                    "this export has parameters — pass the "
                    "prefix-NNNN.params file (or keep it next to the "
                    "symbol json)")
            want = meta.get("params_sha256")
            if want is not None:
                got = sha256_file(param_file)
                if got != want:
                    raise MXNetError(
                        f"export artifact {param_file} failed its "
                        f"checksum (params_sha256 {want[:12]}…, file "
                        f"digests to {got[:12]}…) — the weights are "
                        "truncated or garbled (or not the file this "
                        "symbol json was exported with); re-export or "
                        "restore them before serving")
            from ..ndarray_io import load_params
            loaded = load_params(param_file)
            missing = [k for k in order if k not in loaded]
            if missing:
                raise MXNetError(
                    f"{param_file} is missing exported params: {missing}")
            params = [jnp.asarray(loaded[k]._data) for k in order]
        exp = jax_export.deserialize(bytearray(program))
        key = jnp.zeros((2,), jnp.uint32)   # inference: dropout is off
        dynamic = bool(meta.get("dynamic_batch"))
        sig = [(tuple(i["shape"][1:]), _np.dtype(i["dtype"]))
               for i in meta["inputs"]]
        fixed = None if dynamic else int(meta["inputs"][0]["shape"][0])

        # params ride as ARGUMENTS (not closure constants): the lowered
        # program — and so its key in jax's persistent cache — is
        # weight-independent, shared across re-exports of the same
        # architecture
        aot = jax.jit(lambda ps, *xs: exp.call(key, list(ps), *xs))
        params_t = tuple(params)

        def fn(arrays: Sequence[_np.ndarray]) -> List[_np.ndarray]:
            jarrs = [jnp.asarray(a) for a in arrays]
            leaves = aot(params_t, *jarrs)
            return [_np.asarray(o) for o in leaves]

        name = os.path.basename(symbol_file).replace("-symbol.json", "")
        return ServedModel(fn, sig, fixed, name or "export")

    @staticmethod
    def from_block(block: Any,
                   input_signature: Optional[Sequence[Tuple[
                       Tuple[int, ...], Any]]] = None) -> "ServedModel":
        """Serve a live (Hybrid)Block.  ``input_signature`` is per-input
        (shape_without_batch, dtype); defaults to the block's last
        hybridized call signature (run it once first)."""
        from .. import autograd
        from ..ndarray.ndarray import NDArray

        if hasattr(block, "hybridize") and not getattr(block, "_active",
                                                       False):
            block.hybridize()
        if input_signature is None:
            last = getattr(block, "_last_sig", None)
            if last is None:
                raise MXNetError(
                    "from_block needs the input signature: run the block "
                    "once, or pass input_signature=[(sample_shape, "
                    "dtype), ...] (shapes WITHOUT the batch dim)")
            input_signature = [(tuple(s[1:]), d) for s, d in last]

        def fn(arrays: Sequence[_np.ndarray]) -> List[_np.ndarray]:
            import jax
            nds = [NDArray(a) for a in arrays]
            with autograd.predict_mode():
                out = block(*nds)
            leaves, _ = jax.tree_util.tree_flatten(
                out, is_leaf=lambda o: isinstance(o, NDArray))
            return [o.asnumpy() for o in leaves]

        sig = [(tuple(s), _np.dtype(d)) for s, d in input_signature]
        return ServedModel(fn, sig, None, type(block).__name__)

    @staticmethod
    def from_module(module: Any) -> "ServedModel":
        """Serve a bound Module's network (inference half of the classic
        workflow)."""
        if not getattr(module, "params_initialized", False):
            raise MXNetError("module must be bound + initialized before "
                             "serving")
        sig = [(tuple(d.shape[1:]) if hasattr(d, "shape")
                else tuple(d[1][1:]),
                getattr(d, "dtype", _np.float32))
               for d in module._data_shapes]
        return ServedModel.from_block(module.symbol, sig)

    # -- execution ----------------------------------------------------------
    def predict(self, arrays: Sequence[_np.ndarray]) -> List[_np.ndarray]:
        """Run one padded batch; returns per-output numpy arrays (axis 0
        = padded batch).  Tracks first-seen batch signatures as bucket
        compiles and times the execution."""
        shapes = tuple(tuple(a.shape) for a in arrays)
        with self._seen_lock:
            new = shapes not in self._seen_buckets
            if new:
                self._seen_buckets.add(shapes)
        if new:
            BUCKET_COMPILES.labels(bucket=_sig_str(shapes)).inc()
        t0 = time.perf_counter()
        out = self._fn(arrays)
        INFER_SECONDS.observe(time.perf_counter() - t0,
                              exemplar=_tracing.current_trace_id())
        return out

    def warmup(self, policy: BucketPolicy) -> int:
        """Pre-compile every bucket signature the policy can emit (zeros
        input); returns how many signatures were warmed.  After this, a
        request stream confined to the bucket grid never compiles."""
        n = 0
        for sig in policy.warmup_signatures(self.input_signature):
            if self.fixed_batch is not None \
                    and sig[0][0][0] != self.fixed_batch:
                raise MXNetError(
                    f"static export serves only batch={self.fixed_batch}; "
                    f"configure BucketPolicy(batch_buckets="
                    f"[{self.fixed_batch}]) (or re-export with "
                    "dynamic_batch=True)")
            self.predict([_np.zeros(s, d) for s, d in sig])
            n += 1
        return n

    def default_policy(self, **kw: Any) -> BucketPolicy:
        """A policy consistent with this model (static exports pin the
        batch bucket to the exported batch)."""
        if self.fixed_batch is not None and "batch_buckets" not in kw:
            kw["batch_buckets"] = [self.fixed_batch]
        return BucketPolicy(**kw)

    def describe(self) -> Dict[str, Any]:
        with self._seen_lock:
            seen = list(self._seen_buckets)
        return {
            "name": self.name,
            "inputs": [{"sample_shape": list(s), "dtype": str(d)}
                       for s, d in self.input_signature],
            "fixed_batch": self.fixed_batch,
            "buckets_compiled": sorted(_sig_str(s) for s in seen),
        }


# ---------------------------------------------------------------------------
# DecodeModel — the stateful autoregressive path (continuous batching)
# ---------------------------------------------------------------------------

# decode-method codes: the sampler rides INSIDE the compiled step, so
# the method travels as a traced (S,) int32 operand, never a Python
# constant (a constant would recompile the step per method mix)
METHOD_CODES = {"greedy": 0, "sample": 1, "top_k": 2, "top_p": 3}


def _sample_tokens(logits, seeds, ctrs, temps, topks, topps, methods):
    """Fused per-slot token selection over (S, V) logits — the
    on-device sampler.  Per slot: temperature scaling, then the
    method's filter (top-k kth-largest threshold / top-p nucleus
    threshold), then a categorical draw under the slot's counter-PRNG
    key ``fold_in(PRNGKey(seed), counter)``; greedy slots take the raw
    argmax.  Every parameter is a traced operand, so one executable
    serves every per-request method/parameter mix, and the math
    mirrors ``model_zoo.generation._select`` exactly — the zoo stays
    the host-side parity oracle (pinned in tests)."""
    import jax

    with jax.named_scope("sample"):
        return _sample_rows(logits, seeds, ctrs, temps, topks, topps,
                            methods)


def _sample_rows(logits, seeds, ctrs, temps, topks, topps, methods):
    import jax
    import jax.numpy as jnp

    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    neg = jnp.float32(-jnp.inf).astype(scaled.dtype)
    asc = jnp.sort(scaled, axis=-1)
    # top-k: the kth-largest value is asc[V - k] (k pre-clamped to
    # [1, V] at submit, clipped again here so free slots riding along
    # with k=0 stay finite)
    kidx = jnp.clip(V - topks, 0, V - 1)
    kth_k = jnp.take_along_axis(asc, kidx[:, None], axis=-1)
    # top-p: smallest probability-sorted prefix reaching mass top_p
    # (the most probable token is always kept)
    desc = asc[:, ::-1]
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < topps[:, None]
    kth_p = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                    keepdims=True)
    m = methods[:, None]
    filt = jnp.where((m == 2) & (scaled < kth_k), neg, scaled)
    filt = jnp.where((m == 3) & (filt < kth_p), neg, filt)

    def draw(seed, ctr, row):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), ctr)
        return jax.random.categorical(key, row, axis=-1)

    sampled = jax.vmap(draw)(seeds, ctrs, filt).astype(jnp.int32)
    return jnp.where(methods == 0, greedy, sampled)


def _select_one(logits, seed, ctr, temp, topk, topp, method):
    """The first-token selector (prefill logits -> token): the SAME
    fused sampler on one row, so host-emitted first tokens and
    step-emitted tokens share one code path and one key-stream
    discipline."""
    return _sample_tokens(
        logits[None], seed[None], ctr[None], temp[None],
        topk[None], topp[None], method[None])[0]


def _put_token(tokens, slot, token):
    """A step's token array with ``token`` at ``slot``: an admission's
    first token joins the array the next step is fed from, on the
    device (a family that hands more back behind the tokens keeps it)."""
    from jax import lax
    return lax.dynamic_update_slice(
        tokens, token.astype(tokens.dtype)[None], (slot,))


def _first_token_programs(family: str) -> Tuple[Any, Any]:
    """A family's first-token programs: the selector over prefill
    logits and the put of its token into a step's token array."""
    return (_tracing.program(_select_one, "select", family),
            _tracing.program(_put_token, "select", family))


def _select_tokens(logits, seeds, pos, bases, temps, topks, topps, methods,
                   j=None):
    """Each slot's token from its (S, V) ``logits`` inside a program:
    greedy argmax, or the fused sampler under the key counter ``pos -
    base`` (``pos + j - base`` for column ``j`` of a multi-token pass).
    The sampler rides behind a runtime ``lax.cond``: an all-greedy
    iteration (the default traffic) runs only the argmax branch, so
    sampling support costs nothing until a slot samples, and it stays
    ONE executable, so greedy tokens are bit-identical whichever branch
    the batch selects (argmax is comparison-only)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def _mixed(lg):
        at = pos if j is None else pos + j
        return _sample_tokens(lg, seeds, at - bases, temps, topks, topps,
                              methods)

    def _greedy(lg):
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)

    with jax.named_scope("sample"):
        return lax.cond(jnp.any(methods != 0), _mixed, _greedy, logits)


class Prefill:
    """A prompt pass dispatched and not waited for
    (:meth:`DecodeModel.dispatch_prefill`): its last-token ``logits``
    (V,) and what it hands on (``held``: the K/V rows, and a family's
    state) still on the device.  What the family says of the pass
    (``said``, on its ``model.prefill`` ``span``) is read to the host
    by :meth:`DecodeModel.read_prefill`, once the first token is."""

    __slots__ = ("logits", "held", "said", "span")

    def __init__(self, logits: Any, held: List[Any], said: Any,
                 span: Any) -> None:
        self.logits, self.held, self.said, self.span = \
            logits, held, said, span


class DecodeModel:
    """The decode surface the continuous-batching
    :class:`~mxnet_tpu.serving.generation.GenerationEngine` drives, the
    same for every family: a stateful ``(params, cache, positions) ->
    next tokens`` step over slot rows, compiled ONCE per KV capacity
    bucket, plus a per-prompt-bucket prefill, the first-token selector
    and the put of that token into a step's token array.

    A family is a subclass in its own module (``serving.gpt``,
    ``serving.hybrid``, ``serving.moe``, ``serving.loop``,
    ``serving.delta``) that names the zoo class it serves (``zoo``) and
    supplies its model math (:meth:`_model_fns`: a prefill and a step
    that ends at the logits), its cache (``make_cache``) and
    :meth:`row_blocks`; this class selects the tokens inside the step,
    registers the programs, accounts and times them on the spans and
    refuses what a family without rollback cannot do.  Parameters are a
    pure pytree extracted once from the live block (its zoo module's
    ``_collect``).  Compile accounting rides the SAME per-bucket counter
    as the one-shot path (``mxnet_serving_bucket_compiles_total``,
    labels ``decode:SxL`` / ``prefill:Lp``), so warmup moves every
    compile to startup and the smoke gate can pin "0 after warmup".
    """

    # the family's name on spans, in the program table and in refusals
    family = ""
    # the zoo class the family serves, ``module.Class``; ``from_block``
    # reads the table of families by it
    zoo: Optional[str] = None
    _families: Dict[str, type] = {}
    # the buffer lists of the cache's ``buffers()`` the step takes and
    # hands back (donated): K and V rows, and the fixed-size kinds where
    # the cache holds any
    cache_buffers = 2
    # longest prompt one prefill program takes (None: the top KV bucket)
    # and the smallest bucket prompts are padded to
    max_prompt: Optional[int] = None
    min_prompt_bucket = 8
    # whether a slot can be rewound (speculation) and its rows shared
    # (prefix cache), and where not, why: the end of every refusal
    supports_rollback = False
    no_rollback_why = ""
    # what a family says of its programs on the ``model.prefill`` and
    # ``model.step.dispatch`` spans beside what every family says
    span_attrs: Dict[str, Any] = {}

    def __init_subclass__(cls, **kwargs: Any) -> None:
        super().__init_subclass__(**kwargs)
        if cls.zoo is not None:
            DecodeModel._families[cls.zoo] = cls

    def __init__(self, params: Any, cfg: Dict[str, Any], max_length: int,
                 name: str) -> None:
        self.params = params
        self.cfg = cfg
        self.kinds = list(cfg.get("kinds", ()))
        self.max_length = int(max_length)
        self.name = name
        self.vocab_size, self.units = params["embed"].shape
        self.num_heads = int(cfg["num_heads"])
        self.head_dim = int(cfg["head_dim"])
        self.n_layers = len(self.kinds) or int(cfg["num_layers"])
        self.dtype = params["embed"].dtype
        self.logits_dtype = _np.dtype(_np.float32)
        self._seen_lock = threading.Lock()
        self._seen: set = set()
        prefill, logits_step = self._model_fns()
        n, output = self.cache_buffers, self._step_output

        def _step(params, *args):
            # args: the cache's buffer lists, then toks (S,) the last
            # token of each slot, pos (S,) its write position (free
            # slots ride along at 0, their outputs ignored on the host)
            # and the (S,) sampling vectors.  The sampling vectors are
            # traced operands that change only at admission, so
            # per-request parameters never recompile the step and the
            # engine reuses their device mirrors; the key counter is
            # derived in-program (pos - base: base is the slot's prompt
            # length minus its stream offset, minus one), so no
            # per-token host vector rides the loop.  The host reads
            # back the (S,) tokens, never (S, V) logits
            toks, pos, seeds, bases, temps, topks, topps, methods = args[n:]
            logits, *out = logits_step(params, *args[:n], toks, pos)
            tok = _select_tokens(logits, seeds, pos, bases, temps, topks,
                                 topps, methods)
            return (output(tok, *out[n:]), *out[:n])

        # jax names the program's parameters after these
        _step.__signature__ = inspect.Signature([
            inspect.Parameter(a, inspect.Parameter.POSITIONAL_OR_KEYWORD)
            for a in ("params", *("ks", "vs", "state")[:n], "toks", "pos",
                      "seeds", "bases", "temps", "topks", "topps",
                      "methods")])
        fam = self.family
        self._prefill_fn = _tracing.program(prefill, "prefill", fam)
        self._select_fn, self._put_fn = _first_token_programs(fam)
        # every buffer list is DONATED: XLA updates the resident cache
        # in place instead of allocating it afresh every token
        self._step_fn = _tracing.program(
            _step, "decode", fam, donate_argnums=tuple(range(1, n + 1)))

    def _model_fns(self) -> Tuple[Any, Any]:
        """The family's model math over ``self.params``' pytree:
        ``prefill(params, toks, t0) -> (logits (V,), *held)`` over a
        prompt padded past its traced real length ``t0`` (``held`` is
        what ``PagedKVCache.write_prompt`` installs), and the step
        ``(params, *buffers, toks, pos) -> (logits (S, V),
        *new_buffers, *extra)``, ``extra`` going to
        :meth:`_step_output`."""
        raise NotImplementedError(f"{type(self).__name__} names no model")

    # -- constructors -------------------------------------------------------
    @classmethod
    def from_block(cls, block: Any) -> "DecodeModel":
        """Build from a live zoo LM (weights as currently
        initialized/loaded).  On this class the family is the one whose
        ``zoo`` class the block is an instance of; on a family it is
        that family."""
        if cls is DecodeModel:
            served = DecodeModel._families
            for klass in type(block).__mro__:
                family = served.get(f"{klass.__module__}.{klass.__name__}")
                if family is not None:
                    return family.from_block(block)
            raise MXNetError(
                "DecodeModel serves decoder-only zoo LMs ("
                + ", ".join(sorted(z.rpartition(".")[2] for z in served))
                + f"); got {type(block).__name__}")
        params, cfg = cls._collect(block)
        return cls(params, cfg, block._max_length, type(block).__name__)

    @classmethod
    def _collect(cls, block: Any) -> Tuple[Any, Dict[str, Any]]:
        """The block's weights as one pytree, and its config."""
        import importlib
        zoo = importlib.import_module(cls.zoo.rpartition(".")[0])
        return zoo._collect(block), dict(block.config)

    def no_rollback(self, what: str) -> MXNetError:
        """The refusal of ``what`` for a family that cannot rewind or
        share a slot."""
        return MXNetError(f"{what} is not available for the "
                          f"{self.family} family: {self.no_rollback_why}")

    # -- execution ----------------------------------------------------------
    def _account(self, tag: str) -> None:
        with self._seen_lock:
            new = tag not in self._seen
            if new:
                self._seen.add(tag)
        if new:
            BUCKET_COMPILES.labels(bucket=tag).inc()

    def prefill(self, tokens: _np.ndarray, bucket_len: int
                ) -> Tuple[Any, ...]:
        """Run the prompt pass padded to ``bucket_len``; returns
        (last-token logits (V,) numpy, per-layer ks/vs device arrays
        (bucket_len, nh, d)) and, for a family whose slots hold more
        than rows, fourth what ``PagedKVCache.write_prompt(state=)``
        installs beside them."""
        logits, held, _, _ = self._run_prefill(tokens, bucket_len, True)
        return (logits, *held)

    def dispatch_prefill(self, tokens: _np.ndarray,
                         bucket_len: int) -> Prefill:
        """:meth:`prefill`'s pass, dispatched and not waited for: the
        logits stay on the device for :meth:`dispatch_select`, the rows
        (and state) go to ``write_prompt`` as they are, and nothing is
        read to the host until :meth:`read_prefill`.  The
        ``model.prefill`` span and ``mxnet_gen_step_seconds{phase=
        "prefill"}`` then time the dispatch alone."""
        return Prefill(*self._run_prefill(tokens, bucket_len, False))

    def read_prefill(self, pre: Prefill) -> None:
        """Read what the family says of a dispatched pass to the host
        and onto its ``model.prefill`` span (the sparse-expert family's
        load, as :meth:`prefill` says it)."""
        self._say_prefill(pre.span, pre.said)

    def _run_prefill(self, tokens: _np.ndarray, bucket_len: int,
                     read: bool) -> Tuple[Any, List[Any], Any, Any]:
        import jax.numpy as jnp
        toks = _np.asarray(tokens, _np.int32).reshape(-1)
        t0 = toks.shape[0]
        if t0 < 1:
            raise MXNetError("empty prompt")
        if bucket_len < t0:
            raise MXNetError(
                f"prompt length {t0} exceeds its bucket {bucket_len}")
        padded = _np.zeros((bucket_len,), _np.int32)
        padded[:t0] = toks
        self._account(f"prefill:{bucket_len}")
        with _tracing.child_span("model.prefill", bucket=bucket_len,
                                 family=self.family,
                                 **self.span_attrs) as span:
            t = time.perf_counter()
            logits, *held = self._prefill_fn(
                self.params, jnp.asarray(padded), _np.int32(t0))
            held, said = self._prefill_extras(held)
            if read:
                self._say_prefill(span, said)
                logits = _np.asarray(logits)
            dt = time.perf_counter() - t
        _metrics.GEN_STEP_SECONDS.labels(phase="prefill").observe(
            dt, exemplar=_tracing.current_trace_id())
        return logits, held, said, span

    # what a family's programs hand back or take beside the tokens and
    # the buffers, where it has any (serving.moe: the held experts' load)
    def _prefill_extras(self, held: List[Any]) -> Tuple[List[Any], Any]:
        """``held``, what the prefill program returned after the logits,
        split into what is handed on and what is only said."""
        return held, None

    def _say_prefill(self, span: Any, said: Any) -> None:
        """Read ``said`` (:meth:`_prefill_extras`) and say it on
        ``span``."""

    def _step_tokens(self, tokens: _np.ndarray) -> _np.ndarray:
        """The host's (S,) last tokens as the step program takes them."""
        return tokens

    def _step_output(self, tokens: Any, *extra: Any) -> Any:
        """What the step program hands back before the buffers: the (S,)
        tokens and, behind them, the ``extra`` of the family's step."""
        return tokens

    def _read_step(self, span: Any, out: _np.ndarray) -> _np.ndarray:
        """The (S,) tokens of what a step handed back, read to the
        host; the rest is said on ``span``."""
        return out

    def greedy_sampling(self, n_slots: int) -> Tuple[_np.ndarray, ...]:
        """All-greedy per-slot sampling vectors (seed, counter base,
        temperature, top_k, top_p, method) — the default when no slot
        asked for sampling."""
        return (_np.zeros((n_slots,), _np.int32),
                _np.zeros((n_slots,), _np.int32),
                _np.ones((n_slots,), _np.float32),
                _np.ones((n_slots,), _np.int32),
                _np.ones((n_slots,), _np.float32),
                _np.zeros((n_slots,), _np.int32))

    def device_sampling(self, sampling: Sequence[_np.ndarray]
                        ) -> Tuple[Any, ...]:
        """Device mirrors of the per-slot sampling vectors, dtype
        canonicalized.  The engine caches the result across
        iterations (the lanes change only at admission/retirement),
        keeping the per-iteration host->device traffic at exactly the
        pre-sampling two arrays (tokens + positions)."""
        import jax.numpy as jnp
        seeds, bases, temps, topks, topps, methods = sampling
        return (jnp.asarray(_np.asarray(seeds, _np.int32)),
                jnp.asarray(_np.asarray(bases, _np.int32)),
                jnp.asarray(_np.asarray(temps, _np.float32)),
                jnp.asarray(_np.asarray(topks, _np.int32)),
                jnp.asarray(_np.asarray(topps, _np.float32)),
                jnp.asarray(_np.asarray(methods, _np.int32)))

    def row_blocks(self, positions: _np.ndarray,
                   bucket: int) -> Optional[Tuple[int, int]]:
        """``(read, all)`` for a family whose decode step reads the
        cache's rows by extent: the position blocks one step at these
        per-slot positions fetches, and those a bucket of ``bucket``
        rows holds for every slot.  None where the step reads the whole
        bucket whatever the positions, as this family's does."""
        return None

    def dispatch(self, cache: Any, tokens: Any,
                 positions: _np.ndarray,
                 sampling: Optional[Sequence[Any]] = None) -> Any:
        """Launch one resident decode iteration over every slot and
        return without waiting for it: consumes the cache's buffers
        (donated), installs the updated ones, and hands back the step's
        (S,) int32 next-token array UN-READ, still on the device, for
        :meth:`collect`.

        ``tokens`` is the host vector of each slot's last token, or the
        un-read array the step before this one returned: it then feeds
        this step where it lies, so the launch needs nothing the host
        has not got (the span says ``ahead=1``; for a family that reads
        by extent it also says ``row_blocks`` of ``row_blocks_all``,
        :meth:`row_blocks`, and the two ``mxnet_gen_row_blocks_*``
        counters move by them).  Either way the
        compiled program is the same one: host vectors are uploaded
        COMMITTED to the cache's device, as a step's own results are (a
        jitted call keys its executable on that).  ``sampling`` as in
        :meth:`step`."""
        import jax
        S = cache.max_slots
        if sampling is None:
            sampling = self.greedy_sampling(S)
        if not isinstance(sampling[0], jax.Array):
            # host vectors: one-shot callers; the engine hands in its
            # cached device mirrors instead
            sampling = self.device_sampling(sampling)
        self._account(f"decode:{S}x{cache.bucket}")
        ahead = isinstance(tokens, jax.Array)
        extent = {}
        blocks = self.row_blocks(positions, cache.bucket)
        if blocks is not None:
            extent = {"row_blocks": blocks[0], "row_blocks_all": blocks[1]}
            _metrics.GEN_ROW_BLOCKS_READ_TOTAL.inc(blocks[0])
            _metrics.GEN_ROW_BLOCKS_TOTAL.inc(blocks[1])
        # the host-serial part of a step: the uploads, the jitted call
        # returning, the new buffers installed
        with _tracing.child_span("model.step.dispatch", slots=S,
                                 bucket=cache.bucket, family=self.family,
                                 ahead=int(ahead), **extent,
                                 **self.span_attrs):
            if not ahead:
                tokens = jax.device_put(
                    self._step_tokens(_np.asarray(tokens, _np.int32)),
                    cache.device)
            # every kind of buffer the cache holds is donated and
            # comes back updated
            toks, *new = self._step_fn(
                self.params, *cache.buffers(), tokens,
                jax.device_put(_np.asarray(positions, _np.int32),
                               cache.device), *sampling)
            cache.replace(*new)
        return toks

    def collect(self, toks: Any) -> _np.ndarray:
        """Wait for a dispatched step: its (S,) int32 tokens on the
        host."""
        with _tracing.child_span("model.step.readback") as span:
            return self._read_step(span, _np.asarray(toks))

    def step(self, cache: Any, tokens: _np.ndarray,
             positions: _np.ndarray,
             sampling: Optional[Sequence[Any]] = None
             ) -> _np.ndarray:
        """One resident decode iteration over every slot, synchronous:
        :meth:`dispatch` then :meth:`collect`.  Consumes the cache's
        buffers (donated), installs the updated ones, returns the (S,)
        int32 next-token vector on the host (greedy or sampled per slot
        — ``sampling`` is the (seeds, counter bases, temperatures,
        top_ks, top_ps, methods) vectors, host or device
        (:meth:`device_sampling`); None means all-greedy).  Warm-up and
        one-shot callers use this; the engine calls the two halves
        itself, so that it can launch the next step before it reads
        this one's tokens (and observes
        ``mxnet_gen_step_seconds{phase="decode"}`` itself, as what the
        step cost its loop; here that is the whole call)."""
        with _tracing.child_span("model.step", slots=cache.max_slots,
                                 bucket=cache.bucket, family=self.family):
            t = time.perf_counter()
            out = self.collect(
                self.dispatch(cache, tokens, positions, sampling))
            _metrics.GEN_STEP_SECONDS.labels(phase="decode").observe(
                time.perf_counter() - t,
                exemplar=_tracing.current_trace_id())
            return out

    # a family that can rewind a slot and share its rows has programs
    # for these two; for any other the refusal says why not
    def verify(self, *args: Any, **kwargs: Any) -> _np.ndarray:
        """One speculative verification pass over every slot."""
        raise self.no_rollback("speculative verification")

    def prefill_suffix(self, *args: Any, **kwargs: Any) -> Any:
        """A prompt pass over a suffix against resident prefix rows."""
        raise self.no_rollback("suffix prefill over a shared prefix")

    def select(self, logits: _np.ndarray, seed: int, counter: int,
               temperature: float, top_k: int, top_p: float,
               method: int) -> int:
        """First-token selection over prefill logits — the single-row
        twin of the in-step sampler (same fused code path, same
        ``fold_in(PRNGKey(seed), counter)`` key stream), so a
        sequence's token at index ``i`` is identical whether the
        prefill or the decode step emitted it (the resurrection
        replay-from-transcript contract extends to sampling)."""
        return int(self.dispatch_select(logits, seed, counter,
                                        temperature, top_k, top_p, method))

    def dispatch_select(self, logits: Any, seed: int, counter: int,
                        temperature: float, top_k: int, top_p: float,
                        method: int, into: Any = None,
                        slot: int = 0) -> Any:
        """:meth:`select`, dispatched and not read: the token stays on
        the device.  ``into`` is a step's token array, still on the
        device: the return is then that array with the token at
        ``slot`` (one small program), for the next step to be fed
        from and for :meth:`collect` to read with the step's own."""
        import jax
        import jax.numpy as jnp
        with _tracing.child_span("model.select"):
            # logits keep the model dtype: the step's sampler sees the
            # same representation, so the two paths stay bit-identical
            tok = self._select_fn(
                jnp.asarray(logits),
                _np.int32(seed), _np.int32(counter),
                _np.float32(temperature), _np.int32(top_k),
                _np.float32(top_p), _np.int32(method))
            if into is None:
                return tok
            # committed where the array is, as a step's results are
            return self._put_fn(into, _np.int32(slot),
                                jax.device_put(tok, into.sharding))

    def warmup(self, cache: Any, prompt_buckets: Sequence[int],
               suffix_pairs: bool = True) -> int:
        """Pre-compile the full program grid: one prefill per prompt
        bucket, the first-token selector and its put, and one decode
        step per KV capacity bucket (run on the cache's own buffer
        shapes); a family with a suffix prefill adds its pairs where
        ``suffix_pairs`` (the prefix cache is enabled).  After this,
        traffic confined to the grids never compiles."""
        import jax
        n = 0
        for pb in prompt_buckets:
            self.prefill(_np.zeros((1,), _np.int32), int(pb))
            n += 1
        # one call warms the selector for every method (the method is
        # a traced operand — a single executable)
        self.select(_np.zeros((self.vocab_size,), self.logits_dtype),
                    seed=0, counter=0, temperature=1.0, top_k=1,
                    top_p=1.0, method=0)
        n += 1
        # the engine's admission chain: the logits stay on the device,
        # the token joins a step's token array there (committed to the
        # cache's device, as a step's results are)
        pre = self.dispatch_prefill(_np.zeros((1,), _np.int32),
                                    int(min(prompt_buckets)))
        _np.asarray(self.dispatch_select(
            pre.logits, 0, 0, 1.0, 1, 1.0, 0,
            into=jax.device_put(self._step_tokens(
                _np.zeros((cache.max_slots,), _np.int32)), cache.device)))
        self.read_prefill(pre)
        n += 1
        S = cache.max_slots
        toks = _np.zeros((S,), _np.int32)
        pos = _np.zeros((S,), _np.int32)
        for b in cache.grid:
            # walk the bucket grid directly (not via grow(): warmup
            # must not count as live migrations)
            cache.bucket = int(b)
            cache._alloc_buffers(cache.bucket)
            self.step(cache, toks, pos)
            n += 1
        # hand the cache back at rest on the smallest bucket
        cache.bucket = cache.grid[0]
        cache._alloc_buffers(cache.bucket)
        return n

    def describe(self) -> Dict[str, Any]:
        import jax
        with self._seen_lock:
            seen = sorted(self._seen)
        return {
            "name": self.name,
            "kind": "decode",
            "family": self.family,
            "vocab_size": int(self.vocab_size),
            "units": int(self.units),
            "layers": self.n_layers,
            "heads": self.num_heads,
            "max_length": self.max_length,
            "dtype": str(self.dtype),
            "param_platforms": sorted({
                d.platform
                for a in jax.tree_util.tree_leaves(self.params)
                if isinstance(a, jax.Array) for d in a.devices()}),
            "programs_compiled": seen,
        }


def _guess_param_file(symbol_file: str) -> Optional[str]:
    """Newest ``prefix-NNNN.params`` next to ``prefix-symbol.json``."""
    if not symbol_file.endswith("-symbol.json"):
        return None
    prefix = symbol_file[:-len("-symbol.json")]
    cands = sorted(
        f for f in (os.listdir(os.path.dirname(prefix) or ".") or [])
        if f.startswith(os.path.basename(prefix) + "-")
        and f.endswith(".params"))
    if not cands:
        return None
    return os.path.join(os.path.dirname(prefix) or ".", cands[-1])


def load_served(model: Any, param_file: Optional[str] = None,
                **kw: Any) -> ServedModel:
    """Sniff ``model`` into a :class:`ServedModel`: an export prefix or
    ``-symbol.json`` path, a Module, or a (Hybrid)Block."""
    if isinstance(model, str):
        sym = model if model.endswith("-symbol.json") \
            else f"{model}-symbol.json"
        return ServedModel.from_export(sym, param_file)
    if hasattr(model, "params_initialized"):        # Module duck-type
        return ServedModel.from_module(model)
    if hasattr(model, "collect_params"):            # gluon Block
        return ServedModel.from_block(model, **kw)
    raise MXNetError(f"cannot serve a {type(model).__name__}")
