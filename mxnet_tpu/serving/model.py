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


def _slot_block_step(p, x, ck, cv, pos, nh: int, ga):
    """One decode token for EVERY slot: ``x`` (S, 1, C), caches
    (S, C, L) — heads and head dim on one axis, positions last: the
    order the device stores them in (``kv_cache`` module docstring) —
    ``pos`` (S,) int32: the per-slot-position variant of
    ``model_zoo.generation._block_step`` (which shares one scalar
    position across the batch; continuous batching cannot)."""
    import math as _math
    import jax
    import jax.numpy as jnp
    from jax import lax

    gelu_approx, eps = ga
    S, _, C = x.shape
    d = C // nh
    L = ck.shape[2]
    with jax.named_scope("attn/qkv"):
        h = _pure_ln(x, p["ln1_g"], p["ln1_b"], eps)
        qkv = h @ p["qkv_w"].T + p["qkv_b"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        qh = q.reshape(S, 1, nh, d)
        kcol = k.reshape(S, C, 1)
        vcol = v.reshape(S, C, 1)
    # slot i writes its k/v column at ITS position pos[i]: one in-place
    # dynamic_update_slice a slot, NOT a scatter — a TPU scatter wants
    # its update window on the minor axes, so XLA would relayout the
    # whole buffer to [S][L][C] and back around it, every layer, every
    # token (PERF.md PR 27).  A position past L-1 clamps onto row L-1
    # where the scatter dropped it; only a verify pass at the grid's
    # top gets there, for rows no emitted token reads (the submit-time
    # budget check)
    with jax.named_scope("cache/write"):
        for i in range(S):
            # positions are never negative: without the flag every traced
            # index gets a wrap-around select, slots x 2 x layers times a
            # program, a second of tracing on every start
            at = (i, 0, lax.index_in_dim(pos, i, keepdims=False))
            ck = lax.dynamic_update_slice(
                ck, lax.slice_in_dim(kcol, i, i + 1), at,
                allow_negative_indices=False)
            cv = lax.dynamic_update_slice(
                cv, lax.slice_in_dim(vcol, i, i + 1), at,
                allow_negative_indices=False)
    with jax.named_scope("attn/core"):
        # the heads' view of the buffers is free: d (64) is whole sublane
        # tiles, so splitting C moves nothing
        kh = ck.reshape(S, nh, d, L)
        vh = cv.reshape(S, nh, d, L)
        scores = jnp.einsum("sqhd,shdk->shqk", qh, kh) / _math.sqrt(d)
        # slot i sees cache positions 0..pos[i] (its prompt + its decoded
        # tokens); pad garbage beyond pos[i] stays invisible until the loop
        # overwrites it position by position
        visible = jnp.arange(L)[None, :] <= pos[:, None]          # (S, L)
        scores = jnp.where(visible[:, None, None, :], scores,
                           jnp.float32(-jnp.inf).astype(scores.dtype))
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("shqk,shdk->sqhd", probs, vh).reshape(S, 1, C)
    with jax.named_scope("attn/out"):
        x = x + (out @ p["out_w"].T + p["out_b"])
    with jax.named_scope("ffn/up"):
        h = _pure_ln(x, p["ln2_g"], p["ln2_b"], eps)
        ffn = jax.nn.gelu(h @ p["f1_w"].T + p["f1_b"],
                          approximate=gelu_approx)
    with jax.named_scope("ffn/down"):
        return x + (ffn @ p["f2_w"].T + p["f2_b"]), ck, cv


def _pure_ln(x, g, b, eps):
    import jax.numpy as jnp
    from jax import lax
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * g + b


def _block_suffix(p, x, pk, pv, q, nh: int, ga):
    """Causal pass over a prompt SUFFIX against resident prefix KV:
    ``x`` (1, Sb, C) embeds suffix tokens at absolute positions
    ``q..q+Sb``, ``pk``/``pv`` (Pb, nh, d) hold the shared prefix's
    rows (valid through traced ``q``; pad garbage past it is masked).
    Returns (x_out, suffix ck/cv (Sb, nh, d)) — the prefix rows are
    already in the cache, only the suffix rows are new."""
    import math as _math
    import jax
    import jax.numpy as jnp

    gelu_approx, eps = ga
    _, T, C = x.shape
    d = C // nh
    Pb = pk.shape[0]
    with jax.named_scope("attn/qkv"):
        h = _pure_ln(x, p["ln1_g"], p["ln1_b"], eps)
        qkv = h @ p["qkv_w"].T + p["qkv_b"]
        qq, kk, vv = jnp.split(qkv, 3, axis=-1)
        qh = qq.reshape(T, nh, d)
        kh = kk.reshape(T, nh, d)
        vh = vv.reshape(T, nh, d)
    with jax.named_scope("attn/core"):
        k_all = jnp.concatenate([pk, kh], axis=0)       # (Pb + T, nh, d)
        v_all = jnp.concatenate([pv, vh], axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qh, k_all) / _math.sqrt(d)
        cols = jnp.arange(Pb + T)
        # suffix position i (absolute q+i) sees: real prefix rows (< q)
        # and suffix rows up to itself (causal); prefix pad garbage in
        # q..Pb stays invisible
        vis = (cols[None, :] < q) | (
            (cols[None, :] >= Pb)
            & (cols[None, :] - Pb <= jnp.arange(T)[:, None]))
        scores = jnp.where(vis[None, :, :], scores,
                           jnp.float32(-jnp.inf).astype(scores.dtype))
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("hqk,khd->qhd", probs, v_all).reshape(1, T, C)
    with jax.named_scope("attn/out"):
        x = x + (out @ p["out_w"].T + p["out_b"])
    with jax.named_scope("ffn/up"):
        h = _pure_ln(x, p["ln2_g"], p["ln2_b"], eps)
        ffn = jax.nn.gelu(h @ p["f1_w"].T + p["f1_b"],
                          approximate=gelu_approx)
    with jax.named_scope("ffn/down"):
        return x + (ffn @ p["f2_w"].T + p["f2_b"]), kh, vh


class DecodeModel:
    """The decode-capable serving path: a stateful
    ``(params, kv_cache, positions) -> next tokens`` step over slot
    rows, compiled ONCE per KV capacity bucket, plus a per-prompt-bucket
    prefill — the two programs the continuous-batching
    :class:`~mxnet_tpu.serving.generation.GenerationEngine` runs
    resident.

    Built from a live :class:`~mxnet_tpu.gluon.model_zoo.gpt.GPTModel`
    (the zoo's decoder-only family): parameters are extracted once into
    a pure pytree (``model_zoo.generation._collect``) and the decode
    math mirrors the zoo's KV-cache step, extended to per-slot
    positions.  Compile accounting rides the SAME per-bucket counter as
    the one-shot path (``mxnet_serving_bucket_compiles_total``, labels
    ``decode:SxL`` / ``prefill:Lp``), so warmup moves every compile to
    startup and the smoke gate can pin "0 after warmup".

    A zoo family with another kind of layer is a subclass
    (``serving.hybrid.HybridDecodeModel``) that ``from_block`` picks by
    the block's type; the class attributes below are what the engine
    reads of a family.
    """

    family = "gpt"
    # longest prompt one prefill program takes (None: the top KV bucket)
    # and the smallest bucket prompts are padded to
    max_prompt: Optional[int] = None
    min_prompt_bucket = 8
    # whether a slot can be rewound (speculation) and its rows shared
    # (prefix cache), and where not, why: the end of every refusal
    supports_rollback = True
    no_rollback_why = ""
    # what a family says of its programs on the ``model.prefill`` and
    # ``model.step.dispatch`` spans beside what every family says
    span_attrs: Dict[str, Any] = {}

    def __init__(self, params: Any, num_heads: int, ga: Tuple[Any, Any],
                 max_length: int, name: str) -> None:
        import jax

        self.params = params
        self.num_heads = int(num_heads)
        self.ga = (bool(ga[0]), float(ga[1]))
        self.max_length = int(max_length)
        self.name = name
        self.vocab_size, self.units = params["embed"].shape
        self.head_dim = self.units // self.num_heads
        self.n_layers = len(params["blocks"])
        self.dtype = params["blocks"][0]["qkv_w"].dtype
        self.logits_dtype = self.dtype
        self._seen_lock = threading.Lock()
        self._seen: set = set()
        nh, ga_s = self.num_heads, self.ga

        def _prefill(params, toks, t0):
            # toks (Lp,) int32 (pad tokens after t0), t0 traced scalar;
            # returns (last-real-token logits (V,), ks/vs lists of
            # (Lp, nh, d)) — garbage pad KV past t0 is masked by the
            # decode position mask until overwritten
            from jax import lax
            from ..gluon.model_zoo.generation import _block_prefill
            Lp = toks.shape[0]
            with jax.named_scope("embed"):
                x = params["embed"][toks][None] + params["pos"][None, :Lp]
            ks, vs = [], []
            for p in params["blocks"]:
                x, ck, cv = _block_prefill(p, x, nh, Lp, ga_s)
                ks.append(ck[0])
                vs.append(cv[0])
            with jax.named_scope("head"):
                x = _pure_ln(x, params["lnf_g"], params["lnf_b"], ga_s[1])
                h = lax.dynamic_slice_in_dim(x[0], t0 - 1, 1, axis=0)[0]
                return h @ params["embed"].T, ks, vs

        def _step(params, ks, vs, toks, pos, seeds, bases, temps,
                  topks, topps, methods):
            # toks (S,) int32 last emitted per slot, pos (S,) int32
            # write positions; free slots ride along with pos=0 and
            # their outputs are ignored on the host.  The sampling
            # vectors (seed/base/temperature/top-k/top-p/method, all
            # (S,)) are traced operands: per-request parameter changes
            # never recompile the step — and they change only at
            # admission, so the engine reuses their device mirrors
            # across iterations.  The key COUNTER is derived
            # in-program (ctr = pos - base: base is the slot's
            # original prompt length minus its stream offset, minus
            # one) so no per-token host vector rides the hot loop
            with jax.named_scope("embed"):
                x = (params["embed"][toks][:, None, :]
                     + params["pos"][pos][:, None, :])
            new_ks, new_vs = [], []
            for p, ck, cv in zip(params["blocks"], ks, vs):
                x, ck, cv = _slot_block_step(p, x, ck, cv, pos, nh, ga_s)
                new_ks.append(ck)
                new_vs.append(cv)
            with jax.named_scope("head"):
                x = _pure_ln(x, params["lnf_g"], params["lnf_b"], ga_s[1])
                logits = x[:, 0, :] @ params["embed"].T

            # token selection ON DEVICE (greedy argmax or the fused
            # temperature/top-k/top-p sampler under per-slot counter
            # keys): the host reads back (S,) int32 per iteration,
            # never (S, V) logits.  The sampler rides behind a
            # runtime lax.cond: an all-greedy iteration (the default
            # traffic) executes only the argmax branch, so sampling
            # support costs nothing until a slot actually samples —
            # and it stays ONE executable, so greedy tokens are
            # bit-identical whichever branch the batch composition
            # selects (argmax is comparison-only, no FP reassociation)
            def _mixed(lg):
                return _sample_tokens(lg, seeds, pos - bases, temps,
                                      topks, topps, methods)

            def _greedy(lg):
                import jax.numpy as jnp
                return jnp.argmax(lg, axis=-1).astype(jnp.int32)

            from jax import lax
            import jax.numpy as jnp
            with jax.named_scope("sample"):
                next_tok = lax.cond(jnp.any(methods != 0), _mixed,
                                    _greedy, logits)
            return next_tok, new_ks, new_vs

        def _verify(params, ks, vs, toks, pos, seeds, bases, temps,
                    topks, topps, methods):
            # speculative verification: toks (S, K1) int32 — column 0
            # is each slot's last emitted token, columns 1.. the draft
            # proposals; pos (S,) the write position of column 0.  The
            # program is K1 UNROLLED repetitions of the single-token
            # step (same ``_slot_block_step`` math, same shapes per
            # sub-step, same lax.cond'd sampler), each scattering its
            # K/V at pos+j and sampling under counter pos+j-base — so
            # the token this pass computes at any position is
            # BIT-IDENTICAL to what the sequential one-token step
            # would have computed there (the byte-identical-streams
            # contract CI pins).  Inputs past the accepted prefix feed
            # garbage forward; the host discards those columns and
            # rolls their KV rows back (PagedKVCache.truncate)
            from jax import lax
            import jax.numpy as jnp
            K1 = toks.shape[1]
            outs = []
            for j in range(K1):
                with jax.named_scope("embed"):
                    x = (params["embed"][toks[:, j]][:, None, :]
                         + params["pos"][pos + j][:, None, :])
                new_ks, new_vs = [], []
                for p, ck, cv in zip(params["blocks"], ks, vs):
                    x, ck, cv = _slot_block_step(p, x, ck, cv, pos + j,
                                                 nh, ga_s)
                    new_ks.append(ck)
                    new_vs.append(cv)
                ks, vs = new_ks, new_vs
                with jax.named_scope("head"):
                    x = _pure_ln(x, params["lnf_g"], params["lnf_b"],
                                 ga_s[1])
                    logits = x[:, 0, :] @ params["embed"].T

                def _mixed(lg, _j=j):
                    return _sample_tokens(lg, seeds, (pos + _j) - bases,
                                          temps, topks, topps, methods)

                def _greedy(lg):
                    return jnp.argmax(lg, axis=-1).astype(jnp.int32)

                with jax.named_scope("sample"):
                    outs.append(lax.cond(jnp.any(methods != 0), _mixed,
                                         _greedy, logits))
            return jnp.stack(outs, axis=1), ks, vs

        def _prefill_sfx(params, pre_ks, pre_vs, toks, q, t0):
            # suffix pass for shared-prefix admissions: pre_ks/pre_vs
            # are the resident prefix rows (Pb, nh, d) per layer, toks
            # (Sb,) the pad-bucketed suffix, q the traced real prefix
            # length, t0 the traced real suffix length.  Returns the
            # last-real-suffix-token logits + the SUFFIX KV rows only
            from jax import lax
            Sb = toks.shape[0]
            with jax.named_scope("embed"):
                x = (params["embed"][toks][None]
                     + lax.dynamic_slice_in_dim(params["pos"], q, Sb,
                                                axis=0)[None])
            ks_o, vs_o = [], []
            for p, pk, pv in zip(params["blocks"], pre_ks, pre_vs):
                x, ck, cv = _block_suffix(p, x, pk, pv, q, nh, ga_s)
                ks_o.append(ck)
                vs_o.append(cv)
            with jax.named_scope("head"):
                x = _pure_ln(x, params["lnf_g"], params["lnf_b"], ga_s[1])
                h = lax.dynamic_slice_in_dim(x[0], t0 - 1, 1, axis=0)[0]
                return h @ params["embed"].T, ks_o, vs_o

        fam = self.family
        self._prefill_fn = _tracing.program(_prefill, "prefill", fam)
        self._prefill_sfx_fn = _tracing.program(_prefill_sfx,
                                                "prefill_suffix", fam)
        self._select_fn, self._put_fn = _first_token_programs(fam)
        # the KV buffers are DONATED: XLA updates the resident cache in
        # place instead of allocating a fresh (S, h * d, L) per layer
        # every token
        self._step_fn = _tracing.program(_step, "decode", fam,
                                         donate_argnums=(1, 2))
        # same donation contract as _step: verify scatters k+1 rows
        # into the resident buffers in place; rejected rows are
        # invisible (visibility mask <= pos) until overwritten
        self._verify_fn = _tracing.program(_verify, "verify", fam,
                                           donate_argnums=(1, 2))

    # -- constructors -------------------------------------------------------
    @staticmethod
    def from_block(block: Any) -> "DecodeModel":
        """Build from a live zoo LM (weights as currently
        initialized/loaded).  Five families are served: a ``GPTModel``
        with dense FFNs (one built with ``moe_experts`` keeps
        ``MoEDense``'s capacity mask, which drops tokens, and is
        refused), a ``Phi4FlashModel`` (``serving.hybrid``), a
        ``Cohere2MoEModel`` (``serving.moe``: dropless top-k experts, of
        which the model holds a share), an ``OuroModel``
        (``serving.loop``: one stack of layers run several times a
        token) and an ``OlmoHybridModel`` (``serving.delta``: delta-rule
        layers beside full attention), each of the last four through
        its own subclass."""
        from ..gluon.model_zoo.cohere2moe import Cohere2MoEModel
        from ..gluon.model_zoo.generation import _collect
        from ..gluon.model_zoo.olmo_hybrid import OlmoHybridModel
        from ..gluon.model_zoo.ouro import OuroModel
        from ..gluon.model_zoo.phi4flash import Phi4FlashModel
        if isinstance(block, OuroModel):
            from .loop import LoopDecodeModel
            return LoopDecodeModel.from_ouro(block)
        if isinstance(block, Phi4FlashModel):
            from .hybrid import HybridDecodeModel
            return HybridDecodeModel.from_phi4flash(block)
        if isinstance(block, Cohere2MoEModel):
            from .moe import MoEDecodeModel
            return MoEDecodeModel.from_cohere2moe(block)
        if isinstance(block, OlmoHybridModel):
            from .delta import DeltaDecodeModel
            return DeltaDecodeModel.from_olmo_hybrid(block)
        if not hasattr(block, "blocks") or not hasattr(block,
                                                       "word_embed"):
            raise MXNetError(
                f"DecodeModel serves decoder-only zoo LMs (GPTModel, "
                f"Phi4FlashModel, Cohere2MoEModel, OuroModel, "
                f"OlmoHybridModel); got "
                f"{type(block).__name__}")
        params = _collect(block)
        ga = (params.pop("gelu_approx"), params.pop("ln_eps"))
        nh = next(iter(block.blocks._children.values()))._num_heads
        return DecodeModel(params, nh, ga, block._max_length,
                           type(block).__name__)

    def make_cache(self, max_slots: int, buckets: Sequence[int],
                   prefix_slots: Optional[int] = None,
                   prefix: Any = None) -> Any:
        """The slot cache this family decodes over."""
        from .kv_cache import PagedKVCache
        return PagedKVCache(
            self.n_layers, self.num_heads, self.head_dim, max_slots,
            buckets=buckets, dtype=self.dtype, prefix=prefix,
            prefix_slots=prefix_slots)

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

    # what a family's programs hand back or take beside the GPT family's,
    # where it has any (serving.moe: the held experts' load)
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

    def verify(self, cache: Any, tokens: _np.ndarray,
               positions: _np.ndarray,
               sampling: Optional[Sequence[Any]] = None
               ) -> _np.ndarray:
        """One speculative verification pass over every slot:
        ``tokens`` is (S, k+1) int32 — column 0 each slot's last
        emitted token, columns 1.. the k draft proposals — and the
        return is the (S, k+1) int32 target tokens for those
        positions, each bit-identical to what ``step`` would have
        produced sequentially (same kernel math, same counter-PRNG
        lanes).  The cache's buffers gain k+1 rows per slot starting
        at ``positions``; the caller owns acceptance and rolls back
        rejected rows via ``cache.truncate``.  One compiled program
        per (S, bucket, k+1) triple."""
        import jax
        import jax.numpy as jnp
        S = cache.max_slots
        toks = _np.asarray(tokens, _np.int32)
        if toks.ndim != 2 or toks.shape[0] != S or toks.shape[1] < 2:
            raise MXNetError(
                f"verify wants an (S, k+1) token matrix with k >= 1; "
                f"got shape {toks.shape} for {S} slots")
        if sampling is None:
            sampling = self.greedy_sampling(S)
        if not isinstance(sampling[0], jax.Array):
            sampling = self.device_sampling(sampling)
        seeds, bases, temps, topks, topps, methods = sampling
        self._account(f"verify:{S}x{cache.bucket}x{toks.shape[1]}")
        with _tracing.child_span("model.verify", slots=S,
                                 bucket=cache.bucket):
            t = time.perf_counter()
            out_toks, new_ks, new_vs = self._verify_fn(
                self.params, cache._k, cache._v,
                jnp.asarray(toks),
                jnp.asarray(_np.asarray(positions, _np.int32)),
                seeds, bases, temps, topks, topps, methods)
            cache.replace(new_ks, new_vs)
            out = _np.asarray(out_toks)
            dt = time.perf_counter() - t
        _metrics.GEN_STEP_SECONDS.labels(phase="verify").observe(
            dt, exemplar=_tracing.current_trace_id())
        return out

    def prefill_suffix(self, tokens: _np.ndarray, prefix_ks: List[Any],
                       prefix_vs: List[Any], q: int, bucket_len: int
                       ) -> Tuple[_np.ndarray, List[Any], List[Any]]:
        """Run the prompt pass over only the SUFFIX ``tokens`` (real
        positions ``q..q+len``) against resident prefix K/V rows —
        the shared-prefix admission path.  Returns (last-real-token
        logits (V,) numpy, per-layer suffix ks/vs (bucket_len, nh,
        d)).  One compiled program per (prefix bucket, suffix bucket)
        pair; ``q`` and the real suffix length are traced operands."""
        import jax.numpy as jnp
        toks = _np.asarray(tokens, _np.int32).reshape(-1)
        t0 = toks.shape[0]
        if t0 < 1:
            raise MXNetError("empty prompt suffix")
        if bucket_len < t0:
            raise MXNetError(
                f"suffix length {t0} exceeds its bucket {bucket_len}")
        padded = _np.zeros((bucket_len,), _np.int32)
        padded[:t0] = toks
        Pb = int(prefix_ks[0].shape[0])
        self._account(f"prefill_sfx:{Pb}x{bucket_len}")
        with _tracing.child_span("model.prefill", bucket=bucket_len,
                                 family=self.family):
            t = time.perf_counter()
            logits, ks, vs = self._prefill_sfx_fn(
                self.params, list(prefix_ks), list(prefix_vs),
                jnp.asarray(padded), _np.int32(q), _np.int32(t0))
            out = _np.asarray(logits)
            dt = time.perf_counter() - t
        _metrics.GEN_STEP_SECONDS.labels(phase="prefill").observe(
            dt, exemplar=_tracing.current_trace_id())
        return out, ks, vs

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
        bucket, one suffix prefill per (prefix bucket, suffix bucket)
        pair (the shared-prefix admission path; skipped when the
        prefix cache is disabled), the first-token selector, and one
        decode step per KV capacity bucket (run on the cache's own
        buffer shapes).  After this, traffic confined to the grids
        never compiles."""
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
        if suffix_pairs:
            dev = cache.device
            top = max(int(pb) for pb in prompt_buckets)
            rows = {int(pb): [jax.device_put(
                _np.zeros((int(pb), self.num_heads, self.head_dim),
                          self.dtype), dev)
                for _ in range(self.n_layers)]
                for pb in prompt_buckets}
            for Pb in prompt_buckets:
                for Sb in prompt_buckets:
                    if int(Pb) + int(Sb) > top:
                        # unreachable at runtime: entries store
                        # bucket-aligned prefixes (Pb == q) and the
                        # admission capacity rule bounds q + Sb by the
                        # top prompt bucket — compiling these pairs
                        # would only inflate warmup and the persistent
                        # cache
                        continue
                    self.prefill_suffix(
                        _np.zeros((1,), _np.int32), rows[int(Pb)],
                        rows[int(Pb)], q=1, bucket_len=int(Sb))
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
