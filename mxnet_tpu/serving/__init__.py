"""Inference serving — the missing half of the deployment story.

Reference parity (leezu/mxnet): the reference pairs its training runtime
with a standalone predict path (``src/c_predict_api.cc`` — load a
symbol+params export, bind an inference-only executor, answer forwards
with no Python).  It never shipped a *server*; model serving was left to
MMS/TorchServe-era sidecars that called the predict API per request.

Design (tpu-first): on TPU the two costs that dominate request serving
are (1) per-request dispatch of tiny batches — the MXU is idle below
batch ~8 — and (2) recompiles: every distinct input shape traced through
XLA is a fresh multi-second compilation.  This subsystem addresses both
in-process, with no dependencies beyond the stdlib:

* :class:`~mxnet_tpu.serving.batching.BucketPolicy` — pad-to-bucket
  shape policy: request batches round UP to a configured batch bucket
  (and, opt-in, variable-length samples pad to a length bucket), so the
  number of distinct compiled executables is bounded by the bucket grid,
  not by traffic.
* :class:`~mxnet_tpu.serving.batching.DynamicBatcher` — bounded request
  queue + micro-batch assembly: flush on a full bucket or on the oldest
  request's batching timeout; overload (queue full / deadline passed)
  sheds requests with a structured :class:`OverloadError` instead of
  piling latency onto everyone behind them.
* :class:`~mxnet_tpu.serving.model.ServedModel` — the executable: an
  ``export()`` artifact (StableHLO, incl. the ``dynamic_batch``
  polymorphic form) or a live (Hybrid)Block/Module, behind one
  ``predict(arrays) -> arrays`` surface with per-bucket compile
  accounting and warmup.
* :class:`~mxnet_tpu.serving.server.ModelServer` — composition +
  lifecycle: worker thread, futures-based in-process API, metrics.
* :mod:`~mxnet_tpu.serving.http` — a stdlib ``http.server`` front end
  (``tools/serve.py``): POST /v1/inference, POST /v1/generate (chunked
  per-token streaming), GET /metrics (Prometheus text from the PR-1
  registry), GET /healthz.
* :class:`~mxnet_tpu.serving.generation.GenerationEngine` +
  :class:`~mxnet_tpu.serving.kv_cache.PagedKVCache` +
  :class:`~mxnet_tpu.serving.model.DecodeModel` — iteration-level
  CONTINUOUS BATCHING for autoregressive LLM generation: a resident,
  bucket-compiled decode step over a slot-based KV cache, admission
  between decode iterations, per-step EOS/max-token retirement, and
  per-token streaming (:class:`TokenStream`), hosted by
  :class:`~mxnet_tpu.serving.server.GenerationServer`.

* :mod:`~mxnet_tpu.serving.speculation` (ISSUE 17) — SPECULATIVE
  DECODING: a :class:`DraftModel` (either a truncated-layer
  :class:`SelfSpeculativeDraft` reusing the target's own weights and KV
  rows, or an :class:`IndependentDraft` wrapping a small same-tokenizer
  model with its own mirrored :class:`PagedKVCache`) proposes ``k``
  tokens per engine iteration; a single bucket-compiled ``verify_k``
  target pass scores all of them at once, the accept rule replays the
  per-slot counter-PRNG lanes so output stays **byte-identical** to
  non-speculative decoding at the same seed, and rejected rows roll
  back via ``PagedKVCache.truncate``.

* :mod:`~mxnet_tpu.serving.replica` + the server-side resilience layer
  (ISSUE 7): both servers host ``MXNET_SERVING_REPLICAS`` worker
  replicas behind a router — a dead worker's requests requeue (and
  in-flight generation streams resume **token-identical**, exactly-once
  at the :class:`TokenStream` index boundary) on the survivors while a
  :class:`~mxnet_tpu.serving.replica.ReplicaSupervisor` restarts it
  with jittered backoff behind a circuit breaker (explicit
  :class:`DegradedError` degraded mode past the budget); SIGTERM
  drains gracefully (:func:`serve_until_preempted`: 429 sheds,
  readiness 503 / liveness 200, bounded by
  ``MXNET_SERVING_DRAIN_DEADLINE_S``, exit 0).

Every stage publishes to :mod:`mxnet_tpu.metrics` (queue-depth gauge,
batch-size / queue-wait / inference-latency histograms, shed counter by
reason, per-bucket compile counter, recovery/restart/breaker/drain
families) — ``metrics_dump.py``-style observability works out of the
box.
"""
from .batching import (BucketPolicy, DynamicBatcher, OverloadError,
                       Request, SlotScheduler)
from .model import DecodeModel, ServedModel, load_served
# each decode family registers the zoo class it serves with
# DecodeModel.from_block
from . import delta, gpt, hybrid, loop, moe                  # noqa: F401
from .kv_cache import PagedKVCache, PrefixCache
from .generation import GenerationEngine, StreamTimeout, TokenStream
from .replica import ReplicaSupervisor
from .server import (DegradedError, GenerationServer, ModelServer,
                     serve_until_preempted)
from .speculation import (DraftModel, IndependentDraft,
                          SelfSpeculativeDraft)
from .http import make_http_server

__all__ = [
    "BucketPolicy", "DynamicBatcher", "OverloadError", "Request",
    "SlotScheduler", "ServedModel", "DecodeModel", "PagedKVCache",
    "PrefixCache", "GenerationEngine", "StreamTimeout", "TokenStream",
    "GenerationServer", "load_served", "ModelServer", "DegradedError",
    "DraftModel", "SelfSpeculativeDraft", "IndependentDraft",
    "ReplicaSupervisor", "make_http_server", "serve_until_preempted",
]
