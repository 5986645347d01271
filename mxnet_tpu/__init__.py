"""mxnet_tpu — a TPU-native deep-learning framework with MXNet's capabilities.

A brand-new framework (NOT a port) with the API surface of Apache MXNet
(reference: leezu/mxnet), designed tpu-first on jax/XLA: the async
dependency engine maps to XLA's async dispatch, ``hybridize`` maps to a
jit-compiled executable cache, KVStore maps to SPMD collectives over a
device mesh. See SURVEY.md for the full blueprint.

Usage mirrors the reference::

    import mxnet_tpu as mx
    x = mx.np.ones((2, 3), ctx=mx.tpu())
    net = mx.gluon.nn.Dense(10)
    net.initialize()
    with mx.autograd.record():
        y = net(x)
"""
__version__ = "0.1.0"

# Install the runtime lock-order sanitizer BEFORE any submodule import
# allocates a lock (MXNET_SANITIZE=locks; see
# docs/static_analysis.md#lockdep) — lockdep tracks only locks created
# after the factories are patched, and analysis.lockdep is stdlib-only
# so this costs nothing when the env is unset.
import os as _os
_sanitizers = {t.strip()
               for t in _os.environ.get("MXNET_SANITIZE", "").split(",")
               if t.strip()}
if _sanitizers - {"locks"}:
    # a typo must not silently disarm a sanitizer the user asked for
    raise ValueError(
        f"unknown MXNET_SANITIZE value(s) {sorted(_sanitizers - {'locks'})}"
        " — supported: 'locks' (see docs/static_analysis.md)")
if "locks" in _sanitizers:
    from .analysis.lockdep import install as _lockdep_install
    _lockdep_install()
    del _lockdep_install
del _sanitizers

# Join a launcher-described multi-process job BEFORE anything touches the
# XLA backend (jax.distributed.initialize must run first) — the analog of
# the reference reading DMLC_* rendezvous env at import. No-op when the
# env is absent; see base.join_distributed_job for the knobs.
from .base import join_distributed_job as _join
_join()
# ... and give jax's persistent compilation cache its directory before
# anything compiles (see base.place_compile_cache).
from .base import place_compile_cache as _place_cache
_place_cache()

from . import base
from .base import MXNetError
from .context import (Context, cpu, cpu_pinned, gpu, tpu, current_context,
                      num_gpus, num_tpus)
from . import engine
from . import bulk
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import numpy as np  # noqa: A004 - mirrors mx.np
from . import npx
from . import autograd
from .ndarray import random
from . import util
from .util import set_np, is_np_array, is_np_shape

# Subpackages that may import heavier deps load lazily via __getattr__.
_LAZY = {
    "gluon": ".gluon",
    "optimizer": ".optimizer",
    "metric": ".metric",
    "metrics": ".metrics",
    "initializer": ".initializer",
    "init": ".initializer",
    "kvstore": ".kvstore",
    "kv": ".kvstore",
    "io": ".io",
    "image": ".image",
    "recordio": ".recordio",
    "profiler": ".profiler",
    "amp": ".amp",
    "parallel": ".parallel",
    "test_utils": ".test_utils",
    "runtime": ".runtime",
    "lr_scheduler": ".lr_scheduler",
    "callback": ".callback",
    "model": ".model",
    "mod": ".module",
    "module": ".module",
    "operator": ".operator",
    "monitor": ".monitor",
    "mon": ".monitor",
    "symbol": ".symbol",
    "sym": ".symbol",
    "contrib": ".contrib",
    "subgraph": ".subgraph",
    "rtc": ".rtc",
    "serving": ".serving",
    "checkpoint": ".checkpoint",
    "faults": ".faults",
    "retry": ".retry",
    "preemption": ".preemption",
    "health": ".health",
    "name": ".name",
    "attribute": ".attribute",
    "visualization": ".visualization",
    "viz": ".visualization",
}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(_LAZY[name], __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'mxnet_tpu' has no attribute {name!r}")


def waitall() -> None:
    """Block until all asynchronous device work completes."""
    engine.waitall()
