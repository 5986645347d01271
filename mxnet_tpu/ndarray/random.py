"""Random sampling ops over a splittable threefry PRNG.

Reference parity (leezu/mxnet): ``src/operator/random/sample_op.*`` and
``src/common/random_generator.*`` (philox/curand per-thread generators),
python ``mxnet/ndarray/random.py``.

Design (tpu-first): adopts jax's counter-based threefry keys (documented
break from philox — same statistical family, different streams). A global
key is held per process; every eager sample splits it (the analog of the
reference's per-op ``FResourceRequest::kParallelRandom`` states). Under
hybridize tracing, the key is threaded through the traced function as an
input so compiled graphs stay pure (see gluon/block.py CachedOp).
"""
from __future__ import annotations

import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..base import getenv, register_env
from .ndarray import NDArray, from_jax
from .register import invoke

__all__ = ["seed", "uniform", "normal", "randn", "randint", "gamma",
           "exponential", "poisson", "bernoulli", "multinomial", "choice",
           "shuffle", "beta", "laplace", "gumbel", "rand", "current_key",
           "split_key", "trace_key_scope", "chisquare", "rayleigh",
           "weibull", "pareto", "power", "logistic", "lognormal",
           "negative_binomial", "generalized_negative_binomial", "f", "t",
           "dirichlet", "binomial", "permutation", "randperm",
           "standard_normal", "random_sample", "sample"]

register_env("MXNET_RANDOM_SEED", 0, "Initial global PRNG seed.")


class _RngState(threading.local):
    def __init__(self) -> None:
        self.key = jax.random.PRNGKey(getenv("MXNET_RANDOM_SEED", 0))
        # During hybridize tracing, ops must draw subkeys from the traced
        # key input rather than the concrete global key.
        self.trace_key: Optional[Any] = None
        self.trace_count = 0


_STATE = _RngState()


def seed(seed_state: int, ctx: Any = "all") -> None:
    """Reset the global PRNG (``mx.random.seed``)."""
    _STATE.key = jax.random.PRNGKey(int(seed_state))


def current_key() -> Any:
    return _STATE.key


_split_jit = None


def split_key() -> Any:
    """Draw a fresh subkey (eager) or fold from the traced key (tracing).

    The eager split runs JITTED: one dispatch for split + unpack
    instead of three eager ones — 0.37 ms against 2.29 ms per call on
    the v5e host (PR 21 chip probe), on every step's critical path."""
    if _STATE.trace_key is not None:
        _STATE.trace_count += 1
        return jax.random.fold_in(_STATE.trace_key, _STATE.trace_count)
    global _split_jit
    if _split_jit is None:
        _split_jit = jax.jit(lambda k: tuple(jax.random.split(k)))
    _STATE.key, sub = _split_jit(_STATE.key)
    return sub


class trace_key_scope:
    """Bind a traced PRNG key for the duration of a hybridize trace."""

    def __init__(self, key: Any) -> None:
        self._key = key

    def __enter__(self) -> None:
        self._prev = (_STATE.trace_key, _STATE.trace_count)
        _STATE.trace_key, _STATE.trace_count = self._key, 0

    def __exit__(self, *exc: Any) -> None:
        _STATE.trace_key, _STATE.trace_count = self._prev


def _shape(shape) -> tuple:
    if shape is None:
        return ()
    if isinstance(shape, int):
        return (shape,)
    return tuple(shape)


def _sample(name: str, fn, ctx=None) -> NDArray:
    out = fn(split_key())
    nd = from_jax(out)
    from .. import engine
    engine.track(out)
    return nd


def uniform(low=0.0, high=1.0, shape=None, dtype="float32", ctx=None, **kw):
    """Uniform samples in [low, high) (``mx.nd.random.uniform``)."""
    shp = _shape(shape)
    return _sample("uniform",
                   lambda k: jax.random.uniform(k, shp, dtype=dtype,
                                                minval=low, maxval=high), ctx)


def rand(*shape, ctx=None, dtype="float32"):
    return uniform(0.0, 1.0, shape=shape, dtype=dtype, ctx=ctx)


def normal(loc=0.0, scale=1.0, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    return _sample("normal",
                   lambda k: loc + scale * jax.random.normal(k, shp, dtype=dtype),
                   ctx)


def randn(*shape, loc=0.0, scale=1.0, dtype="float32", ctx=None):
    return normal(loc, scale, shape=shape, dtype=dtype, ctx=ctx)


def randint(low, high=None, shape=None, dtype="int32", ctx=None, **kw):
    if high is None:
        low, high = 0, low
    shp = _shape(shape)
    return _sample("randint",
                   lambda k: jax.random.randint(k, shp, low, high, dtype=dtype),
                   ctx)


def gamma(alpha=1.0, beta=1.0, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    return _sample("gamma",
                   lambda k: jax.random.gamma(k, alpha, shp, dtype=dtype) * beta,
                   ctx)


def exponential(scale=1.0, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    return _sample("exponential",
                   lambda k: jax.random.exponential(k, shp, dtype=dtype) * scale,
                   ctx)


def poisson(lam=1.0, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    return _sample("poisson",
                   lambda k: jax.random.poisson(k, lam, shp).astype(dtype), ctx)


def bernoulli(prob=0.5, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    return _sample("bernoulli",
                   lambda k: jax.random.bernoulli(k, prob, shp).astype(dtype),
                   ctx)


def beta(a=1.0, b=1.0, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    return _sample("beta",
                   lambda k: jax.random.beta(k, a, b, shp).astype(dtype), ctx)


def laplace(loc=0.0, scale=1.0, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    return _sample("laplace",
                   lambda k: loc + scale * jax.random.laplace(k, shp, dtype=dtype),
                   ctx)


def gumbel(loc=0.0, scale=1.0, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    return _sample("gumbel",
                   lambda k: loc + scale * jax.random.gumbel(k, shp, dtype=dtype),
                   ctx)


def multinomial(data, shape=1, get_prob=False, dtype="int32", **kw):
    """Sample category indices from (batched) probability rows; with
    ``get_prob=True`` also return the log-probability of each draw
    (``mx.nd.random.multinomial`` — REINFORCE-style usage)."""
    n = shape if isinstance(shape, int) else int(jnp.prod(jnp.array(shape)))
    probs = data._data if isinstance(data, NDArray) else jnp.asarray(data)
    logits = jnp.log(jnp.maximum(probs, 1e-38))
    k = split_key()
    squeeze = isinstance(shape, int) and shape == 1
    if logits.ndim == 1:
        out = jax.random.categorical(k, logits, shape=(n,))
        logp = jax.nn.log_softmax(logits)[out]
        if squeeze:
            out, logp = out[0], logp[0]
    else:
        out = jax.random.categorical(k, logits[:, None, :], axis=-1,
                                     shape=(logits.shape[0], n))
        logp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                   out, axis=1)
        if squeeze:
            out, logp = out[:, 0], logp[:, 0]
    if get_prob:
        return from_jax(out.astype(dtype)), from_jax(logp)
    return from_jax(out.astype(dtype))


def choice(a, size=None, replace=True, p=None, ctx=None):
    aa = a._data if isinstance(a, NDArray) else a
    pp = p._data if isinstance(p, NDArray) else p
    shp = _shape(size)
    return _sample("choice",
                   lambda k: jax.random.choice(k, aa, shp, replace=replace, p=pp),
                   ctx)


_seed_jit = None


def split_seed():
    """Fresh (2,) uint32 seed words. Jitted end to end when eager: one
    dispatch instead of an eager key_data/reshape/slice/cast chain
    (per-dispatch cost on the chip: see ``split_key``)."""
    key = split_key()
    if isinstance(key, jax.core.Tracer):
        return jax.random.key_data(key).reshape(-1)[:2].astype(jnp.uint32)
    global _seed_jit
    if _seed_jit is None:
        _seed_jit = jax.jit(lambda k: jax.random.key_data(k)
                            .reshape(-1)[:2].astype(jnp.uint32))
    return _seed_jit(key)


def shuffle(data):
    """Random permutation along the first axis (``mx.nd.random.shuffle``).

    Delegates to the registered ``shuffle`` op so the tape, AMP/profiler
    hooks and the per-op executable cache all apply (and the seed rides
    as an op input — compiled programs reshuffle every call)."""
    from . import ops as _ops
    return _ops.shuffle(data)


def chisquare(df=1.0, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    return _sample("chisquare",
                   lambda k: jax.random.chisquare(k, df, shape=shp,
                                                  dtype=dtype), ctx)


def rayleigh(scale=1.0, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    return _sample("rayleigh",
                   lambda k: jax.random.rayleigh(k, scale, shape=shp,
                                                 dtype=dtype), ctx)


def weibull(a=1.0, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    return _sample("weibull",
                   lambda k: jax.random.weibull_min(k, 1.0, a, shape=shp,
                                                    dtype=dtype), ctx)


def pareto(a=1.0, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    # numpy's pareto is the Lomax form: samples of (X - 1) with X ~ Pareto(a)
    return _sample("pareto",
                   lambda k: jax.random.pareto(k, a, shape=shp,
                                               dtype=dtype) - 1.0, ctx)


def power(a=1.0, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    return _sample("power",
                   lambda k: jax.random.uniform(k, shp, dtype=dtype)
                   ** (1.0 / a), ctx)


def logistic(loc=0.0, scale=1.0, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    return _sample("logistic",
                   lambda k: loc + scale * jax.random.logistic(k, shp,
                                                               dtype=dtype),
                   ctx)


def lognormal(mean=0.0, sigma=1.0, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    return _sample("lognormal",
                   lambda k: jnp.exp(mean + sigma * jax.random.normal(
                       k, shp, dtype=dtype)), ctx)


def negative_binomial(k=1, p=0.5, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    kk, pp = k, p

    def impl(key):
        k1, k2 = jax.random.split(key)
        # NB(k, p) == Poisson(Gamma(k, (1-p)/p))
        lam = jax.random.gamma(k1, kk, shp) * ((1.0 - pp) / pp)
        return jax.random.poisson(k2, lam, shp).astype(dtype)

    return _sample("negative_binomial", impl, ctx)


def generalized_negative_binomial(mu=1.0, alpha=1.0, shape=None,
                                  dtype="float32", ctx=None, **kw):
    shp = _shape(shape)

    def impl(key):
        k1, k2 = jax.random.split(key)
        r = 1.0 / alpha
        lam = jax.random.gamma(k1, r, shp) * (mu * alpha)
        return jax.random.poisson(k2, lam, shp).astype(dtype)

    return _sample("generalized_negative_binomial", impl, ctx)


def f(dfnum=1.0, dfden=1.0, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)

    def impl(key):
        k1, k2 = jax.random.split(key)
        num = jax.random.chisquare(k1, dfnum, shape=shp, dtype=dtype) / dfnum
        den = jax.random.chisquare(k2, dfden, shape=shp, dtype=dtype) / dfden
        return num / den

    return _sample("f", impl, ctx)


def t(df=1.0, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    return _sample("t",
                   lambda k: jax.random.t(k, df, shape=shp, dtype=dtype), ctx)


def dirichlet(alpha, shape=None, dtype="float32", ctx=None, **kw):
    al = alpha._data if isinstance(alpha, NDArray) else jnp.asarray(
        alpha, dtype=dtype)
    shp = _shape(shape)
    return _sample("dirichlet",
                   lambda k: jax.random.dirichlet(k, al, shape=shp,
                                                  dtype=dtype), ctx)


def binomial(n=1, p=0.5, shape=None, dtype="float32", ctx=None, **kw):
    shp = _shape(shape)
    return _sample("binomial",
                   lambda k: jax.random.binomial(k, n, p, shape=shp).astype(
                       dtype), ctx)


def permutation(x, ctx=None):
    if isinstance(x, int):
        return _sample("permutation",
                       lambda k: jax.random.permutation(k, x), ctx)
    arr = x._data if isinstance(x, NDArray) else jnp.asarray(x)
    return _sample("permutation",
                   lambda k: jax.random.permutation(k, arr, axis=0), ctx)


def randperm(n, ctx=None):
    return permutation(n, ctx=ctx)


def standard_normal(shape=None, dtype="float32", ctx=None):
    return normal(0.0, 1.0, shape=shape, dtype=dtype, ctx=ctx)


def random_sample(shape=None, dtype="float32", ctx=None):
    return uniform(0.0, 1.0, shape=shape, dtype=dtype, ctx=ctx)


def sample(shape=None, dtype="float32", ctx=None):
    return uniform(0.0, 1.0, shape=shape, dtype=dtype, ctx=ctx)
